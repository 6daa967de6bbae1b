"""Fault injection, retry/backoff, and circuit breaking.

The robustness half of the serving story (PR 1 shipped backpressure;
this package ships degradation): preemption, coordinator drops, and transient
device errors are the steady state on shared TPU fleets, so every layer
that talks to a device, the filesystem, or another process goes through
one of three small primitives:

* :mod:`faults`  — seeded deterministic fault injection at named sites
  (``engine.forward``, ``checkpoint.save``, ``relay.connect``, ...),
  activated per-process or via ``$ZNICZ_FAULT_PLAN``; pytest ``chaos``
  tests and ``python -m znicz_tpu chaos`` share it.
* :mod:`retry`   — bounded attempts, exponential backoff + jitter,
  per-attempt timeout, transient-vs-deterministic classifier.
* :mod:`breaker` — circuit breaker (closed→open→half_open→closed) with
  :class:`~breaker.EngineUnavailable` carrying Retry-After for fronts.
* :mod:`overload` — overload defense in depth: end-to-end
  :class:`~overload.Deadline` propagation, the process-wide
  :class:`~overload.RetryBudget`, :class:`~overload.HedgePolicy` for
  hedged replica dispatch, and the :class:`~overload.CoDelShedder`
  adaptive admission ladder (docs/resilience.md "Overload defense").

See docs/resilience.md for the knob reference and degradation matrix.
"""

from .breaker import CircuitBreaker, EngineUnavailable
from .faults import FaultInjected, FaultPlan, FaultSpec, inject
from .overload import (CoDelShedder, Deadline, DeadlineExceeded,
                       DoomedDeadline, Draining, EarlyReject,
                       HedgePolicy, RetryBudget, Shed)
from .retry import AttemptTimeout, RetryPolicy, default_transient

__all__ = ["AttemptTimeout", "CircuitBreaker", "CoDelShedder",
           "Deadline", "DeadlineExceeded", "DoomedDeadline",
           "Draining", "EarlyReject", "EngineUnavailable",
           "FaultInjected", "FaultPlan", "FaultSpec", "HedgePolicy",
           "RetryBudget", "RetryPolicy", "Shed", "default_transient",
           "inject"]
