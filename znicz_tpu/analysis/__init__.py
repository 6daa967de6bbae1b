"""znicz_tpu.analysis — "zlint", the project's AST-based static
analyzer (ISSUE 4).

Four rule families over the threaded/jitted surfaces the last three
PRs grew (serving, resilience, telemetry, elastic):

* ``lock-discipline`` — lock-guarded attributes accessed outside the
  lock (:mod:`.locks`);
* ``jit-host-sync`` / ``jit-traced-branch`` — host syncs and Python
  branches on traced values inside jit-compiled functions, plus
  ``unseeded-random`` for global-RNG draws (:mod:`.jaxrules`);
* ``handler-blocking`` — blocking calls on HTTP-handler and
  dispatch-thread paths (:mod:`.handlers`);
* ``metric-drift`` — metric names out of sync between code,
  docs/observability.md and tools/metrics_smoke.sh
  (:mod:`.metric_drift`);
* ``duration-clock`` — durations computed from the wall clock
  (``time.time()`` arithmetic) instead of ``time.monotonic()`` /
  ``perf_counter`` (:mod:`.clocks`);
* ``deadline-discipline`` — unbounded blocking waits (``Queue.get`` /
  ``Event.wait`` / ``Condition.wait`` / bare ``join`` / socket
  connects without timeout) on serving dispatch paths, where every
  wait must be bounded so end-to-end deadlines can fire
  (:mod:`.deadlines`);
* ``lock-order-cycle`` / ``lock-leak`` / ``condition-wait-predicate``
  — the zsan static layer: cycles in the interprocedural lock-
  acquisition-order graph, ``.acquire()`` without a guaranteed
  release, and ``cond.wait()`` outside a ``while`` predicate loop
  (:mod:`.concurrency`; runtime twin: :mod:`znicz_tpu.sanitizer`);
* ``retry-after-discipline`` — 429/503/504 refusals in serving/ +
  fleet/ without a ``Retry-After`` header (:mod:`.retry_after`);
* ``env-routing`` — ``os.environ`` touched under ops/ or parallel/,
  where routes are picked from layer list, shapes and platform
  (:mod:`.envrouting`).

Run it: ``python -m znicz_tpu lint`` (or ``tools/lint.sh``); gate:
``pytest -m lint``.  Suppress: ``# zlint: disable=RULE`` inline, or a
justified entry in ``tools/zlint_baseline.json``.  Full docs:
``docs/static_analysis.md``.
"""

from .clocks import DurationClockRule
from .concurrency import (ConditionWaitPredicateRule, LockLeakRule,
                          LockOrderCycleRule)
from .core import (Analyzer, Finding, ModuleInfo, RepoRule, Rule,
                   load_baseline, write_baseline)
from .cli import changed_paths, default_rules, main, run_repo
from .deadlines import DeadlineDisciplineRule
from .envrouting import EnvRoutingRule
from .handlers import HandlerSafetyRule
from .jaxrules import JaxHygieneRule, UnseededRandomRule
from .locks import LockDisciplineRule
from .metric_drift import MetricDriftRule
from .retry_after import RetryAfterRule
from .span_drift import SpanNameDriftRule

__all__ = [
    "Analyzer", "Finding", "ModuleInfo", "Rule", "RepoRule",
    "load_baseline", "write_baseline", "default_rules", "run_repo",
    "changed_paths", "main", "LockDisciplineRule", "JaxHygieneRule",
    "UnseededRandomRule", "HandlerSafetyRule", "MetricDriftRule",
    "DurationClockRule", "DeadlineDisciplineRule",
    "SpanNameDriftRule", "LockOrderCycleRule", "LockLeakRule",
    "ConditionWaitPredicateRule", "RetryAfterRule", "EnvRoutingRule",
]
