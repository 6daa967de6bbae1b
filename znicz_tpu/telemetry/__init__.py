"""Unified observability: metrics registry, request tracing, profiling.

PR 1–2 gave the repo production behaviors (batching, backpressure,
retries, a circuit breaker, elastic restarts) but each grew its own
ad-hoc JSON counters — no shared registry, no latency histograms, no
request correlation.  This package is the cross-cutting seam every
later perf/robustness PR reports through:

* :mod:`registry` — process-wide, thread-safe counters / gauges /
  bounded histograms; one store, two scrape views (back-compat JSON
  dicts + Prometheus text exposition v0.0.4).
* :mod:`tracing`  — request ids (``X-Request-Id`` in/out) propagated
  HTTP handler → micro-batcher → engine, plus lightweight spans with
  monotonic timings feeding ``span_duration_ms`` histograms.
* :mod:`profiler` — opt-in ``jax.profiler`` capture: whole-process
  (``serve --profile-dir``, ``$ZNICZ_PROFILE_DIR``) or windowed
  per-N-steps during training (:class:`~profiler.StepTraceHook`).
* :mod:`buildinfo` — the git-rev stamp (shared with bench.py) that
  makes scraped metrics attributable to a build.
* :mod:`compilestats` — compile accounting at every executable-creation
  site (``compile_time_ms{site}``, ``compiles_total{site,cause}``,
  executable-cache hit/miss counters): "zero request-path compiles in
  steady state" as a testable metric.
* :mod:`programs` — the register of the executables a process built
  (role, argument shapes, memory plan, a weak handle to the compiled
  text); :mod:`scopes` — device time by the step's scopes and idle
  time by the host's open span, out of a capture and those texts.
* :mod:`flightrecorder` — bounded ring of recent request / train-step
  records with threshold-retained slow outliers and last-N errors;
  serves ``GET /debug/flightrecorder``.
* :mod:`debugz` — ``GET /statusz`` (human one-pager), thread/stack
  introspection (``/debug/threadz``, SIGUSR1 dump), process uptime.

Everything here is stdlib-only (JAX is imported lazily and only by the
profiler), so resilience/serving/parallel can record unconditionally.

See docs/observability.md for the metric inventory, span fields,
profiler knobs, and a scrape example.
"""

from .flightrecorder import RECORDER, FlightRecorder
from .registry import (REGISTRY, Counter, Gauge, Histogram,
                       MetricsRegistry, PROMETHEUS_CONTENT_TYPE)
from .tracing import (Span, accept_request_id, current_request_id,
                      new_request_id, recent_spans, span)

__all__ = ["RECORDER", "FlightRecorder", "REGISTRY", "Counter",
           "Gauge", "Histogram", "MetricsRegistry",
           "PROMETHEUS_CONTENT_TYPE", "Span", "accept_request_id",
           "current_request_id", "new_request_id", "recent_spans",
           "span"]
