"""Opt-in ``jax.profiler`` trace capture for serving and training.

SNIPPETS exemplar [1] is the standard JAX practice: gate
``jax.profiler.start_trace/stop_trace`` behind a flag and wire it into
the loop.  This module is that pattern made reusable:

* :func:`start_trace` / :func:`stop_trace` — guarded process-wide
  capture (no-op with a warning when JAX is absent; refuses to nest —
  the profiler is a singleton in jaxlib too);
* :func:`trace` — context-manager form (``None`` dir → null context),
  used by the serve CLI's ``--profile-dir`` for whole-process capture;
* :class:`StepTraceHook` — periodic capture for long training runs:
  every ``every`` steps, record ``duration`` steps into a numbered
  subdirectory.  A multi-day run cannot afford (or store) one giant
  trace; a window every N steps is how regressions get localized.
  ``StandardWorkflow.train(profile_dir=..., profile_every=N)`` wires
  this into the fused epoch loop (epoch-granular there: the whole
  epoch is one device-side scan, so the epoch IS the host-visible
  step).

Knobs reach it three ways, most-specific wins: explicit arguments,
``serve --profile-dir``, and the ``ZNICZ_PROFILE_DIR`` /
``ZNICZ_PROFILE_EVERY`` environment variables (so an operator can
profile a deployed process without touching its launch script).
View traces with TensorBoard's profile plugin / xprof.
"""

from __future__ import annotations

import contextlib
import logging
import os
import signal as _signal
import threading

from . import scopes

_log = logging.getLogger(__name__)


@contextlib.contextmanager
def _shutdown_signals_blocked():
    """Block SIGINT/SIGTERM on the calling thread for the duration —
    threads spawned inside (the profiler session's workers) inherit
    the mask and so can never be picked as the delivery target for a
    process-directed Ctrl-C/SIGTERM.  Without this, sandboxed kernels
    (gVisor) have been observed parking an external SIGINT on a
    profiler thread forever, making a profiled server unkillable
    except by SIGKILL."""
    try:
        old = _signal.pthread_sigmask(
            _signal.SIG_BLOCK, {_signal.SIGINT, _signal.SIGTERM})
    except (ValueError, OSError):        # exotic host: skip the guard
        yield
        return
    try:
        yield
    finally:
        _signal.pthread_sigmask(_signal.SIG_SETMASK, old)

_lock = threading.Lock()
_active_dir: str | None = None

PROFILE_DIR_ENV = "ZNICZ_PROFILE_DIR"
PROFILE_EVERY_ENV = "ZNICZ_PROFILE_EVERY"


def dir_from_env() -> str | None:
    """``$ZNICZ_PROFILE_DIR`` or None (empty string means unset)."""
    return os.environ.get(PROFILE_DIR_ENV, "").strip() or None


def every_from_env() -> int | None:
    raw = os.environ.get(PROFILE_EVERY_ENV, "").strip()
    try:
        return int(raw) if raw else None
    except ValueError:
        _log.warning("ignoring non-integer %s=%r", PROFILE_EVERY_ENV,
                     raw)
        return None


def start_trace(trace_dir: str) -> bool:
    """Begin capturing into ``trace_dir`` (created if needed), with the
    **python tracer OFF**.  Returns False — never raises — when JAX is
    unavailable or a capture is already running: profiling is
    observability, and observability failing must not take the
    workload down.

    Why off: the python tracer hooks every live Python thread via
    ``PyEval_SetProfile`` at session start — observed here to break
    external SIGINT/SIGTERM delivery for the rest of the process when
    a request-handler thread is mid-flight at that instant (the server
    becomes unkillable except by SIGKILL).  The trace this repo wants
    is the host/device (XLA op) timeline; Python-side timing is
    already covered by telemetry.tracing spans and the step gauges."""
    global _active_dir
    with _lock:
        if _active_dir is not None:
            _log.warning("profiler already tracing into %s; ignoring "
                         "start_trace(%s)", _active_dir, trace_dir)
            return False
        try:
            import jax
            os.makedirs(trace_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            with _shutdown_signals_blocked():
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=options)
        except Exception as e:
            _log.warning("jax.profiler unavailable (%s); profiling "
                         "disabled", e)
            return False
        _active_dir = trace_dir
        return True


def stop_trace() -> str | None:
    """End the active capture; returns its directory (None when no
    capture was running)."""
    global _active_dir
    with _lock:
        if _active_dir is None:
            return None
        trace_dir, _active_dir = _active_dir, None
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:
            _log.warning("profiler trace export failed: %s", e)
        return trace_dir


def active_dir() -> str | None:
    with _lock:
        return _active_dir


class trace:
    """``with trace(dir):`` — whole-block capture; ``dir=None`` is a
    null context, so call sites stay unconditional."""

    def __init__(self, trace_dir: str | None):
        self.trace_dir = trace_dir
        self._started = False

    def __enter__(self):
        if self.trace_dir is not None:
            self._started = start_trace(self.trace_dir)
        return self

    def __exit__(self, *exc):
        if self._started:
            stop_trace()


class StepTraceHook:
    """Capture ``duration`` steps every ``every`` steps into
    ``<profile_dir>/step<N>``.

    Drive it with :meth:`on_step` once per step and :meth:`close` when
    the loop ends (closing mid-window stops the capture cleanly).
    Each capture it closes it also READS (``read``:
    :func:`~znicz_tpu.telemetry.scopes.profile_record`): device time by
    the step's scopes through the texts of the executables the trainer
    registered, idle time by the host's open span, as one
    ``train_profile`` flight-recorder record (``records``), so that a
    windowed capture of a long run says which layer's time grew without
    anyone opening a file.  ``start``/``stop``/``read`` are injectable
    for tests; ``read=None`` leaves the files unread.
    """

    def __init__(self, profile_dir: str, every: int = 100,
                 duration: int = 1, start=start_trace, stop=stop_trace,
                 read=scopes.profile_record):
        if every < 1 or duration < 1:
            raise ValueError(f"every/duration must be >= 1, got "
                             f"{every}/{duration}")
        self.profile_dir = profile_dir
        self.every = int(every)
        self.duration = int(duration)
        self._start, self._stop, self._read = start, stop, read
        self._capturing_until: int | None = None
        #: directories of completed captures, for tests/logs, and what
        #: was read from each (None: a capture that could not be read)
        self.captured: list[str] = []
        self.records: list = []
        self._current: str | None = None

    def on_step(self, step: int) -> None:
        if self._capturing_until is not None:
            if step >= self._capturing_until:
                self._finish()
            else:
                return
        if step % self.every == 0:
            d = os.path.join(self.profile_dir, f"step{step}")
            if self._start(d):
                self._current = d
                self._capturing_until = step + self.duration

    def _finish(self) -> None:
        self._stop()
        if self._current is not None:
            self.captured.append(self._current)
            if self._read is not None:
                self.records.append(self._read(self._current))
        self._current = None
        self._capturing_until = None

    def close(self) -> None:
        if self._capturing_until is not None:
            self._finish()
