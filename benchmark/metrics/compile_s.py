"""Seconds the backend spent compiling, or loading compiled programs from
the persistent cache, during set-up (jax.monitoring's
backend_compile_duration, every executable, the deferred tail's too)."""


def read(run: dict):
    t0 = run["window"]["t0"]
    return sum(d for t, d, _ in run["compile_events"] if t <= t0)
