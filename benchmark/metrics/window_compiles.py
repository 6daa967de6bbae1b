"""Programs compiled or loaded from the cache inside the measured window.
0 in a sound run: the warm-up has met every shape."""


def read(run: dict):
    w = run["window"]
    return float(sum(1 for t, _, _ in run["compile_events"]
                     if w["t0"] < t <= w["t1"]))
