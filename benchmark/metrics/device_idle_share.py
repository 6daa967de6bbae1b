"""Share of the traced epochs in which no operation ran on the device:
1 - union of the operations' intervals over the traced span."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
