"""Share of the window's epochs the host spent inside device calls
before the device had the work: the trainer's ``trainer.prep`` and
``trainer.dispatch`` spans (row fields ``prep_ms`` + ``dispatch_ms``) over
``wall_ms``.  ``epoch_host_share`` counts the time outside those calls;
this is the launch cost inside them."""


def read(run: dict):
    rows = [r for r in run["window"]["rows"] if r.get("wall_ms")]
    if not rows or any(r.get("prep_ms") is None
                       or r.get("dispatch_ms") is None for r in rows):
        return None
    return 100.0 * sum(r["prep_ms"] + r["dispatch_ms"] for r in rows) / sum(
        r["wall_ms"] for r in rows)
