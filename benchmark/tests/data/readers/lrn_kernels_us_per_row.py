"""A test's reader, of the kind a later PR brings for a kernel of its
own: device microseconds the LRN kernels (``pallas_lrn*`` /
``pallas_gd_lrn*`` by ``short_name``) took for each trained row of the
traced epochs.  It takes the kernels' time from ``trace.ops_s`` (every
operation, not the ten longest), whether the configuration has such a
layer from ``config``, and the rows a step trains from ``traffic``.  A
configuration without a ``norm`` layer, or a trace without those
kernels, gives it nothing to read."""


def read(run: dict):
    has_lrn = any(la["type"] == "norm" for la in run["config"]["layers"])
    rows = run["traffic"]["minibatch"] * (run["trace"] or {}).get(
        "train_steps", 0)
    if not has_lrn or not rows:
        return None
    seconds = sum(op["total_s"] for name, op in run["trace"]["ops_s"].items()
                  if name.startswith(("pallas_lrn", "pallas_gd_lrn")))
    return 1e6 * seconds / rows if seconds else None
