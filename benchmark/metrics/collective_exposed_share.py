"""Device time of the collectives (``all-reduce*``, ``all-gather*``,
``reduce-scatter*``, ``collective-permute*`` on the ``XLA Ops`` line, by
``short_name``) over the device time of the training executables, a
chip: operations run one after another on a TPU core, so time in a
collective is time not computing.  A one-chip trace has none and gives
nothing to read."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")


def read(run: dict):
    t = run["trace"]
    if not t or not t.get("train_exec_s") or run["chips"] < 2:
        return None
    seconds = sum(op["total_s"] for name, op in t["ops_s"].items()
                  if name.startswith(COLLECTIVES))
    if not seconds:
        return None
    return 100.0 * seconds / max(t.get("planes", 1), 1) / t["train_exec_s"]
