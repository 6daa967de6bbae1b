"""What the largest executable an epoch launches plans for temporaries,
in gigabytes: row field ``plan_temp_bytes_max`` (the trainer's register
of executables: ``compiled.memory_analysis()`` ``temp_size_in_bytes`` of
every program it built, the largest among those a ``trainer.dispatch``
span launched in the epoch), mean over the window's rows.  A plan, not a
reading of the device: known when the executable is built, the same
figure a compile for a described chip gives in the sandbox.  Rows
without the field (a program without the register, a runtime that gives
no plan) give nothing to read."""


def read(run: dict):
    plans = [r.get("plan_temp_bytes_max") for r in run["window"]["rows"]]
    if not plans or any(p is None for p in plans):
        return None
    return sum(plans) / len(plans) / 1e9
