"""What the device has to have for the window's launches to load, in
gigabytes: row field ``launch_need_bytes`` (``bytes_in_use`` on the
fullest device just before a launch, read once an epoch and role, plus
that launch's planned temporaries), the largest over the window's rows.
Gigabytes and not a share of a peak: the device's own limit is 16.9e9
where the published memory is 16e9, and the loader's reserve is smaller
than the plan.  Rows without the field (a program without the register,
a device that does not say what it holds) give nothing to read."""


def read(run: dict):
    needs = [r.get("launch_need_bytes") for r in run["window"]["rows"]]
    if not needs or any(n is None for n in needs):
        return None
    return max(needs) / 1e9
