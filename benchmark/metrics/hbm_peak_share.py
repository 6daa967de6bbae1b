"""Peak bytes in use on the fullest device, after the window, over the
chip's published HBM."""


def read(run: dict):
    if run["peaks"] is None or not run["memory_peak_bytes"]:
        return None
    return 100.0 * run["memory_peak_bytes"] / run["peaks"]["hbm_bytes"]
