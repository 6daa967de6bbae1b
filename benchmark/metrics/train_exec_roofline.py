"""Least time the chip could take for the traced training steps, the
larger of operations over the bf16 peak and bytes over the HBM peak
(parameters and velocities read and written once a step, the minibatch
read once), over the device time of the training executables.
Compute-bound in both first configurations (the operations' bound is
some 20 to 50 times the bytes')."""


def read(run: dict):
    t = run["trace"]
    if (not t or not t["train_exec_s"] or not t["train_steps"]
            or run["peaks"] is None):
        return None
    f, p = run["flops"], run["peaks"]
    least = max(run["batch"] * f["train_step"] / p["bf16_flops"],
                run["step_bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least * t["train_steps"] / (t["train_exec_s"]
                                               * run["chips"])
