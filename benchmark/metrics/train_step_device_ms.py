"""Device time of the training executables' runs in the traced epochs
(the trace's per-executable line) over the minibatches they trained."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["train_exec_s"] or not t["train_steps"]:
        return None
    return 1e3 * t["train_exec_s"] / t["train_steps"]
