"""Executables the program dispatched an epoch, mean over the window's
rows (row field ``launches``, counted at the trainer's
``trainer.dispatch`` span)."""


def read(run: dict):
    counts = [r.get("launches") for r in run["window"]["rows"]]
    if not counts or any(c is None for c in counts):
        return None
    return sum(counts) / len(counts)
