"""Of the token-layer pairs of ``falcon-h1-34b-instruct``'s hidden layers in
the window's epochs, the share that ran through both mixers of a parallel
block: the program's counter ``parallel_tokens`` over ``tokens`` times the
layers that run, in the ``train_step`` rows (expected: every layer, every
token, exactly 100%).  It says that each layer ran its attention and its
Mamba-2 mixer side by side in the timed path.  Rows without the counter (a
program without the kind) give nothing to read."""


def read(run: dict):
    rows = [r for r in run["window"]["rows"] if r.get("parallel_tokens")]
    if not rows:
        return None
    layers = int(run["config"]["num_hidden_layers"])
    return 100.0 * sum(r["parallel_tokens"] for r in rows) / (
        layers * sum(r["tokens"] for r in rows))
