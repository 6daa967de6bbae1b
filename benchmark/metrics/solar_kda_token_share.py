"""Of the token-layer pairs of ``solar-open2-250b``'s hidden layers in the
window's epochs, the share that a Kimi-delta-attention layer scanned: the
program's counter ``kda_tokens`` over ``tokens`` times the layers that run,
in the ``train_step`` rows (expected: three of four layers, exactly 75%).
It says that the three KDA layers ran as KDA layers in the timed path.
Rows without the counter (a program without the kind) give nothing to
read."""


def read(run: dict):
    rows = [r for r in run["window"]["rows"] if r.get("kda_tokens")]
    if not rows:
        return None
    layers = int(run["config"]["num_hidden_layers"])
    return 100.0 * sum(r["kda_tokens"] for r in rows) / (
        layers * sum(r["tokens"] for r in rows))
