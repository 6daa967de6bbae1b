"""Share of the bf16 peak the attention kernels reach on the NEEDED
score-and-mix work of the traced epochs: ``4 * head_dim * heads`` a kept
query/key pair forward (``j <= i``, in a sliding layer ``i - j <
window``), twice that backward (the kernel's own recomputation of the
scores in its backward is not needed work), over trained and evaluated
rows; against the kernels' device time, found by name (``splash_*`` by
``short_name``: JAX's shipped splash attention).  Compute-bound at these
shapes: a full layer's 0.55 TFLOP forward against 0.15 GB of q, k, v and
out.  The count is of the work, whatever implements the kernel; a trace
without such kernels gives nothing to read."""

from benchmark.lib import decoder_model as model


def read(run: dict):
    t, cfg, traffic = run["trace"], run["config"], run["traffic"]
    if not t or run["peaks"] is None or "layer_types" not in cfg:
        return None
    seconds = sum(op["total_s"] for name, op in t["ops_s"].items()
                  if name.startswith("splash_"))
    train_rows, eval_rows = model.traced_rows(run)
    if not seconds or not train_rows:
        return None
    by_type = model.attention_flops(cfg, int(traffic["seq_len"]))
    forward = sum(by_type[kind] for kind in
                  cfg["layer_types"][:int(cfg["num_hidden_layers"])])
    need = forward * (3.0 * train_rows + eval_rows)
    return 100.0 * need / (run["peaks"]["bf16_flops"] * seconds
                           / max(t.get("planes", 1), 1))
