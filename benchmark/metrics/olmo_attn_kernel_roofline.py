"""Share of the bf16 peak the attention kernels reach on the NEEDED
score-and-mix work of ``olmo-hybrid-7b``'s one full-attention layer in the
traced epochs: ``4 * head size * heads`` a kept query/key pair forward (``j
<= i``: full causal, 30 heads of 128), twice that backward (the kernel's
own recomputation of the scores in its backward is not needed work), over
trained and evaluated rows; against the kernels' device time, found by name
(``splash_*`` by ``short_name``: JAX's shipped splash attention).
Compute-bound at these shapes.  The count is of the work, whatever
implements the kernel; a trace without such kernels, or a configuration of
another family, gives nothing to read."""

from benchmark.lib import olmo_hybrid_model as model


def read(run: dict):
    t, cfg, traffic = run["trace"], run["config"], run["traffic"]
    if not t or run["peaks"] is None or "linear_key_head_dim" not in cfg:
        return None
    seconds = sum(op["total_s"] for name, op in t["ops_s"].items()
                  if name.startswith("splash_"))
    train_rows, eval_rows = model.traced_rows(run)
    if not seconds or not train_rows:
        return None
    forward = model.flops_by_part(cfg, traffic)["attention_scores"]
    need = forward * (3.0 * train_rows + eval_rows)
    return 100.0 * need / (run["peaks"]["bf16_flops"] * seconds
                           / max(t.get("planes", 1), 1))
