"""Share of the bf16 peak the attention kernels reach on the NEEDED
score-and-mix work of ``falcon-h1-34b-instruct``'s attention mixers in the
traced epochs: ``4 * head size * heads`` a kept query/key pair forward (``j
<= i``: full causal, the 5 query heads of 128 held over 1 key/value head,
rotary tables at theta 1e11; every one of the four layers), twice that
backward (the kernel's own recomputation of the scores in its backward is
not needed work), over trained and evaluated rows; against the kernels'
device time, found by name (``splash_*`` by ``short_name``: JAX's shipped
splash attention).  Compute-bound at these shapes.  The count is of the
work, whatever implements the kernel; a trace without such kernels, or a
configuration of another family, gives nothing to read."""

from benchmark.lib import falcon_h1_model as model


def read(run: dict):
    t, cfg, traffic = run["trace"], run["config"], run["traffic"]
    if not t or run["peaks"] is None or "ssm_out_multiplier" not in cfg:
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
