"""Share of its roofline the grouped expert products of
``granite-4.0-h-small`` reach on the NEEDED work of the traced epochs, as
``moe_gmm_roofline`` reads it for ``mellum2-12b-a2.5b``, at this
configuration's keys and held share: ``2 * 3 * hidden_size *
intermediate_size`` operations a held token-expert pair forward, twice that
backward, pairs held from the window rows' ``moe_assignments_held`` /
``moe_assignments``; the larger of operations over the bf16 peak and bytes
over the HBM peak (the ``num_local_experts`` held experts' weights read
once a product: three products forward, three input-gradient products and
three weight gradients written in float32 backward).  The products' time is
found by name (``gmm`` in the ``short_name``: JAX's shipped megablox
``gmm`` / ``tgmm``); a trace without them, or rows without the counters,
give nothing to read."""

from benchmark.lib import granite_model as model


def read(run: dict):
    t, cfg, traffic = run["trace"], run["config"], run["traffic"]
    rows = [r for r in run["window"]["rows"] if r.get("moe_assignments")]
    if not t or run["peaks"] is None or not rows:
        return None
    seconds = sum(op["total_s"] for name, op in t["ops_s"].items()
                  if "gmm" in name.split(" ")[0])
    train_rows, eval_rows = model.traced_rows(run)
    if not seconds or not train_rows:
        return None
    share = (sum(r["moe_assignments_held"] for r in rows)
             / sum(r["moe_assignments"] for r in rows))
    layers = int(cfg["num_hidden_layers"])
    pairs_a_row = (share * int(traffic["seq_len"])
                   * int(cfg["num_experts_per_tok"]) * layers)
    need = model.expert_flops_per_assignment(cfg) * pairs_a_row * (
        3.0 * train_rows + eval_rows)
    held_weights = 3 * int(cfg["num_local_experts"]) * int(
        cfg["hidden_size"]) * int(cfg["intermediate_size"])
    steps = train_rows / run["batch"]
    evals = eval_rows / run["batch"]
    # bf16 operands read; float32 weight gradients written
    bytes_ = layers * held_weights * (2.0 * (2 * steps + evals)
                                      + 4.0 * steps)
    least = max(need / run["peaks"]["bf16_flops"],
                bytes_ / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / max(t.get("planes", 1), 1))
