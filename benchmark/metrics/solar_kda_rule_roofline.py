"""Share of its roofline Kimi delta attention's per-channel rule reaches, as
the kernels, on the NEEDED work of ``solar-open2-250b``'s KDA layers in the
traced epochs.  The least time of a layer's rule for one row is
``max(operations / bf16 peak, bytes / HBM bandwidth)``: forward, the
recurrence's own operations (``flops_by_part``'s ``kda_rule``) over ``q``,
``k``, the running sums of the decays (``K`` each) and ``v`` read and ``o``
written in float32; backward, twice those operations over ``q``, ``k``, the
running sums, ``v`` and ``do`` read and ``dq``, ``dk``, the running sums'
and ``dv`` written.  Trained rows take both, evaluated rows the forward
(the forward the backward runs again is not needed work).  Against the
kernels' device time, found by name (``pallas_kda_rule*`` by
``short_name``: ``ops/gdn.py``).  The count is of the work, whatever
implements the rule; a trace without such kernels, or a configuration of
another family, gives nothing to read."""

from benchmark.lib import solar_open2_model as model


def read(run: dict):
    t, cfg, traffic = run["trace"], run["config"], run["traffic"]
    if not t or run["peaks"] is None or "linear_attn_config" not in cfg:
        return None
    seconds = sum(op["total_s"] for name, op in t["ops_s"].items()
                  if name.startswith("pallas_kda_rule"))
    train_rows, eval_rows = model.traced_rows(run)
    if not seconds or not train_rows:
        return None
    s, peaks = model.sizes(cfg), run["peaks"]
    layers = model.kinds(cfg).count("kda")
    ops = model.flops_by_part(cfg, traffic)["kda_rule"]
    # float32 bytes a row of one channel of every head and KDA layer
    per_width = 4.0 * int(traffic["seq_len"]) * s["kda_heads"] * layers

    def least(ops, widths):
        return max(ops / peaks["bf16_flops"],
                   per_width * widths / peaks["hbm_bytes_per_s"])
    forward = least(ops, 3 * s["dk"] + 2 * s["dv"])
    backward = least(2.0 * ops, 6 * s["dk"] + 3 * s["dv"])
    need = train_rows * (forward + backward) + eval_rows * forward
    return 100.0 * need / (seconds / max(t.get("planes", 1), 1))
