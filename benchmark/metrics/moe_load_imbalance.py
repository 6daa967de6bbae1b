"""The most pairs one held expert got in one layer of one step of the
window (the rows' ``moe_expert_load_max``) over the mean load of a held
expert in a layer of a step (pairs held over steps x expert layers x
experts held): 1 is a perfect balance; the grouped product's tiles pad
each expert's rows, so imbalance costs it little, but an exchange
between chips would wait for the fullest."""


def read(run: dict):
    cfg = run["config"]
    rows = [r for r in run["window"]["rows"]
            if r.get("moe_assignments_held") and r.get("steps")]
    if not rows or "num_experts" not in cfg:
        return None
    mean = sum(r["moe_assignments_held"] for r in rows) / (
        sum(r["steps"] for r in rows) * int(cfg["num_hidden_layers"])
        * int(cfg["num_experts"]))
    return max(r["moe_expert_load_max"] for r in rows) / mean
