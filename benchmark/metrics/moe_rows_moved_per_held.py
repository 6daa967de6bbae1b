"""Rows the expert layers moved between token order and expert order in
the window's epochs over the token-expert pairs they held: the program's
counters ``moe_rows_moved`` (rows of the sorted pieces that ran, a piece
under a condition that did not run adding nothing) over
``moe_assignments_held`` in the ``train_step`` rows.  1.0 would be every
moved row a held pair; a first piece of one and a half balanced shares
reads 1.5 at balance, and more where a later piece ran in some step.
Rows without the counter (a model without expert layers, a program
without the counter) give nothing to read."""


def read(run: dict):
    rows = [r for r in run["window"]["rows"]
            if r.get("moe_rows_moved") and r.get("moe_assignments_held")]
    if not rows:
        return None
    return (sum(r["moe_rows_moved"] for r in rows)
            / sum(r["moe_assignments_held"] for r in rows))
