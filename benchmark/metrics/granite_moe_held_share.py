"""Of the token-expert pairs the routers of ``granite-4.0-h-small`` chose in
the window's epochs, the share whose expert this chip holds: the program's
counters ``moe_assignments_held`` over ``moe_assignments`` in the
``train_step`` rows (expected: 9 experts held of 72, 12.5%).  Rows without
the counters (a program without the expert layers' counters) give nothing
to read."""


def read(run: dict):
    rows = [r for r in run["window"]["rows"] if r.get("moe_assignments")]
    if not rows:
        return None
    return 100.0 * (sum(r["moe_assignments_held"] for r in rows)
                    / sum(r["moe_assignments"] for r in rows))
