"""Share of the window's epochs spent outside device calls, from the
program's own timeline rows (host_ms over wall_ms)."""


def read(run: dict):
    rows = [r for r in run["window"]["rows"] if r.get("wall_ms")]
    if not rows:
        return None
    return 100.0 * sum(r["host_ms"] for r in rows) / sum(
        r["wall_ms"] for r in rows)
