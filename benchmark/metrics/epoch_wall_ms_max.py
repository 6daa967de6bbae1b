"""The slowest epoch of the window, by the benchmark's clock."""


def read(run: dict):
    walls = run["window"]["epoch_walls_s"]
    return 1e3 * max(walls) if walls else None
