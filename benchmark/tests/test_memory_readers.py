"""The two readers of what a launch plans and needs (PR 38), on hand-made
window rows: every row with the field, none with it (the parent's
program, a runtime without a plan, the CPU), and a window in which only
some rows have it, which is read as nothing and never as a part."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _run(rows: list) -> dict:
    return {"window": {"rows": rows}, "trace": None, "peaks": None}


ROW = {"epoch": 7, "wall_ms": 750.0, "launches": 4, "compiles": 0}
PLANNED = [
    {**ROW, "plan_temp_bytes_max": 2_994_589_696,
     "launch_need_bytes": 12_600_000_000, "hbm_in_use_bytes": 9_605_410_304,
     "hbm_limit_bytes": 16_909_336_064},
    {**ROW, "plan_temp_bytes_max": 2_994_589_696,
     "launch_need_bytes": 12_900_000_000, "hbm_in_use_bytes": 9_905_410_304,
     "hbm_limit_bytes": 16_909_336_064},
    {**ROW, "plan_temp_bytes_max": 3_000_589_696,
     "launch_need_bytes": 12_700_000_000, "hbm_in_use_bytes": 9_699_410_304,
     "hbm_limit_bytes": 16_909_336_064},
]
# a device that does not say what it holds: a plan and no need
PLAN_ONLY = [{**ROW, "plan_temp_bytes_max": 6408}] * 2


@pytest.mark.parametrize("name, rows, value", [
    # present: the mean of the plans, the largest of the needs
    ("step_plan_temp_gb", PLANNED, 2.996589696),
    ("launch_need_gb", PLANNED, 12.9),
    ("step_plan_temp_gb", PLAN_ONLY, 6.408e-6),
    ("launch_need_gb", PLAN_ONLY, None),
    # absent: the parent's rows, an empty window
    ("step_plan_temp_gb", [ROW, ROW], None),
    ("launch_need_gb", [ROW, ROW], None),
    ("step_plan_temp_gb", [], None),
    ("launch_need_gb", [], None),
    # mixed: one row without the field leaves the metric out
    ("step_plan_temp_gb", [*PLANNED, ROW], None),
    ("launch_need_gb", [ROW, *PLANNED], None),
    ("launch_need_gb", [*PLANNED, *PLAN_ONLY], None),
])
def test_a_reader_reads_every_row_or_nothing(name, rows, value):
    got = _reader(name)(_run(rows))
    if value is None:
        assert got is None
    else:
        assert got == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name", ["step_plan_temp_gb", "launch_need_gb"])
def test_the_metric_is_declared_for_every_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "GB", "better": "lower",
                     "source": "program_counter", "layer": "device",
                     "moves": "train_images_per_s"}
    # the need holds the plan: what a result line's two values obey
    planned, need = (_reader(n)(_run(PLANNED))
                     for n in ("step_plan_temp_gb", "launch_need_gb"))
    assert need >= planned > 0
    assert need * 1e9 <= PLANNED[0]["hbm_limit_bytes"]
