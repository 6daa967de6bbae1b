"""The two readers of the program's own spans (ISSUE 26): on the test-size
cell ``epoch_launches`` is the calls one epoch of ``tiny-b8`` makes and
``epoch_dispatch_share`` a share; on rows without the fields (a program
from before the spans) both read nothing and the line leaves them out."""

import importlib.util
import os

import pytest

from benchmark import run as runner
from conftest import ROOT, cell_args


def _reader(name, directory=os.path.join(ROOT, "benchmark", "metrics")):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(directory, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_traced_tiny_cell_reports_both(tiny_bench):
    bench, base = tiny_bench
    rc, res = runner.run_cell(cell_args(trace=1), bench, base=base,
                              require_chip=False)
    assert rc == 0 and res["correct"] is True
    m = res["metrics"]
    # 48 rows at 8: the deferred tail's update, the head of 5, the tail's
    # evaluation, the validation pass; no test set
    assert m["epoch_launches"] == {"value": 4.0, "unit": "count"}
    assert 0.0 < m["epoch_dispatch_share"]["value"] < 100.0
    assert m["epoch_dispatch_share"]["unit"] == "%"
    # what epoch_host_share read, it still reads: outside the calls
    assert 0.0 < m["epoch_host_share"]["value"] < 100.0


OLD_ROW = {"epoch": 3, "steps": 6, "examples": 48, "wall_ms": 20.0,
           "device_ms": 19.0, "host_ms": 1.0, "examples_per_sec": 2400.0}
NEW_ROW = dict(OLD_ROW, launches=4, prep_ms=0.5, dispatch_ms=1.5,
               readback_ms=16.0, compiles=0, prev_tail_ms=0.1)


@pytest.mark.parametrize("name,rows,expected", [
    ("epoch_launches", [NEW_ROW, dict(NEW_ROW, launches=5)], 4.5),
    ("epoch_dispatch_share", [NEW_ROW, NEW_ROW], 10.0),
    ("epoch_launches", [OLD_ROW, OLD_ROW], None),
    ("epoch_dispatch_share", [OLD_ROW, OLD_ROW], None),
    ("epoch_launches", [], None),
    ("epoch_dispatch_share", [], None),
    # the streamed trainer opens no trainer.* spans: the fields are None
    ("epoch_launches", [dict(NEW_ROW, launches=None)], None),
    ("epoch_dispatch_share", [dict(NEW_ROW, prep_ms=None,
                                   dispatch_ms=None)], None),
])
def test_readers_on_rows(name, rows, expected):
    got = _reader(name)({"window": {"rows": rows}})
    assert got == (None if expected is None else pytest.approx(expected))
