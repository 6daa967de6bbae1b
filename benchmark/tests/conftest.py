"""The harness's own checks: ``python3 -m pytest benchmark/tests -q`` with
``JAX_PLATFORMS=cpu ZNICZ_TPU_PALLAS_INTERPRET=1``.  Not part of the
repo's tier-1 tests."""

import argparse
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("ZNICZ_TPU_PALLAS_INTERPRET", "1")


@pytest.fixture
def tiny_bench(tmp_path):
    """A whole benchmark of one test-size cell in a scratch directory:
    its own configuration, traffic and limits files beside a copy of the
    real metric readers.  Returns (bench dict, base directory)."""
    here = os.path.dirname(os.path.abspath(__file__))
    shutil.copytree(os.path.join(here, "data"), tmp_path / "bench")
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    tmp_path / "bench" / "metrics")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    bench = dict(
        real, paths=["bench"],
        configs=[{"name": "tiny", "source": "test", "reduced": [],
                  "file": "bench/configs/tiny.json", "why": "test"}],
        workloads=[{"name": "tiny-train", "config": "tiny",
                    "traffic": "tiny-b8", "chips": 1, "why": "test"}])
    for m in bench["per_layer"] + bench["end_to_end"]:
        m.pop("workloads", None)
    return bench, str(tmp_path)


def cell_args(**kw):
    base = dict(workload="tiny-train", seed=2147483999, seconds=0.5,
                trace=0, override=[], keep_trace=None)
    base.update(kw)
    return argparse.Namespace(**base)
