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


#: a reader of the tests' own (``data/readers/``), listed in the scratch
#: benchmark beside the real ones
TEST_READER = "lrn_kernels_us_per_row"


@pytest.fixture
def tiny_bench(tmp_path):
    """A whole benchmark of two test-size cells in a scratch directory
    (``tiny-train``: images; ``tiny-vec-train``: rows that are not
    images, with a model file, workflow file and reference of its own):
    their configuration, traffic and limits files beside a copy of the
    real metric readers, and the ``BENCHMARK.json`` that lists them.
    Returns (bench dict, base directory)."""
    here = os.path.dirname(os.path.abspath(__file__))
    shutil.copytree(os.path.join(here, "data"), tmp_path / "bench")
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    tmp_path / "bench" / "metrics")
    shutil.copy(os.path.join(here, "data", "readers", TEST_READER + ".py"),
                tmp_path / "bench" / "metrics")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    bench = dict(
        real, paths=["bench"],
        configs=[{"name": name, "source": "test", "reduced": [],
                  "file": f"bench/configs/{name}.json", "why": "test"}
                 for name in ("tiny", "tiny-vec")],
        workloads=[{"name": f"{name}-train", "config": name,
                    "traffic": "tiny-b8", "chips": 1, "why": "test"}
                   for name in ("tiny", "tiny-vec")])
    bench["per_layer"] = bench["per_layer"] + [
        {"name": TEST_READER, "unit": "us", "better": "lower",
         "source": "device_trace", "layer": "kernels",
         "moves": "train_images_per_s"}]
    for m in bench["per_layer"] + bench["end_to_end"]:
        m.pop("workloads", None)
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    return bench, str(tmp_path)


def cell_args(**kw):
    base = dict(workload="tiny-train", seed=2147483999, seconds=0.5,
                trace=0, override=[], keep_trace=None)
    base.update(kw)
    return argparse.Namespace(**base)
