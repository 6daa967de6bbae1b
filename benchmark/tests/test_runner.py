"""The runner at test widths on the CPU: the window, the result line, the
files found by name, and ``correct`` coming out false under each fault a
training cell can have."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import run as runner
from conftest import ROOT, cell_args

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_window_result_and_correct(tiny_bench):
    bench, base = tiny_bench
    rc, res = runner.run_cell(cell_args(seconds=0.5), bench, base=base,
                              require_chip=False)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert RESULT_KEYS <= set(res) and list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    w = res["window"]
    # stops within one epoch of --seconds
    assert 0.5 <= w["seconds"] < 0.5 + 2.0 * w["seconds"] / w["epochs"] + 1.0
    assert res["attempted"] == w["epochs"] * 6 and res["failed"] == 0
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap",
                 "out_grad_diff"):
        c = res["checks"][name]
        assert c["value"] < 1e-5 < c["limit"]          # f32 on the CPU
    assert res["checks"]["rows_misfed"] == {"value": 0, "limit": 0}


def test_traced_run_reports_per_layer_metrics_only(tiny_bench):
    bench, base = tiny_bench
    rc, res = runner.run_cell(cell_args(trace=1), bench, base=base,
                              require_chip=False)
    assert rc == 0 and res["correct"] is True
    names = set(res["metrics"])
    assert {"compile_s", "dataset_s", "window_compiles",
            "epoch_host_share", "epoch_wall_ms_max"} <= names
    # no chip: no share of a peak, no device time, and never a 0 for one
    assert not names & {"step_mfu", "train_exec_roofline",
                        "train_step_device_ms", "device_idle_share",
                        "hbm_peak_share", "train_images_per_s"}
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert "breakdown" in res and "busy_s" in res["device"]


def _broken(monkeypatch, fault):
    """Break the timed path underneath the runner."""
    from znicz_tpu.parallel import fused
    if fault == "state unchanged":
        monkeypatch.setattr(
            fused, "apply_updates",
            lambda spec, params, vels, grads, *a, **k: (params, vels))
    elif fault == "half of the batch left out":
        orig = fused._loss_and_err

        def half(spec, out, target, mask):
            keep = (np.arange(mask.shape[0]) < mask.shape[0] // 2)
            return orig(spec, out, target, mask * keep.astype("float32"))
        monkeypatch.setattr(fused, "_loss_and_err", half)
    elif fault == "rows fed twice":
        orig = fused.FusedTrainer._idx_matrix

        def dup(self, indices, batch, ctr_base=0):
            idx, mask, ctrs = orig(self, indices, batch, ctr_base)
            idx[:, 1::2] = idx[:, 0::2]
            return idx, mask, ctrs
        monkeypatch.setattr(fused.FusedTrainer, "_idx_matrix", dup)


@pytest.mark.parametrize("fault", ["state unchanged",
                                   "half of the batch left out",
                                   "rows fed twice"])
def test_fault_under_the_timed_path_is_not_correct(tiny_bench, monkeypatch,
                                                   fault):
    bench, base = tiny_bench
    _broken(monkeypatch, fault)
    rc, res = runner.run_cell(cell_args(seconds=0.2), bench, base=base,
                              require_chip=False)
    assert rc == 0 and res["correct"] is False, (fault, res["checks"])


def test_lower_precision_of_the_program_is_not_correct(tiny_bench):
    """The program's own bfloat16 path against CPU-tight limits."""
    bench, base = tiny_bench
    rc, res = runner.run_cell(
        cell_args(seconds=0.2,
                  override=["common.compute_dtype='bfloat16'"]),
        bench, base=base, require_chip=False)
    assert rc == 0 and res["correct"] is False, res["checks"]


@pytest.mark.parametrize("missing", ["configs/tiny.json", "configs/tiny.py",
                                     "traffic/tiny-b8.json",
                                     "limits/tiny-train.json",
                                     "metrics/step_mfu.py"])
def test_missing_file_is_named(tiny_bench, missing):
    bench, base = tiny_bench
    os.remove(os.path.join(base, "bench", missing))
    with pytest.raises(runner.BenchError, match=os.path.basename(missing)):
        runner.find_cell(bench, "tiny-train", base)


def test_unknown_device_kind_is_an_error():
    from benchmark.lib import peaks
    assert peaks.peaks_of("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks_of("TPU v9")


def test_no_chip_exits_nonzero_and_prints_no_result():
    """The command itself, where JAX finds only the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "alexnet-b128-train", "--seed", "1", "--seconds",
         "1", "--trace", "1"], env=env, cwd=ROOT, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "chip" in out.stderr


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for w in bench["workloads"]:
        found = runner.find_cell(bench, w["name"], ROOT)
        assert set(found["limits"]) == {"loss_gap", "grad_norm_gap",
                                        "change_norm_gap", "out_grad_diff",
                                        "rows_misfed"}
        assert len(found["readers"]) == len(bench["per_layer"])
