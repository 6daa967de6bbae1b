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


@pytest.mark.parametrize("trace", [0, 1])
def test_rows_that_are_not_images_run_to_a_correct_result(tiny_bench, trace):
    """``tiny-vec``: a feature vector a row, with a model file, workflow
    file and reference of its own, through the same ``run_cell``."""
    bench, base = tiny_bench
    rc, res = runner.run_cell(
        cell_args(workload="tiny-vec-train", trace=trace), bench,
        base=base, require_chip=False)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["window"]["row"] == {"kind": "vector"}
    assert res["attempted"] == res["window"]["epochs"] * 6
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap",
                 "out_grad_diff"):
        c = res["checks"][name]
        assert c["value"] < 1e-5 < c["limit"]          # f32 on the CPU
    assert res["checks"]["rows_misfed"] == {"value": 0, "limit": 0}
    if trace:
        assert {"compile_s", "epoch_launches"} <= set(res["metrics"])
    else:
        assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}


def test_traced_run_reports_per_layer_metrics_only(tiny_bench):
    bench, base = tiny_bench
    rc, res = runner.run_cell(cell_args(trace=1), bench, base=base,
                              require_chip=False)
    assert rc == 0 and res["correct"] is True
    assert res["window"]["row"] == {"kind": "image"}
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


@pytest.mark.parametrize("workload,fault", [
    ("tiny-train", "state unchanged"),
    ("tiny-train", "half of the batch left out"),
    ("tiny-train", "rows fed twice"),
    ("tiny-vec-train", "half of the batch left out")])
def test_fault_under_the_timed_path_is_not_correct(tiny_bench, monkeypatch,
                                                   workload, fault):
    bench, base = tiny_bench
    _broken(monkeypatch, fault)
    rc, res = runner.run_cell(cell_args(workload=workload, seconds=0.2),
                              bench, base=base, require_chip=False)
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


@pytest.mark.parametrize("key", ["workflow", "reference", "model"])
def test_file_a_configuration_names_is_checked(tiny_bench, key):
    bench, base = tiny_bench
    path = os.path.join(base, "bench", "configs", "tiny.json")
    with open(path) as fh:
        cfg = json.load(fh)
    named = dict(cfg, **{key: "benchmark/lib/no_such_file.py"})
    unnamed = {k: v for k, v in cfg.items() if k != key}
    for config, says in ((named, "no_such_file.py"), (unnamed, key)):
        with open(path, "w") as fh:
            json.dump(config, fh)
        with pytest.raises(runner.BenchError, match=says):
            runner.find_cell(bench, "tiny-train", base)


@pytest.mark.parametrize("workload,config", [("tiny-train", "tiny"),
                                             ("tiny-vec-train", "tiny-vec")])
def test_unknown_layer_kind_exits_2_with_one_line(tiny_bench, capsys,
                                                  workload, config):
    """A count or a shape the model file cannot give is a fault of the
    configuration: one line, no result, no traceback, before the chip
    is looked for."""
    bench, base = tiny_bench
    path = os.path.join(base, "bench", "configs", config + ".json")
    with open(path) as fh:
        cfg = json.load(fh)
    cfg["layers"][0]["type"] = "attention"
    with open(path, "w") as fh:
        json.dump(cfg, fh)
    rc = runner.main(["--workload", workload, "--seed", "5", "--seconds",
                      "0.2", "--rehearse"], base=base)
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert len(err.strip().splitlines()) == 1 and "'attention'" in err


@pytest.mark.parametrize("workload,variants", [
    ("tiny-train", {"control_fp8", "stated_bf16", "fault_half_batch",
                    "fault_frozen"}),
    ("tiny-vec-train", {"fault_half_batch", "fault_frozen"})])
def test_limits_study_reads_each_configuration_through_its_files(
        tiny_bench, capsys, workload, variants):
    """The chip's study of the limits, at test size: rows, weights and the
    output leaf from the model file, the variants from the reference."""
    import limits_study
    _, base = tiny_bench
    limits_study.main(["--workload", workload, "--seeds", "11"], base=base)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    by_name = {line["variant"]: line for line in lines}
    assert set(by_name) == variants | {"reference"}
    assert by_name["fault_frozen"]["change_norm_gap"] == pytest.approx(1.0)
    assert by_name["fault_half_batch"]["grad_norm_gap"] > 0.1


def test_run_py_knows_no_image_and_no_config_tree():
    with open(os.path.join(ROOT, "benchmark", "run.py")) as fh:
        text = fh.read()
    for word in ("input_size", "input_channels", "alexnet.", "n_classes",
                 "noise", '"layers"'):
        assert word not in text, word
    for name in ("probe.py", "correct.py"):
        with open(os.path.join(ROOT, "benchmark", "lib", name)) as fh:
            text = fh.read()
        for word in ("(w, b)", "len(ref_g) - 2", "hypers_bias"):
            assert word not in text, (name, word)


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
