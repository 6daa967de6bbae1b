"""``tiny-decoder`` through the real trainer on the CPU: a configuration
whose rows are token sequences and whose layers are the token-sequence
kinds, through the same ``run_cell`` as the image cells; ``correct``
true, and false for each fault planted under the timed path."""

import json
import os
import shutil

import pytest

from benchmark import run as runner
from conftest import ROOT, cell_args

CELL = "tiny-decoder-train"


@pytest.fixture
def decoder_bench(tmp_path):
    """A scratch benchmark of the one test-size decoder cell, beside a
    copy of the real metric readers."""
    here = os.path.dirname(os.path.abspath(__file__))
    shutil.copytree(os.path.join(here, "data"), tmp_path / "bench")
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    tmp_path / "bench" / "metrics")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    bench = dict(
        real, paths=["bench"],
        configs=[{"name": "tiny-decoder", "source": "test", "reduced": [],
                  "file": "bench/configs/tiny-decoder.json",
                  "why": "test"}],
        workloads=[{"name": CELL, "config": "tiny-decoder",
                    "traffic": "tiny-s32-b2", "chips": 1, "why": "test"}])
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:          # the new cell's own readers too
            m["workloads"] = [CELL]
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    return bench, str(tmp_path)


@pytest.mark.parametrize("trace", [0, 1])
def test_token_sequences_run_to_a_correct_result(decoder_bench, trace):
    bench, base = decoder_bench
    rc, res = runner.run_cell(cell_args(workload=CELL, trace=trace), bench,
                              base=base, require_chip=False)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["window"]["row"]["kind"] == "sequence"
    assert res["attempted"] == res["window"]["epochs"] * 6
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap",
                 "out_grad_diff"):
        c = res["checks"][name]
        assert c["value"] < 2e-5 < c["limit"], (name, c)   # f32 on the CPU
    assert res["checks"]["rows_misfed"] == {"value": 0, "limit": 0}
    if trace:
        m = res["metrics"]
        # a quarter of the experts held: about a quarter of the pairs
        assert 5.0 < m["moe_held_share"]["value"] < 60.0
        assert m["moe_load_imbalance"]["value"] >= 1.0
        # no chip: no share of a peak, never a 0 for one
        assert not set(m) & {"attn_kernel_roofline", "moe_gmm_roofline",
                             "collective_exposed_share", "step_mfu"}
    else:
        assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}


def _broken(monkeypatch, fault):
    """Break the timed path underneath the runner."""
    from znicz_tpu.ops import attention, moe
    from znicz_tpu.parallel import fused
    if fault == "state unchanged":
        monkeypatch.setattr(
            fused, "apply_updates",
            lambda spec, params, vels, grads, *a, **k: (params, vels))
    elif fault == "no_window":
        orig = attention.attention
        monkeypatch.setattr(attention, "attention",
                            lambda q, k, v, window: orig(q, k, v, None))
    elif fault == "held_renorm":
        orig = moe.held_expert_sum

        def renorm(xn, weights, experts, wg, wu, wd, first, *rest):
            import jax.numpy as jnp
            held = (experts >= first) & (experts < first + wg.shape[0])
            w = jnp.where(held, weights, 0.0)
            w = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-30)
            return orig(xn, w, experts, wg, wu, wd, first, *rest)
        monkeypatch.setattr(moe, "held_expert_sum", renorm)


@pytest.mark.parametrize("fault", ["state unchanged", "no_window",
                                   "held_renorm"])
def test_fault_under_the_timed_path_is_not_correct(decoder_bench,
                                                   monkeypatch, fault):
    bench, base = decoder_bench
    _broken(monkeypatch, fault)
    rc, res = runner.run_cell(cell_args(workload=CELL, seconds=0.2), bench,
                              base=base, require_chip=False)
    assert rc == 0 and res["correct"] is False, (fault, res["checks"])


def test_reference_variants_plant_what_they_say():
    from benchmark.lib import decoder_reference as ref
    assert {"control_fp8", "stated_bf16", "fault_half_tokens",
            "fault_frozen", "fault_no_window",
            "fault_held_renorm"} == set(ref.VARIANTS)


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return [w["name"] for w in json.load(fh)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_a_cell_reads_the_metrics_that_list_it(cell):
    """What ``test_runner.py::test_benchmark_json_names_files_that_exist``
    counted before a metric could list its ``workloads``: every file the
    cell names is there, and its readers are the per-layer metrics
    without such a list and those whose list names the cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    found = runner.find_cell(bench, cell, ROOT)
    assert set(found["limits"]) == {"loss_gap", "grad_norm_gap",
                                    "change_norm_gap", "out_grad_diff",
                                    "rows_misfed"}
    assert set(found["readers"]) == {
        m["name"] for m in bench["per_layer"]
        if cell in m.get("workloads", [cell])}
    for m in bench["per_layer"]:
        for listed in m.get("workloads", []):
            assert listed in _cells(), (m["name"], listed)
