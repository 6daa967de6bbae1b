"""``tiny-falcon-h1`` through the real trainer on the CPU: the layer kinds of
``falcon-h1-34b-instruct`` at test widths (four parallel blocks, attention
and a Mamba-2 mixer side by side on one normed input with the model's
multipliers, a dense gated feed-forward with its gate multiplier after
each, an untied head over an eighth of the vocabulary) through the same
``run_cell`` as every other cell (what ``run.py --rehearse`` calls);
``correct`` true, and false for each fault planted under the timed path."""

import json
import os
import shutil

import jax.numpy as jnp
import pytest

from benchmark import run as runner
from conftest import ROOT, cell_args

CELL = "tiny-falcon-h1-train"
REAL_CELL = "falconh1-tp4-train"


@pytest.fixture
def parallel_bench(tmp_path):
    """A scratch benchmark of the one test-size cell, beside a copy of the
    real metric readers; the readers that list the real cell read this
    one."""
    here = os.path.dirname(os.path.abspath(__file__))
    shutil.copytree(os.path.join(here, "data"), tmp_path / "bench")
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    tmp_path / "bench" / "metrics")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    bench = dict(
        real, paths=["bench"],
        configs=[{"name": "tiny-falcon-h1", "source": "test",
                  "reduced": [], "why": "test",
                  "file": "bench/configs/tiny-falcon-h1.json"}],
        workloads=[{"name": CELL, "config": "tiny-falcon-h1",
                    "traffic": "tiny-parallel-s64-b2", "chips": 1,
                    "why": "test"}])
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL_CELL in m["workloads"] else []
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    return bench, str(tmp_path)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_parallel_hybrid_runs_to_a_correct_result(parallel_bench,
                                                      trace, monkeypatch,
                                                      tmp_path):
    from znicz_tpu.telemetry.registry import REGISTRY
    bench, base = parallel_bench
    timeline = tmp_path / "timeline.jsonl"
    monkeypatch.setenv("ZNICZ_TIMELINE_JSONL", str(timeline))
    rc, res = runner.run_cell(cell_args(workload=CELL, trace=trace), bench,
                              base=base, require_chip=False)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["window"]["row"]["kind"] == "sequence"
    assert res["attempted"] == res["window"]["epochs"] * 6
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap",
                 "out_grad_diff"):
        c = res["checks"][name]
        # float32 on the CPU; the two chunked scans and the group norms
        # sum in other orders than the reference's
        assert c["value"] < 0.7 * c["limit"] <= 5e-4, (name, c)
    assert res["checks"]["rows_misfed"] == {"value": 0, "limit": 0}
    rows = [json.loads(line) for line in open(timeline)]
    assert rows and all(r["mixer_routes"] == "parallel:4"
                        and r["parallel_tokens"] == 4 * r["tokens"] > 0
                        for r in rows)
    assert REGISTRY.gauge("train_parallel_tokens").value() \
        == rows[-1]["parallel_tokens"]
    if trace:
        m = res["metrics"]
        listed = {p["name"] for p in bench["per_layer"]
                  if CELL in p.get("workloads", [CELL])}
        assert set(m) <= listed and "falcon_parallel_token_share" in m
        # every layer ran both mixers over every token
        assert m["falcon_parallel_token_share"]["value"] == 100.0
        # no chip: no share of a peak, never a 0 for one; and no reader of
        # another configuration's counters
        assert not set(m) & {"falcon_attn_kernel_roofline", "step_mfu",
                             "olmo_attn_kernel_roofline",
                             "olmo_linear_token_share", "moe_held_share",
                             "granite_moe_held_share"}
    else:
        assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}


def _broken(monkeypatch, fault):
    """Break the timed path underneath the runner."""
    from znicz_tpu.ops import ssm
    from znicz_tpu.parallel import fused

    def with_cfg(kind, inner=None, **items):
        orig = fused.SEQUENCE_FWD[kind]

        def call(leaves, x, cfg, cdt):
            if inner is None:
                return orig(leaves, x, {**cfg, **items}, cdt)
            mixer = {**dict(cfg[inner]), **items}
            return orig(leaves, x, {**cfg, inner: tuple(sorted(
                mixer.items()))}, cdt)
        monkeypatch.setitem(fused.SEQUENCE_FWD, kind, call)
    if fault == "state unchanged":
        monkeypatch.setattr(
            fused, "apply_updates",
            lambda spec, params, vels, grads, *a, **k: (params, vels))
    elif fault == "no key multiplier":
        with_cfg("parallel_block", "attn", score_scale=None)
    elif fault == "no ssm multipliers":
        with_cfg("parallel_block", "ssm", in_scales=None)
    elif fault == "no gate multiplier":
        with_cfg("mlp_block", gate_scale=None)
    elif fault == "no out multipliers":
        with_cfg("parallel_block", attn_scale=None, ssm_scale=None)
    elif fault == "boundary state":
        # the state carried over a chunk, not decayed by it
        monkeypatch.setattr(ssm, "chunk_decay",
                            lambda cum: jnp.ones(cum.shape[:-1], cum.dtype))
    elif fault == "rotary":
        with_cfg("parallel_block", "attn", rope=tuple(sorted(
            {"rope_type": "default", "rope_theta": 10000.0}.items())))


@pytest.mark.parametrize("fault", [
    "state unchanged", "no key multiplier", "no ssm multipliers",
    "no gate multiplier", "no out multipliers", "rotary", "boundary state"])
def test_fault_under_the_timed_path_is_not_correct(parallel_bench,
                                                   monkeypatch, fault):
    bench, base = parallel_bench
    _broken(monkeypatch, fault)
    rc, res = runner.run_cell(cell_args(workload=CELL, seconds=0.2), bench,
                              base=base, require_chip=False)
    assert rc == 0 and res["correct"] is False, (fault, res["checks"])


def test_reference_variants_plant_what_they_say():
    from benchmark.lib import falcon_h1_reference as ref
    assert {"control_fp8", "stated_bf16", "fault_sequential",
            "fault_no_ssm_multipliers", "fault_no_key_multiplier",
            "fault_gate_multiplier_outside", "fault_norm_before_gate",
            "fault_no_out_multipliers", "fault_rotary",
            "fault_boundary_state", "fault_half_tokens",
            "fault_frozen"} == set(ref.VARIANTS)


def test_the_real_cell_reads_exactly_the_metrics_that_list_it():
    """The cell's readers are those without a list and those that list it:
    none of another configuration's."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    found = runner.find_cell(bench, REAL_CELL, ROOT)
    own = {m["name"] for m in bench["per_layer"]
           if REAL_CELL in m.get("workloads", [])}
    assert own == {"falcon_attn_kernel_roofline",
                   "falcon_parallel_token_share"}
    shared = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert set(found["readers"]) == own | shared
    assert found["traffic"]["seq_len"] % found["config"][
        "mamba_chunk_size"] == 0
    assert found["cell"]["chips"] == 1
    # the model file repeats the configuration's count of the cut
    model = runner.import_file(found["files"]["model"])
    assert model.flops(found["config"], found["traffic"])["params"] \
        == 769_637_472
