"""``tiny-olmo-hybrid`` through the real trainer on the CPU: the layer kinds
of ``olmo-hybrid-7b`` at test widths (three gated-delta-rule layers and one
full-attention layer with query/key norms, a dense gated feed-forward after
each, every norm on its sublayer's output, an untied head over an eighth of
the vocabulary) through the same ``run_cell`` as every other cell (what
``run.py --rehearse`` calls); ``correct`` true, and false for each fault
planted under the timed path."""

import json
import os
import shutil

import pytest

from benchmark import run as runner
from conftest import ROOT, cell_args

CELL = "tiny-olmo-hybrid-train"
REAL_CELL = "olmo-hybrid-pp8-train"


@pytest.fixture
def linear_bench(tmp_path):
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
        configs=[{"name": "tiny-olmo-hybrid", "source": "test",
                  "reduced": [], "why": "test",
                  "file": "bench/configs/tiny-olmo-hybrid.json"}],
        workloads=[{"name": CELL, "config": "tiny-olmo-hybrid",
                    "traffic": "tiny-linear-s64-b2", "chips": 1,
                    "why": "test"}])
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL_CELL in m["workloads"] else []
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    return bench, str(tmp_path)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_linear_hybrid_runs_to_a_correct_result(linear_bench, trace):
    bench, base = linear_bench
    rc, res = runner.run_cell(cell_args(workload=CELL, trace=trace), bench,
                              base=base, require_chip=False)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["window"]["row"]["kind"] == "sequence"
    assert res["attempted"] == res["window"]["epochs"] * 6
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap",
                 "out_grad_diff"):
        c = res["checks"][name]
        # float32 on the CPU; the chunked form's triangular system and a
        # norm's gain that moves by whole spacings of float32 read a
        # little higher than a product's rounding
        assert c["value"] < 0.7 * c["limit"] <= 5e-4, (name, c)
    assert res["checks"]["rows_misfed"] == {"value": 0, "limit": 0}
    if trace:
        m = res["metrics"]
        listed = {p["name"] for p in bench["per_layer"]
                  if CELL in p.get("workloads", [CELL])}
        assert set(m) <= listed and "olmo_linear_token_share" in m
        # three of the four layers scanned every token
        assert m["olmo_linear_token_share"]["value"] == 75.0
        # no chip: no share of a peak, never a 0 for one; and no reader of
        # another configuration's counters
        assert not set(m) & {"olmo_attn_kernel_roofline", "step_mfu",
                             "attn_kernel_roofline", "moe_held_share",
                             "granite_moe_held_share"}
    else:
        assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}


def _broken(monkeypatch, fault):
    """Break the timed path underneath the runner."""
    import jax.numpy as jnp
    from znicz_tpu.ops import attention, gdn
    from znicz_tpu.parallel import fused

    def with_cfg(kind, **items):
        orig = fused.SEQUENCE_FWD[kind]
        monkeypatch.setitem(
            fused.SEQUENCE_FWD, kind,
            lambda leaves, x, cfg, cdt: orig(leaves, x, {**cfg, **items},
                                             cdt))
    if fault == "state unchanged":
        monkeypatch.setattr(
            fused, "apply_updates",
            lambda spec, params, vels, grads, *a, **k: (params, vels))
    elif fault == "no decay":
        orig = gdn.delta_rule
        monkeypatch.setattr(
            gdn, "delta_rule",
            lambda q, k, v, g, beta, *a: orig(q, k, v, jnp.zeros_like(g),
                                              beta, *a))
    elif fault == "beta one":
        orig = gdn.delta_rule
        monkeypatch.setattr(
            gdn, "delta_rule",
            lambda q, k, v, g, beta, *a: orig(q, k, v, g, 0.5 * beta, *a))
    elif fault == "no l2norm":
        monkeypatch.setattr(gdn, "l2_norm", lambda x: x)
    elif fault == "boundary state":
        # the state a chunk hands on is lost
        monkeypatch.setattr(gdn, "carried",
                            lambda state, whole: jnp.zeros_like(state))
    elif fault == "input norm":
        for kind in ("gdn_block", "attn_block", "mlp_block"):
            with_cfg(kind, norm="pre")
    elif fault == "no qk norm":
        orig = attention.attn_block_fwd
        monkeypatch.setitem(
            fused.SEQUENCE_FWD, "attn_block",
            lambda leaves, x, cfg, cdt: orig(
                leaves[:5], x, {**cfg, "qk_norm": False}, cdt))
    elif fault == "rotary":
        with_cfg("attn_block", rope=tuple(sorted(
            {"rope_type": "default", "rope_theta": 10000.0}.items())))


@pytest.mark.parametrize("fault", [
    "state unchanged", "no decay", "beta one", "no l2norm",
    "boundary state", "input norm", "no qk norm", "rotary"])
def test_fault_under_the_timed_path_is_not_correct(linear_bench,
                                                   monkeypatch, fault):
    bench, base = linear_bench
    _broken(monkeypatch, fault)
    rc, res = runner.run_cell(cell_args(workload=CELL, seconds=0.2), bench,
                              base=base, require_chip=False)
    assert rc == 0 and res["correct"] is False, (fault, res["checks"])


def test_reference_variants_plant_what_they_say():
    from benchmark.lib import olmo_hybrid_reference as ref
    assert {"control_fp8", "stated_bf16", "fault_no_decay",
            "fault_beta_one", "fault_no_l2norm", "fault_gate_first",
            "fault_boundary_state", "fault_input_norm", "fault_no_qk_norm",
            "fault_rotary", "fault_half_tokens",
            "fault_frozen"} == set(ref.VARIANTS)


def test_the_real_cell_reads_exactly_the_metrics_that_list_it():
    """The cell's readers are those without a list and those that list it:
    none of another configuration's."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    found = runner.find_cell(bench, REAL_CELL, ROOT)
    own = {m["name"] for m in bench["per_layer"]
           if REAL_CELL in m.get("workloads", [])}
    assert own == {"olmo_attn_kernel_roofline", "olmo_linear_token_share"}
    shared = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert set(found["readers"]) == own | shared
    assert found["traffic"]["seq_len"] % found["config"]["assumed"][
        "chunk"] == 0
    assert found["cell"]["chips"] == 1
