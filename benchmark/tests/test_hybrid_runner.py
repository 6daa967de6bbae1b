"""``tiny-hybrid`` through the real trainer on the CPU: the layer kinds of
``granite-4.0-h-small`` at test widths (two Mamba layers around one
attention layer, 8 experts at 3 a token with 4 held, half of a shared
expert, a tied head) through the same ``run_cell`` as every other cell;
``correct`` true, and false for each fault planted under the timed
path."""

import json
import os
import shutil

import pytest

from benchmark import run as runner
from conftest import ROOT, cell_args

CELL = "tiny-hybrid-train"
REAL_CELL = "granite4h-tp8-train"


@pytest.fixture
def hybrid_bench(tmp_path):
    """A scratch benchmark of the one test-size hybrid cell, beside a copy
    of the real metric readers; the readers that list the real cell read
    this one."""
    here = os.path.dirname(os.path.abspath(__file__))
    shutil.copytree(os.path.join(here, "data"), tmp_path / "bench")
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    tmp_path / "bench" / "metrics")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    bench = dict(
        real, paths=["bench"],
        configs=[{"name": "tiny-hybrid", "source": "test", "reduced": [],
                  "file": "bench/configs/tiny-hybrid.json",
                  "why": "test"}],
        workloads=[{"name": CELL, "config": "tiny-hybrid",
                    "traffic": "tiny-s32-b2", "chips": 1, "why": "test"}])
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL_CELL in m["workloads"] else []
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    return bench, str(tmp_path)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_hybrid_runs_to_a_correct_result(hybrid_bench, trace):
    bench, base = hybrid_bench
    rc, res = runner.run_cell(cell_args(workload=CELL, trace=trace), bench,
                              base=base, require_chip=False)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["window"]["row"]["kind"] == "sequence"
    assert res["attempted"] == res["window"]["epochs"] * 6
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap",
                 "out_grad_diff"):
        c = res["checks"][name]
        # float32 on the CPU; a norm's gain that starts at 1.0 moves by
        # whole spacings of float32, so the change reads a little higher
        assert c["value"] < 0.7 * c["limit"] <= 7e-5, (name, c)
    assert res["checks"]["rows_misfed"] == {"value": 0, "limit": 0}
    if trace:
        m = res["metrics"]
        # half of the experts held: about half of the pairs
        assert 25.0 < m["granite_moe_held_share"]["value"] < 75.0
        # no chip: no share of a peak, never a 0 for one
        assert not set(m) & {"granite_moe_gmm_roofline", "step_mfu",
                             "moe_held_share", "attn_kernel_roofline"}
    else:
        assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}


def _broken(monkeypatch, fault):
    """Break the timed path underneath the runner."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import attention, moe, ssm
    from znicz_tpu.parallel import fused
    if fault == "state unchanged":
        monkeypatch.setattr(
            fused, "apply_updates",
            lambda spec, params, vels, grads, *a, **k: (params, vels))
    elif fault == "boundary decay":
        # the state a chunk hands on comes out of it undecayed
        monkeypatch.setattr(ssm, "chunk_decay",
                            lambda cum: jnp.ones_like(cum[..., -1]))
    elif fault == "no shared expert":
        monkeypatch.setattr(moe, "shared_expert",
                            lambda xn, sg, su, sd, cdt: jnp.zeros_like(xn))
    elif fault == "residual scale one":
        for mod, name in ((ssm, "mamba_block_fwd"),
                          (attention, "attn_block_fwd"),
                          (moe, "moe_block_fwd")):
            monkeypatch.setitem(
                fused.SEQUENCE_FWD, name[:-4],
                lambda leaves, x, cfg, cdt, f=getattr(mod, name): f(
                    leaves, x, {**cfg, "scale": 1.0}, cdt))
    elif fault == "head untied":
        orig = attention.lm_head_fwd
        monkeypatch.setitem(
            fused.SEQUENCE_FWD, "lm_head",
            lambda leaves, x, cfg, cdt: orig(
                (leaves[0], jax.lax.stop_gradient(leaves[1])), x, cfg,
                cdt))
    elif fault == "rotary":
        orig = attention.attn_block_fwd
        rope = tuple(sorted({"rope_type": "default",
                             "rope_theta": 10000.0}.items()))
        monkeypatch.setitem(
            fused.SEQUENCE_FWD, "attn_block",
            lambda leaves, x, cfg, cdt: orig(
                leaves, x, {**cfg, "rope": rope}, cdt))


@pytest.mark.parametrize("fault", ["state unchanged", "boundary decay",
                                   "no shared expert", "residual scale one",
                                   "head untied", "rotary"])
def test_fault_under_the_timed_path_is_not_correct(hybrid_bench,
                                                   monkeypatch, fault):
    bench, base = hybrid_bench
    _broken(monkeypatch, fault)
    rc, res = runner.run_cell(cell_args(workload=CELL, seconds=0.2), bench,
                              base=base, require_chip=False)
    assert rc == 0 and res["correct"] is False, (fault, res["checks"])


def test_reference_variants_plant_what_they_say():
    from benchmark.lib import granite_reference as ref
    assert {"control_fp8", "stated_bf16", "fault_boundary_decay",
            "fault_no_shared", "fault_residual_one", "fault_untied",
            "fault_rotary", "fault_half_tokens",
            "fault_frozen"} == set(ref.VARIANTS)
