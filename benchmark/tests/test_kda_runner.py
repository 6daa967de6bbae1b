"""``tiny-solar-open2`` through the real trainer on the CPU: the layer kinds of
``solar-open2-250b`` at test widths (one gated NoPE attention layer and
three Kimi-delta-attention layers, a decay a key channel, each followed by
an expert layer of 8 held experts of 32 beside a quarter of the shared
expert's columns, an untied head over an eighth of the vocabulary) through
the same ``run_cell`` as every other cell (what ``run.py --rehearse``
calls); ``correct`` true, and false for each fault planted under the timed
path."""

import json
import os
import shutil

import jax.numpy as jnp
import pytest

from benchmark import run as runner
from conftest import ROOT, cell_args

CELL = "tiny-solar-open2-train"
REAL_CELL = "solar2-tp4ep40-train"


@pytest.fixture
def kda_bench(tmp_path):
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
        configs=[{"name": "tiny-solar-open2", "source": "test",
                  "reduced": [], "why": "test",
                  "file": "bench/configs/tiny-solar-open2.json"}],
        workloads=[{"name": CELL, "config": "tiny-solar-open2",
                    "traffic": "tiny-kda-s128-b2", "chips": 1,
                    "why": "test"}])
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL_CELL in m["workloads"] else []
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    return bench, str(tmp_path)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_kda_hybrid_runs_to_a_correct_result(kda_bench, trace,
                                                 monkeypatch, tmp_path):
    from znicz_tpu.telemetry.registry import REGISTRY
    bench, base = kda_bench
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
        # float32 on the CPU; the chunked rule and the sorted experts sum
        # in other orders than the reference's recurrence and masks
        assert c["value"] < 0.7 * c["limit"] <= 5e-4, (name, c)
    assert res["checks"]["rows_misfed"] == {"value": 0, "limit": 0}
    rows = [json.loads(line) for line in open(timeline)]
    assert rows and all(r["mixer_routes"] == "kda:3 full:1"
                        and r["rule_routes"] == "kda:chunked"
                        and r["kda_tokens"] == 3 * r["tokens"] > 0
                        for r in rows)
    assert REGISTRY.gauge("train_kda_tokens").value() \
        == rows[-1]["kda_tokens"]
    if trace:
        m = res["metrics"]
        listed = {p["name"] for p in bench["per_layer"]
                  if CELL in p.get("workloads", [CELL])}
        assert set(m) <= listed and "solar_kda_token_share" in m
        # three of the four layers scanned every token
        assert m["solar_kda_token_share"]["value"] == 75.0
        # no chip: no share of a peak; and no reader of another
        # configuration's counters
        assert not set(m) & {"step_mfu", "olmo_linear_token_share",
                             "falcon_parallel_token_share",
                             "moe_held_share", "granite_moe_held_share"}
    else:
        assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}


def _broken(monkeypatch, fault):
    """Break the timed path underneath the runner."""
    from znicz_tpu.ops import gdn
    from znicz_tpu.parallel import fused
    if fault == "state unchanged":
        monkeypatch.setattr(
            fused, "apply_updates",
            lambda spec, params, vels, grads, *a, **k: (params, vels))
    elif fault == "head-mean decay":
        rule = gdn.kda_rule

        def averaged(q, k, v, g, beta, chunk, cdt=jnp.float32):
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
            return rule(q, k, v, g, beta, chunk, cdt)
        monkeypatch.setattr(gdn, "kda_rule", averaged)
    elif fault == "boundary state":
        # the state carried over a chunk, not decayed by it
        monkeypatch.setattr(gdn, "carried", lambda state, whole: state)
    elif fault == "no output gate":
        orig = fused.SEQUENCE_FWD["kda_block"]

        def ungated(leaves, x, cfg, cdt):
            bg = leaves[9]
            return orig((*leaves[:9], jnp.full_like(bg, 30.0),
                         *leaves[10:]), x, cfg, cdt)
        monkeypatch.setitem(fused.SEQUENCE_FWD, "kda_block", ungated)


@pytest.mark.parametrize("fault", [
    "state unchanged", "head-mean decay", "boundary state",
    "no output gate"])
def test_fault_under_the_timed_path_is_not_correct(kda_bench, monkeypatch,
                                                   fault):
    bench, base = kda_bench
    _broken(monkeypatch, fault)
    rc, res = runner.run_cell(cell_args(workload=CELL, seconds=0.2), bench,
                              base=base, require_chip=False)
    assert rc == 0 and res["correct"] is False, (fault, res["checks"])


def test_reference_variants_plant_what_they_say():
    from benchmark.lib import solar_open2_reference as ref
    assert {"control_fp8", "stated_bf16", "fault_head_mean_decay",
            "fault_beta_one", "fault_no_kda_gate", "fault_no_attn_gate",
            "fault_no_shared", "fault_half_tokens",
            "fault_frozen"} == set(ref.VARIANTS)


def test_the_real_cell_reads_exactly_the_metrics_that_list_it():
    """The cell's readers are those without a list and those that list it:
    none of another configuration's."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    found = runner.find_cell(bench, REAL_CELL, ROOT)
    own = {m["name"] for m in bench["per_layer"]
           if REAL_CELL in m.get("workloads", [])}
    assert own == {"solar_kda_token_share"}
    shared = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert set(found["readers"]) == own | shared
    assert found["traffic"]["seq_len"] % found["config"]["assumed"][
        "chunk"] == 0
    assert found["cell"]["chips"] == 1
    # the model file repeats the count of the cut, leaf by leaf
    model = runner.import_file(found["files"]["model"])
    assert model.flops(found["config"], found["traffic"])["params"] \
        == 858_579_376
