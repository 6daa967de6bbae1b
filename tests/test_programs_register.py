"""The trainer's register of executables (ISSUE 38): one entry a build
with its role, shapes and memory plan; the plan under a gauge, in the
``compile`` and ``trainer.dispatch`` spans and in four fields of the
``train_step`` row; how the crowding rule decided in the start record;
an ``error`` record where the runtime refuses a launch; no compiled text
rendered and no profile read in a run that asked for neither."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.ops import tuning
from znicz_tpu.parallel import FusedTrainer, extract_model, fused
from znicz_tpu.telemetry import (compilestats, flightrecorder, programs,
                                 scopes, tracing)
from znicz_tpu.telemetry.registry import REGISTRY

import test_train_tracing as tt


def _scan(p, x):
    return jax.lax.scan(lambda c, xs: (c + xs.sum(), xs.mean()), p, x)


# -- the register -------------------------------------------------------------
@pytest.mark.parametrize("calls, entries", [
    ([(3, 4)], 1),                       # a build
    ([(3, 4), (3, 4), (3, 4)], 1),       # calls that built nothing
    ([(3, 4), (1, 4), (3, 4)], 2),       # a second shape, once
])
def test_one_entry_a_build_and_none_for_a_call_that_built_nothing(
        calls, entries):
    programs.clear()
    tracing.clear()
    fn = compilestats.build_timed(jax.jit(_scan), "test.site", "cold")
    for shape in calls:
        carry, means = fn(jnp.float32(0), np.ones(shape, np.float32),
                          role="train.head" if shape[0] > 1
                          else "train.step")
        assert float(carry) == shape[0] * shape[1]
    found = programs.entries(site="test.site")
    assert len(found) == entries
    assert len(tracing.recent_spans(name="compile")) == entries
    first = found[0]
    assert (first.role, first.name) == ("train.head", "jit__scan")
    assert first.shapes == ("f32[]", "f32[3,4]")
    assert fn.last is (found[-1] if calls[-1] == (1, 4) else first)
    if entries == 2:
        assert (found[1].role, found[1].shapes) == (
            "train.step", ("f32[]", "f32[1,4]"))


def test_an_entry_describes_a_tree_by_its_leaves_and_bytes():
    tree = [(jnp.ones((4, 4)), None), (jnp.ones(3, jnp.bfloat16),)]
    assert programs.shapes_of((tree, np.int32(1))) == (
        "2 leaves 70 B", "s32[]")


def test_the_compile_span_and_the_gauge_carry_the_plan():
    programs.clear()
    tracing.clear()
    fn = compilestats.build_timed(jax.jit(_scan), "test.site", "cold")
    fn(jnp.float32(0), np.ones((5, 2), np.float32), role="eval.test")
    entry, = programs.entries(site="test.site")
    assert entry.plan and set(entry.plan) <= set(programs.PLAN_PARTS)
    span = tracing.recent_spans(name="compile")[-1]
    assert span.attrs["role"] == "eval.test"
    for part, value in entry.plan.items():
        assert span.attrs[f"plan_{part}_bytes"] == value
        assert REGISTRY.gauge("train_program_plan_bytes").value(
            role="eval.test", part=part) == value


class _NoPlan:
    def __init__(self, stats):
        self.stats = stats

    def memory_analysis(self):
        if isinstance(self.stats, Exception):
            raise self.stats
        return self.stats


@pytest.mark.parametrize("stats, plan", [
    (None, {}),
    (NotImplementedError("no analysis on this runtime"), {}),
    (types.SimpleNamespace(temp_size_in_bytes=7), {"temp": 7}),
    (types.SimpleNamespace(temp_size_in_bytes=0, alias_size_in_bytes=3),
     {"temp": 0, "alias": 3}),           # a 0 the runtime GAVE is a 0
])
def test_plan_parts_are_absent_never_0_where_the_runtime_gives_none(
        stats, plan):
    programs.clear()
    compiled = _NoPlan(stats)
    entry = programs.register("test.site", "role.without.a.plan",
                              "jit_f", compiled, (np.ones(2),))
    assert entry.plan == plan
    gauge = REGISTRY.gauge("train_program_plan_bytes")
    for part in set(programs.PLAN_PARTS) - set(plan):
        assert all(dict(labels).get("part") != part
                   or dict(labels).get("role") != "role.without.a.plan"
                   for labels, _ in gauge.samples())


def test_the_register_is_bounded_and_holds_executables_weakly():
    programs.clear()
    compiled = jax.jit(lambda x: x + 1).lower(np.ones(2)).compile()
    entry = programs.register("test.site", None, "jit_f", compiled,
                              (np.ones(2),))
    assert "HloModule" in entry.text()
    assert entry.to_dict()["text_at_hand"]
    del compiled
    assert entry.text() is None and not entry.to_dict()["text_at_hand"]
    for _ in range(programs.CAPACITY + 5):
        programs.register("test.site", None, "jit_f", _NoPlan(None), ())
    assert len(programs.entries()) == programs.CAPACITY
    json.dumps(programs.snapshot())


# -- the row's four fields ------------------------------------------------------
def _span(name, ms, **attrs):
    sp = tracing.Span(name, attrs)
    sp.duration_ms = ms
    return sp


@pytest.mark.parametrize("launches, memory", [
    # a plan and a reading: every field
    ([{"plan_temp_bytes": 300, "bytes_in_use": 1000, "bytes_limit": 5000},
      {"plan_temp_bytes": 900},
      {"plan_temp_bytes": 100, "bytes_in_use": 1400, "bytes_limit": 5000}],
     {"plan_temp_bytes_max": 900, "hbm_in_use_bytes": 1400,
      "launch_need_bytes": 1500, "hbm_limit_bytes": 5000}),
    # a plan and a device that does not say what it holds (the CPU)
    ([{"plan_temp_bytes": 300}, {"plan_temp_bytes": 200}],
     {"plan_temp_bytes_max": 300}),
    # a reading without a plan
    ([{"bytes_in_use": 1000, "bytes_limit": 5000}],
     {"hbm_in_use_bytes": 1000, "hbm_limit_bytes": 5000}),
    # neither (an older trainer, the streamed one)
    ([{}, {}], {}),
])
def test_train_breakdown_fills_the_memory_fields_from_span_attributes(
        launches, memory):
    spans = [_span("train.head", 10.0), _span("trainer.prep", 1.0)] + [
        _span("trainer.dispatch", 2.0, role="train.step", **attrs)
        for attrs in launches]
    row = flightrecorder.train_breakdown(spans)
    assert row["launches"] == len(launches)
    fields = ("plan_temp_bytes_max", "hbm_in_use_bytes",
              "launch_need_bytes", "hbm_limit_bytes")
    assert {k: row[k] for k in fields if k in row} == memory


# -- the trainer's launches -----------------------------------------------------
class _Device:
    def __init__(self, in_use):
        self.in_use, self.reads = in_use, 0

    def memory_stats(self):
        self.reads += 1
        return {"bytes_in_use": self.in_use, "bytes_limit": 16_000,
                "peak_bytes_in_use": 99_999}


@pytest.fixture(scope="module")
def small_trainer():
    wf = tt._workflow()
    spec, params, vels = extract_model(wf)
    trainer = FusedTrainer(spec=spec, params=params, vels=vels)
    ld = wf.loader
    return trainer, ld.original_data.devmem, ld.original_labels.devmem


def test_a_launch_reads_the_device_once_an_epoch_and_role(small_trainer):
    trainer, data, labels = small_trainer
    trainer._devices = [_Device(1000), _Device(3000)]
    programs.clear()
    dispatches = []
    for _ in range(2):                      # two epochs, two request ids
        with tracing.request() as rid, tracing.collect(rid) as spans:
            for role in ("eval.validation", "eval.validation", "eval.test"):
                trainer.eval_epoch(data, labels, np.arange(tt.BATCH),
                                   tt.BATCH, role=role)
        dispatches.append([s for s in spans
                           if s.name == "trainer.dispatch"])
    plan, = {e.plan["temp"] for e in programs.entries(site="train.fused")}
    for epoch in dispatches:
        assert [s.attrs["role"] for s in epoch] == [
            "eval.validation", "eval.validation", "eval.test"]
        assert [s.attrs.get("bytes_in_use") for s in epoch] == [
            3000, None, 3000]               # the fullest device, once
        assert [s.attrs.get("bytes_limit") for s in epoch] == [
            16_000, None, 16_000]
        assert all(s.attrs["plan_temp_bytes"] == plan for s in epoch)
        row = flightrecorder.train_breakdown(epoch)
        assert row["launch_need_bytes"] == 3000 + plan
    assert [d.reads for d in trainer._devices] == [4, 4]
    # one executable, entered once, under the role that built it
    entry, = programs.entries(site="train.fused")
    assert (entry.role, entry.name) == ("eval.validation", "jit_eval_epoch")


def test_a_refused_launch_leaves_an_error_record_and_raises_unchanged(
        small_trainer):
    trainer = small_trainer[0]
    trainer._devices = [_Device(15_000)]
    refusal = RuntimeError(
        "RESOURCE_EXHAUSTED: Error loading program: Attempting to reserve "
        "5.44G at the bottom of memory. There are 3.36G free")

    def fn(*args, role=None):
        raise refusal
    fn.last = types.SimpleNamespace(plan={"temp": 5_440, "argument": 12})
    flightrecorder.RECORDER.clear()
    with pytest.raises(RuntimeError) as caught:
        with trainer._dispatch(fn, "train.step"):
            fn(1, 2, role="train.step")
    assert caught.value is refusal
    record, = flightrecorder.RECORDER.snapshot()["errors"]
    assert record["kind"] == "error" and record["role"] == "train.step"
    assert record["plan"] == {"temp": 5_440, "argument": 12}
    assert (record["bytes_in_use"], record["bytes_limit"]) == (15_000,
                                                               16_000)
    assert "Attempting to reserve 5.44G" in record["error"]
    # any other failure is the caller's alone
    other = ValueError("shapes do not match")

    def broken(*args, role=None):
        raise other
    with pytest.raises(ValueError):
        with trainer._dispatch(broken, "train.step"):
            broken()
    assert len(flightrecorder.RECORDER.snapshot()["errors"]) == 1


# -- the start record -----------------------------------------------------------
@pytest.mark.parametrize("room, crowded", [
    (None, False),         # the device does not say (the CPU)
    (5, False),            # 3 x leaves = 5/8 x 4.8 x leaves: not over
    (4, True),
])
def test_the_start_record_says_how_the_crowding_rule_decided(
        monkeypatch, room, crowded):
    import test_hybrid_lm as hybrid
    _, spec, weights, _, _ = hybrid.setup()
    held = sum(a.size * 4 for a in jax.tree.leaves(weights))
    monkeypatch.setattr(tuning, "device_memory_bytes",
                        lambda: None if room is None else room * held)
    said = fused.crowding(spec, weights)
    assert said["crowded"] is crowded
    assert said["state_bytes"] == 3 * held
    if room is None:
        assert "crowd_limit_bytes" not in said      # absent, never 0
    else:
        assert said["crowd_limit_bytes"] == 5 * room * held // 8
    assert fused.state_crowds_device(spec, weights) is crowded
    trainer = FusedTrainer(spec=spec, params=weights,
                           vels=jax.tree.map(jnp.zeros_like, weights))
    assert trainer.crowding == said and trainer.crowded is crowded
    # a mesh or an accumulation keeps the scan whatever the rule says,
    # and the record says what the trainer does
    accum = FusedTrainer(spec=spec, params=weights,
                         vels=jax.tree.map(jnp.zeros_like, weights),
                         accum_steps=2)
    assert accum.crowding["crowded"] is False
    assert accum.crowding["state_bytes"] == 3 * held


# -- a whole run ------------------------------------------------------------------
def test_a_run_registers_every_executable_once_and_renders_no_text(
        monkeypatch, tmp_path):
    """Through ``train(fused=True)``: each executable of the run under a
    role, the row's plan, the start record's three fields, and neither a
    compiled text rendered nor a capture read, which nobody asked for."""
    rendered, read = [], []
    monkeypatch.setattr(programs.Program, "text",
                        lambda self: rendered.append(self.name))
    monkeypatch.setattr(scopes, "read_capture",
                        lambda path: read.append(path))
    monkeypatch.setattr(scopes, "profile_record",
                        lambda *a, **kw: read.append(a))
    programs.clear()
    wf = tt._workflow()
    path = tmp_path / "rows.jsonl"
    wf.train(fused=True, max_epochs=2, timeline_jsonl=str(path))
    assert rendered == [] and read == []
    found = programs.entries(site="train.fused")
    assert sorted((e.role, e.name) for e in found) == [
        ("eval.train", "jit_eval_epoch"),
        ("eval.validation", "jit_eval_epoch"),
        ("train.head", "jit_train_epoch"),
        ("train.step", "jit_train_epoch")]
    assert all(e.plan.get("temp", 0) > 0 for e in found)
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    largest = max(e.plan["temp"] for e in found if e.role != "train.step")
    assert rows[0]["plan_temp_bytes_max"] == largest
    assert rows[1]["plan_temp_bytes_max"] == max(
        e.plan["temp"] for e in found)
    for row in rows:
        assert row["crowded"] is False and row["state_bytes"] > 0
        # the CPU does not say what it holds: absent, never 0
        for field in ("crowd_limit_bytes", "launch_need_bytes",
                      "hbm_in_use_bytes", "hbm_limit_bytes"):
            assert field not in row
