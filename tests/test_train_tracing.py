"""The fused trainer names itself (ISSUE 26): a ``jax.named_scope`` per
layer and phase in the compiled step, ``name=`` on every Pallas call,
the epoch loop's span tree with the ``train_step`` row cut from it, the
same spans as annotations in a profiler capture, and every executable
build counted."""

import ast
import glob
import os
import re

import numpy as np
import pytest

from znicz_tpu import prng
from znicz_tpu.backends import Device
from znicz_tpu.config import root
from znicz_tpu.models import cifar
from znicz_tpu.parallel import FusedTrainer, extract_model, fused
from znicz_tpu.telemetry import compilestats, flightrecorder, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: conv → LRN → pool (merged by extract_model) → dropout → fc → softmax
LAYERS = [
    {"type": "conv_str", "->": {"n_kernels": 8, "kx": 5, "sliding": 2},
     "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
    {"type": "norm", "->": {"n": 5}},
    {"type": "max_pooling", "->": {"kx": 3, "sliding": 2}},
    {"type": "dropout", "->": {"dropout_ratio": 0.3}},
    {"type": "all2all_tanh", "->": {"output_sample_shape": 24},
     "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 10},
     "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
]
#: the spec rows' labels: unit index of the first forward unit + kind
LABELS = ["L00.conv", "L01.lrn_pool", "L03.dropout", "L04.fc", "L05.fc"]
BATCH, N_TRAIN, N_VALID = 40, 200, 80      # head (4, b), tail (1, b)


def _workflow():
    saved = root.cifar.synthetic.to_dict()
    saved_batch = root.cifar.minibatch_size
    root.cifar.synthetic.update({"n_train": N_TRAIN, "n_valid": N_VALID,
                                 "n_test": 0, "noise": 0.3, "size": 16})
    root.cifar.minibatch_size = BATCH
    try:
        prng.seed_all(1234)
        wf = cifar.CifarWorkflow(layers=LAYERS)
        wf.initialize(device=Device.create("xla"))
    finally:
        root.cifar.synthetic.update(saved)
        root.cifar.minibatch_size = saved_batch
    return wf


# -- device-side names -------------------------------------------------------
@pytest.fixture(scope="module")
def program_texts():
    """The lowered text of both epoch programs (locations carry the name
    stack) and the compiled text of the training one (``op_name``)."""
    wf = _workflow()
    spec, params, vels = extract_model(wf)
    assert [fused.layer_label(spec, i)
            for i in range(len(spec.layers))] == LABELS
    tr = FusedTrainer(spec=spec, params=params, vels=vels)
    tr._build()
    ld = wf.loader
    data, target = ld.original_data.devmem, ld.original_labels.devmem
    idx, mask, ctrs = tr._idx_matrix(np.arange(2 * BATCH), BATCH)
    scales = np.ones(2, np.float32)
    lowered = tr._train_epoch_fn.fn.lower(
        tr.params, tr.vels, data, target, idx, mask, ctrs, np.uint32(0),
        scales, scales)
    return {
        "train": lowered.as_text(debug_info=True),
        "train_compiled": lowered.compile().as_text(),
        "eval": tr._eval_epoch_fn.fn.lower(
            tr.params, data, target, idx, mask).as_text(debug_info=True)}


TRAIN_SCOPES = (["input", "loss"]
                + [f"fwd/{la}" for la in LABELS]
                + [f"bwd/{la}" for la in LABELS]
                + [f"upd/{la}" for la in LABELS
                   if la.endswith((".conv", ".fc"))])
#: dropout is the identity in evaluation: it traces no operation
EVAL_SCOPES = ["input", "loss"] + [f"fwd/{la}" for la in LABELS
                                   if not la.endswith(".dropout")]


def _names(text: str, scope: str) -> bool:
    """An operation's location or ``op_name`` holds the scope, first (the
    scan body is a function of its own in the lowered text) or after the
    enclosing names."""
    return re.search(r'["/]' + re.escape(scope) + "/", text) is not None


@pytest.mark.parametrize("scope", TRAIN_SCOPES)
def test_train_epoch_names_every_scope(program_texts, scope):
    assert _names(program_texts["train"], scope)


@pytest.mark.parametrize("scope", EVAL_SCOPES)
def test_eval_epoch_names_every_scope(program_texts, scope):
    assert _names(program_texts["eval"], scope)


@pytest.mark.parametrize("scope", ["input", "loss", "fwd/L00.conv",
                                   "bwd/L01.lrn_pool", "upd/L05.fc"])
def test_compiled_text_keeps_the_scopes(program_texts, scope):
    """What a device trace is joined to: ``op_name`` in the metadata of
    the compiled HLO's instructions."""
    assert re.search(r'op_name="jit\(train_epoch\)/[^"]*/'
                     + re.escape(scope) + "/",
                     program_texts["train_compiled"])


def test_accumulation_branch_is_scoped():
    wf = _workflow()
    spec, params, vels = extract_model(wf)
    tr = FusedTrainer(spec=spec, params=params, vels=vels, accum_steps=2)
    tr._build()
    ld = wf.loader
    idx, mask, ctrs = tr._idx_matrix(np.arange(2 * BATCH), BATCH)
    scales = np.ones(2, np.float32)
    text = tr._train_epoch_fn.fn.lower(
        tr.params, tr.vels, ld.original_data.devmem,
        ld.original_labels.devmem, idx, mask, ctrs, np.uint32(0), scales,
        scales).as_text(debug_info=True)
    assert _names(text, "accum")
    assert _names(text, "upd/L05.fc")


OPS_FILES = sorted(
    os.path.relpath(p, REPO) for p in glob.glob(
        os.path.join(REPO, "znicz_tpu", "ops", "*.py"))
    if "pallas_call(" in open(p, encoding="utf-8").read())


def test_ops_files_with_kernels_were_found():
    assert len(OPS_FILES) >= 6


@pytest.mark.parametrize("path", OPS_FILES)
def test_every_pallas_call_is_named(path):
    with open(os.path.join(REPO, path), encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    calls = [node for node in ast.walk(tree)
             if isinstance(node, ast.Call)
             and isinstance(node.func, ast.Attribute)
             and node.func.attr == "pallas_call"]
    assert calls
    unnamed = [c.lineno for c in calls
               if not any(kw.arg == "name" for kw in c.keywords)]
    assert not unnamed, f"{path}: pallas_call without name= at {unnamed}"


# -- the epoch loop's spans and the row cut from them -------------------------
@pytest.fixture(scope="module")
def fused_run(tmp_path_factory):
    """Two epochs through ``train(fused=True)`` under a profiler capture,
    with every trainer call counted from outside."""
    from jax.profiler import ProfileData
    wf = _workflow()
    calls = []
    orig_train, orig_eval = FusedTrainer.train_epoch, FusedTrainer.eval_epoch

    def train_epoch(self, *a, **kw):
        calls.append("train")
        return orig_train(self, *a, **kw)

    def eval_epoch(self, *a, **kw):
        calls.append("eval")
        return orig_eval(self, *a, **kw)

    tracing.clear()
    flightrecorder.RECORDER.clear()
    profile_dir = str(tmp_path_factory.mktemp("profile"))
    FusedTrainer.train_epoch, FusedTrainer.eval_epoch = (train_epoch,
                                                         eval_epoch)
    try:
        wf.train(fused=True, max_epochs=2, profile_dir=profile_dir)
    finally:
        FusedTrainer.train_epoch, FusedTrainer.eval_epoch = (orig_train,
                                                             orig_eval)
    rows = [r for r in flightrecorder.RECORDER.snapshot()["recent"]
            if r["kind"] == "train_step"]
    host_events = set()
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events.update(ev.name for ev in line.events)
    return {"rows": rows, "calls": calls, "spans": tracing.recent_spans(),
            "host_events": host_events}


def test_rows_count_the_launches_made(fused_run):
    rows, calls = fused_run["rows"], fused_run["calls"]
    assert len(rows) == 2
    # epoch 0: head, tail evaluation, validation; epoch 1: the deferred
    # tail update first
    assert [r["launches"] for r in rows] == [3, 4]
    assert sum(r["launches"] for r in rows) == len(calls) == 7


@pytest.mark.parametrize("epoch", [0, 1])
def test_row_times_add_up(fused_run, epoch):
    r = fused_run["rows"][epoch]
    inside = r["prep_ms"] + r["dispatch_ms"] + r["readback_ms"]
    assert 0 < inside <= r["device_ms"] + 1e-2
    assert r["device_ms"] + r["host_ms"] == pytest.approx(r["wall_ms"],
                                                          abs=1e-2)
    assert 0 < r["host_ms"] < r["wall_ms"]


def test_rows_carry_compiles_and_the_tail_before(fused_run):
    first, second = fused_run["rows"]
    # the head, the one-step evaluation, the two-step validation
    assert first["compiles"] >= 3
    assert second["compiles"] >= 1          # the deferred tail's (1, b)
    assert first["prev_tail_ms"] is None
    assert second["prev_tail_ms"] > 0
    compile_spans = [s for s in fused_run["spans"] if s.name == "compile"]
    assert len(compile_spans) == first["compiles"] + second["compiles"]


def _tree(spans):
    by_id = {s.span_id: s for s in spans}
    return by_id, [s for s in spans if s.name == "train.epoch"]


def test_span_tree_children_name_their_parent(fused_run):
    by_id, epochs = _tree(fused_run["spans"])
    assert [e.attrs["epoch"] for e in epochs] == [0, 1]
    assert all(e.parent_id is None for e in epochs)
    for name, parent in (("train.head", "train.epoch"),
                         ("train.eval_tail", "train.epoch"),
                         ("train.eval.validation", "train.epoch"),
                         ("train.tail_update", "train.epoch"),
                         ("train.decision", "train.epoch"),
                         ("trainer.prep", None),
                         ("trainer.dispatch", None),
                         ("trainer.readback", None),
                         ("compile", "trainer.dispatch")):
        found = [s for s in fused_run["spans"] if s.name == name]
        assert found, name
        for s in found:
            up = by_id[s.parent_id]
            if parent is None:      # the trainer's: under a device call
                assert up.name in flightrecorder._TRAIN_CALL_SPANS
            else:
                assert up.name == parent, (name, up.name)
    # the deferred tail update is queued, not read back
    tail = next(s for s in fused_run["spans"]
                if s.name == "train.tail_update")
    under = [s.name for s in fused_run["spans"]
             if s.parent_id == tail.span_id]
    assert under == ["trainer.prep", "trainer.dispatch"]


def test_spans_share_their_epochs_request_id(fused_run):
    by_id, epochs = _tree(fused_run["spans"])
    ids = [e.request_ids for e in epochs]
    assert all(len(i) == 1 for i in ids) and ids[0] != ids[1]
    for s in fused_run["spans"]:
        top = s
        while top.parent_id is not None:
            top = by_id[top.parent_id]
        assert top.name == "train.epoch"
        assert s.request_ids == top.request_ids


@pytest.mark.parametrize("name", [
    "train.epoch", "train.head", "train.eval_tail",
    "train.eval.validation", "train.tail_update", "train.decision",
    "trainer.prep", "trainer.dispatch", "trainer.readback"])
def test_profiler_capture_holds_the_spans(fused_run, name):
    """The spans are ``TraceAnnotation``s: a capture taken while the
    loop runs has them in its host plane."""
    assert name in fused_run["host_events"]


# -- every build counted --------------------------------------------------------
def _fc_trainer():
    gen = np.random.default_rng(0)
    hyp = (0.1, 0.0, 0.0, 0.9)
    spec = fused.ModelSpec(layers=(
        fused.LayerSpec("fc", "tanh", True, hyp, hyp),
        fused.LayerSpec("fc", "linear", True, hyp, hyp)), loss="softmax")
    params = [(gen.standard_normal((64, 32)).astype(np.float32),
               np.zeros(32, np.float32)),
              (gen.standard_normal((32, 10)).astype(np.float32),
               np.zeros(10, np.float32))]
    vels = [tuple(np.zeros_like(a) for a in p) for p in params]
    data = gen.standard_normal((64, 64)).astype(np.float32)
    labels = gen.integers(0, 10, 64).astype(np.int32)
    return FusedTrainer(spec=spec, params=params, vels=vels), data, labels


def _fused_compiles() -> int:
    return compilestats.snapshot()["compiles"].get(
        "train.fused", {}).get("cold", 0)


def test_every_executable_is_counted():
    """Head ``(3, b)``, tail ``(1, b)`` and two evaluation shapes are four
    builds; the same shapes again are none."""
    tr, data, labels = _fc_trainer()
    tracing.clear()
    before = _fused_compiles()

    def epoch():
        tr.train_epoch(data, labels, np.arange(16), 16, sync=False)
        tr.train_epoch(data, labels, np.arange(48), 16)
        tr.eval_epoch(data, labels, np.arange(16), 16)
        tr.eval_epoch(data, labels, np.arange(32), 16)

    epoch()
    assert _fused_compiles() - before == 4
    spans = tracing.recent_spans(name="compile")
    assert len(spans) == 4
    assert {s.attrs["site"] for s in spans} == {"train.fused"}
    dispatches = {s.span_id for s in
                  tracing.recent_spans(name="trainer.dispatch")}
    assert all(s.parent_id in dispatches for s in spans)
    epoch()
    assert _fused_compiles() - before == 4
    assert len(tracing.recent_spans(name="compile")) == 4


@pytest.mark.parametrize("cause", ["", "warm"])
def test_build_timed_refuses_an_unknown_cause(cause):
    with pytest.raises(ValueError):
        compilestats.build_timed(lambda: None, "test.site", cause)


# -- the row's reduction ----------------------------------------------------------
def _span(name, ms):
    sp = tracing.Span(name, {})
    sp.duration_ms = ms
    return sp


def test_train_breakdown_sums_by_name():
    spans = [_span("train.head", 10.0), _span("trainer.prep", 1.0),
             _span("trainer.dispatch", 2.0), _span("trainer.readback", 6.0),
             _span("train.eval.validation", 5.0),
             _span("trainer.prep", 0.5), _span("trainer.dispatch", 1.5),
             _span("compile", 1.4), _span("train.decision", 3.0),
             _span("train.save", 4.0), _span("engine.forward", 9.0)]
    assert flightrecorder.train_breakdown(spans) == {
        "device_ms": 15.0, "launches": 2, "prep_ms": 1.5,
        "dispatch_ms": 3.5, "readback_ms": 6.0, "compiles": 1}
    assert flightrecorder.train_tail_ms(spans) == 7.0


def test_train_breakdown_without_trainer_spans_reads_none():
    out = flightrecorder.train_breakdown([_span("train.head", 10.0)])
    assert out["device_ms"] == 10.0 and out["compiles"] == 0
    assert [out[k] for k in ("launches", "prep_ms", "dispatch_ms",
                             "readback_ms")] == [None] * 4


# -- the span itself ----------------------------------------------------------------
def test_span_ids_nest_and_unwind():
    tracing.clear()
    with tracing.span("outer") as outer:
        with tracing.span("inner") as inner:
            pass
        with tracing.span("second") as second:
            pass
    with tracing.span("after") as after:
        pass
    assert outer.parent_id is None and after.parent_id is None
    assert inner.parent_id == second.parent_id == outer.span_id
    assert len({outer.span_id, inner.span_id, second.span_id,
                after.span_id}) == 4
    d = inner.to_dict()
    assert (d["span_id"], d["parent_id"]) == (inner.span_id,
                                              outer.span_id)


def test_span_parent_unwinds_after_an_error():
    with pytest.raises(RuntimeError):
        with tracing.span("failing"):
            raise RuntimeError("boom")
    with tracing.span("next") as sp:
        pass
    assert sp.parent_id is None


def test_tracing_imports_without_jax():
    """The module never imports JAX: loaded as a package of its own (the
    ``znicz_tpu`` package itself imports JAX), a process gets its spans
    and no annotation."""
    import subprocess
    import sys
    code = (
        "import importlib.util, os, sys\n"
        "d = os.path.join('znicz_tpu', 'telemetry')\n"
        "spec = importlib.util.spec_from_file_location(\n"
        "    'telemetry_alone', os.path.join(d, '__init__.py'),\n"
        "    submodule_search_locations=[d])\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "sys.modules['telemetry_alone'] = mod\n"
        "spec.loader.exec_module(mod)\n"
        "from telemetry_alone import tracing\n"
        "with tracing.span('a', step_num=3) as a:\n"
        "    with tracing.span('b') as b:\n"
        "        pass\n"
        "assert b.parent_id == a.span_id\n"
        "assert len(tracing.recent_spans()) == 2\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
