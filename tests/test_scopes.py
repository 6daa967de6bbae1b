"""``telemetry/scopes.py`` (ISSUE 38): device time by scope through the
compiled texts PER EXECUTABLE, idle time by the host's open span, the
record a ``StepTraceHook`` leaves of the capture it closed, and the two
tools that hold no parser of their own any more.

The fixture under ``tests/data/scopes`` is hand-written: two executables
whose ``fusion.1`` has the same name and result under DIFFERENT scopes
(``fwd/L00.conv`` in ``jit_train_epoch``, ``fwd/L05.fc`` in
``jit_eval_epoch``: the case a sum of operations by name gets wrong), a
third executable nobody has a text of, an event whose result is not the
text's, a copy without metadata, a ``while`` container, and the host's
``python3`` line on the same clock.  The ``*.out.txt`` files are what the
tools printed BEFORE their parsers moved into the package."""

import gzip
import importlib.util
import json
import os

import pytest

from znicz_tpu.telemetry import flightrecorder, programs, scopes
from znicz_tpu.telemetry.profiler import StepTraceHook

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data", "scopes")
TEXTS = ("train_epoch.hlo.txt", "eval_epoch.hlo.txt")


def _read(name: str) -> str:
    with open(os.path.join(DATA, name)) as fh:
        return fh.read()


@pytest.fixture(scope="module")
def planes():
    with gzip.open(os.path.join(DATA, "trace_planes.json.gz"), "rt") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def texts():
    return [(scopes.module_of(_read(name)), scopes.text_index(_read(name)))
            for name in TEXTS]


# -- compiled texts -----------------------------------------------------------
def test_a_text_is_indexed_outside_its_fused_computations(texts):
    (name, train), (other, evaluation) = texts
    assert (name, other) == ("jit_train_epoch", "jit_eval_epoch")
    # add.3 lives in a fused computation: its fusion stands for it
    assert "add.3" not in train and "fusion.1" in train
    assert train["fusion.1"] == (
        "f32[8,16]{1,0:T(8,128)}",
        "jit(train_epoch)/jit(main)/while/body/fwd/L00.conv/add")
    assert evaluation["fusion.1"][1].endswith("fwd/L05.fc/tanh")
    assert train["copy.6"] == ("f32[8,16]{0,1:T(8,128)}", "")


@pytest.mark.parametrize("name, path, units, scope", [
    ("fusion.1", "jit(f)/jit(main)/while/body/fwd/L00.conv/add", False,
     ("fwd", "conv", "-", "-")),
    ("fusion.1", "jit(f)/jit(main)/while/body/fwd/L00.conv/add", True,
     ("fwd", "L00.conv", "-", "-")),
    ("gmm.4", "jit(f)/while/body/bwd/L02.moe_block/experts/gmm", False,
     ("bwd", "moe_block", "experts", "gmm")),
    ("fusion.9", "jit(f)/fwd/L03.gdn_block/gdn_block/delta_rule/dot", False,
     ("fwd", "gdn_block", "delta_rule", "-")),       # the innermost
    ("fusion.2", "jit(f)/while/body/loss", False, ("loss", "-", "-", "-")),
    ("copy.6", "", False, (scopes.NO_SCOPE, "-", "-", "-")),
])
def test_scope_of_an_instruction(name, path, units, scope):
    assert scopes.scope_of(name, path, units) == scope


# -- the join, an executable at a time -----------------------------------------
BY_SCOPE = {
    ("fwd", "conv", "-", "-"): 2.0,          # jit_train_epoch's fusion.1
    ("fwd", "fc", "-", "-"): 2.0,            # jit_eval_epoch's fusion.1
    ("fwd", "attn_block", "scores", "splash"): 1.5,
    ("bwd", "moe_block", "experts", "-"): 1.4,
    ("upd", "conv", "-", "-"): 1.2,
    ("fwd", "moe_block", "experts", "gmm"): 1.0,
    ("loss", "-", "-", "-"): 0.4,
    (scopes.NOT_JOINED, "jit_convert_element_type", "-", "-"): 0.3,
    (scopes.NO_SCOPE, "-", "-", "-"): 0.2,
    (scopes.NOT_JOINED, "jit_eval_epoch", "-", "-"): 0.1,
}


@pytest.mark.parametrize("named", [True, False])
def test_time_by_scope_joins_each_executable_to_its_own_text(
        planes, texts, named):
    """With the texts' names (the register's way) and without (texts
    given to a tool by hand: the most shared instructions decide)."""
    given = texts if named else [(None, index) for _, index in texts]
    got = scopes.by_scope(planes, given)
    assert {k: round(v, 6) for k, v in got.items()} == BY_SCOPE


def test_an_executable_without_a_text_reads_not_joined_never_0(
        planes, texts):
    got = scopes.by_scope(planes, texts[:1])     # the evaluation's is gone
    assert got[scopes.NOT_JOINED, "jit_eval_epoch", "-", "-"] == \
        pytest.approx(4.0)
    assert ("fwd", "fc", "-", "-") not in got
    assert got["fwd", "conv", "-", "-"] == pytest.approx(2.0)
    assert sum(got.values()) == pytest.approx(sum(BY_SCOPE.values()))


def test_idle_time_goes_to_the_innermost_open_span(planes):
    """1.0 + 4.1 ms while the host waited for the head's result, 7.7 ms
    while it was inside the validation's launch (which compiled); the
    ``compile`` span is no ``train.*`` / ``trainer.*`` span, and a
    container's event fills no gap."""
    assert scopes.host_spans(planes)[0] == (0, 30_000_000, "train.epoch")
    idle = scopes.idle_by_span(planes)
    assert {k: round(v, 6) for k, v in idle.items()} == {
        "trainer.readback": 5.1, "trainer.dispatch": 7.7}
    lone = {k: v for k, v in planes.items() if k.startswith("/device")}
    assert dict(scopes.idle_by_span(lone)) == {
        scopes.NO_SPAN: pytest.approx(12.8)}


# -- the record of a capture the program took itself ----------------------------
class _Entry:
    def __init__(self, name, text):
        self.name, self._text, self.asked = name, text, 0

    def text(self):
        self.asked += 1
        return self._text


def test_profile_record_reads_a_capture_through_the_register(
        monkeypatch, planes, tmp_path):
    trace = tmp_path / "step4" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(scopes, "read_capture", lambda path: planes)
    entries = [_Entry("jit_train_epoch", _read(TEXTS[0])),
               _Entry("jit_eval_epoch", _read(TEXTS[1])),
               _Entry("jit_eval_epoch", None),        # its executable went
               _Entry("jit_put_rows", "HloModule jit_put_rows")]
    recorder = flightrecorder.FlightRecorder()
    record = scopes.profile_record(str(tmp_path / "step4"), recorder,
                                   entries)
    # only executables the capture names are rendered
    assert [e.asked for e in entries] == [1, 1, 1, 0]
    assert record["texts"] == 2
    assert record["text_bytes"] == sum(len(_read(t)) for t in TEXTS)
    assert record["by_scope"][:2] == [["fwd", "conv", "-", "-", 2.0],
                                      ["fwd", "fc", "-", "-", 2.0]]
    assert {tuple(r[:4]): r[4] for r in record["by_scope"]} == BY_SCOPE
    assert record["device_ms"] == 10.1 and record["events"] == 16
    assert record["no_scope_ms"] == 0.2
    assert record["not_joined_ms"] == 0.4
    assert record["not_joined_share"] == pytest.approx(0.4 / 10.1, abs=1e-4)
    assert record["idle_ms_by_span"] == {"trainer.dispatch": 7.7,
                                         "trainer.readback": 5.1}
    kept, = [r for r in recorder.snapshot()["recent"]
             if r["kind"] == "train_profile"]
    assert kept["by_scope"] == record["by_scope"]
    json.dumps(kept)


def test_a_capture_that_cannot_be_read_is_a_warning_not_a_failure(
        tmp_path, caplog):
    recorder = flightrecorder.FlightRecorder()
    assert scopes.profile_record(str(tmp_path), recorder, []) is None
    assert "not read" in caplog.text
    assert recorder.snapshot()["recent"] == []


def test_the_hook_reads_each_capture_it_closes():
    read = []
    hook = StepTraceHook("/tmp/prof", every=2, start=lambda d: True,
                         stop=lambda: None,
                         read=lambda d: read.append(d) or {"dir": d})
    for step in range(4):
        hook.on_step(step)
    hook.close()
    assert read == hook.captured == ["/tmp/prof/step0", "/tmp/prof/step2"]
    assert hook.records == [{"dir": d} for d in read]
    unread = StepTraceHook("/tmp/prof", start=lambda d: True,
                           stop=lambda: None, read=None)
    unread.on_step(0)
    unread.close()
    assert unread.captured == ["/tmp/prof/step0"] and unread.records == []


def test_a_hook_capture_of_a_real_run_leaves_a_train_profile_record(
        tmp_path):
    """``train(profile_dir=..., profile_every=...)`` on the CPU: the
    capture is read with ``jax.profiler.ProfileData``, the register's
    texts are at hand, and the record is there.  The CPU's capture has no
    device plane, so it holds no device time: times come from a chip."""
    import test_train_tracing as tt
    programs.clear()
    flightrecorder.RECORDER.clear()
    wf = tt._workflow()
    wf.train(fused=True, max_epochs=2, profile_dir=str(tmp_path),
             profile_every=1)
    records = [r for r in flightrecorder.RECORDER.snapshot()["recent"]
               if r["kind"] == "train_profile"]
    assert [os.path.basename(r["trace_dir"]) for r in records] == [
        "step0", "step1"]
    for record in records:
        assert record["outcome"] == "ok" and record["reduce_s"] >= 0
        assert record["device_ms"] == 0 and record["by_scope"] == []
    planes = scopes.read_capture(scopes.find_xplane(
        os.path.join(str(tmp_path), "step1")))
    names = {name for _, _, name in scopes.host_spans(planes)}
    assert {"train.epoch", "train.head", "trainer.dispatch"} <= names
    # the register's handles give the texts such a join goes through
    text = programs.entries(role="train.head")[-1].text()
    assert any(path.endswith("/add") or "/fwd/L00.conv/" in path
               for _, path in scopes.text_index(text).values())


# -- the tools call the package -------------------------------------------------
def _tool(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("tool, args, pinned", [
    ("trace_scopes", ["trace_planes.json.gz", *TEXTS, "--rows", "2"],
     "trace_scopes.out.txt"),
    ("trace_scopes", ["trace_planes.json.gz", *TEXTS, "--units"],
     "trace_scopes.units.out.txt"),
    ("hlo_scope_bytes", [TEXTS[0], "experts"], "hlo_scope_bytes.out.txt"),
    ("hlo_scope_bytes", [TEXTS[0], "--shape", "8,16", "--shape", "16,8"],
     "hlo_scope_bytes.shape.out.txt"),
])
def test_the_tools_print_what_they_printed(capsys, tool, args, pinned):
    module = _tool(tool)
    argv = [tool] + [os.path.join(DATA, a) if a.endswith((".gz", ".txt"))
                     else a for a in args]
    assert module.main(argv) == 0
    assert capsys.readouterr().out == _read(pinned)


@pytest.mark.parametrize("tool", ["trace_scopes", "hlo_scope_bytes"])
def test_the_tools_hold_no_parser_of_their_own(tool):
    module = _tool(tool)
    source = _read(os.path.join(REPO, "tools", tool + ".py"))
    assert "znicz_tpu.telemetry" in source
    for own in ("def instructions", "def op_name", "def scope_of",
                "op_name=", "(?:ROOT"):
        assert own not in source
    if tool == "hlo_scope_bytes":
        assert module.instructions is scopes.instructions
        assert module.op_name is scopes.op_name
        assert module.LINE is scopes.LINE
