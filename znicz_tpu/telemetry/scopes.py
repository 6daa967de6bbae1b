"""Device time by the program's scopes, idle time by the host's open
span: the parsing of compiled texts and the join of a capture to them.

Every HLO instruction of a training step carries a scope in its
``op_name`` (``fwd|bwd|upd/L<unit>.<kind>``, ``input``, ``loss``, inner
scopes such as ``experts`` or ``delta_rule``: docs/observability.md,
"Train timeline"), and the program's spans lie in a capture's host plane
on the device's clock.  A TPU capture's device events carry the
instruction's NAME only, and two executables number their instructions
alike (``fusion.1`` of the training program is another operation than
``fusion.1`` of an evaluation), so the join goes through the compiled
text PER EXECUTABLE: an executable of the capture takes, among the
texts whose module has its name, the one most of whose instructions
(name and result shape) it shares.

Three layers, each usable alone:

* texts: :func:`instructions`, :func:`op_name`, :func:`text_index`
  (what ``tools/hlo_scope_bytes.py`` and ``tools/trace_scopes.py``
  read compiled texts with);
* a capture as plain lists, ``{plane: {line: [[name, start_ns,
  duration_ns]]}}`` (what ``benchmark/run.py --keep-trace`` writes as
  ``trace_planes.json.gz``; :func:`read_capture` makes the same of an
  ``.xplane.pb``, host plane included): :func:`by_scope`,
  :func:`idle_by_span`;
* the program's own captures: :func:`profile_record` reads the capture
  a ``StepTraceHook`` just closed, joins it through the texts of the
  register of executables (:mod:`~znicz_tpu.telemetry.programs`,
  rendered here and nowhere else) and leaves one ``train_profile``
  flight-recorder record.

Stdlib only but for :func:`read_capture`, which needs
``jax.profiler.ProfileData``.  Times come from the capture, so from the
device that ran.
"""

from __future__ import annotations

import bisect
import collections
import glob
import logging
import os
import re
import time

_log = logging.getLogger(__name__)

#: an instruction's line in a compiled text: name, result, opcode, rest
LINE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
#: a device event's name is its instruction's line: name, result
HEAD = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+)")
#: operations that only contain other operations
CONTAINERS = ("while", "conditional", "call")
PHASES = ("fwd", "bwd", "upd", "input", "loss", "accum")
#: scopes inside a layer's row, outermost first
INNER = ("parallel_block", "attn_mixer", "mamba_mixer",
         "rope", "scores", "qk_norm", "route", "experts", "combine",
         "shared_expert", "mamba_block", "ssd_scan", "mlp_block",
         "gdn_block", "short_conv", "delta_rule", "kda_block", "kda_gates",
         "kda_rule", "attn_gate")
KERNELS = ("gmm", "splash")
#: the device planes' lines, as the TPU profiler names them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the host's spans a gap of the device is put down to
SPAN_PREFIXES = ("train.", "trainer.")

NOT_JOINED = "<not joined>"
NO_SCOPE = "<no scope>"
NO_SPAN = "<no span>"


# -- compiled texts -----------------------------------------------------------
def instructions(text: str):
    """``(name, result text, opcode, rest of the line)`` of every
    instruction of a compiled text outside the fused computations."""
    fused = False
    for line in text.splitlines():
        if "fused_computation" in line.split("(")[0] and line.endswith("{"):
            fused = True
        elif line.startswith("}"):
            fused = False
        m = LINE.match(line)
        if m and not fused:
            yield m.groups()


def op_name(rest: str) -> str:
    found = re.search(r'op_name="([^"]*)"', rest)
    return found.group(1) if found else ""


def text_index(text: str) -> dict:
    """{instruction name: (result shape text, op_name)} of a compiled
    text, fused computations' own instructions left out."""
    return {name: (result.split()[0], op_name(rest))
            for name, result, _, rest in instructions(text)}


def module_of(text: str) -> str | None:
    """The module's name in a compiled text's first line
    (``jit_train_epoch``)."""
    found = re.match(r"HloModule ([\w.\-]+)", text)
    return found.group(1) if found else None


def scope_of(name: str, path: str, units: bool = False) -> tuple:
    """``(phase, layer, inner scope, kernel)`` of an instruction ``name``
    whose ``op_name`` is ``path``; ``units`` keeps a layer's unit number
    (``L03.conv``, not ``conv``)."""
    phase = next((p for p in PHASES
                  if f"/{p}/" in path or path.endswith("/" + p)), NO_SCOPE)
    layer = re.search(r"/(L\d+\.\w+)" if units else r"/L\d+\.(\w+)", path)
    inner = [s for s in INNER if f"/{s}/" in path or path.endswith("/" + s)]
    kernel = next((k for k in KERNELS if k in name), "")
    return (phase, layer.group(1) if layer else "-",
            inner[-1] if inner else "-", kernel or "-")


# -- a capture, as plain lists --------------------------------------------------
def _device_planes(planes: dict):
    return (lines for lines in planes.values()
            if OPS_LINE in lines or MODULES_LINE in lines)


def _events_by_module(lines: dict) -> dict:
    """{executable as the capture names it: [(instruction, result,
    duration_ns)]} of one device plane, containers left out."""
    modules = sorted((s, s + d, name)
                     for name, s, d in lines.get(MODULES_LINE, []))
    starts = [m[0] for m in modules]
    by_module = collections.defaultdict(list)
    for name, s, d in lines.get(OPS_LINE, []):
        head = HEAD.match(name)
        if not head or head.group(1).split(".")[0] in CONTAINERS:
            continue
        at = bisect.bisect_right(starts, s) - 1
        module = (modules[at][2] if at >= 0 and s < modules[at][1]
                  else "?")
        by_module[module].append((head.group(1), head.group(2), d))
    return by_module


def by_scope(planes: dict, texts: list, units: bool = False
             ) -> collections.Counter:
    """Device milliseconds by ``(phase, layer, inner scope, kernel)``.
    ``texts``: ``(module name or None, text_index(...))`` pairs; an
    executable of the capture takes, among the texts of its name (all of
    them where none has it), the one most of whose instructions it
    shares, name and result shape; an event its text does not hold is
    ``(NOT_JOINED, the executable's name, "-", "-")``."""
    total: collections.Counter = collections.Counter()
    for lines in _device_planes(planes):
        for module, events in _events_by_module(lines).items():
            base = module.split("(")[0]
            mine = [t for n, t in texts if n == base] or [
                t for n, t in texts if n is None]
            shared = [sum(1 for n, shape, _ in events
                          if t.get(n, ("",))[0] == shape) for t in mine]
            text = mine[shared.index(max(shared))] if mine else {}
            for n, shape, d in events:
                shape_there, path = text.get(n, ("", ""))
                key = (scope_of(n, path, units) if shape_there == shape
                       else (NOT_JOINED, base, "-", "-"))
                total[key] += d / 1e6
    return total


def _union(intervals: list) -> list:
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def host_spans(planes: dict) -> list:
    """``(start_ns, end_ns, name)`` of the program's ``train.*`` and
    ``trainer.*`` spans in a capture's host planes."""
    return sorted(
        (s, s + d, name) for lines in planes.values()
        if OPS_LINE not in lines and MODULES_LINE not in lines
        for events in lines.values() for name, s, d in events
        if name.startswith(SPAN_PREFIXES))


def idle_by_span(planes: dict) -> collections.Counter:
    """Milliseconds each device did nothing between two operations,
    summed over the devices by the innermost ``train.*`` / ``trainer.*``
    span open on the host when the gap began (``NO_SPAN`` where none
    was).  Host and device share the capture's clock: no offset is
    fitted."""
    spans = host_spans(planes)
    # between two neighbouring starts or ends the innermost open span
    # (the one that started last) is one and the same
    edges = sorted({t for s, e, _ in spans for t in (s, e)})
    open_at = [max(((s, name) for s, e, name in spans if s <= t < e),
                   default=(0, NO_SPAN))[1] for t in edges]
    idle: collections.Counter = collections.Counter()
    for lines in _device_planes(planes):
        busy = _union([
            (s, s + d) for name, s, d in lines.get(OPS_LINE, [])
            if (HEAD.match(name) or [None, "?"])[1].split(".")[0]
            not in CONTAINERS])
        for (_, a1), (b0, _) in zip(busy, busy[1:]):
            at = bisect.bisect_right(edges, a1) - 1
            idle[open_at[at] if at >= 0 else NO_SPAN] += (b0 - a1) / 1e6
    return idle


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def read_capture(path: str) -> dict:
    """An ``.xplane.pb`` as plain lists: the device planes' operations
    (an operation's name cut to its head, ``%fusion.1 = f32[8,16]{1,0}``:
    the whole line is some hundred bytes an event, half a million events
    an epoch) and executables, and of the host planes the program's
    spans."""
    from jax.profiler import ProfileData
    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ops = device and line.name == OPS_LINE
            events = []
            for ev in line.events:
                name = ev.name
                if ops:
                    head = HEAD.match(name)
                    name = head.group(0) if head else name
                elif not device and not name.startswith(SPAN_PREFIXES):
                    continue
                events.append([name, int(ev.start_ns),
                               int(ev.duration_ns)])
            if events:
                lines[line.name] = events
        if lines:
            planes[plane.name] = lines
    return planes


# -- the program's own captures -------------------------------------------------
def profile_record(trace_dir: str, recorder=None, entries=None,
                   top: int = 40) -> dict | None:
    """Read the capture under ``trace_dir``, join it to the texts of the
    register's executables whose names it holds, and record one
    ``train_profile`` record: ``by_scope`` (the ``top`` largest, as
    ``[phase, layer, scope, kernel, ms]``), ``device_ms``,
    ``no_scope_ms``, ``not_joined_ms`` with ``not_joined_share`` (an
    executable whose text is not at hand reads here, never as 0),
    ``idle_ms_by_span``, and what the reading cost: ``texts`` rendered,
    their ``text_bytes``, ``events``, ``reduce_s``.  Never raises: a
    capture that cannot be read is a warning and None."""
    from . import flightrecorder, programs
    t0 = time.monotonic()
    try:
        path = find_xplane(trace_dir)
        if path is None:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        planes = read_capture(path)
        names = {module.split("(")[0]
                 for lines in _device_planes(planes)
                 for module, _, _ in lines.get(MODULES_LINE, [])}
        texts, text_bytes = [], 0
        for entry in (programs.entries() if entries is None else entries):
            if entry.name not in names:
                continue
            text = entry.text()
            if text is not None:
                text_bytes += len(text)
                texts.append((entry.name, text_index(text)))
        scopes = by_scope(planes, texts)
        idle = idle_by_span(planes)
    except Exception as e:      # observability must not stop the training
        _log.warning("capture under %s not read: %s", trace_dir, e)
        return None
    whole = sum(scopes.values())
    not_joined = sum(ms for key, ms in scopes.items()
                     if key[0] == NOT_JOINED)
    record = {
        "trace_dir": trace_dir,
        "device_ms": round(whole, 3),
        "by_scope": [[*key, round(ms, 3)] for key, ms in
                     scopes.most_common(top)],
        "no_scope_ms": round(sum(ms for key, ms in scopes.items()
                                 if key[0] == NO_SCOPE), 3),
        "not_joined_ms": round(not_joined, 3),
        "not_joined_share": round(not_joined / whole, 4) if whole else None,
        "idle_ms_by_span": {name: round(ms, 3)
                            for name, ms in idle.most_common()},
        "texts": len(texts), "text_bytes": text_bytes,
        "events": sum(len(lines.get(OPS_LINE, []))
                      for lines in _device_planes(planes)),
        "reduce_s": round(time.monotonic() - t0, 3)}
    (recorder or flightrecorder.RECORDER).record(
        "train_profile", duration_ms=record["reduce_s"] * 1e3, **record)
    return record
