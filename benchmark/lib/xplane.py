"""From a profiler trace (``.xplane.pb``) to numbers.

``load`` reads the device planes with ``jax.profiler.ProfileData`` into
plain lists; ``reduce`` works on those lists alone, so a recorded trace
kept as JSON pins its numbers in a test."""

from __future__ import annotations

import glob
import os
import re

#: the device plane's lines, as the TPU profiler names them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: operations that only contain other operations: counting them as busy
#: would hide the idle time between the operations they contain
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """{plane name: {line name: [[event name, start_ns, duration_ns]]}}
    for the device planes (``/device:...``)."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        out[plane.name] = {
            line.name: [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]
            for line in plane.lines}
    return out


def _is_container(name: str) -> bool:
    base = name.partition(" = ")[0].lstrip("%").split(".")[0]
    return base.split("(")[0].strip() in CONTAINERS


_SHAPE = re.compile(r"\w+\[[\d,]*\]")
_KIND = re.compile(r"kind=(\w+)")


def short_name(name: str) -> str:
    """An operation's name as the trace gives it is its whole HLO line;
    keep the name, the fusion kind and the first result's shape:
    ``fusion.475 kOutput f32[128,27,27,96]``."""
    head, _, rest = name.partition(" = ")
    parts = [head.lstrip("%")]
    kind, shape = _KIND.search(rest), _SHAPE.search(rest)
    if kind:
        parts.append(kind.group(1))
    if shape:
        parts.append(shape.group(0))
    return " ".join(parts)


def _union(intervals: list) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def reduce(planes: dict, top: int = 10) -> dict:
    """Busy time (union of the operations' intervals, containers left
    out, averaged over the device planes that ran anything), the traced
    window (first operation's start to last one's end, over all planes),
    time per executable, every operation's summed time and count by
    its ``short_name`` (``ops_s``), the ``top`` of them that took most
    time (``device_ops``) and the longest idle gaps, named by the
    executables on either side."""
    busy, spans = [], []
    ops: dict = {}
    modules: dict = {}
    gaps: dict = {}
    for lines in planes.values():
        events = [e for e in lines.get(OPS_LINE, [])
                  if not _is_container(e[0])]
        if not events:
            continue
        merged = _union([(s, s + d) for _, s, d in events])
        busy.append(sum(b - a for a, b in merged))
        spans.append((merged[0][0], merged[-1][1]))
        for name, _, d in events:
            op = ops.setdefault(short_name(name), [0, 0])
            op[0] += d
            op[1] += 1
        mods = sorted((s, s + d, name)
                      for name, s, d in lines.get(MODULES_LINE, []))
        for s, e, name in mods:
            m = modules.setdefault(name, {"count": 0, "total_s": 0.0})
            m["count"] += 1
            m["total_s"] += (e - s) / 1e9
        for (a0, a1), (b0, b1) in zip(merged, merged[1:]):
            before = next((n for s, e, n in reversed(mods) if s <= a1),
                          "?")
            after = next((n for s, e, n in mods if e >= b0), "?")
            key = (f"inside {before}" if before == after and any(
                s <= a1 and e >= b0 for s, e, n in mods if n == before)
                else f"{before} -> {after}")
            gaps[key] = gaps.get(key, 0) + (b0 - a1)
    if not busy:
        return {"busy_s": 0.0, "window_s": 0.0, "modules": {},
                "ops_s": {}, "device_ops": [], "idle_gaps": [],
                "planes": 0}
    window = (max(e for _, e in spans) - min(s for s, _ in spans)) / 1e9
    rank = lambda d: [[k, v / 1e9] for k, v in sorted(      # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": window,
            "modules": modules,
            "ops_s": {k: {"total_s": d / 1e9, "count": n}
                      for k, (d, n) in ops.items()},
            "device_ops": rank({k: d for k, (d, _) in ops.items()}),
            "idle_gaps": rank(gaps), "planes": len(busy)}


def describe(path: str, limit: int = 25) -> str:
    """A trace at a glance, for a person: every plane and line, how many
    events, and the first distinct names with their statistics."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            seen = {}
            for ev in events:
                if ev.name not in seen:
                    seen[ev.name] = ev
                if len(seen) >= limit:
                    break
            for name, ev in seen.items():
                try:
                    stats = {k: (v if not isinstance(v, (bytes, str))
                                 else str(v)[:60]) for k, v in ev.stats}
                except Exception as e:      # a person's view only
                    stats = {"stats": repr(e)}
                out.append(f"    {name[:90]!r} start={ev.start_ns:.0f} "
                           f"dur={ev.duration_ns:.0f} {stats}")
    return "\n".join(out)
