"""The register of the executables a process built: what each plans to
reserve, under which name a profile lists it, and a way to its compiled
text.

A build was noticed before (``compilestats``: a ``compile`` span, a
count, a cost) and nothing of what was built was kept: not its memory
plan (``compiled.memory_analysis()``, known the moment the executable
exists and the figure a load that fails quotes), not the name the
profiler files its device time under, not its text, which is the only
place a device event's scope (``op_name``) can be looked up.  Every
build that ``compilestats.BuildTimed`` makes, and the ahead-of-time
builds beside it (``register`` called with the ``Compiled``), leaves one
:class:`Program` here:

* ``site`` (``train.fused``, ``train.stream``) and the ``role`` its
  builder states (``train.head``: the scan over the steps;
  ``train.step``: the one-minibatch program, the deferred tail's too;
  ``eval.<set>``; ``prepare_set``);
* ``shapes``: the arguments that made it, one short text an argument
  (a tree of leaves as its count and bytes);
* ``plan``: ``temp``, ``argument``, ``output``, ``alias`` and
  ``generated_code`` bytes; a part is ABSENT, never 0, where the
  runtime gives none, and the gauge ``train_program_plan_bytes{role,
  part}`` carries what is there;
* ``name``: the module's name in a profile (``jit_train_epoch``); the
  number a TPU profile puts behind it (``jit_train_epoch(53285...)``)
  is a fingerprint the runtime computes when it loads the program and
  hands to no Python call (``LoadedExecutable.fingerprint`` is another
  number), so a capture's executables are matched to entries by name
  first and, between entries of one name, by the instruction names and
  result shapes they share (:mod:`~znicz_tpu.telemetry.scopes`);
* ``text()``: the compiled text, rendered when asked and never before
  (a decoder program's is tens of megabytes); ``None`` once the
  executable is gone.

The register is bounded (a deque; an entry is a few hundred bytes) and
holds each executable weakly: the handle lives as long as whoever calls
it (the trainer's ``BuildTimed``), and an ahead-of-time build that was
dropped after use keeps its plan and loses its text.

JAX is imported inside the functions that need it, as everywhere in
this package.
"""

from __future__ import annotations

import collections
import re
import threading
import time
import weakref

from .registry import REGISTRY

#: a plan's parts, and the field of ``memory_analysis()`` each reads
PLAN_PARTS = {"temp": "temp_size_in_bytes",
              "argument": "argument_size_in_bytes",
              "output": "output_size_in_bytes",
              "alias": "alias_size_in_bytes",
              "generated_code": "generated_code_size_in_bytes"}

#: entries kept; a training run builds about ten executables
CAPACITY = 256

_lock = threading.Lock()
_entries: collections.deque = collections.deque(maxlen=CAPACITY)


def _plan_gauge():
    """Made with the first plan, so that a process that built nothing
    shows no such family."""
    return REGISTRY.gauge(
        "train_program_plan_bytes",
        "what an executable the trainer built plans to reserve on the "
        "device, by the role its builder states and by part (temp | "
        "argument | output | alias | generated_code: "
        "compiled.memory_analysis()); the newest build of a role")


def plan_of(compiled) -> dict:
    """``{part: bytes}`` of an executable's memory plan; a part the
    runtime does not give is left out."""
    try:
        stats = compiled.memory_analysis()
    except Exception:       # a runtime without the analysis
        return {}
    plan = {}
    for part, field in PLAN_PARTS.items():
        value = getattr(stats, field, None)
        if value is not None:
            plan[part] = int(value)
    return plan


def signature(args) -> tuple:
    """What decides which executable a call takes: the arguments' tree
    and every leaf's shape, dtype and sharding (hashable; about a third
    of a microsecond a leaf)."""
    import jax
    leaves, tree = jax.tree_util.tree_flatten(args)
    return tree, tuple((getattr(a, "shape", ()),
                        getattr(a, "dtype", type(a)),
                        getattr(a, "sharding", None)) for a in leaves)


def _leaf_text(a) -> str:
    import numpy as np
    dtype = np.dtype(getattr(a, "dtype", type(a)))
    short = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
             "int32": "s32", "uint32": "u32", "int8": "s8", "uint8": "u8",
             "bool": "pred"}.get(dtype.name, dtype.name)
    return f"{short}[{','.join(map(str, getattr(a, 'shape', ())))}]"


def shapes_of(args) -> tuple:
    """One short text an argument: a leaf as ``f32[63,128]``, a tree as
    its leaves' count and bytes (``212 leaves 4223664128 B``)."""
    import jax
    import numpy as np
    out = []
    for arg in args:
        leaves = jax.tree_util.tree_leaves(arg)
        if len(leaves) == 1 and leaves[0] is arg:
            out.append(_leaf_text(arg))
        else:
            held = sum(int(np.prod(getattr(a, "shape", ())))
                       * np.dtype(getattr(a, "dtype", type(a))).itemsize
                       for a in leaves)
            out.append(f"{len(leaves)} leaves {held} B")
    return tuple(out)


class Program:
    """One executable that was built: see the module's text."""

    __slots__ = ("site", "role", "name", "shapes", "plan", "built_at",
                 "_handle")

    def __init__(self, site: str, role: str | None, name: str,
                 shapes: tuple, plan: dict, handle):
        self.site = site
        self.role = role
        self.name = name
        self.shapes = shapes
        self.plan = plan
        self.built_at = time.time()
        self._handle = handle

    def text(self) -> str | None:
        """The compiled text, rendered now; None once the executable is
        gone."""
        compiled = self._handle()
        return None if compiled is None else compiled.as_text()

    def to_dict(self) -> dict:
        return {"site": self.site, "role": self.role, "name": self.name,
                "shapes": list(self.shapes), "plan": dict(self.plan),
                "built_at": self.built_at,
                "text_at_hand": self._handle() is not None}

    def __repr__(self):
        return (f"<Program {self.name} site={self.site} role={self.role} "
                f"plan={self.plan}>")


def module_name(fn) -> str:
    """The name ``jax.jit`` gives the module it makes of ``fn``, which is
    the executable's name in a profile (``jit_train_epoch``)."""
    return "jit_" + re.sub(r"[^\w.\-]", "_",
                           getattr(fn, "__name__", "") or "fn")


def register(site: str, role: str | None, name: str, compiled,
             args=()) -> Program:
    """One entry for ``compiled`` (a ``jax.stages.Compiled``), built from
    ``args`` (arrays or ``ShapeDtypeStruct``s); the gauge takes its
    plan.  Never renders the text."""
    entry = Program(site, role, name, shapes_of(args), plan_of(compiled),
                    weakref.ref(compiled))
    with _lock:
        _entries.append(entry)
    if role is not None and entry.plan:
        gauge = _plan_gauge()
        for part, value in entry.plan.items():
            gauge.set(value, role=role, part=part)
    return entry


def entries(site: str | None = None, role: str | None = None) -> list:
    """The entries, oldest first, of one site and one role where
    given."""
    with _lock:
        found = list(_entries)
    return [e for e in found if (site is None or e.site == site)
            and (role is None or e.role == role)]


def snapshot() -> list:
    """JSON-able view, for a debug endpoint or a person."""
    return [e.to_dict() for e in entries()]


def clear() -> None:
    with _lock:
        _entries.clear()
