#!/usr/bin/env python3
"""List what a compiled program runs under some scopes, with the bytes
each instruction reads and writes: the fusions, kernels, gathers, sorts
and copies of ``compiled.as_text()`` (``tools/compile_decoder_step.py
--text-dir``) whose ``op_name`` holds one of the given scope names.
Counted from shapes: what the compiler says the instruction touches, not
what the chip moved, and no time.  The texts are read with the
program's own parser (``znicz_tpu/telemetry/scopes.py``).

    python3 tools/hlo_scope_bytes.py train_epoch.hlo.txt \
        [--in bwd/L02.moe_block] experts combine

    python3 tools/hlo_scope_bytes.py train_epoch.hlo.txt \
        --shape 9,4096,768 --shape 9,768,4096

lists instead what MAKES an array of those shapes, whatever its scope
(the zeros a conditional's idle branch hands back carry none): by
computation (``step``: the program's own; ``idle``: a conditional's
branch 0; ``later``: its branch 1), opcode and result, with the bytes
written.

A gather's operand is counted whole, though it reads only the rows it
takes: its bytes in overstate; bytes out are what it writes.  An
instruction under ``cond/branch_1_fun`` (the later piece of the sorted
pairs) is marked ``later``; ``T`` marks a transposed (backward)
instruction."""

import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from znicz_tpu.telemetry.scopes import (LINE, instructions,  # noqa: E402
                                        op_name)

ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
        "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
        "u64": 8}
SHAPE = re.compile(r"\b(" + "|".join(ITEM) + r")\[([0-9,]*)\]")
SKIP = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
        "while", "conditional", "call", "iota", "after-all", "partition-id",
        "replica-id"}


def nbytes(text: str) -> int:
    total = 0
    for dtype, dims in SHAPE.findall(text):
        n = ITEM[dtype]
        for dim in dims.split(","):
            n *= int(dim) if dim else 1
        total += n
    return total


def rows(text: str, scopes, within: str = ""):
    """``(scope path, name, opcode, result text, bytes in, bytes out)`` of
    every instruction outside the fused computations whose ``op_name``
    holds one of ``scopes`` (and ``within``)."""
    shapes, out = {}, []
    for name, result, opcode, rest in instructions(text):
        shapes[name] = result
        path = op_name(rest)
        if opcode in SKIP or within not in path or not any(
                f"/{s}/" in path or path.endswith("/" + s) for s in scopes):
            continue
        operands = re.findall(r"%([\w.\-]+)", rest.split("),")[0])
        out.append((path, name, opcode, result, sum(
            nbytes(shapes.get(o, "")) for o in operands), nbytes(result)))
    return out


def made_of_shape(text: str, dims) -> collections.Counter:
    """``{(where, opcode, result): arrays made}`` over the instructions
    outside the fused computations whose result is one array of a shape in
    ``dims`` (``"9,4096,768"``; ``result``: ``bf16[9,4096,768]``); views,
    buffer markers and the half of an asynchronous copy that writes nothing
    are left out."""
    branches = dict(
        pair for found in re.findall(
            r"branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}", text)
        for pair in zip(found, ("idle", "later")))
    silent = SKIP | {"copy-start", "slice-start"}
    made, where = collections.Counter(), "step"
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[0].lstrip("%")
            where = ("step" if line.startswith("ENTRY") else branches.get(
                name, "fused" if "fused_computation" in name else name))
        m = LINE.match(line)
        shape = m and SHAPE.match(m.group(2))
        if (shape and shape.group(2) in dims and where != "fused"
                and m.group(3) not in silent
                and (m.group(3) != "custom-call"        # a kernel, not a
                     or "tpu_custom_call" in line)):    # buffer's marker
            opcode = m.group(3)
            if opcode in ("fusion", "custom-call"):
                opcode += ":" + re.sub(r"[.\d]+$", "", m.group(1))
            made[where, opcode, shape.group(0)] += 1
    return made


def main(argv) -> int:
    args, within = list(argv[1:]), ""
    if "--shape" in args:
        dims = [args[i + 1] for i, a in enumerate(args) if a == "--shape"]
        with open(args[0]) as fh:
            made = made_of_shape(fh.read(), dims)
        for (where, opcode, result), n in sorted(made.items()):
            print(f"{where:24s} {opcode:24s} {result:20s} x{n:3d} "
                  f"{n * nbytes(result) / 1e6:9.1f} MB written")
        return 0
    if "--in" in args:
        at = args.index("--in")
        within = args[at + 1]
        del args[at:at + 2]
    path, scopes = args[0], args[1:] or ["experts", "combine"]
    with open(path) as fh:
        found = rows(fh.read(), scopes, within)
    total = collections.Counter()
    for op_path, name, opcode, result, b_in, b_out in found:
        scope = [s for s in scopes if f"/{s}" in op_path][-1]
        side = ("T" if "transpose(" in op_path else "-") + (
            " later" if "cond/branch_1_fun" in op_path else " first")
        total[scope, side, "instructions"] += 1
        total[scope, side, "in_mb"] += b_in / 1e6
        total[scope, side, "out_mb"] += b_out / 1e6
        if b_in + b_out < 1e6:          # index arithmetic: in the totals only
            continue
        print(f"{scope:8s} {side:7s} {opcode:12s} {name:28s} "
              f"in {b_in / 1e6:9.2f} MB out {b_out / 1e6:9.2f} MB  "
              f"{result[:60]}")
    for key in sorted(total):
        print("total", *key, round(total[key], 1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
