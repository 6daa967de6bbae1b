#!/usr/bin/env python3
"""Put a kept device trace under the program's scope names: join the
events of ``trace_planes.json.gz`` (``benchmark/run.py --trace 1
--keep-trace DIR``) to the ``op_name`` of the same instruction in the
compiled text of the program that ran (``--xla_dump_to`` on the machine
that traced, or ``tools/compile_decoder_step.py --text-dir`` here), and
sum device time by phase (``fwd``/``bwd``/``upd``), layer kind, inner
scope and kernel.  An executable of the trace takes the text most of
whose instructions (name and result shape) it shares; an event found in
none is ``<not joined>``.  Times come from the trace, so from a chip.

    python3 tools/trace_scopes.py trace_planes.json.gz TEXT... [--rows N]
        [--units]

``--units`` keeps a layer's unit number (``L03.conv``, not ``conv``):
one line a layer of a model whose layers are few.
"""

import collections
import gzip
import json
import re
import sys

import hlo_scope_bytes

HEAD = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+)")
CONTAINERS = ("while", "conditional", "call")
INNER = ("rope", "scores", "qk_norm", "route", "experts", "combine",
         "shared_expert", "mamba_block", "ssd_scan", "mlp_block",
         "gdn_block", "short_conv", "delta_rule")


def instructions(text: str) -> dict:
    """{instruction name: (result shape text, op_name)} of a compiled
    text, fused computations' own instructions left out."""
    return {name: (result.split()[0], hlo_scope_bytes.op_name(rest))
            for name, result, _, rest in hlo_scope_bytes.instructions(text)}


def scope_of(name: str, path: str, units: bool = False) -> tuple:
    phase = next((p for p in ("fwd", "bwd", "upd", "input", "loss", "accum")
                  if f"/{p}/" in path or path.endswith("/" + p)),
                 "<no scope>")
    layer = re.search(r"/(L\d+\.\w+)" if units else r"/L\d+\.(\w+)", path)
    inner = [s for s in INNER if f"/{s}/" in path or path.endswith("/" + s)]
    kernel = next((k for k in ("gmm", "splash") if k in name), "")
    return (phase, layer.group(1) if layer else "-",
            inner[-1] if inner else "-", kernel or "-")


def main(argv) -> int:
    args, rows = list(argv[1:]), 1.0
    units = "--units" in args
    if units:
        args.remove("--units")
    if "--rows" in args:
        at = args.index("--rows")
        rows = float(args[at + 1])
        del args[at:at + 2]
    with gzip.open(args[0], "rt") as fh:
        planes = json.load(fh)
    texts = []
    for path in args[1:]:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as fh:
            texts.append(instructions(fh.read()))
    total = collections.Counter()
    for lines in planes.values():
        modules = sorted((s, s + d, name)
                         for name, s, d in lines.get("XLA Modules", []))
        by_module = collections.defaultdict(list)
        for name, s, d in lines.get("XLA Ops", []):
            head = HEAD.match(name)
            if not head or head.group(1).split(".")[0] in CONTAINERS:
                continue
            module = next((n for a, b, n in modules if a <= s < b), "?")
            by_module[module].append((head.group(1), head.group(2), d))
        for module, events in by_module.items():
            shared = [sum(1 for n, shape, _ in events
                          if t.get(n, ("",))[0] == shape) for t in texts]
            text = texts[shared.index(max(shared))] if texts else {}
            for n, shape, d in events:
                shape_there, path = text.get(n, ("", ""))
                key = (scope_of(n, path, units) if shape_there == shape
                       else ("<not joined>", module.split("(")[0], "-", "-"))
                total[key] += d / 1e6
    whole = sum(total.values())
    print(f"{'phase':12s} {'layer':16s} {'scope':8s} {'kernel':7s} "
          f"{'ms':>10s} {'ms/row':>8s} {'share':>6s}")
    for key, ms in sorted(total.items(), key=lambda kv: -kv[1]):
        print(f"{key[0]:12s} {key[1]:16s} {key[2]:8s} {key[3]:7s} "
              f"{ms:10.1f} {ms / rows:8.2f} {100 * ms / whole:5.1f}%")
    print(f"{'all':46s} {whole:10.1f} {whole / rows:8.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
