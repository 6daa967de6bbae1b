#!/usr/bin/env python3
"""Put a kept device trace under the program's scope names: join the
events of ``trace_planes.json.gz`` (``benchmark/run.py --trace 1
--keep-trace DIR``) to the ``op_name`` of the same instruction in the
compiled text of the program that ran (``--xla_dump_to`` on the machine
that traced, or ``tools/compile_decoder_step.py --text-dir`` here), and
sum device time by phase (``fwd``/``bwd``/``upd``), layer kind, inner
scope and kernel.  An executable of the trace takes, among the texts
whose module has its name, the one most of whose instructions (name and
result shape) it shares; an event found in none is ``<not joined>``.
The parsing and the join are the program's own
(``znicz_tpu/telemetry/scopes.py``, which reads the captures the trainer
takes itself with them).  Times come from the trace, so from a chip.

    python3 tools/trace_scopes.py trace_planes.json.gz TEXT... [--rows N]
        [--units]

``--units`` keeps a layer's unit number (``L03.conv``, not ``conv``):
one line a layer of a model whose layers are few.
"""

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from znicz_tpu.telemetry import scopes          # noqa: E402


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
            text = fh.read()
        texts.append((scopes.module_of(text), scopes.text_index(text)))
    total = scopes.by_scope(planes, texts, units)
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
