"""Launcher config file of ``tiny-vec``: the sizes live in the ``.json``
of the same name."""
import json
import os

with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json",
          encoding="utf-8") as _fh:
    _CFG = json.load(_fh)

root.bench_vec.layers = _CFG["layers"]        # noqa: F821 (root is injected)
root.bench_vec.features = _CFG["features"]    # noqa: F821
root.bench_vec.n_classes = _CFG["n_classes"]  # noqa: F821
