"""Launcher config file of this configuration: plain Python that mutates
the global ``root`` (the program runs it before the workflow module's
``setdefaults``).  The sizes live in the ``.json`` of the same name."""
import json
import os

with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json",
          encoding="utf-8") as _fh:
    _CFG = json.load(_fh)

root.alexnet.layers = _CFG["layers"]          # noqa: F821 (root is injected)
root.alexnet.size = _CFG["input_size"]        # noqa: F821
root.alexnet.n_classes = _CFG["n_classes"]    # noqa: F821
