"""The workflow file the benchmark hands to the program's ``Launcher``.

The model is the program's own: ``StandardWorkflow`` over the layer list
in ``root.alexnet.layers`` (the configuration's), the same trainer, the
same epoch loop.  What is the benchmark's is the data set: a
``FullBatchLoader`` whose rows are functions of ``--seed`` and the row
number (``benchmark/lib/data.py``), made on the device in one call and
never brought to the host, so that set-up is short and the plain
reference can make the rows it follows again by itself.  The program's
``ImagenetSyntheticLoader`` draws each image in a Python loop and
normalises the set on the host."""

from __future__ import annotations

import os
import sys

import numpy as np

from znicz_tpu import prng
from znicz_tpu.config import root
from znicz_tpu.loader.fullbatch import FullBatchLoader
from znicz_tpu.models import alexnet as _alexnet   # noqa: F401 (defaults)
from znicz_tpu.standard_workflow import StandardWorkflow

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import data as _data            # noqa: E402


class SeededResidentLoader(FullBatchLoader):
    """Rows [test | validation | train] made on the device from the seed."""

    def __init__(self, workflow=None, name="seeded_resident_loader", *,
                 seed: int, size: int, n_classes: int, sizes: dict,
                 **kwargs):
        kwargs.setdefault("normalization_type", "none")
        super().__init__(workflow, name, **kwargs)
        self.seed, self.size, self.n_classes = int(seed), int(size), \
            int(n_classes)
        self.sizes = dict(sizes)
        # the shuffle follows --seed too; the program's own streams keep
        # the Launcher's fixed seed (see run.py)
        self.prng = prng.RandomGenerator("loader", self.seed)

    def load_data(self) -> None:
        n_test, n_valid, n_train = (int(self.sizes[k]) for k in
                                    ("n_test", "n_valid", "n_train"))
        n = n_test + n_valid + n_train
        images, labels = _data.make_rows(
            self.seed, np.arange(n, dtype=np.uint32), self.size, 3,
            self.n_classes, float(self.sizes.get("noise", 0.4)))
        self.original_data.devmem = images
        self.original_labels.devmem = labels
        self.class_lengths = [n_test, n_valid, n_train]

    def _normalize(self) -> None:
        """The rows are made in their final range; fitting a normaliser
        would bring the whole set to the host and back."""


class ResidentWorkflow(StandardWorkflow):
    """``AlexNetWorkflow`` with the seeded loader in place of its own."""

    def __init__(self):
        cfg = root.alexnet
        loader = SeededResidentLoader(
            minibatch_size=cfg.get("minibatch_size"),
            seed=root.bench.get("seed"), size=cfg.get("size"),
            n_classes=cfg.get("n_classes"),
            sizes=cfg.synthetic.to_dict())
        super().__init__(None, "ResidentWorkflow",
                         layers=cfg.get("layers"), loader=loader,
                         loss_function="softmax",
                         decision_config=cfg.decision.to_dict(),
                         snapshotter_config=None)


WORKFLOW = ResidentWorkflow


def run(device=None, fused: bool = True, **_):
    """The Launcher's entry point (``python -m znicz_tpu <this file>
    <config> --fused``)."""
    from znicz_tpu.backends import Device
    wf = ResidentWorkflow()
    wf.initialize(device=device or Device.create("auto"))
    wf.train(fused=fused)
    return wf
