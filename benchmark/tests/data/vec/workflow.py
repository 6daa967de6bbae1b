"""The workflow file of ``tiny-vec``: the program's ``StandardWorkflow``
over the layer list in ``root.bench_vec.layers``, with a
``FullBatchLoader`` whose rows are the vectors ``model.py`` makes from
``--seed``."""

from __future__ import annotations

import os
import sys

import numpy as np

from znicz_tpu import prng
from znicz_tpu.config import root
from znicz_tpu.loader.fullbatch import FullBatchLoader
from znicz_tpu.standard_workflow import StandardWorkflow

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), *[os.pardir] * 4)))
from benchmark.tests.data.vec import model as _model     # noqa: E402

root.bench_vec.setdefaults({
    "minibatch_size": 8, "noise": 0.5,
    "sizes": {"n_train": 48, "n_valid": 16, "n_test": 0},
    "decision": {"max_epochs": 3, "fail_iterations": 3}})


class SeededVectorLoader(FullBatchLoader):
    """Rows [test | validation | train] made on the device from the seed."""

    def __init__(self, workflow=None, name="seeded_vector_loader", *,
                 seed: int, cfg: dict, sizes: dict, **kwargs):
        kwargs.setdefault("normalization_type", "none")
        super().__init__(workflow, name, **kwargs)
        self.seed, self.cfg, self.sizes = int(seed), dict(cfg), dict(sizes)
        self.prng = prng.RandomGenerator("loader", self.seed)

    def load_data(self) -> None:
        lengths = [int(self.sizes[k]) for k in
                   ("n_test", "n_valid", "n_train")]
        vectors, labels = _model.make_rows(
            self.seed, np.arange(sum(lengths), dtype=np.uint32), self.cfg)
        self.original_data.devmem = vectors
        self.original_labels.devmem = labels
        self.class_lengths = lengths

    def _normalize(self) -> None:
        """The rows are made in their final range."""


class VectorWorkflow(StandardWorkflow):
    def __init__(self):
        cfg = root.bench_vec
        loader = SeededVectorLoader(
            minibatch_size=cfg.get("minibatch_size"),
            seed=root.bench.get("seed"), sizes=cfg.sizes.to_dict(),
            cfg={"features": cfg.get("features"),
                 "n_classes": cfg.get("n_classes"),
                 "assumed": {"noise": cfg.get("noise")}})
        super().__init__(None, "VectorWorkflow", layers=cfg.get("layers"),
                         loader=loader, loss_function="softmax",
                         decision_config=cfg.decision.to_dict(),
                         snapshotter_config=None)


WORKFLOW = VectorWorkflow


def run(device=None, fused: bool = True, **_):
    from znicz_tpu.backends import Device
    wf = VectorWorkflow()
    wf.initialize(device=device or Device.create("auto"))
    wf.train(fused=fused)
    return wf
