"""The workflow file of the decoder language models: the program's
``StandardWorkflow`` over the layer list ``lib/decoder_model.py`` makes of
the configuration, with the program's ``SequenceLoader`` holding rows
made on the device from ``--seed``.  The same ``Launcher``, trainer,
epoch loop and update as the image classifiers."""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from znicz_tpu import prng
from znicz_tpu.config import root
from znicz_tpu.loader.sequence import SequenceLoader
from znicz_tpu.standard_workflow import StandardWorkflow

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import decoder_model as _model     # noqa: E402

root.bench_decoder.setdefaults({
    "minibatch_size": 1, "seq_len": 32,
    "sizes": {"n_train": 16, "n_valid": 4, "n_test": 0},
    "decision": {"max_epochs": 3, "fail_iterations": 3}})


class SeededSequenceLoader(SequenceLoader):
    """Rows [test | validation | train] made on the device from the seed."""

    def __init__(self, workflow=None, name="seeded_sequence_loader", *,
                 seed: int, cfg: dict, traffic: dict, **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.seed, self.cfg, self.traffic = int(seed), cfg, dict(traffic)
        self.prng = prng.RandomGenerator("loader", self.seed)

    def load_data(self) -> None:
        lengths = [int(self.traffic[k]) for k in
                   ("n_test", "n_valid", "n_train")]
        ids, nxt = _model.make_rows(
            self.seed, np.arange(sum(lengths), dtype=np.uint32), self.cfg,
            self.traffic)
        self.original_data.devmem = ids
        self.original_labels.devmem = nxt
        self.class_lengths = lengths


class DecoderWorkflow(StandardWorkflow):
    def __init__(self):
        tree = root.bench_decoder
        with open(tree.get("config_json"), encoding="utf-8") as fh:
            cfg = json.load(fh)
        loader = SeededSequenceLoader(
            minibatch_size=tree.get("minibatch_size"),
            seed=root.bench.get("seed"), cfg=cfg,
            traffic={"seq_len": tree.get("seq_len"),
                     **tree.sizes.to_dict()})
        super().__init__(None, "DecoderWorkflow",
                         layers=_model.layer_list(cfg), loader=loader,
                         loss_function="softmax",
                         decision_config=tree.decision.to_dict(),
                         snapshotter_config=None)


WORKFLOW = DecoderWorkflow


def run(device=None, fused: bool = True, **_):
    from znicz_tpu.backends import Device
    wf = DecoderWorkflow()
    wf.initialize(device=device or Device.create("auto"))
    wf.train(fused=fused)
    return wf
