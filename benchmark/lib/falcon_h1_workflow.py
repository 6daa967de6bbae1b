"""The workflow file of the parallel hybrids: the program's
``StandardWorkflow`` over the layer list ``lib/falcon_h1_model.py`` makes of
the configuration, with the seeded ``SequenceLoader`` of
``lib/decoder_workflow.py`` and its config tree (``root.bench_decoder``).  The same ``Launcher``, trainer, epoch loop and
update as every other cell."""

from __future__ import annotations

import json

from znicz_tpu.config import root
from znicz_tpu.parallel import fused
from znicz_tpu.standard_workflow import StandardWorkflow

from benchmark.lib import decoder_workflow as _base
from benchmark.lib import falcon_h1_model as _model
from benchmark.lib.errors import BenchError


class ParallelHybridWorkflow(StandardWorkflow):
    def __init__(self):
        for kind in ("parallel_block", "mlp_block"):
            if kind not in fused.SEQUENCE_FWD:
                raise BenchError(
                    f"the program has no layer kind {kind!r} (its sequence "
                    f"kinds: {sorted(fused.SEQUENCE_FWD)})")
        tree = root.bench_decoder
        with open(tree.get("config_json"), encoding="utf-8") as fh:
            cfg = json.load(fh)
        loader = _base.SeededSequenceLoader(
            minibatch_size=tree.get("minibatch_size"),
            seed=root.bench.get("seed"), cfg=cfg,
            traffic={"seq_len": tree.get("seq_len"),
                     **tree.sizes.to_dict()})
        super().__init__(None, "ParallelHybridWorkflow",
                         layers=_model.layer_list(cfg), loader=loader,
                         loss_function="softmax",
                         decision_config=tree.decision.to_dict(),
                         snapshotter_config=None)


WORKFLOW = ParallelHybridWorkflow


def run(device=None, fused: bool = True, **_):
    from znicz_tpu.backends import Device
    wf = ParallelHybridWorkflow()
    wf.initialize(device=device or Device.create("auto"))
    wf.train(fused=fused)
    return wf
