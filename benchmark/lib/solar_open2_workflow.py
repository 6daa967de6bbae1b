"""The workflow file of the KDA hybrids with experts in every layer: the
program's ``StandardWorkflow`` over the layer list
``lib/solar_open2_model.py`` makes of the configuration, with the seeded
``SequenceLoader`` of ``lib/decoder_workflow.py`` and its config tree
(``root.bench_decoder``).  The same ``Launcher``, trainer, epoch loop and
update as every other cell.  A program without the kinds the layer list
names (the parent of the commit that brought ``kda_block``) ends here in
one line."""

from __future__ import annotations

import json

from znicz_tpu.config import root
from znicz_tpu.parallel import fused
from znicz_tpu.standard_workflow import StandardWorkflow

from benchmark.lib import decoder_workflow as _base
from benchmark.lib import solar_open2_model as _model
from benchmark.lib.errors import BenchError


class KDAHybridWorkflow(StandardWorkflow):
    def __init__(self):
        for kind in ("kda_block", "attn_block", "moe_block"):
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
        super().__init__(None, "KDAHybridWorkflow",
                         layers=_model.layer_list(cfg), loader=loader,
                         loss_function="softmax",
                         decision_config=tree.decision.to_dict(),
                         snapshotter_config=None)


WORKFLOW = KDAHybridWorkflow


def run(device=None, fused: bool = True, **_):
    from znicz_tpu.backends import Device
    wf = KDAHybridWorkflow()
    wf.initialize(device=device or Device.create("auto"))
    wf.train(fused=fused)
    return wf
