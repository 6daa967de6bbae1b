"""The routing of every shipped layer list, written out.

``parallel/fused.extract_model`` decides from the layer list which rows
merge into an LRN+pool pair and which conv hands the pair its
activation's derivative; from the shapes one device holds, the storage
dtype and the kernel tier (``windowed_pairs``) which pairs take the
window kernels on the convolutions' own layout, leaving their conv whole,
and which keep the column-parity kernels, fed by a conv that emits the
halves; ``pool_routes`` and ``pair_routes`` say which pools and pairs
took which kernels.  Here each model of ``znicz_tpu/models/`` (its
shipped layer list over a few rows of its input shape, cut where the
shape is large) and each image configuration of the benchmark (its layer
list read as data, at a cut input size) has its rows written out, so a
change of routing shows as a diff of this table and not only as a number
on the chip."""

import json
import os

import pytest

import helpers
from znicz_tpu.ops import tuning
from znicz_tpu.parallel import fused

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH = 8


def _model_layers(name):
    """The layer list ``znicz_tpu/models/<name>.py`` ships."""
    import importlib

    from znicz_tpu.config import root
    module = importlib.import_module("znicz_tpu.models." + name)
    tree = {"autoencoder": "mnist_ae"}.get(name, name)
    return getattr(root, tree).get("layers") or module.make_layers()


def _bench_layers(name):
    with open(os.path.join(_REPO, "benchmark", "configs",
                           name + ".json")) as fh:
        return json.load(fh)["layers"]


def _table(rows, pool_routes, units=None, pair_routes="window:0 split:0"):
    return {"rows": rows, "pool_routes": pool_routes,
            "pair_routes": pair_routes,
            "units": units or tuple(range(len(rows)))}


_ALEXNET_UNITS = (0, 1, 3, 4, 6, 7, 8, 9, 10, 11, 12, 13, 14)
_ALEXNET_TAIL = ["conv", "conv", "conv", "max_pool", "dropout", "fc",
                 "dropout", "fc", "fc"]
#: both LRN+pool pairs merged, folded into their convs and, at a batch
#: of whole sublane tiles, on the window kernels: the convs stay whole;
#: pool5 (3x3/2) on the tap stack
ALEXNET = _table(
    ["conv[act_folded]", "lrn_pool[fold_act=strict_relu]",
     "conv[act_folded]", "lrn_pool[fold_act=strict_relu]"]
    + _ALEXNET_TAIL, "windowed:0 taps:1", units=_ALEXNET_UNITS,
    pair_routes="window:2 split:0")
#: at a batch the rule refuses: the column-parity kernels, fed split
#: halves by their convs (what every batch had before PR 33)
ALEXNET_SPLIT = _table(
    ["conv[act_folded,split_out]",
     "lrn_pool[fold_act=strict_relu,emit_split]",
     "conv[act_folded,split_out]",
     "lrn_pool[fold_act=strict_relu,emit_split]"]
    + _ALEXNET_TAIL, "windowed:0 taps:1", units=_ALEXNET_UNITS,
    pair_routes="window:0 split:2")

#: name -> (layer list, one row's shape, loss, the table[, the batch]).  A
#: row reads
#: ``kind`` with, in brackets, what the rewrites (and a tie) left in its
#: config; ``units`` is ``unit_index`` (a merged row names its LRN);
#: a depooling is routed like the pool it undoes
CASES = {
    "alexnet": (lambda: _model_layers("alexnet"), (67, 67, 3), "softmax",
                ALEXNET),
    "bench-alexnet": (lambda: _bench_layers("alexnet"), (67, 67, 3),
                      "softmax", ALEXNET),
    "bench-alexnet at a batch of 12": (
        lambda: _bench_layers("alexnet"), (67, 67, 3), "softmax",
        ALEXNET_SPLIT, 12),
    "bench-vgg11": (lambda: _bench_layers("vgg11"), (32, 32, 3), "softmax",
                    _table(["conv", "max_pool", "conv", "max_pool", "conv",
                            "conv", "max_pool", "conv", "conv", "max_pool",
                            "conv", "conv", "max_pool", "dropout", "fc",
                            "dropout", "fc", "fc"], "windowed:5 taps:0")),
    # its LRN FOLLOWS the pool: nothing to merge
    "cifar": (lambda: _model_layers("cifar"), (32, 32, 3), "softmax",
              _table(["conv", "max_pool", "lrn", "conv", "avg_pool", "fc",
                      "fc"], "windowed:1 taps:0")),
    "mnist": (lambda: _model_layers("mnist"), (784,), "softmax",
              _table(["fc", "fc"], "windowed:0 taps:0")),
    "autoencoder": (lambda: _model_layers("autoencoder"), (28, 28, 1),
                    "mse", _table(["conv", "max_pool", "depooling[tie=1]",
                                   "deconv"], "windowed:2 taps:0")),
    "kanji": (lambda: _model_layers("kanji"), (24, 24, 1), "softmax",
              _table(["conv", "max_pool", "fc", "fc"],
                     "windowed:1 taps:0")),
    "yale_faces": (lambda: _model_layers("yale_faces"), (32, 32, 1),
                   "softmax", _table(["conv", "max_pool", "conv",
                                      "max_pool", "fc", "fc"],
                                     "windowed:2 taps:0")),
    "video_ae": (lambda: _model_layers("video_ae"), (16, 16, 1), "mse",
                 _table(["conv", "max_pool", "depooling[tie=1]",
                         "deconv[tie=0]"], "windowed:2 taps:0")),
    "wine": (lambda: _model_layers("wine"), (13,), "softmax",
             _table(["fc", "fc"], "windowed:0 taps:0")),
}


def _describe(spec):
    rows = []
    for la in spec.layers:
        cfg = la.cfg
        marks = [k if cfg[k] is True else f"{k}={cfg[k]}"
                 for k in ("act_folded", "split_out", "fold_act",
                           "emit_split", "tie") if k in cfg]
        rows.append(la.kind + (f"[{','.join(marks)}]" if marks else ""))
    return rows


@pytest.mark.parametrize("name", sorted(CASES))
def test_rows_of_every_shipped_layer_list(monkeypatch, name):
    layers, shape, loss, table, *batch = CASES[name]
    wf = helpers.tiny_workflow(layers(), shape, *batch or [BATCH], loss)
    # the Pallas tier, as on the chip: off it no pool or pair is routed
    monkeypatch.setattr(tuning, "_INTERPRET", True)
    spec, params, vels = fused.extract_model(wf)
    got = {"rows": _describe(spec), "units": spec.unit_index,
           "pool_routes": fused.pool_routes(spec, wf.forwards),
           "pair_routes": fused.pair_routes(spec, wf.forwards)}
    assert got == table
    assert len(params) == len(vels) == len(spec.layers)


def test_off_the_pallas_tier_the_rows_are_the_layer_lists():
    """On the XLA tier a pair is the composed ops whatever the batch:
    the rewrites follow from the layer list alone, and no route is
    claimed."""
    wf = helpers.tiny_workflow(_bench_layers("alexnet"), (67, 67, 3), BATCH)
    spec = fused.extract_model(wf)[0]
    assert _describe(spec) == ALEXNET_SPLIT["rows"]
    assert fused.pair_routes(spec, wf.forwards) == "window:0 split:0"
    assert fused.pool_routes(spec, wf.forwards) == "windowed:0 taps:0"


def test_the_start_record_states_both_routes(monkeypatch, tmp_path):
    """``pair_routes`` beside ``pool_routes``, in the trainer's start
    line and in every timeline row."""
    import logging
    monkeypatch.setattr(tuning, "_INTERPRET", True)
    wf = helpers.tiny_workflow(_bench_layers("alexnet"), (67, 67, 3), BATCH)
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    wf.logger.addHandler(handler)
    try:
        path = tmp_path / "rows.jsonl"
        wf.train(fused=True, max_epochs=1, timeline_jsonl=str(path))
    finally:
        wf.logger.removeHandler(handler)
    start = [ln for ln in lines if ln.startswith("fused trainer on")]
    assert len(start) == 1
    assert "pool_routes='windowed:0 taps:1'" in start[0]
    assert "pair_routes='window:2 split:0'" in start[0]
    rows = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert rows and all(r["pair_routes"] == "window:2 split:0"
                        and r["pool_routes"] == "windowed:0 taps:1"
                        for r in rows)
