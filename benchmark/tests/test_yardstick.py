"""The yardstick's arithmetic: operation counts, the trace reduction on a
recorded trace, the reference against its control."""

import gzip
import json
import os

import numpy as np
import pytest

from conftest import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name,train_g,matmul_g,params_m", [
    ("alexnet", 7.205370072, 6.813514392, 62.378344),
    ("vgg11", 46.48651244, 45.676846008, 132.863336)])
def test_flops_pinned_and_equal_to_the_programs(name, train_g, matmul_g,
                                                params_m):
    from benchmark.lib import image_model
    cfg = _config(name)
    shape = (cfg["input_size"], cfg["input_size"], cfg["input_channels"])
    f = image_model.flops(cfg, None)
    assert image_model.step_bytes(cfg, None, 64) == 4.0 * (
        4 * f["params"] + 64 * int(np.prod(shape)))
    assert image_model.output_leaf(cfg) == 2 * sum(
        sh is not None for sh in image_model.param_shapes(cfg)) - 2
    assert f["train_step"] == pytest.approx(train_g * 1e9, rel=1e-9)
    assert f["matmul_train"] == pytest.approx(matmul_g * 1e9, rel=1e-9)
    assert f["params"] == pytest.approx(params_m * 1e6, rel=1e-9)

    # the program's own count, for the same net, today
    from znicz_tpu.ops import flops as prog
    from znicz_tpu.parallel.fused import LayerSpec, ModelSpec
    kinds = {"conv_str": "conv", "all2all_str": "fc", "softmax": "fc",
             "norm": "lrn", "max_pooling": "max_pool", "dropout": "dropout"}
    layers, params = [], []
    for la, sh in zip(cfg["layers"], image_model.param_shapes(cfg)):
        c = la["->"]
        conf = {"conv": {"stride": c.get("sliding", 1),
                         "padding": c.get("padding", 0)},
                "max_pool": {"ksize": (c.get("ky"), c.get("kx")),
                             "stride": c.get("sliding"), "padding": 0},
                "lrn": {"n": c.get("n")}}.get(kinds[la["type"]], {})
        layers.append(LayerSpec(kinds[la["type"]], "linear", True, (), (),
                                tuple(sorted(conf.items()))))
        params.append((None, None) if sh is None else
                      (np.broadcast_to(np.float32(0), sh[0]),
                       np.broadcast_to(np.float32(0), sh[1])))
    p = prog.model_flops(ModelSpec(tuple(layers), "softmax"), params, shape)
    assert p["train_step"] == pytest.approx(f["train_step"], rel=1e-12)
    assert p["forward"] == pytest.approx(f["forward"], rel=1e-12)
    assert p["params"] == f["params"]


def test_xplane_reduction_on_the_recorded_trace():
    from benchmark.lib import xplane
    with gzip.open(os.path.join(HERE, "data", "trace_recorded.json.gz"),
                   "rt") as fh:
        rec = json.load(fh)
    out = xplane.reduce(rec["planes"])
    want = rec["pinned"]
    assert out["planes"] == want["planes"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert out["device_ops"][0][0] == want["top_op"]
    # every operation is there for a reader, not the ten longest only;
    # the ten longest of them are what the result's breakdown carries
    assert len(out["ops_s"]) > 10
    ranked = sorted(out["ops_s"].items(), key=lambda kv: -kv[1]["total_s"])
    assert [[k, v["total_s"]] for k, v in ranked[:10]] == out["device_ops"]
    events = [e for e in rec["planes"]["/device:TPU:0"]["XLA Ops"]
              if not xplane._is_container(e[0])]
    assert sum(v["count"] for v in out["ops_s"].values()) == len(events)
    assert sum(v["total_s"] for v in out["ops_s"].values()) == pytest.approx(
        sum(e[2] for e in events) / 1e9, rel=1e-9)
    assert out["idle_gaps"][0][0] == want["top_gap"][0]
    assert out["idle_gaps"][0][1] == pytest.approx(want["top_gap"][1])
    assert not any(name.startswith("while") for name, _ in out["device_ops"])
    for name, m in want["modules"].items():
        assert out["modules"][name]["count"] == m["count"]
        assert out["modules"][name]["total_s"] == pytest.approx(
            m["total_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]


def test_xplane_union_and_containers():
    from benchmark.lib import xplane
    planes = {"/device:TPU:0": {
        "XLA Modules": [["jit_a(1)", 0, 100], ["jit_b(2)", 150, 50]],
        "XLA Ops": [["while.1", 0, 100], ["fusion.1", 0, 40],
                    ["fusion.2", 30, 30], ["copy.3", 80, 20],
                    ["fusion.9", 150, 50]]}}
    out = xplane.reduce(planes)
    assert out["busy_s"] == pytest.approx((60 + 20 + 50) / 1e9)
    assert out["window_s"] == pytest.approx(200 / 1e9)
    gaps = dict((k, v) for k, v in out["idle_gaps"])
    assert gaps["inside jit_a(1)"] == pytest.approx(20 / 1e9)
    assert gaps["jit_a(1) -> jit_b(2)"] == pytest.approx(50 / 1e9)
    assert out["ops_s"]["fusion.1"] == {"total_s": 40 / 1e9, "count": 1}
    assert "while.1" not in out["ops_s"]
    assert xplane.reduce({})["busy_s"] == 0.0
    assert xplane.reduce({})["ops_s"] == {}


def test_a_reader_reaches_config_traffic_and_every_operation():
    """The tests' own reader (``data/readers/``) on the recorded trace:
    it finds its kernels' time in ``ops_s`` though only two of them are
    among the ten longest, and reads nothing where the configuration has
    no such layer or the trace no such kernel."""
    from benchmark.lib import xplane
    from conftest import TEST_READER
    from test_program_spans import _reader
    read = _reader(TEST_READER, os.path.join(HERE, "data", "readers"))
    with gzip.open(os.path.join(HERE, "data", "trace_recorded.json.gz"),
                   "rt") as fh:
        trace = xplane.reduce(json.load(fh)["planes"])
    trace["train_steps"] = 64
    mine = {k: v["total_s"] for k, v in trace["ops_s"].items()
            if k.startswith(("pallas_lrn", "pallas_gd_lrn"))}
    assert len(mine) > len([k for k, _ in trace["device_ops"] if k in mine])
    run = {"config": _config("alexnet"), "traffic": {"minibatch": 128},
           "trace": trace}
    assert read(run) == pytest.approx(1e6 * sum(mine.values()) / (64 * 128))
    assert read(dict(run, config=_config("vgg11"))) is None
    assert read(dict(run, trace=None)) is None
    assert read(dict(run, trace=dict(trace, ops_s={}))) is None


def _tiny_config():
    with open(os.path.join(HERE, "data", "configs", "tiny.json")) as fh:
        return json.load(fh)


def _tiny_follow(**kw):
    import jax
    from benchmark.lib import image_model, reference
    cfg = _tiny_config()
    seed, b = 2147483999, 8
    shapes = image_model.param_shapes(cfg)
    x, y = image_model.make_rows(seed, np.arange(3 * b, dtype=np.uint32),
                                 cfg)
    with jax.default_matmul_precision("highest"):
        return reference.follow(cfg, image_model.make_weights(seed, shapes),
                                x.reshape(3, b, 67, 67, 3),
                                y.reshape(3, b), seed=seed, **kw)


def test_control_and_planted_faults_read_far_from_the_reference():
    """The control (the reference at float8 operands) and each planted
    fault, against the reference itself, by the comparison that decides
    ``correct``; kept at a size a test can hold."""
    from benchmark.lib import correct, image_model, reference
    ref = _tiny_follow()
    out_leaf = image_model.output_leaf(_tiny_config())
    assert out_leaf == 6        # conv, conv, fc, softmax: (w, b) each

    def numbers(other):
        return correct.first_steps_numbers(other, ref, out_leaf)
    same = numbers(_tiny_follow())
    assert max(same.values()) == 0.0
    bf16 = numbers(_tiny_follow(operand=reference.bf16_operand))
    fp8 = numbers(_tiny_follow(operand=reference.fp8_operand))
    assert min(bf16.values()) > 0

    def fails(other, factor):
        """The numbers by which ``other`` reads ``factor`` times what the
        stated precision reads."""
        return {k for k in bf16 if other[k] > factor * bf16[k]}
    assert "out_grad_diff" in fails(fp8, 3)
    half = numbers(_tiny_follow(half_batch=True))
    assert {"grad_norm_gap", "change_norm_gap"} <= fails(half, 10)
    frozen = numbers(_tiny_follow(frozen=True))
    assert frozen["change_norm_gap"] == pytest.approx(1.0)
    assert frozen["loss_gap"] > 3 * bf16["loss_gap"]


def test_rows_misfed_counts():
    from benchmark.lib import correct
    tr, va = (16, 64), (0, 16)
    perm = np.random.default_rng(0).permutation(np.arange(*tr))
    good = [{"kind": "train", "epoch": 3, "indices": perm[:40]},
            {"kind": "eval", "indices": perm[40:]},
            {"kind": "eval", "indices": np.arange(*va)},
            {"kind": "train", "epoch": 3, "indices": perm[40:]}]
    assert correct.rows_misfed(good, tr, va) == 0
    twice = [dict(good[0], indices=np.r_[perm[:39], perm[:1]])] + good[1:]
    assert correct.rows_misfed(twice, tr, va) == 2
    assert correct.rows_misfed(good[:3], tr, va) == 1   # nothing checked
    short = good[:2] + [{"kind": "eval", "indices": np.arange(8)}, good[3]]
    assert correct.rows_misfed(short, tr, va) == 16


# -- layers of any number of leaves ---------------------------------------
LR, WD, MOM = 0.05, 0.01, 0.9


class _StubTrainer:
    """A trainer whose layers hold 1, no, 2 and 4 leaves, as the
    program's ``FusedTrainer`` lays them out (a layer without parameters
    is a tuple of Nones), under the program's update rule on a gradient
    the test knows: g = 0.5 * p + (step + 1)."""

    def __init__(self, with_vels=True):
        rng = np.random.default_rng(7)
        shapes = [[(5, 3)], None, [(4, 6), (6,)],
                  [(6, 2), (2,), (3, 3, 2), (70,)]]
        self.params = [(None, None) if sh is None else tuple(
            np.asarray(rng.normal(size=s), np.float32) for s in sh)
            for sh in shapes]
        if with_vels:
            self.vels = [tuple(None if a is None else np.zeros_like(a)
                               for a in la) for la in self.params]
        self.grads = []

    def train_epoch(self, data, target, indices, batch, **kw):
        for step in range(len(indices) // batch):
            step += kw.get("ctr_base", 0) // batch
            g = [tuple(None if p is None else 0.5 * p + (step + 1)
                       for p in la) for la in self.params]
            self.grads.append(g)
            self.vels = [tuple(None if p is None else
                               MOM * v - LR * (gp + WD * p)
                               for p, v, gp in zip(la, lv, lg))
                         for la, lv, lg in zip(self.params, self.vels, g)]
            self.params = [tuple(None if p is None else p + v
                                 for p, v in zip(la, lv))
                           for la, lv in zip(self.params, self.vels)]
        return {"loss": np.arange(len(indices) // batch, dtype=np.float32)}


STUB_HYPERS = [tuple({"learning_rate": LR, "weights_decay": WD}
                     for _ in range(n)) if n else None
               for n in (1, 0, 0, 2, 4)]


def test_probe_and_correct_on_layers_of_1_2_and_4_leaves():
    from benchmark.lib import correct, data
    from benchmark.lib.probe import TrainerProbe
    trainer, b = _StubTrainer(), 4
    p0 = [tuple(None if a is None else a.copy() for a in la)
          for la in trainer.params]
    probe = TrainerProbe(STUB_HYPERS)
    out = probe._followed_head(_StubTrainer.train_epoch, trainer, None, None,
                               np.arange(5 * b), b, {"epoch": 0})
    first = probe.first
    assert list(out["loss"]) == [0, 0, 0, 0, 1] and first["losses"] == [0] * 3
    assert [None if la is None else len(la) for la in first["grad_norms"]] \
        == [1, None, 2, 4]

    # the reference's side: parameterised layers only, from what the test
    # knows (the first gradient; the change after three steps, from a
    # second stub that the probe does not touch)
    g1 = [la for la in trainer.grads[0] if la[0] is not None]
    other = _StubTrainer()
    other.train_epoch(None, None, np.arange(3 * b), b)
    moved = [tuple(a - a0 for a, a0 in zip(la, la0))
             for la, la0 in zip(other.params, p0) if la0[0] is not None]
    leaf, sketches = 0, []
    for la in g1:
        sketches.append(tuple(np.asarray(data.sketch(a, leaf + j)).tolist()
                              for j, a in enumerate(la)))
        leaf += len(la)
    norm = lambda a: float(np.linalg.norm(a))             # noqa: E731
    ref = {"losses": [1.0, 1.0, 1.0],
           "grad_norms": [tuple(map(norm, la)) for la in g1],
           "change_norms": [tuple(map(norm, la)) for la in moved],
           "grad_sketches": sketches}
    first["losses"] = [1.0, 1.0, 1.0]
    got = correct.first_steps_numbers(first, ref, 3)
    assert got["loss_gap"] == 0.0
    for name in ("grad_norm_gap", "change_norm_gap", "out_grad_diff"):
        assert got[name] < 1e-5, (name, got)
    table = correct.leaf_table(first, ref)
    assert [line.split(":")[0] for line in table] == [
        "leaf 0.0", "leaf 1.0", "leaf 1.1", "leaf 2.0", "leaf 2.1",
        "leaf 2.2", "leaf 2.3"]

    # out_grad_diff reads the leaf the model file names, and that alone
    bent = dict(ref, grad_sketches=[tuple(
        [v + 1.0 for v in sk] if (k, j) == (2, 0) else sk
        for j, sk in enumerate(la)) for k, la in enumerate(sketches)])
    assert correct.first_steps_numbers(first, bent, 3)["out_grad_diff"] > .1
    assert correct.first_steps_numbers(first, bent, 2)["out_grad_diff"] < 1e-5
    assert correct.first_steps_numbers(first, bent, 7)["out_grad_diff"] \
        == float("inf")
    # a leaf the program did not move is the worst leaf's gap, 1
    still = dict(first, change_norms=[
        la if la is None or len(la) < 4 else (*la[:3], 0.0)
        for la in first["change_norms"]])
    assert correct.first_steps_numbers(still, ref, 3)["change_norm_gap"] \
        == pytest.approx(1.0)


def test_probe_names_a_trainer_it_cannot_read():
    from benchmark.lib.errors import BenchError
    from benchmark.lib.probe import TrainerProbe
    call = (_StubTrainer.train_epoch, None, None, np.arange(20), 4,
            {"epoch": 0})
    with pytest.raises(BenchError, match="momentum SGD.*_StubTrainer"):
        TrainerProbe(STUB_HYPERS)._followed_head(
            call[0], _StubTrainer(with_vels=False), *call[1:])
    with pytest.raises(BenchError, match=r"\[1, 2, 4\] leaves.*\[1, 2\]"):
        TrainerProbe(STUB_HYPERS[:4])._followed_head(
            call[0], _StubTrainer(), *call[1:])
    with pytest.raises(BenchError, match="too short"):
        TrainerProbe(STUB_HYPERS)._followed_head(
            call[0], _StubTrainer(), None, None, np.arange(12), 4, {})
