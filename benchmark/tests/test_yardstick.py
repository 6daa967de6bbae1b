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
    from benchmark.lib import data, flops
    cfg = _config(name)
    shape = (cfg["input_size"], cfg["input_size"], cfg["input_channels"])
    f = flops.model_flops(cfg["layers"], shape)
    assert f["train_step"] == pytest.approx(train_g * 1e9, rel=1e-9)
    assert f["matmul_train"] == pytest.approx(matmul_g * 1e9, rel=1e-9)
    assert f["params"] == pytest.approx(params_m * 1e6, rel=1e-9)

    # the program's own count, for the same net, today
    from znicz_tpu.ops import flops as prog
    from znicz_tpu.parallel.fused import LayerSpec, ModelSpec
    kinds = {"conv_str": "conv", "all2all_str": "fc", "softmax": "fc",
             "norm": "lrn", "max_pooling": "max_pool", "dropout": "dropout"}
    layers, params = [], []
    for la, sh in zip(cfg["layers"],
                      data.param_shapes(cfg["layers"], *shape[1:])):
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
    assert xplane.reduce({})["busy_s"] == 0.0


def _tiny_follow(**kw):
    import jax
    from benchmark.lib import data, reference
    with open(os.path.join(HERE, "data", "configs", "tiny.json")) as fh:
        cfg = json.load(fh)
    seed, b = 2147483999, 8
    shapes = data.param_shapes(cfg["layers"], 67, 3)
    x, y = data.make_rows(seed, np.arange(3 * b, dtype=np.uint32), 67, 3,
                          10, 0.4)
    with jax.default_matmul_precision("highest"):
        return reference.follow(cfg["layers"],
                                data.make_weights(seed, shapes),
                                x.reshape(3, b, 67, 67, 3),
                                y.reshape(3, b), seed=seed, **kw)


def test_control_and_planted_faults_read_far_from_the_reference():
    """The control (the reference at float8 operands) and each planted
    fault, against the reference itself, by the comparison that decides
    ``correct``; kept at a size a test can hold."""
    from benchmark.lib import correct, reference
    ref = _tiny_follow()

    def numbers(other):
        return correct.first_steps_numbers(other, ref)
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
