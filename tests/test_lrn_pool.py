"""Fused LRN→max-pool pair (ops/lrn_pool.py + the extract_model merge).

Contract (mirrors the repo's kernel-test convention), through the
dispatchers ``lrn_maxpool`` / ``gd_lrn_maxpool`` in the Pallas
interpreter: a batch that is a multiple of 8 takes the window kernels,
any other the composed XLA ops.  Winner OFFSETS are bit-identical to the
numpy golden path on both routes (same window math, same flat tap
order), and so are the composed route's values; the window kernels'
values are equal to float32 rounding (their in-kernel LRN is contracted
into fused multiply-adds otherwise: at 96 and 256 channels a few
elements in ten thousand differ in the last bit, as in
``tests/test_lrn_pool_window.py``).  Backward gradients match to f32
tolerance (the in-kernel jnp math may FMA-contract where numpy rounds
twice — same tolerance class as the standalone LRN kernel tests).  On
the XLA dispatch tier (no Pallas) the merged spec is op-for-op the same
composition as the split spec, so a merged-spec FusedTrainer trains
BIT-identically to the split-spec one there — asserted below.
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import helpers
from znicz_tpu import prng
from znicz_tpu.ops import lrn_pool, normalization as lrn_math, \
    pooling as pool_ops, tuning


@pytest.fixture
def interpret_mode(monkeypatch):
    monkeypatch.setattr(tuning, "_INTERPRET", True)
    yield


def _x(shape, stream="x", scale=1.0):
    return np.asarray(prng.get(stream).normal(size=shape),
                      np.float32) * scale


GEOMS = [
    # (B, H, W, C, ksize, stride)  — stride-W must be 2 (the gate)
    (2, 9, 9, 8, (3, 3), (2, 2)),       # odd W (AlexNet-like)
    (1, 8, 8, 16, (3, 3), (2, 2)),      # even W
    (3, 11, 7, 4, (2, 3), (2, 2)),      # rectangular window, odd W
    (2, 10, 12, 8, (2, 2), (1, 2)),     # row stride 1 (overlapping rows)
    (2, 13, 9, 8, (4, 2), (3, 2)),      # tall window, row stride 3
    # the two SHIPPED AlexNet geometries (shrunk batch/extent, real C):
    # C=96 pads the lane axis, C=256 spans two full lane tiles
    (1, 15, 15, 96, (3, 3), (2, 2)),    # L1-like
    (1, 9, 9, 256, (3, 3), (2, 2)),     # L2-like
]
#: a pair's two routes on the Pallas tier: the window kernels at a batch
#: of whole sublane tiles (8), the composed ops at the GEOM's own batch
#: (none of them a multiple of 8)
ROUTES = ("window", "composed")


def _batch(b, route):
    return 8 if route == "window" else b


def _route(fn, *args):
    """``window`` where the program ``fn`` traces to calls the window
    kernels, ``composed`` where it calls no Pallas kernel at all."""
    text = str(jax.make_jaxpr(fn)(*args))
    if "pallas_call" not in text:
        return "composed"
    assert re.search(r"pallas_(gd_)?lrn_maxpool_window", text), text
    return "window"


def _lrn_pool(x, lrn=(5, 1e-4, 0.75, 2.0), ks=(3, 3), st=(2, 2),
              use_abs=False):
    """(y, idx) of the dispatcher and the route it took."""
    def fn(x):
        return lrn_pool.lrn_maxpool(x, *lrn, ks, st, 0, use_abs)
    x = jnp.asarray(x)
    y, idx = fn(x)
    return np.asarray(y), np.asarray(idx), _route(fn, x)


def _gd_lrn_pool(errp, idx, x, ks=(3, 3), st=(2, 2), fold_act=None):
    """dx of the dispatcher and the route it took."""
    def fn(errp, idx, x):
        return lrn_pool.gd_lrn_maxpool(errp, idx, x, 5, 1e-4, 0.75, 2.0,
                                       ks, st, 0, fold_act)
    args = tuple(jnp.asarray(a) for a in (errp, idx, x))
    return np.asarray(fn(*args)), _route(fn, *args)


@pytest.mark.usefixtures("interpret_mode")
class TestFusedForward:
    @pytest.mark.parametrize("route", ROUTES)
    @pytest.mark.parametrize("b,h,w,c,ks,st", GEOMS)
    def test_bit_identical_to_composed(self, b, h, w, c, ks, st, route):
        x = _x((_batch(b, route), h, w, c))
        y_ref, idx_ref = lrn_pool.np_lrn_maxpool(
            x, 5, 1e-4, 0.75, 2.0, ks, st, 0)
        y, idx, took = _lrn_pool(x, ks=ks, st=st)
        assert took == route
        np.testing.assert_array_equal(idx, idx_ref)
        if route == "composed":
            np.testing.assert_array_equal(y, y_ref)
        else:       # float32 rounding of the in-kernel LRN (docstring)
            np.testing.assert_allclose(y, y_ref, rtol=1e-6, atol=0)

    def test_maxabs_variant(self):
        x = _x((8, 9, 9, 8))
        y_ref, idx_ref = lrn_pool.np_lrn_maxpool(
            x, 5, 1e-4, 0.75, 2.0, (3, 3), (2, 2), 0, use_abs=True)
        y, idx, took = _lrn_pool(x, use_abs=True)
        assert took == "window"
        np.testing.assert_array_equal(y, y_ref)
        np.testing.assert_array_equal(idx, idx_ref)

    def test_small_lrn_window(self):
        x = _x((8, 9, 9, 8))
        y_ref, idx_ref = lrn_pool.np_lrn_maxpool(
            x, 3, 5e-4, 0.75, 1.0, (3, 3), (2, 2), 0)
        y, idx, took = _lrn_pool(x, lrn=(3, 5e-4, 0.75, 1.0))
        assert took == "window"
        np.testing.assert_array_equal(y, y_ref)
        np.testing.assert_array_equal(idx, idx_ref)

    def test_gate(self):
        assert lrn_pool.fusable((3, 3), (2, 2), 0)
        assert not lrn_pool.fusable((3, 3), (2, 2), 1)    # padding
        assert not lrn_pool.fusable((3, 3), (3, 3), 0)    # stride-W 3
        assert not lrn_pool.fusable((2, 2), (2, 1), 0)    # stride-W 1


@pytest.mark.usefixtures("interpret_mode")
class TestFusedBackward:
    @pytest.mark.parametrize("route", ROUTES)
    @pytest.mark.parametrize("b,h,w,c,ks,st", GEOMS)
    def test_matches_composed_golden(self, b, h, w, c, ks, st, route):
        x = _x((_batch(b, route), h, w, c))
        _, idx = lrn_pool.np_lrn_maxpool(x, 5, 1e-4, 0.75, 2.0, ks, st, 0)
        errp = _x(idx.shape, "err", 0.1)
        dx_ref = lrn_pool.np_gd_lrn_maxpool(
            errp, idx, x, 5, 1e-4, 0.75, 2.0, ks, st, 0)
        dx, took = _gd_lrn_pool(errp, idx, x, ks, st)
        assert took == route
        np.testing.assert_allclose(dx, np.asarray(dx_ref, np.float32),
                                   rtol=1e-5, atol=1e-7)

    @pytest.mark.parametrize("act", ["strict_relu", "tanh", "sigmoid"])
    def test_fold_act_matches_composed(self, act):
        """fold_act folds the preceding layer's activation derivative
        into the pair backward — must equal the composed golden
        (pool bwd → lrn bwd → act bwd)."""
        x = _x((8, 9, 9, 8), scale=0.7)
        if act == "strict_relu":
            x = np.maximum(x, 0.0)       # y of a strict-relu layer ≥ 0
        _, idx = lrn_pool.np_lrn_maxpool(x, 5, 1e-4, 0.75, 2.0,
                                         (3, 3), (2, 2), 0)
        errp = _x(idx.shape, "err", 0.1)
        dx_ref = lrn_pool.np_gd_lrn_maxpool(
            errp, idx, x, 5, 1e-4, 0.75, 2.0, (3, 3), (2, 2), 0,
            fold_act=act)
        dx, took = _gd_lrn_pool(errp, idx, x, fold_act=act)
        assert took == "window"
        np.testing.assert_allclose(dx, np.asarray(dx_ref, np.float32),
                                   rtol=1e-5, atol=1e-7)

    def test_gradient_against_jax_autodiff(self):
        """Independent check: the hand-written pair backward matches
        jax.grad through the composed differentiable forward (max-pool
        picks unique winners for random data, so grads agree)."""
        x = _x((8, 9, 9, 8))
        errp_shape = pool_ops.pool_out_shape(x.shape, (3, 3), (2, 2), 0)
        errp = _x(errp_shape, "err", 0.1)

        def scalar(xx):
            y = lrn_math.xla_lrn(xx, 5, 1e-4, 0.75, 2.0)[0]
            p, _ = pool_ops.xla_max_pooling(y, (3, 3), (2, 2), 0)
            return jnp.sum(p * jnp.asarray(errp))

        dx_auto = jax.grad(scalar)(jnp.asarray(x))
        _, idx = lrn_pool.np_lrn_maxpool(x, 5, 1e-4, 0.75, 2.0,
                                         (3, 3), (2, 2), 0)
        dx, took = _gd_lrn_pool(errp, idx, x)
        assert took == "window"
        np.testing.assert_allclose(dx, np.asarray(dx_auto),
                                   rtol=2e-4, atol=2e-5)


class TestSpecMerge:
    def _mk_layers(self):
        from znicz_tpu.parallel.fused import LayerSpec
        H = (0.01, 0.0, 0.0, 0.9)
        mk = lambda kind, **cfg: LayerSpec(       # noqa: E731
            kind=kind, activation="linear", include_bias=False,
            hypers=H, hypers_bias=H, config=tuple(sorted(cfg.items())))
        return mk

    def test_merge_and_tie_remap(self):
        from znicz_tpu.parallel import fused
        mk = self._mk_layers()
        layers = [
            mk("conv", stride=(1, 1), padding=0),            # 0
            mk("lrn", n=5, alpha=1e-4, beta=0.75, k=2.0),    # 1 ┐ merge
            mk("max_pool", ksize=(3, 3), stride=(2, 2),      # 2 ┘
               padding=0),
            mk("conv", stride=(1, 1), padding=0),            # 3
            mk("depooling", ksize=(3, 3), stride=(2, 2),     # 4 tie → 2
               padding=0, tie=2),
            mk("deconv", stride=(1, 1), padding=0, tie=0),   # 5 tie → 0
        ]
        pv = [(None, None)] * len(layers)
        spec, out_p, out_v = fused.model_of_rows(
            fused.fold_pair_act(fused.merge_lrn_pool(layers)), pv, pv,
            "mse")
        out_l, src = spec.layers, spec.unit_index
        kinds = [la.kind for la in out_l]
        assert kinds == ["conv", "lrn_pool", "conv", "depooling",
                         "deconv"]
        assert out_l[3].cfg["tie"] == 1     # pool(2) → merged(1)
        assert out_l[4].cfg["tie"] == 0
        assert len(out_p) == len(out_l) == len(out_v)
        # write_back map: spec rows address their ORIGINAL units
        assert src == (0, 1, 3, 4, 5)
        merged_cfg = out_l[1].cfg
        assert merged_cfg["n"] == 5 and merged_cfg["ksize"] == (3, 3)
        assert merged_cfg["use_abs"] is False
        # linear conv: nothing to fold
        assert "fold_act" not in merged_cfg
        assert "act_folded" not in out_l[0].cfg

    def test_activation_fold_marks_both_layers(self):
        from znicz_tpu.parallel.fused import (LayerSpec, fold_pair_act,
                                              merge_lrn_pool)
        H = (0.01, 0.0, 0.0, 0.9)
        conv = LayerSpec(kind="conv", activation="strict_relu",
                         include_bias=True, hypers=H, hypers_bias=H,
                         config=(("padding", 0), ("stride", (1, 1))))
        mk = self._mk_layers()
        layers = [conv,
                  mk("lrn", n=5, alpha=1e-4, beta=0.75, k=2.0),
                  mk("max_pool", ksize=(3, 3), stride=(2, 2),
                     padding=0)]
        merged = merge_lrn_pool(layers)
        assert "fold_act" not in merged[1].cfg      # (i) alone folds nothing
        out_l = fold_pair_act(merged)
        assert [la.kind for la in out_l] == ["conv", "lrn_pool"]
        assert out_l[1].cfg["fold_act"] == "strict_relu"
        assert out_l[0].cfg["act_folded"] is True

    def test_non_fusable_kept_split(self):
        from znicz_tpu.parallel import fused
        mk = self._mk_layers()
        layers = [
            mk("lrn", n=5, alpha=1e-4, beta=0.75, k=2.0),
            mk("max_pool", ksize=(3, 3), stride=(3, 3), padding=0),
        ]
        pv = [(None, None)] * 2
        spec, _, _ = fused.model_of_rows(fused.merge_lrn_pool(layers),
                                         pv, pv, "mse")
        assert [la.kind for la in spec.layers] == ["lrn", "max_pool"]
        assert spec.unit_index == (0, 1)

    def test_unrewritten_rows_are_the_units(self):
        """``workflow_rows`` (extract_model's first half) hands back one
        row a forward unit, LRN and pool apart, nothing folded:
        the reference every rewrite is compared against."""
        from znicz_tpu.parallel import fused
        wf = TestTrainEquivalence()._workflow()
        rows, params, vels = fused.workflow_rows(wf)
        assert len(rows) == len(params) == len(vels) == len(wf.forwards)
        kinds = [la.kind for la in rows]
        assert "lrn_pool" not in kinds and kinds.count("lrn") == 2
        assert kinds[1:3] == ["lrn", "max_pool"]
        assert not any({"act_folded", "fold_act"} & set(la.cfg)
                       for la in rows)
        spec, _, _ = helpers.routed(wf)
        assert spec.layers == tuple(rows)
        assert spec.unit_index == tuple(range(len(rows)))


class TestWriteBack:
    def test_write_back_lands_on_the_right_units(self):
        """Review r3: the merge makes spec rows FEWER than forward
        units; write_back must address units through spec.unit_index —
        a positional zip put conv weights on a pooling unit."""
        from znicz_tpu.backends import Device
        from znicz_tpu.config import root
        from znicz_tpu.models import alexnet
        from znicz_tpu.nn.all2all import All2All
        from znicz_tpu.nn.conv import Conv
        from znicz_tpu.parallel import FusedTrainer, fused

        saved = root.alexnet.to_dict()
        try:
            root.alexnet.synthetic.update({"n_train": 32, "n_valid": 0,
                                           "n_test": 0})
            root.alexnet.update({"minibatch_size": 16, "size": 67,
                                 "n_classes": 7})
            root.alexnet.layers = alexnet.make_layers(
                n_classes=7, widths=(8, 12, 8, 8, 8, 24, 16))
            prng.seed_all(3)
            wf = alexnet.AlexNetWorkflow()
            wf.initialize(device=Device.create("xla"))
        finally:
            root.alexnet.update(saved)
        spec, params, vels = fused.extract_model(wf)
        assert len(spec.layers) < len(wf.forwards)      # merge happened
        assert len(spec.unit_index) == len(spec.layers)
        tr = FusedTrainer(spec=spec, params=params, vels=vels)
        ld = wf.loader
        tr.train_epoch(ld.original_data.devmem,
                       ld.original_labels.devmem, np.arange(32), 16)
        tr.workflow = wf
        tr.write_back()
        n_checked = 0
        for row, ((w, b), la) in enumerate(zip(tr.params, spec.layers)):
            if w is None:
                continue
            unit = wf.forwards[spec.unit_index[row]]
            # a weight row must land on a parameterized unit of the
            # right kind, holding exactly the trained array
            assert isinstance(unit, (Conv, All2All)), type(unit)
            np.testing.assert_array_equal(np.asarray(unit.weights.mem),
                                          np.asarray(w))
            n_checked += 1
        assert n_checked == 8            # 5 convs + 3 fc


def _run(spec, params, vels, data, labels, idx, batch, epochs=2, **kw):
    """(last epoch's metrics, trained params) of a FusedTrainer."""
    from znicz_tpu.parallel import FusedTrainer
    tr = FusedTrainer(spec=spec, params=params, vels=vels, **kw)
    for ep in range(epochs):
        m = tr.train_epoch(data, labels, idx, batch, epoch=ep, sync=True)
    return m, [np.asarray(a) for leaves in tr.params for a in leaves
               if a is not None]


class TestTrainEquivalence:
    """Merged spec trains like the split spec (and hence, by the existing
    fused-vs-unit-graph suite, like the unit graph)."""

    def _workflow(self, batch=32):
        from znicz_tpu.backends import Device
        from znicz_tpu.config import root
        from znicz_tpu.models import alexnet

        # the global config tree is restored after the build: whichever
        # test file shares this worker next reads root.alexnet too
        saved = root.alexnet.to_dict()
        try:
            root.alexnet.synthetic.update({"n_train": 2 * batch,
                                           "n_valid": batch, "n_test": 0})
            root.alexnet.update({"minibatch_size": batch, "size": 67,
                                 "n_classes": 7})
            root.alexnet.layers = alexnet.make_layers(
                n_classes=7, widths=(8, 12, 8, 8, 8, 24, 16))
            wf = alexnet.AlexNetWorkflow()
            wf.initialize(device=Device.create("xla"))
        finally:
            root.alexnet.update(saved)
        return wf

    #: batch -> the route the pairs take on the Pallas tier
    BATCHES = {8: "window", 12: "composed"}

    @pytest.mark.parametrize("storage", ["float32", "bfloat16"])
    @pytest.mark.parametrize("batch", sorted(BATCHES))
    def test_merged_equals_split(self, monkeypatch, batch, storage):
        """On the Pallas tier (the interpreter), at a batch the window
        rule admits and one it refuses, in float32 and bfloat16 storage:
        the rows ``extract_model`` ships against the unit graph's own
        rows.  Float32 on the composed route is the same arithmetic and
        agrees to the bit; on the window kernels, whose LRN is contracted
        into fused multiply-adds otherwise (the kernel tests above), the
        loss agrees to float32 rounding.  Under bfloat16 storage the
        pair's and the unit rows' kernels round apart, and two epochs
        agree to bfloat16 rounding (6.5e-5 on weights of 0.5 found
        here)."""
        from znicz_tpu.parallel import fused

        monkeypatch.setattr(tuning, "_INTERPRET", True)
        prng.seed_all(77)
        wf = self._workflow(batch)
        spec_m, params_m, vels_m = fused.extract_model(wf, storage)
        assert any(la.kind == "lrn_pool" for la in spec_m.layers)
        assert fused.pair_routes(spec_m, wf.forwards) == (
            "window:2 composed:0" if self.BATCHES[batch] == "window"
            and storage == "float32" else "window:0 composed:2")
        spec_s, params_s, vels_s = helpers.routed(wf)
        spec_s = dataclasses.replace(spec_s, storage_dtype=storage)
        assert all(la.kind != "lrn_pool" for la in spec_s.layers)

        ld = wf.loader
        args = (ld.original_data.devmem, ld.original_labels.devmem,
                np.arange(ld.class_lengths[2]), batch)
        m_m, p_m = _run(spec_m, params_m, vels_m, *args)
        m_s, p_s = _run(spec_s, params_s, vels_s, *args)
        tol = {("composed", "float32"): {"rtol": 0, "atol": 0},
               ("window", "float32"): {"rtol": 1e-6, "atol": 0}}.get(
                   (self.BATCHES[batch], storage), {"rtol": 1e-3,
                                                   "atol": 2e-4})
        np.testing.assert_allclose(np.asarray(m_m["loss"]),
                                   np.asarray(m_s["loss"]), **tol)
        assert len(p_m) == len(p_s)
        for a, b in zip(p_m, p_s):
            np.testing.assert_allclose(a, b, **tol)

    @pytest.mark.parametrize("mode", ["mesh_dp", "mesh_tp", "bf16",
                                      "accum"])
    def test_merged_and_folded_rows_train_like_the_unit_rows(self, mode):
        """Under every shipped training mode — data/tensor-parallel
        meshes, bf16 activation storage, gradient accumulation — the rows
        ``extract_model`` ships train as the unit graph's own rows do, on
        the XLA tier: to the bit."""
        from znicz_tpu.parallel import fused, make_mesh

        prng.seed_all(13)
        wf = self._workflow()
        spec_m, params_m, vels_m = fused.extract_model(wf)
        spec_s, params_s, vels_s = helpers.routed(wf)
        kw = {}
        if mode == "mesh_dp":
            kw["mesh"] = make_mesh(n_data=8, n_model=1)
        elif mode == "mesh_tp":
            kw["mesh"] = make_mesh(n_data=4, n_model=2)
        elif mode == "bf16":
            spec_m = dataclasses.replace(spec_m, storage_dtype="bfloat16")
            spec_s = dataclasses.replace(spec_s, storage_dtype="bfloat16")
        elif mode == "accum":
            kw["accum_steps"] = 2
        ld = wf.loader
        args = (np.asarray(ld.original_data.mem),
                np.asarray(ld.original_labels.mem), np.arange(64), 32)
        m_m, p_m = _run(spec_m, params_m, vels_m, *args, **kw)
        m_s, p_s = _run(spec_s, params_s, vels_s, *args, **kw)
        assert np.isfinite(np.asarray(m_m["loss"])).all()
        np.testing.assert_array_equal(np.asarray(m_m["loss"]),
                                      np.asarray(m_s["loss"]))
        assert len(p_m) == len(p_s)
        for a, b in zip(p_m, p_s):
            np.testing.assert_array_equal(a, b)


class TestRewritesAlone:
    """The paths that stay reachable from the layer list alone and that
    AlexNet's does not take, each against the rows it rewrites, on the
    XLA tier: to the bit where nothing is folded or the folded
    derivative is a mask (strict_relu); a value-dependent derivative
    (tanh: ``err * (a*y*y + b)``) is the same arithmetic traced inside
    another fusion, which XLA may contract into an FMA differently: equal
    to float32 rounding (found here: 1.5e-8 on weights of 0.2)."""

    GD = {"learning_rate": 0.05, "gradient_moment": 0.9}
    PAIR = [{"type": "norm", "->": {"n": 5}},
            {"type": "max_pooling", "->": {"kx": 3, "sliding": 2}},
            {"type": "softmax", "->": {"output_sample_shape": 10},
             "<-": GD}]
    #: name -> (layers, the rewrites of the one side, of the other; None
    #: is extract_model), what the first side's pair and the row before
    #: it hold, whether the two sides agree to the bit
    CASES = {
        # (ii) against (i): what ``nofold`` against ``fused1`` was
        "fold_against_merge": (
            [{"type": "conv_str",
              "->": {"n_kernels": 8, "kx": 5, "sliding": 2}, "<-": GD}],
            ("merge_lrn_pool", "fold_pair_act"), ("merge_lrn_pool",),
            {"fold_act": "strict_relu"}, {"act_folded": True}, True),
        # a deconv before the pair folds and stays whole
        "deconv_before_pair": (
            [{"type": "conv_tanh",
              "->": {"n_kernels": 8, "kx": 3, "padding": 1}, "<-": GD},
             {"type": "deconv_tanh",
              "->": {"n_kernels": 8, "kx": 3, "padding": 1,
                     "n_channels": 6}, "<-": GD}],
            None, (), {"fold_act": "tanh"}, {"act_folded": True}, False),
        # a pair after a linear conv merges and nothing more
        "pair_after_linear_conv": (
            [{"type": "conv",
              "->": {"n_kernels": 8, "kx": 3, "padding": 1}, "<-": GD}],
            None, (), {}, {}, True),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_bit_equal_to_the_other_rows(self, case):
        from znicz_tpu.parallel import FusedTrainer, fused

        head, one, other, pair_has, before_has, to_the_bit = \
            self.CASES[case]
        wf = helpers.tiny_workflow(head + self.PAIR, (16, 16, 3), 16)

        def model(rewrites):
            if rewrites is None:
                return fused.extract_model(wf)
            return helpers.routed(wf, *(getattr(fused, r)
                                        for r in rewrites))
        spec_a, params_a, vels_a = model(one)
        spec_b, params_b, vels_b = model(other)
        pair = next(i for i, la in enumerate(spec_a.layers)
                    if la.kind == "lrn_pool")
        marks = ("fold_act", "act_folded")
        assert {k: v for k, v in spec_a.layers[pair].cfg.items()
                if k in marks} == pair_has
        assert {k: v for k, v in spec_a.layers[pair - 1].cfg.items()
                if k in marks} == before_has
        assert spec_a != spec_b

        ld = wf.loader
        idx = np.arange(16, 48)

        def run(spec, params, vels):
            tr = FusedTrainer(spec=spec, params=params, vels=vels)
            m = tr.train_epoch(ld.original_data.devmem,
                               ld.original_labels.devmem, idx, 16)
            return m, [np.asarray(a) for leaves in tr.params
                       for a in leaves if a is not None]
        m_a, p_a = run(spec_a, params_a, vels_a)
        m_b, p_b = run(spec_b, params_b, vels_b)
        tol = ({"rtol": 0, "atol": 0} if to_the_bit
               else {"rtol": 2e-6, "atol": 1e-7})
        np.testing.assert_allclose(np.asarray(m_a["loss"]),
                                   np.asarray(m_b["loss"]), **tol)
        assert len(p_a) == len(p_b)
        for a, b in zip(p_a, p_b):
            np.testing.assert_allclose(a, b, **tol)
