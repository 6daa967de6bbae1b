"""Pallas kernel tests, interpret mode on CPU (SURVEY.md §2.3 native
kernel parity): each kernel must reproduce its numpy golden / XLA tier
bit-for-bit (dropout RNG) or to f32 tolerance (math kernels)."""

import numpy as np
import pytest

import jax.numpy as jnp

from znicz_tpu import prng
from znicz_tpu.ops import (activations, dropout as drop_ops,
                           elementwise, normalization as lrn_ops,
                           pooling as pool_ops, tuning)


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setattr(tuning, "_INTERPRET", True)
    yield


def _x(shape, stream="x"):
    return np.asarray(prng.get(stream).normal(size=shape), np.float32)


class TestActivationKernels:
    @pytest.mark.parametrize("name", ["tanh", "relu", "strict_relu",
                                      "sigmoid", "log", "sincos", "mul",
                                      "tanhlog"])
    def test_fwd_bwd_vs_golden(self, name):
        act = activations.BY_NAME[name]
        x = _x((13, 37)) * 0.8          # odd sizes exercise padding
        y_ref = act.fwd(x, np)
        y = elementwise.pallas_act_fwd(name, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-5,
                                   atol=1e-5)
        err = _x((13, 37), "err")
        e_ref = act.bwd(err, y_ref, x if act.needs_input else None, np)
        e = elementwise.pallas_act_bwd(
            name, jnp.asarray(err), jnp.asarray(y_ref),
            jnp.asarray(x) if act.needs_input else None)
        np.testing.assert_allclose(np.asarray(e), e_ref, rtol=1e-5,
                                   atol=1e-5)


class TestDropoutKernel:
    def test_bit_identical_to_golden(self):
        x = _x((7, 50, 3))
        seed, counters, ratio = 1234, (11, 2, 300), 0.4
        mask = drop_ops.make_mask(seed, counters, x.shape, ratio, np)
        ref = x * mask
        out = elementwise.pallas_dropout(jnp.asarray(x), seed, counters,
                                         ratio)
        np.testing.assert_array_equal(np.asarray(out), ref)

    def test_dispatcher(self):
        x = _x((4, 32))
        out = drop_ops.dropout_apply(jnp.asarray(x), 9, (1, 2, 3), 0.5)
        mask = drop_ops.make_mask(9, (1, 2, 3), x.shape, 0.5, np)
        np.testing.assert_array_equal(np.asarray(out), x * mask)


class TestLRNKernel:
    def test_fwd_bwd_vs_golden(self):
        x = _x((3, 5, 5, 19))
        y_ref, d_ref = lrn_ops.np_lrn(x, 5, 1e-4, 0.75, 2.0)
        y, d = elementwise.pallas_lrn(jnp.asarray(x), 5, 1e-4, 0.75, 2.0)
        np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(d), d_ref, rtol=1e-5,
                                   atol=1e-6)
        err = _x((3, 5, 5, 19), "err")
        e_ref = lrn_ops.np_gd_lrn(err, x, d_ref, 5, 1e-4, 0.75, 2.0)
        e = elementwise.pallas_gd_lrn(jnp.asarray(err), jnp.asarray(x),
                                      jnp.asarray(d_ref), 5, 1e-4, 0.75,
                                      2.0)
        np.testing.assert_allclose(np.asarray(e), e_ref, rtol=1e-5,
                                   atol=1e-6)

    def test_remat_variants_match_cached(self):
        """lrn_y / gd_lrn_x (no cached denom — the fused path's forms)
        must agree with the cached-denom kernels bit-for-bit: identical
        expressions evaluated over the same x, just fewer HBM passes."""
        x = _x((3, 5, 5, 19))
        err = _x((3, 5, 5, 19), "err")
        y_cached, d = elementwise.pallas_lrn(jnp.asarray(x))
        y = elementwise.pallas_lrn_y(jnp.asarray(x))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_cached))
        e_cached = elementwise.pallas_gd_lrn(jnp.asarray(err),
                                             jnp.asarray(x), d)
        e = elementwise.pallas_gd_lrn_x(jnp.asarray(err), jnp.asarray(x))
        np.testing.assert_array_equal(np.asarray(e), np.asarray(e_cached))
        # numpy golden for the recompute form
        e_np = lrn_ops.np_gd_lrn_x(err, x)
        np.testing.assert_allclose(np.asarray(e), e_np, rtol=1e-5,
                                   atol=1e-6)


class TestKohonenKernel:
    def test_distance_argmin_vs_golden(self):
        from znicz_tpu.ops import kohonen as som_ops
        x = _x((13, 37))                 # odd sizes exercise padding
        w = _x((150, 37), "w")           # >128 neurons: two neuron tiles
        win_ref, d_ref = som_ops.np_forward(x, w)
        win, dmin = som_ops.pallas_distance_argmin(jnp.asarray(x),
                                                   jnp.asarray(w))
        np.testing.assert_array_equal(np.asarray(win), win_ref)
        np.testing.assert_allclose(np.asarray(dmin), d_ref.min(axis=1),
                                   rtol=1e-4, atol=1e-4)

    def test_single_tile(self):
        from znicz_tpu.ops import kohonen as som_ops
        x = _x((4, 8))
        w = _x((9, 8), "w")              # 3x3 SOM, one padded tile
        win_ref, _ = som_ops.np_forward(x, w)
        win, _ = som_ops.pallas_distance_argmin(jnp.asarray(x),
                                                jnp.asarray(w))
        np.testing.assert_array_equal(np.asarray(win), win_ref)


class TestPoolSelectKernel:
    @pytest.mark.parametrize("use_abs", [False, True])
    def test_vs_golden(self, use_abs):
        x = _x((2, 6, 6, 5))
        golden = (pool_ops.np_maxabs_pooling if use_abs
                  else pool_ops.np_max_pooling)
        y_ref, idx_ref = golden(x, (2, 2), (2, 2), (0, 0))
        y, idx = pool_ops._pallas_max_pool(jnp.asarray(x), (2, 2), (2, 2),
                                           (0, 0), use_abs)
        np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(idx), idx_ref)

    def test_scatter_backward_vs_golden(self):
        x = _x((2, 6, 6, 5))
        _, idx = pool_ops.np_max_pooling(x, (2, 2), (2, 2), (0, 0))
        err = _x((2, 3, 3, 5), "err")
        ref = pool_ops.np_gd_max_pooling(err, idx, x.shape, (2, 2),
                                         (2, 2), (0, 0))
        dx = pool_ops.gd_max_pooling(jnp.asarray(err), jnp.asarray(idx),
                                     x.shape, (2, 2), (2, 2), (0, 0))
        np.testing.assert_allclose(np.asarray(dx), ref, rtol=1e-6)

    def test_scatter_backward_overlapping(self):
        x = _x((2, 7, 7, 3))
        _, idx = pool_ops.np_max_pooling(x, (3, 3), (2, 2), (1, 1))
        err = _x(idx.shape, "err")
        ref = pool_ops.np_gd_max_pooling(err, idx, x.shape, (3, 3),
                                         (2, 2), (1, 1))
        dx = pool_ops.gd_max_pooling(jnp.asarray(err), jnp.asarray(idx),
                                     x.shape, (3, 3), (2, 2), (1, 1))
        np.testing.assert_allclose(np.asarray(dx), ref, rtol=1e-6,
                                   atol=1e-6)

    def test_depool_roundtrip(self):
        x = _x((2, 6, 6, 5))
        y, idx = pool_ops.np_max_pooling(x, (2, 2), (2, 2), (0, 0))
        up_ref = pool_ops.np_depooling(y, idx, x.shape, (2, 2), (2, 2),
                                       (0, 0))
        up = pool_ops.depooling(jnp.asarray(y), jnp.asarray(idx), x.shape,
                                (2, 2), (2, 2), (0, 0))
        np.testing.assert_allclose(np.asarray(up), up_ref, rtol=1e-6)
        err = _x(x.shape, "err")
        g_ref = pool_ops.np_gd_depooling(err, idx, (2, 2), (2, 2), (0, 0))
        g = pool_ops.gd_depooling(jnp.asarray(err), jnp.asarray(idx),
                                  (2, 2), (2, 2), (0, 0))
        np.testing.assert_allclose(np.asarray(g), g_ref, rtol=1e-6)

    def test_overlapping_padded(self):
        x = _x((2, 7, 7, 3))
        y_ref, idx_ref = pool_ops.np_max_pooling(x, (3, 3), (2, 2), (1, 1))
        y, idx = pool_ops._pallas_max_pool(jnp.asarray(x), (3, 3), (2, 2),
                                           (1, 1), False)
        np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(idx), idx_ref)
        # round-trip: the scatter backward accepts the Pallas offsets
        err = _x(y_ref.shape, "err")
        dx = pool_ops.np_gd_max_pooling(err, np.asarray(idx), x.shape,
                                        (3, 3), (2, 2), (1, 1))
        dx_ref = pool_ops.np_gd_max_pooling(err, idx_ref, x.shape,
                                            (3, 3), (2, 2), (1, 1))
        np.testing.assert_allclose(dx, dx_ref)
