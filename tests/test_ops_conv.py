"""Conv-stack op tests: numpy golden vs XLA vs jax.grad (SURVEY.md §4
backend-equivalence pattern) for conv, pooling, LRN, dropout, rngbits."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.ops import conv, dropout, normalization, pooling, rngbits

CONV_CASES = [
    # (h, w, c, oc, kh, kw, stride, pad)
    (8, 8, 3, 5, 3, 3, 1, 1),
    (9, 7, 4, 6, 3, 2, 2, 1),
    (12, 12, 2, 3, 5, 5, 3, 2),
    (6, 6, 1, 2, 2, 2, 2, 0),
    (11, 5, 3, 4, 3, 3, (2, 1), (1, 0)),
]


@pytest.mark.parametrize("case", CONV_CASES)
def test_conv_forward_tiers_agree(case):
    h, w, c, oc, kh, kw, s, p = case
    rng = np.random.default_rng(7)
    x = rng.normal(size=(2, h, w, c)).astype(np.float32)
    wt = rng.normal(size=(kh, kw, c, oc)).astype(np.float32)
    y_np = conv.np_conv2d(x, wt, s, p)
    y_x = np.asarray(conv.xla_conv2d(jnp.asarray(x), jnp.asarray(wt), s, p))
    np.testing.assert_allclose(y_np, y_x, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", CONV_CASES)
def test_conv_hand_gradients_match_jax_grad(case):
    h, w, c, oc, kh, kw, s, p = case
    rng = np.random.default_rng(11)
    x = rng.normal(size=(2, h, w, c)).astype(np.float32)
    wt = rng.normal(size=(kh, kw, c, oc)).astype(np.float32)
    err = rng.normal(size=conv.np_conv2d(x, wt, s, p).shape
                     ).astype(np.float32)

    def scalar(x_, w_):
        return jnp.sum(conv.xla_conv2d(x_, w_, s, p) * err)

    gx_ref, gw_ref = jax.grad(scalar, argnums=(0, 1))(
        jnp.asarray(x), jnp.asarray(wt))
    # numpy hand-written golden
    np.testing.assert_allclose(
        conv.np_conv2d_grad_input(err, wt, x.shape, s, p), gx_ref,
        atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(
        conv.np_conv2d_grad_weights(x, err, wt.shape, s, p), gw_ref,
        atol=1e-3, rtol=1e-3)
    # hand-written XLA formulations
    np.testing.assert_allclose(
        np.asarray(conv.xla_conv2d_grad_input(
            jnp.asarray(err), jnp.asarray(wt), x.shape, s, p)),
        gx_ref, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(conv.xla_conv2d_grad_weights(
            jnp.asarray(x), jnp.asarray(err), wt.shape, s, p)),
        gw_ref, atol=1e-3, rtol=1e-3)


POOL_CASES = [
    # (h, w, c, ksize, stride, pad)
    (8, 8, 3, 2, 2, 0),
    (9, 9, 2, 3, 2, 1),
    (6, 10, 4, (2, 3), (2, 3), 0),
    (7, 7, 1, 3, 3, 1),
]


@pytest.mark.parametrize("case", POOL_CASES)
@pytest.mark.parametrize("kind", ["max", "maxabs", "avg"])
def test_pooling_tiers_agree(case, kind):
    h, w, c, k, s, p = case
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, h, w, c)).astype(np.float32)
    if kind == "avg":
        y_np = pooling.np_avg_pooling(x, k, s, p)
        y_x = np.asarray(pooling.xla_avg_pooling(jnp.asarray(x), k, s, p))
        np.testing.assert_allclose(y_np, y_x, atol=1e-5, rtol=1e-5)
        return
    fn_np = (pooling.np_max_pooling if kind == "max"
             else pooling.np_maxabs_pooling)
    fn_x = (pooling.xla_max_pooling if kind == "max"
            else pooling.xla_maxabs_pooling)
    y_np, off_np = fn_np(x, k, s, p)
    y_x, off_x = fn_x(jnp.asarray(x), k, s, p)
    np.testing.assert_allclose(y_np, np.asarray(y_x), atol=1e-6)
    np.testing.assert_array_equal(off_np, np.asarray(off_x))


@pytest.mark.parametrize("case", POOL_CASES)
def test_max_pooling_backward_matches_jax_grad(case):
    h, w, c, k, s, p = case
    rng = np.random.default_rng(5)
    # distinct values → unique argmax → jax.grad of reduce-max comparable
    x = rng.permutation(2 * h * w * c).reshape(2, h, w, c) \
        .astype(np.float32)
    y_np, off = pooling.np_max_pooling(x, k, s, p)
    err = rng.normal(size=y_np.shape).astype(np.float32)

    def scalar(x_):
        y, _ = pooling.xla_max_pooling(x_, k, s, p)
        return jnp.sum(y * err)

    gx_ref = jax.grad(scalar)(jnp.asarray(x))
    gx_np = pooling.np_gd_max_pooling(err, off, x.shape, k, s, p)
    np.testing.assert_allclose(gx_np, np.asarray(gx_ref), atol=1e-4)
    gx_x = pooling.xla_gd_max_pooling(jnp.asarray(err), jnp.asarray(off),
                                      x.shape, k, s, p)
    np.testing.assert_allclose(gx_np, np.asarray(gx_x), atol=1e-6)


@pytest.mark.parametrize("case", POOL_CASES)
def test_avg_pooling_backward_matches_jax_grad(case):
    h, w, c, k, s, p = case
    rng = np.random.default_rng(6)
    x = rng.normal(size=(2, h, w, c)).astype(np.float32)
    y = pooling.np_avg_pooling(x, k, s, p)
    err = rng.normal(size=y.shape).astype(np.float32)

    def scalar(x_):
        return jnp.sum(pooling.xla_avg_pooling(x_, k, s, p) * err)

    gx_ref = jax.grad(scalar)(jnp.asarray(x))
    gx_np = pooling.np_gd_avg_pooling(err, x.shape, k, s, p)
    np.testing.assert_allclose(gx_np, np.asarray(gx_ref), atol=1e-4)
    gx_x = pooling.xla_gd_avg_pooling(jnp.asarray(err), x.shape, k, s, p)
    np.testing.assert_allclose(gx_np, np.asarray(gx_x), atol=1e-6)


def test_stochastic_pooling_numpy_vs_xla_same_mask():
    rng = np.random.default_rng(8)
    x = np.abs(rng.normal(size=(2, 8, 8, 3))).astype(np.float32)
    u = pooling.stochastic_uniform(42, (1, 2, 3), (2, 4, 4, 3), xp=np)
    u_j = pooling.stochastic_uniform(42, (1, 2, 3), (2, 4, 4, 3), xp=jnp)
    np.testing.assert_array_equal(u, np.asarray(u_j))
    y_np, idx_np = pooling.np_stochastic_pooling(x, 2, 2, 0, u)
    y_x, idx_x = pooling.xla_stochastic_pooling(jnp.asarray(x), 2, 2, 0,
                                                jnp.asarray(u))
    np.testing.assert_allclose(y_np, np.asarray(y_x), atol=1e-6)
    np.testing.assert_array_equal(idx_np, np.asarray(idx_x))
    # sampled value is always a window member with positive weight
    assert ((idx_np >= 0) & (idx_np < 4)).all()
    # deterministic (eval) mode: probability-weighted average
    y_det, _ = pooling.np_stochastic_pooling(x, 2, 2, 0, None,
                                             deterministic=True)
    assert y_det.shape == y_np.shape
    assert (y_det <= x.reshape(2, 4, 2, 4, 2, 3).max((2, 4)) + 1e-6).all()


def test_lrn_tiers_and_gradient():
    rng = np.random.default_rng(9)
    x = rng.normal(size=(2, 4, 4, 16)).astype(np.float32)
    y_np, d_np = normalization.np_lrn(x)
    y_x, d_x = normalization.xla_lrn(jnp.asarray(x))
    np.testing.assert_allclose(y_np, np.asarray(y_x), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(d_np, np.asarray(d_x), atol=1e-5, rtol=1e-5)
    err = rng.normal(size=y_np.shape).astype(np.float32)

    def scalar(x_):
        y, _ = normalization.xla_lrn(x_)
        return jnp.sum(y * err)

    gx_ref = jax.grad(scalar)(jnp.asarray(x))
    gx_np = normalization.np_gd_lrn(err, x, d_np)
    np.testing.assert_allclose(gx_np, np.asarray(gx_ref), atol=1e-4,
                               rtol=1e-4)
    gx_x = normalization.xla_gd_lrn(jnp.asarray(err), jnp.asarray(x), d_x)
    np.testing.assert_allclose(gx_np, np.asarray(gx_x), atol=1e-5,
                               rtol=1e-5)


def test_rngbits_numpy_jnp_bit_identical():
    key_np = rngbits.fold(12345, 3, 7, 11, xp=np)
    key_j = rngbits.fold(12345, 3, 7, 11, xp=jnp)
    assert int(key_np) == int(np.asarray(key_j))
    u_np = rngbits.uniform01(key_np, 1000, xp=np)
    u_j = rngbits.uniform01(key_j, 1000, xp=jnp)
    np.testing.assert_array_equal(u_np, np.asarray(u_j))
    assert (u_np >= 0).all() and (u_np < 1).all()
    # distribution sanity: roughly uniform
    assert abs(u_np.mean() - 0.5) < 0.05


def test_rngbits_jit_traceable_counters():
    @jax.jit
    def f(epoch, mb):
        key = rngbits.fold(99, epoch, mb, xp=jnp)
        return rngbits.uniform01(key, 16, xp=jnp)

    a = np.asarray(f(0, 1))
    b = rngbits.uniform01(rngbits.fold(99, 0, 1, xp=np), 16, xp=np)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(np.asarray(f(0, 2)), a)


def test_dropout_mask_identical_and_backward():
    mask_np = dropout.make_mask(777, (1, 2, 3), (32, 16), 0.4, xp=np)
    mask_j = dropout.make_mask(777, (1, 2, 3), (32, 16), 0.4, xp=jnp)
    np.testing.assert_array_equal(mask_np, np.asarray(mask_j))
    vals = np.unique(mask_np)
    assert set(np.round(vals, 5)) <= {0.0, np.float32(np.round(1 / 0.6, 5))}
    keep_frac = (mask_np > 0).mean()
    assert 0.45 < keep_frac < 0.75          # ≈ 0.6
    x = np.random.default_rng(1).normal(size=(32, 16)).astype(np.float32)
    err = np.ones_like(x)
    np.testing.assert_allclose(dropout.np_dropout(x, mask_np),
                               x * mask_np)
    np.testing.assert_allclose(dropout.np_gd_dropout(err, mask_np),
                               mask_np)


class TestRandomGeometrySweep:
    """Seeded random conv/pool geometries beyond the hand-picked cases:
    numpy golden vs XLA vs jax.grad over ~a dozen configurations each —
    the backend-equivalence contract at fuzz breadth (SURVEY.md §4)."""

    def test_conv_fwd_and_grads(self):
        gen = np.random.default_rng(20260730)
        for _ in range(10):
            b = int(gen.integers(1, 4))
            h = int(gen.integers(4, 13))
            w_ = int(gen.integers(4, 13))
            cin = int(gen.integers(1, 6))
            cout = int(gen.integers(1, 7))
            kh = int(gen.integers(1, min(h, 5) + 1))
            kw = int(gen.integers(1, min(w_, 5) + 1))
            stride = int(gen.integers(1, 3))
            # padding < kernel: every real conv config satisfies this,
            # and padding ≥ kernel aborts XLA-CPU's transposed-conv
            # compiler (negative padding in the lhs transpose)
            pad = int(gen.integers(0, min(kh, kw)))
            x = gen.standard_normal((b, h, w_, cin)).astype(np.float32)
            wgt = gen.standard_normal((kh, kw, cin, cout)).astype(
                np.float32) * 0.2
            y_np = conv.np_conv2d(x, wgt, stride, pad)
            y_x = np.asarray(conv.xla_conv2d(jnp.asarray(x),
                                             jnp.asarray(wgt), stride,
                                             pad))
            np.testing.assert_allclose(
                y_x, y_np, rtol=2e-4, atol=2e-5,
                err_msg=f"fwd {b,h,w_,cin,cout,kh,kw,stride,pad}")
            err = gen.standard_normal(y_np.shape).astype(np.float32)
            gw_np = conv.np_conv2d_grad_weights(x, err, wgt.shape,
                                                stride, pad)
            gx_np = conv.np_conv2d_grad_input(err, wgt, x.shape,
                                              stride, pad)
            # jax.grad cross-check: the hand-written grads must be the
            # true derivative
            loss = lambda xx, ww: jnp.sum(          # noqa: E731
                conv.xla_conv2d(xx, ww, stride, pad)
                * jnp.asarray(err))
            gx_j = np.asarray(jax.grad(loss, 0)(jnp.asarray(x),
                                                jnp.asarray(wgt)))
            gw_j = np.asarray(jax.grad(loss, 1)(jnp.asarray(x),
                                                jnp.asarray(wgt)))
            np.testing.assert_allclose(
                gx_np, gx_j, rtol=3e-4, atol=3e-5,
                err_msg=f"gx {b,h,w_,cin,cout,kh,kw,stride,pad}")
            np.testing.assert_allclose(
                gw_np, gw_j, rtol=3e-4, atol=3e-5,
                err_msg=f"gw {b,h,w_,cin,cout,kh,kw,stride,pad}")

    def test_pool_fwd_and_scatter(self):
        from znicz_tpu.ops import pooling as pool
        gen = np.random.default_rng(123456)
        for _ in range(12):
            b = int(gen.integers(1, 4))
            h = int(gen.integers(3, 12))
            w_ = int(gen.integers(3, 12))
            c = int(gen.integers(1, 6))
            kh = int(gen.integers(1, min(h, 4) + 1))
            kw = int(gen.integers(1, min(w_, 4) + 1))
            stride = int(gen.integers(1, 4))
            pad = int(gen.integers(0, min(kh, kw)))
            x = gen.standard_normal((b, h, w_, c)).astype(np.float32)
            y_np, off_np = pool.np_max_pooling(x, (kh, kw),
                                               (stride, stride), pad)
            y_x, off_x = pool.max_pooling(jnp.asarray(x), (kh, kw),
                                          (stride, stride), pad)
            np.testing.assert_allclose(
                np.asarray(y_x), y_np, rtol=1e-6, atol=1e-7,
                err_msg=f"pool {b,h,w_,c,kh,kw,stride,pad}")
            err = gen.standard_normal(y_np.shape).astype(np.float32)
            gx_np = pool.np_gd_max_pooling(err, off_np, x.shape,
                                           (kh, kw), (stride, stride),
                                           pad)
            gx_x = pool.gd_max_pooling(jnp.asarray(err),
                                       jnp.asarray(off_np), x.shape,
                                       (kh, kw), (stride, stride), pad)
            np.testing.assert_allclose(
                np.asarray(gx_x), gx_np, rtol=1e-6, atol=1e-7,
                err_msg=f"gd_pool {b,h,w_,c,kh,kw,stride,pad}")
