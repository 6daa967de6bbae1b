"""Fused LRN → max-pool pair (forward and backward), one HBM pass each.

Parity target: the composition of the reference's ``normalization.cl/.cu``
and ``pooling.cl/.cu`` kernels (SURVEY.md §2.3 rows 3–4) as AlexNet uses
them back-to-back (conv → LRN → pool3/2, twice).

Why fuse: the pair dominates the AlexNet step (~39% per the round-2
ablation, docs/performance.md) and is pure HBM traffic.  Run separately,
the LRN output ``y`` (the net's biggest activations: (B,55,55,96) and
(B,27,27,256)) is written once and re-read once forward, and the
scattered gradient ``err_y`` is written+read again backward — plus the
pool's XLA tap stack materializes ~kh·kw/stride² more.  Computing LRN
*inside* the pooling pass eliminates ``y`` and ``err_y`` entirely: the
forward reads x and writes only the 4×-smaller pooled output + winner
offsets; the backward reads (pooled err, offsets, x) and writes dx.

TPU shape of the kernel (only constructs already proven to lower in this
repo's Mosaic kernels — lane-axis LRN window sums, contiguous second-
minor slices, flat-order winner select; no strided in-kernel loads):

* **column-parity split** — max-pool taps step the W axis by the pool
  stride (2 in every shipped config).  A stride-2 slice is not a Mosaic
  block, so x is pre-split OUTSIDE the kernel into even/odd-column
  halves (one cheap XLA pass); every pool tap then becomes a CONTIGUOUS
  slice of one half.  LRN's window runs across channels (the lane axis)
  at fixed spatial position, so it commutes with the split trivially.
* **row taps via index maps** — the H axis needs rows sh·i+t for tap row
  t; with a one-row block the BlockSpec index map expresses that stride
  directly, so the kernel reads exactly the kh rows it needs.
* **flat-order select** — taps are compared in the reference's row-major
  tap order with strict ``>`` (ties keep the first tap), bit-identical
  to ``pooling._max_pool``; the backward adds contributions in the same
  flat tap order, so the f32 accumulation order matches the split path's
  per-tap scatter exactly.

The fused pair is gated: pool stride-W must be 2 (the parity split) and
padding 0.  Everything else falls back to the composed split ops.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import normalization as lrn_math
from . import pooling as pool_ops
from . import tuning
from .geometry import norm2, out_size


def fusable(ksize, stride, padding) -> bool:
    """Whether the pallas-fused pair supports this pool geometry."""
    (sh, sw) = norm2(stride)
    (ph, pw) = norm2(padding)
    return sw == 2 and ph == 0 and pw == 0 and sh >= 1


# -- composed formulations (golden path + non-TPU dispatch) ----------------
def np_lrn_maxpool(x, n, alpha, beta, k, ksize, stride, padding,
                   use_abs=False):
    """Composed numpy golden path: → (pooled, offsets)."""
    y = lrn_math.np_lrn(x, n, alpha, beta, k)[0]
    if use_abs:
        return pool_ops.np_maxabs_pooling(y, ksize, stride, padding)
    return pool_ops.np_max_pooling(y, ksize, stride, padding)


def xla_lrn_maxpool(x, n, alpha, beta, k, ksize, stride, padding,
                    use_abs=False):
    y = lrn_math.xla_lrn(x, n, alpha, beta, k)[0]
    if use_abs:
        return pool_ops.xla_maxabs_pooling(y, ksize, stride, padding)
    return pool_ops.xla_max_pooling(y, ksize, stride, padding)


def np_gd_lrn_maxpool(errp, offsets, x, n, alpha, beta, k, ksize, stride,
                      padding, fold_act=None):
    """Composed numpy golden backward: pooled err → dx.

    ``fold_act``: name of the PRECEDING layer's activation whose
    derivative is folded in (``dx · act.bwd(·, y=x)``) — x here IS that
    layer's post-activation output, so the pair backward can emit the
    pre-activation error directly and the separate elementwise pass
    over the net's biggest tensor disappears."""
    from . import activations
    err_y = pool_ops.np_gd_max_pooling(errp, offsets, x.shape, ksize,
                                       stride, padding)
    dx = lrn_math.np_gd_lrn_x(err_y, x, n, alpha, beta, k)
    if fold_act is not None:
        dx = activations.BY_NAME[fold_act].bwd(dx, x, None, np)
    return dx


def xla_gd_lrn_maxpool(errp, offsets, x, n, alpha, beta, k, ksize,
                       stride, padding, fold_act=None):
    from . import activations
    err_y = pool_ops.xla_gd_max_pooling(errp, offsets, x.shape, ksize,
                                        stride, padding)
    dx = lrn_math.xla_gd_lrn_x(err_y, x, n, alpha, beta, k)
    if fold_act is not None:
        dx = activations.BY_NAME[fold_act].bwd(dx, x, None, jnp)
    return dx


# -- the fused Pallas pair -------------------------------------------------
def split_cols(x):
    """(x_even, x_odd): column-parity halves along W (NHWC).  Public:
    the fused path caches these INSTEAD of x for folded pairs, so the
    backward never re-splits (one fewer full HBM round-trip over the
    net's biggest activation)."""
    return x[:, :, 0::2, :], x[:, :, 1::2, :]


def interleave_cols(xe, xo, w: int):
    """Inverse of :func:`split_cols` (pads the odd half when W is odd)."""
    b, h, we, c = xe.shape
    if xo.shape[2] < we:
        xo = jnp.pad(xo, ((0, 0), (0, 0), (0, we - xo.shape[2]),
                          (0, 0)))
    return jnp.stack([xe, xo], axis=3).reshape(b, h, 2 * we, c)[:, :, :w]


def _batch_block(b: int, bytes_per_b: int, budget: int = 3 << 20) -> int:
    """Largest divisor of B whose working set fits the VMEM budget.

    ``bytes_per_b`` models the block's HBM-facing buffers only; Mosaic's
    scoped-VMEM footprint is larger — every in/out block is
    double-buffered for the grid pipeline and the kernel body's
    temporaries (LRN window sums, tap-select where-chains) live on the
    VMEM stack.  Measured on a v5e: the AlexNet pair-1 geometry
    (b=128, 55×55×96, kh=kw=3) at a 32-batch block needs 16.54 MB
    scoped VMEM — past the 16 MB/core limit.  A 3 MB budget halves the
    block (bb=16 ⇒ ~8.3 MB) and leaves ~2× headroom at every shipped
    geometry."""
    cap = max(1, budget // max(1, bytes_per_b))
    best = 1
    for d in range(1, b + 1):
        if b % d == 0 and d <= cap:
            best = d
    return best


def _lrn_pool_fwd_kernel(*refs, kh, kw, ow, n, alpha, beta, k, use_abs):
    """refs: kh×even tiles, kh×odd tiles, y_out, idx_out.

    Each even/odd tile is (Bb, 1, We|Wo, C).  LRN runs per row tap (on
    the f32 cast), taps are selected in flat row-major order with strict
    ``>`` — bit-identical values/offsets to the composed split ops."""
    xe_refs = refs[:kh]
    xo_refs = refs[kh:2 * kh]
    y_ref, idx_ref = refs[2 * kh], refs[2 * kh + 1]
    best = None
    best_val = None
    idx = None
    for t in range(kh):
        ye = lrn_math._fwd(xe_refs[t][:].astype(jnp.float32),
                           n, alpha, beta, k, jnp)[0].astype(y_ref.dtype)
        yo = lrn_math._fwd(xo_refs[t][:].astype(jnp.float32),
                           n, alpha, beta, k, jnp)[0].astype(y_ref.dtype)
        for ct in range(kw):
            half = ye if ct % 2 == 0 else yo
            off = ct // 2
            tap = half[:, :, off:off + ow, :]
            score = jnp.abs(tap) if use_abs else tap
            flat = t * kw + ct
            if best is None:
                best, best_val = score, tap
                idx = jnp.zeros(tap.shape, jnp.int32)
            else:
                take = score > best
                best = jnp.where(take, score, best)
                best_val = jnp.where(take, tap, best_val)
                idx = jnp.where(take, jnp.int32(flat), idx)
    y_ref[:] = best_val
    idx_ref[:] = idx


def pallas_lrn_maxpool(x, n, alpha, beta, k, ksize, stride, padding,
                       use_abs=False):
    """Fused forward: x → (pooled, offsets); y never touches HBM."""
    xe, xo = split_cols(x)
    return pallas_lrn_maxpool_split(xe, xo, n, alpha, beta, k, ksize,
                                    stride, padding, use_abs)


@functools.partial(jax.jit, static_argnames=(
    "n", "alpha", "beta", "k", "ksize", "stride", "padding", "use_abs"))
def pallas_lrn_maxpool_split(xe, xo, n, alpha, beta, k, ksize, stride,
                             padding, use_abs=False):
    """Fused forward over pre-split column-parity halves (the caller
    may keep xe/xo as the backward cache — see split_cols)."""
    (kh, kw), (sh, sw) = norm2(ksize), norm2(stride)
    assert fusable(ksize, stride, padding), "gate with fusable() first"
    b, h, _, c = xe.shape
    w = xe.shape[2] + xo.shape[2]
    oh, ow = out_size(h, kh, sh, 0), out_size(w, kw, sw, 0)
    we, wo = xe.shape[2], xo.shape[2]
    bytes_per_b = 4 * c * (kh * (we + wo) + 4 * we + 2 * ow)
    bb = _batch_block(b, bytes_per_b)

    e_spec = [pl.BlockSpec((bb, 1, we, c),
                           lambda bi, i, t=t: (bi, sh * i + t, 0, 0))
              for t in range(kh)]
    o_spec = [pl.BlockSpec((bb, 1, wo, c),
                           lambda bi, i, t=t: (bi, sh * i + t, 0, 0))
              for t in range(kh)]
    out_spec = pl.BlockSpec((bb, 1, ow, c), lambda bi, i: (bi, i, 0, 0))
    y, idx = pl.pallas_call(
        functools.partial(_lrn_pool_fwd_kernel, kh=kh, kw=kw, ow=ow,
                          n=n, alpha=alpha, beta=beta, k=k,
                          use_abs=use_abs),
        grid=(b // bb, oh),
        in_specs=e_spec + o_spec,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, oh, ow, c), xe.dtype),
                   jax.ShapeDtypeStruct((b, oh, ow, c), jnp.int32)],
        name="pallas_lrn_maxpool_split",
        interpret=tuning.interpret_mode(),
    )(*([xe] * kh + [xo] * kh))
    return y, idx


def _lrn_pool_bwd_kernel(*refs, kh, kw, sh, oh, ow, we, wo, n, alpha,
                         beta, k, n_contrib, fold_act):
    """refs: xe_row, xo_row, n_contrib×errp rows, n_contrib×idx rows,
    dxe_out, dxo_out.

    Input row h receives pooled-err contributions from output rows
    i = h//sh − m (m ascending ⇒ tap row t = h−sh·i ascending), each
    masked by offset equality and placed at its column-parity offset —
    the same flat-tap addition order as the composed scatter.  The LRN
    backward then recomputes the denominator from x in VMEM."""
    xe_ref, xo_ref = refs[0], refs[1]
    errp_refs = refs[2:2 + n_contrib]
    idx_refs = refs[2 + n_contrib:2 + 2 * n_contrib]
    dxe_ref, dxo_ref = refs[2 + 2 * n_contrib], refs[3 + 2 * n_contrib]
    h = pl.program_id(1)
    shp = errp_refs[0].shape                      # (Bb, 1, OW, C)
    err_even = jnp.zeros(shp[:2] + (we, shp[3]), jnp.float32)
    err_odd = jnp.zeros(shp[:2] + (wo, shp[3]), jnp.float32)
    for m in range(n_contrib):
        i_raw = h // sh - m                       # traced scalar
        t = h - sh * i_raw
        valid = (i_raw >= 0) & (i_raw < oh) & (t < kh)
        e = errp_refs[m][:].astype(jnp.float32)
        ix = idx_refs[m][:]
        for ct in range(kw):
            mask = (ix == t * kw + ct) & valid
            contrib = jnp.where(mask, e, jnp.float32(0.0))
            off = ct // 2
            if ct % 2 == 0:
                err_even = err_even + jnp.pad(
                    contrib,
                    ((0, 0), (0, 0), (off, we - ow - off), (0, 0)))
            else:
                err_odd = err_odd + jnp.pad(
                    contrib,
                    ((0, 0), (0, 0), (off, wo - ow - off), (0, 0)))
    xe = xe_ref[:].astype(jnp.float32)
    xo = xo_ref[:].astype(jnp.float32)
    dxe = lrn_math._bwd_recompute(err_even, xe, n, alpha, beta, k, jnp)
    dxo = lrn_math._bwd_recompute(err_odd, xo, n, alpha, beta, k, jnp)
    if fold_act is not None:
        # the preceding layer's activation derivative (needs y only,
        # and y IS this x) — emits the pre-activation error in the same
        # pass, saving the separate elementwise sweep over dx.  y is
        # passed in its STORAGE dtype (the raw ref value), exactly as
        # the split path's act.bwd sees it — keeps bf16-storage
        # bit-equality for value-dependent derivatives (tanh/sigmoid)
        from . import activations
        act = activations.BY_NAME[fold_act]
        dxe = act.bwd(dxe, xe_ref[:], None, jnp)
        dxo = act.bwd(dxo, xo_ref[:], None, jnp)
    dxe_ref[:] = dxe
    dxo_ref[:] = dxo


def pallas_gd_lrn_maxpool(errp, offsets, x, n, alpha, beta, k, ksize,
                          stride, padding, fold_act=None):
    """Fused backward: (pooled err, offsets, x) → dx; err_y never
    touches HBM.  ``fold_act`` additionally folds the preceding
    layer's activation derivative (y-only activations) into the same
    pass — see np_gd_lrn_maxpool."""
    xe, xo = split_cols(x)
    return pallas_gd_lrn_maxpool_split(errp, offsets, xe, xo, n, alpha,
                                       beta, k, ksize, stride, padding,
                                       fold_act)


@functools.partial(jax.jit, static_argnames=(
    "n", "alpha", "beta", "k", "ksize", "stride", "padding",
    "fold_act", "return_split"))
def pallas_gd_lrn_maxpool_split(errp, offsets, xe, xo, n, alpha, beta,
                                k, ksize, stride, padding,
                                fold_act=None, return_split=False):
    """Fused backward over pre-split halves — when the forward cached
    (xe, xo) the re-split of x disappears entirely.  ``return_split``
    hands the (dxe, dxo) halves back un-interleaved (phase-2: the
    split-out conv's gradients consume them directly)."""
    (kh, kw), (sh, sw) = norm2(ksize), norm2(stride)
    assert fusable(ksize, stride, padding), "gate with fusable() first"
    b, h, _, c = xe.shape
    w = xe.shape[2] + xo.shape[2]
    _, oh, ow, _ = errp.shape
    we, wo = xe.shape[2], xo.shape[2]
    n_contrib = (kh + sh - 1) // sh
    bytes_per_b = 4 * c * (we + wo + 2 * n_contrib * ow
                           + 3 * (we + wo))
    bb = _batch_block(b, bytes_per_b)

    def row_spec(width):
        return pl.BlockSpec((bb, 1, width, c), lambda bi, i: (bi, i, 0, 0))

    def contrib_spec(m):
        def imap(bi, i, m=m):
            j = i // sh - m
            return (bi, jnp.clip(j, 0, oh - 1), 0, 0)
        return pl.BlockSpec((bb, 1, ow, c), imap)

    dxe, dxo = pl.pallas_call(
        functools.partial(_lrn_pool_bwd_kernel, kh=kh, kw=kw, sh=sh,
                          oh=oh, ow=ow, we=we, wo=wo, n=n, alpha=alpha,
                          beta=beta, k=k, n_contrib=n_contrib,
                          fold_act=fold_act),
        grid=(b // bb, h),
        in_specs=([row_spec(we), row_spec(wo)]
                  + [contrib_spec(m) for m in range(n_contrib)] * 2),
        out_specs=[row_spec(we), row_spec(wo)],
        out_shape=[jax.ShapeDtypeStruct((b, h, we, c), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, wo, c), jnp.float32)],
        name="pallas_gd_lrn_maxpool_split",
        interpret=tuning.interpret_mode(),
    )(xe, xo, *([errp] * n_contrib + [offsets] * n_contrib))
    if return_split:
        return dxe, dxo
    # interleave the parity halves back: (..., We, 2, C) → (..., 2·We, C)
    return interleave_cols(dxe, dxo, w)


# -- dispatchers -----------------------------------------------------------
def lrn_maxpool(x, n, alpha, beta, k, ksize, stride, padding,
                use_abs=False):
    if tuning.use_pallas() and fusable(ksize, stride, padding):
        return tuning.batch_sharded(
            lambda x: pallas_lrn_maxpool(x, n, alpha, beta, k, ksize,
                                         stride, padding, use_abs), x)
    return xla_lrn_maxpool(x, n, alpha, beta, k, ksize, stride, padding,
                           use_abs)


def gd_lrn_maxpool(errp, offsets, x, n, alpha, beta, k, ksize, stride,
                   padding, fold_act=None):
    if tuning.use_pallas() and fusable(ksize, stride, padding):
        return tuning.batch_sharded(
            lambda errp, offsets, x: pallas_gd_lrn_maxpool(
                errp, offsets, x, n, alpha, beta, k, ksize, stride,
                padding, fold_act), errp, offsets, x)
    return xla_gd_lrn_maxpool(errp, offsets, x, n, alpha, beta, k, ksize,
                              stride, padding, fold_act)


def lrn_maxpool_split(xe, xo, n, alpha, beta, k, ksize, stride, padding,
                      use_abs=False):
    """Split-input dispatcher (the fused path's cache-the-halves mode:
    forward consumes and the backward reuses xe/xo, so x is never
    re-split).  The XLA tier re-interleaves — it has no split gain."""
    if tuning.use_pallas() and fusable(ksize, stride, padding):
        return tuning.batch_sharded(
            lambda xe, xo: pallas_lrn_maxpool_split(
                xe, xo, n, alpha, beta, k, ksize, stride, padding,
                use_abs), xe, xo)
    w = xe.shape[2] + xo.shape[2]
    return xla_lrn_maxpool(interleave_cols(xe, xo, w), n, alpha, beta,
                           k, ksize, stride, padding, use_abs)


def gd_lrn_maxpool_split(errp, offsets, xe, xo, n, alpha, beta, k,
                         ksize, stride, padding, fold_act=None,
                         return_split=False):
    if tuning.use_pallas() and fusable(ksize, stride, padding):
        return tuning.batch_sharded(
            lambda errp, offsets, xe, xo: pallas_gd_lrn_maxpool_split(
                errp, offsets, xe, xo, n, alpha, beta, k, ksize, stride,
                padding, fold_act, return_split),
            errp, offsets, xe, xo)
    w = xe.shape[2] + xo.shape[2]
    dx = xla_gd_lrn_maxpool(errp, offsets,
                            interleave_cols(xe, xo, w), n, alpha, beta,
                            k, ksize, stride, padding, fold_act)
    return split_cols(dx) if return_split else dx
