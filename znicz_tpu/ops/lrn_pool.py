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

On the Pallas tier the pair has TWO kernel families, picked by
:func:`windowed` from the operands alone (no option); both share the
winner rule — taps compared in the reference's row-major order with
strict ``>`` (ties keep the first tap, ``elementwise._winner``),
bit-identical to ``pooling._max_pool`` — and add the backward's
contributions in the same flat tap order, so the float32 sums match the
composed per-tap scatter exactly.

* **Window kernels** (``pallas_lrn_maxpool_window`` /
  ``pallas_gd_lrn_maxpool_window``; PR 33): float32 activations at a
  batch that is a multiple of 8.  x is viewed ``(H, W, B, C)``, the
  layout XLA's TPU convolutions emit and consume — inside the step the
  transposes are bitcasts and no copy stands beside a kernel — so the
  (8, 128) register tiles lie over (batch, channel) and are whole
  whatever H and W are, and BOTH window axes are leading dims of a
  block.  Forward, a block is ``sh·R`` input rows × all of W × 8 batch
  rows × all of C, with the ``kh − sh`` rows the next block starts with
  as one-row halo operands (the only rows fetched and normalised
  twice): every row is normalised once into a VMEM scratch whose
  columns are read back as (column pair, parity), so a tap is an index,
  not a shifted register.  Backward, blocks are of INPUT rows: each
  takes its R pooled-err and offset rows and the halo rows above them,
  sums the two column parities apart, interleaves them as leading dims
  and writes every dx element once.  The LRN window sum rotates whole
  lane registers (``_lane_window_sum``).  ``R`` comes from a VMEM
  budget (``_window_rows``); a last block that reaches beyond the
  array works on the rows it holds.
* **Column-parity kernels** (``pallas_lrn_maxpool_split`` /
  ``pallas_gd_lrn_maxpool_split``): everything else ``fusable`` admits —
  a batch that fills no sublane tile, packed bfloat16 activations.  W is
  the sublane axis there and a stride-2 slice of it is not a block, so x
  is pre-split OUTSIDE the kernel into even/odd-column halves and every
  pool tap becomes a contiguous slice of one half (the fused step has
  the conv before the pair emit the halves: rewrite (iii) of
  ``parallel/fused.py``); row taps come through index maps of one-row
  blocks, so a forward row is normalised once a window that holds it.

The fused pair is gated: pool stride-W must be 2 and padding 0
(:func:`fusable`).  Everything else falls back to the composed ops.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from . import elementwise
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


# -- the pair on the convolutions' own layout ------------------------------
def windowed(x_shape, ksize, stride, padding, dtype=jnp.float32) -> bool:
    """Whether the Pallas tier runs the pair on the (H, W, B, C) view of
    an unsplit ``x_shape`` (header): a geometry :func:`fusable` admits, a
    batch that fills whole sublane tiles (a multiple of 8: the view's
    (8, 128) tiles lie over batch × channel) and float32 activations
    (packed bfloat16 does not lower, as in ``pooling.windowed``).
    Everything else keeps the column-parity kernels."""
    return (fusable(ksize, stride, padding) and x_shape[0] % 8 == 0
            and jnp.dtype(dtype) == jnp.float32)


#: VMEM the window kernels give their blocks (every operand,
#: double-buffered), and what a kernel may scope with its normalised-row
#: scratch and temporaries on top (a v5e has 128 MiB; the default scope
#: is 16).  Whole-height blocks measured fastest forward — no row is
#: normalised twice: 0.84 against 0.92 ms at 9 blocks of AlexNet's pair
#: 1, 0.40 against 0.52 ms at 4 of pair 2 (PERF.md section 6, PR 33) —
#: and 24 MiB holds pair 2 whole and pair 1 in two blocks.
_WINDOW_VMEM = 24 << 20
_WINDOW_VMEM_LIMIT = 64 << 20
#: batch rows of a block: one (8, 128)-tile group.  16 and 32 measured
#: no faster, and cost the rows a block can hold
_WINDOW_BATCH = 8


def _window_rows(oh, w, ow, c, sh, n_halo, out_major) -> int:
    """Pooled rows R of one block of the window kernels — all of W and C
    (the LRN window runs over the channels, so C is never cut) and
    ``_WINDOW_BATCH`` batch rows: as many as ``_WINDOW_VMEM`` holds
    double-buffered, in blocks of near-equal length: ``sh·R`` rows of x
    and ``n_halo`` one-row operands, two pooled-size rows a pooled row,
    and dx where ``out_major`` (the backward).  More rows a block mean
    fewer halo rows fetched and normalised twice; a last block that
    reaches beyond the array works on the rows it holds."""
    slab = _WINDOW_BATCH * tuning.round_up(c, elementwise._LANES) * 4
    per_row = (sh * w * (2 if out_major else 1) + 2 * ow) * slab
    fixed = n_halo * (2 * ow if out_major else w) * slab
    cap = min(oh, max(1, (_WINDOW_VMEM // 2 - fixed) // per_row))
    return -(-oh // -(-oh // cap))


def _lane_window_sum(a, n: int, xp=jnp):
    """``lrn_math._window_sum`` (and its signature) for a Mosaic kernel:
    the same n terms added in the same order, each a rotation of whole
    128-lane registers where the generic form pads the lane axis and
    slices it n times (on the chip that was half of these kernels'
    time: PERF.md section 6, PR 33).  ``a``'s channels are zero-filled to whole registers; a
    term's lanes that the rotation wrapped are taken from the
    neighbouring register, and are 0 beyond the first and last channel
    as the generic form's padding is."""
    c = a.shape[-1]
    lanes = elementwise._LANES
    width = tuning.round_up(c, lanes)
    if width != c:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, width - c)])
    regs = [a[..., j:j + lanes] for j in range(0, width, lanes)]
    lane = jax.lax.broadcasted_iota(jnp.int32, regs[0].shape, a.ndim - 1)
    # beyond the last channel lie zeros already, unless C fills its
    # last register (or comes within the window's reach of doing so)
    zero_fill = width - c >= n // 2
    out = []
    for j, reg in enumerate(regs):
        acc = None
        for o in range(-((n - 1) // 2), n // 2 + 1):    # term a[c + o]
            if o == 0:
                term = reg
            else:
                term = pltpu.roll(reg, (-o) % lanes, a.ndim - 1)
                # the lanes that wrapped: from the register before
                # (o < 0) or after (o > 0), zeros at either end
                nb = j - 1 if o < 0 else j + 1
                wrapped = lane < -o if o < 0 else lane >= lanes - o
                if 0 <= nb < len(regs):
                    term = jnp.where(
                        wrapped,
                        pltpu.roll(regs[nb], (-o) % lanes, a.ndim - 1),
                        term)
                elif not (zero_fill and (o > 0 or len(regs) == 1)):
                    term = jnp.where(wrapped, jnp.float32(0.0), term)
            acc = term if acc is None else acc + term
        out.append(acc)
    s = out[0] if len(out) == 1 else jnp.concatenate(out, axis=-1)
    return s[..., :c] if width != c else s


def _lrn_pool_window_fwd_kernel(*refs, kh, kw, sh, r, h, w, oh, ow, n_halo,
                                n, alpha, beta, k, use_abs):
    """refs: x rows (sh·R, W, Bb, C), n_halo one-row tiles below them,
    y_out, idx_out (R, OW, Bb, C), normalised-row scratch
    (sh·R + n_halo, 2·⌈W/2⌉, Bb, C).

    Each input row is normalised once into the scratch, whose columns
    are then read as (column pair, parity): both tap indices are leading
    dims — pooled row i reads rows sh·i + t, column pairs j + ct // 2 of
    parity ct % 2 — so no tap is a strided or shifted access."""
    x_ref, halo = refs[0], refs[1:1 + n_halo]
    y_ref, idx_ref, norm_ref = refs[1 + n_halo:]
    rows, w2, bb, c = norm_ref.shape
    taps = norm_ref.reshape(rows, w2 // 2, 2, bb, c)

    def norm(row):
        return lrn_math._fwd(row.astype(jnp.float32), n, alpha, beta, k,
                             jnp, _lane_window_sum)[0].astype(norm_ref.dtype)

    def norm_row(q, carry):
        norm_ref[q, pl.ds(0, w)] = norm(x_ref[q])
        return carry
    # a last block that reaches beyond the array: the rows it holds
    j = pl.program_id(0)
    jax.lax.fori_loop(0, jnp.minimum(sh * r, h - sh * r * j), norm_row, 0)
    for q, h_ref in enumerate(halo):
        @pl.when(sh * r * (j + 1) + q < h)
        def _():
            norm_ref[sh * r + q, pl.ds(0, w)] = norm(h_ref[0])

    def pool_row(i, carry):
        y_ref[i], idx_ref[i] = elementwise._winner(
            lambda t: taps[sh * i + t // kw, pl.ds(t % kw // 2, ow),
                           t % kw % 2],
            kh * kw, use_abs)
        return carry
    jax.lax.fori_loop(0, jnp.minimum(r, oh - r * j), pool_row, 0)


@functools.partial(jax.jit, static_argnames=(
    "n", "alpha", "beta", "k", "ksize", "stride", "padding", "use_abs"))
def pallas_lrn_maxpool_window(x, n, alpha, beta, k, ksize, stride, padding,
                              use_abs=False):
    """Fused forward on the (H, W, B, C) view of an unsplit x → (pooled,
    offsets): a block is ``sh·R`` input rows, all of W and C and 8 batch
    rows, with the ``kh − sh`` rows the next block starts with as
    one-row operands — the only rows normalised twice."""
    (kh, kw), (sh, _) = norm2(ksize), norm2(stride)
    assert windowed(x.shape, ksize, stride, padding, x.dtype), \
        "gate with windowed() first"
    b, h, w, c = x.shape
    oh, ow = out_size(h, kh, sh, 0), out_size(w, kw, 2, 0)
    n_halo = max(kh - sh, 0)
    r, bb = _window_rows(oh, w, ow, c, sh, n_halo, False), _WINDOW_BATCH
    halo = [pl.BlockSpec(
        (1, w, bb, c),
        lambda j, bi, q=q: (jnp.minimum(sh * r * (j + 1) + q, h - 1),
                            0, bi, 0)) for q in range(n_halo)]
    out = pl.BlockSpec((r, ow, bb, c), lambda j, bi: (j, 0, bi, 0))
    xv = elementwise._window_view(x)
    y, idx = pl.pallas_call(
        functools.partial(_lrn_pool_window_fwd_kernel, kh=kh, kw=kw, sh=sh,
                          r=r, h=h, w=w, oh=oh, ow=ow, n_halo=n_halo, n=n,
                          alpha=alpha, beta=beta, k=k, use_abs=use_abs),
        grid=(pl.cdiv(oh, r), b // bb),
        in_specs=[pl.BlockSpec((sh * r, w, bb, c),
                               lambda j, bi: (j, 0, bi, 0))] + halo,
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((oh, ow, b, c), x.dtype),
                   jax.ShapeDtypeStruct((oh, ow, b, c), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((sh * r + n_halo, w + w % 2, bb, c),
                                   x.dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_WINDOW_VMEM_LIMIT),
        name="pallas_lrn_maxpool_window",
        interpret=tuning.interpret_mode(),
    )(*([xv] * (1 + n_halo)))
    return elementwise._window_unview(y), elementwise._window_unview(idx)


def _gd_lrn_pool_window_kernel(*refs, kh, kw, sh, r, h, oh, ow, w, n_halo,
                               n, alpha, beta, k, fold_act):
    """refs: x rows (sh·R, W, Bb, C), errp and idx rows (R, OW, Bb, C),
    n_halo one-row errp tiles and as many idx tiles from above them,
    dx_out (sh·R, W, Bb, C).

    Input row sh·il + p takes pooled row il − m through tap row
    p + sh·m, m ascending, each tap column landing at columns
    2·j + ct: the flat tap order of the composed scatter, so the float32
    sums are bit-equal to it.  The two column parities are summed apart
    and interleaved as (column pair, parity), leading dims only; the LRN
    backward recomputes the denominator from x in VMEM."""
    from . import activations
    x_ref, e_ref, i_ref = refs[:3]
    halo_e, halo_i = refs[3:3 + n_halo], refs[3 + n_halo:3 + 2 * n_halo]
    dx_ref = refs[3 + 2 * n_halo]
    first = r * pl.program_id(0)            # this block's first pooled row
    cols = (w + 1) // 2                     # column pairs

    def rows_of(il):
        """The ``sh`` input rows under pooled row ``il`` of the block."""
        for p in range(sh):
            err = [jnp.zeros((cols,) + e_ref.shape[2:], jnp.float32)] * 2
            for m in range((kh - p + sh - 1) // sh):
                src = il - m
                if isinstance(src, int) and src < 0:    # from above
                    e, ix = halo_e[n_halo + src][0], halo_i[n_halo + src][0]
                else:
                    e, ix = e_ref[src], i_ref[src]
                # a clipped index map's row: no pooled row, no error
                valid = (first + src >= 0) & (first + src < oh)
                e = jnp.where(valid, e.astype(jnp.float32), jnp.float32(0.0))
                for ct in range(kw):
                    contrib = jnp.where(ix == (p + sh * m) * kw + ct, e,
                                        jnp.float32(0.0))
                    off = ct // 2
                    err[ct % 2] = err[ct % 2] + jnp.pad(
                        contrib, ((off, cols - ow - off), (0, 0), (0, 0)))
            # (pair, parity) back to columns: leading dims only
            err_row = jnp.stack(err, axis=1).reshape(
                (2 * cols,) + e_ref.shape[2:])[:w]
            x_row = x_ref[sh * il + p]
            dx = lrn_math._bwd_recompute(
                err_row, x_row.astype(jnp.float32), n, alpha, beta, k, jnp,
                _lane_window_sum)
            if fold_act is not None:
                # the preceding layer's activation derivative (needs y
                # only, and y IS this x, in its storage dtype)
                dx = activations.BY_NAME[fold_act].bwd(dx, x_row, None, jnp)
            dx_ref[sh * il + p] = dx

    for il in range(min(n_halo, r)):
        rows_of(il)

    def body(il, carry):
        rows_of(il)
        return carry
    # a last block that reaches beyond the array: the rows it holds
    held = -((sh * first - h) // sh)
    jax.lax.fori_loop(min(n_halo, r), jnp.minimum(r, held), body, 0)


@functools.partial(jax.jit, static_argnames=(
    "n", "alpha", "beta", "k", "ksize", "stride", "padding", "fold_act"))
def pallas_gd_lrn_maxpool_window(errp, offsets, x, n, alpha, beta, k, ksize,
                                 stride, padding, fold_act=None):
    """Fused backward on the (H, W, B, C) view: (pooled err, offsets, x)
    → dx, by blocks of INPUT rows so that every dx element is written
    once; a block takes its R pooled rows and the ``n_halo`` before
    them (clipped index maps, masked in the kernel)."""
    (kh, kw), (sh, _) = norm2(ksize), norm2(stride)
    assert windowed(x.shape, ksize, stride, padding, x.dtype), \
        "gate with windowed() first"
    b, h, w, c = x.shape
    _, oh, ow, _ = errp.shape
    n_halo = (kh + sh - 1) // sh - 1
    r, bb = _window_rows(oh, w, ow, c, sh, n_halo, True), _WINDOW_BATCH
    last = pl.cdiv(oh, r) - 1
    rows = pl.BlockSpec((sh * r, w, bb, c), lambda j, bi: (j, 0, bi, 0))
    pooled = pl.BlockSpec(
        (r, ow, bb, c), lambda j, bi: (jnp.minimum(j, last), 0, bi, 0))
    halo = [pl.BlockSpec(
        (1, ow, bb, c),
        lambda j, bi, q=q: (jnp.clip(r * j - n_halo + q, 0, oh - 1),
                            0, bi, 0)) for q in range(n_halo)]
    ev = elementwise._window_view(errp)
    iv = elementwise._window_view(offsets)
    dx = pl.pallas_call(
        functools.partial(_gd_lrn_pool_window_kernel, kh=kh, kw=kw, sh=sh,
                          r=r, h=h, oh=oh, ow=ow, w=w, n_halo=n_halo, n=n,
                          alpha=alpha, beta=beta, k=k, fold_act=fold_act),
        grid=(pl.cdiv(h, sh * r), b // bb),
        in_specs=[rows, pooled, pooled] + halo + halo,
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((h, w, b, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_WINDOW_VMEM_LIMIT),
        name="pallas_gd_lrn_maxpool_window",
        interpret=tuning.interpret_mode(),
    )(elementwise._window_view(x), ev, iv,
      *([ev] * n_halo + [iv] * n_halo))
    return elementwise._window_unview(dx)


# -- dispatchers -----------------------------------------------------------
def _pallas_pair(x, ksize, stride, padding, gd=False):
    """The Pallas pair kernel of an unsplit x, picked from the operand
    as one device holds it: the window kernels where :func:`windowed`
    admits it, the column-parity kernels behind their own split pass
    otherwise."""
    if windowed(x.shape, ksize, stride, padding, x.dtype):
        return (pallas_gd_lrn_maxpool_window if gd
                else pallas_lrn_maxpool_window)
    return pallas_gd_lrn_maxpool if gd else pallas_lrn_maxpool


def lrn_maxpool(x, n, alpha, beta, k, ksize, stride, padding,
                use_abs=False):
    if tuning.use_pallas() and fusable(ksize, stride, padding):
        return tuning.batch_sharded(
            lambda x: _pallas_pair(x, ksize, stride, padding)(
                x, n, alpha, beta, k, ksize, stride, padding, use_abs), x)
    return xla_lrn_maxpool(x, n, alpha, beta, k, ksize, stride, padding,
                           use_abs)


def gd_lrn_maxpool(errp, offsets, x, n, alpha, beta, k, ksize, stride,
                   padding, fold_act=None):
    if tuning.use_pallas() and fusable(ksize, stride, padding):
        return tuning.batch_sharded(
            lambda errp, offsets, x: _pallas_pair(
                x, ksize, stride, padding, gd=True)(
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
