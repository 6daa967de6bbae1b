"""Pallas elementwise kernels: activations, dropout, LRN, pool-select.

Parity target: the remaining hand-written kernel rows of SURVEY.md §2.3 —
activation elementwise kernels (row 6), ``dropout.cl/.cu`` + device RNG
(row 7), ``normalization.cl/.cu`` LRN (row 4), and the select/argmax core
of ``pooling.cl/.cu`` (row 3).  The matmul/conv/softmax/update rows live
in their own modules.

Design: one shared flatten-to-(rows, 128) tiling for rank-free
elementwise work (VPU lanes on the minor dim); LRN keeps channels on the
lane axis and does its n-tap window sum on the loaded block; dropout
evaluates the counter-RNG hash (``ops.rngbits`` murmur3 finalizer —
bit-identical to the numpy golden path) *inside* the kernel from the
block's global element offset, so mask generation + scale + apply is one
HBM pass; pooling has two kernel families, picked by
``pooling.windowed`` from the operands: windows that tile their input
exactly are pooled on a windowed view, one pass a direction with the
taps taken inside VMEM (``pallas_pool_window`` /
``pallas_gd_pool_window``); every other pool's winner select consumes
XLA-stacked window taps (T, rows, C) and emits value + dense slot index
in one pass, and there the strided tap gather/scatter stays in XLA
(SURVEY.md §7 hard part (a))."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import activations, rngbits, tuning

_LANES = 128


def _flatten_blocks(n: int, n_operands: int = 2):
    """(rows, padded_rows, block_rows) for an n-element flat tensor laid
    out (rows, 128); blocks VMEM-budget-sized for ``n_operands`` live
    buffers (tuning.block_rows — big blocks keep the grid short)."""
    npad = tuning.round_up(max(n, _LANES), _LANES)
    rows = npad // _LANES
    br = tuning.block_rows(n_operands, _LANES, rows=rows)
    rows_pad = tuning.round_up(rows, br)
    return rows, rows_pad, br, npad


def _to_rows(a, npad, rows_pad):
    flat = jnp.ravel(a)
    flat = jnp.pad(flat, (0, npad - flat.size))
    a2 = flat.reshape(-1, _LANES)
    if rows_pad != a2.shape[0]:
        a2 = jnp.pad(a2, ((0, rows_pad - a2.shape[0]), (0, 0)))
    return a2


# -- activations -----------------------------------------------------------
def _act_fwd_kernel(x_ref, o_ref, *, name):
    act = activations.BY_NAME[name]
    o_ref[:] = act.fwd(x_ref[:].astype(jnp.float32), jnp).astype(
        o_ref.dtype)


def _act_bwd_kernel(e_ref, y_ref, x_ref, o_ref, *, name):
    act = activations.BY_NAME[name]
    x = x_ref[:].astype(jnp.float32) if x_ref is not None else None
    o_ref[:] = act.bwd(e_ref[:].astype(jnp.float32),
                       y_ref[:].astype(jnp.float32), x, jnp).astype(
        o_ref.dtype)


def _lastaxis_blocks(x, n_operands: int = 2):
    """(x2, rows, rows_pad, br, c): last axis preserved as the lane dim —
    required by position-dependent activations (sincos's even/odd lanes);
    used whenever the activation's math references the last-axis index."""
    c = x.shape[-1]
    rows = int(x.size // c)
    x2 = x.reshape(rows, c)
    br = tuning.block_rows(n_operands, c, rows=rows)
    rows_pad = tuning.round_up(rows, br)
    if rows_pad != rows:
        x2 = jnp.pad(x2, ((0, rows_pad - rows), (0, 0)))
    return x2, rows, rows_pad, br, c


#: Activations whose math depends on the last-axis position.
_POSITIONAL = ("sincos",)


@functools.partial(jax.jit, static_argnames=("name",))
def pallas_act_fwd(name: str, x):
    """y = act(x) as one tiled VPU pass (reference elementwise kernels)."""
    if name in _POSITIONAL:
        x2, rows, rows_pad, br, c = _lastaxis_blocks(x)
        y = pl.pallas_call(
            functools.partial(_act_fwd_kernel, name=name),
            grid=(rows_pad // br,),
            in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows_pad, c), x.dtype),
            name=f"pallas_act_fwd_{name}",
            interpret=tuning.interpret_mode(),
        )(x2)
        return y[:rows].reshape(x.shape)
    n = x.size
    rows, rows_pad, br, npad = _flatten_blocks(n)
    x2 = _to_rows(x, npad, rows_pad)
    y = pl.pallas_call(
        functools.partial(_act_fwd_kernel, name=name),
        grid=(rows_pad // br,),
        in_specs=[pl.BlockSpec((br, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, _LANES), x.dtype),
        name=f"pallas_act_fwd_{name}",
        interpret=tuning.interpret_mode(),
    )(x2)
    return y.reshape(-1)[:n].reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("name",))
def pallas_act_bwd(name: str, err_y, y, x=None):
    """err_x from (err_y, y[, x]) — the unit-zoo derivative convention."""
    act = activations.BY_NAME[name]
    if name in _POSITIONAL:
        e2, rows, rows_pad, br, c = _lastaxis_blocks(err_y, 4)
        y2 = _lastaxis_blocks(y, 4)[0]
        x2 = _lastaxis_blocks(x, 4)[0]
        spec = pl.BlockSpec((br, c), lambda i: (i, 0))
        out = pl.pallas_call(
            functools.partial(_act_bwd_kernel, name=name),
            grid=(rows_pad // br,),
            in_specs=[spec, spec, spec], out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((rows_pad, c), err_y.dtype),
            name=f"pallas_act_bwd_{name}",
            interpret=tuning.interpret_mode(),
        )(e2, y2, x2)
        return out[:rows].reshape(err_y.shape)
    n = err_y.size
    rows, rows_pad, br, npad = _flatten_blocks(n, 4)
    e2 = _to_rows(err_y, npad, rows_pad)
    y2 = _to_rows(y, npad, rows_pad)
    spec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    if act.needs_input:
        if x is None:
            raise ValueError(f"{name} backward needs the forward input")
        x2 = _to_rows(x, npad, rows_pad)
        kernel = functools.partial(_act_bwd_kernel, name=name)
        out = pl.pallas_call(
            kernel, grid=(rows_pad // br,),
            in_specs=[spec, spec, spec], out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((rows_pad, _LANES),
                                           err_y.dtype),
            name=f"pallas_act_bwd_{name}",
            interpret=tuning.interpret_mode(),
        )(e2, y2, x2)
    else:
        def kernel(e_ref, y_ref, o_ref):
            _act_bwd_kernel(e_ref, y_ref, None, o_ref, name=name)
        out = pl.pallas_call(
            kernel, grid=(rows_pad // br,),
            in_specs=[spec, spec], out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((rows_pad, _LANES),
                                           err_y.dtype),
            name=f"pallas_act_bwd_{name}",
            interpret=tuning.interpret_mode(),
        )(e2, y2)
    return out.reshape(-1)[:n].reshape(err_y.shape)


# -- dropout ---------------------------------------------------------------
def _dropout_kernel(key_ref, x_ref, o_ref, *, ratio, br):
    i = pl.program_id(0)
    key = key_ref[0]
    base = (i * br * _LANES)
    idx = (jax.lax.broadcasted_iota(jnp.uint32, x_ref.shape, 0) * _LANES
           + jax.lax.broadcasted_iota(jnp.uint32, x_ref.shape, 1)
           + jnp.uint32(base))
    # identical math to rngbits.uniform01 → bit-identical masks
    h = rngbits._mix(idx * jnp.uint32(rngbits._C2) ^ key, jnp)
    # Mosaic can't lower uint32→f32; values are < 2²⁴ so int32 is exact.
    u = (h >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(1.0 / (1 << 24))
    keep = (u >= jnp.float32(ratio)).astype(jnp.float32)
    scale = jnp.float32(1.0 / (1.0 - ratio))
    o_ref[:] = (x_ref[:].astype(jnp.float32) * keep * scale).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ratio", "seed"))
def pallas_dropout(x, seed: int, counters, ratio: float):
    """Fused mask-gen + scale + apply in one HBM pass (reference
    dropout kernel + device RNG, with the counter-RNG determinism fix)."""
    key = rngbits.fold(seed, *counters, xp=jnp).reshape(1)
    n = x.size
    rows, rows_pad, br, npad = _flatten_blocks(n)
    x2 = _to_rows(x, npad, rows_pad)
    from jax.experimental.pallas import tpu as pltpu
    out = pl.pallas_call(
        functools.partial(_dropout_kernel, ratio=ratio, br=br),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows_pad // br,),
            in_specs=[pl.BlockSpec((br, _LANES), lambda i, k: (i, 0))],
            out_specs=pl.BlockSpec((br, _LANES), lambda i, k: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((rows_pad, _LANES), x.dtype),
        name="pallas_dropout",
        interpret=tuning.interpret_mode(),
    )(key, x2)
    return out.reshape(-1)[:n].reshape(x.shape)


# -- LRN -------------------------------------------------------------------
# The kernel bodies reuse normalization's xp-generic formulas with
# xp=jnp so the Mosaic tier can never silently diverge from the
# numpy/XLA tiers — one accumulation order, bit-for-bit across tiers.

def _lrn_fwd_kernel(x_ref, y_ref, d_ref, *, n, alpha, beta, k):
    from . import normalization as lrn_math
    x = x_ref[:].astype(jnp.float32)
    y, d = lrn_math._fwd(x, n, alpha, beta, k, jnp)
    d_ref[:] = d
    y_ref[:] = y.astype(y_ref.dtype)


def _lrn_fwd_y_kernel(x_ref, y_ref, *, n, alpha, beta, k):
    from . import normalization as lrn_math
    x = x_ref[:].astype(jnp.float32)
    y_ref[:] = lrn_math._fwd(x, n, alpha, beta, k, jnp)[0].astype(
        y_ref.dtype)


def _lrn_pallas(name, kernel, inputs, out_dtypes, n_operands):
    """Shared rows×channels tiling for the LRN kernel family: channels
    on the lane axis, row blocks budget-sized for ``n_operands`` live
    buffers; pads rows to the block, slices the pad back off."""
    x = inputs[0]
    c = x.shape[-1]
    lead = x.shape[:-1]
    rows = int(x.size // c)
    br = tuning.block_rows(n_operands, c, rows=rows)
    rows_pad = tuning.round_up(rows, br)

    def to2(a):
        a2 = a.reshape(rows, c)
        return jnp.pad(a2, ((0, rows_pad - rows), (0, 0))) \
            if rows_pad != rows else a2
    spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    many = len(out_dtypes) > 1
    shapes = [jax.ShapeDtypeStruct((rows_pad, c), dt)
              for dt in out_dtypes]
    outs = pl.pallas_call(
        kernel, grid=(rows_pad // br,),
        in_specs=[spec] * len(inputs),
        out_specs=[spec] * len(out_dtypes) if many else spec,
        out_shape=shapes if many else shapes[0],
        name=name,
        interpret=tuning.interpret_mode(),
    )(*(to2(a) for a in inputs))
    res = tuple(o[:rows].reshape(*lead, c)
                for o in (outs if many else (outs,)))
    return res if many else res[0]


@functools.partial(jax.jit, static_argnames=("n", "alpha", "beta", "k"))
def pallas_lrn(x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    """Cross-channel LRN fwd: rows = every spatial position, channels on
    the lane axis; window sum + powers in one VMEM pass → (y, denom)."""
    return _lrn_pallas(
        "pallas_lrn",
        functools.partial(_lrn_fwd_kernel, n=n, alpha=alpha, beta=beta,
                          k=k),
        (x,), (x.dtype, jnp.float32), 4)      # 1 in + 2 out + temps


def _lrn_bwd_kernel(e_ref, x_ref, d_ref, o_ref, *, n, alpha, beta):
    from . import normalization as lrn_math
    e = e_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    d = d_ref[:].astype(jnp.float32)
    o_ref[:] = lrn_math._bwd(e, x, d, n, alpha, beta, jnp).astype(
        o_ref.dtype)


def _lrn_bwd_x_kernel(e_ref, x_ref, o_ref, *, n, alpha, beta, k):
    """Backward with in-kernel denom recompute — saves the fwd's d
    write plus this read, the two HBM passes the remat removes."""
    from . import normalization as lrn_math
    e = e_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    o_ref[:] = lrn_math._bwd_recompute(e, x, n, alpha, beta, k,
                                       jnp).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "alpha", "beta", "k"))
def pallas_gd_lrn(err, x, d, n=5, alpha=1e-4, beta=0.75, k=2.0):
    return _lrn_pallas(
        "pallas_gd_lrn",
        functools.partial(_lrn_bwd_kernel, n=n, alpha=alpha, beta=beta),
        (err, x, d), (jnp.float32,), 5)       # 3 in + 1 out + temps


@functools.partial(jax.jit, static_argnames=("n", "alpha", "beta", "k"))
def pallas_lrn_y(x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    """LRN forward emitting only y — one HBM read + one write."""
    return _lrn_pallas(
        "pallas_lrn_y",
        functools.partial(_lrn_fwd_y_kernel, n=n, alpha=alpha, beta=beta,
                          k=k),
        (x,), (x.dtype,), 3)                  # 1 in + 1 out + temps


@functools.partial(jax.jit, static_argnames=("n", "alpha", "beta", "k"))
def pallas_gd_lrn_x(err, x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    """LRN backward recomputing the denominator from x in VMEM."""
    return _lrn_pallas(
        "pallas_gd_lrn_x",
        functools.partial(_lrn_bwd_x_kernel, n=n, alpha=alpha, beta=beta,
                          k=k),
        (err, x), (jnp.float32,), 4)          # 2 in + 1 out + temps


# -- pooling winner select -------------------------------------------------
def _winner(tap, n_taps, use_abs):
    """(value, slot index) of the winning tap, ``tap(t)`` loading the
    t-th: THE winner rule of every pooling kernel — taps in row-major
    order, strict ``>`` so ties keep the first, max-abs keeps the sign —
    bit-identical to ``pooling._max_pool``."""
    best_val = tap(0)
    best = jnp.abs(best_val) if use_abs else best_val
    idx = jnp.zeros(best.shape, jnp.int32)
    for t in range(1, n_taps):
        sl = tap(t)
        score = jnp.abs(sl) if use_abs else sl
        take = score > best
        best = jnp.where(take, score, best)
        best_val = jnp.where(take, sl, best_val)
        idx = jnp.where(take, jnp.int32(t), idx)
    return best_val, idx


def _pool_select_kernel(taps_ref, y_ref, idx_ref, *, n_taps, use_abs):
    best_val, idx = _winner(lambda t: taps_ref[t], n_taps, use_abs)
    y_ref[:] = best_val.astype(y_ref.dtype)
    idx_ref[:] = idx


@functools.partial(jax.jit, static_argnames=("use_abs",))
def pallas_pool_select(taps, use_abs: bool = False):
    """(value, window-slot index) over stacked window taps (T, rows, C) —
    the select/argmax core of the reference pooling kernel; tap stacking
    and the backward scatter stay in XLA (SURVEY.md §7 hard part (a))."""
    t, rows, c = taps.shape
    br = tuning.block_rows(t + 2, c, rows=rows)
    rows_pad = tuning.round_up(rows, br)
    if rows_pad != rows:
        taps = jnp.pad(taps, ((0, 0), (0, rows_pad - rows), (0, 0)))
    y, idx = pl.pallas_call(
        functools.partial(_pool_select_kernel, n_taps=t, use_abs=use_abs),
        grid=(rows_pad // br,),
        in_specs=[pl.BlockSpec((t, br, c), lambda i: (0, i, 0))],
        out_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                   pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_pad, c), taps.dtype),
                   jax.ShapeDtypeStruct((rows_pad, c), jnp.int32)],
        name="pallas_pool_select",
        interpret=tuning.interpret_mode(),
    )(taps)
    return y[:rows], idx[:rows]


def _pool_scatter_kernel(e_ref, i_ref, o_ref, *, n_taps):
    err = e_ref[:].astype(jnp.float32)
    idx = i_ref[:]
    for t in range(n_taps):
        o_ref[t] = jnp.where(idx == jnp.int32(t), err,
                             jnp.float32(0.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_taps",))
def pallas_pool_scatter(err, offsets, n_taps: int):
    """GD-pooling backward core (SURVEY.md §2.3 gd_pooling row, §7 hard
    part (a)): expand (err, winner-slot offsets) into the per-tap
    contribution stack ``out[t] = err·(offsets == t)`` in ONE read of
    err+offsets (the XLA formulation re-reads both once per tap).  The
    regular strided placement of the taps into dx stays in XLA, mirroring
    the forward's stack-in-XLA / select-in-Pallas split."""
    rows, c = err.shape
    br = tuning.block_rows(n_taps + 2, c, rows=rows)
    rows_pad = tuning.round_up(rows, br)
    if rows_pad != rows:
        err = jnp.pad(err, ((0, rows_pad - rows), (0, 0)))
        offsets = jnp.pad(offsets, ((0, rows_pad - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_pool_scatter_kernel, n_taps=n_taps),
        grid=(rows_pad // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((n_taps, br, c), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_taps, rows_pad, c), err.dtype),
        name="pallas_pool_scatter",
        interpret=tuning.interpret_mode(),
    )(err, offsets)
    return out[:, :rows]


def _pool_gather_kernel(taps_ref, i_ref, o_ref, *, n_taps):
    idx = i_ref[:]
    acc = jnp.where(idx == 0, taps_ref[0].astype(jnp.float32),
                    jnp.float32(0.0))
    for t in range(1, n_taps):
        acc = acc + jnp.where(idx == jnp.int32(t),
                              taps_ref[t].astype(jnp.float32),
                              jnp.float32(0.0))
    o_ref[:] = acc.astype(o_ref.dtype)


@jax.jit
def pallas_pool_gather(taps, offsets):
    """Depooling backward core (adjoint of the offset scatter): select
    each window's recorded winner tap and sum — ``out = Σ_t
    taps[t]·(offsets == t)`` in one pass over the (T, rows, C) stack."""
    t, rows, c = taps.shape
    br = tuning.block_rows(t + 2, c, rows=rows)
    rows_pad = tuning.round_up(rows, br)
    if rows_pad != rows:
        taps = jnp.pad(taps, ((0, 0), (0, rows_pad - rows), (0, 0)))
        offsets = jnp.pad(offsets, ((0, rows_pad - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_pool_gather_kernel, n_taps=t),
        grid=(rows_pad // br,),
        in_specs=[pl.BlockSpec((t, br, c), lambda i: (0, i, 0)),
                  pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, c), taps.dtype),
        name="pallas_pool_gather",
        interpret=tuning.interpret_mode(),
    )(taps, offsets)
    return out[:rows]


# -- non-overlapping pooling on a windowed view ----------------------------
#: VMEM the windowed pool kernels give their blocks (every operand,
#: double-buffered).  A kernel's temporaries come on top: at 12 MiB the
#: 56 px pool of VGG-A overran the 16 MiB a v5e kernel may scope, and
#: 2 MiB was 1-3% slower than 6 (on-chip, PERF.md section 6, PR 27).
_WINDOW_VMEM = 6 << 20


def _window_blocks(ow, n_taps, b, c):
    """(columns, batch rows, lanes) of one block of the windowed pool
    kernels: a whole (batch, channel) tile group a pooled column, as
    many columns as the VMEM budget holds — ``n_taps`` input slabs and
    two output slabs a column forward, the reverse backward."""
    cb, bb = min(c, _LANES), min(b, 256)
    per_col = (n_taps + 2) * tuning.round_up(bb, 8) * _LANES * 4
    return max(1, min(ow, _WINDOW_VMEM // (2 * per_col))), bb, cb


def _window_view(a):
    """(B, H, W, C) → (H, W, B, C): the pooled axes lead and the
    (8, 128) tiles lie over (batch, channel), which is the layout
    XLA's TPU convolutions emit and consume at a batch that fills the
    sublanes — inside the step this transpose is a bitcast, and no
    layout copy stands beside the kernels (PERF.md section 6, PR 27)."""
    return jnp.transpose(a, (1, 2, 0, 3))


def _window_unview(a):
    return jnp.transpose(a, (2, 0, 1, 3))


def _pool_window_kernel(x_ref, y_ref, idx_ref, *, kh, kw, use_abs):
    # both tap indices are leading dims of the block: the taps never
    # exist outside VMEM, and none is a strided access
    y_ref[0], idx_ref[0] = _winner(
        lambda t: x_ref[0, t // kw, :, t % kw], kh * kw, use_abs)


@functools.partial(jax.jit, static_argnames=("ksize", "use_abs"))
def pallas_pool_window(x, ksize, use_abs: bool = False):
    """Max / max-abs pool whose windows do not overlap (stride = ksize,
    no padding, H and W whole multiples) in ONE pass: x is viewed as
    (OH, kh, OW, kw, B, C) — the window axes split off leading dims, so
    no data moves — and each block's kh·kw taps are taken inside VMEM.
    Same winner rule as ``pooling._max_pool`` → (y, window-slot index)."""
    kh, kw = ksize
    b, h, w, c = x.shape
    oh, ow = h // kh, w // kw
    wb, bb, cb = _window_blocks(ow, kh * kw, b, c)
    out = pl.BlockSpec((1, wb, bb, cb), lambda r, q, n, l: (r, q, n, l))
    y, idx = pl.pallas_call(
        functools.partial(_pool_window_kernel, kh=kh, kw=kw,
                          use_abs=use_abs),
        grid=(oh, pl.cdiv(ow, wb), pl.cdiv(b, bb), pl.cdiv(c, cb)),
        in_specs=[pl.BlockSpec((1, kh, wb, kw, bb, cb),
                               lambda r, q, n, l: (r, 0, q, 0, n, l))],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((oh, ow, b, c), x.dtype),
                   jax.ShapeDtypeStruct((oh, ow, b, c), jnp.int32)],
        name="pallas_pool_window",
        interpret=tuning.interpret_mode(),
    )(_window_view(x).reshape(oh, kh, ow, kw, b, c))
    return _window_unview(y), _window_unview(idx)


def _gd_pool_window_kernel(e_ref, i_ref, dx_ref, *, kh, kw):
    err = e_ref[0].astype(jnp.float32)
    idx = i_ref[0]
    for t in range(kh * kw):
        dx_ref[0, t // kw, :, t % kw] = jnp.where(
            idx == jnp.int32(t), err, jnp.float32(0.0))


@functools.partial(jax.jit, static_argnames=("ksize",))
def pallas_gd_pool_window(err, offsets, ksize):
    """Backward (and depooling) of the non-overlapping pool in ONE
    pass: every dx element belongs to one window, so it is written
    once — err where its slot won, 0 elsewhere — with no zero fill, no
    tap stack and no add.  → dx (B, OH·kh, OW·kw, C) float32."""
    kh, kw = ksize
    b, oh, ow, c = err.shape
    wb, bb, cb = _window_blocks(ow, kh * kw, b, c)
    inp = pl.BlockSpec((1, wb, bb, cb), lambda r, q, n, l: (r, q, n, l))
    dx = pl.pallas_call(
        functools.partial(_gd_pool_window_kernel, kh=kh, kw=kw),
        grid=(oh, pl.cdiv(ow, wb), pl.cdiv(b, bb), pl.cdiv(c, cb)),
        in_specs=[inp, inp],
        out_specs=pl.BlockSpec((1, kh, wb, kw, bb, cb),
                               lambda r, q, n, l: (r, 0, q, 0, n, l)),
        out_shape=jax.ShapeDtypeStruct((oh, kh, ow, kw, b, c),
                                       jnp.float32),
        name="pallas_gd_pool_window",
        interpret=tuning.interpret_mode(),
    )(_window_view(err), _window_view(offsets))
    return _window_unview(dx.reshape(oh * kh, ow * kw, b, c))
