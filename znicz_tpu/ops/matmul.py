"""Matrix multiply: numpy golden, XLA, and a Pallas MXU kernel.

Parity target: the reference's tiled matrix-multiplication kernels
(SURVEY.md §2.3 row 1: BLOCK_SIZE-templated ``.cl``/``.cu`` shared by
All2All forward and GD weight gradients).  TPU-native design: a block-tiled
Pallas kernel accumulating in float32 VMEM scratch over a (M/bm, N/bn, K/bk)
grid with K innermost (sequential revisits of the same output tile), bf16
inputs feeding the MXU.  ``lax.dot`` is the always-available XLA tier and
the numerical cross-check in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning


def np_matmul(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Golden path (reference numpy_run: explicit numpy.dot)."""
    return np.dot(x, w)


def xla_matmul(x, w, out_dtype=None):
    out_dtype = out_dtype or x.dtype
    return jax.lax.dot(x, w,
                       preferred_element_type=jnp.float32).astype(out_dtype)


def _mxu_cast(dtype):
    """Operand dtype fed to the MXU: bf16 on real TPU hardware for f32
    inputs — the SAME single-pass precision XLA's default lowering uses
    for f32 convs/dots, so the Pallas tier competes (and agrees
    numerically) with the XLA tier it is benchmarked against.  On CPU
    (interpret mode) there is no MXU and the golden-path tests expect
    full f32 — no cast.

    Consequence for callers of the dispatching ``matmul()``: on TPU,
    f32 inputs are NOT multiplied in full f32 precision on the Pallas
    tier (accumulation stays f32)."""
    if jnp.dtype(dtype) == jnp.float32 and tuning.on_tpu():
        return jnp.bfloat16
    return None


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int, cast):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x, w = x_ref[:], w_ref[:]
    if cast is not None:
        x, w = x.astype(cast), w.astype(cast)
    acc_ref[:] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k", "out_dtype"))
def pallas_matmul(x, w, block_m: int = 128, block_n: int = 128,
                  block_k: int = 512, out_dtype=None):
    """Block-tiled MXU matmul with f32 accumulation.

    Pads M/N/K up to tile multiples (XLA's pad/slice fuse away), so any
    shape is accepted; for MXU efficiency callers should keep dims ≥128.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype

    bm = min(block_m, tuning.round_up(m, tuning.min_tile(x.dtype)[0]))
    bn = min(block_n, tuning.round_up(n, 128))
    bk = min(block_k, tuning.round_up(k, 128))
    mp, np_, kp = (tuning.round_up(m, bm), tuning.round_up(n, bn),
                   tuning.round_up(k, bk))
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=grid[2],
                          cast=_mxu_cast(x.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        name="pallas_matmul",
        interpret=tuning.interpret_mode(),
    )(x, w)
    return out[:m, :n]


def matmul(x, w, out_dtype=None):
    """Dispatching matmul for jax arrays: Pallas on TPU, XLA otherwise."""
    if tuning.use_pallas() and x.ndim == 2 and w.ndim == 2:
        return pallas_matmul(x, w, out_dtype=out_dtype)
    return xla_matmul(x, w, out_dtype=out_dtype)
