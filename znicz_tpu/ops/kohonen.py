"""Kohonen self-organizing-map ops: distances, winners, neighborhood pull.

Parity target: the reference's Kohonen distance/argmin/neighborhood-update
kernels (SURVEY.md §2.3 Kohonen row) behind ``KohonenForward`` /
``KohonenTrainer`` [baseline].

TPU-native design: the (B, N) squared-distance matrix is computed as
``‖x‖² − 2·x·Wᵀ + ‖w‖²`` — one MXU matmul instead of the reference's
per-neuron distance kernel; the winner search is a row argmin on the VPU;
the neighborhood-decayed weight pull is two more matmuls
(``hᵀ·x`` and a rank-1 scale of W), so a whole trainer step is
matmul-shaped and fuses under jit.  All functions are generic over the
numpy/jnp namespace: numpy IS the golden tier (SURVEY.md §4)."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _matmul(a, b, xp):
    """Full-f32 matmul on every backend: TPU matmuls default to bf16 MXU
    passes, which breaks the numpy↔XLA backend-equivalence contract
    (winner flips from 1e-3 noise compound over epochs)."""
    if xp is np:
        return a @ b
    import jax
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def grid_coords(sy: int, sx: int, xp=np):
    """(N, 2) float32 grid coordinates of an sy×sx SOM sheet, row-major
    (neuron n sits at (n // sx, n % sx))."""
    n = xp.arange(sy * sx)
    return xp.stack([n // sx, n % sx], axis=1).astype(np.float32)


def distances(x, w, xp=np):
    """Squared euclidean distances (B, N): x (B, F), w (N, F)."""
    x2 = (x * x).sum(axis=1, keepdims=True)         # (B, 1)
    w2 = (w * w).sum(axis=1)                        # (N,)
    return x2 - 2.0 * _matmul(x, w.T, xp) + w2


def winners(d, xp=np):
    """Row argmin of the distance matrix → (B,) int32 winner indices."""
    return xp.argmin(d, axis=1).astype(np.int32)


def neighborhood(win, coords, sigma, xp=np):
    """Gaussian sheet-distance weights (B, N): h[b, n] =
    exp(−‖c_n − c_win(b)‖² / (2σ²))."""
    cw = coords[win]                                 # (B, 2)
    d2 = ((coords[None, :, :] - cw[:, None, :]) ** 2).sum(axis=2)
    return xp.exp(-d2 / (2.0 * sigma * sigma))


def som_update(w, x, win, coords, lr, sigma, xp=np):
    """One neighborhood-decayed batch pull.

    Δw_n = lr/B · Σ_b h[b,n]·(x_b − w_n)  — computed as the matmul
    ``hᵀ·x`` minus a per-neuron rescale of w (no (B, N, F) intermediate).
    Returns (new_w, mean |Δw|) — the latter feeds KohonenDecision."""
    b = x.shape[0]
    h = neighborhood(win, coords, sigma, xp)         # (B, N)
    num = _matmul(h.T, x, xp)                        # (N, F)
    s = h.sum(axis=0)                                # (N,)
    delta = (lr / b) * (num - s[:, None] * w)
    return w + delta, xp.abs(delta).mean()


def np_forward(x, w):
    d = distances(x, w, np)
    return winners(d, np), d


def xla_forward(x, w):
    d = distances(x, w, jnp)
    return winners(d, jnp), d


def np_train_step(w, x, coords, lr, sigma):
    win, _ = np_forward(x, w)
    return som_update(w, x, win, coords, lr, sigma, np)


def xla_train_step(w, x, coords, lr, sigma):
    win, _ = xla_forward(x, w)
    return som_update(w, x, win, coords, lr, sigma, jnp)


def quantization_error(x, w, xp=np):
    """Mean distance from each sample to its winner (SOM quality metric)."""
    d = distances(x, w, xp)
    return xp.sqrt(xp.maximum(d.min(axis=1), 0.0)).mean()


# -- Pallas tier -----------------------------------------------------------
# Parity row SURVEY.md §2.3 "Kohonen distance/argmin/neighborhood kernels":
# the reference computed a (B, N) distance matrix kernel then an argmin
# kernel over it.  The TPU kernel fuses both: neuron tiles stream through
# VMEM, each contributes one MXU matmul to a running (min, argmin) pair,
# and the (B, N) matrix never exists in HBM.

def _dist_argmin_kernel(x_ref, w_ref, min_ref, arg_ref, *, bn, n_valid):
    j = pl.program_id(1)
    x = x_ref[:].astype(jnp.float32)                      # (bb, F)
    w = w_ref[:].astype(jnp.float32)                      # (bn, F)
    x2 = (x * x).sum(axis=1, keepdims=True)               # (bb, 1)
    w2 = (w * w).sum(axis=1)                              # (bn,)
    # HIGHEST precision matches _matmul's backend-equivalence contract:
    # default MXU f32 (bf16 passes) flips near-tie winners vs the golden.
    cross = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST)
    d = x2 - 2.0 * cross + w2[None, :]                    # (bb, bn)
    col = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
           + jnp.int32(bn) * j)
    d = jnp.where(col < n_valid, d, jnp.float32(np.inf))  # mask N padding
    blk_min = jnp.min(d, axis=1, keepdims=True)           # (bb, 1)
    blk_arg = jnp.argmin(d, axis=1).astype(jnp.int32)[:, None] \
        + jnp.int32(bn) * j
    blk_min = jnp.broadcast_to(blk_min, min_ref.shape)
    blk_arg = jnp.broadcast_to(blk_arg, arg_ref.shape)

    @pl.when(j == 0)
    def _init():
        min_ref[:] = blk_min
        arg_ref[:] = blk_arg

    @pl.when(j > 0)
    def _merge():
        cur = min_ref[:]
        better = blk_min < cur                 # strict: ties keep the
        min_ref[:] = jnp.where(better, blk_min, cur)      # first neuron,
        arg_ref[:] = jnp.where(better, blk_arg, arg_ref[:])  # = argmin


@jax.jit
def pallas_distance_argmin(x, w):
    """Fused winner search: (B, F) samples × (N, F) codebook →
    ``(win int32 (B,), dmin f32 (B,))`` without materializing (B, N)."""
    from . import tuning
    b, f = x.shape
    n, f2 = w.shape
    assert f == f2, (x.shape, w.shape)
    bb = min(256, tuning.round_up(b, 8))
    bn = 128
    bp, np_, fp = (tuning.round_up(b, bb), tuning.round_up(n, bn),
                   tuning.round_up(f, 128))
    if (bp, fp) != (b, f):
        x = jnp.pad(x, ((0, bp - b), (0, fp - f)))
    if (np_, fp) != (n, f):
        w = jnp.pad(w, ((0, np_ - n), (0, fp - f)))
    grid = (bp // bb, np_ // bn)               # neuron tiles innermost:
    dmin, win = pl.pallas_call(                # sequential merge per row
        functools.partial(_dist_argmin_kernel, bn=bn, n_valid=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, fp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, fp), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 128), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 128), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 128), jnp.float32),
            jax.ShapeDtypeStruct((bp, 128), jnp.int32),
        ],
        name="pallas_distance_argmin",
        interpret=tuning.interpret_mode(),
    )(x, w)
    return win[:b, 0], dmin[:b, 0]


def forward_winners(x, w):
    """Dispatching winner search for jax arrays: the fused Pallas kernel
    on TPU, the XLA distance matrix elsewhere.  Returns (win, dmin)."""
    from . import tuning
    if tuning.use_pallas():
        return pallas_distance_argmin(x, w)
    d = distances(x, w, jnp)
    return winners(d, jnp), d.min(axis=1)
