"""Local-response normalization (across channels), forward + backward.

Parity target: the reference's ``normalization.cl/.cu`` LRN kernels
(SURVEY.md §2.3 row 4; AlexNet-style LRN [baseline]).

Math (cross-channel window of size n, symmetric):

    S_i = Σ_{j ∈ [i−n/2, i+n/2]} x_j²          (clipped to valid channels)
    d_i = k + α·S_i
    y_i = x_i · d_i^{−β}

Hand-written backward (the reference's LRNormalizerBackward contract): with
q_j = err_j · x_j · d_j^{−β−1},

    dx_i = err_i · d_i^{−β} − 2αβ · x_i · Σ_{j: i ∈ win(j)} q_j

and for a symmetric window the adjoint window equals the window itself, so
both passes reuse one windowed-channel-sum primitive — on TPU this is a
cumsum difference along the minor (lane) axis, one VPU pass, no im2col."""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

#: Reference defaults (AlexNet LRN).
DEFAULTS = dict(n=5, alpha=1e-4, beta=0.75, k=2.0)


def _window_sum(a, n: int, xp):
    """Sum over a centered channel window of size n (last axis), clipped.

    n static shifted slices of a zero-padded copy — n is tiny (5 in every
    shipped config) and the adds fuse into one VPU pass, where a
    cumsum+gather formulation pays a lane-axis gather on TPU (measured
    ~40% of the whole AlexNet step before this form)."""
    half_lo = (n - 1) // 2
    half_hi = n // 2
    c = a.shape[-1]
    pad = [(0, 0)] * (a.ndim - 1) + [(half_lo, half_hi)]
    ap = xp.pad(a, pad)
    acc = None
    for i in range(n):
        sl = ap[..., i:i + c]
        acc = sl if acc is None else acc + sl
    return acc


def _dpow_nbeta(d, beta, xp):
    """d^(−β), with β=0.75 (every shipped config) as 1/(√d·√√d).

    sqrt/mul/div are correctly-rounded IEEE ops in numpy, XLA and
    Mosaic alike, so the same expression stays bit-reproducible across
    all three tiers — a transcendental ``pow`` is neither (and costs a
    log+exp pair on the VPU).  Non-default β falls back to pow."""
    if beta == 0.75:
        r = xp.sqrt(d)
        return 1.0 / (r * xp.sqrt(r))
    return d ** (-beta)


def _fwd(x, n, alpha, beta, k, xp, window_sum=_window_sum):
    """``window_sum``: another lowering of :func:`_window_sum` with the
    same terms in the same order (``ops/lrn_pool.py`` has one for its
    Mosaic kernels), so the result is the same bits."""
    s = window_sum(x * x, n, xp)
    d = k + alpha * s
    return x * _dpow_nbeta(d, beta, xp), d


def np_lrn(x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    """→ (y, denom); denom is cached for the backward pass."""
    return _fwd(x, n, alpha, beta, k, np)


def xla_lrn(x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    return _fwd(x, n, alpha, beta, k, jnp)


def _bwd(err, x, d, n, alpha, beta, xp, window_sum=_window_sum):
    p = _dpow_nbeta(d, beta, xp)
    q = err * x * (p / d)
    return err * p - 2.0 * alpha * beta * x * window_sum(q, n, xp)


def np_gd_lrn(err, x, d, n=5, alpha=1e-4, beta=0.75, k=2.0):
    return _bwd(err, x, d, n, alpha, beta, np)


def xla_gd_lrn(err, x, d, n=5, alpha=1e-4, beta=0.75, k=2.0):
    return _bwd(err, x, d, n, alpha, beta, jnp)


# -- remat variants (fused-path fast forms) --------------------------------
# LRN is HBM-bound: the denominator d is a full activation-sized tensor,
# and caching it from forward to backward costs one HBM write + one read
# of the biggest tensors in the net (AlexNet: (B,55,55,96)+(B,27,27,256)).
# Recomputing d from x inside the backward (one extra windowed VPU sum —
# FLOPs the TPU has to spare) removes both passes.  The unit-graph path
# keeps the (y, denom) contract for parity with the reference's
# LRNormalizerForward; the fused trainer uses these.

def _bwd_recompute(err, x, n, alpha, beta, k, xp, window_sum=_window_sum):
    d = k + alpha * window_sum(x * x, n, xp)
    return _bwd(err, x, d, n, alpha, beta, xp, window_sum)


def np_gd_lrn_x(err, x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    return _bwd_recompute(err, x, n, alpha, beta, k, np)


def xla_gd_lrn_x(err, x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    return _bwd_recompute(err, x, n, alpha, beta, k, jnp)


# -- dispatchers (Pallas kernel on TPU, XLA formulation elsewhere) ---------
def lrn(x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    from . import tuning
    if tuning.use_pallas():
        from . import elementwise
        return elementwise.pallas_lrn(x, n, alpha, beta, k)
    return xla_lrn(x, n, alpha, beta, k)


def gd_lrn(err, x, d, n=5, alpha=1e-4, beta=0.75, k=2.0):
    from . import tuning
    if tuning.use_pallas():
        from . import elementwise
        return elementwise.pallas_gd_lrn(err, x, d, n, alpha, beta, k)
    return xla_gd_lrn(err, x, d, n, alpha, beta, k)


def lrn_y(x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    """Forward emitting only y (denom rematerialized in backward)."""
    from . import tuning
    if tuning.use_pallas():
        from . import elementwise
        return tuning.batch_sharded(
            lambda x: elementwise.pallas_lrn_y(x, n, alpha, beta, k), x)
    return xla_lrn(x, n, alpha, beta, k)[0]


def gd_lrn_x(err, x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    """Backward recomputing denom from x in-kernel (no cached d)."""
    from . import tuning
    if tuning.use_pallas():
        from . import elementwise
        return tuning.batch_sharded(
            lambda err, x: elementwise.pallas_gd_lrn_x(
                err, x, n, alpha, beta, k), err, x)
    return xla_gd_lrn_x(err, x, n, alpha, beta, k)
