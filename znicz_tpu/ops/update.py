"""Fused parameter update: SGD + momentum + L1/L2 decay + accumulation.

Parity target: the reference's ``weights_update`` gradient-apply kernels
(SURVEY.md §2.3) and ``GradientDescentBase`` semantics (§2.2: learning_rate,
weights_decay, l1_vs_l2, gradient_moment momentum, accumulate_gradient).

Reference update rule (reconstructed; the contract the numpy golden pins):

    reg  = weights_decay · ((1 − l1_vs_l2)·w + 0.5·l1_vs_l2·sign(w))
    g    = grad + reg
    vel' = gradient_moment · vel − learning_rate · g
    w'   = w + vel'

TPU-native: one fused elementwise Pallas pass over the flattened parameter
(VPU-bound, single HBM read-modify-write) instead of the reference's
per-buffer kernel launches; the XLA tier fuses equivalently under jit.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import tuning


def np_sgd_update(w, grad, vel, lr, weights_decay=0.0, l1_vs_l2=0.0,
                  momentum=0.0):
    """Golden path; returns (w', vel')."""
    reg = weights_decay * ((1.0 - l1_vs_l2) * w
                           + 0.5 * l1_vs_l2 * np.sign(w))
    g = grad + reg
    vel_new = momentum * vel - lr * g
    return (w + vel_new).astype(w.dtype), vel_new.astype(vel.dtype)


def xla_sgd_update(w, grad, vel, lr, weights_decay=0.0, l1_vs_l2=0.0,
                   momentum=0.0):
    reg = weights_decay * ((1.0 - l1_vs_l2) * w
                           + 0.5 * l1_vs_l2 * jnp.sign(w))
    g = grad + reg
    vel_new = momentum * vel - lr * g
    return (w + vel_new).astype(w.dtype), vel_new.astype(vel.dtype)


def _update_kernel(h_ref, w_ref, g_ref, v_ref, wo_ref, vo_ref):
    lr, wd, l1, mom = h_ref[0], h_ref[1], h_ref[2], h_ref[3]
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    reg = wd * ((1.0 - l1) * w + 0.5 * l1 * jnp.sign(w))
    v_new = mom * v - lr * (g + reg)
    wo_ref[:] = (w + v_new).astype(wo_ref.dtype)
    vo_ref[:] = v_new.astype(vo_ref.dtype)


@jax.jit
def pallas_sgd_update(w, grad, vel, hypers):
    """Fused update over a flattened parameter.

    ``hypers`` = f32[4] array (lr, weights_decay, l1_vs_l2, momentum) so
    schedule changes don't retrace."""
    shape, dtype = w.shape, w.dtype
    n = w.size
    npad = tuning.round_up(max(n, 128), 128)
    cols = 128
    rows = npad // cols
    br = tuning.block_rows(5, cols, rows=rows)   # 3 in + 2 out

    def flat(a):
        a = jnp.ravel(a).astype(jnp.float32)
        return jnp.pad(a, (0, npad - n)).reshape(rows, cols)

    wf, gf, vf = flat(w), flat(grad), flat(vel)
    rows_pad = tuning.round_up(rows, br)
    if rows_pad != rows:
        pad = ((0, rows_pad - rows), (0, 0))
        wf, gf, vf = (jnp.pad(a, pad) for a in (wf, gf, vf))
    from jax.experimental.pallas import tpu as pltpu
    w_new, v_new = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,   # hypers land in SMEM, passed first
            grid=(rows_pad // br,),
            in_specs=[pl.BlockSpec((br, cols), lambda i, h: (i, 0)),
                      pl.BlockSpec((br, cols), lambda i, h: (i, 0)),
                      pl.BlockSpec((br, cols), lambda i, h: (i, 0))],
            out_specs=[pl.BlockSpec((br, cols), lambda i, h: (i, 0)),
                       pl.BlockSpec((br, cols), lambda i, h: (i, 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows_pad, cols), dtype),
                   jax.ShapeDtypeStruct((rows_pad, cols), jnp.float32)],
        name="pallas_sgd_update",
        interpret=tuning.interpret_mode(),
    )(hypers.astype(jnp.float32), wf, gf, vf)
    w_new = w_new.reshape(-1)[:n].reshape(shape)
    v_new = v_new.reshape(-1)[:n].reshape(shape).astype(vel.dtype)
    return w_new, v_new


def sgd_update_h(w, grad, vel, hypers):
    """Dispatching update for jax arrays; ``hypers`` = f32[4] array
    (lr, weights_decay, l1_vs_l2, momentum) so schedules don't retrace."""
    if tuning.use_pallas():
        return pallas_sgd_update(w, grad, vel, hypers)
    return xla_sgd_update(w, grad, vel, hypers[0], hypers[1], hypers[2],
                          hypers[3])


def sgd_update(w, grad, vel, lr, weights_decay=0.0, l1_vs_l2=0.0,
               momentum=0.0):
    """Scalar-hyper convenience wrapper over :func:`sgd_update_h`."""
    hypers = jnp.asarray([lr, weights_decay, l1_vs_l2, momentum],
                         jnp.float32)
    return sgd_update_h(w, grad, vel, hypers)
