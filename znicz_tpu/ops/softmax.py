"""Row softmax + fused softmax/cross-entropy.

Parity target: the reference's ``softmax.cl``/``.cu`` and evaluator kernels
(SURVEY.md §2.3): row-wise max-subtracted softmax producing both
probabilities and the argmax index (``All2AllSoftmax.max_idx`` [baseline]),
and the EvaluatorSoftmax cross-entropy error ``y − onehot(label)``.

TPU-native design: one Pallas kernel computes max, exp, sum, normalize and
argmax per row tile in VMEM (single HBM pass); the fused CE variant also
emits per-row loss and the error signal, replacing the reference's separate
evaluator kernel launch.  Per-row scalars (argmax, loss) are carried as
(rows, 1) buffers — TPU vector layouts want ≥2-D tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import tuning


# -- numpy goldens ---------------------------------------------------------
def np_softmax(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    m = x.max(axis=1, keepdims=True)
    e = np.exp(x - m)
    y = e / e.sum(axis=1, keepdims=True)
    return y, x.argmax(axis=1)


def np_softmax_ce(probs: np.ndarray, labels: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray]:
    """(per-row CE loss, error signal y − onehot). ``probs`` are softmax
    outputs (the reference evaluator consumed All2AllSoftmax output)."""
    n, c = probs.shape
    onehot = np.zeros_like(probs)
    onehot[np.arange(n), labels] = 1.0
    loss = -np.log(np.maximum(probs[np.arange(n), labels], 1e-30))
    return loss, probs - onehot


# -- XLA tier --------------------------------------------------------------
def xla_softmax(x):
    y = jax.nn.softmax(x, axis=1)
    return y, jnp.argmax(x, axis=1)


def xla_softmax_ce(probs, labels):
    n, c = probs.shape
    onehot = jax.nn.one_hot(labels, c, dtype=probs.dtype)
    loss = -jnp.log(jnp.maximum(
        jnp.take_along_axis(probs, labels[:, None], axis=1)[:, 0], 1e-30))
    return loss, probs - onehot


def xla_softmax_ce_from_logits(logits, labels):
    """(probs, per-row loss, err) from logits — the fused-step formulation."""
    n, c = logits.shape
    m = jnp.max(logits, axis=1, keepdims=True)
    sh = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(sh), axis=1, keepdims=True))
    logp = sh - lse
    y = jnp.exp(logp)
    onehot = jax.nn.one_hot(labels, c, dtype=logits.dtype)
    loss = -jnp.sum(logp * onehot, axis=1)
    return y, loss, y - onehot


# -- Pallas kernels --------------------------------------------------------
def _softmax_kernel(x_ref, y_ref, idx_ref):
    x = x_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    y_ref[:] = (e / jnp.sum(e, axis=1, keepdims=True)).astype(y_ref.dtype)
    idx_ref[:] = jnp.argmax(x, axis=1, keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def pallas_softmax(x, block_rows: int = 256):
    """Row softmax + argmax in one VMEM pass; rows tiled over the grid."""
    n, c = x.shape
    br = min(block_rows, tuning.round_up(n, 8))
    npad = tuning.round_up(n, br)
    if npad != n:
        x = jnp.pad(x, ((0, npad - n), (0, 0)))
    y, idx = pl.pallas_call(
        _softmax_kernel,
        grid=(npad // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((npad, c), x.dtype),
                   jax.ShapeDtypeStruct((npad, 1), jnp.int32)],
        name="pallas_softmax",
        interpret=tuning.interpret_mode(),
    )(x)
    return y[:n], idx[:n, 0]


def _softmax_ce_kernel(logit_ref, label_ref, y_ref, loss_ref, err_ref):
    x = logit_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=1, keepdims=True)
    y = e / s
    labels = label_ref[:]                       # (rows, 1) int32
    onehot = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
              == labels).astype(jnp.float32)
    logp = (x - m) - jnp.log(s)                 # stable log-softmax
    loss_ref[:] = -jnp.sum(logp * onehot, axis=1, keepdims=True)
    y_ref[:] = y.astype(y_ref.dtype)
    err_ref[:] = (y - onehot).astype(err_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def pallas_softmax_ce_from_logits(logits, labels, block_rows: int = 256):
    """Fused softmax + CE + error from *logits* (single HBM pass).

    Returns (probs, per-row loss, err = probs − onehot)."""
    n, c = logits.shape
    br = min(block_rows, tuning.round_up(n, 8))
    npad = tuning.round_up(n, br)
    if npad != n:
        logits = jnp.pad(logits, ((0, npad - n), (0, 0)))
        labels = jnp.pad(labels, (0, npad - n), constant_values=0)
    labels2d = labels.astype(jnp.int32)[:, None]
    y, loss, err = pl.pallas_call(
        _softmax_ce_kernel,
        grid=(npad // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0)),
                   pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((npad, c), logits.dtype),
                   jax.ShapeDtypeStruct((npad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((npad, c), logits.dtype)],
        name="pallas_softmax_ce_from_logits",
        interpret=tuning.interpret_mode(),
    )(logits, labels2d)
    return y[:n], loss[:n, 0], err[:n]


def softmax(x):
    if tuning.use_pallas():
        return pallas_softmax(x)
    return xla_softmax(x)


#: widest row the fused CE kernel takes: its three ``(256, classes)``
#: float32 windows, double-buffered, have to fit VMEM (6 MiB at 1,000
#: classes; a vocabulary of 24,576 would need 151 MiB), and it writes
#: the probabilities, one ``(rows, classes)`` array more than the loss
#: and the error need.  Wider rows take XLA's fusions
KERNEL_MAX_CLASSES = 2048


def softmax_ce_from_logits(logits, labels):
    if tuning.use_pallas() and logits.shape[1] <= KERNEL_MAX_CLASSES:
        return tuning.batch_sharded(pallas_softmax_ce_from_logits,
                                    logits, labels)
    return xla_softmax_ce_from_logits(logits, labels)
