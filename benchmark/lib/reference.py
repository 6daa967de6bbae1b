"""The plain reference: a configuration's layer list, forward, loss,
gradients and the momentum update in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision, with ``jax.grad`` for the
backward pass.  No kernels, no scan, no caches, nothing imported from
the program and nothing taken from what it made: images and weights come
from ``benchmark/lib/data.py`` and the seed.

Layer semantics (NHWC images, HWIO conv weights, (n_in, n_out) fc
weights, all as the configuration file states them):

  conv_str     relu(conv(x, w, stride, pad) + b)
  norm         x * (k + alpha * sum_{n-window over channels} x^2)^-beta
  max_pooling  max over ky x kx windows, stride ``sliding``, no padding
  dropout      x * mask / (1 - ratio) while training, identity otherwise
  all2all_str  relu(flatten(x) @ w + b)
  softmax      flatten(x) @ w + b  -> mean cross-entropy over the batch

The update, per leaf:  v <- m*v - lr*(g + wd*p);  p <- p + v.

Dropout masks are part of the arithmetic.  The program draws them from a
counter hash, so the reference carries its own copy of that published
rule (``dropout_mask``): murmur3's 32-bit finaliser folded over (stream
seed, unit id, epoch, samples consumed after the step) and the element
index; keep = u >= ratio.

``operand`` is the control's hook: a function applied to both operands
of every convolution and matrix product, forward and backward."""

from __future__ import annotations

import hashlib
import zlib

import jax
import jax.numpy as jnp
import numpy as np

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)
HIGHEST = jax.lax.Precision.HIGHEST


# -- dropout masks --------------------------------------------------------
def _mix(x):
    x = x ^ (x >> np.uint32(16))
    x = x * _C1
    x = x ^ (x >> np.uint32(13))
    x = x * _C2
    return x ^ (x >> np.uint32(16))


def dropout_stream_seed(seed: int) -> int:
    digest = hashlib.sha256(f"{int(seed)}:dropout".encode()).digest()
    return int.from_bytes(digest[:8], "little") & 0xFFFFFFFF


def dropout_unit_id(layer_index: int) -> int:
    return zlib.crc32(f"fwd{layer_index}_dropout".encode())


def dropout_mask(seed: int, layer_index: int, epoch: int, ctr: int,
                 shape: tuple, ratio: float):
    key = _mix(jnp.uint32(dropout_stream_seed(seed)))
    for c in (dropout_unit_id(layer_index), epoch, ctr):
        key = _mix((key ^ jnp.uint32(c)) + _GOLDEN)
    idx = jnp.arange(int(np.prod(shape)), dtype=jnp.uint32)
    u = (_mix((idx * _C2) ^ key) >> np.uint32(8)).astype(jnp.float32) \
        * np.float32(1.0 / (1 << 24))
    keep = (u >= np.float32(ratio)).astype(jnp.float32)
    return (keep * np.float32(1.0 / (1.0 - ratio))).reshape(shape)


# -- the two bilinear operations ------------------------------------------
def _bilinear(op, operand):
    """``op(x, w)`` with ``operand`` applied to x, w and, in the
    backward pass, to the incoming error as well."""
    if operand is None:
        return op

    @jax.custom_vjp
    def f(x, w):
        return op(operand(x), operand(w))

    def fwd(x, w):
        return f(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        _, vjp = jax.vjp(op, operand(x), operand(w))
        return vjp(operand(g))

    f.defvjp(fwd, bwd)
    return f


def _conv(stride: int, pad: int):
    def op(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return op


def _dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


# -- forward and loss -----------------------------------------------------
def logits_of(layers, params, x, *, masks=None, operand=None):
    """``masks``: {layer index: mask} while training, None otherwise."""
    h = x
    for i, (layer, p) in enumerate(zip(layers, params)):
        kind, cfg = layer["type"], layer.get("->", {})
        if kind == "conv_str":
            conv = _bilinear(_conv(cfg.get("sliding", 1),
                                   cfg.get("padding", 0)), operand)
            h = jnp.maximum(conv(h, p[0]) + p[1], 0.0)
        elif kind == "norm":
            n, c = cfg["n"], h.shape[-1]
            sq = jnp.pad(h * h, [(0, 0)] * 3 + [((n - 1) // 2, n // 2)])
            s = sum(sq[..., j:j + c] for j in range(n))
            h = h * (cfg["k"] + cfg["alpha"] * s) ** (-cfg["beta"])
        elif kind == "max_pooling":
            st = cfg.get("sliding", 1)
            h = jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, cfg["ky"], cfg["kx"], 1),
                (1, st, st, 1), "VALID")
        elif kind == "dropout":
            if masks is not None:
                h = h * masks[i]
        elif kind == "all2all_str":
            h = jnp.maximum(_bilinear(_dot, operand)(
                h.reshape(h.shape[0], -1), p[0]) + p[1], 0.0)
        elif kind == "softmax":
            h = _bilinear(_dot, operand)(h.reshape(h.shape[0], -1),
                                         p[0]) + p[1]
        else:
            raise ValueError(f"the reference has no layer type {kind!r}")
    return h


def mean_loss(layers, params, x, labels, *, masks=None, operand=None,
              rows=None):
    """Mean cross-entropy; ``rows`` restricts the mean (a planted fault:
    part of the batch left out, the mean taken over the rest)."""
    logits = logits_of(layers, params, x, masks=masks, operand=operand)
    logp = jax.nn.log_softmax(logits, axis=1)
    per_row = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return jnp.mean(per_row if rows is None else per_row[:rows])


def _mask_shapes(layers, input_shape, batch):
    from . import flops
    shapes = [tuple(input_shape)] + flops.shapes_after(layers, input_shape)
    return {i: (batch, *shapes[i]) for i, la in enumerate(layers)
            if la["type"] == "dropout"}


def _sketches(pairs):
    """``data.sketch`` of every leaf, numbered in order (w, b, w, b ...)."""
    from . import data
    return [tuple(data.sketch(a, 2 * k + j) for j, a in enumerate(pair))
            for k, pair in enumerate(pairs)]


def _lists(pairs):
    return [tuple(np.asarray(a).tolist() for a in pair) for pair in pairs]


def follow(cfg, params, images, labels, *, seed: int, epoch: int = 0,
           steps: int = 3, operand=None, half_batch: bool = False,
           frozen: bool = False) -> dict:
    """Train ``steps`` minibatches (``images``: (steps, batch, h, w, c),
    ``labels``: (steps, batch), as the model file's ``make_rows`` made
    them) of the configuration ``cfg`` (its ``layers``) from ``params``
    with zero velocities.  ``seed`` keys the dropout
    masks: the seed the program was launched with.  Returns the losses, the
    per-leaf norms of the first gradient and of the parameters' change,
    in the order of the parameterised layers.

    ``half_batch`` and ``frozen`` plant the faults the check has to
    catch: half of each batch left out, and a step that returns its
    state unchanged."""
    layers = cfg["layers"]
    batch = images.shape[1]
    mshapes = _mask_shapes(layers, images.shape[2:], batch)
    leaves = [i for i, p in enumerate(params) if p is not None]
    hyp = {i: layers[i]["<-"] for i in leaves}

    def step(params, vels, x, y, ctr):
        masks = {i: dropout_mask(seed, i, epoch, ctr, sh,
                                 layers[i]["->"]["dropout_ratio"])
                 for i, sh in mshapes.items()}
        loss, grads = jax.value_and_grad(
            lambda ps: mean_loss(
                layers, ps, x, y, masks=masks, operand=operand,
                rows=batch // 2 if half_batch else None))(params)
        new_p, new_v = list(params), list(vels)
        for i in leaves:
            h = hyp[i]
            (w, b), (gw, gb), (vw, vb) = params[i], grads[i], vels[i]
            vw = h["gradient_moment"] * vw - h["learning_rate"] * (
                gw + h["weights_decay"] * w)
            vb = h["gradient_moment_bias"] * vb - h["learning_rate_bias"] \
                * (gb + h["weights_decay_bias"] * b)
            new_p[i], new_v[i] = (w + vw, b + vb), (vw, vb)
        norms = [tuple(jnp.sqrt(jnp.sum(g * g)) for g in grads[i])
                 for i in leaves]
        return new_p, new_v, loss, norms, _sketches(
            [grads[i] for i in leaves])

    step = jax.jit(step)
    p0 = params
    vels = [None if p is None else tuple(jnp.zeros_like(a) for a in p)
            for p in params]
    losses, grad_norms, grad_sketches = [], None, None
    for s in range(steps):
        new_p, new_v, loss, norms, sketches = step(
            params, vels, images[s], labels[s], (s + 1) * batch)
        if not frozen:
            params, vels = new_p, new_v
        losses.append(float(loss))
        if s == 0:
            grad_norms = [tuple(float(n) for n in pair) for pair in norms]
            grad_sketches = _lists(sketches)
    moved = [tuple(a - a0 for a, a0 in zip(params[i], p0[i]))
             for i in leaves]
    change = [tuple(float(jnp.sqrt(jnp.sum(jnp.square(d)))) for d in pair)
              for pair in moved]
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "grad_sketches": grad_sketches}


def fp8_operand(t):
    """The control's precision: float8 (e4m3) operands with a per-tensor
    scale, the step below the configuration's bfloat16 operands."""
    amax = jnp.maximum(jnp.max(jnp.abs(t)), np.float32(1e-30))
    scale = amax / np.float32(448.0)
    return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16_operand(t):
    """What the configuration states: one bfloat16 pass."""
    return t.astype(jnp.bfloat16).astype(jnp.float32)


#: what ``tests/limits_study.py`` reads beside the reference itself, as
#: keywords of ``follow``: the control, the stated precision, the faults
VARIANTS = {"control_fp8": {"operand": fp8_operand},
            "stated_bf16": {"operand": bf16_operand},
            "fault_half_batch": {"half_batch": True},
            "fault_frozen": {"frozen": True}}
