"""Inputs and weights as pure functions of ``--seed``.

Every image is a function of (seed, global row number) alone, so the
loader can make the whole resident set in one jitted call on the device
and the plain reference can make the few rows it follows again, after
the program's set has been freed, without taking anything from the
program.  Weights likewise: one jitted call, He-normal, zero biases.

The integer hash is the murmur3 32-bit finalizer (public domain)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)
PROTO = 8          # prototypes are PROTO x PROTO x channels


def _mix(x):
    x = x ^ (x >> np.uint32(16))
    x = x * _C1
    x = x ^ (x >> np.uint32(13))
    x = x * _C2
    return x ^ (x >> np.uint32(16))


def seed_array(seed: int) -> np.ndarray:
    """The seed as the jitted makers take it: two 32-bit words
    (``--seed`` may pass 2**31), and an argument, not a constant of the
    program, so that every seed runs the same compiled program."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                      np.uint32)


def _key(words, salt: int):
    k = _mix(words[0] ^ np.uint32(salt))
    return _mix((k ^ words[1]) + _GOLDEN)


def _unit(bits):
    """uint32 bits -> float32 in [-1, 1)."""
    return ((bits >> np.uint32(8)).astype(jnp.float32)
            * np.float32(2.0 / (1 << 24)) - np.float32(1.0))


def labels_of(words, rows, n_classes: int):
    rows = jnp.asarray(rows).astype(jnp.uint32)
    bits = _mix((rows * _C2) ^ _key(words, 0x1ABE1))
    return (bits % np.uint32(n_classes)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _make_rows(words, rows, size: int, channels: int, n_classes: int,
               noise: float):
    rows = jnp.asarray(rows).astype(jnp.uint32)
    labels = labels_of(words, rows, n_classes)
    rep = size // PROTO + 1
    yy = (jnp.arange(size, dtype=jnp.uint32) // np.uint32(rep))
    cell = (yy[:, None] * np.uint32(PROTO) + yy[None, :])      # (s, s)
    ch = jnp.arange(channels, dtype=jnp.uint32)
    pidx = cell[:, :, None] * np.uint32(channels) + ch         # (s, s, c)
    lab = labels.astype(jnp.uint32)[:, None, None, None]
    proto = _unit(_mix(((lab * np.uint32(PROTO * PROTO * channels)
                         + pidx[None]) * _C2) ^ _key(words, 0x9207)))
    elem = jnp.arange(size * size * channels, dtype=jnp.uint32).reshape(
        size, size, channels)
    rkey = _mix((rows * _C1) ^ _key(words, 0x5EED))[:, None, None, None]
    eps = _unit(_mix((elem[None] * _C2) ^ rkey))
    return proto + np.float32(noise) * eps, labels


def make_rows(seed: int, rows, size: int, channels: int, n_classes: int,
              noise: float):
    """(images, labels) of the global row numbers ``rows``: a class
    prototype (PROTO x PROTO x channels, nearest-neighbour upsampled)
    plus uniform noise in [-noise, noise)."""
    return _make_rows(seed_array(seed), np.asarray(rows, np.uint32), size,
                      channels, n_classes, noise)


SKETCH = 64       # signed sums a tensor is reduced to


def sketch(a, leaf: int):
    """``SKETCH`` sums of a tensor's elements under pseudo-random signs,
    block by block.  Two tensors' sketches differ by the sketch of their
    difference, whose squared norm estimates the difference's own
    (expectation exact, spread about sqrt(2 / SKETCH)): the norm of a
    difference between tensors that are never on the device together."""
    flat = a.reshape(-1).astype(jnp.float32)
    idx = jnp.arange(flat.shape[0], dtype=jnp.uint32)
    bits = _mix((idx * _C2) ^ _mix(jnp.uint32(leaf) + _GOLDEN))
    signed = jnp.where(bits >> np.uint32(31), flat, -flat)
    return jnp.pad(signed, (0, (-flat.shape[0]) % SKETCH)).reshape(
        SKETCH, -1).sum(axis=1)


def make_weights(seed: int, shapes: list) -> list:
    """He-normal weights and zero biases for a list of
    (weights' shape, bias's shape) or None, on the default device, in
    one jitted call."""
    @jax.jit
    def build(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(0x2E1C2), words[0]), words[1])
        out = []
        for i, sh in enumerate(shapes):
            if sh is None:
                out.append(None)
                continue
            w_shape, b_shape = sh
            fan_in = int(np.prod(w_shape[:-1]))
            w = jax.random.normal(jax.random.fold_in(key, i), w_shape,
                                  jnp.float32) * np.float32(
                                      np.sqrt(2.0 / fan_in))
            out.append((w, jnp.zeros(b_shape, jnp.float32)))
        return out
    return build(seed_array(seed))
