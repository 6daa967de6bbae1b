"""Pooling: max / max-abs / avg / stochastic, with winner offsets for
backprop.

Parity target: the reference's ``pooling.cl/.cu`` + ``gd_pooling`` kernels
(SURVEY.md §2.3 row 3: max/avg pool forward storing winner offsets, and the
offset-scatter backward).

TPU-native design (SURVEY.md §7 hard part (a) — irregular scatter):

* Winner offsets are stored as a *dense* int32 window-slot index in
  ``[0, KH·KW)`` per output element (not flat input offsets as the
  reference's GPU kernels used) — a static-shape tensor XLA handles.
* Forward runs as a static KH·KW-step running max/argmax over strided
  slices (unrolled at trace time; XLA fuses it into one VPU pass per tap).
* Backward scatters by equality-select against the stored slot index and
  strided ``.at[].add`` — dense compare+add, no gather/scatter engine
  needed, MXU-free and VPU-friendly.
* On the Pallas tier max / max-abs pooling, its backward and depooling
  have TWO paths, picked by :func:`windowed` from the operands alone (no
  option).  *Windowed*: where the windows tile the input exactly
  (stride = ksize, no padding, H and W whole multiples; VGG's 2×2/2)
  each input element belongs to one window, so x is viewed as
  (OH, KH, OW, KW, B, C) and ONE kernel pass a direction reads x and
  writes y + idx, or reads err + idx and writes every dx element once
  (``elementwise.pallas_pool_window`` / ``pallas_gd_pool_window``): no
  tap stack, no zero fill, no add.  *Tap stack*: overlapping, padded or
  ragged windows (AlexNet's 3×3/2), a batch that is not a multiple of 8
  and packed activations stack the KH·KW strided taps in XLA and select /
  scatter in the kernels below them, then ``.at[].add`` into dx — which
  overlapping windows need and non-overlapping ones do not.  The two
  share only the winner rule (row-major taps, strict ``>``, ties keep
  the first tap), so ``idx`` and the float32 gradient are bit-equal
  either way.
* Max pooling pads with −∞ (a padded zero must never win); avg pooling
  pads with 0 and divides by the full window area (reference semantics).

Layout NHWC throughout (channels minor → VPU lanes)."""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from . import rngbits
from .geometry import norm2 as _norm2, out_size


def pool_out_shape(x_shape, ksize, stride=None, padding=0):
    """NHWC output shape of a pooling window over ``x_shape``."""
    (kh, kw), (ph, pw) = _norm2(ksize), _norm2(padding)
    (sh, sw) = _norm2(stride if stride is not None else ksize)
    b, h, w, c = x_shape
    return (b, out_size(h, kh, sh, ph), out_size(w, kw, sw, pw), c)


def _taps(kh: int, kw: int):
    return [(t, t // kw, t % kw) for t in range(kh * kw)]


def _pad(x, ph, pw, value, xp):
    if ph == 0 and pw == 0:
        return x
    if xp is np:
        return np.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)),
                      constant_values=value)
    return jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)),
                   constant_values=value)


def _slices(xp_arr, kh, kw, sh, sw, oh, ow):
    """Strided window slices, one per tap: each (B, OH, OW, C)."""
    return [xp_arr[:, i:i + oh * sh:sh, j:j + ow * sw:sw, :]
            for _, i, j in _taps(kh, kw)]


# -- forward (generic over numpy / jnp namespace) --------------------------
def _max_pool(x, ksize, stride, padding, xp, use_abs: bool):
    (kh, kw), (sh, sw), (ph, pw) = _norm2(ksize), _norm2(stride), \
        _norm2(padding)
    b, h, w, c = x.shape
    oh, ow = out_size(h, kh, sh, ph), out_size(w, kw, sw, pw)
    xpad = _pad(x, ph, pw, -np.inf if not use_abs else 0.0, xp)
    best = None
    best_val = None
    idx = None
    for t, sl in enumerate(_slices(xpad, kh, kw, sh, sw, oh, ow)):
        score = xp.abs(sl) if use_abs else sl
        if best is None:
            best, best_val = score, sl
            idx = xp.zeros(sl.shape, np.int32)
        else:
            take = score > best
            best = xp.where(take, score, best)
            best_val = xp.where(take, sl, best_val)
            idx = xp.where(take, np.int32(t), idx)
    return best_val, idx


def np_max_pooling(x, ksize, stride=None, padding=0):
    """→ (y, offsets).  Golden path."""
    return _max_pool(x, ksize, stride or ksize, padding, np, False)


def xla_max_pooling(x, ksize, stride=None, padding=0):
    return _max_pool(x, ksize, stride or ksize, padding, jnp, False)


def np_maxabs_pooling(x, ksize, stride=None, padding=0):
    """Winner is the element with max |value|; output keeps its sign."""
    return _max_pool(x, ksize, stride or ksize, padding, np, True)


def xla_maxabs_pooling(x, ksize, stride=None, padding=0):
    return _max_pool(x, ksize, stride or ksize, padding, jnp, True)


def windowed(x_shape, ksize, stride=None, padding=0,
             dtype=jnp.float32) -> bool:
    """Whether the Pallas tier pools ``x_shape`` on the windowed view
    (header): the windows tile the input exactly — stride equal to the
    window, no padding, H and W whole multiples of it — so every input
    element belongs to one window; the batch fills whole sublane tiles
    (a multiple of 8: the view's tiles lie over batch × channel); and
    the activations are float32 (packed bfloat16 does not lower: Mosaic
    refuses the relayout of its compare mask, as in ROADMAP Speed 2).
    Everything else keeps the tap stack."""
    (kh, kw), (ph, pw) = _norm2(ksize), _norm2(padding)
    (sh, sw) = _norm2(stride if stride is not None else ksize)
    b, h, w, _ = x_shape
    return ((sh, sw) == (kh, kw) and ph == pw == 0
            and h % kh == 0 and w % kw == 0 and b % 8 == 0
            and jnp.dtype(dtype) == jnp.float32)


def _pallas_max_pool(x, ksize, stride, padding, use_abs):
    """Windows that tile x: one kernel pass on the windowed view.
    Otherwise stack the window taps in XLA and run the winner select in
    the Pallas kernel (SURVEY.md §2.3 pooling row; §7 hard part (a)
    split)."""
    from . import elementwise
    if windowed(x.shape, ksize, stride, padding, x.dtype):
        return elementwise.pallas_pool_window(x, _norm2(ksize), use_abs)
    b, h, w, c = x.shape
    _, oh, ow, _ = pool_out_shape(x.shape, ksize, stride, padding)
    taps = _tap_stack(x, (oh, ow), ksize, stride, padding,
                      -np.inf if not use_abs else 0.0, jnp)
    y, idx = elementwise.pallas_pool_select(
        taps.reshape(taps.shape[0], -1, c), use_abs=use_abs)
    return y.reshape(b, oh, ow, c), idx.reshape(b, oh, ow, c)


def max_pooling(x, ksize, stride=None, padding=0):
    """Dispatcher: Pallas winner-select kernel on TPU, XLA otherwise."""
    from . import tuning
    if tuning.use_pallas():
        return tuning.batch_sharded(
            lambda x: _pallas_max_pool(x, ksize, stride or ksize,
                                       padding, False), x)
    return xla_max_pooling(x, ksize, stride, padding)


def maxabs_pooling(x, ksize, stride=None, padding=0):
    from . import tuning
    if tuning.use_pallas():
        return tuning.batch_sharded(
            lambda x: _pallas_max_pool(x, ksize, stride or ksize,
                                       padding, True), x)
    return xla_maxabs_pooling(x, ksize, stride, padding)


def _avg_pool(x, ksize, stride, padding, xp):
    (kh, kw), (sh, sw), (ph, pw) = _norm2(ksize), _norm2(stride), \
        _norm2(padding)
    b, h, w, c = x.shape
    oh, ow = out_size(h, kh, sh, ph), out_size(w, kw, sw, pw)
    xpad = _pad(x, ph, pw, 0.0, xp)
    acc = None
    for sl in _slices(xpad, kh, kw, sh, sw, oh, ow):
        acc = sl if acc is None else acc + sl
    return acc * (1.0 / (kh * kw))


def np_avg_pooling(x, ksize, stride=None, padding=0):
    return _avg_pool(x, ksize, stride or ksize, padding, np)


def xla_avg_pooling(x, ksize, stride=None, padding=0):
    return _avg_pool(x, ksize, stride or ksize, padding, jnp)


def _stochastic_pool(x, ksize, stride, padding, u, xp, use_abs: bool,
                     deterministic: bool):
    """Zeiler–Fergus stochastic pooling.  ``u``: uniforms shaped like the
    output (ignored when deterministic).  Train: sample a window element
    with probability ∝ max(x,0) (or |x|); eval: probability-weighted sum."""
    (kh, kw), (sh, sw), (ph, pw) = _norm2(ksize), _norm2(stride), \
        _norm2(padding)
    b, h, w, c = x.shape
    oh, ow = out_size(h, kh, sh, ph), out_size(w, kw, sw, pw)
    xpad = _pad(x, ph, pw, 0.0, xp)
    slices = _slices(xpad, kh, kw, sh, sw, oh, ow)
    weights = [xp.abs(sl) if use_abs else xp.maximum(sl, 0.0)
               for sl in slices]
    total = weights[0]
    for a in weights[1:]:
        total = total + a
    if deterministic:
        num = slices[0] * weights[0]
        for sl, a in zip(slices[1:], weights[1:]):
            num = num + sl * a
        y = xp.where(total > 0, num / xp.maximum(total, 1e-30), 0.0)
        return y, xp.zeros((b, oh, ow, c), np.int32)
    thr = u * total
    cum = xp.zeros_like(total)
    idx = xp.zeros((b, oh, ow, c), np.int32)
    chosen = xp.zeros_like(total)
    done = cum > thr                      # all-zero windows never trigger
    for t, (sl, a) in enumerate(zip(slices, weights)):
        cum = cum + a
        hit = (cum > thr) & ~done
        idx = xp.where(hit, np.int32(t), idx)
        chosen = xp.where(hit, sl, chosen)
        done = done | hit
    y = xp.where(total > 0, chosen, 0.0)
    return y, idx


def np_stochastic_pooling(x, ksize, stride=None, padding=0, u=None,
                          use_abs=False, deterministic=False):
    return _stochastic_pool(x, ksize, stride or ksize, padding, u, np,
                            use_abs, deterministic)


def xla_stochastic_pooling(x, ksize, stride=None, padding=0, u=None,
                           use_abs=False, deterministic=False):
    return _stochastic_pool(x, ksize, stride or ksize, padding, u, jnp,
                            use_abs, deterministic)


def stochastic_uniform(stream_seed: int, counters, out_shape, xp=np):
    """Output-shaped uniforms from the counter RNG (same bits all tiers)."""
    key = rngbits.fold(stream_seed, *counters, xp=xp)
    n = int(np.prod(out_shape))
    return rngbits.uniform01(key, n, xp=xp).reshape(out_shape)


# -- backward --------------------------------------------------------------
def np_gd_max_pooling(err, offsets, x_shape, ksize, stride=None, padding=0):
    """Scatter err to the stored winner slot of each window."""
    (kh, kw), (sh, sw), (ph, pw) = _norm2(ksize), \
        _norm2(stride or ksize), _norm2(padding)
    b, h, w, c = x_shape
    _, oh, ow, _ = err.shape
    dx = np.zeros((b, h + 2 * ph, w + 2 * pw, c), np.float32)
    for t, i, j in _taps(kh, kw):
        dx[:, i:i + oh * sh:sh, j:j + ow * sw:sw, :] += \
            err * (offsets == t)
    return dx[:, ph:ph + h, pw:pw + w, :]


def xla_gd_max_pooling(err, offsets, x_shape, ksize, stride=None,
                       padding=0):
    (kh, kw), (sh, sw), (ph, pw) = _norm2(ksize), \
        _norm2(stride or ksize), _norm2(padding)
    b, h, w, c = x_shape
    _, oh, ow, _ = err.shape
    dx = jnp.zeros((b, h + 2 * ph, w + 2 * pw, c), jnp.float32)
    for t, i, j in _taps(kh, kw):
        dx = dx.at[:, i:i + oh * sh:sh, j:j + ow * sw:sw, :].add(
            err * (offsets == t))
    return dx[:, ph:ph + h, pw:pw + w, :]


def _pallas_gd_max_pool(err, offsets, x_shape, ksize, stride, padding):
    """Pallas offset-scatter backward.  Windows that tile dx: one
    kernel pass writes every element once.  Otherwise the per-tap
    equality select runs in one kernel pass
    (elementwise.pallas_pool_scatter) and the regular strided placement
    of each tap into dx stays in XLA."""
    from . import elementwise
    (kh, kw), (sh, sw), (ph, pw) = _norm2(ksize), \
        _norm2(stride or ksize), _norm2(padding)
    _, h, w, c = x_shape
    b, oh, ow, _ = err.shape      # b from the operand: batch_sharded
    if windowed((b, h, w, c), ksize, stride, padding, err.dtype):
        return elementwise.pallas_gd_pool_window(err, offsets, (kh, kw))
    taps = elementwise.pallas_pool_scatter(
        err.reshape(-1, c), offsets.reshape(-1, c), kh * kw)
    taps = taps.reshape(kh * kw, b, oh, ow, c)
    dx = jnp.zeros((b, h + 2 * ph, w + 2 * pw, c), jnp.float32)
    for t, i, j in _taps(kh, kw):
        dx = dx.at[:, i:i + oh * sh:sh, j:j + ow * sw:sw, :].add(taps[t])
    return dx[:, ph:ph + h, pw:pw + w, :]


def gd_max_pooling(err, offsets, x_shape, ksize, stride=None, padding=0):
    """Dispatcher: Pallas scatter kernel on TPU, XLA otherwise."""
    from . import tuning
    if tuning.use_pallas():
        return tuning.batch_sharded(
            lambda err, offsets: _pallas_gd_max_pool(
                err, offsets, x_shape, ksize, stride, padding),
            err, offsets)
    return xla_gd_max_pooling(err, offsets, x_shape, ksize, stride,
                              padding)


def np_depooling(x, offsets, out_shape, ksize, stride=None, padding=0):
    """Unpooling (decoder path): scatter each pooled value back to its
    recorded winner slot — the same dense compare+add scatter as the
    max-pool backward, used as a *forward* op (reference Depooling)."""
    return np_gd_max_pooling(x, offsets, out_shape, ksize, stride, padding)


def xla_depooling(x, offsets, out_shape, ksize, stride=None, padding=0):
    return xla_gd_max_pooling(x, offsets, out_shape, ksize, stride, padding)


def _depool_gather(err, offsets, ksize, stride, padding, xp):
    """Adjoint of the depooling scatter: gather err at each window's
    recorded winner slot → (B, OH, OW, C) shaped like the pooled tensor."""
    (kh, kw), (ph, pw) = _norm2(ksize), _norm2(padding)
    (sh, sw) = _norm2(stride if stride is not None else ksize)
    b, oh, ow, c = offsets.shape
    epad = _pad(err, ph, pw, 0.0, xp)
    acc = None
    for t, i, j in _taps(kh, kw):
        sl = epad[:, i:i + oh * sh:sh, j:j + ow * sw:sw, :]
        term = sl * (offsets == t)
        acc = term if acc is None else acc + term
    return acc


def np_gd_depooling(err, offsets, ksize, stride=None, padding=0):
    return _depool_gather(err, offsets, ksize, stride, padding, np)


def xla_gd_depooling(err, offsets, ksize, stride=None, padding=0):
    return _depool_gather(err, offsets, ksize, stride, padding, jnp)


def depooling(x, offsets, out_shape, ksize, stride=None, padding=0):
    """Dispatcher for the decoder-path scatter (same core as gd_max)."""
    from . import tuning
    if tuning.use_pallas():
        return tuning.batch_sharded(
            lambda x, offsets: _pallas_gd_max_pool(
                x, offsets, out_shape, ksize, stride, padding),
            x, offsets)
    return xla_depooling(x, offsets, out_shape, ksize, stride, padding)


def _tap_stack(x, out_hw, ksize, stride, padding, pad_value, xp):
    """Pad + stack the strided window taps: (T, B, OH, OW, C) — the
    shared extraction behind the forward select, the depooling-backward
    gather, and the stochastic tiers (one place owns the slicing math)."""
    (kh, kw), (ph, pw) = _norm2(ksize), _norm2(padding)
    (sh, sw) = _norm2(stride if stride is not None else ksize)
    oh, ow = out_hw
    xpad = _pad(x, ph, pw, pad_value, xp)
    stack = np.stack if xp is np else jnp.stack
    return stack(_slices(xpad, kh, kw, sh, sw, oh, ow))


def gd_depooling(err, offsets, ksize, stride=None, padding=0):
    """Dispatcher: winner-tap gather kernel on TPU, XLA otherwise."""
    from . import elementwise, tuning
    if not tuning.use_pallas():
        return xla_gd_depooling(err, offsets, ksize, stride, padding)

    def gather(err, offsets):
        b, oh, ow, c = offsets.shape
        taps = _tap_stack(err, (oh, ow), ksize, stride, padding, 0.0,
                          jnp)
        out = elementwise.pallas_pool_gather(
            taps.reshape(taps.shape[0], -1, c), offsets.reshape(-1, c))
        return out.reshape(b, oh, ow, c)
    return tuning.batch_sharded(gather, err, offsets)


def np_gd_avg_pooling(err, x_shape, ksize, stride=None, padding=0):
    (kh, kw), (sh, sw), (ph, pw) = _norm2(ksize), \
        _norm2(stride or ksize), _norm2(padding)
    b, h, w, c = x_shape
    _, oh, ow, _ = err.shape
    scaled = err * (1.0 / (kh * kw))
    dx = np.zeros((b, h + 2 * ph, w + 2 * pw, c), np.float32)
    for t, i, j in _taps(kh, kw):
        dx[:, i:i + oh * sh:sh, j:j + ow * sw:sw, :] += scaled
    return dx[:, ph:ph + h, pw:pw + w, :]


def xla_gd_avg_pooling(err, x_shape, ksize, stride=None, padding=0):
    (kh, kw), (sh, sw), (ph, pw) = _norm2(ksize), \
        _norm2(stride or ksize), _norm2(padding)
    b, h, w, c = x_shape
    _, oh, ow, _ = err.shape
    scaled = err * (1.0 / (kh * kw))
    dx = jnp.zeros((b, h + 2 * ph, w + 2 * pw, c), jnp.float32)
    for t, i, j in _taps(kh, kw):
        dx = dx.at[:, i:i + oh * sh:sh, j:j + ow * sw:sw, :].add(scaled)
    return dx[:, ph:ph + h, pw:pw + w, :]
