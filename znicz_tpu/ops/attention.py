"""Decoder attention: RMSNorm, rotary embeddings, causal / sliding-window
grouped-query attention, the embedding and the output head.

The token-sequence layer kinds of ``parallel/fused.py`` (``embed``,
``attn_block``, ``lm_head``) are pure functions ``fwd(leaves, x, cfg,
cdt) -> (y, counters)`` here (``counters``: the device counters of the
call, none for these three); their gradient is ``jax.vjp`` of the same
function (a kernel brings its own ``custom_vjp``).

Attention never holds a T x T score matrix.  Two tiers, dispatched as
the repo's other ops are:

* TPU (and the Pallas interpreter): JAX's shipped splash attention
  (``jax.experimental.pallas.ops.tpu.splash_attention``), one MQA kernel
  a key/value head over its group of query heads, with a causal or a
  local mask.  It skips the blocks the mask empties, so a window of 1024
  at T = 8192 does about a quarter of a full layer's score work, and its
  operations are named ``splash_mqa_fwd*`` / ``splash_mqa_dkv*`` in a
  device trace.
* everywhere else: :func:`blocked_attention`, plain ``jnp`` over query
  blocks, each block rematerialised in the backward; a sliding layer
  reads only the key span its window reaches.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import tuning

#: query rows a block of the plain tier and of the kernels
BLOCK_Q = 512
#: key rows a block of the splash backward, one fused kernel for dq, dk
#: and dv: it writes a partial dq a key block, ``T / BLOCK_KV_BWD`` of
#: them, summed after (PERF.md section 5 has what the other settings read)
BLOCK_KV_BWD = 1024
#: positions the fine rotary table holds (see :func:`rope_tables`)
ROPE_FINE = 64


# -- norms and tables -------------------------------------------------------
def rms_norm(x, g, eps: float):
    """``x / sqrt(mean(x^2) + eps) * g`` in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope_inv_freq(head_dim: int, rope: dict) -> np.ndarray:
    """The rotary frequencies ``(head_dim / 2,)`` of one layer type.
    ``rope``: ``{"rope_type": "default" | "yarn", "rope_theta", ...}``
    as a model's ``rope_parameters`` entry gives it.  YaRN: the
    linear-ramp blend of ``1 / pos_freq`` (kept above ``beta_fast``
    rotations over the original length) and ``1 / (factor * pos_freq)``
    (below ``beta_slow``), at every sequence length."""
    half = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
    pos_freq = float(rope["rope_theta"]) ** half
    if rope.get("rope_type", "default") == "default":
        return 1.0 / pos_freq
    if rope["rope_type"] != "yarn":
        raise NotImplementedError(f"rope_type {rope['rope_type']!r}")
    base, orig = float(rope["rope_theta"]), float(
        rope["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))),
               head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return (1.0 / (float(rope["factor"]) * pos_freq)) * (1.0 - keep) \
        + (1.0 / pos_freq) * keep


@functools.lru_cache(maxsize=8)
def rope_parts(seq_len: int, head_dim: int, rope: tuple):
    """``(cos, sin)`` of the rotary angles at every ``ROPE_FINE``-th
    position, ``(ceil(seq_len / ROPE_FINE), head_dim / 2)``, and at the
    positions ``0 .. ROPE_FINE - 1``, ``(ROPE_FINE, head_dim / 2)``: four
    float32 host arrays made in float64.  ``rope``: the sorted items of
    the layer type's rope entry."""
    inv = rope_inv_freq(head_dim, dict(rope))
    coarse = np.arange(0, seq_len, ROPE_FINE, dtype=np.float64)
    fine = np.arange(ROPE_FINE, dtype=np.float64)
    return tuple(f(pos[:, None] * inv[None, :]).astype(np.float32)
                 for pos in (coarse, fine) for f in (np.cos, np.sin))


def rope_tables(seq_len: int, head_dim: int, rope: tuple):
    """``(cos, sin)``, each ``(seq_len, head_dim)`` float32, the two
    halves alike (half-rotation), times the type's ``attention_factor``
    (1 unless the entry gives one).  Made in the program from
    :func:`rope_parts` by the angle sum of position ``ROPE_FINE * a + b``,
    within 2e-7 of the float64 tables (three of their roundings to
    float32).  A layer then holds 96 KB of constants at 8,192 positions;
    whole tables as constants were 8 MB at each of a training program's
    24 uses and put its executable beyond what a compile cache keeps
    (PERF.md section 6, PR 30)."""
    cos_a, sin_a, cos_b, sin_b = map(
        jnp.asarray, rope_parts(seq_len, head_dim, rope))
    cos_a, sin_a = cos_a[:, None, :], sin_a[:, None, :]
    cos_b, sin_b = cos_b[None, :, :], sin_b[None, :, :]
    f = float(dict(rope).get("attention_factor", 1.0))

    def whole(t):
        t = t.reshape(-1, head_dim // 2)[:seq_len] * f
        return jnp.concatenate([t, t], axis=1)
    return (whole(cos_a * cos_b - sin_a * sin_b),
            whole(sin_a * cos_b + cos_a * sin_b))


def apply_rope(x, cos, sin):
    """``x``: ``(B, T, heads, head_dim)``; half-rotation."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


# -- attention: the plain blocked tier -------------------------------------
def blocked_attention(q, k, v, window: int | None, block_q: int = BLOCK_Q):
    """Causal (``window`` None) or sliding-window grouped-query attention.
    ``q``: ``(B, T, H, D)`` already scaled, ``k``/``v``: ``(B, T, KV, D)``;
    query head ``h`` reads key/value head ``h // (H // KV)``.  Kept: ``j <=
    i`` and, with a window, ``i - j < window``.  Scores and softmax in
    float32, one query block at a time (rematerialised in the backward),
    over the key span the block can see."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    bq = min(block_q, t)
    if t % bq:
        raise ValueError(f"sequence length {t} is no multiple of the "
                         f"query block {bq}")
    span = t if window is None else min(t, bq + window - 1)
    qb = q.reshape(b, t // bq, bq, kv, g, d).swapaxes(0, 1)

    @jax.checkpoint
    def one(args):
        blk, q_blk = args
        q0 = blk * bq
        start = jnp.clip(q0 + bq - span, 0, t - span)
        ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, ks,
                       preferred_element_type=jnp.float32)
        i = q0 + jnp.arange(bq)[:, None]
        j = start + jnp.arange(span)[None, :]
        keep = j <= i
        if window is not None:
            keep &= i - j < window
        s = jnp.where(keep, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), vs,
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(one, (jnp.arange(t // bq), qb))
    return out.swapaxes(0, 1).reshape(b, t, h, d)


# -- attention: the kernel tier --------------------------------------------
@functools.lru_cache(maxsize=16)
def _splash_kernel(seq_len: int, group: int, window: int | None,
                   interpret: bool):
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    shape = (seq_len, seq_len)
    mask = (sa.CausalMask(shape) if window is None
            else sa.LocalMask(shape, (window - 1, 0), 0))
    blk = min(BLOCK_Q, seq_len)
    sizes = sa.BlockSizes(block_q=blk, block_kv=blk, block_kv_compute=blk,
                          block_q_dkv=blk,
                          block_kv_dkv=min(BLOCK_KV_BWD, seq_len),
                          block_kv_dkv_compute=blk,
                          use_fused_bwd_kernel=True)
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mqa_single_device(
            sa.MultiHeadMask([mask] * group), block_sizes=sizes,
            interpret=interpret)


def splash_attention(q, k, v, window: int | None, kernel=None):
    """The same function as :func:`blocked_attention` through the shipped
    splash MQA kernel, one call a (row, key/value head).  ``kernel``: one
    built with other block sizes, for tools/bench_decoder_kernels.py."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    if kernel is None:
        kernel = _splash_kernel(t, h // kv, window, tuning.interpret_mode())
    qh = q.reshape(b, t, kv, h // kv, d).transpose(0, 2, 3, 1, 4)
    out = jax.vmap(jax.vmap(kernel))(qh, k.transpose(0, 2, 1, 3),
                                     v.transpose(0, 2, 1, 3))
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, d)


def kernel_route(seq_len: int, head_dim: int) -> bool:
    """Whether :func:`attention` takes the splash kernel for these
    shapes (else the plain blocked tier): on the Pallas tier, for heads
    of whole 128-lane tiles and a sequence of whole query blocks."""
    return (tuning.use_pallas() and head_dim % 128 == 0
            and seq_len % min(BLOCK_Q, seq_len) == 0 and seq_len % 128 == 0)


def attention(q, k, v, window: int | None):
    if kernel_route(q.shape[1], q.shape[3]):
        return splash_attention(q, k, v, window)
    return blocked_attention(q, k, v, window)


# -- the layer kinds ---------------------------------------------------------
def embed_fwd(leaves, ids, cfg: dict, cdt=jnp.float32):
    """``embed``: leaf ``table (V_held, d)``; ids ``(B, T)`` int in,
    ``(B, T, d)`` float32 out, times ``cfg["scale"]`` where the model
    has an embedding multiplier."""
    (table,) = leaves
    out = jnp.take(table, ids, axis=0).astype(jnp.float32)
    if cfg.get("scale") is not None:
        out = out * cfg["scale"]
    return out, {}


def residual_block(x, g, cfg: dict, inner):
    """``x + scale * inner(RMSNorm(x; g))`` (``cfg["norm"]`` "pre", or no
    such key) or ``x + scale * RMSNorm(inner(x); g)`` ("post": the norm on
    the sublayer's output): the block around a mixer or a feed-forward,
    float32; ``cfg``: ``eps``, ``scale`` (None: 1)."""
    post = cfg.get("norm", "pre") == "post"
    out = inner(x if post else rms_norm(x, g, cfg["eps"]))
    if post:
        out = rms_norm(out, g, cfg["eps"])
    if cfg.get("scale") is not None:
        out = out * cfg["scale"]
    return x + out


def attn_block_fwd(leaves, x, cfg: dict, cdt=jnp.float32):
    """``attn_block``: ``x + scale * Attn(RMSNorm(x; g1))``, or with
    ``cfg["norm"]`` "post" ``x + scale * RMSNorm(Attn(x); g1)``.  Leaves
    ``g1 (d,)``, ``wq (d, H*D)``, ``wk (d, KV*D)``, ``wv (d, KV*D)``, ``wo
    (H*D, d)`` for the ``H`` query and ``KV`` key/value heads held and,
    with ``cfg["qk_norm"]``, ``gq (H*D,)``, ``gk (KV*D,)``: the gains of
    RMS norms on the query and key projections, over all the channels held
    and before the split into heads.  ``cfg``:
    ``heads``, ``kv_heads``, ``head_dim``, ``window`` (None: full causal),
    ``rope`` (sorted items of the layer type's rope entry; None: no
    positional embedding), ``eps`` and, where the model states them,
    ``score_scale`` (on ``q k^T``; else ``1 / sqrt(head_dim)``) and
    ``scale`` (the block's scale on what it adds to the stream).  Matmul
    operands in ``cdt``, accumulation, the residual, the norms and the
    softmax in float32."""
    g1, wq, wk, wv, wo, *qk_gains = leaves
    b, t, _ = x.shape
    nh, nkv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    if bool(qk_gains) != bool(cfg.get("qk_norm")):
        raise ValueError(f"attn_block with qk_norm {cfg.get('qk_norm')!r} "
                         f"and {len(leaves)} leaves")

    def mixer(xn):
        xn = xn.astype(cdt)

        def proj(w, heads, gain=None):
            y = jnp.dot(xn, w.astype(cdt),
                        preferred_element_type=jnp.float32)
            if gain is not None:
                with jax.named_scope("qk_norm"):
                    y = rms_norm(y, gain, cfg["eps"])
            return y.reshape(b, t, heads, hd)
        q, k, v = (proj(wq, nh, *qk_gains[:1]), proj(wk, nkv, *qk_gains[1:]),
                   proj(wv, nkv))
        score_scale = cfg.get("score_scale")
        if score_scale is None:
            score_scale = 1.0 / math.sqrt(hd)
        if cfg["rope"] is None:
            q = q * score_scale
        else:
            with jax.named_scope("rope"):
                cos, sin = rope_tables(t, hd, tuple(cfg["rope"]))
                q = apply_rope(q, cos, sin) * score_scale
                k = apply_rope(k, cos, sin)
        with jax.named_scope("scores"):
            o = attention(q.astype(cdt), k.astype(cdt), v.astype(cdt),
                          cfg["window"])
        return jnp.dot(o.reshape(b, t, nh * hd).astype(cdt), wo.astype(cdt),
                       preferred_element_type=jnp.float32)
    return residual_block(x, g1, cfg, mixer), {}


def lm_head_fwd(leaves, x, cfg: dict, cdt=jnp.float32):
    """``lm_head``: ``RMSNorm(x; gf) @ W``; leaves ``gf (d,)``, ``W (d,
    V_held)``; ``(B, T, V_held)`` float32 logits out.  A tied head
    (``cfg["tied_to"]``: the layer whose table it shares) is handed that
    layer's ``table (V_held, d)`` as its second leaf and multiplies by its
    transpose; ``cfg["scale"]``, where the model has a logits scaling, is
    on the logits."""
    gf, w = leaves
    xn = rms_norm(x, gf, cfg["eps"]).astype(cdt)
    if cfg.get("tied_to") is None:
        out = jnp.dot(xn, w.astype(cdt), preferred_element_type=jnp.float32)
    else:
        out = jax.lax.dot_general(
            xn, w.astype(cdt), (((xn.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    if cfg.get("scale") is not None:
        out = out * cfg["scale"]
    return out, {}


def block_vjp(call, leaves, x, err, fresh: bool = False):
    """``(leaf gradients, input gradient)`` of a layer kind's ``call(leaves,
    x) -> (y, counters)`` at ``err``, the gradient with respect to ``y``:
    the block is run again from its input and differentiated by JAX (a
    kernel brings its ``custom_vjp``).  Integer ids have no gradient:
    None.  ``fresh``: the leaves pass an optimization barrier first, so
    that nothing the forward pass made of them (their casts to the operand
    dtype) is shared with this recomputation and kept alive for it."""
    if fresh:
        leaves = jax.lax.optimization_barrier(leaves)
    if jnp.issubdtype(x.dtype, jnp.integer):
        _, vjp, _ = jax.vjp(lambda ls: call(ls, x), leaves, has_aux=True)
        return vjp(err)[0], None
    _, vjp, _ = jax.vjp(call, leaves, x, has_aux=True)
    return vjp(err)


def attn_route(cfg: dict) -> str:
    return "full" if cfg["window"] is None else "window"
