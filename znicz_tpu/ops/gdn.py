"""The gated-delta-rule mixer of a linear-attention hybrid decoder (Yang,
Kautz, Hatamizadeh, "Gated Delta Networks", ICLR 2025).

``gdn_block`` is ``x + scale * RMSNorm(Mixer(x); g1)`` (``norm`` "post";
"pre": the norm on the mixer's input, as the repo's other blocks).  Per
head (key size ``K``, value size ``V``; ``n`` is the mixer's input)

    q, k, v = n Wq, n Wk, n Wv             (H heads of K, K, V)
    each <- silu(causal depthwise conv, ``conv`` taps, no bias)
    q <- q / |q|_2 * K^(-1/2),  k <- k / |k|_2        (over a head's K)
    beta_t = 2 sigmoid(n Wb),  g_t = -exp(A_log) softplus(n Wa + dt_bias)
    S_t = a_t S_{t-1} + beta_t (v_t - a_t S_{t-1} k_t) k_t^T,   a_t = exp(g_t)
    o_t = S_t q_t                            (S in R^{V x K}, S_0 = 0)
    out = (RMSNorm(o_t; g_o over a head's V) * silu(n Wg)) Wo

The 2 of ``beta`` is the model's ``allow_neg_eigval``: the transition's
eigenvalue along ``k`` is ``1 - beta``, in (-1, 1).  The gated norm norms
first and gates after (the other order from ``mamba_block``).

The delta rule runs in its chunked form, ``chunk`` tokens at a time
(:func:`delta_rule`).  A token's rank-one correction acts on the state the
tokens before it left, so inside a chunk the corrections compose into a
unit-lower-triangular system, which is SOLVED (forward substitution) before
the products a state-space scan has.  With ``c`` the running sum of ``g``
inside a chunk and ``S`` the state the chunk starts from:

    A  = strict_lower(beta_i (k_i . k_j) exp(c_i - c_j))
    W  = (I + A)^-1 (beta k exp(c)),   U = (I + A)^-1 (beta v)
    u' = U - W S^T
    o  = (q exp(c)) S^T + tril((q k^T) * exp(c_i - c_j)) u'
    S <- exp(c_L) S + u'^T (k exp(c_L - c))

Decays, running sums, every ``exp`` (of a difference of running sums,
never a ratio), ``softplus``, ``sigmoid``, the L2 norms, the convs, the
Gram matrix of the system and its solution are float32; the operands of
the other products are in ``cdt``, accumulated in float32.  The backward
is ``jax.vjp`` of this function under the trainer's block
rematerialisation; inside it the projections with their convs, and each
group of ``GROUP`` chunks of the delta rule, are rematerialised by
themselves, so that the wide float32 intermediates of the one are never
alive beside the other's (the step of one such layer and its feed-forward
at 4,096 tokens plans 1.70 GB of temporaries where it planned 3.34:
compiles for a described v5e, PR 36).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import residual_block, rms_norm
from .ssm import causal_conv

HIGHEST = jax.lax.Precision.HIGHEST

#: leaves of the block, in the order of ``gdn_block_fwd``'s ``leaves``
LEAVES = ("g1", "wq", "wk", "wv", "wa", "wb", "wg", "conv_q", "conv_k",
          "conv_v", "a_log", "dt_bias", "g_o", "wo")

#: under the square root of the L2 norms (the published kernel's)
L2_EPS = 1e-6

#: chunks of the delta rule that are set up, solved and rematerialised
#: together
GROUP = 8


def leaf_shapes(d: int, heads: int, key_dim: int, value_dim: int,
                conv: int) -> dict:
    qk, vv = heads * key_dim, heads * value_dim
    return {"g1": (d,), "wq": (d, qk), "wk": (d, qk), "wv": (d, vv),
            "wa": (d, heads), "wb": (d, heads), "wg": (d, vv),
            "conv_q": (conv, qk), "conv_k": (conv, qk),
            "conv_v": (conv, vv), "a_log": (heads,), "dt_bias": (heads,),
            "g_o": (value_dim,), "wo": (vv, d)}


def l2_norm(x):
    """``x / |x|_2`` over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


def carried(state, whole):
    """What a chunk hands on of the state it started from: ``state (B, H,
    V, K)`` times the chunk's whole decay ``whole (B, H)``."""
    return whole[..., None, None] * state


def unit_lower_inverse(m):
    """The inverse of unit-lower-triangular matrices ``m (..., n, n)``
    (``n`` a power of two; what stands on and above the diagonal is not
    read), float32 at ``highest``, by halves: the inverse of ``[[P, 0],
    [C, Q]]`` is ``[[P', 0], [-Q' C P', Q']]``, from the diagonal's ones up
    to the whole in ``log2(n)`` levels of two batched products each.  The
    algebra is forward substitution's, done a block at a time: no power
    series, so nothing that a large ``beta`` makes cancel."""
    n = m.shape[-1]
    if n & (n - 1):
        raise ValueError(f"a chunk of {n} tokens is no power of two")
    lead = m.shape[:-2]
    inv = jnp.ones((*lead, n, 1, 1), m.dtype)       # blocks of one token
    size = 1
    while size < n:
        blocks = n // (2 * size)
        # the diagonal blocks of twice the size, their lower left quarters
        pairs = jnp.einsum("...iaib->...iab", m.reshape(
            *lead, blocks, 2 * size, blocks, 2 * size))
        p, q = (inv.reshape(*lead, blocks, 2, size, size)[..., half, :, :]
                for half in (0, 1))
        c = -jnp.einsum("...ij,...jk,...kl->...il", q,
                        pairs[..., size:, :size], p, precision=HIGHEST)
        inv = jnp.concatenate([
            jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
            jnp.concatenate([c, q], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def chunk_group(state, q, k, v, g, beta, cdt):
    """``GROUP`` chunks of the delta rule from the state they start from:
    ``q``, ``k (G, B, H, L, K)``, ``v (G, B, H, L, V)``, ``g``, ``beta (G,
    B, H, L, 1)``, ``state (B, H, V, K)`` -> (the closing state, ``o (G, B,
    H, L, V)``).  The chunks' systems are set up and solved together, then
    a loop over the chunks carries the state."""
    f32 = jnp.float32
    chunk = q.shape[-2]
    cum = jnp.cumsum(g[..., 0], axis=-1)                 # (G, B, H, L)
    # exp(c_i - c_j) where j <= i, 0 elsewhere
    keep = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = cum[..., :, None] - cum[..., None, :]
    decay = jnp.where(keep, jnp.exp(jnp.where(keep, seg, 0.0)), 0.0)
    # the chunk's own system: (I + A) [W | U] = [beta k exp(c) | beta v]
    kb = k * beta
    a = jnp.where(jnp.tril(keep, -1), decay * jnp.einsum(
        "cbhik,cbhjk->cbhij", kb, k, precision=HIGHEST), 0.0)
    wu = jnp.einsum(
        "cbhij,cbhjk->cbhik", unit_lower_inverse(a),
        jnp.concatenate([kb * jnp.exp(cum)[..., None], v * beta], axis=-1),
        precision=HIGHEST)
    w, u = wu[..., :k.shape[-1]], wu[..., k.shape[-1]:]
    qk = decay * jnp.einsum("cbhik,cbhjk->cbhij", q.astype(cdt),
                            k.astype(cdt), preferred_element_type=f32)
    q_in = q * jnp.exp(cum)[..., None]
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]
    whole = jnp.exp(cum[..., -1])                        # (G, B, H)

    def step(state, chunk_of):
        w, u, qk, q_in, k_out, whole = chunk_of
        s = state.astype(cdt)
        new = u - jnp.einsum("bhik,bhvk->bhiv", w.astype(cdt), s,
                             preferred_element_type=f32)
        o = jnp.einsum("bhik,bhvk->bhiv", q_in.astype(cdt), s,
                       preferred_element_type=f32) \
            + jnp.einsum("bhij,bhjv->bhiv", qk.astype(cdt), new.astype(cdt),
                         preferred_element_type=f32)
        state = carried(state, whole) + jnp.einsum(
            "bhiv,bhik->bhvk", new.astype(cdt), k_out.astype(cdt),
            preferred_element_type=f32)
        return state, o
    return jax.lax.scan(step, state, (w, u, qk, q_in, k_out, whole))


def delta_rule(q, k, v, g, beta, chunk: int, cdt=jnp.float32):
    """``o_t = S_t q_t`` of ``S_t = exp(g_t) S_{t-1} + beta_t (v_t -
    exp(g_t) S_{t-1} k_t) k_t^T`` in chunks of ``chunk`` tokens.  ``q``,
    ``k (B, T, H, K)``, ``v (B, T, H, V)``, ``g`` (not positive) and
    ``beta (B, T, H)``; float32 in and out.  The chunks go ``GROUP`` at a
    time (:func:`chunk_group`), each group rematerialised by itself in the
    backward: what is kept a group is the state it starts from, and the
    float32 ``(chunks, B, H, L, L)`` and ``(chunks, B, H, L, K + V)``
    arrays of the chunked form are never whole."""
    bsz, t, h, _ = q.shape
    if t % chunk:
        raise ValueError(f"sequence length {t} is no multiple of the "
                         f"delta rule's chunk {chunk}")
    nc = t // chunk
    group = GROUP if nc % GROUP == 0 else nc

    def groups(a):          # (B, T, H, .) -> (nc / G, G, B, H, L, .)
        return a.reshape(bsz, nc // group, group, chunk, h, -1).transpose(
            1, 2, 0, 4, 3, 5)
    _, o = jax.lax.scan(
        jax.checkpoint(lambda state, xs: chunk_group(state, *xs, cdt)),
        jnp.zeros((bsz, h, v.shape[-1], k.shape[-1]), jnp.float32),
        tuple(groups(a) for a in (q, k, v, g, beta)))
    return o.transpose(2, 0, 1, 4, 3, 5).reshape(bsz, t, h, v.shape[-1])


def mixer(leaves, xn, cfg: dict, cdt=jnp.float32):
    """The mixer of its input ``xn (B, T, d)``: ``(B, T, d)`` float32;
    ``leaves``: :data:`LEAVES` without ``g1``.  The projections with
    their convs are rematerialised by themselves in the backward
    (``jax.checkpoint``), as the delta rule's groups of chunks are: the
    float32 intermediates of the one, a dozen ``(B, T, H V)`` arrays, are
    then never alive together with the other's."""
    (wq, wk, wv, wa, wb, wg, conv_q, conv_k, conv_v, a_log, dt_bias, g_o,
     wo) = leaves
    bsz, t, _ = xn.shape
    h, dk, dv = cfg["heads"], cfg["key_dim"], cfg["value_dim"]
    xc = xn.astype(cdt)

    def dot(a, w):
        return jnp.dot(a, w.astype(cdt), preferred_element_type=jnp.float32)

    @jax.checkpoint
    def inputs(xc, wq, wk, wv, wa, wb, conv_q, conv_k, conv_v, a_log,
               dt_bias):
        with jax.named_scope("short_conv"):
            q, k, v = (jax.nn.silu(causal_conv(dot(xc, w), taps, 0.0))
                       for w, taps in ((wq, conv_q), (wk, conv_k),
                                       (wv, conv_v)))
        q = l2_norm(q.reshape(bsz, t, h, dk)) * dk ** -0.5
        k = l2_norm(k.reshape(bsz, t, h, dk))
        beta = 2.0 * jax.nn.sigmoid(dot(xc, wb))
        g = -jnp.exp(a_log) * jax.nn.softplus(dot(xc, wa) + dt_bias)
        return q, k, v.reshape(bsz, t, h, dv), g, beta
    rule_inputs = inputs(xc, wq, wk, wv, wa, wb, conv_q, conv_k, conv_v,
                         a_log, dt_bias)
    with jax.named_scope("delta_rule"):
        o = delta_rule(*rule_inputs, cfg["chunk"], cdt)
    gated = rms_norm(o, g_o, cfg["eps"]) * jax.nn.silu(
        dot(xc, wg)).reshape(bsz, t, h, dv)
    return dot(gated.reshape(bsz, t, h * dv).astype(cdt), wo)


def gdn_block_fwd(leaves, x, cfg: dict, cdt=jnp.float32):
    """``gdn_block``: leaves as :data:`LEAVES` (shapes:
    :func:`leaf_shapes`); ``cfg``: ``heads``, ``key_dim``, ``value_dim``,
    ``conv``, ``chunk``, ``eps``, ``norm`` ("pre" | "post": where ``g1``'s
    norm sits), ``scale`` (the block's scale on what it adds to the
    stream; None: 1).  -> ``(y, counters)``; ``gdn_tokens`` counts the
    tokens this layer scanned."""
    g1, *rest = leaves
    bsz, t, _ = x.shape
    h, dk, dv = cfg["heads"], cfg["key_dim"], cfg["value_dim"]
    if rest[0].shape[1] != h * dk or rest[2].shape[1] != h * dv:
        raise ValueError(f"gdn_block of {h} heads, keys of {dk} and values "
                         f"of {dv}: Wq {rest[0].shape}, Wv {rest[2].shape}")
    with jax.named_scope("gdn_block"):
        y = residual_block(x, g1, cfg, lambda xn: mixer(rest, xn, cfg, cdt))
    return y, {"gdn_tokens": jnp.asarray(bsz * t, jnp.int32)}
