"""The Mamba-2 mixer of a hybrid decoder, for the heads one chip holds.

``mamba_block`` is ``x + scale * Mamba(RMSNorm(x; g1))``.  Per head (``P``
channels, state ``N``, one group: B and C are shared by every head)

    [z | xBC | dt] = n W_in                 (widths d_in, d_in + 2N, H)
    xBC <- silu(causal depthwise conv of xBC, ``conv`` taps, with bias)
    x | B | C = split(xBC)                  (d_in, N, N)
    D_t = softplus(dt_t + dt_bias),  a = -exp(A_log)
    S_t = exp(D_t a) S_{t-1} + D_t x_t (x) B_t       (S in R^{P x N}, S_0 = 0)
    y_t = S_t C_t + D x_t
    out = RMSNorm(y * silu(z); g_m) W_out   (gate first, then the norm)

with ``d_in = H * P`` for the ``H`` heads HELD here: the layer is told
``heads`` (the model's) and ``heads_held``, and its leaves are the held
heads' columns of ``W_in``, rows of ``W_out`` and channels of everything
between; B, C and their conv taps are whole.  What the absent heads would
add through ``W_out`` is left out, as an expert block leaves out its absent
experts; the gated norm's mean square runs over the channels held (a
deployment sums one number a token over its chips; no code stands in for
them here).

The scan runs in its chunked (state-space-duality) form, ``chunk`` tokens
at a time: inside a chunk the masked decay matrix times ``C B^T`` times
``D x``; each chunk's closing state; a short recurrence over the chunks'
states; and the carried state's term.  Decays, cumulative sums, ``exp`` and
``softplus`` are float32 and every decay is the ``exp`` of a difference of
cumulative sums (never a ratio of two ``exp``s); the operands of the four
products are in ``cdt``, accumulated in float32.  The backward is
``jax.vjp`` of this function under the trainer's block rematerialisation:
nothing is kept a token but the block's input.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import rms_norm

#: leaves of the block, in the order of ``mamba_block_fwd``'s ``leaves``
LEAVES = ("g1", "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
          "g_m", "w_out")


def leaf_shapes(d: int, heads_held: int, head_dim: int, state: int,
                conv: int) -> dict:
    """The leaves' shapes for ``heads_held`` heads of ``head_dim``."""
    d_in = heads_held * head_dim
    return {"g1": (d,), "w_in": (d, 2 * d_in + 2 * state + heads_held),
            "conv_w": (conv, d_in + 2 * state),
            "conv_b": (d_in + 2 * state,), "dt_bias": (heads_held,),
            "a_log": (heads_held,), "d_skip": (heads_held,),
            "g_m": (d_in,), "w_out": (d_in, d)}


def causal_conv(x, w, b):
    """Depthwise causal convolution over time: ``x (B, T, C)``, taps ``w
    (K, C)`` (the last tap is the current token's), bias ``b (C,)``;
    float32."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(w[j] * xp[:, j:j + t] for j in range(k))


def chunk_decay(cum):
    """The decay of a whole chunk, by which the state carried into it
    comes out of it: ``cum (B, nc, H, L)``, the decay exponents summed
    inside each chunk."""
    return jnp.exp(cum[..., -1])


def ssd_scan(x, dt, a, b_in, c_in, chunk: int, cdt=jnp.float32):
    """``y_t = S_t C_t`` of ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``
    in chunks of ``chunk`` tokens.  ``x (B, T, H, P)``, ``dt (B, T, H)``
    (after the softplus), ``a (H,)`` negative, ``b_in``/``c_in (B, T, N)``;
    float32 in and out."""
    bsz, t, h, p = x.shape
    n = b_in.shape[-1]
    if t % chunk:
        raise ValueError(f"sequence length {t} is no multiple of the "
                         f"scan's chunk {chunk}")
    nc = t // chunk
    f32 = jnp.float32
    # the decay exponents, summed inside each chunk: (B, nc, H, L)
    cum = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, h), axis=2
                     ).transpose(0, 1, 3, 2)
    xd = (x * dt[..., None]).reshape(bsz, nc, chunk, h, p)
    bc = b_in.reshape(bsz, nc, chunk, n).astype(cdt)
    cc = c_in.reshape(bsz, nc, chunk, n).astype(cdt)
    # inside a chunk: (masked decay * C B^T) (dt x)
    keep = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = cum[..., :, None] - cum[..., None, :]        # sum over s+1 .. t
    decay = jnp.where(keep, jnp.exp(jnp.where(keep, seg, 0.0)), 0.0)
    cb = jnp.einsum("bcln,bcsn->bcls", cc, bc, preferred_element_type=f32)
    y = jnp.einsum("bchls,bcshp->bclhp",
                   (decay * cb[:, :, None]).astype(cdt), xd.astype(cdt),
                   preferred_element_type=f32)
    # each chunk's closing state, had it started from nothing
    to_end = jnp.exp(cum[..., -1:] - cum)              # (B, nc, H, L)
    closing = jnp.einsum(
        "bcshp,bcsn->bchpn",
        (xd * to_end.transpose(0, 1, 3, 2)[..., None]).astype(cdt), bc,
        preferred_element_type=f32)
    # the state each chunk starts from: a recurrence over the chunks
    whole = chunk_decay(cum)                           # (B, nc, H)

    def carry(state, chunk_of):
        g, s = chunk_of
        return g[..., None, None] * state + s, state
    _, start = jax.lax.scan(
        carry, jnp.zeros((bsz, h, p, n), f32),
        (whole.swapaxes(0, 1), closing.swapaxes(0, 1)))
    start = start.swapaxes(0, 1)                       # (B, nc, H, P, N)
    # the carried state's term
    y = y + jnp.einsum("bcln,bchpn->bclhp", cc, start.astype(cdt),
                       preferred_element_type=f32) \
        * jnp.exp(cum).transpose(0, 1, 3, 2)[..., None]
    return y.reshape(bsz, t, h, p)


def gated_scan(leaves, xn, cfg: dict, cdt=jnp.float32):
    """The mixer up to its norm: ``(y + D x) * silu(z)`` of the normalised
    input ``xn (B, T, d)`` for the heads held, ``(B, T, d_in)`` float32;
    ``leaves``: ``w_in``, ``conv_w``, ``conv_b``, ``dt_bias``, ``a_log``,
    ``d_skip``.  Head by head it is the same whatever else is held."""
    w_in, conv_w, conv_b, dt_bias, a_log, d_skip = leaves
    bsz, t, _ = xn.shape
    h, p, n = cfg["heads_held"], cfg["head_dim"], cfg["state"]
    d_in = h * p
    zxbcdt = jnp.dot(xn.astype(cdt), w_in.astype(cdt),
                     preferred_element_type=jnp.float32)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, conv_w, conv_b))
    xs, b_in, c_in = jnp.split(xbc, [d_in, d_in + n], axis=-1)
    xs = xs.reshape(bsz, t, h, p)
    dt = jax.nn.softplus(dt + dt_bias)
    with jax.named_scope("ssd_scan"):
        y = ssd_scan(xs, dt, -jnp.exp(a_log), b_in, c_in, cfg["chunk"], cdt)
    return (y + d_skip[:, None] * xs).reshape(bsz, t, d_in) * jax.nn.silu(z)


def mamba_block_fwd(leaves, x, cfg: dict, cdt=jnp.float32):
    """``mamba_block``: leaves as :data:`LEAVES` (shapes:
    :func:`leaf_shapes`); ``cfg``: ``heads``, ``heads_held``, ``head_dim``,
    ``state``, ``conv``, ``chunk``, ``eps``, ``scale`` (the block's scale
    on what it adds to the stream; None: 1).  -> ``(y, counters)``;
    ``ssm_tokens`` counts the tokens this layer scanned."""
    g1, w_in, *scan_leaves, g_m, w_out = leaves
    bsz, t, _ = x.shape
    h, p, n = cfg["heads_held"], cfg["head_dim"], cfg["state"]
    if w_in.shape[1] != 2 * h * p + 2 * n + h or not 0 < h <= cfg["heads"]:
        raise ValueError(f"mamba_block holding {h} of {cfg['heads']} heads "
                         f"of {p}, state {n}: W_in {w_in.shape}")
    with jax.named_scope("mamba_block"):
        gated = gated_scan((w_in, *scan_leaves),
                           rms_norm(x, g1, cfg["eps"]), cfg, cdt)
        out = jnp.dot(rms_norm(gated, g_m, cfg["eps"]).astype(cdt),
                      w_out.astype(cdt), preferred_element_type=jnp.float32)
    if cfg.get("scale") is not None:
        out = out * cfg["scale"]
    return x + out, {"ssm_tokens": jnp.asarray(bsz * t, jnp.int32)}
