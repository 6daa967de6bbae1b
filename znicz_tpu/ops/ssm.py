"""The Mamba-2 mixer of a hybrid decoder, for the heads one chip holds.

``mamba_block`` is ``x + scale * Mamba(RMSNorm(x; g1))``.  Per head (``P``
channels, state ``N``, the B and C of its group; ``G`` groups held)

    [z | xBC | dt] = n W_in                 (widths d_in, d_in + 2GN, H)
    xBC <- silu(causal depthwise conv of xBC, ``conv`` taps, with bias)
    x | B | C = split(xBC)                  (d_in, GN, GN)
    D_t = softplus(dt_t + dt_bias),  a = -exp(A_log)
    S_t = exp(D_t a) S_{t-1} + D_t x_t (x) B_t       (S in R^{P x N}, S_0 = 0)
    y_t = S_t C_t + D x_t
    out = RMSNorm(y * silu(z); g_m) W_out   (gate first, then the norm,
                                             a group's channels at a time)

with ``d_in = H * P`` for the ``H`` heads HELD here: the layer is told
``heads`` (the model's) and ``heads_held``, and its leaves are the held
heads' columns of ``W_in``, rows of ``W_out`` and channels of everything
between.  What the absent heads would add through ``W_out`` is left out,
as an expert block leaves out its absent experts.

B and C come in ``groups`` (the model's; 1: shared by every head), head
``j`` of the model reading group ``j // (heads / groups)``.  A chip holds
whole groups or a part of one (``groups_held`` of them: their B and C
columns of ``W_in`` and their conv taps), and the gated norm's mean square
runs over each held group's channels held (a deployment whose group spans
chips sums one number a token over them; no code stands in for them here).
``in_scales``, where the model has them, multiply the five pieces ``z | x |
B | C | dt`` of ``n W_in`` (None: 1).

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
import numpy as np

from .attention import rms_norm

#: leaves of the block, in the order of ``mamba_block_fwd``'s ``leaves``
LEAVES = ("g1", "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
          "g_m", "w_out")


def leaf_shapes(d: int, heads_held: int, head_dim: int, state: int,
                conv: int, groups_held: int = 1) -> dict:
    """The leaves' shapes for ``heads_held`` heads of ``head_dim`` and the
    B and C of ``groups_held`` groups."""
    d_in, bc = heads_held * head_dim, 2 * groups_held * state
    return {"g1": (d,), "w_in": (d, 2 * d_in + bc + heads_held),
            "conv_w": (conv, d_in + bc),
            "conv_b": (d_in + bc,), "dt_bias": (heads_held,),
            "a_log": (heads_held,), "d_skip": (heads_held,),
            "g_m": (d_in,), "w_out": (d_in, d)}


def groups_held(heads: int, heads_held: int, groups: int) -> int:
    """How many groups of B and C the first ``heads_held`` of ``heads``
    heads read, ``groups`` groups in the model: one where the heads held
    are a part of a group, else as many as they fill.  A layout whose
    heads do not divide into its groups, or whose heads held straddle a
    group, is refused."""
    if groups < 1 or heads % groups:
        raise ValueError(f"{heads} heads do not divide into {groups} groups "
                         "of B and C")
    per = heads // groups
    if not 0 < heads_held <= heads or (per % heads_held
                                       and heads_held % per):
        raise ValueError(f"{heads_held} heads held of {heads} straddle the "
                         f"groups of {per} heads")
    return max(1, heads_held // per)


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


def grouped_scan(x, dt, a, b_in, c_in, chunk: int, groups: int,
                 cdt=jnp.float32):
    """:func:`ssd_scan` of heads in ``groups`` equal groups, each reading
    its own B and C: ``b_in``/``c_in (B, T, groups * N)``, group by group;
    one group is :func:`ssd_scan` itself."""
    if groups == 1:
        return ssd_scan(x, dt, a, b_in, c_in, chunk, cdt)
    hg, n = x.shape[2] // groups, b_in.shape[-1] // groups
    return jnp.concatenate([ssd_scan(
        x[:, :, j * hg:(j + 1) * hg], dt[..., j * hg:(j + 1) * hg],
        a[j * hg:(j + 1) * hg], b_in[..., j * n:(j + 1) * n],
        c_in[..., j * n:(j + 1) * n], chunk, cdt) for j in range(groups)],
        axis=2)


def _piece_scales(scales, d_in: int, bc: int, h: int) -> np.ndarray:
    """One multiplier a column of ``n W_in``: the five ``in_scales`` over
    the pieces ``z | x | B | C | dt`` (widths ``d_in, d_in, bc, bc, h``)."""
    widths = (d_in, d_in, bc, bc, h)
    return np.concatenate([np.full(w, s, np.float32)
                           for w, s in zip(widths, scales)])


def gated_scan(leaves, xn, cfg: dict, cdt=jnp.float32):
    """The mixer up to its norm: ``(y + D x) * silu(z)`` of the normalised
    input ``xn (B, T, d)`` for the heads held, ``(B, T, d_in)`` float32;
    ``leaves``: ``w_in``, ``conv_w``, ``conv_b``, ``dt_bias``, ``a_log``,
    ``d_skip``.  Head by head it is the same whatever else is held."""
    w_in, conv_w, conv_b, dt_bias, a_log, d_skip = leaves
    bsz, t, _ = xn.shape
    h, p, n = cfg["heads_held"], cfg["head_dim"], cfg["state"]
    g = cfg.get("groups_held", 1)
    d_in = h * p
    zxbcdt = jnp.dot(xn.astype(cdt), w_in.astype(cdt),
                     preferred_element_type=jnp.float32)
    if cfg.get("in_scales") is not None:
        zxbcdt = zxbcdt * _piece_scales(cfg["in_scales"], d_in, g * n, h)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, conv_w, conv_b))
    xs, b_in, c_in = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
    xs = xs.reshape(bsz, t, h, p)
    dt = jax.nn.softplus(dt + dt_bias)
    a = -jnp.exp(a_log)
    with jax.named_scope("ssd_scan"):
        y = grouped_scan(xs, dt, a, b_in, c_in, cfg["chunk"], g, cdt)
    return (y + d_skip[:, None] * xs).reshape(bsz, t, d_in) * jax.nn.silu(z)


def gated_norm(gated, g_m, cfg: dict):
    """RMSNorm of the gated output over each held group's channels."""
    g = cfg.get("groups_held", 1)
    if g == 1:
        return rms_norm(gated, g_m, cfg["eps"])
    *lead, d_in = gated.shape
    return rms_norm(gated.reshape(*lead, g, d_in // g),
                    g_m.reshape(g, d_in // g), cfg["eps"]).reshape(gated.shape)


def mamba_mixer(leaves, xn, cfg: dict, cdt=jnp.float32):
    """``Mamba2(xn)`` of the normalised input ``xn (B, T, d)`` for the
    heads held, float32: the gated scan, its norm and ``W_out``;
    ``leaves``: :data:`LEAVES` but ``g1``."""
    *scan_leaves, g_m, w_out = leaves
    h, p, n = cfg["heads_held"], cfg["head_dim"], cfg["state"]
    width = 2 * h * p + 2 * cfg.get("groups_held", 1) * n + h
    if scan_leaves[0].shape[1] != width or not 0 < h <= cfg["heads"]:
        raise ValueError(f"a Mamba mixer holding {h} of {cfg['heads']} "
                         f"heads of {p}, state {n}: W_in "
                         f"{scan_leaves[0].shape}")
    gated = gated_scan(scan_leaves, xn, cfg, cdt)
    return jnp.dot(gated_norm(gated, g_m, cfg).astype(cdt),
                   w_out.astype(cdt), preferred_element_type=jnp.float32)


def mamba_block_fwd(leaves, x, cfg: dict, cdt=jnp.float32):
    """``mamba_block``: leaves as :data:`LEAVES` (shapes:
    :func:`leaf_shapes`); ``cfg``: ``heads``, ``heads_held``, ``head_dim``,
    ``state``, ``conv``, ``chunk``, ``eps``, ``scale`` (the block's scale
    on what it adds to the stream; None: 1) and, where the model has them,
    ``groups_held`` and ``in_scales`` (see the module's text).  -> ``(y, counters)``;
    ``ssm_tokens`` counts the tokens this layer scanned."""
    g1, *mixer_leaves = leaves
    bsz, t, _ = x.shape
    with jax.named_scope("mamba_block"):
        out = mamba_mixer(mixer_leaves, rms_norm(x, g1, cfg["eps"]), cfg,
                          cdt)
    if cfg.get("scale") is not None:
        out = out * cfg["scale"]
    return x + out, {"ssm_tokens": jnp.asarray(bsz * t, jnp.int32)}
