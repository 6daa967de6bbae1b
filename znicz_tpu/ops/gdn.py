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

The delta rule runs ``chunk`` tokens at a time (:func:`delta_rule`).  A
token's rank-one correction acts on the state the tokens before it left,
so inside a chunk the corrections compose into a unit-lower-triangular
system, which is SOLVED (forward substitution) before the products a
state-space scan has.  With ``c`` the running sum of ``g``
inside a chunk and ``S`` the state the chunk starts from:

    A  = strict_lower(beta_i (k_i . k_j) exp(c_i - c_j))
    W  = (I + A)^-1 (beta k exp(c)),   U = (I + A)^-1 (beta v)
    u' = U - W S^T
    o  = (q exp(c)) S^T + tril((q k^T) * exp(c_i - c_j)) u'
    S <- exp(c_L) S + u'^T (k exp(c_L - c))

Decays, running sums, every ``exp`` (of a difference of running sums,
never a ratio), ``softplus``, ``sigmoid``, the L2 norms, the convs, the
Gram matrix of the system, its inverse and the inverse's products are
float32 (``highest``); the operands of the other products are in ``cdt``,
accumulated in float32.  The backward is ``jax.vjp`` of this function
under the trainer's block rematerialisation; inside it the projections
with their convs are rematerialised by themselves, so that their wide
float32 intermediates are never alive beside the delta rule's.

The rule takes one of two forms, by ``tuning.use_pallas()`` (counted in
the start record's ``rule_routes``):

* TPU (and the Pallas interpreter): two Pallas kernels under a
  ``custom_vjp`` (:func:`delta_rule_kernel`), a grid step a (row, head,
  chunk).  The forward carries the state over the chunks in VMEM and sets
  up, inverts and applies each chunk's system there too; HBM holds ``q``,
  ``k``, ``v`` (made head-major), the running sums, beta and ``o`` --
  and, under the ``custom_vjp``'s forward alone, the state each chunk
  starts from (float32, ``(B, H, chunks, V, K)``).  The backward walks the
  chunks from the last, carries the state's gradient in VMEM, sets each
  chunk's system up again from its inputs and saved state and writes
  ``dq``, ``dk``, ``dv``, ``dbeta`` and the gradient of the running sums,
  whose reverse sums inside a chunk JAX makes ``dg``.  The backward's
  products are the forward's transposes at the forward's precision.
* elsewhere: the ``jax.numpy`` chunked form (:func:`delta_rule_chunked`),
  ``GROUP`` chunks set up and solved together, a ``lax.scan`` over them,
  each group rematerialised by itself: HBM holds the group's float32
  ``(GROUP, B, H, L, L)`` and ``(GROUP, B, H, L, K + V)`` arrays and the
  state a group starts from (the step of one such layer and its
  feed-forward at 4,096 tokens plans 1.70 GB of temporaries where the
  whole chunked form planned 3.34: compiles for a described v5e, PR 36).

``kda_block`` is the sibling kind of Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692, section 3): the same rule with a decay a KEY CHANNEL,
``S_t = S_{t-1} Diag(a_t) + beta_t (v_t - S_{t-1} Diag(a_t) k_t) k_t^T``
with ``a_t = exp(g_t)`` in ``R^K``, low-rank gates and a sigmoid output
gate (:func:`kda_mixer`).  Its chunked form (:func:`kda_group`) is the one
above term for term, with ``c`` the running sum of ``g`` a channel:

    A  = strict_lower(beta_i sum_k k_ik k_jk exp(c_ik - c_jk))
    W  = (I + A)^-1 (beta k exp(c)),   U = (I + A)^-1 (beta v)
    o  = (q exp(c)) S^T + tril(sum_k q_ik k_jk exp(c_ik - c_jk)) (U - W S^T)
    S <- S Diag(exp(c_L)) + (U - W S^T)^T (k exp(c_L - c))

The decay between two tokens no longer factors out of the sum over ``k``,
and ``exp(-c)`` overflows once a chunk's decays add up below -88, so no
``exp`` of a positive sum is taken: across the ``SUB``-token sub-chunks of a
chunk the factor is split at the start ``b`` of the later one, ``exp(c_i -
c_b) exp(c_b - c_j)``, both at most 1, and the sums are products of
matrices; inside a sub-chunk the ``(SUB, SUB, K)`` decays are formed whole.
It takes one of two forms by the same rule as the scalar rule
(``rule_routes`` ``kda:kernel`` | ``kda:chunked``):

* TPU (and the Pallas interpreter): two Pallas kernels under a
  ``custom_vjp`` (:func:`kda_rule_kernel`), a grid step a (row, head,
  chunk), the state (backward: its gradient) in VMEM across the chunks.
  HBM holds ``q``, ``k``, ``beta v`` (head-major), the running sums ``c``
  a channel ``(B, H, T, K)``, beta and ``o`` -- and, under the
  ``custom_vjp``'s forward alone, the state each chunk starts from and
  the inverse of its system (float32, ``(B, H, chunks, V, K)`` and ``(B,
  H, chunks, L, L)``).  A chunk's sub-chunk algebra is
  :func:`decayed_products`' (the cross sums products of matrices, the
  decays inside a sub-chunk formed whole, a column at a time); the
  backward sets the chunk up again from its inputs, the saved state and
  the saved inverse and writes ``dq``, ``dk``, ``d(beta v)``, ``dbeta`` and
  ``dc`` a channel.  Its products are the forward's transposes at the
  forward's precision.
* elsewhere: the ``jax.numpy`` chunked form (:func:`kda_rule_chunked`),
  :func:`kda_group` on ``GROUP`` chunks at a time, each group
  rematerialised by itself: the reference the tests hold the kernels to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning
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

#: leaves of a ``kda_block``, in the order of ``kda_block_fwd``'s ``leaves``:
#: the two low-rank gates are ``n Wfa Wfb`` (the decay's) and ``n Wga Wgb +
#: bg`` (the output's)
KDA_LEAVES = ("g1", "wq", "wk", "wv", "wb", "wfa", "wfb", "wga", "wgb", "bg",
              "conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "g_o", "wo")

#: tokens a sub-chunk of the per-channel rule (:func:`kda_group`)
SUB = 16


def leaf_shapes(d: int, heads: int, key_dim: int, value_dim: int,
                conv: int) -> dict:
    qk, vv = heads * key_dim, heads * value_dim
    return {"g1": (d,), "wq": (d, qk), "wk": (d, qk), "wv": (d, vv),
            "wa": (d, heads), "wb": (d, heads), "wg": (d, vv),
            "conv_q": (conv, qk), "conv_k": (conv, qk),
            "conv_v": (conv, vv), "a_log": (heads,), "dt_bias": (heads,),
            "g_o": (value_dim,), "wo": (vv, d)}


def kda_leaf_shapes(d: int, heads: int, key_dim: int, value_dim: int,
                    conv: int, rank: int) -> dict:
    qk, vv = heads * key_dim, heads * value_dim
    return {"g1": (d,), "wq": (d, qk), "wk": (d, qk), "wv": (d, vv),
            "wb": (d, heads), "wfa": (d, rank), "wfb": (rank, qk),
            "wga": (d, rank), "wgb": (rank, vv), "bg": (vv,),
            "conv_q": (conv, qk), "conv_k": (conv, qk),
            "conv_v": (conv, vv), "a_log": (heads,), "dt_bias": (qk,),
            "g_o": (value_dim,), "wo": (vv, d)}


def l2_norm(x):
    """``x / |x|_2`` over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


def carried(state, whole):
    """What a chunk hands on of the state it started from: ``state (...,
    V, K)`` times the chunk's whole decay ``whole``, shaped to broadcast
    against it (the kernels hand the transpose of the same product, the
    gradient carried back over a chunk, through here too)."""
    return whole * state


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
    return _chunks_in_turn(state, (w, u, qk, q_in, k_out, whole),
                           lambda whole: whole[..., None, None], cdt)


def _chunks_in_turn(state, parts, widen, cdt):
    """The loop over a group's chunks that carries the state ``(B, H, V,
    K)``: ``parts`` are the chunks' ``(w, u, qk, q_in, k_out, whole)``,
    ``widen`` shapes a chunk's ``whole`` decay to broadcast against the
    state.  -> (the closing state, ``o (G, B, H, L, V)``)."""
    f32 = jnp.float32

    def step(state, chunk_of):
        w, u, qk, q_in, k_out, whole = chunk_of
        s = state.astype(cdt)
        new = u - jnp.einsum("bhik,bhvk->bhiv", w.astype(cdt), s,
                             preferred_element_type=f32)
        o = jnp.einsum("bhik,bhvk->bhiv", q_in.astype(cdt), s,
                       preferred_element_type=f32) \
            + jnp.einsum("bhij,bhjv->bhiv", qk.astype(cdt), new.astype(cdt),
                         preferred_element_type=f32)
        state = carried(state, widen(whole)) + jnp.einsum(
            "bhiv,bhik->bhvk", new.astype(cdt), k_out.astype(cdt),
            preferred_element_type=f32)
        return state, o
    return jax.lax.scan(step, state, parts)


def decayed_products(left, right, cum, dot):
    """``P_ij = sum_k left_ik right_jk exp(c_ik - c_jk)`` where ``j <= i``,
    0 elsewhere: ``left``, ``right``, and the running sums ``cum`` of decays
    that are not positive, ``(..., L, K)`` -> ``(..., L, L)`` float32.
    Across the ``SUB``-token sub-chunks the decay goes through the later
    sub-chunk's start ``b``, ``exp(c_i - c_b) exp(c_b - c_j)`` (both at
    most 1), and the sum is ``dot(a (..., m, SUB, K), b (..., m, L, K)) ->
    (..., m, SUB, L)``, a product of matrices a sub-chunk; inside one the
    ``(SUB, SUB, K)`` decays are formed whole, in float32."""
    *lead, n, width = left.shape
    sub = min(SUB, n)
    m = n // sub
    # the running sum just before each sub-chunk's first token
    start = jnp.concatenate([jnp.zeros_like(cum[..., :1, :]),
                             cum[..., sub - 1:-1:sub, :]], axis=-2)
    left_s, cum_s = (a.reshape(*lead, m, sub, width) for a in (left, cum))
    # token j lies in a sub-chunk before sub-chunk s
    before = (jnp.arange(n) // sub)[None, :] < jnp.arange(m)[:, None]
    gap = start[..., :, None, :] - cum[..., None, :, :]      # (.., m, L, K)
    cross = dot(left_s * jnp.exp(cum_s - start[..., :, None, :]),
                right[..., None, :, :] * jnp.exp(
                    jnp.where(before[..., None], gap, 0.0)))
    cross = jnp.where(before[:, None, :], cross, 0.0)
    keep = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    seg = cum_s[..., :, None, :] - cum_s[..., None, :, :]    # (.., m, i, j, K)
    decay = jnp.where(keep, jnp.exp(jnp.where(keep, seg, 0.0)), 0.0)
    inner = jnp.sum(left_s[..., :, None, :]
                    * right.reshape(*lead, m, 1, sub, width) * decay, axis=-1)
    on_diagonal = inner[..., :, :, None, :] * jnp.eye(m)[:, None, :, None]
    return (cross + on_diagonal.reshape(*lead, m, sub, n)).reshape(
        *lead, n, n)


def kda_group(state, q, k, v, g, beta, cdt):
    """``GROUP`` chunks of the per-channel rule from the state they start
    from: ``q``, ``k``, ``g (G, B, H, L, K)``, ``v (G, B, H, L, V)``, ``beta
    (G, B, H, L, 1)``, ``state (B, H, V, K)`` -> (the closing state, ``o
    (G, B, H, L, V)``): :func:`chunk_group` with the decays between tokens
    of :func:`decayed_products`."""
    f32 = jnp.float32
    chunk, dk = q.shape[-2:]
    cum = jnp.cumsum(g, axis=-2)                         # (G, B, H, L, K)
    kb = k * beta

    def system(a, b):
        return jnp.einsum("...mik,...mjk->...mij", a, b, precision=HIGHEST)

    def operands(a, b):
        return jnp.einsum("...mik,...mjk->...mij", a.astype(cdt),
                          b.astype(cdt), preferred_element_type=f32)
    a = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1),
                  decayed_products(kb, k, cum, system), 0.0)
    wu = jnp.einsum(
        "cbhij,cbhjk->cbhik", unit_lower_inverse(a),
        jnp.concatenate([kb * jnp.exp(cum), v * beta], axis=-1),
        precision=HIGHEST)
    w, u = wu[..., :dk], wu[..., dk:]
    qk = decayed_products(q, k, cum, operands)
    q_in = q * jnp.exp(cum)
    k_out = k * jnp.exp(cum[..., -1:, :] - cum)
    whole = jnp.exp(cum[..., -1, :])                     # (G, B, H, K)
    return _chunks_in_turn(state, (w, u, qk, q_in, k_out, whole),
                           lambda whole: whole[..., None, :], cdt)


# -- the delta rule: the kernel tier ----------------------------------------
#: bytes of VMEM the kernels may take (their blocks, double-buffered)
_VMEM_LIMIT = 64 * 1024 * 1024


def _mm(a, b, ta=False, tb=False, cdt=None):
    """``a @ b`` (``ta`` / ``tb``: of the transpose), float32 out: the
    operands in ``cdt``, or float32 at ``highest`` where ``cdt`` is None."""
    dims = (((0 if ta else 1,), (1 if tb else 0,)), ((), ()))
    if cdt is None:
        return jax.lax.dot_general(a, b, dims, precision=HIGHEST,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a.astype(cdt), b.astype(cdt), dims,
                               preferred_element_type=jnp.float32)


def _col(row, eye):
    """A ``(1, L)`` row as an ``(L, 1)`` column (exact: a sum of zeros)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _inverse(a, ri, ci):
    """``(I + a)^-1`` of a strictly lower ``a (L, L)`` by halves, as
    :func:`unit_lower_inverse` on whole matrices: ``x`` holds the
    inverses of the diagonal blocks of ``size``; the lower-left quarter
    of each block of twice that, ``-Q C P``, is ``-x C x``."""
    x = (ri == ci).astype(jnp.float32)
    size = 1
    while size < a.shape[0]:
        quarter = (((ri ^ ci) < 2 * size) & ((ri & size) != 0)
                   & ((ci & size) == 0))
        x = x - _mm(_mm(x, jnp.where(quarter, a, 0.0)), x)
        size *= 2
    return x


def _system(q, k, vb, c_row, b_row, cdt):
    """What a chunk computes before its state: ``q``, ``k (L, K)``, ``vb =
    beta v (L, V)``, the running sums ``c_row`` and ``b_row`` (beta) ``(1,
    L)`` -> a dict of the chunk's float32 arrays (``chunk_group``'s)."""
    n = q.shape[0]
    ri = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye, lower, strict = ri == ci, ci <= ri, ci < ri
    c_col, b_col = _col(c_row, eye), _col(b_row, eye)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, c_col - c_row, 0.0)),
                      0.0)
    kb = k * b_col
    gram = _mm(kb, k, tb=True)
    t = _inverse(jnp.where(strict, decay * gram, 0.0), ri, ci)
    e_col = jnp.exp(c_col)
    last = c_row[:, n - 1:]                              # (1, 1)
    qk = _mm(q, k, tb=True, cdt=cdt)
    f_col = jnp.exp(last - c_col)
    return {"eye": eye, "lower": lower, "strict": strict, "b_col": b_col,
            "decay": decay, "kb": kb, "gram": gram, "t": t, "e_col": e_col,
            "w": _mm(t, kb * e_col), "u": _mm(t, vb), "qk_raw": qk,
            "qk": decay * qk, "q_in": q * e_col, "f_col": f_col,
            "k_out": k * f_col, "whole": jnp.exp(last),
            # the same as a row of K (a sum over the rows: Mosaic broadcasts
            # a row over a state, not one number)
            "whole_row": jnp.exp(jnp.sum(jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) == n - 1,
                jnp.broadcast_to(c_col, k.shape), 0.0), axis=0,
                keepdims=True))}


def _chunk(c_ref, b_ref, q_ref, k_ref, v_ref, n):
    """Chunk ``n``'s blocks: (q, k, vb, c_row, b_row)."""
    return (q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
            c_ref[0, 0, pl.ds(n, 1), :], b_ref[0, 0, pl.ds(n, 1), :])


def _corrected(s, sy, cdt):
    """A chunk's corrected values ``u' = U - W S^T`` from the state ``s``
    it starts from."""
    return sy["u"] - _mm(sy["w"], s, tb=True, cdt=cdt)


def _fwd_kernel(*refs, cdt, save):
    """Chunk ``n`` of one (row, head): the state in ``s_scr``, zeroed at
    the row's first chunk; ``st_ref`` (``save``) takes the state the chunk
    starts from."""
    n = pl.program_id(2)
    o_ref, s_scr = refs[5], refs[-1]

    @pl.when(n == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    sy = _system(*_chunk(*refs[:5], n), cdt)
    s = s_scr[...]
    if save:
        refs[6][0, 0, 0] = s
    new = _corrected(s, sy, cdt)
    o_ref[0, 0] = (_mm(sy["q_in"], s, tb=True, cdt=cdt)
                   + _mm(sy["qk"], new, cdt=cdt))
    s_scr[...] = carried(s, sy["whole_row"]) + _mm(new, sy["k_out"],
                                                   ta=True, cdt=cdt)


def _bwd_kernel(*refs, cdt):
    """Chunk ``n`` of one (row, head), the row's chunks walked from its
    last: the chunk's system is set up again from its inputs and the saved
    state it started from; ``ds_scr`` carries the gradient of the state
    the chunk hands on."""
    st_ref, do_ref = refs[5], refs[6]
    dq_ref, dk_ref, dv_ref, dc_ref, db_ref, ds_scr = refs[7:]
    n = pl.num_programs(2) - 1 - pl.program_id(2)

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    q, k, vb, c_row, b_row = _chunk(*refs[:5], n)
    sy = _system(q, k, vb, c_row, b_row, cdt)
    s, do, ds = st_ref[0, 0, 0], do_ref[0, 0], ds_scr[...]
    w, u = sy["w"], sy["u"]
    new = _corrected(s, sy, cdt)
    # o = q_in s^T + qk new;  s' = whole s + new^T k_out;  new = u - w s^T
    dnew = (_mm(sy["qk"], do, ta=True, cdt=cdt)
            + _mm(sy["k_out"], ds, tb=True, cdt=cdt))
    dqk = _mm(do, new, tb=True, cdt=cdt)
    dk_out = _mm(new, ds, cdt=cdt)
    dq_in = _mm(do, s, cdt=cdt)
    dw = -_mm(dnew, s, cdt=cdt)
    d_whole = jnp.sum(jnp.sum(ds * s, axis=1, keepdims=True), axis=0,
                      keepdims=True)
    ds_scr[...] = (carried(ds, sy["whole_row"])
                   + _mm(do, sy["q_in"], ta=True, cdt=cdt)
                   - _mm(dnew, w, ta=True, cdt=cdt))
    # [w | u] = t [kb exp(c) | beta v],  t = (I + a)^-1
    drw, dru = _mm(sy["t"], dw, ta=True), _mm(sy["t"], dnew, ta=True)
    da = jnp.where(sy["strict"], -(_mm(drw, w, tb=True)
                                   + _mm(dru, u, tb=True)), 0.0)
    # a = strict(decay * kb k^T),  qk = decay * (q k^T)
    dgram = da * sy["decay"]
    dqk_raw = dqk * sy["decay"]
    ddecay = da * sy["gram"] + jnp.where(sy["lower"], dqk * sy["qk_raw"],
                                         0.0)
    e_col, b_col, kb = sy["e_col"], sy["b_col"], sy["kb"]
    dkb = _mm(dgram, k) + drw * e_col
    dq_ref[0, 0] = _mm(dqk_raw, k, cdt=cdt) + dq_in * e_col
    dk_ref[0, 0] = (_mm(dqk_raw, q, ta=True, cdt=cdt) + _mm(dgram, kb, ta=True)
                    + dkb * b_col + dk_out * sy["f_col"])
    dv_ref[0, 0] = dru
    # the running sums: exp(c), exp(c_L - c), exp(c_L), the decays
    z = jnp.sum(dk_out * sy["k_out"], axis=1, keepdims=True)
    y = ddecay * sy["decay"]
    dc_col = ((jnp.sum(drw * kb, axis=1, keepdims=True)
               + jnp.sum(dq_in * q, axis=1, keepdims=True)) * e_col
              - z + jnp.sum(y, axis=1, keepdims=True))
    chunk = q.shape[0]
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    d_last = jnp.sum(z, axis=0, keepdims=True) + d_whole * sy["whole"]
    dc_ref[0, 0, pl.ds(n, 1), :] = (
        _row(dc_col, sy["eye"]) - jnp.sum(y, axis=0, keepdims=True)
        + jnp.where(at_last, d_last, 0.0))
    db_ref[0, 0, pl.ds(n, 1), :] = _row(
        jnp.sum(dkb * k, axis=1, keepdims=True), sy["eye"])


def _specs(nc, chunk, widths, reverse=False):
    """Blocks of one grid step (row, head, chunk): the running sums and
    beta whole for the row and head, the chunk of each of ``widths``."""
    def at(i):
        return nc - 1 - i if reverse else i
    whole = pl.BlockSpec((1, 1, nc, chunk), lambda r, hh, i: (r, hh, 0, 0))
    return whole, [pl.BlockSpec((1, 1, chunk, wd),
                                lambda r, hh, i: (r, hh, at(i), 0))
                   for wd in widths]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _states_spec(nc, dv, dk, reverse=False):
    return pl.BlockSpec((1, 1, 1, dv, dk), lambda r, hh, i: (
        r, hh, nc - 1 - i if reverse else i, 0, 0))


def _rule_fwd_call(q, k, vb, c, beta, cdt, save):
    """The forward kernel on head-major ``q``, ``k (B, H, T, K)``, ``vb =
    beta v (B, H, T, V)`` and the chunks' running sums ``c`` and ``beta (B,
    H, nc, L)``: ``o (B, H, T, V)`` (and, ``save``, the state each chunk
    starts from, ``(B, H, nc, V, K)``)."""
    b, h, t, dk = q.shape
    dv = vb.shape[-1]
    nc, chunk = c.shape[2:]
    whole, (qs, ks, vs) = _specs(nc, chunk, (dk, dk, dv))
    out_specs = [vs]
    out_shape = [jax.ShapeDtypeStruct((b, h, t, dv), jnp.float32)]
    if save:
        out_specs.append(_states_spec(nc, dv, dk))
        out_shape.append(jax.ShapeDtypeStruct((b, h, nc, dv, dk),
                                              jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, cdt=cdt, save=save),
        grid=(b, h, nc),
        in_specs=[whole, whole, qs, ks, vs],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        name="pallas_delta_rule_fwd",
        interpret=tuning.interpret_mode(),
    )(c, beta, q, k, vb)
    return out if save else out[0]


def _rule_bwd_call(q, k, vb, c, beta, states, do, cdt):
    """The backward kernel: ``(dq, dk, dvb, dc, dbeta)``, shaped as ``q``,
    ``k``, ``vb``, ``c``, ``beta``."""
    b, h, t, dk = q.shape
    dv = vb.shape[-1]
    nc, chunk = c.shape[2:]
    whole, (qs, ks, vs) = _specs(nc, chunk, (dk, dk, dv), reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, cdt=cdt),
        grid=(b, h, nc),
        in_specs=[whole, whole, qs, ks, vs,
                  _states_spec(nc, dv, dk, reverse=True), vs],
        out_specs=[qs, ks, vs, whole, whole],
        out_shape=[jax.ShapeDtypeStruct(a.shape, jnp.float32)
                   for a in (q, k, vb, c, beta)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        name="pallas_delta_rule_bwd",
        interpret=tuning.interpret_mode(),
    )(c, beta, q, k, vb, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule_kernel(q, k, vb, c, beta, cdt):
    return tuning.batch_sharded(
        lambda *a: _rule_fwd_call(*a, cdt, save=False), q, k, vb, c, beta)


def _rule_kernel_fwd(q, k, vb, c, beta, cdt):
    o, states = tuning.batch_sharded(
        lambda *a: tuple(_rule_fwd_call(*a, cdt, save=True)),
        q, k, vb, c, beta)
    return o, (q, k, vb, c, beta, states)


def _rule_kernel_bwd(cdt, res, do):
    return tuning.batch_sharded(
        lambda *a: tuple(_rule_bwd_call(*a, cdt)), *res, do)


_rule_kernel.defvjp(_rule_kernel_fwd, _rule_kernel_bwd)


def rule_route() -> str:
    """``kernel`` where the delta rule (scalar and per-channel) runs as the
    Pallas kernels, else ``chunked`` (the ``jax.numpy`` form)."""
    return "kernel" if tuning.use_pallas() else "chunked"


def delta_rule_kernel(q, k, v, g, beta, chunk: int, cdt=jnp.float32):
    """:func:`delta_rule` through the kernels: the same arguments and
    result.  Inputs go head-major (``q``'s and ``k``'s relayouts fuse
    into their L2 norms' pass, ``v``'s into the product ``beta v`` after
    one copy of 94 MB a layer at 4,096 tokens), ``v`` as ``beta v`` and
    ``g`` as its running sums inside each chunk (JAX hands their
    gradients back to ``v``, ``beta`` and ``g``)."""
    bsz, t, h, _ = q.shape
    nc = t // chunk

    def by_chunk(a):        # (B, T, H) -> (B, H, nc, L)
        return a.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2)
    o = _rule_kernel(*(a.transpose(0, 2, 1, 3)
                       for a in (q, k, v * beta[..., None])),
                     jnp.cumsum(by_chunk(g), axis=-1), by_chunk(beta), cdt)
    return o.transpose(0, 2, 1, 3)


def delta_rule(q, k, v, g, beta, chunk: int, cdt=jnp.float32):
    """``o_t = S_t q_t`` of ``S_t = exp(g_t) S_{t-1} + beta_t (v_t -
    exp(g_t) S_{t-1} k_t) k_t^T`` in chunks of ``chunk`` tokens.  ``q``,
    ``k (B, T, H, K)``, ``v (B, T, H, V)``, ``g`` (not positive) and
    ``beta (B, T, H)``; float32 in and out.  The kernels where Pallas runs
    (:func:`delta_rule_kernel`), else the ``jax.numpy`` chunked form
    (:func:`delta_rule_chunked`)."""
    if q.shape[1] % chunk:
        raise ValueError(f"sequence length {q.shape[1]} is no multiple of "
                         f"the delta rule's chunk {chunk}")
    form = delta_rule_kernel if tuning.use_pallas() else delta_rule_chunked
    return form(q, k, v, g, beta, chunk, cdt)


def delta_rule_chunked(q, k, v, g, beta, chunk: int, cdt=jnp.float32):
    """:func:`delta_rule` in ``jax.numpy``: the chunks go ``GROUP`` at a
    time (:func:`chunk_group`), each group rematerialised by itself in the
    backward: what is kept a group is the state it starts from, and the
    float32 ``(chunks, B, H, L, L)`` and ``(chunks, B, H, L, K + V)``
    arrays of the chunked form are never whole."""
    return _by_groups(chunk_group, q, k, v, g, beta, chunk, cdt)


def _by_groups(group_fn, q, k, v, g, beta, chunk: int, cdt):
    """A chunked rule over the sequence: ``group_fn`` (:func:`chunk_group`,
    :func:`kda_group`) on ``GROUP`` chunks at a time, a ``lax.scan`` carrying
    the state, each group rematerialised by itself."""
    bsz, t, h, _ = q.shape
    nc = t // chunk
    group = GROUP if nc % GROUP == 0 else nc

    def groups(a):          # (B, T, H, .) -> (nc / G, G, B, H, L, .)
        return a.reshape(bsz, nc // group, group, chunk, h, -1).transpose(
            1, 2, 0, 4, 3, 5)
    _, o = jax.lax.scan(
        jax.checkpoint(lambda state, xs: group_fn(state, *xs, cdt)),
        jnp.zeros((bsz, h, v.shape[-1], k.shape[-1]), jnp.float32),
        tuple(groups(a) for a in (q, k, v, g, beta)))
    return o.transpose(2, 0, 1, 4, 3, 5).reshape(bsz, t, h, v.shape[-1])


# -- the per-channel rule: the kernel tier ----------------------------------
def _kda_row(ref, i):
    """Token ``i`` of a chunk's ``(1, 1, L, K)`` block, a ``(1, K)`` row."""
    return ref[0, 0, pl.ds(i, 1), :]


def _kda_parts(c_ref, c, lo, sub):
    """The two factors of :func:`decayed_products`' cross sums for the
    sub-chunk of rows ``lo`` to ``lo + sub``: ``exp(c_i - c_b)`` of its rows
    and ``exp(c_b - c_j)`` of every row, ``b`` the running sum just before
    the sub-chunk; None for the first sub-chunk, which has no tokens before
    it.  The second is clamped at 1 where ``j`` lies at or after ``b``:
    those columns are masked out of every product."""
    if not lo:
        return None
    start = _kda_row(c_ref, lo - 1)
    return (jnp.exp(c[lo:lo + sub] - start),
            jnp.exp(jnp.minimum(start - c, 0.0)))


def _kda_inner(c_ref, k_ref, cs, lo, j):
    """Token ``lo + j``'s key against the rows ``cs`` of its sub-chunk:
    ``(k_j exp(c_i - c_j), exp(c_i - c_j))``, ``(SUB, K)``, the decay
    clamped at 1 for the rows before ``j`` (masked out of every product)."""
    decay = jnp.exp(jnp.minimum(cs - _kda_row(c_ref, lo + j), 0.0))
    return _kda_row(k_ref, lo + j) * decay, decay


def _kda_products(lefts, cdts, k, c, c_ref, k_ref):
    """:func:`decayed_products` of each of ``lefts (L, K)`` against ``k``
    with the running sums ``c``, the cross sums of the sub-chunks products
    of matrices with the operands in each of ``cdts`` (None: float32 at
    ``highest``), the sums inside a sub-chunk float32 a column at a time.
    -> one ``(L, L)`` a left; what stands above the diagonal is not
    defined."""
    n = k.shape[0]
    sub = min(SUB, n)
    col = jax.lax.broadcasted_iota(jnp.int32, (sub, n), 1)
    rows = [[] for _ in lefts]
    for lo in range(0, n, sub):
        cs, parts = c[lo:lo + sub], _kda_parts(c_ref, c, lo, sub)
        blocks = []
        for left, cdt in zip(lefts, cdts):
            if parts is None:
                blocks.append(jnp.zeros((sub, n), jnp.float32))
            else:
                cross = _mm(left[lo:lo + sub] * parts[0], k * parts[1],
                            tb=True, cdt=cdt)
                blocks.append(jnp.where(col < lo, cross, 0.0))
        for j in range(sub):
            x, _ = _kda_inner(c_ref, k_ref, cs, lo, j)
            blocks = [jnp.where(col == lo + j, jnp.sum(
                left[lo:lo + sub] * x, axis=1, keepdims=True), block)
                for left, block in zip(lefts, blocks)]
        for out, block in zip(rows, blocks):
            out.append(block)
    return [jnp.concatenate(r, axis=0) for r in rows]


def _kda_product_grads(grads, lefts, cdts, k, c, c_ref, k_ref):
    """The transpose of :func:`_kda_products` at its precision: for the
    gradients ``grads (L, L)`` of its products (zero above the diagonal
    and wherever the product is not used) -> (the gradient of each left,
    of ``k``).  That of the running sums is ``sum left * dleft - k * dk``:
    the decay between two tokens is a function of their difference."""
    n = k.shape[0]
    sub = min(SUB, n)
    col = jax.lax.broadcasted_iota(jnp.int32, (sub, n), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, k[:sub].shape, 0)
    dlefts = [[] for _ in lefts]
    dk_rows = []
    dk = jnp.zeros_like(k)
    for lo in range(0, n, sub):
        cs, parts = c[lo:lo + sub], _kda_parts(c_ref, c, lo, sub)
        pieces = [grad[lo:lo + sub] for grad in grads]
        dl = [jnp.zeros_like(cs) for _ in lefts]
        if parts is not None:
            mine, before = parts
            for i, (piece, left, cdt) in enumerate(zip(pieces, lefts, cdts)):
                cross = jnp.where(col < lo, piece, 0.0)
                dl[i] = _mm(cross, k * before, cdt=cdt) * mine
                dk = dk + _mm(cross, left[lo:lo + sub] * mine, ta=True,
                              cdt=cdt) * before
        dk_sub = jnp.zeros_like(cs)
        for j in range(sub):
            x, decay = _kda_inner(c_ref, k_ref, cs, lo, j)
            dk_j = jnp.zeros_like(cs[:1])
            for i, (piece, left) in enumerate(zip(pieces, lefts)):
                g = jnp.sum(jnp.where(col == lo + j, piece, 0.0), axis=1,
                            keepdims=True)
                dl[i] = dl[i] + g * x
                dk_j = dk_j + jnp.sum(g * left[lo:lo + sub] * decay, axis=0,
                                      keepdims=True)
            dk_sub = jnp.where(row == j, dk_j, dk_sub)
        for out, d in zip(dlefts, dl):
            out.append(d)
        dk_rows.append(dk_sub)
    return ([jnp.concatenate(d, axis=0) for d in dlefts],
            dk + jnp.concatenate(dk_rows, axis=0))


def _kda_system(b_ref, q_ref, k_ref, c_ref, v_ref, n, cdt, t=None):
    """What chunk ``n`` computes before its state (:func:`kda_group`'s
    arrays): its blocks ``q``, ``k``, ``c`` (running sums) ``(L, K)``,
    ``vb = beta v (L, V)`` and beta's row of the chunks -> a dict of float32
    arrays; ``t``, the inverse of the chunk's system, where it is known."""
    q, k, vb, c = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], c_ref[0, 0]
    n_tok = q.shape[0]
    ri = jax.lax.broadcasted_iota(jnp.int32, (n_tok, n_tok), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (n_tok, n_tok), 1)
    eye, lower, strict = ri == ci, ci <= ri, ci < ri
    b_col = _col(b_ref[0, 0, pl.ds(n, 1), :], eye)
    kb = k * b_col
    if t is None:
        a, qk = _kda_products((kb, q), (None, cdt), k, c, c_ref, k_ref)
        t = _inverse(jnp.where(strict, a, 0.0), ri, ci)
    else:
        qk, = _kda_products((q,), (cdt,), k, c, c_ref, k_ref)
    e = jnp.exp(c)
    last = _kda_row(c_ref, n_tok - 1)                    # (1, K)
    f = jnp.exp(last - c)
    return {"q": q, "k": k, "c": c, "eye": eye, "lower": lower,
            "strict": strict, "b_col": b_col, "kb": kb, "t": t, "e": e,
            "w": _mm(t, kb * e), "u": _mm(t, vb),
            "qk": jnp.where(lower, qk, 0.0), "q_in": q * e, "f": f,
            "k_out": k * f, "whole_row": jnp.exp(last)}


def _kda_fwd_kernel(b_ref, q_ref, k_ref, c_ref, v_ref, *refs, cdt, save):
    """Chunk ``n`` of one (row, head): the state in ``s_scr``, zeroed at the
    row's first chunk; with ``save`` two more outputs take the state the
    chunk starts from and the inverse of its system."""
    n = pl.program_id(2)
    o_ref, s_scr = refs[0], refs[-1]

    @pl.when(n == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    sy = _kda_system(b_ref, q_ref, k_ref, c_ref, v_ref, n, cdt)
    s = s_scr[...]
    if save:
        refs[1][0, 0, 0] = s
        refs[2][0, 0, 0] = sy["t"]
    new = _corrected(s, sy, cdt)
    o_ref[0, 0] = (_mm(sy["q_in"], s, tb=True, cdt=cdt)
                   + _mm(sy["qk"], new, cdt=cdt))
    s_scr[...] = carried(s, sy["whole_row"]) + _mm(new, sy["k_out"],
                                                   ta=True, cdt=cdt)


def _kda_bwd_kernel(b_ref, q_ref, k_ref, c_ref, v_ref, st_ref, t_ref, do_ref,
                    dq_ref, dk_ref, dc_ref, dv_ref, db_ref, ds_scr, *, cdt):
    """Chunk ``n`` of one (row, head), the row's chunks walked from its
    last: the chunk's arrays are made again from its inputs, the state it
    started from and the inverse of its system, which the forward kept;
    ``ds_scr`` carries the gradient of the state the chunk hands on."""
    n = pl.num_programs(2) - 1 - pl.program_id(2)

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    sy = _kda_system(b_ref, q_ref, k_ref, c_ref, v_ref, n, cdt,
                     t_ref[0, 0, 0])
    s, do, ds = st_ref[0, 0, 0], do_ref[0, 0], ds_scr[...]
    q, k, c, w, u = sy["q"], sy["k"], sy["c"], sy["w"], sy["u"]
    new = _corrected(s, sy, cdt)
    # o = q_in s^T + qk new;  s' = s whole + new^T k_out;  new = u - w s^T
    dnew = (_mm(sy["qk"], do, ta=True, cdt=cdt)
            + _mm(sy["k_out"], ds, tb=True, cdt=cdt))
    dqk = jnp.where(sy["lower"], _mm(do, new, tb=True, cdt=cdt), 0.0)
    dk_out = _mm(new, ds, cdt=cdt)
    dq_in = _mm(do, s, cdt=cdt)
    d_whole = jnp.sum(ds * s, axis=0, keepdims=True)     # (1, K)
    ds_scr[...] = (carried(ds, sy["whole_row"])
                   + _mm(do, sy["q_in"], ta=True, cdt=cdt)
                   - _mm(dnew, w, ta=True, cdt=cdt))
    # [w | u] = t [kb exp(c) | beta v],  t = (I + a)^-1
    drw = -_mm(sy["t"], _mm(dnew, s, cdt=cdt), ta=True)
    dru = _mm(sy["t"], dnew, ta=True)
    da = jnp.where(sy["strict"], -(_mm(drw, w, tb=True)
                                   + _mm(dru, u, tb=True)), 0.0)
    # a = strict(decayed(kb, k)),  qk = lower(decayed(q, k))
    kb, e = sy["kb"], sy["e"]
    (dkb_a, dq_qk), dk_dec = _kda_product_grads(
        (da, dqk), (kb, q), (None, cdt), k, c, c_ref, k_ref)
    dkb = dkb_a + drw * e
    dq_ref[0, 0] = dq_qk + dq_in * e
    dk_ref[0, 0] = dk_dec + dk_out * sy["f"] + dkb * sy["b_col"]
    dv_ref[0, 0] = dru
    # the running sums: the decays between tokens, exp(c), exp(c_L - c),
    # exp(c_L)
    z = dk_out * sy["k_out"]
    d_last = jnp.sum(z, axis=0, keepdims=True) + d_whole * sy["whole_row"]
    at_last = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) == k.shape[0] - 1
    dc_ref[0, 0] = (kb * dkb_a + q * dq_qk - k * dk_dec + drw * kb * e
                    + dq_in * sy["q_in"] - z + jnp.where(at_last, d_last, 0.0))
    db_ref[0, 0, pl.ds(n, 1), :] = _row(
        jnp.sum(dkb * k, axis=1, keepdims=True), sy["eye"])


# Both calls are jitted so that their kernel bodies, unrolled over the
# tokens of a sub-chunk, are traced and lowered once a process and shape:
# traced at every call (three layers, the custom_vjp's forward and backward,
# every program an epoch builds) they added tens of seconds to a start.
@functools.partial(jax.jit, static_argnames=("cdt", "save"))
def _kda_fwd_call(q, k, c, vb, beta, cdt, save):
    """The forward kernel on head-major ``q``, ``k``, the running sums ``c
    (B, H, T, K)``, ``vb = beta v (B, H, T, V)`` and ``beta (B, H, nc, L)``:
    ``o (B, H, T, V)`` (and, ``save``, the state each chunk starts from,
    ``(B, H, nc, V, K)``, and the inverse of its system, ``(B, H, nc, L,
    L)``)."""
    b, h, t, dk = q.shape
    dv = vb.shape[-1]
    nc, chunk = beta.shape[2:]
    whole, (qs, vs) = _specs(nc, chunk, (dk, dv))
    out_specs = [vs]
    out_shape = [jax.ShapeDtypeStruct((b, h, t, dv), jnp.float32)]
    if save:
        out_specs.append(_states_spec(nc, dv, dk))
        out_shape.append(jax.ShapeDtypeStruct((b, h, nc, dv, dk),
                                              jnp.float32))
        out_specs.append(_states_spec(nc, chunk, chunk))
        out_shape.append(jax.ShapeDtypeStruct((b, h, nc, chunk, chunk),
                                              jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kda_fwd_kernel, cdt=cdt, save=save),
        grid=(b, h, nc),
        in_specs=[whole, qs, qs, qs, vs],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        name="pallas_kda_rule_fwd",
        interpret=tuning.interpret_mode(),
    )(beta, q, k, c, vb)
    return out if save else out[0]


@functools.partial(jax.jit, static_argnames=("cdt",))
def _kda_bwd_call(q, k, c, vb, beta, states, inverses, do, cdt):
    """The backward kernel: ``(dq, dk, dc, dvb, dbeta)``, shaped as ``q``,
    ``k``, ``c``, ``vb``, ``beta``."""
    b, h, t, dk = q.shape
    dv = vb.shape[-1]
    nc, chunk = beta.shape[2:]
    whole, (qs, vs) = _specs(nc, chunk, (dk, dv), reverse=True)
    return pl.pallas_call(
        functools.partial(_kda_bwd_kernel, cdt=cdt),
        grid=(b, h, nc),
        in_specs=[whole, qs, qs, qs, vs,
                  _states_spec(nc, dv, dk, reverse=True),
                  _states_spec(nc, chunk, chunk, reverse=True), vs],
        out_specs=[qs, qs, qs, vs, whole],
        out_shape=[jax.ShapeDtypeStruct(a.shape, jnp.float32)
                   for a in (q, k, c, vb, beta)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        name="pallas_kda_rule_bwd",
        interpret=tuning.interpret_mode(),
    )(beta, q, k, c, vb, states, inverses, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda_kernel(q, k, c, vb, beta, cdt):
    return tuning.batch_sharded(
        lambda *a: _kda_fwd_call(*a, cdt=cdt, save=False), q, k, c, vb, beta)


def _kda_kernel_fwd(q, k, c, vb, beta, cdt):
    o, states, inverses = tuning.batch_sharded(
        lambda *a: tuple(_kda_fwd_call(*a, cdt=cdt, save=True)),
        q, k, c, vb, beta)
    return o, (q, k, c, vb, beta, states, inverses)


def _kda_kernel_bwd(cdt, res, do):
    return tuning.batch_sharded(
        lambda *a: tuple(_kda_bwd_call(*a, cdt=cdt)), *res, do)


_kda_kernel.defvjp(_kda_kernel_fwd, _kda_kernel_bwd)


def kda_rule_kernel(q, k, v, g, beta, chunk: int, cdt=jnp.float32):
    """:func:`kda_rule` through the kernels: the same arguments and result.
    ``q``, ``k`` and ``v`` go head-major, ``v`` as ``beta v``, ``g`` as its
    running sums inside each chunk (JAX hands their gradients back to
    ``v``, ``beta`` and ``g``)."""
    bsz, t, h, dk = q.shape
    nc = t // chunk

    def head_major(a):      # (B, T, H, .) -> (B, H, T, .)
        return a.transpose(0, 2, 1, 3)
    c = jnp.cumsum(head_major(g).reshape(bsz, h, nc, chunk, dk),
                   axis=-2).reshape(bsz, h, t, dk)
    o = _kda_kernel(head_major(q), head_major(k), c,
                    head_major(v * beta[..., None]),
                    beta.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2),
                    cdt)
    return head_major(o)


def kda_rule_chunked(q, k, v, g, beta, chunk: int, cdt=jnp.float32):
    """:func:`kda_rule` in ``jax.numpy``: :func:`kda_group` on ``GROUP``
    chunks at a time, each group rematerialised by itself."""
    return _by_groups(kda_group, q, k, v, g, beta, chunk, cdt)


def kda_rule(q, k, v, g, beta, chunk: int, cdt=jnp.float32):
    """``o_t = S_t q_t`` of ``S_t = S_{t-1} Diag(exp(g_t)) + beta_t (v_t -
    S_{t-1} Diag(exp(g_t)) k_t) k_t^T`` in chunks of ``chunk`` tokens: ``q``,
    ``k``, ``g (B, T, H, K)`` (``g`` not positive: a decay a key channel),
    ``v (B, T, H, V)``, ``beta (B, T, H)``; float32 in and out.  The kernels
    where Pallas runs (:func:`kda_rule_kernel`), else the ``jax.numpy``
    chunked form (:func:`kda_rule_chunked`)."""
    if q.shape[1] % chunk:
        raise ValueError(f"sequence length {q.shape[1]} is no multiple of "
                         f"the delta rule's chunk {chunk}")
    if chunk % min(SUB, chunk):
        raise ValueError(f"a chunk of {chunk} tokens is no multiple of the "
                         f"sub-chunk {SUB}")
    form = kda_rule_kernel if tuning.use_pallas() else kda_rule_chunked
    return form(q, k, v, g, beta, chunk, cdt)


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


def kda_mixer(leaves, xn, cfg: dict, cdt=jnp.float32):
    """Kimi Delta Attention of its input ``xn (B, T, d)``: ``(B, T, d)``
    float32; ``leaves``: :data:`KDA_LEAVES` without ``g1``.  Per head (``n``
    the input)

        q, k, v = silu(causal depthwise conv(n Wq | n Wk | n Wv)), no bias
        q <- q / |q|_2 * K^(-1/2),  k <- k / |k|_2
        beta_t = 2 sigmoid(n Wb)
        g_t = -exp(A_log) softplus((n Wfa) Wfb + dt_bias)   (K a head)
        o = kda_rule(q, k, v, g, beta)
        out = (RMSNorm(o; g_o over a head's V) * sigmoid((n Wga) Wgb + bg)) Wo

    The projections, convs and decays are rematerialised by themselves in
    the backward, as :func:`mixer`'s are."""
    (wq, wk, wv, wb, wfa, wfb, wga, wgb, bg, conv_q, conv_k, conv_v, a_log,
     dt_bias, g_o, wo) = leaves
    bsz, t, _ = xn.shape
    h, dk, dv = cfg["heads"], cfg["key_dim"], cfg["value_dim"]
    xc = xn.astype(cdt)

    def dot(a, w):
        return jnp.dot(a, w.astype(cdt), preferred_element_type=jnp.float32)

    def low_rank(x, wa, wb):
        return dot(dot(x, wa).astype(cdt), wb)

    @jax.checkpoint
    def inputs(xc, wq, wk, wv, wb, wfa, wfb, conv_q, conv_k, conv_v, a_log,
               dt_bias):
        with jax.named_scope("short_conv"):
            q, k, v = (jax.nn.silu(causal_conv(dot(xc, w), taps, 0.0))
                       for w, taps in ((wq, conv_q), (wk, conv_k),
                                       (wv, conv_v)))
        q = l2_norm(q.reshape(bsz, t, h, dk)) * dk ** -0.5
        k = l2_norm(k.reshape(bsz, t, h, dk))
        with jax.named_scope("kda_gates"):
            beta = 2.0 * jax.nn.sigmoid(dot(xc, wb))
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                (low_rank(xc, wfa, wfb) + dt_bias).reshape(bsz, t, h, dk))
        return q, k, v.reshape(bsz, t, h, dv), g, beta
    rule_inputs = inputs(xc, wq, wk, wv, wb, wfa, wfb, conv_q, conv_k,
                         conv_v, a_log, dt_bias)
    with jax.named_scope("kda_rule"):
        o = kda_rule(*rule_inputs, cfg["chunk"], cdt)
    with jax.named_scope("kda_gates"):
        gate = jax.nn.sigmoid(low_rank(xc, wga, wgb) + bg)
    gated = rms_norm(o, g_o, cfg["eps"]) * gate.reshape(bsz, t, h, dv)
    return dot(gated.reshape(bsz, t, h * dv).astype(cdt), wo)


def kda_block_fwd(leaves, x, cfg: dict, cdt=jnp.float32):
    """``kda_block``: ``x + scale * KDA(RMSNorm(x; g1))`` (``cfg["norm"]``
    "pre"; "post": the norm on the mixer's output); leaves as
    :data:`KDA_LEAVES` (shapes: :func:`kda_leaf_shapes`); ``cfg``:
    ``heads``, ``key_dim``, ``value_dim``, ``conv``, ``chunk``, ``eps``,
    ``norm``, ``scale`` (None: 1).  -> ``(y, counters)``; ``kda_tokens``
    counts the tokens this layer scanned."""
    g1, *rest = leaves
    bsz, t, _ = x.shape
    h, dk, dv = cfg["heads"], cfg["key_dim"], cfg["value_dim"]
    if len(rest) != len(KDA_LEAVES) - 1 or rest[0].shape[1] != h * dk \
            or rest[2].shape[1] != h * dv:
        raise ValueError(f"kda_block of {h} heads, keys of {dk} and values "
                         f"of {dv}: {len(leaves)} leaves, Wq "
                         f"{rest[0].shape}, Wv {rest[2].shape}")
    with jax.named_scope("kda_block"):
        y = residual_block(x, g1, cfg,
                           lambda xn: kda_mixer(rest, xn, cfg, cdt))
    return y, {"kda_tokens": jnp.asarray(bsz * t, jnp.int32)}
