"""The sparse expert layer of a decoder, for the experts one chip holds.

``moe_block`` is ``h + MoE(RMSNorm(h; g2))`` (with the columns held of a
shared expert beside the sparse ones, and a scale on the sum, where the
model has them) with a router over ALL the
model's experts and the products of the experts HELD here: the layer is
told ``experts`` (the router's width), ``experts_held = (first, count)``
and ``top_k``; it routes every token over all experts, normalises the
chosen weights over all ``top_k`` of them, and adds the terms of its own
experts.  What the absent experts would have added is left out (the
expert-parallel exchange that would bring it is ROADMAP Reach 4); no
code stands in for the absent chips.

The work follows the assignments held, whatever their spread: the
token-expert pairs are sorted by held expert (the pairs of absent
experts behind them) and multiplied in ONE grouped product a projection
over the rows that are held.  No token is dropped and no expert has a
capacity: shapes are static, so the sorted pairs are taken in two pieces
(a long sequence goes ``CHUNK_TOKENS`` at a time): the first, one and a
half times a balanced router's share (12,288 of a chunk's 32,768 pairs
where a quarter of the experts is held), holds every held pair in the
usual case; the later one takes all the rest and runs only when the held
pairs reach into it, forward AND backward: the sum over the pieces has a
``custom_vjp`` (``sum_of_pieces``) whose backward stands under the same
condition and adds the later piece's gradients into the first's, so a
piece that did not run makes no gradient of zeros for the experts' weights
and none is added (170 MB a layer at 9 x 4096 x 768).  What moves, each
way once: a piece's rows of the
normalised input, gathered into expert order in the operand dtype
(``dispatch_rows``), and the products' float32 rows, gathered back and
summed ``top_k`` a token with their routing weights (``combine``); the
backward of each is the other's movement, written by hand in sorted
space.  A pair that is not live in a piece reads nothing, so the rows no
kernel wrote are never zeroed and never looked at.  ``moe_rows_moved``
counts the rows of the pieces that ran.  Two tiers, dispatched as the
repo's other ops:

* TPU (and the Pallas interpreter): JAX's shipped megablox kernels
  (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for the products
  and the input gradients, ``tgmm`` for the weight gradients) under a
  ``custom_vjp`` of our own, so that each of the three products of the
  backward gets tiles that divide ITS shape;
* everywhere else ``jax.lax.ragged_dot``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import tuning
from .attention import residual_block, rms_norm

HIGHEST = jax.lax.Precision.HIGHEST

#: rows of a grouped product's tile: a group of n rows costs about
#: ``1 + TILE_M / n`` of its work (its first and last tiles are shared)
TILE_M = 256

#: tokens the expert products take at a time: the buffers of the sorted
#: assignments have the worst-case length ``tokens * top_k`` (every pair
#: on a held expert), ten or so float32 ``(rows, d)`` arrays at the peak
#: of the backward, so a sequence of 8,192 is done in two halves, each
#: rematerialised by itself
CHUNK_TOKENS = 4096


def _tile(n: int, most: int = 1024) -> int:
    """The largest divisor of ``n`` that is a multiple of 128 and at most
    ``most`` (``n`` itself where it has none)."""
    for t in range(most - most % 128, 0, -128):
        if n % t == 0:
            return t
    return n


# -- the grouped product ------------------------------------------------------
def _megablox():
    """The module of the shipped kernels (the package's ``gmm`` attribute
    is its own ``custom_vjp`` of them, with one tiling for all three
    products)."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _mosaic_gmm(lhs, rhs, sizes, transpose_rhs=False):
    backend = _megablox()
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return backend.gmm(lhs, rhs, sizes, jnp.float32,
                       (min(TILE_M, lhs.shape[0]), _tile(k), _tile(n)),
                       transpose_rhs=transpose_rhs,
                       interpret=tuning.interpret_mode())


def _mosaic_tgmm(lhs, g, sizes):
    """``lhs[rows of group e].T @ g[rows of e]`` for every group: the
    kernel selects the rows of a boundary tile that are its group's (a
    select, not a product), so what the rows beyond ``sum(sizes)`` hold
    reaches no group.  Accumulated in float32 and written in the operands'
    dtype, rounded once as the kernel stores a group: the rounding a
    ``convert`` of a float32 result would do in a pass of its own."""
    return _megablox().tgmm(
        lhs.swapaxes(0, 1), g, sizes, lhs.dtype,
        (min(TILE_M, lhs.shape[0]), _tile(lhs.shape[1]), _tile(g.shape[1])),
        interpret=tuning.interpret_mode())


@jax.custom_vjp
def pallas_grouped_matmul(lhs, rhs, sizes):
    """``lhs[rows of group e] @ rhs[e]`` for every group: ``lhs (M, K)``,
    ``rhs (E, K, N)``, ``sizes (E,)`` int32, rows sorted by group; float32
    out.  The kernels never visit the rows beyond ``sum(sizes)``: what
    they hold is NOT DEFINED, here and in the input gradient, and no one
    may read them (the expert block's gathers mask them out; the weight
    gradient's kernel selects its groups' rows)."""
    return _mosaic_gmm(lhs, rhs, sizes)


def _pgm_fwd(lhs, rhs, sizes):
    return pallas_grouped_matmul(lhs, rhs, sizes), (lhs, rhs, sizes)


def _pgm_bwd(res, g):
    lhs, rhs, sizes = res
    g = g.astype(lhs.dtype)
    d_lhs = _mosaic_gmm(g, rhs, sizes, transpose_rhs=True)
    d_rhs = _mosaic_tgmm(lhs, g, sizes)
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


pallas_grouped_matmul.defvjp(_pgm_fwd, _pgm_bwd)


def xla_grouped_matmul(lhs, rhs, sizes):
    """The same function by ``jax.lax.ragged_dot`` (which writes zero to
    the rows beyond the groups)."""
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)


def kernel_route(rows: int, d: int, f: int) -> bool:
    """Whether the grouped products of a layer of these widths take the
    megablox kernels: on the Pallas tier, for widths of whole 128-lane
    tiles and whole row tiles."""
    return (tuning.use_pallas() and d % 128 == 0 and f % 128 == 0
            and rows % min(TILE_M, rows) == 0 and rows % 8 == 0)


def grouped_matmul(lhs, rhs, sizes):
    impl = (pallas_grouped_matmul
            if kernel_route(lhs.shape[0], lhs.shape[1], rhs.shape[2])
            else xla_grouped_matmul)
    return impl(lhs, rhs, sizes)


# -- rows there and back: gathers in both directions ----------------------------
# ``order`` sorts the ``tokens * top_k`` pairs by held expert (the pairs of
# absent experts last); a piece takes ``rows`` of the sorted pairs
# (``taken``), of which the first ``live`` are on held experts, and ``row
# (tokens, top_k)`` gives every pair its row in the piece.  A pair whose row
# is not in ``0 .. live`` (it is in another piece, or its expert is absent)
# reads nothing: its index is clipped and its term masked, so the rows from
# ``live`` on, which no kernel writes, are never looked at.
def _live(row, live):
    return (row >= 0) & (row < live)


def _sum_pair_rows(src, row, live, scale=None):
    """``sum_k src[row[t, k]] (* scale[t, k])`` over the pairs of token
    ``t`` whose row is live, in the order of ``k``: ``(tokens, d)``,
    accumulated in float32 and rounded once to ``src``'s dtype.  The taken
    rows land ``k`` outermost, as rows of ``(tokens, d)`` slabs (``k``
    innermost would put a token's ``top_k`` rows into the sublanes of one
    tile).  Float32 rows are taken by ONE gather; packed 16-bit rows a slab
    at a time, because one gather of them into ``(top_k, tokens, d)`` runs
    1.1 ms slower a chunk on a v5e (PERF.md section 6, PR 31)."""
    ok = _live(row, live).T[..., None]
    at = row.T
    scale = None if scale is None else scale.T[..., None]

    def slabs(at, ok, scale):
        back = jnp.take(src, at, axis=0, mode="clip").astype(jnp.float32)
        return jnp.where(ok, back if scale is None else back * scale, 0.0)
    if src.dtype.itemsize >= 4:
        out = jnp.sum(slabs(at, ok, scale), axis=0)
    else:
        out = sum(slabs(at[k], ok[k], None if scale is None else scale[k])
                  for k in range(at.shape[0]))
    return out.astype(src.dtype)


@jax.custom_vjp
def dispatch_rows(x, taken, row, live):
    """``x[taken // top_k]``: row ``i`` is the token of the ``i``-th sorted
    pair (``taken = order[lo:lo + rows]``).  The gradient is a gather and
    a sum over ``top_k`` too, not a scatter."""
    return jnp.take(x, taken // row.shape[1], axis=0, mode="clip")


def _dispatch_fwd(x, taken, row, live):
    return dispatch_rows(x, taken, row, live), (row, live)


def _dispatch_bwd(res, g):
    return _sum_pair_rows(g, *res), None, None, None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(ys, weights, taken, row, live):
    """The way back: ``sum_k weights[t, k] * ys[row[t, k]]`` over the live
    pairs of token ``t``: ``ys (rows, d)`` float32 -> ``(tokens, d)``.
    The backward is written in sorted space: the gradient of ``ys`` is a
    gather of ``rows`` rows of the ``(tokens, d)`` gradient times the
    row's weight, and the weights' gradient the row-wise product of the
    same gathered rows with ``ys``, carried to its pair by a gather of
    scalars."""
    return _sum_pair_rows(ys, row, live, weights)


def _combine_fwd(ys, weights, taken, row, live):
    return combine(ys, weights, taken, row, live), (ys, weights, taken, row,
                                                     live)


def _combine_bwd(res, d_out):
    ys, weights, taken, row, live = res
    valid = jnp.arange(ys.shape[0], dtype=jnp.int32) < live
    g = jnp.take(d_out, taken // row.shape[1], axis=0, mode="clip")
    w_row = jnp.take(weights.reshape(-1), taken, mode="clip")
    d_ys = jnp.where(valid[:, None], g * w_row[:, None], 0.0)
    d_w_row = jnp.where(valid, jnp.sum(ys * g, axis=1), 0.0)
    d_weights = jnp.where(_live(row, live),
                          jnp.take(d_w_row, row, mode="clip"), 0.0)
    return d_ys, d_weights.astype(weights.dtype), None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


# -- the layer -----------------------------------------------------------------
def route(xn, wr, top_k: int, norm_topk_prob: bool):
    """Router of the published width over ``xn (N, d)``: float32 at
    ``highest`` (a flipped last expert is a different function, not
    rounding), softmax over all experts, the ``top_k`` largest, their
    weights normalised over all of them.  -> ``(weights, experts)``,
    each ``(N, top_k)``."""
    logits = jnp.dot(xn.astype(jnp.float32), wr.astype(jnp.float32),
                     precision=HIGHEST)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_e


def piece_rows(pairs: int, expected: float) -> tuple:
    """``(first, rest)``: the lengths in which ``pairs`` sorted pairs are
    taken when a balanced router sends the share ``expected`` of them
    here.  The first piece is one and a half times that share, rounded up
    to whole row tiles; the rest is ONE later piece (0: the first holds
    every pair)."""
    unit = TILE_M if pairs % TILE_M == 0 else 8
    first = max(1, -(-int(1.5 * expected * pairs) // unit)) * unit
    if pairs % 8 or first >= pairs:
        return pairs, 0
    return first, pairs - first


def _piece(lo: int, rows: int, sort):
    """The sorted pairs ``lo .. lo + rows`` of ``sort = (order, place,
    counts, ends)`` as a function of the operands, rematerialised by
    itself in a backward pass."""
    order, place, counts, ends = sort

    @jax.checkpoint
    def run(xc, weights, wgc, wuc, wdc):
        taken = jax.lax.dynamic_slice_in_dim(order, lo, rows)
        sizes = (jnp.clip(ends, lo, lo + rows)
                 - jnp.clip(ends - counts, lo, lo + rows))
        row, live = place - lo, jnp.sum(sizes)
        with jax.named_scope("experts"):
            xs = dispatch_rows(xc, taken, row, live)
            hidden = (jax.nn.silu(grouped_matmul(xs, wgc, sizes))
                      * grouped_matmul(xs, wuc, sizes)).astype(xc.dtype)
            ys = grouped_matmul(hidden, wdc, sizes)
        with jax.named_scope("combine"):
            return combine(ys, weights, taken, row, live)
    return run


def _reaches_later(lengths, sort):
    """Whether the held pairs reach beyond the first piece."""
    return sort[3][-1] > lengths[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def sum_of_pieces(lengths, operands, sort):
    """The first piece of the sorted pairs plus, where the held pairs reach
    into it, the later one (``lengths = piece_rows(...)``, both nonzero).
    The backward stands under the forward's condition and adds the later
    piece's gradients into the first's: a piece that did not run makes no
    gradient, not one of zeros."""
    first_rows, rest_rows = lengths
    return jax.lax.cond(
        _reaches_later(lengths, sort),
        lambda out: out + _piece(first_rows, rest_rows, sort)(*operands),
        lambda out: out, _piece(0, first_rows, sort)(*operands))


def _sum_fwd(lengths, operands, sort):
    return sum_of_pieces(lengths, operands, sort), (operands, sort)


def _sum_bwd(lengths, res, d_out):
    operands, sort = res
    first_rows, rest_rows = lengths

    def grads(lo, rows):
        return jax.vjp(_piece(lo, rows, sort), *operands)[1](d_out)
    got = jax.lax.cond(
        _reaches_later(lengths, sort),
        lambda got: jax.tree.map(jnp.add, got, grads(first_rows, rest_rows)),
        lambda got: got, grads(0, first_rows))
    # the weights' gradients leave the conditional's tuple by themselves:
    # beside the rows' gradients, which the backward pass goes on with at
    # once, XLA's scheduler holds them all to the end of the step, a
    # layer's three leaves of gradients each (PERF.md section 6, PR 37)
    return got[:2] + jax.lax.optimization_barrier(got[2:]), None


sum_of_pieces.defvjp(_sum_fwd, _sum_bwd)


def held_expert_sum(xn, weights, experts, wg, wu, wd, first: int, cdt,
                    expected: float = 1.0):
    """``sum_k [e_k held] w_k * (silu(x Wg_e) * x Wu_e) Wd_e`` over the
    rows of ``xn (N, d)``; ``wg``/``wu (E_held, d, f)``, ``wd (E_held, f,
    d)`` are experts ``first .. first + E_held``.  -> ``(sum (N, d)
    float32, counts (E_held,) int32, rows moved int32)``: the pairs on each
    held expert, and the rows of the pieces that ran.

    ``expected``: the share of all pairs a balanced router sends here
    (experts held over experts).  The ``N * top_k`` pairs are sorted by held
    expert and taken in two pieces (``piece_rows``): the first, one
    and a half times that share (12,288 of a chunk's 32,768 for a quarter),
    holds every held pair in the usual case; the later one takes all the
    rest and is skipped unless the held pairs reach into it, so none is
    dropped whatever the spread; its backward is skipped with it
    (``sum_of_pieces``: the first piece's gradients go on as they are, no
    zeros are made or added).  What moves, a piece: its rows
    of ``xn`` gathered in the operand dtype; the products' float32 rows
    gathered back and summed ``top_k`` a token with their weights, a pair
    that is not live in the piece reading nothing; backward the same two
    movements mirrored (``combine``, ``dispatch_rows``).  No row is zeroed:
    nothing reads the rows no kernel wrote.  Each piece is rematerialised
    by itself in the backward pass."""
    n, top_k = experts.shape
    pairs = n * top_k
    e_held = wg.shape[0]
    local = experts - first
    held = (local >= 0) & (local < e_held)
    key = jnp.where(held, local, e_held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # every pair's place among the sorted: a second sort inverts the first
    # (19 us on a v5e against 150 us for a scatter of ``arange``: PERF.md)
    place = jnp.argsort(order).astype(jnp.int32).reshape(n, top_k)
    counts = jnp.sum(key[:, None] == jnp.arange(e_held, dtype=key.dtype),
                     axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    xc, wgc, wuc, wdc = (a.astype(cdt) for a in (xn, wg, wu, wd))
    operands, sort = (xc, weights, wgc, wuc, wdc), (order, place, counts, ends)
    lengths = first_rows, rest_rows = piece_rows(pairs, expected)
    moved = jnp.asarray(first_rows, jnp.int32)
    if not rest_rows:
        return _piece(0, first_rows, sort)(*operands), counts, moved
    out = sum_of_pieces(lengths, operands, sort)
    moved = moved + jnp.where(_reaches_later(lengths, sort), rest_rows,
                              0).astype(jnp.int32)
    return out, counts, moved


def shared_expert(xn, sg, su, sd, cdt):
    """``(silu(x Sg) * x Su) Sd`` for every row of ``xn (N, d)``: the
    columns held of an expert every token takes, ungated: ``sg``/``su (d,
    f_held)``, ``sd (f_held, d)``."""
    xc = xn.astype(cdt)

    def dot(a, w):
        return jnp.dot(a, w.astype(cdt), preferred_element_type=jnp.float32)
    return dot((jax.nn.silu(dot(xc, sg)) * dot(xc, su)).astype(cdt), sd)


def moe_block_fwd(leaves, x, cfg: dict, cdt=jnp.float32):
    """``moe_block``: ``x + scale * (MoE(n) + Shared(n))``, ``n =
    RMSNorm(x; g2)``, for the experts held.  Leaves ``g2 (d,)``, ``wr (d,
    experts)``, ``wg``, ``wu (E_held, d, f)``, ``wd (E_held, f, d)`` and,
    where the model has a shared expert (``cfg["shared"]``), the columns
    held of it: ``sg``, ``su (d, f_held)``, ``sd (f_held, d)``; ``cfg``:
    ``experts``, ``experts_held`` as ``(first, count)``, ``top_k``,
    ``norm_topk_prob``, ``eps``, ``scale`` (the block's scale on what it
    adds to the stream; None: 1).
    -> ``(y, counters)``; the counters are this layer's, this call's:
    ``moe_assignments``, ``moe_assignments_held``, ``moe_expert_load_max``,
    ``moe_rows_moved`` (rows of the sorted pieces that ran, all chunks: over
    ``moe_assignments_held`` it says how far the movement is from the pairs
    held, and whether a later piece ran; rows that pad a short last
    minibatch are routed and counted too)."""
    g2, wr, wg, wu, wd, *shared = leaves
    b, t, d = x.shape
    first, count = cfg["experts_held"]
    if (wr.shape[1] != cfg["experts"] or wg.shape[0] != count
            or bool(shared) != bool(cfg.get("shared"))):
        raise ValueError(f"moe_block of {cfg['experts']} experts holding "
                         f"{count}: router {wr.shape}, experts {wg.shape}, "
                         f"{len(shared)} leaves of a shared expert")
    xn = rms_norm(x, g2, cfg["eps"]).reshape(b * t, d)
    with jax.named_scope("route"):
        weights, experts = route(xn, wr, cfg["top_k"],
                                 cfg["norm_topk_prob"])
    expected = count / cfg["experts"]
    n, chunk = b * t, CHUNK_TOKENS
    if n > chunk and n % chunk == 0:
        def some(args):
            return held_expert_sum(*args, wg, wu, wd, first, cdt, expected)
        out, counts, moved = jax.lax.map(jax.checkpoint(some), tuple(
            a.reshape(n // chunk, chunk, a.shape[-1])
            for a in (xn, weights, experts)))
        out, counts, moved = (out.reshape(n, d), jnp.sum(counts, axis=0),
                              jnp.sum(moved))
    else:
        out, counts, moved = held_expert_sum(xn, weights, experts, wg, wu,
                                             wd, first, cdt, expected)
    counters = {
        "moe_assignments": jnp.asarray(b * t * cfg["top_k"], jnp.int32),
        "moe_assignments_held": jnp.sum(counts),
        "moe_expert_load_max": jnp.max(counts),
        "moe_rows_moved": moved}
    if shared:
        with jax.named_scope("shared_expert"):
            out = out + shared_expert(xn, *shared, cdt)
    if cfg.get("scale") is not None:
        out = out * cfg["scale"]
    return x + out.reshape(b, t, d), counters


def mlp_block_fwd(leaves, x, cfg: dict, cdt=jnp.float32):
    """``mlp_block``: the dense gated feed-forward, ``x + scale *
    MLP(RMSNorm(x; g2))`` or, with ``cfg["norm"]`` "post", ``x + scale *
    RMSNorm(MLP(x); g2)``; ``MLP(n) = (silu(n Wg) * n Wu) Wd``, the
    shared expert's form over the whole width.  Leaves ``g2 (d,)``, ``wg``,
    ``wu (d, f)``, ``wd (f, d)``; ``cfg``: ``eps``, ``norm``, ``scale``."""
    g2, wg, wu, wd = leaves
    b, t, d = x.shape
    with jax.named_scope("mlp_block"):
        return residual_block(x, g2, cfg, lambda xn: shared_expert(
            xn.reshape(b * t, d), wg, wu, wd, cdt).reshape(b, t, d)), {}
