"""The sparse expert layer of a decoder, for the experts one chip holds.

``moe_block`` is ``h + MoE(RMSNorm(h; g2))`` with a router over ALL the
model's experts and the products of the experts HELD here: the layer is
told ``experts`` (the router's width), ``experts_held = (first, count)``
and ``top_k``; it routes every token over all experts, normalises the
chosen weights over all ``top_k`` of them, and adds the terms of its own
experts.  What the absent experts would have added is left out (the
expert-parallel exchange that would bring it is ROADMAP Reach 4); no
code stands in for the absent chips.

The work follows the assignments held, whatever their spread: the
token-expert pairs are sorted by held expert (the pairs of absent
experts behind them), gathered once, multiplied in ONE grouped product a
projection over the rows that are held, and gathered back.  No token is
dropped and no expert has a capacity: shapes are static, so the sorted
pairs are taken in pieces of at least one and a half times a balanced
router's share (a long sequence goes ``CHUNK_TOKENS`` at a time); the
first piece holds every held pair in the usual case, a later one runs
only when the held pairs reach into it, and the grouped product visits
only the row tiles that hold pairs.  Two tiers, dispatched as the repo's other ops:

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
from .attention import rms_norm

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


def _valid_rows(rows: int, sizes):
    return (jnp.arange(rows, dtype=jnp.int32) < jnp.sum(sizes))[:, None]


@jax.custom_vjp
def pallas_grouped_matmul(lhs, rhs, sizes):
    """``lhs[rows of group e] @ rhs[e]`` for every group: ``lhs (M, K)``,
    ``rhs (E, K, N)``, ``sizes (E,)`` int32, rows sorted by group; float32
    out.  The kernels never visit the rows beyond ``sum(sizes)``: they
    read back zero here, and so do their input gradients."""
    return jnp.where(_valid_rows(lhs.shape[0], sizes),
                     _mosaic_gmm(lhs, rhs, sizes), 0.0)


def _pgm_fwd(lhs, rhs, sizes):
    return pallas_grouped_matmul(lhs, rhs, sizes), (lhs, rhs, sizes)


def _pgm_bwd(res, g):
    backend = _megablox()
    lhs, rhs, sizes = res
    g = g.astype(lhs.dtype)         # rows beyond the groups: never read
    d_lhs = jnp.where(_valid_rows(lhs.shape[0], sizes),
                      _mosaic_gmm(g, rhs, sizes, transpose_rhs=True), 0.0)
    d_rhs = backend.tgmm(
        lhs.swapaxes(0, 1), g, sizes, jnp.float32,
        (min(TILE_M, lhs.shape[0]), _tile(lhs.shape[1]), _tile(g.shape[1])),
        interpret=tuning.interpret_mode())
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


pallas_grouped_matmul.defvjp(_pgm_fwd, _pgm_bwd)


def xla_grouped_matmul(lhs, rhs, sizes):
    """The same function by ``jax.lax.ragged_dot`` (rows beyond the
    groups are zero there too)."""
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
# (``taken``), and ``inverse`` gives every pair its row in the piece, or
# ``rows`` where the pair is not in it.
def _from_sorted(a, inverse):
    """Row ``inverse[p]`` of ``a (rows, d)`` for every pair ``p``; zero for
    a pair that was not taken (``inverse[p] >= rows``)."""
    padded = jnp.concatenate([a, jnp.zeros((1, a.shape[1]), a.dtype)])
    return jnp.take(padded, jnp.minimum(inverse, a.shape[0]), axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch_rows(x, taken, inverse, top_k):
    """``x[taken // top_k]``: row ``i`` is the token of the ``i``-th sorted
    pair (``taken = order[:rows]``).  The gradient is a gather and a sum
    over ``top_k`` too, not a scatter."""
    return jnp.take(x, taken // top_k, axis=0)


def _dispatch_fwd(x, taken, inverse, top_k):
    return jnp.take(x, taken // top_k, axis=0), inverse


def _dispatch_bwd(top_k, inverse, g):
    back = _from_sorted(g, inverse)
    return back.reshape(-1, top_k, g.shape[-1]).sum(axis=1), None, None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def collect_rows(ys, taken, inverse):
    """Every pair's row of ``ys (rows, d)``, zero for a pair not taken:
    ``(tokens * top_k, d)``.  The gradient is ``g[taken]``."""
    return _from_sorted(ys, inverse)


def _collect_fwd(ys, taken, inverse):
    return _from_sorted(ys, inverse), taken


def _collect_bwd(taken, g):
    return jnp.take(g, taken, axis=0), None, None


collect_rows.defvjp(_collect_fwd, _collect_bwd)


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


def held_expert_sum(xn, weights, experts, wg, wu, wd, first: int, cdt,
                    expected: float = 1.0):
    """``sum_k [e_k held] w_k * (silu(x Wg_e) * x Wu_e) Wd_e`` over the
    rows of ``xn (N, d)``; ``wg``/``wu (E_held, d, f)``, ``wd (E_held, f,
    d)`` are experts ``first .. first + E_held``.  -> ``(sum (N, d)
    float32, counts (E_held,) int32)``: the pairs on each held expert.

    ``expected``: the share of all pairs a balanced router sends here
    (experts held over experts).  The ``N * top_k`` sorted pairs are taken
    in pieces of at least one and a half times that share: the first
    piece holds every held pair in the usual case, and a later piece is
    skipped (``lax.cond``) unless the held pairs reach into it, so none
    is dropped whatever the spread.  Each piece is rematerialised by
    itself in the backward pass."""
    n, top_k = experts.shape
    e_held = wg.shape[0]
    local = experts - first
    held = (local >= 0) & (local < e_held)
    key = jnp.where(held, local, e_held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(e_held, dtype=key.dtype),
                     axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    xc, wgc, wuc, wdc = (a.astype(cdt) for a in (xn, wg, wu, wd))
    pieces = max(1, int(1.0 / (1.5 * expected)))
    while (n * top_k) % pieces or (n * top_k // pieces) % 8:
        pieces -= 1
    rows = n * top_k // pieces

    @jax.checkpoint
    def piece(lo, xc, weights, wgc, wuc, wdc):
        """The sorted pairs ``lo .. lo + rows``."""
        taken = jax.lax.dynamic_slice_in_dim(order, lo, rows)
        sizes = (jnp.clip(ends, lo, lo + rows)
                 - jnp.clip(ends - counts, lo, lo + rows))
        # a pair's row in this piece (``rows``: it is in another)
        local_row = jnp.where((inverse >= lo) & (inverse < lo + rows),
                              inverse - lo, rows)
        with jax.named_scope("experts"):
            xs = dispatch_rows(xc, taken, local_row, top_k)
            hidden = (jax.nn.silu(grouped_matmul(xs, wgc, sizes))
                      * grouped_matmul(xs, wuc, sizes)).astype(cdt)
            ys = grouped_matmul(hidden, wdc, sizes)
        with jax.named_scope("combine"):
            back = collect_rows(ys, taken, local_row)
            return jnp.sum(back.reshape(n, top_k, -1) * weights[..., None],
                           axis=1)
    out = piece(0, xc, weights, wgc, wuc, wdc)
    for i in range(1, pieces):
        out = out + jax.lax.cond(
            ends[-1] > i * rows, piece,
            lambda lo, xc, *_: jnp.zeros((n, xc.shape[1]), jnp.float32),
            i * rows, xc, weights, wgc, wuc, wdc)
    return out, counts


def moe_block_fwd(leaves, x, cfg: dict, cdt=jnp.float32):
    """``moe_block``: ``x + MoE(RMSNorm(x; g2))`` for the experts held.
    Leaves ``g2 (d,)``, ``wr (d, experts)``, ``wg``, ``wu (E_held, d, f)``,
    ``wd (E_held, f, d)``; ``cfg``: ``experts``, ``experts_held`` as
    ``(first, count)``, ``top_k``, ``norm_topk_prob``, ``eps``.
    -> ``(y, counters)``; the counters are this layer's, this call's:
    ``moe_assignments``, ``moe_assignments_held``, ``moe_expert_load_max``
    (rows that pad a short last minibatch are routed and counted too)."""
    g2, wr, wg, wu, wd = leaves
    b, t, d = x.shape
    first, count = cfg["experts_held"]
    if wr.shape[1] != cfg["experts"] or wg.shape[0] != count:
        raise ValueError(f"moe_block of {cfg['experts']} experts holding "
                         f"{count}: router {wr.shape}, experts {wg.shape}")
    xn = rms_norm(x, g2, cfg["eps"]).reshape(b * t, d)
    with jax.named_scope("route"):
        weights, experts = route(xn, wr, cfg["top_k"],
                                 cfg["norm_topk_prob"])
    expected = count / cfg["experts"]
    n, chunk = b * t, CHUNK_TOKENS
    if n > chunk and n % chunk == 0:
        def some(args):
            return held_expert_sum(*args, wg, wu, wd, first, cdt, expected)
        out, counts = jax.lax.map(jax.checkpoint(some), tuple(
            a.reshape(n // chunk, chunk, a.shape[-1])
            for a in (xn, weights, experts)))
        out, counts = out.reshape(n, d), jnp.sum(counts, axis=0)
    else:
        out, counts = held_expert_sum(xn, weights, experts, wg, wu, wd,
                                      first, cdt, expected)
    counters = {
        "moe_assignments": jnp.asarray(b * t * cfg["top_k"], jnp.int32),
        "moe_assignments_held": jnp.sum(counts),
        "moe_expert_load_max": jnp.max(counts)}
    return x + out.reshape(b, t, d), counters


#: how a layer's counters fold into a step's, and a step's into an
#: epoch's
COUNTER_FOLDS = {"moe_assignments": "sum", "moe_assignments_held": "sum",
                 "moe_expert_load_max": "max", "tokens": "sum"}
