"""Sorted segment sums as a Pallas kernel: ``zeros((rows, d)).at[ids].add(
src)`` where ``ids`` come sorted and ``src``'s rows in the same order.  A
grid step zeroes a block of output rows in VMEM, adds the rows of its ids
(brought from HBM a chunk at a time) in the order given, and Pallas writes
the block once, where XLA's scatter-add reads and writes a whole output in
HBM.  A row is a ``(1, d)`` load-add-store into the block: about 4 cycles
a 128-lane register and 25 a row on a v5e (PERF.md section 6, PR 44).  Two
users, each with a ``pallas_call`` name of its own in a trace: the
embedding's table gradient (``attention.embed_grad``,
``pallas_embed_grad``) and the expert layer's way back from sorted space to
token space (``moe.pair_sum``, ``pallas_pair_sum``)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning

#: bytes of VMEM a block of output rows takes, double-buffered
#: (:func:`block_rows`)
VMEM = 16 << 20
#: sorted rows one DMA brings into VMEM
CHUNK = 64


def block_rows(rows: int, width: int) -> int:
    """Output rows a grid step sums: a power of two whose float32 block
    fits ``VMEM`` double-buffered, at least 8, no more than ``rows``
    rounded up to 8."""
    r = max(8, VMEM // (2 * 4 * width))
    return min(1 << (r.bit_length() - 1), tuning.round_up(rows, 8))


def _kernel(starts_ref, ids_ref, src_hbm, out_ref, buf, sem, *, chunk: int,
            n: int):
    """Block ``b`` of output rows: zeroed in VMEM, then each of its ids'
    rows (sorted ``src``, ``chunk`` at a time from HBM) added at its id, in
    the order given; Pallas writes the block once.  A chunk starts on a
    multiple of 8 rows (HBM's tile) and inside the ``n`` rows; the first
    one is on its way while the block is zeroed."""
    b = pl.program_id(0)
    r = out_ref.shape[0]
    first, end = starts_ref[b], starts_ref[b + 1]

    def fetch(t0):
        base = jnp.minimum(t0 // 8 * 8, n - chunk)
        return base, pltpu.make_async_copy(
            src_hbm.at[pl.ds(pl.multiple_of(base, 8), chunk)], buf, sem)

    @pl.when(first < end)
    def _():
        fetch(first)[1].start()
    out_ref[...] = jnp.zeros_like(out_ref)

    def one_chunk(t0):
        base, copy = fetch(t0)
        copy.wait()
        stop = jnp.minimum(end, base + chunk)

        def add(t, c):
            at = ids_ref[t] - b * r
            out_ref[pl.ds(at, 1), :] += buf[pl.ds(t - base, 1), :]
            return c
        jax.lax.fori_loop(t0, stop, add, 0)

        @pl.when(stop < end)
        def _():
            fetch(stop)[1].start()
        return stop
    jax.lax.while_loop(lambda t: t < end, one_chunk, first)


# jitted so that the kernel is traced and lowered once a process and shape,
# not at every call (ten expert layers, each piece's forward, its
# rematerialisation and its backward, in every program an epoch builds)
@functools.partial(jax.jit, static_argnames=("rows", "name"))
def sorted_segment_sum(ids, src, rows: int, name: str):
    """``zeros((rows, d)).at[ids].add(src)`` in float32: ``ids (n,)``
    int32 sorted ascending, ``n`` a multiple of 8; ``src (n, d)`` float32,
    row ``i`` of id ``ids[i]``.  An id at or beyond ``rows`` is dropped: it
    sorts after every block's, and its row is never added.  A block's first
    id is found by ``searchsorted`` at the block starts; each grid step adds
    its block's rows in float32 in the order given and writes the block
    once; a block with no id is written as zeros.  ``name``: the
    ``pallas_call``'s name in a trace."""
    n, width = src.shape
    r = block_rows(rows, width)
    blocks = pl.cdiv(rows, r)
    starts = jnp.searchsorted(ids, jnp.minimum(
        jnp.arange(blocks + 1, dtype=jnp.int32) * r, rows)).astype(jnp.int32)
    chunk = min(CHUNK, n)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((r, width), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((chunk, width), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((rows, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=2 * VMEM),
        name=name,
        interpret=tuning.interpret_mode(),
    )(starts, ids, src)

