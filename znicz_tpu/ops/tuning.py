"""Kernel dispatch + tile-size selection.

Replaces the reference's device-info database of tuned per-(device, dtype,
op) BLOCK_SIZEs (SURVEY.md §2.1 Backends row): on TPU the MXU/VPU geometry
is fixed (128×128 MXU, 8×128 VPU lanes), so tiles are derived from dtype
min-tile rules instead of an empirical database.
"""

from __future__ import annotations

import contextlib
import contextvars
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

#: Force interpret-mode Pallas (CPU testing of kernel logic).
_INTERPRET = os.environ.get("ZNICZ_TPU_PALLAS_INTERPRET", "0") == "1"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    """Pallas kernels run on real TPU, or anywhere under interpret mode.

    The ZNICZ_TPU_NO_PALLAS kill-switch is re-read per call, not at
    import."""
    if os.environ.get("ZNICZ_TPU_NO_PALLAS", "0") == "1":
        return False
    return on_tpu() or _INTERPRET


def interpret_mode() -> bool:
    return _INTERPRET and not on_tpu()


def device_memory_bytes() -> int | None:
    """Bytes of memory the default device gives a program, or None where
    it does not say (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def kernel_tier() -> str:
    """The tier the ``ops`` dispatchers take in this process, as the
    trainer states it at start: ``pallas`` (Mosaic on a TPU),
    ``pallas-interpret`` (the Pallas interpreter, off-TPU) or ``xla``."""
    if not use_pallas():
        return "xla"
    return "pallas-interpret" if interpret_mode() else "pallas"


#: the mesh the jit being traced is laid out over (see kernel_mesh)
_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "znicz_tpu_kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Trace-time scope naming the ``("data", "model")`` mesh of the
    enclosing jit.  Mosaic cannot partition a kernel automatically, so
    a Pallas call traced under a multi-device mesh must sit in a
    ``shard_map``; the dispatchers cannot see a mesh from their traced
    operands, so the trainer that owns the mesh names it here and
    :func:`batch_sharded` reads it."""
    token = _KERNEL_MESH.set(mesh)
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def batch_sharded(fn, *arrays):
    """``fn(*arrays)`` for a Pallas-tier call whose operands and
    results all lead with the batch dim.  Under :func:`kernel_mesh` it
    runs as a ``shard_map`` over every mesh axis — batch rows split
    over ``data``, replicated over ``model`` — so each device lowers
    the kernel on its own rows (the kernels are per-sample: no
    collective is needed).  ``fn`` must take its shapes from its
    operands: inside the map it sees the per-device batch."""
    mesh = _KERNEL_MESH.get()
    if mesh is None:
        return fn(*arrays)
    rows = P("data")
    return jax.shard_map(fn, mesh=mesh, in_specs=rows, out_specs=rows,
                         check_vma=False)(*arrays)


def device_rows(b: int) -> int:
    """Rows of a batch of ``b`` on one device inside
    :func:`batch_sharded`, under the mesh being traced."""
    mesh = _KERNEL_MESH.get()
    return b if mesh is None else b // mesh.shape["data"]


# dtype → (sublane, lane) minimum tile (pallas_guide.md tiling table)
_MIN_TILE = {
    jnp.float32: (8, 128),
    jnp.bfloat16: (16, 128),
    jnp.int8: (32, 128),
}


def min_tile(dtype) -> tuple[int, int]:
    return _MIN_TILE.get(jnp.dtype(dtype).type, (8, 128))


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


#: Per-operand VMEM budget for elementwise block sizing (bytes).  Measured
#: on v5e (2026-07-30 A/B, AlexNet batch 256): 256-row blocks (128 KiB)
#: beat 2048-row blocks by ~14% — the short-block pipeline hides HBM
#: latency better than big transfers, so the budget floor is the sweet
#: spot.
_VMEM_BUDGET = 768 * 1024


def block_rows(n_operands: int, lanes: int = 128, dtype_bytes: int = 4,
               rows: int | None = None) -> int:
    """Rows per elementwise block for an (rows, lanes) layout: all
    operands' blocks fit the VMEM budget double-buffered, floored at
    the 256-row minimum that measured fastest (see _VMEM_BUDGET)."""
    per_buf = _VMEM_BUDGET // max(1, n_operands * 2)
    br = max(256, per_buf // max(1, lanes * dtype_bytes))
    br = 1 << (br.bit_length() - 1)          # floor to a power of two
    if rows is not None:
        br = min(br, round_up(rows, 8))
    return br
