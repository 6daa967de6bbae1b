"""The four longest operations of ``alexnet-b128-train`` and pool5's two
kernels, compiled for a described v5e at the cell's shapes: the merged
LRN+pool pair's forward and backward over the column-parity halves the
split convs hand it (128x55x55x96 and 128x27x27x256), and pool5's
select and scatter (128x13x13x256, 3x3/2, the tap stack).  The TPU's own
Mosaic and XLA compilers run here, with no chip, and refuse what the
chip would refuse (an access Mosaic cannot lower, a block over the
scoped VMEM): what ``bench.py --kernels`` showed only on a chip.
Nothing runs, so this says nothing of results or times.

The topology is described inside a fixture, never at import: only the
worker that is given this file loads the TPU's library."""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from znicz_tpu.ops import lrn_pool, pooling, tuning

#: the pairs of benchmark/configs/alexnet.json at minibatch 128:
#: (B, H, W, C) of the pair's input, as the conv before it emits it
PAIRS = {"L01": (128, 55, 55, 96), "L04": (128, 27, 27, 256)}
LRN = (5, 1e-4, 0.75, 2.0)                 # n, alpha, beta, k
POOL = ((3, 3), (2, 2), 0)                 # ksize, stride, padding
POOL5 = (128, 13, 13, 256)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back: keep it out
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """The dispatch a TPU process takes: the real kernels, not the
    interpreter."""
    monkeypatch.setattr(tuning, "on_tpu", lambda: True)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _halves(shape, chip):
    """The column-parity halves of a ``shape`` array: even columns, odd
    columns."""
    b, h, w, c = shape
    return tuple(jax.ShapeDtypeStruct((b, h, cols, c), jnp.float32,
                                      sharding=chip)
                 for cols in (-(-w // 2), w // 2))


@pytest.mark.parametrize("layer", sorted(PAIRS))
def test_pair_forward_compiles_for_a_v5e(one_chip, mosaic, layer):
    text = _compiled_text(
        lambda xe, xo: lrn_pool.pallas_lrn_maxpool_split(
            xe, xo, *LRN, *POOL), *_halves(PAIRS[layer], one_chip))
    assert "tpu_custom_call" in text
    assert "pallas_lrn_maxpool_split" in text


@pytest.mark.parametrize("layer", sorted(PAIRS))
def test_pair_backward_compiles_for_a_v5e(one_chip, mosaic, layer):
    """As the step calls it: the conv's strict ReLU folded in, the
    halves handed back un-interleaved."""
    shape = PAIRS[layer]
    pooled = pooling.pool_out_shape(shape, *POOL)
    err = jax.ShapeDtypeStruct(pooled, jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct(pooled, jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda e, i, xe, xo: lrn_pool.pallas_gd_lrn_maxpool_split(
            e, i, xe, xo, *LRN, *POOL, fold_act="strict_relu",
            return_split=True), err, idx, *_halves(shape, one_chip))
    assert "tpu_custom_call" in text
    assert "pallas_gd_lrn_maxpool_split" in text


def test_pool5_select_compiles_for_a_v5e(one_chip, mosaic):
    assert not pooling.windowed(POOL5, *POOL)        # the tap stack
    x = jax.ShapeDtypeStruct(POOL5, jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda x: pooling.max_pooling(x, *POOL), x)
    assert "tpu_custom_call" in text and "pallas_pool_select" in text


def test_pool5_scatter_compiles_for_a_v5e(one_chip, mosaic):
    pooled = pooling.pool_out_shape(POOL5, *POOL)
    err = jax.ShapeDtypeStruct(pooled, jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct(pooled, jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda e, i: pooling.gd_max_pooling(e, i, POOL5, *POOL), err, idx)
    assert "tpu_custom_call" in text and "pallas_pool_scatter" in text
