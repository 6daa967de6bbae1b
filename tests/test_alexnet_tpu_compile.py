"""The longest Pallas operations of ``alexnet-b128-train`` and pool5's
two kernels, compiled for a described v5e at the cell's shapes: the
merged LRN+pool pair's forward and backward on the convolutions' own
layout (the window kernels the cell runs since PR 33, 128x55x55x96 and
128x27x27x256), the same over the column-parity halves (what a batch
that is no multiple of 8 still runs), pool5's select and scatter
(128x13x13x256, 3x3/2, the tap stack), and a conv -> pair -> conv ->
pair step whose text must show no layout copy beside the window
kernels.  The TPU's own Mosaic and XLA compilers run here, with no
chip, and refuse what the chip would refuse (an access Mosaic cannot
lower, a block over the scoped VMEM): what ``bench.py --kernels`` showed
only on a chip.  Nothing runs, so this says nothing of results or
times.

The topology is described inside a fixture, never at import: only the
worker that is given this file loads the TPU's library."""

import os
import re

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


@pytest.mark.parametrize("layer", sorted(PAIRS))
def test_window_forward_compiles_for_a_v5e(one_chip, mosaic, layer):
    shape = PAIRS[layer]
    assert lrn_pool.windowed(shape, *POOL)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda x: lrn_pool.lrn_maxpool(x, *LRN, *POOL), x)
    assert "tpu_custom_call" in text
    assert "pallas_lrn_maxpool_window" in text
    assert "pallas_lrn_maxpool_split" not in text


@pytest.mark.parametrize("layer", sorted(PAIRS))
def test_window_backward_compiles_for_a_v5e(one_chip, mosaic, layer):
    """As the step calls it: the conv's strict ReLU folded in."""
    shape = PAIRS[layer]
    pooled = pooling.pool_out_shape(shape, *POOL)
    err = jax.ShapeDtypeStruct(pooled, jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct(pooled, jnp.int32, sharding=one_chip)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda e, i, x: lrn_pool.gd_lrn_maxpool(
            e, i, x, *LRN, *POOL, fold_act="strict_relu"), err, idx, x)
    assert "tpu_custom_call" in text
    assert "pallas_gd_lrn_maxpool_window" in text
    assert "pallas_gd_lrn_maxpool_split" not in text


def test_no_layout_copy_stands_beside_the_window_kernels(one_chip, mosaic):
    """The window kernels work on (H, W, B, C), the layout XLA's TPU
    convolutions emit and take: in a compiled conv -> pair -> conv ->
    pair -> conv step (AlexNet's head at its widths, the convs whole as
    ``extract_model`` leaves them at this batch) the kernels must take
    the convs' own fusions, with no float32 copy, transpose or
    ``dynamic-update-slice`` of a pair's input, output or gradient —
    PR 32 measured what such passes cost when XLA makes them (13 ms a
    step, PERF.md section 6)."""
    from znicz_tpu.parallel import fused
    hyp = (0.01, 0.0005, 0.0, 0.9)

    def conv(stride, padding, folded=True):
        return fused.LayerSpec("conv", "strict_relu", True, hyp, hyp, tuple(
            sorted({"stride": stride, "padding": padding,
                    **({"act_folded": True} if folded else {})}.items())))
    pair = fused.LayerSpec("lrn_pool", "linear", False, hyp, hyp, tuple(
        sorted(dict(zip(("n", "alpha", "beta", "k"), LRN),
                    ksize=POOL[0], stride=POOL[1], padding=(0, 0),
                    use_abs=False, fold_act="strict_relu").items())))
    spec = fused.ModelSpec(
        (conv((4, 4), (0, 0)), pair, conv((1, 1), (2, 2)), pair,
         conv((1, 1), (1, 1), folded=False),
         fused.LayerSpec("fc", "linear", True, hyp, hyp)), "softmax")
    b = 128

    def shaped(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = [(shaped(11, 11, 3, 96), shaped(96)), (None, None),
              (shaped(5, 5, 96, 256), shaped(256)), (None, None),
              (shaped(3, 3, 256, 32), shaped(32)),
              (shaped(13 * 13 * 32, 16), shaped(16))]

    def steps(params, vels, data, labels):
        def body(carry, batch):
            p, v, _ = fused.train_minibatch(
                spec, *carry, batch[0].astype(jnp.float32), batch[1])
            return (p, v), None
        return jax.lax.scan(body, (params, vels), (data, labels))[0]
    text = _compiled_text(steps, params, params,
                          shaped(2, b, 227, 227, 3, dtype=jnp.bfloat16),
                          shaped(2, b, dtype=jnp.int32))
    assert text.count("pallas_lrn_maxpool_window") >= 2
    assert text.count("pallas_gd_lrn_maxpool_window") >= 2
    assert "maxpool_split" not in text
    # the pairs' inputs, outputs and gradients, in either dim order
    paired = {f"f32[{dims}]" for h, c in ((55, 96), (27, 96), (27, 256),
                                          (13, 256))
              for dims in (f"{b},{h},{h},{c}", f"{h},{h},{b},{c}")}
    for op in ("copy", "transpose", "dynamic-update-slice"):
        moved = re.findall(r"= (f32\[[\d,]*\])\{[^}]*\} " + op + r"\(",
                           text)
        assert not paired & set(moved), (op, sorted(paired & set(moved)))
    assert re.findall(r"= (f32\[[\d,]*\])\{[^}]*\} copy\(", text), \
        "no copy at all: the pattern no longer matches"


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
