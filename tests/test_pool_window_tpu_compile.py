"""The windowed pool kernels compiled for a described v5e, at VGG-A's
five pool shapes: the TPU's own Mosaic and XLA compilers run here, with
no chip, and refuse what the chip would refuse (an access Mosaic cannot
lower, a block over the scoped VMEM).  Nothing runs, so this says
nothing of results or times.

The topology is described inside a fixture, never at import: only the
worker that is given this file loads the TPU's library."""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from znicz_tpu.ops import elementwise, tuning

#: (B, H, W, C) of `vgg11`'s pools at minibatch 64 (benchmark/configs)
VGG_POOLS = {"L01": (64, 224, 224, 64), "L03": (64, 112, 112, 128),
             "L06": (64, 56, 56, 256), "L09": (64, 28, 28, 512),
             "L12": (64, 14, 14, 512)}


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


@pytest.mark.parametrize("layer", sorted(VGG_POOLS))
def test_forward_compiles_for_a_v5e(one_chip, mosaic, layer):
    shape = VGG_POOLS[layer]
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda x: elementwise.pallas_pool_window(x, (2, 2), False), x)
    assert "tpu_custom_call" in text and "pallas_pool_window" in text


@pytest.mark.parametrize("layer", sorted(VGG_POOLS))
def test_backward_compiles_for_a_v5e(one_chip, mosaic, layer):
    b, h, w, c = VGG_POOLS[layer]
    err = jax.ShapeDtypeStruct((b, h // 2, w // 2, c), jnp.float32,
                               sharding=one_chip)
    idx = jax.ShapeDtypeStruct(err.shape, jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda e, i: elementwise.pallas_gd_pool_window(e, i, (2, 2)),
        err, idx)
    assert "tpu_custom_call" in text and "pallas_gd_pool_window" in text


def test_no_layout_copy_stands_beside_the_kernels(one_chip, mosaic):
    """The windowed view is a transpose to (H, W, B, C).  It costs
    nothing only while that is the layout XLA's TPU convolutions emit
    and take: in a compiled conv -> pool -> conv -> pool -> fc step the
    transposes must come out as bitcasts, with no float32 copy of a
    pool's input or output array (on the chip such a copy cost more
    than the kernel it fed: PERF.md section 6, PR 27)."""
    import re

    from znicz_tpu.parallel import fused
    hyp = (0.01, 0.0005, 0.0, 0.9)
    conv = fused.LayerSpec("conv", "strict_relu", True, hyp, hyp, (
        ("padding", (1, 1)), ("stride", (1, 1))))
    pool = fused.LayerSpec("max_pool", "linear", False, hyp, hyp, (
        ("ksize", (2, 2)), ("padding", (0, 0)), ("stride", (2, 2))))
    spec = fused.ModelSpec(
        (conv, pool, conv, pool,
         fused.LayerSpec("fc", "linear", True, hyp, hyp)), "softmax")
    b, px = 64, 56

    def shaped(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = [(shaped(3, 3, 3, 64), shaped(64)), (None, None),
              (shaped(3, 3, 64, 128), shaped(128)), (None, None),
              (shaped((px // 4) ** 2 * 128, 16), shaped(16))]

    def steps(params, vels, data, labels):
        def body(carry, batch):
            p, v, _ = fused.train_minibatch(
                spec, *carry, batch[0].astype(jnp.float32), batch[1])
            return (p, v), None
        return jax.lax.scan(body, (params, vels), (data, labels))[0]
    text = _compiled_text(steps, params, params,
                          shaped(2, b, px, px, 3, dtype=jnp.bfloat16),
                          shaped(2, b, dtype=jnp.int32))
    assert text.count("pallas_pool_window") >= 2
    assert text.count("pallas_gd_pool_window") >= 2
    pooled = {f"f32[{b},{s},{s},{c}]"
              for s, c in ((px, 64), (px // 2, 64), (px // 2, 128))}
    copies = re.findall(r"= (f32\[[\d,]*\])\{[^}]*\} copy\(", text)
    assert copies, "no copy at all: the pattern no longer matches"
    assert not pooled & set(copies), sorted(pooled & set(copies))
