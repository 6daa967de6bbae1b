"""The decoder's kernels compiled for a described v5e at the published
shapes of ``mellum2-12b-a2.5b`` (benchmark/configs): splash attention,
sliding and full, forward and backward, at 8,192 tokens with 32 query
heads over 4 key/value heads of 128; and the grouped expert products of
16 held experts of 2304 x 896 over the 32,768 assignment rows of half a
sequence (``ops/moe.CHUNK_TOKENS``), forward and backward; and the rows'
way there and back around them (``dispatch_rows``, ``combine``) over the
first piece of those rows; and the gated delta rule's kernels at the
shapes of one of ``olmo-hybrid-7b``'s linear layers (4,096 tokens, 30
heads, keys of 96, values of 192, chunks of 64), forward and backward;
and Kimi delta attention's per-channel rule's kernels at the shapes of
one of ``solar-open2-250b``'s KDA layers (4,096 tokens, 16 heads, keys and
values of 128, chunks of 64), forward and backward.  The TPU's own
Mosaic and XLA compilers run here, with no chip; nothing runs, so this
says nothing of results or times.

The topology is described inside a fixture, never at import: only the
worker that is given this file loads the TPU's library."""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from znicz_tpu.ops import attention, gdn, moe, tuning

T, HEADS, KV_HEADS, HEAD_DIM, WINDOW = 8192, 32, 4, 128, 1024
D, F, HELD, TOP_K = 2304, 896, 16, 8


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
    attention._splash_kernel.cache_clear()
    yield
    attention._splash_kernel.cache_clear()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _qkv(one_chip):
    def s(heads):
        return jax.ShapeDtypeStruct((1, T, heads, HEAD_DIM), jnp.bfloat16,
                                    sharding=one_chip)
    return s(HEADS), s(KV_HEADS), s(KV_HEADS)


@pytest.mark.parametrize("window", [WINDOW, None],
                         ids=["sliding", "full"])
def test_attention_forward_compiles_for_a_v5e(one_chip, mosaic, window):
    assert attention.kernel_route(T, HEAD_DIM)
    text = _compiled_text(
        lambda q, k, v: attention.attention(q, k, v, window),
        *_qkv(one_chip))
    assert "tpu_custom_call" in text and "splash_mqa_fwd" in text


@pytest.mark.parametrize("window", [WINDOW, None],
                         ids=["sliding", "full"])
def test_attention_backward_compiles_for_a_v5e(one_chip, mosaic, window):
    def loss(q, k, v):
        return jnp.sum(attention.attention(q, k, v, window)
                       .astype(jnp.float32))
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_qkv(one_chip))
    assert "splash_mqa_fwd" in text and "splash_mqa_dkv" in text


def _expert_shapes(one_chip):
    m = moe.CHUNK_TOKENS * TOP_K
    return (jax.ShapeDtypeStruct((m, D), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((HELD, D, F), jnp.bfloat16,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((HELD, F, D), jnp.bfloat16,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((HELD,), jnp.int32, sharding=one_chip))


def _expert_products(xs, w_up, w_down, sizes):
    h = moe.grouped_matmul(xs, w_up, sizes)
    return moe.grouped_matmul(h.astype(xs.dtype), w_down, sizes)


def test_grouped_products_compile_for_a_v5e(one_chip, mosaic):
    assert moe.kernel_route(moe.CHUNK_TOKENS * TOP_K, D, F)
    text = _compiled_text(_expert_products, *_expert_shapes(one_chip))
    assert text.count("tpu_custom_call") >= 2


def test_grouped_products_backward_compiles_for_a_v5e(one_chip, mosaic):
    def loss(xs, w_up, w_down, sizes):
        return jnp.sum(_expert_products(xs, w_up, w_down, sizes))
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_expert_shapes(one_chip))
    # two products forward, and an input and a weight gradient of each
    assert text.count("tpu_custom_call") >= 5


def _way_shapes(one_chip):
    n, pairs = moe.CHUNK_TOKENS, moe.CHUNK_TOKENS * TOP_K
    rows = moe.piece_rows(pairs, HELD / 64)[0]
    assert rows == 12288 and rows % moe.TILE_M == 0

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return (s((n, D), jnp.bfloat16), s((rows, D), jnp.float32),
            s((n, TOP_K), jnp.float32), s((rows,), jnp.int32),
            s((n, TOP_K), jnp.int32), s((), jnp.int32))


def _there_and_back(x, ys, weights, taken, row, live):
    """A piece without its products: ``ys`` stands for what they make of
    the dispatched rows."""
    xs = moe.dispatch_rows(x, taken, row, live)
    return moe.combine(ys + xs.astype(jnp.float32), weights, taken, row,
                       live)


def _pair_sized_buffers(text: str) -> int:
    """Arrays of ``tokens * top_k`` rows of width d that the program itself
    (not a fused computation's inside) writes."""
    pairs = moe.CHUNK_TOKENS * TOP_K
    return len(re.findall(
        rf"= (?:f32|bf16)\[(?:{pairs}|{TOP_K},{moe.CHUNK_TOKENS}),{D}\]",
        text[text.index("ENTRY"):]))


def test_rows_there_and_back_compile_for_a_v5e(one_chip, mosaic):
    text = _compiled_text(_there_and_back, *_way_shapes(one_chip))
    assert f"f32[{moe.CHUNK_TOKENS},{D}]" in text
    # the rows taken back, once: gathered, then weighted and summed in place
    assert _pair_sized_buffers(text) <= 2, "a pass over all the pairs more"


def test_rows_there_and_back_backward_compiles_for_a_v5e(one_chip, mosaic):
    def loss(x, ys, weights, taken, row, live):
        return jnp.sum(_there_and_back(x, ys, weights, taken, row, live))
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_way_shapes(one_chip))
    # the gradients of the rows, of the products' rows and of the weights
    assert f"bf16[{moe.CHUNK_TOKENS},{D}]" in text
    assert _pair_sized_buffers(text) == 0


# -- a later piece that does not run: granite-4.0-h-small's expert layer ----
G_N, G_D, G_F, G_HELD, G_EXPERTS, G_TOP_K = 2048, 4096, 768, 9, 72, 10


def _hlo_scope_bytes():
    """``tools/hlo_scope_bytes.py``, which is no package's."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "hlo_scope_bytes", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "hlo_scope_bytes.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def granite_expert_grads(one_chip):
    """The compiled text of one expert layer's forward (its output kept)
    and backward at ``granite-4.0-h-small``'s widths, 9 held experts of 4096 x 768 over
    2,048 tokens at 10 a token (pieces of 3,840 and 16,640 rows), as a TPU
    process dispatches it."""
    assert moe.piece_rows(G_N * G_TOP_K, G_HELD / G_EXPERTS) == (3840, 16640)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tuning, "on_tpu", lambda: True)

        def s(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def layer(xn, weights, experts, wg, wu, wd, d_out):
            out, vjp = jax.vjp(lambda *a: moe.held_expert_sum(
                a[0], a[1], experts, *a[2:], 0, jnp.bfloat16,
                G_HELD / G_EXPERTS)[0], xn, weights, wg, wu, wd)
            return out, vjp(d_out)
        return _compiled_text(
            layer, s((G_N, G_D)), s((G_N, G_TOP_K)),
            s((G_N, G_TOP_K), jnp.int32), s((G_HELD, G_D, G_F)),
            s((G_HELD, G_D, G_F)), s((G_HELD, G_F, G_D)), s((G_N, G_D)))


def test_a_skipped_later_piece_costs_no_leaf_sized_pass(granite_expert_grads):
    """One expert layer's backward at ``granite-4.0-h-small``'s widths:
    beside the conditional, and in its branch that does nothing, no
    ``broadcast`` and no ``copy`` makes an array of an expert leaf's shape
    (56.6 MB each in bfloat16)."""
    made = _hlo_scope_bytes().made_of_shape(
        granite_expert_grads,
        [f"{G_HELD},{G_D},{G_F}", f"{G_HELD},{G_F},{G_D}"])
    assert sum(n for (where, opcode, _), n in made.items()
               if where == "later" and opcode.endswith("tgmm")) == 3, made
    passes = {key: n for key, n in made.items() if key[0] in ("step", "idle")
              and key[1] in ("broadcast", "copy")}
    assert not passes, passes


def test_the_way_back_is_the_segment_sum_kernel(mosaic, granite_expert_grads):
    """The same program takes the first piece's way back from sorted space
    (3,840 rows of 20,480 pairs) as ``pallas_pair_sum``, ``combine``'s
    forward and ``dispatch_rows``' gradient, and makes no array of every
    token's ``top_k`` rows, ``(top_k, tokens, d)`` or ``(tokens * top_k,
    d)``, but in the later piece's branch (16,640 rows: the gather): the
    gather's way back writes ``f32[10,2048,4096]`` (335 MB) and reads it
    again to sum it."""
    assert moe.first_piece_route(G_N, G_D, G_TOP_K,
                                 G_HELD / G_EXPERTS) == "kernel"
    assert moe.combine_route(G_D, 16640 / 20480) == "gather"
    assert "pallas_pair_sum" in granite_expert_grads
    made = _hlo_scope_bytes().made_of_shape(
        granite_expert_grads, [f"{G_TOP_K},{G_N},{G_D}",
                               f"{G_N * G_TOP_K},{G_D}"])
    assert not [key for key in made if key[0] != "later"], made


def _rule_shapes(one_chip):
    t, h, dk, dv = 4096, 30, 96, 192
    return tuple(jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
                 for shape in ((1, t, h, dk), (1, t, h, dk), (1, t, h, dv),
                               (1, t, h), (1, t, h)))


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_delta_rule_kernels_compile_for_a_v5e(one_chip, mosaic, backward):
    def rule(*a):
        return gdn.delta_rule(*a, 64, jnp.bfloat16)

    def loss(*a):
        return jnp.sum(rule(*a))
    assert gdn.rule_route() == "kernel"
    text = _compiled_text(jax.grad(loss, argnums=tuple(range(5)))
                          if backward else rule, *_rule_shapes(one_chip))
    assert ("pallas_delta_rule_bwd" in text) == backward
    assert "pallas_delta_rule_fwd" in text


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_kda_rule_kernels_compile_for_a_v5e(one_chip, mosaic, backward):
    """Kimi delta attention's per-channel rule at the shapes of one of
    ``solar-open2-250b``'s KDA layers (4,096 tokens, 16 heads, keys and
    values of 128, chunks of 64, bfloat16 operands): the kernels, and no
    loop of the chunked form left beside them."""
    t, h, dk = 4096, 16, 128

    def rule(*a):
        return gdn.kda_rule(*a, 64, jnp.bfloat16)

    def loss(*a):
        return jnp.sum(rule(*a))
    assert gdn.rule_route() == "kernel"
    shapes = tuple(jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
                   for shape in ((1, t, h, dk),) * 4 + ((1, t, h),))
    text = _compiled_text(jax.grad(loss, argnums=tuple(range(5)))
                          if backward else rule, *shapes)
    assert ("pallas_kda_rule_bwd" in text) == backward
    assert "pallas_kda_rule_fwd" in text
    assert not re.search(r"\bwhile\b", text)


#: the four decoder cells' tables and tokens a step: rows, width, tokens
EMBED_SHAPES = {"falcon-h1-34b-instruct": (32640, 5120, 4096),
                "olmo-hybrid-7b": (12544, 3840, 4096),
                "mellum2-12b-a2.5b": (24576, 2304, 8192),
                "granite-4.0-h-small": (12544, 4096, 2048)}


@pytest.mark.parametrize("config", sorted(EMBED_SHAPES))
def test_the_embedding_gradient_compiles_for_a_v5e(one_chip, mosaic, config):
    """The table's gradient is the kernel: no scatter is left in the
    compiled program."""
    rows, width, tokens = EMBED_SHAPES[config]

    def loss(table, ids):
        out, _ = attention.embed_fwd((table,), ids, {"width": width})
        return jnp.sum(out)
    assert attention.embed_route(width) == "kernel"
    text = _compiled_text(jax.grad(loss), jax.ShapeDtypeStruct(
        (rows, width), jnp.float32, sharding=one_chip), jax.ShapeDtypeStruct(
        (1, tokens), jnp.int32, sharding=one_chip))
    assert "pallas_embed_grad" in text
    assert not re.search(r"\bscatter\(", text)
