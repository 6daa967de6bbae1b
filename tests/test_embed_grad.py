"""The embedding's table gradient as a sorted segment sum
(``ops/attention.embed_grad``: the ids sorted, the Pallas kernel
``pallas_embed_grad`` in interpret mode summing a block of table rows at a
time) against XLA's scatter-add, ``zeros.at[ids].add(dy)``, which is what
``jnp.take``'s transpose computes; the step that takes it, and the start
record's ``embed_routes``."""

import contextlib
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import decoder_model
from znicz_tpu.ops import attention, segment, tuning
from znicz_tpu.parallel import fused

WIDTH = 128


@pytest.fixture
def kernel(monkeypatch):
    """The kernel in interpret mode, with blocks of 16 table rows and
    chunks of 8 sorted rows at ``WIDTH``, so that a small table has many
    blocks and a block many chunks."""
    monkeypatch.setattr(tuning, "_INTERPRET", True)
    monkeypatch.setattr(segment, "VMEM", 2 * 4 * WIDTH * 16)
    monkeypatch.setattr(segment, "CHUNK", 8)
    assert attention.embed_route(WIDTH) == "kernel"
    assert segment.block_rows(200, WIDTH) == 16


def _markov(n_rows: int = 2, seq_len: int = 512, vocab: int = 200):
    """Ids of the decoder cells' rows: the seeded Markov chain of
    ``benchmark/lib/decoder_model.py`` (4 branches from 1,024 states)."""
    cfg = {"vocab_size": vocab,
           "assumed": {"branching": 4, "chain_states": 1024}}
    ids, _ = decoder_model.make_rows(20261015, np.arange(n_rows), cfg,
                                     {"seq_len": seq_len})
    return ids.reshape(-1)


#: ids a case, over a table of 200 rows (blocks of 16: the last holds 8)
CASES = {
    # every id once
    "distinct": lambda: jax.random.permutation(jax.random.key(1), 200)[:150],
    # the cells' rows: most ids more than once
    "markov": _markov,
    # one id 500 times among others
    "repeated": lambda: jnp.concatenate([
        jnp.full((500,), 37), jnp.arange(0, 200, 3)]),
    # a block's first and last rows, the table's first and last; blocks 4
    # to 10 have no token
    "block_edges": lambda: jnp.asarray([0, 15, 16, 31, 32, 47, 48, 63, 199,
                                        192, 191, 176, 0, 199, 16, 63]),
    # below 0 counts from the end once; the rest outside [0, 200) drops
    "out_of_range": lambda: jnp.asarray([-1, -200, -201, 200, 207, 10**6,
                                         -10**6, 5, 5, 199, -1, 0]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_segment_sum_is_the_scatter(kernel, case):
    ids = CASES[case]().astype(jnp.int32)
    dy = jax.random.normal(jax.random.key(2), (ids.shape[0], WIDTH))
    got = np.asarray(jax.jit(attention.embed_grad, static_argnums=2)(
        ids, dy, 200))
    want = np.asarray(jnp.zeros((200, WIDTH)).at[ids].add(dy))
    if case in ("distinct", "block_edges", "out_of_range"):
        # no id repeats, or each repeat is a sum of two: bit for bit
        np.testing.assert_array_equal(got, want)
    else:
        assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
    if case == "block_edges":
        assert not got[64:176].any()             # blocks with no token
        assert got[[0, 16, 199]].any(axis=1).all()
    if case == "out_of_range":
        assert not got[1:5].any() and got[199].any()


#: the test modules whose configurations the step is built from
MODULES = {"tiny-decoder": "test_decoder_lm", "tiny-falcon-h1":
           "test_parallel_hybrid_lm", "tiny-hybrid": "test_hybrid_lm"}


def _widened(name: str):
    """``name``'s spec, weights and one minibatch at a width of 128 (the
    kernel takes whole 128-lane rows); ``tiny-hybrid``'s head is tied to
    the table."""
    mod = importlib.import_module(MODULES[name])
    cfg = mod.config((0, 8)) if name == "tiny-decoder" else mod.config()
    cfg["hidden_size"] = WIDTH
    if name == "tiny-hybrid":           # its Mamba heads span 2 x the width
        cfg["mamba_d_head"] *= 2
    weights = mod.model.make_weights(mod.SEED, mod.model.param_shapes(cfg))
    x, y = mod.model.make_rows(mod.SEED, np.arange(4, 6, dtype=np.uint32),
                               cfg, mod.TRAFFIC)
    return mod.spec_of(cfg), weights, x, y


@pytest.mark.parametrize("name", sorted(MODULES))
def test_the_step_takes_the_kernels_gradient(monkeypatch, name):
    """Through ``fused.grad_minibatch`` (``attention.block_vjp`` of the
    embedding, and the tied head's sum): the table's gradient within 1e-6
    of the scatter's, every other leaf's the same."""
    monkeypatch.setattr(tuning, "_INTERPRET", True)
    spec, weights, x, y = _widened(name)
    assert fused.embed_routes(spec) == "kernel:1 scatter:0"

    def grads():
        return jax.jit(lambda p, a, b: fused.grad_minibatch(
            spec, p, a, b)[0])(weights, x, y)
    got = grads()
    monkeypatch.setattr(attention, "embed_route", lambda width: "scatter")
    want = grads()
    (table,), (want_table,) = got[0], want[0]
    assert np.abs(table - want_table).max() \
        <= 1e-6 * np.abs(want_table).max()
    for leaves, want_leaves in zip(got[1:], want[1:]):
        for a, b in zip(leaves, want_leaves):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("route", ["kernel", "scatter", "mesh", "narrow"])
def test_the_routes_name_the_gradient_the_step_traces(monkeypatch, route):
    """``embed_routes`` counts the embedding rows by how their table's
    gradient is made, and the traced gradient holds that form: the kernel
    on the Pallas tier (here the interpreter), XLA's scatter-add on the
    plain CPU, under a kernel mesh, and at a width that is no multiple of
    128."""
    monkeypatch.setattr(tuning, "_INTERPRET", route != "scatter")
    spec, weights, x, _ = _widened("tiny-decoder")
    (table,) = weights[0]
    if route == "narrow":
        table = table[:, :96]
        spec = fused.ModelSpec((dataclasses.replace(
            spec.layers[0], config=tuple(sorted(
                {**spec.layers[0].cfg, "width": 96}.items()))),
            *spec.layers[1:]), spec.loss)
    mesh = (jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(2, 1),
                              ("data", "model")) if route == "mesh" else None)
    kernel = int(route == "kernel")
    assert fused.embed_routes(spec, mesh) \
        == f"kernel:{kernel} scatter:{1 - kernel}"

    def loss(t):
        out, _ = attention.embed_fwd((t,), x, spec.layers[0].cfg)
        return jnp.sum(out * out)
    with (tuning.kernel_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        assert fused.embed_routes(spec) \
            == f"kernel:{kernel} scatter:{1 - kernel}"
        text = str(jax.make_jaxpr(jax.grad(loss))(table))
    assert text.count("name=pallas_embed_grad") == kernel
    assert ("scatter-add" in text) != bool(kernel)
