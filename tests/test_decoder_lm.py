"""The token-sequence layer kinds (``embed``, ``attn_block``, ``moe_block``,
``lm_head``) at test widths on the CPU: d 64, 4 query heads over 2
key/value heads of 16, window 8, T 32, 8 experts of width 32 with 2 a
token, vocabulary 128, layers sliding x3 + full.  The fused trainer is
held against the benchmark's plain reference
(``benchmark/lib/decoder_reference.py``, which imports nothing of the
program) with seeded weights."""

import contextlib
import copy
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import decoder_model as model          # noqa: E402
from benchmark.lib import decoder_reference as reference  # noqa: E402
from znicz_tpu.nn import decoder as units                 # noqa: E402
from znicz_tpu.ops import attention, moe, segment, tuning  # noqa: E402
from znicz_tpu.parallel import fused                      # noqa: E402

TRAFFIC = {"seq_len": 32, "minibatch": 2, "n_train": 12, "n_valid": 4,
           "n_test": 0}
SEED = 20261001


def config(experts_held=(0, 8)) -> dict:
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "tiny-decoder.json")) as fh:
        cfg = json.load(fh)
    cfg["deployment"]["experts_held"] = list(experts_held)
    cfg["num_experts"] = experts_held[1]
    return cfg


def spec_of(cfg: dict) -> fused.ModelSpec:
    """The trainer's spec of the configuration, as ``extract_model``
    makes it of the units."""
    kinds = {cls.MAPPING[0]: cls for cls in (
        units.Embedding, units.AttentionBlock, units.MoEBlock,
        units.LMHead)}
    layers = []
    for la in model.layer_list(cfg):
        unit = kinds[la["type"]](None, **la["->"])
        h = la["<-"]
        layers.append(fused.sequence_layer(unit, (
            h["learning_rate"], h["weights_decay"], 0.0,
            h["gradient_moment"])))
    return fused.ModelSpec(tuple(layers), "softmax")


def setup(experts_held):
    cfg = config(experts_held)
    weights = model.make_weights(SEED, model.param_shapes(cfg))
    x, y = model.make_rows(SEED, np.arange(4, 10, dtype=np.uint32), cfg,
                           TRAFFIC)
    return cfg, spec_of(cfg), weights, x.reshape(3, 2, -1), y.reshape(
        3, 2, -1)


# -- the fused trainer against the reference ---------------------------------
@pytest.mark.parametrize("experts_held", [(0, 8), (2, 2)],
                         ids=["all_held", "quarter_held"])
def test_three_steps_follow_the_reference(experts_held):
    cfg, spec, weights, x, y = setup(experts_held)
    ref = reference.follow(cfg, copy.deepcopy(weights), x, y)
    want = jax.grad(lambda ps: jnp.mean(reference.token_losses(
        cfg, ps, x[0], y[0])))([tuple(ls) for ls in weights])
    grads, _ = jax.jit(lambda p, a, b: fused.grad_minibatch(
        spec, p, a, b))(weights, x[0], y[0])
    for got_layer, want_layer in zip(grads, want):
        for got, exp in zip(got_layer, want_layer):
            np.testing.assert_allclose(got, exp, rtol=2e-4, atol=2e-7)
    p0 = jax.tree.map(np.asarray, weights)
    trainer = fused.FusedTrainer(
        spec=spec, params=weights,
        vels=jax.tree.map(jnp.zeros_like, weights))
    rows = jnp.concatenate(list(x)), jnp.concatenate(list(y))
    losses = [float(trainer.train_epoch(
        *rows, np.arange(2 * s, 2 * s + 2), 2, ctr_base=2 * s)["loss"][0])
        for s in range(3)]
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-5)
    assert losses[2] < losses[0]
    change = [tuple(float(np.linalg.norm(np.asarray(a) - a0))
                    for a, a0 in zip(ls, ls0))
              for ls, ls0 in zip(trainer.params, p0)]
    for got, exp in zip(change, ref["change_norms"]):
        np.testing.assert_allclose(got, exp, rtol=2e-4)


def test_counters_are_the_references_own_routing():
    cfg, spec, weights, x, y = setup((2, 2))
    got = jax.jit(lambda p, a, b: fused.eval_minibatch(spec, p, a, b))(
        weights, x[0], y[0])
    want = reference.routing(cfg, [tuple(ls) for ls in weights], x[0])
    assert {k: int(got[k]) for k in want} == want
    assert int(got["tokens"]) == x[0].size
    assert want["moe_assignments"] == 4 * 2 * x[0].size      # layers x top_k


# -- the expert layer ---------------------------------------------------------
def _moe_case(held=(0, 8)):
    cfg = config(held)
    la = [la for la in model.layer_list(cfg) if la["type"] == "moe_block"][0]
    mcfg = units.MoEBlock(None, **la["->"]).fused_config()
    return cfg, mcfg


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The ``moe_block`` outputs of the four quarters, the residual (and
    the router, which every chip computes alike) counted once, are the
    uncut layer of the reference."""
    cfg, _ = _moe_case()
    shapes = model.param_shapes(cfg)[2]
    leaves = model.make_weights(SEED, [shapes])[0]
    leaves = (leaves[0], leaves[1] * 30.0) + tuple(leaves[2:])   # decisive
    x = jax.random.normal(jax.random.key(3), (2, 32, 64), jnp.float32)
    whole = jnp.stack([reference.experts(cfg, leaves, row, None, False)[0]
                       for row in x])
    total = x
    for first in range(0, 8, 2):
        _, qcfg = _moe_case((first, 2))
        g2, wr, wg, wu, wd = leaves
        share = (g2, wr) + tuple(w[first:first + 2] for w in (wg, wu, wd))
        out, counters = moe.moe_block_fwd(share, x, qcfg)
        total = total + (out - x)
        assert int(counters["moe_assignments"]) == 2 * 32 * 2
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("expected", [1.0, 0.25],
                         ids=["one_length", "two_lengths"])
@pytest.mark.parametrize("target,held_pairs", [(3, 64), (6, 0)],
                         ids=["all_on_one_held_expert", "none_held"])
def test_worst_imbalance_drops_nothing(target, held_pairs, expected):
    """Every token's first choice on ONE expert (held, then absent), its
    second on an absent one: the held sum is the dense product of that
    expert, and every pair is counted, whichever length of the sorted
    buffers the count picks."""
    n, d, f = 64, 64, 32
    ks = jax.random.split(jax.random.key(5), 5)
    xn = jax.random.normal(ks[0], (n, d))
    wg, wu = (jax.random.normal(k, (2, d, f)) * 0.1 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (2, f, d)) * 0.1
    weights = jax.random.uniform(ks[4], (n, 2), minval=0.2, maxval=0.8)
    experts = jnp.stack([jnp.full((n,), target), jnp.full((n,), 7)], 1)
    out, counts, moved = moe.held_expert_sum(xn, weights, experts, wg, wu,
                                             wd, 2, jnp.float32, expected)
    assert int(counts.sum()) == held_pairs
    first, rest = moe.piece_rows(n * 2, expected)
    assert int(moved) == first + (rest if held_pairs > first else 0)
    if held_pairs:
        e = target - 2
        dense = (jax.nn.silu(xn @ wg[e]) * (xn @ wu[e])) @ wd[e]
        np.testing.assert_allclose(out, weights[:, :1] * dense, rtol=1e-4,
                                   atol=1e-6)
        assert int(counts[e]) == n
    else:
        assert not np.asarray(out).any()


def _routed(case: str, n: int, top_k: int = 2):
    """``(weights, experts)`` over 8 experts of which 2 and 3 are held."""
    ks = jax.random.split(jax.random.key(17), 2)
    weights = jax.random.uniform(ks[0], (n, top_k), minval=0.2, maxval=0.8)
    if case == "balanced":
        _, experts = jax.lax.top_k(jax.random.normal(ks[1], (n, 8)), top_k)
    else:
        target = {"none_held": 6, "all_on_one_held_expert": 3}[case]
        experts = jnp.stack([jnp.full((n,), target), jnp.full((n,), 7)], 1)
    return weights, experts.astype(jnp.int32)


def _dense_expert_sum(xn, weights, experts, wg, wu, wd, first):
    """The same sum by plain ``jnp``: every held expert over every row."""
    out = jnp.zeros_like(xn)
    for e in range(wg.shape[0]):
        w = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=1)
        out = out + w[:, None] * (
            (jax.nn.silu(xn @ wg[e]) * (xn @ wu[e])) @ wd[e])
    return out


@pytest.mark.parametrize("expected,d", [(1.0, 64), (0.25, 64), (0.125, 128)],
                         ids=["one_piece", "two_pieces", "kernel"])
@pytest.mark.parametrize("case", ["balanced", "none_held",
                                  "all_on_one_held_expert"])
def test_the_way_there_and_back_has_the_dense_gradients(monkeypatch, case,
                                                        expected, d):
    """``dispatch_rows`` and ``combine`` with their hand-written backwards
    against ``jax.grad`` of the dense sum: the output and the gradients of
    the input, the ROUTING WEIGHTS and the three expert weights.  With
    every first choice on one held expert the 64 held pairs pass the first
    piece (48 rows, 24 in the kernel case), so the later piece runs.  In
    the kernel case (``d`` 128, the Pallas interpreter, a first piece of
    24 of the 128 pairs) the first piece's way back is ``pair_sum``'s
    kernel (``combine_route``) and the later piece's the gather; its output
    and gradients are then also the gather's at the same width to the bit
    (the same float32 terms, two a token: no order of addition to differ
    in)."""
    monkeypatch.setattr(tuning, "_INTERPRET", d == 128)
    first = moe.piece_rows(128, expected)[0]
    assert moe.combine_route(d, first / 128) == (
        "kernel" if d == 128 else "gather")
    n, f = 64, 32
    ks = jax.random.split(jax.random.key(19), 5)
    xn = jax.random.normal(ks[0], (n, d))
    wg, wu = (jax.random.normal(k, (2, d, f)) * 0.1 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (2, f, d)) * 0.1
    cot = jax.random.normal(ks[4], (n, d))
    weights, experts = _routed(case, n)

    def ours(xn, weights, wg, wu, wd):
        return moe.held_expert_sum(xn, weights, experts, wg, wu, wd, 2,
                                   jnp.float32, expected)[0]

    def dense(xn, weights, wg, wu, wd):
        return _dense_expert_sum(xn, weights, experts, wg, wu, wd, 2)
    args = (xn, weights, wg, wu, wd)
    np.testing.assert_allclose(ours(*args), dense(*args), rtol=1e-4,
                               atol=1e-6)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * cot), range(5))(*args)
    got, want = grads(ours), grads(dense)
    if d == 128:
        out = ours(*args)
        monkeypatch.setattr(tuning, "_INTERPRET", False)
        assert moe.combine_route(d, first / 128) == "gather"
        for a, b in zip((out,) + got, (ours(*args),) + grads(ours)):
            np.testing.assert_array_equal(a, b)
    for name, a, b in zip(("xn", "weights", "wg", "wu", "wd"), got, want):
        # at 128 columns the float32 rounding of the grouped products'
        # backward, by either way back, reads up to 1.2 times the 64-wide
        # tolerance at an element that cancels (against a float64 dense
        # sum too): the absolute term scales with the gradient's size there
        scale = 1.0 if d == 64 else float(np.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6 * scale,
                                   err_msg=name)
    if case == "none_held":
        assert not any(np.asarray(a).any() for a in got)
    else:
        assert np.asarray(got[1]).any()


def _expert_sum_case(n=64, d=64, f=32):
    """``(xn, wg, wu, wd, cot)``: rows, experts 2 and 3 of 8, and a
    cotangent of the sum."""
    ks = jax.random.split(jax.random.key(37), 5)
    wg, wu = (jax.random.normal(k, (2, d, f)) * 0.1 for k in ks[1:3])
    return (jax.random.normal(ks[0], (n, d)), wg, wu,
            jax.random.normal(ks[3], (2, f, d)) * 0.1,
            jax.random.normal(ks[4], (n, d)))


def _made(jaxpr, later_piece=True):
    """``(primitive, aval)`` of every array a jaxpr makes, those of its
    inner jaxprs too; ``later_piece`` False: but for the branch of a
    ``cond`` that runs (index 1: the later piece of the sorted pairs); the
    branch that does nothing is walked."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                yield eqn.primitive.name, v.aval
        for key, param in eqn.params.items():
            inner = param if isinstance(param, (tuple, list)) else (param,)
            if (not later_piece and eqn.primitive.name == "cond"
                    and key == "branches"):
                inner = inner[:1]
            for sub in inner:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _made(sub, later_piece)


def test_a_skipped_piece_makes_no_gradient():
    """The gradient of the expert sum with a later piece: no array of an
    expert leaf's shape is made of nothing (``broadcast_in_dim``) and none
    is added, but in the branch that runs the later piece; there the
    piece's gradients are added into the first piece's."""
    xn, wg, wu, wd, cot = _expert_sum_case()
    weights, experts = _routed("balanced", 64)
    assert moe.piece_rows(128, 0.25) == (48, 80)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(moe.held_expert_sum(
        a[0], a[1], experts, *a[2:], 2, jnp.float32, 0.25)[0] * cot),
        range(5)))(xn, weights, wg, wu, wd).jaxpr
    leaf = {wg.shape, wd.shape}
    made = [(name, aval.shape) for name, aval in _made(jaxpr, False)
            if aval.shape in leaf]
    assert made, "the walk sees the leaves' gradients"
    assert not [m for m in made if m[0] in (
        "broadcast_in_dim", "add", "add_any")], made
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    later = [e.primitive.name for e in conds[-1].params["branches"][1].eqns
             if e.outvars[0].aval.shape in leaf]
    assert later.count("add") == 3, later


@pytest.mark.parametrize("case", ["balanced", "none_held"])
def test_a_skipped_piece_leaves_the_first_pieces_gradients(case):
    """Two pieces of which the later one is skipped against ONE piece over
    the same pairs: output and all five gradients bit for bit."""
    xn, wg, wu, wd, cot = _expert_sum_case()
    weights, experts = _routed(case, 64)

    def run(expected):
        def loss(xn, weights, wg, wu, wd):
            out, _, moved = moe.held_expert_sum(
                xn, weights, experts, wg, wu, wd, 2, jnp.float32, expected)
            return jnp.sum(out * cot), (out, moved)
        return jax.value_and_grad(loss, range(5), has_aux=True)(
            xn, weights, wg, wu, wd)
    (_, (out, moved)), got = run(0.25)
    (_, (one, every)), want = run(1.0)
    assert (int(moved), int(every)) == (48, 128)
    for a, b in zip((out,) + got, (one,) + want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["one_gather", "a_gather_a_slab"])
def test_pair_rows_are_summed_live_and_in_order(dtype):
    """Both forms of ``_sum_pair_rows`` against a plain loop: dead pairs
    (row below 0, at or beyond ``live``) add nothing, whatever their row
    holds."""
    ks = jax.random.split(jax.random.key(31), 3)
    src = jax.random.normal(ks[0], (24, 16)).astype(dtype)
    src = src.at[10:].set(jnp.nan)                       # no one's rows
    row = jax.random.randint(ks[1], (12, 4), -8, 24)
    scale = jax.random.uniform(ks[2], (12, 4))
    for given in (None, scale):
        got = moe._sum_pair_rows(src, row, jnp.asarray(10), given)
        want = np.zeros((12, 16), np.float32)
        for t in range(12):
            for k in range(4):
                if 0 <= int(row[t, k]) < 10:
                    want[t] += np.asarray(src[row[t, k]], np.float32) * (
                        1.0 if given is None else float(scale[t, k]))
        assert got.dtype == dtype and got.shape == (12, 16)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want,
            rtol=1e-6 if dtype == jnp.float32 else 1e-2, atol=1e-6)


def _steered_block(collapsed: bool):
    """A ``moe_block`` holding experts 2 and 3 of 8, 2 a token, whose router
    reads two planted features: every token on (2, 5), or the even tokens
    on (2, 5) and the odd ones on (6, 7): exactly a quarter of the pairs
    held."""
    _, mcfg = _moe_case((2, 2))
    ks = jax.random.split(jax.random.key(23), 4)
    x = jax.random.normal(ks[0], (2, 32, 64)) * 0.1
    x = x.at[..., 0].set(5.0).at[..., 1].set(
        jnp.where(jnp.arange(32) % 2 == 0, 5.0, -5.0))
    wr = np.zeros((64, 8), np.float32)
    if collapsed:
        wr[0, 2], wr[0, 5] = 4.0, 3.0
    else:
        wr[:2, 2], wr[:2, 5] = 4.0, 3.0
        wr[:2, 6], wr[:2, 7] = (4.0, -4.0), (3.0, -3.0)
    wg, wu = (jax.random.normal(k, (2, 64, 32)) * 0.1 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (2, 32, 64)) * 0.1
    return (jnp.ones((64,)), jnp.asarray(wr), wg, wu, wd), x, mcfg


@pytest.mark.parametrize("collapsed", [False, True],
                         ids=["balanced", "collapsed"])
def test_rows_moved_counts_the_pieces_that_ran(monkeypatch, collapsed):
    """Two chunks of 32 tokens: 64 pairs each, a first piece of 24 rows
    and a later one of 40 that runs only where more than 24 are held."""
    monkeypatch.setattr(moe, "CHUNK_TOKENS", 32)
    leaves, x, mcfg = _steered_block(collapsed)
    assert moe.piece_rows(64, 0.25) == (24, 40)
    _, counters = jax.jit(
        lambda ls, x: moe.moe_block_fwd(ls, x, mcfg))(leaves, x)
    assert int(counters["moe_assignments"]) == 128
    assert int(counters["moe_assignments_held"]) == (64 if collapsed else 32)
    assert int(counters["moe_rows_moved"]) == (2 * 64 if collapsed
                                               else 2 * 24)
    assert fused.COUNTERS["moe_rows_moved"][0] == "sum"


def _float_arrays(jaxpr):
    """Every float array a jaxpr makes, those of its inner jaxprs too."""
    return (aval.shape for _, aval in _made(jaxpr)
            if jnp.issubdtype(aval.dtype, jnp.floating))


@pytest.mark.parametrize("collapsed", [False, True],
                         ids=["balanced", "collapsed"])
def test_no_pair_sized_float_array_there_or_back(collapsed):
    """The block run and differentiated as the trainer does
    (``block_vjp``): no float array of ``(tokens * top_k, d)`` anywhere,
    and nothing float32 of that many rows of width d but what the one
    gather of the way back makes (``(top_k, tokens, d)``: the taken rows,
    weighted, masked); the pieces' own are 3/8 and 5/8 of that."""
    leaves, x, mcfg = _steered_block(collapsed)
    n, top_k, d = 64, 2, 64
    jaxpr = jax.make_jaxpr(lambda ls, x, err: attention.block_vjp(
        lambda ls, x: moe.moe_block_fwd(ls, x, mcfg), ls, x, err))(
            leaves, x, jnp.ones_like(x))
    shapes = set(_float_arrays(jaxpr.jaxpr))
    assert (48, d) in shapes and (80, d) in shapes      # the walk sees them
    wide = {s for s in shapes if len(s) >= 2 and s[-1] == d
            and int(np.prod(s[:-1])) >= n * top_k}
    assert wide <= {(top_k, n, d)}, wide


# -- attention -------------------------------------------------------------------
def _attn_case(window):
    cfg = config()
    la = [la for la in model.layer_list(cfg)
          if la["type"] == "attn_block"][0]
    acfg = dict(units.AttentionBlock(None, **la["->"]).fused_config(),
                window=window)
    leaves = model.make_weights(SEED, [model.param_shapes(cfg)[1]])[0]
    return acfg, leaves


@pytest.mark.parametrize("seq_len,same", [(8, True), (32, False)])
def test_a_sliding_layer_is_a_full_one_up_to_its_window(seq_len, same):
    (sliding, leaves), (full, _) = _attn_case(8), _attn_case(None)
    x = jax.random.normal(jax.random.key(7), (2, seq_len, 64))
    a = attention.attn_block_fwd(leaves, x, sliding)[0]
    b = attention.attn_block_fwd(leaves, x, full)[0]
    if same:
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    else:
        np.testing.assert_allclose(a[:, :8], b[:, :8], rtol=1e-5, atol=1e-6)
        assert float(jnp.abs(a[:, 8:] - b[:, 8:]).max()) > 1e-3


def test_yarn_frequencies_of_the_published_rope():
    """``rope_parameters.full_attention`` of Mellum2: correction
    dimensions 18 and 35 of 64; kept below, a sixteenth above, a linear
    ramp between."""
    rope = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    inv = attention.rope_inv_freq(128, rope)
    want = {0: 1.0, 17: 0.030634520893224042, 18: 0.024955408670558694,
            26: 0.0027043825167258223, 35: 4.7781061769823416e-05,
            63: 1.5344629944572555e-07}
    for i, value in want.items():
        assert inv[i] == pytest.approx(value, rel=1e-12)
    assert inv[17] == pytest.approx(500000 ** (-34 / 128))
    assert inv[63] == pytest.approx(500000 ** (-126 / 128) / 16)
    np.testing.assert_allclose(inv, reference.inv_freq(128, rope))
    cos, _ = attention.rope_tables(4, 128, tuple(sorted(rope.items())))
    assert cos[0, 0] == pytest.approx(1.2772588722239782)
    plain = attention.rope_inv_freq(
        128, {"rope_type": "default", "rope_theta": 500000})
    assert plain[63] == pytest.approx(500000 ** (-126 / 128))


# -- the kernel tier against its twin -------------------------------------------
@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setattr(tuning, "_INTERPRET", True)
    attention._splash_kernel.cache_clear()
    yield
    attention._splash_kernel.cache_clear()


@pytest.mark.parametrize("window", [128, None], ids=["sliding", "full"])
def test_splash_attention_is_its_jnp_twin(interpreter, window):
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 128)) * 0.3
    k, v = (jax.random.normal(kk, (1, 256, 1, 128)) for kk in ks[1:])
    assert attention.kernel_route(256, 128)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, window)))
    np.testing.assert_allclose(
        attention.splash_attention(q, k, v, window),
        attention.blocked_attention(q, k, v, window, block_q=128),
        rtol=2e-3, atol=2e-3)
    got = jax.grad(loss(attention.splash_attention), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(attention.blocked_attention), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3)


def test_megablox_products_are_ragged_dot(interpreter):
    ks = jax.random.split(jax.random.key(13), 2)
    lhs = jax.random.normal(ks[0], (512, 128))
    rhs = jax.random.normal(ks[1], (3, 128, 256)) * 0.1
    sizes = jnp.asarray([200, 0, 150], jnp.int32)       # 162 rows unheld
    assert moe.kernel_route(512, 128, 256)

    def run(impl):
        def f(lhs, rhs):
            out = impl(lhs, rhs, sizes)
            return jnp.sum(jnp.sin(out)), out
        (_, out), grads = jax.value_and_grad(f, (0, 1), has_aux=True)(
            lhs, rhs)
        return out, grads
    out, grads = run(moe.pallas_grouped_matmul)
    want, want_grads = run(moe.xla_grouped_matmul)
    # the rows beyond the groups are no one's: the kernels leave them be
    np.testing.assert_allclose(out[:350], want[:350], rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(grads[0][:350], want_grads[0][:350],
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(grads[1], want_grads[1], rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("collapsed", [False, True],
                         ids=["first_piece", "both_pieces"])
def test_rows_no_kernel_wrote_reach_nothing(interpreter, monkeypatch,
                                            collapsed):
    """NaN planted in the rows beyond ``sum(sizes)`` of every grouped
    product's operands and output (the kernels leave those rows as they
    find them): no output and no gradient of the expert sum sees it."""
    def beyond(a, sizes):
        return jnp.where((jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None],
                         a, jnp.nan)
    gmm, tgmm = moe._mosaic_gmm, moe._mosaic_tgmm
    monkeypatch.setattr(moe, "_mosaic_gmm", lambda lhs, rhs, sizes, **kw: beyond(
        gmm(beyond(lhs, sizes), rhs, sizes, **kw), sizes))
    monkeypatch.setattr(moe, "_mosaic_tgmm", lambda lhs, g, sizes: tgmm(
        beyond(lhs, sizes), beyond(g, sizes), sizes))
    n, d, f, top_k = 128, 128, 128, 4
    ks = jax.random.split(jax.random.key(29), 6)
    xn = jax.random.normal(ks[0], (n, d))
    wg, wu = (jax.random.normal(k, (2, d, f)) * 0.1 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (2, f, d)) * 0.1
    weights = jax.random.uniform(ks[4], (n, top_k), minval=0.1, maxval=0.4)
    if collapsed:       # 3 of every 4 pairs held: 384 > the first 256 rows
        experts = jnp.tile(jnp.asarray([2, 3, 2, 7]), (n, 1))
    else:               # 1 of 4 held
        experts = jnp.tile(jnp.asarray([5, 3, 6, 7]), (n, 1))
    assert moe.piece_rows(n * top_k, 0.25) == (256, 256)
    assert moe.kernel_route(256, d, f)

    def ours(xn, weights, wg, wu, wd):
        out, _, moved = moe.held_expert_sum(
            xn, weights, experts, wg, wu, wd, 2, jnp.float32, 0.25)
        return jnp.sum(jnp.sin(out)), (out, moved)
    (_, (out, moved)), got = jax.value_and_grad(
        ours, range(5), has_aux=True)(xn, weights, wg, wu, wd)
    assert int(moved) == (512 if collapsed else 256)
    want_out = _dense_expert_sum(xn, weights, experts, wg, wu, wd, 2)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(_dense_expert_sum(
        a[0], a[1], experts, *a[2:], 2))), range(5))(xn, weights, wg, wu, wd)
    np.testing.assert_allclose(out, want_out, rtol=2e-2, atol=2e-3)
    for name, a, b in zip(("xn", "weights", "wg", "wu", "wd"), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3, err_msg=name)


def _piece_of(experts, first: int, e_held: int, expected: float,
              later: bool):
    """``(taken, row, live)`` of the first piece of the sorted pairs, or of
    the later one, as ``moe.held_expert_sum`` takes them."""
    n, top_k = experts.shape
    local = experts - first
    key = jnp.where((local >= 0) & (local < e_held), local,
                    e_held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32).reshape(n, top_k)
    counts = jnp.sum(key[:, None] == jnp.arange(e_held), axis=0)
    first_rows, rest_rows = moe.piece_rows(n * top_k, expected)
    lo, rows = (first_rows, rest_rows) if later else (0, first_rows)
    live = jnp.clip(jnp.sum(counts) - lo, 0, rows).astype(jnp.int32)
    return order[lo:lo + rows], place - lo, live


def _pair_routing(case: str, n: int = 128, top_k: int = 4):
    """``(experts, later)`` over 8 experts of which 0-3 are held; ``later``:
    the case's live rows are those of the later piece."""
    ks = jax.random.split(jax.random.key(41), 2)
    absent = jnp.tile(jnp.arange(4, 8), (n, 1))
    if case == "none_held":
        return absent, False
    if case == "every_pair_on_one_token":
        return absent.at[37].set(jnp.arange(4)), False
    if case == "later_piece":             # 3 of every 4 pairs held
        return jnp.tile(jnp.asarray([0, 1, 2, 6]), (n, 1)), True
    _, experts = jax.lax.top_k(jax.random.normal(ks[0], (n, 8)), top_k)
    if case == "a_token_without_a_live_pair":
        experts = experts.at[5].set(jnp.arange(4, 8))
    return experts, False


PAIR_CASES = ["balanced", "none_held", "every_pair_on_one_token",
              "a_token_without_a_live_pair", "later_piece"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["combine", "dispatch_gradient"])
@pytest.mark.parametrize("case", PAIR_CASES)
def test_the_kernel_way_back_is_the_gather(interpreter, monkeypatch, case,
                                           dtype):
    """``pair_sum`` (the sorted segment sum over the live rows, the kernel
    in interpret mode, blocks of 16 tokens and chunks of 8 rows) against
    ``_sum_pair_rows`` (the gather over every pair): ``combine``'s forward
    (float32 rows times their pairs' weights) and ``dispatch_rows``'
    gradient (bfloat16 rows, no weights), with NaN in every row from
    ``live`` on."""
    monkeypatch.setattr(segment, "VMEM", 2 * 4 * 128 * 16)
    monkeypatch.setattr(segment, "CHUNK", 8)
    n, top_k, d = 128, 4, 128
    assert segment.block_rows(n, d) == 16
    experts, later = _pair_routing(case, n, top_k)
    taken, row, live = _piece_of(experts, 0, 4, 0.25, later)
    ks = jax.random.split(jax.random.key(43), 2)
    src = jax.random.normal(ks[0], (taken.shape[0], d)).astype(dtype)
    src = jnp.where((jnp.arange(src.shape[0]) < live)[:, None], src, jnp.nan)
    weights = (jax.random.uniform(ks[1], (n, top_k), minval=0.1, maxval=0.4)
               if dtype == jnp.float32 else None)
    got = jax.jit(moe.pair_sum, static_argnums=(3, 4))(
        src, taken, live, n, top_k, weights)
    want = moe._sum_pair_rows(src, row, live, weights)
    assert got.dtype == dtype and got.shape == (n, d)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-6,
                               atol=1e-6)
    held_tokens = np.asarray(jnp.any(moe._live(row, live), axis=1))
    assert not np.asarray(got, np.float32)[~held_tokens].any()
    if case == "none_held":
        assert int(live) == 0
    if case == "every_pair_on_one_token":
        assert int(live) == 4 and held_tokens.tolist() == [
            t == 37 for t in range(n)]
    if case == "a_token_without_a_live_pair":
        assert not held_tokens[5] and held_tokens.sum() > n // 2
    if case == "later_piece":
        assert int(live) == 128 and moe.piece_rows(n * top_k, 0.25) == (
            256, 256)


@pytest.mark.parametrize("route", ["kernel", "gather", "mesh", "narrow",
                                   "wide_share"])
def test_the_combine_routes_name_the_way_back_the_step_traces(monkeypatch,
                                                             route):
    """``combine_routes`` counts the expert rows by their way back, and the
    traced expert sum holds that form: the kernel on the Pallas tier (here
    the interpreter) at a width of whole 128-lane tiles where the first
    piece holds at most a quarter of the pairs (1 expert held of 8, 2 a
    token: 24 rows of 128), the gather on the plain CPU, under a kernel
    mesh, at a width of 64 and where the piece holds more (2 held: 48
    rows)."""
    monkeypatch.setattr(tuning, "_INTERPRET", route != "gather")
    d = 64 if route == "narrow" else 128
    held = 2 if route == "wide_share" else 1
    cfg = config((2, held))
    cfg["hidden_size"] = d
    spec = spec_of(cfg)
    forwards = [types.SimpleNamespace(input=np.zeros((2, 32, d)))
                for _ in spec.layers]
    mesh = (jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(2, 1),
                              ("data", "model")) if route == "mesh" else None)
    kernel = 4 * int(route == "kernel")
    assert fused.combine_routes(spec, forwards, mesh) \
        == f"kernel:{kernel} gather:{4 - kernel}"
    xn, wg, wu, wd, cot = _expert_sum_case(d=d)
    wg, wu, wd = wg[:held], wu[:held], wd[:held]
    weights, experts = _routed("balanced", 64)

    def loss(xn, weights, wg, wu, wd):
        return jnp.sum(moe.held_expert_sum(
            xn, weights, experts, wg, wu, wd, 2, jnp.float32, held / 8)[0]
            * cot)
    with (tuning.kernel_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        text = str(jax.make_jaxpr(jax.grad(loss, range(5)))(
            xn, weights, wg, wu, wd))
    # the first piece's forward, the forward and the input's gradient in
    # its backward; the later piece takes the gather
    assert text.count("name=pallas_pair_sum") == (3 if kernel else 0)


def test_the_start_record_names_the_way_back(sample, tmp_path):
    """Through the sample's workflow on the plain CPU: the start line and
    every timeline row carry ``combine_routes``, the gather in all four
    expert rows (width 64)."""
    from znicz_tpu.backends import Device
    from znicz_tpu.models import decoder_lm
    wf = decoder_lm.DecoderLMWorkflow()
    wf.initialize(device=Device.create("xla"))
    path = str(tmp_path / "timeline.jsonl")
    wf.train(fused=True, max_epochs=1, timeline_jsonl=path)
    rows = [json.loads(line) for line in open(path)]
    assert rows and all(r["combine_routes"] == "kernel:0 gather:4"
                        for r in rows)


# -- units, workflow, launcher ----------------------------------------------------
@pytest.fixture
def sample():
    from znicz_tpu.config import root
    saved = root.decoder_lm.to_dict()
    yield root.decoder_lm
    root.decoder_lm.update(saved)


def test_five_leaf_layer_round_trip_and_refusals(sample, tmp_path):
    from znicz_tpu.backends import Device
    from znicz_tpu.export import export_workflow
    from znicz_tpu.models.decoder_lm import DecoderLMWorkflow
    from znicz_tpu.parallel.mesh import make_mesh
    wf = DecoderLMWorkflow()
    wf.initialize(device=Device.create("xla"))
    spec, params, vels = fused.extract_model(wf)
    assert [len(p) for p in params] == [1, 5, 5, 5, 5, 5, 5, 5, 5, 2]
    assert spec.layers[2].kind == "moe_block" \
        and spec.layers[-1].kind == "lm_head"
    assert fused.attn_routes(spec) == "window:3 full:1"
    trainer = fused.FusedTrainer(workflow=wf)
    trainer.params = [tuple(a + 1.0 for a in ls) for ls in trainer.params]
    trainer.vels = [tuple(a + 0.5 for a in ls) for ls in trainer.vels]
    trainer.write_back()
    moe_unit, moe_gd = wf.forwards[2], wf.gds[2]
    for j, leaf in enumerate(moe_unit.LEAVES):
        np.testing.assert_allclose(getattr(moe_unit, leaf).mem,
                                   params[2][j] + 1.0)
        np.testing.assert_allclose(
            getattr(moe_gd, "velocity_" + leaf).mem, vels[2][j] + 0.5)
    # the snapshotter saves the leaves like any Vector
    from znicz_tpu.snapshotter import collect_state
    arrays, _ = collect_state(wf)
    assert {f"{moe_unit.name}/wg", f"{moe_gd.name}/velocity_wg"} \
        <= set(arrays)
    with pytest.raises(NotImplementedError, match="expert"):
        fused.FusedTrainer(spec=spec, params=params, vels=vels,
                           mesh=make_mesh(2, 1))
    with pytest.raises(NotImplementedError, match="Reach 3"):
        export_workflow(wf, str(tmp_path / "m.znn"))


def _last_train_step(flightrecorder) -> dict:
    return [r for r in flightrecorder.RECORDER.snapshot()["recent"]
            if r.get("kind") == "train_step"][-1]


def test_the_sample_trains_through_the_launcher(sample):
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.telemetry import flightrecorder
    wf = Launcher("znicz_tpu.models.decoder_lm", backend="xla", fused=True,
                  epochs=3, seed=7).run()
    metrics = wf.decision.epoch_metrics
    assert len(metrics) == 3
    assert metrics[-1]["train_loss"] < metrics[0]["train_loss"] < np.log(
        128) + 0.2
    for m in metrics:        # errors are counted a target, and so shared
        assert 0.0 < m["train_err_pct"] <= 100.0
        assert m["train_err_pct"] == pytest.approx(
            100.0 * m["train_n_err"] / (32 * 32))
        assert m["validation_err_pct"] == pytest.approx(
            100.0 * m["validation_n_err"] / (8 * 32))
    row = _last_train_step(flightrecorder)
    assert row["tokens"] == 32 * 32
    assert row["moe_assignments"] == row["moe_assignments_held"] \
        == 32 * 32 * 2 * 4
    assert 0 < row["moe_expert_load_max"] <= 4 * 32 * 2
    # every expert held: one piece of all the pairs, every layer
    assert row["moe_rows_moved"] == row["moe_assignments"]


@pytest.fixture(scope="module")
def counted_epoch():
    """One epoch of the sample through the Launcher, a Mamba layer in
    its first attention layer's place, a gated-delta-rule layer in its
    second's, a parallel hybrid layer in its third's and a
    Kimi-delta-attention layer before the full one, so that every
    declared counter counts: its ``train_step`` row and the registry's
    families after it."""
    from znicz_tpu.config import root
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.telemetry import flightrecorder
    from znicz_tpu.telemetry.registry import REGISTRY
    saved = root.decoder_lm.to_dict()
    root.decoder_lm.layer_types = ["mamba", "linear", "parallel", "kda",
                                   "full"]
    try:
        Launcher("znicz_tpu.models.decoder_lm", backend="xla", fused=True,
                 epochs=1, seed=7).run()
    finally:
        root.decoder_lm.update(saved)
    return _last_train_step(flightrecorder), REGISTRY


@pytest.mark.parametrize("name", sorted(fused.COUNTERS))
def test_every_declared_counter_reaches_row_gauge_and_docs(counted_epoch,
                                                           name):
    """``fused.COUNTERS`` is the one declaration: each name in it has a
    fold the epoch loop knows, a field in the ``train_step`` row, a
    ``train_<name>`` gauge with a help text holding the epoch's fold, and
    its line in docs/observability.md."""
    import os
    row, registry = counted_epoch
    fold, gauge = fused.COUNTERS[name]
    assert fold in ("sum", "max") and hasattr(np, fold)
    assert isinstance(row[name], int) and row[name] > 0
    made = gauge()
    assert made.name == "train_" + name and len(made.help) > 20
    assert made is registry.gauge("train_" + name)
    assert made.value() == row[name]
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "observability.md")).read()
    assert f"| `train_{name}` | gauge |" in doc
    assert name in doc.replace("train_" + name, "")    # the row's field


def test_a_model_without_the_kinds_has_no_counters():
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.telemetry import flightrecorder
    Launcher("znicz_tpu.models.wine", backend="xla", fused=True,
             epochs=1).run()
    row = _last_train_step(flightrecorder)
    assert not set(fused.COUNTERS) & set(row)
    assert 0.0 <= row.get("examples", 1) and "wall_ms" in row
