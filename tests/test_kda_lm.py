"""The Kimi-delta-attention hybrid with sparse experts in every layer
(``kda_block`` and an ``attn_block`` with an output gate and no positional
embedding, a ``moe_block`` with a shared expert after each) at test widths on
the CPU: d 64, one gated attention layer (4 query heads over 1 key/value head
of 16) and three KDA layers (2 heads of 16, gates of rank 16, chunk 32),
8 of 32 experts of 32 columns with 4 a token and 8 of the shared expert's 32
columns, vocabulary 256 of 2,048, T 64: one chip's share of a layer split 4
ways by heads and by experts.  The fused trainer is held against the
benchmark's plain reference (``benchmark/lib/solar_open2_reference.py``: the
rule a token at a time; it imports nothing of the program) with seeded
weights."""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import correct                              # noqa: E402
from benchmark.lib import solar_open2_model as model           # noqa: E402
from benchmark.lib import solar_open2_reference as reference   # noqa: E402
from znicz_tpu.nn import decoder as units                      # noqa: E402
from znicz_tpu.ops import gdn                                  # noqa: E402
from znicz_tpu.parallel import fused                           # noqa: E402

TRAFFIC = {"seq_len": 64, "minibatch": 2, "n_train": 12, "n_valid": 4,
           "n_test": 0}
SEED = 20261016
#: the leaves a layer of each kind holds, in the layer list's order
LEAVES = [1, 6, 8, 17, 8, 17, 8, 17, 8, 2]
#: the counts the model holds whole, the deployment's name for each, and
#: the whole count
WHOLE = {"num_attention_heads": ("attention_heads_held", 16),
         "num_key_value_heads": ("kv_heads_held", 4),
         "n_routed_experts": ("experts_held", 32)}


def config(shares: int = 4, share: int = 0) -> dict:
    """``tiny-solar-open2`` as the harness's tests have it (``shares`` 4);
    ``shares`` 1: every head, expert and shared column held."""
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "tiny-solar-open2.json")) as fh:
        cfg = json.load(fh)
    if shares == 4 and share == 0:
        return cfg
    dep = cfg["deployment"]
    for key, (name, whole) in WHOLE.items():
        count = whole // shares
        cfg[key] = count
        dep[name] = [share * count, count]
    heads = cfg["published"]["linear_attn_config"]["num_heads"] // shares
    cfg["linear_attn_config"]["num_heads"] = heads
    dep["kda_heads_held"] = [share * heads, heads]
    cols = int(cfg["moe_intermediate_size"]) // shares
    dep["shared_columns_held"] = [share * cols, cols]
    return cfg


def layer_units(cfg: dict) -> list:
    return units.units_of(model.layer_list(cfg))


def spec_of(cfg: dict) -> fused.ModelSpec:
    layers = []
    for la, unit in zip(model.layer_list(cfg), layer_units(cfg)):
        h = la["<-"]
        layers.append(fused.sequence_layer(unit, (
            h["learning_rate"], h["weights_decay"], 0.0,
            h["gradient_moment"])))
    return fused.ModelSpec(tuple(layers), "softmax")


_MADE: dict = {}


def _weights(cfg: dict) -> list:
    """The seeded weights of the configuration's tree, built once a
    configuration, as a copy that a trainer may donate."""
    key = json.dumps(cfg, sort_keys=True)
    if key not in _MADE:
        _MADE[key] = model.make_weights(SEED, model.param_shapes(cfg))
    return jax.tree.map(jnp.copy, _MADE[key])


def setup(cfg=None):
    cfg = cfg or config()
    weights = _weights(cfg)
    x, y = model.make_rows(SEED, np.arange(4, 10, dtype=np.uint32), cfg,
                           TRAFFIC)
    return cfg, spec_of(cfg), weights, x.reshape(3, 2, -1), y.reshape(
        3, 2, -1)


def kind_cfg(cfg: dict, kind: str) -> dict:
    return [u for u in layer_units(cfg) if u.KIND == kind][0].fused_config()


def _leaves(cfg, place: int):
    """The leaves of layer ``place``, with gains and biases that are not
    all ones or zeros, so that each is seen, and the projections that write
    into the stream 30 times as large, so that what the block adds stands
    out of the stream's float32 rounding."""
    leaves = list(_weights(cfg)[place])
    for at, leaf in enumerate(leaves):
        if leaf.ndim == 1 and not (len(leaves) == model.KDA_LEAVES
                                   and at in (13, 14)):    # a_log, dt_bias
            leaves[at] = leaf + 0.3 * jax.random.normal(
                jax.random.key(at), leaf.shape)
    writes = {model.KDA_LEAVES: (16,), model.ATTN_LEAVES: (4,),
              model.MOE_LEAVES: (4, 7)}[len(leaves)]
    for at in writes:
        leaves[at] = 30.0 * leaves[at]
    return tuple(leaves)


def _stream(seed: int = 5):
    """A stream of the embedding's scale (a unit-scale table)."""
    return jax.random.normal(jax.random.key(seed), (2, 64, 64), jnp.float32)


def _value_and_grads(fn, leaves, x, err):
    """``fn(leaves, x)`` and its gradients at ``err``, in one jitted call
    at ``highest``."""
    @jax.jit
    def run(leaves, x, err):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(fn, leaves, x)
            return out, vjp(err)
    return run(leaves, x, err)


PLACES = {"attn_block": 1, "moe_block": 2, "kda_block": 3}
REF_KINDS = {"attn_block": "attention", "moe_block": "moe",
             "kda_block": "kda"}


# -- the chunked per-channel rule against the recurrence itself ---------------
def _rule_case(deep: bool, t: int = 256, widths=(8, 16)):
    """Two rows of three heads, keys of 8 and values of 16 (``widths``);
    per-channel decays of ``exp(g)`` between 0.37 and 1 a token, and with
    ``deep`` the tokens 64-127 at ``g`` in [-8, 0]: a chunk of 64 whose
    decays add up to about -256 a channel (``exp(-c)`` would overflow
    float32 there); ``beta`` on both sides of 1."""
    (b, h), (dk, dv) = (2, 3), widths
    k = jax.random.split(jax.random.key(13), 6)
    g = -jnp.exp(jax.random.uniform(k[3], (b, t, h, dk), minval=np.log(1e-3),
                                    maxval=0.0))
    if deep:
        g = g.at[:, 64:128].set(-8.0 * jax.random.uniform(k[5],
                                                          (b, 64, h, dk)))
    return (gdn.l2_norm(jax.random.normal(k[0], (b, t, h, dk))) * dk ** -0.5,
            gdn.l2_norm(jax.random.normal(k[1], (b, t, h, dk))),
            jax.random.normal(k[2], (b, t, h, dv)), g,
            jax.random.uniform(k[4], (b, t, h), minval=0.05, maxval=1.95))


def _recur(q, k, v, g, beta):
    return jax.vmap(lambda q, k, v, g, beta: reference.recurrence(
        q, k, v, jnp.exp(g), beta))(q, k, v, g, beta)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("deep", [False, True], ids=["shallow", "deep"])
def test_the_chunked_kda_rule_is_the_recurrence(chunk, deep):
    """Forward and every input's gradient.  Tolerances: float32 against
    float32 at ``highest``; the chunked form sums in another order and
    through the inverse of the chunk's system (1e-4 of the larger value,
    as for the scalar rule)."""
    args = _rule_case(deep)
    if deep:
        assert float(jnp.min(jnp.sum(args[3][:, 64:128], axis=1))) < -200

    def chunked(*a):
        return gdn.kda_rule(*a, chunk)

    @jax.jit
    def both(*a):
        with jax.default_matmul_precision("highest"):
            def grads(fn):
                return jax.grad(lambda *x: jnp.sum(jnp.sin(fn(*x))),
                                argnums=(0, 1, 2, 3, 4))(*a)
            return chunked(*a), _recur(*a), grads(chunked), grads(_recur)
    got, want, got_g, want_g = both(*args)
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0.5 and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * scale)
    for g_got, g_want in zip(got_g, want_g):
        assert np.isfinite(np.asarray(g_got)).all()
        np.testing.assert_allclose(
            g_got, g_want, rtol=2e-4,
            atol=2e-5 * float(jnp.max(jnp.abs(g_want))))


def test_a_decay_the_same_over_a_head_is_the_scalar_rule():
    """With ``g`` equal over a head's K channels the per-channel form is
    the gated delta rule's (``gdn.delta_rule_chunked``), to rounding."""
    q, k, v, g, beta = _rule_case(False)
    head = g[..., :1]
    with jax.default_matmul_precision("highest"):
        got = gdn.kda_rule(q, k, v, jnp.broadcast_to(head, g.shape), beta, 64)
        want = gdn.delta_rule_chunked(q, k, v, head[..., 0], beta, 64)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * float(jnp.max(jnp.abs(want))))
    # and a per-channel decay is not its head's mean
    mean = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    with jax.default_matmul_precision("highest"):
        full, averaged = (gdn.kda_rule(q, k, v, d, beta, 64)
                          for d in (g, mean))
    assert float(jnp.max(jnp.abs(full - averaged))) > 0.05


def test_a_length_or_chunk_the_rule_cannot_take_is_refused():
    args = _rule_case(False, t=96)
    with pytest.raises(ValueError, match="no multiple of the delta rule's"):
        gdn.kda_rule(*args, 64)
    with pytest.raises(ValueError, match="no multiple of the sub-chunk"):
        gdn.kda_rule(*args, 24)
    with pytest.raises(ValueError, match="no multiple of the delta rule's"):
        layer_units(config())[3].leaf_shapes((2, 48, 64))


# -- the kernels (Pallas, interpreted here) against the chunked form ----------
@pytest.fixture
def kernels(monkeypatch):
    """The rule's kernel tier: the Pallas kernels in interpret mode (set as
    ``test_linear_lm.py``'s ``form`` sets it)."""
    from znicz_tpu.ops import tuning
    monkeypatch.setattr(tuning, "_INTERPRET", True)
    assert gdn.rule_route() == "kernel"


@pytest.mark.parametrize("chunk,widths,deep", [
    (64, (8, 16), True), (32, (16, 8), False)],
    ids=["deep-k8-v16", "shallow-k16-v8"])
def test_the_kernels_are_the_chunked_form(kernels, chunk, widths, deep):
    """Forward and every input's gradient of the kernels against the
    ``jax.numpy`` chunked form, both float32 at ``highest``: the same algebra
    summed in another order (1e-5 of the larger value).  With ``deep`` the
    second chunk's decays add up below -200, and every value is finite."""
    args = _rule_case(deep, t=128, widths=widths)
    if deep:
        assert float(jnp.min(jnp.sum(args[3][:, 64:128], axis=1))) < -200

    @jax.jit
    def both(*a):
        with jax.default_matmul_precision("highest"):
            def run(rule):
                def fn(*x):
                    return rule(*x, chunk)
                return fn(*a), jax.grad(lambda *x: jnp.sum(jnp.sin(fn(*x))),
                                        argnums=(0, 1, 2, 3, 4))(*a)
            return run(gdn.kda_rule), run(gdn.kda_rule_chunked)
    (got, got_g), (want, want_g) = both(*args)
    for g_got, g_want in zip((got, *got_g), (want, *want_g)):
        assert np.isfinite(np.asarray(g_got)).all()
        np.testing.assert_allclose(
            g_got, g_want, rtol=1e-5,
            atol=1e-5 * float(jnp.max(jnp.abs(g_want))))


def test_the_states_the_forward_keeps_are_the_chunked_forms(kernels):
    """The state each chunk starts from and the inverse of its system, as
    the forward kernel keeps them for the backward, against the state
    :func:`gdn.kda_group` carries a chunk at a time (a chunk of decays
    below -200 among them) and the inverse of its chunked form's system."""
    q, k, v, g, beta = _rule_case(True, t=128)
    chunk, (bsz, t, h, dk) = 32, q.shape
    nc = t // chunk

    def head_major(a):
        return a.transpose(0, 2, 1, 3)

    def chunk_of(a, n):     # (B, T, H, .) -> (1, B, H, L, .)
        return head_major(a[:, n * chunk:(n + 1) * chunk])[None]
    with jax.default_matmul_precision("highest"):
        _, states, inverses = gdn._kda_fwd_call(
            head_major(q), head_major(k), jnp.cumsum(head_major(g).reshape(
                bsz, h, nc, chunk, dk), axis=-2).reshape(bsz, h, t, dk),
            head_major(v * beta[..., None]),
            beta.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2),
            jnp.float32, save=True)
        state = jnp.zeros(states.shape[:2] + states.shape[3:])
        for n in range(nc):
            np.testing.assert_allclose(
                states[:, :, n], state, rtol=1e-5,
                atol=1e-5 * float(jnp.max(jnp.abs(state))) + 1e-30)
            state, _ = gdn.kda_group(state, *(chunk_of(a, n) for a in (
                q, k, v, g, beta[..., None])), jnp.float32)
            qn, kn, gn, bn = (chunk_of(a, n)[0] for a in (
                q, k, g, beta[..., None]))
            system = jnp.tril(gdn.decayed_products(
                kn * bn, kn, jnp.cumsum(gn, axis=-2), lambda a, b: jnp.einsum(
                    "...mik,...mjk->...mij", a, b)), -1)
            np.testing.assert_allclose(inverses[:, :, n],
                                       gdn.unit_lower_inverse(system),
                                       rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(states[:, :, -1]))) > 0.1


def test_the_routes_name_the_kernels_the_step_traces(kernels):
    """Under the kernel tier ``rule_routes`` reads ``kda:kernel`` and the
    traced training step calls the two kernels once a KDA layer (a
    ``custom_vjp``'s call holds its forward's jaxpr beside the primal's),
    each kernel traced once (its call's jaxpr is shared), and no scalar
    rule."""
    cfg, spec, weights, x, y = setup()
    assert fused.rule_routes(spec) == "kda:kernel"
    text = str(jax.make_jaxpr(lambda p, a, b: fused.grad_minibatch(
        spec, p, a, b))(weights, x[0], y[0]))
    assert text.count("name=_kda_fwd_call") == 2 * 3
    assert text.count("name=_kda_bwd_call") == 3
    # the forward with and without the states it keeps, and the backward
    assert text.count("name=pallas_kda_rule_fwd") == 2
    assert text.count("name=pallas_kda_rule_bwd") == 1
    assert "pallas_delta_rule" not in text


# -- each kind against the reference -------------------------------------------
@pytest.mark.parametrize("shares", [1, 4], ids=["whole", "share"])
@pytest.mark.parametrize("kind", ["kda_block", "attn_block", "moe_block"])
def test_a_block_and_its_gradients_are_the_references(kind, shares):
    """Whole and as chip 0's share of four.  Tolerances: float32 against
    float32 at ``highest``; the chunked rule and the sorted experts sum in
    other orders (1e-4 of the larger value)."""
    cfg = config(shares)
    leaves, x = _leaves(cfg, PLACES[kind]), _stream()
    ref = reference.make_blocks(cfg)[REF_KINDS[kind]]
    fwd = kind_cfg(cfg, kind)
    call = fused.SEQUENCE_FWD[kind]
    err = jax.random.normal(jax.random.key(9), x.shape)
    want, want_g = _value_and_grads(ref, leaves, x, err)
    got, got_g = _value_and_grads(lambda ls, h: call(ls, h, fwd)[0],
                                  leaves, x, err)
    added = float(jnp.max(jnp.abs(want - x)))
    assert added > 1e-2
    np.testing.assert_allclose(got - x, want - x, rtol=1e-4,
                               atol=1e-4 * added)
    for g_got, g_want in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(
            g_got, g_want, rtol=2e-4,
            atol=1e-4 * float(jnp.max(jnp.abs(g_want))) + 1e-12)
    counters = jax.jit(lambda ls, h: call(ls, h, fwd)[1])(leaves, x)
    if kind == "kda_block":
        assert {k: int(v) for k, v in counters.items()} == {
            "kda_tokens": 2 * 64}
    elif kind == "attn_block":
        assert counters == {}


@pytest.mark.parametrize("fault,kind", [
    ("head_mean_decay", "kda"), ("beta_one", "kda"), ("no_kda_gate", "kda"),
    ("no_attn_gate", "attention"), ("no_shared", "moe")])
def test_each_planted_fault_of_the_reference_changes_its_block(fault, kind):
    """The reference's faults (its ``VARIANTS``, which the chip's study of
    the limits reads) change what they say they change."""
    cfg = config()
    place = {"attention": 1, "moe": 2, "kda": 3}[kind]
    leaves, x = _leaves(cfg, place), _stream()
    want, got = (jax.jit(reference.make_blocks(cfg, **kw)[kind])(leaves, x)
                 for kw in ({}, {fault: True}))
    added = float(jnp.max(jnp.abs(want - x)))
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2 * added, fault


def test_a_large_gate_is_the_ungated_mixer():
    """``sigmoid`` of a large positive ``n Wgate`` is 1: the gated mixer is
    then the mixer without its gate (inputs and gains positive, so that
    every ``n Wgate`` is)."""
    cfg = config()
    fwd = kind_cfg(cfg, "attn_block")
    g1, wq, wk, wv, wo, _ = _leaves(cfg, 1)
    x = jnp.abs(_stream()) + 0.1
    gated = (jnp.abs(g1), wq, wk, wv, wo, jnp.full_like(wq, 50.0))
    got, _ = fused.SEQUENCE_FWD["attn_block"](gated, x, fwd)
    plain = {k: v for k, v in fwd.items() if k != "gate"}
    want, _ = fused.SEQUENCE_FWD["attn_block"](gated[:5], x, plain)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # at a gate of zero it halves what the mixer adds
    half = (*gated[:5], jnp.zeros_like(wq))
    got, _ = fused.SEQUENCE_FWD["attn_block"](half, x, fwd)
    np.testing.assert_allclose(got - x, 0.5 * (want - x), rtol=1e-4,
                               atol=1e-6)
    # a gate the block's config does not name is refused
    with pytest.raises(ValueError, match="gate None and 6 leaves"):
        fused.SEQUENCE_FWD["attn_block"](gated, x, plain)


# -- what this cut slices: heads, experts, shared columns ------------------------
def _share_leaves(whole, kind: str, share: int, shares: int, cfg: dict):
    """Share ``share`` of ``shares``' slices of the uncut layer's leaves, as
    a chip of the tensor- and expert-parallel group holds them."""
    def cut(a, axis, n):
        return jax.lax.slice_in_dim(a, share * n, (share + 1) * n, axis=axis)
    if kind == "moe_block":
        g2, wr, wg, wu, wd, sg, su, sd = whole
        e, c = wg.shape[0] // shares, sg.shape[1] // shares
        return (g2, wr, cut(wg, 0, e), cut(wu, 0, e), cut(wd, 0, e),
                cut(sg, 1, c), cut(su, 1, c), cut(sd, 0, c))
    if kind == "attn_block":
        g1, wq, wk, wv, wo, wgate = whole
        q, kv = wq.shape[1] // shares, wk.shape[1] // shares
        return (g1, cut(wq, 1, q), cut(wk, 1, kv), cut(wv, 1, kv),
                cut(wo, 0, q), cut(wgate, 1, q))
    (g1, wq, wk, wv, wb, wfa, wfb, wga, wgb, bg, conv_q, conv_k, conv_v,
     a_log, dt_bias, g_o, wo) = whole
    h = wb.shape[1] // shares
    c = wq.shape[1] // shares
    return (g1, cut(wq, 1, c), cut(wk, 1, c), cut(wv, 1, c), cut(wb, 1, h),
            wfa, cut(wfb, 1, c), wga, cut(wgb, 1, c), cut(bg, 0, c),
            cut(conv_q, 1, c), cut(conv_k, 1, c), cut(conv_v, 1, c),
            cut(a_log, 0, h), cut(dt_bias, 0, c), g_o, cut(wo, 0, c))


@pytest.mark.parametrize("kind", ["kda_block", "attn_block", "moe_block"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """Four shares, each a quarter of the heads (KDA and attention, with
    their gates' columns) or of the experts and of the shared expert's
    columns: the sum of what each adds to the stream (the partial sums Wo
    and Wd hand on), with the residual counted once, is the uncut reference
    layer's output.  The router and the low-rank gates' first products are
    whole on every share, as on every chip of the group."""
    whole = config(shares=1)
    leaves, x = _leaves(whole, PLACES[kind]), _stream(6)
    call = fused.SEQUENCE_FWD[kind]

    @jax.jit
    def by_shares(leaves, x):
        with jax.default_matmul_precision("highest"):
            return sum(call(_share_leaves(leaves, kind, s, 4, whole), x,
                            kind_cfg(config(4, s), kind))[0] - x
                       for s in range(4))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference.make_blocks(whole)[REF_KINDS[kind]])(
            leaves, x) - x
    added = float(jnp.max(jnp.abs(want)))
    assert added > 1e-2
    np.testing.assert_allclose(by_shares(leaves, x), want, rtol=1e-4,
                               atol=1e-4 * added)


def test_the_cut_is_the_counted_share():
    """The model file counts the chip's share of the published layer."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "solar-open2-250b.json")) as fh:
        real = json.load(fh)
    traffic = {"seq_len": 4096}
    shapes = model.param_shapes(real)
    sizes = [sum(int(np.prod(sh)) for sh in leaves) for leaves in shapes]
    assert sizes[1] == 27_262_976 + 4096            # gated attention + g1
    assert sizes[3] == 35_221_648 + 4096            # KDA + g1
    assert sizes[2] == 131_072_000 + 4096           # router, shared, 8 experts
    assert model.flops(real, traffic)["params"] == 858_579_376
    assert model.kinds(real) == ["full_attention", "kda", "kda", "kda"]
    parts = model.flops_by_part(real, traffic)
    assert 5.4e6 < parts["kda_rule"] / 4096 < 5.6e6


def test_a_layout_that_does_not_add_up_is_refused():
    cfg = config()
    cfg["num_key_value_heads"] = 2
    cfg["deployment"]["kv_heads_held"] = [0, 2]
    with pytest.raises(ValueError, match="4 query heads over 2"):
        model.sizes(cfg)
    cfg = config()
    cfg["deployment"]["experts_held"] = [28, 8]
    with pytest.raises(ValueError, match="experts_held"):
        model.sizes(cfg)
    cfg = config()
    cfg["linear_attn_config"]["num_kv_heads"] = 1
    with pytest.raises(ValueError, match="one key head"):
        model.sizes(cfg)
    cfg = config()
    cfg["use_gqa_gate"] = False
    with pytest.raises(ValueError, match="use_gqa_gate"):
        model.sizes(cfg)


# -- the program ------------------------------------------------------------------
def test_three_steps_follow_the_reference():
    """Loss and every leaf's gradient against the reference's ``jax.grad``,
    then three steps of the trainer against ``follow``.  Tolerances:
    float32 against float32 at ``highest`` (the program's operands are
    float32 here); 5e-4 of a leaf's largest element for the sums in other
    orders (the chunked rule, the sorted experts)."""
    cfg, spec, weights, x, y = setup()
    ref = reference.follow(cfg, copy.deepcopy(weights), x, y)
    want = jax.jit(jax.grad(lambda ps: jnp.mean(reference.token_losses(
        cfg, ps, x[0], y[0]))))([tuple(ls) for ls in weights])
    grads, _ = jax.jit(lambda p, a, b: fused.grad_minibatch(
        spec, p, a, b))(weights, x[0], y[0])
    assert [len(g) for g in grads] == LEAVES
    for got_layer, want_layer, ref_norms in zip(grads, want,
                                                ref["grad_norms"]):
        for got, exp, norm in zip(got_layer, want_layer, ref_norms):
            np.testing.assert_allclose(
                got, exp, rtol=5e-4,
                atol=1e-5 * float(jnp.max(jnp.abs(exp))) + 1e-12)
            np.testing.assert_allclose(norm, np.linalg.norm(exp),
                                       rtol=1e-4, atol=1e-12)
    p0 = jax.tree.map(np.asarray, weights)
    trainer = fused.FusedTrainer(
        spec=spec, params=weights,
        vels=jax.tree.map(jnp.zeros_like, weights))
    rows = jnp.concatenate(list(x)), jnp.concatenate(list(y))
    losses = [float(trainer.train_epoch(
        *rows, np.arange(2 * s, 2 * s + 2), 2, ctr_base=2 * s)["loss"][0])
        for s in range(3)]
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-5)
    change = [tuple(float(np.linalg.norm(np.asarray(a) - a0))
                    for a, a0 in zip(ls, ls0))
              for ls, ls0 in zip(trainer.params, p0)]
    for got, exp in zip(change, ref["change_norms"]):
        np.testing.assert_allclose(got, exp, rtol=5e-4, atol=1e-9)


def test_a_head_mean_decay_fails_the_comparison():
    """The check that decides ``correct``, at the tiny cell's limits: the
    reference's own run reads within them against itself, and a run whose
    KDA heads take their channels' mean decay reads beyond one of them
    (``out_grad_diff``, on the last KDA block's ``dt_bias``)."""
    cfg, _, weights, x, y = setup()
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "limits",
                           "tiny-solar-open2-train.json")) as fh:
        limits = json.load(fh)["limits"]
    sound = reference.follow(cfg, copy.deepcopy(weights), x, y)
    faulty = reference.follow(cfg, copy.deepcopy(weights), x, y,
                              head_mean_decay=True)
    leaf = model.output_leaf(cfg)
    ok, _ = correct.judge({**correct.first_steps_numbers(
        sound, sound, leaf), "rows_misfed": 0}, limits)
    assert ok
    numbers = correct.first_steps_numbers(faulty, sound, leaf)
    ok, checks = correct.judge({**numbers, "rows_misfed": 0}, limits)
    assert not ok
    assert numbers["out_grad_diff"] > 10 * limits["out_grad_diff"], checks


def test_the_counter_and_the_routes_read_what_they_should():
    cfg, spec, weights, x, y = setup()
    got = jax.jit(lambda p, a, b: fused.eval_minibatch(spec, p, a, b))(
        weights, x[0], y[0])
    assert int(got["kda_tokens"]) == 3 * x[0].size        # KDA layers
    assert int(got["tokens"]) == x[0].size
    assert int(got["moe_assignments"]) == 4 * 4 * x[0].size
    assert "gdn_tokens" not in got and "ssm_tokens" not in got
    assert fused.mixer_routes(spec) == "kda:3 full:1"
    assert fused.attn_routes(spec) == "window:0 full:1"
    assert fused.rule_routes(spec) == "kda:chunked"
    assert fused.COUNTERS["kda_tokens"][0] == "sum"
    # absent, never 0, without the kind
    import test_linear_lm as linear
    _, other, w, lx, ly = linear.setup()
    counted = jax.jit(lambda p, a, b: fused.eval_minibatch(
        other, p, a, b))(w, lx[0], ly[0])
    assert "kda_tokens" not in counted
    assert fused.rule_routes(other) == "kernel:0 chunked:3"


def test_the_scopes_are_in_the_compiled_text():
    cfg, spec, weights, x, y = setup()
    text = jax.jit(lambda p, a, b: fused.grad_minibatch(
        spec, p, a, b)).lower(weights, x[0], y[0]).as_text(debug_info=True)
    for scope in ("fwd/L03.kda_block/kda_block/checkpoint/short_conv",
                  "fwd/L03.kda_block/kda_block/checkpoint/kda_gates",
                  "fwd/L03.kda_block/kda_block/kda_rule/while",
                  "fwd/L03.kda_block/kda_block/kda_gates",
                  "bwd/L07.kda_block", "fwd/L01.attn_block/attn_gate",
                  "fwd/L01.attn_block/scores", "fwd/L02.moe_block/route",
                  "fwd/L02.moe_block/shared_expert"):
        assert scope in text, scope
    assert "L01.attn_block/rope" not in text            # no rotary tables


def test_the_start_record_names_the_mixers(tmp_path):
    """Through the Launcher's workflow: the start line and every timeline
    row carry ``mixer_routes`` and ``rule_routes``, the ``train_step`` rows
    ``kda_tokens``, and the gauge holds the last epoch's count."""
    from znicz_tpu.backends import Device
    from znicz_tpu.config import root
    from znicz_tpu.models import decoder_lm
    from znicz_tpu.telemetry import flightrecorder
    from znicz_tpu.telemetry.registry import REGISTRY
    saved = root.decoder_lm.to_dict()
    root.decoder_lm.update({
        "layer_types": ["full", "kda", "kda", "kda"], "attn_gate": True,
        "shared_width": 16, "positional": "nope"})
    try:
        wf = decoder_lm.DecoderLMWorkflow()
        wf.initialize(device=Device.create("xla"))
        path = str(tmp_path / "timeline.jsonl")
        wf.train(fused=True, max_epochs=1, timeline_jsonl=path)
    finally:
        root.decoder_lm.update(saved)
    rows = [json.loads(line) for line in open(path)]
    assert rows and all(r["mixer_routes"] == "kda:3 full:1"
                        and r["rule_routes"] == "kda:chunked"
                        and r["kda_tokens"] == 3 * r["tokens"] > 0
                        for r in rows)
    step = [r for r in flightrecorder.RECORDER.snapshot()["recent"]
            if r.get("kind") == "train_step"][-1]
    assert step["kda_tokens"] == rows[-1]["kda_tokens"]
    assert REGISTRY.gauge("train_kda_tokens").value() == step["kda_tokens"]


def test_the_leaves_are_the_units_and_the_model_files():
    cfg = config()
    seen = set()
    for unit, shapes in zip(layer_units(cfg), model.param_shapes(cfg)):
        got = unit.leaf_shapes((2, 64, 64))
        assert [tuple(got[k]) for k in unit.LEAVES] == list(shapes)
        seen.add(unit.KIND)
        if unit.KIND == "kda_block":
            assert unit.LEAVES == gdn.KDA_LEAVES
        if unit.KIND == "attn_block":
            assert unit.LEAVES[-1] == "wgate"
    assert seen == {"embed", "attn_block", "kda_block", "moe_block",
                    "lm_head"}

