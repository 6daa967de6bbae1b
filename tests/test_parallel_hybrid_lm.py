"""The parallel hybrid (``parallel_block``: attention and a Mamba-2 mixer on
one normed input, beside an ``mlp_block`` with a gate multiplier) at test
widths on the CPU: d 64, four layers of 2 query heads over 1 key/value head
of 16 and 2 Mamba heads of 8 (half of one of the model's 2 groups of 4
heads, state 16, chunk 16), feed-forward columns 24 of 96, vocabulary 256,
T 64: one chip's share of a 4-way tensor-parallel layer.  The fused trainer
is held against the benchmark's plain reference
(``benchmark/lib/falcon_h1_reference.py``; it imports nothing of the
program) with seeded weights."""

import copy
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import falcon_h1_model as model           # noqa: E402
from benchmark.lib import falcon_h1_reference as reference   # noqa: E402
from znicz_tpu.nn import decoder as units                    # noqa: E402
from znicz_tpu.ops import hybrid, ssm                        # noqa: E402
from znicz_tpu.parallel import fused                         # noqa: E402

TRAFFIC = {"seq_len": 64, "minibatch": 2, "n_train": 12, "n_valid": 4,
           "n_test": 0}
SEED = 20261015
#: the leaves a layer of each kind holds, in the layer list's order
LEAVES = [1, *[13, 4] * 4, 2]
#: the counts the model holds whole, and the deployment's name for each
WHOLE = {"num_attention_heads": ("attention_heads_held", 8),
         "num_key_value_heads": ("kv_heads_held", 4),
         "mamba_n_heads": ("mamba_heads_held", 8)}


def config(shares: int = 4, share: int = 0) -> dict:
    """``tiny-falcon-h1`` as the harness's tests have it (``shares`` 4);
    ``shares`` 1: every head, both groups and all 96 columns held; 2: half
    of each, a whole group of B and C a share."""
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "tiny-falcon-h1.json")) as fh:
        cfg = json.load(fh)
    if shares == 4 and share == 0:
        return cfg
    for key, (name, whole) in WHOLE.items():
        count = whole // shares
        cfg[key] = count
        cfg["deployment"][name] = [share * count, count]
    cfg["mamba_n_groups"] = max(1, 2 // shares)
    cols = int(cfg["intermediate_size"]) // shares
    cfg["deployment"]["mlp_columns_held"] = [share * cols, cols]
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
    configuration (the build is a jitted call of its own: seconds on a
    CPU), as a copy that a trainer may donate."""
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


def _stream(seed: int = 5):
    """A stream of the size the embedding hands on (its multiplier times
    rows of 0.02), so that a block's output shows beside it."""
    return 0.1 * jax.random.normal(jax.random.key(seed), (2, 64, 64),
                                   jnp.float32)


def _leaves(cfg, place: int = 1):
    """The leaves of layer ``place`` of the configuration's tree, with a
    skip ``D``, gains and biases that are not all ones or zeros, so that
    each is seen; the projections that write into the stream 100 times as
    large (``Wd`` 300: its multiplier is 0.011), so that what the block adds
    stands out of the stream's float32 rounding, and ``Wq``, ``Wk`` 30
    times, so that the scores matter."""
    leaves = list(_weights(cfg)[place])
    for at, leaf in enumerate(leaves):
        if leaf.ndim == 1:
            leaves[at] = leaf + 0.3 * jax.random.normal(
                jax.random.key(at), leaf.shape)
    if len(leaves) == model.MLP_LEAVES:
        leaves[3] = 300.0 * leaves[3]
    else:
        for at in (4, 12):                    # wo, w_out
            leaves[at] = 100.0 * leaves[at]
        # scores of order one at these widths too, not uniform attention
        for at in (1, 2):                     # wq, wk
            leaves[at] = 30.0 * leaves[at]
        # steps near softplus(0) rather than the starting point's 0.001 to
        # 0.1, so that the scan's term is not lost beside D x
        leaves[8] = 0.3 * jax.random.normal(jax.random.key(8),
                                            leaves[8].shape)
    return tuple(leaves)


def _value_and_grads(fn, leaves, x, err):
    """``fn(leaves, x)`` and its gradients at ``err``, in one jitted call
    at ``highest``."""
    @jax.jit
    def run(leaves, x, err):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(fn, leaves, x)
            return out, vjp(err)
    return run(leaves, x, err)


REF_KINDS = {"parallel_block": "parallel", "mlp_block": "mlp"}


# -- the scan: groups against the recurrence ----------------------------------
def _scan_case(groups: int = 2, t: int = 64):
    k = jax.random.split(jax.random.key(11), 5)
    b, h, p, n = 2, 4, 8, 16
    dt = jax.nn.softplus(jax.random.normal(k[0], (b, t, h)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[1], (h,), minval=-2.0, maxval=1.0))
    return (jax.random.normal(k[2], (b, t, h, p)), dt, a,
            jax.random.normal(k[3], (b, t, groups * n)),
            jax.random.normal(k[4], (b, t, groups * n)))


def _per_head(bc, h: int, groups: int):
    b, t, gn = bc.shape
    return jnp.repeat(bc.reshape(b, t, groups, gn // groups), h // groups,
                      axis=2)


def test_the_grouped_scan_is_the_recurrence():
    """Two groups of two heads each reading its own B and C: the program's
    chunked scan, the reference's own chunked form and the recurrence a
    token at a time agree; the boundary fault does not."""
    x, dt, a, b_in, c_in = _scan_case()
    h = x.shape[2]
    b_h, c_h = _per_head(b_in, h, 2), _per_head(c_in, h, 2)

    @jax.jit
    def scans(x, dt, a, b_in, c_in, b_h, c_h):
        with jax.default_matmul_precision("highest"):
            def each(x, dt, b, c):
                dx, log_decay = dt[:, :, None] * x, dt * a
                return (reference.recurrence(dx, jnp.exp(log_decay), b, c),
                        reference.chunked_scan(dx, log_decay, b, c, 16),
                        reference.chunked_scan(dx, log_decay, b, c, 16,
                                               boundary_fault=True))
            return (ssm.grouped_scan(x, dt, a, b_in, c_in, 16, 2),
                    *jax.vmap(each)(x, dt, b_h, c_h))
    got, want, chunked, faulty = scans(x, dt, a, b_in, c_in, b_h, c_h)
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0.5
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(chunked, want, rtol=1e-4, atol=1e-5 * scale)
    assert float(jnp.max(jnp.abs(faulty - want))) > 1e-2 * scale
    # a group is not the other group's: one group for all is another scan
    one = ssm.grouped_scan(x, dt, a, b_in[..., :16], c_in[..., :16], 16, 1)
    assert float(jnp.max(jnp.abs(one - got))) > 1e-2 * scale


# -- each kind against the reference -------------------------------------------
@pytest.mark.parametrize("shares", [1, 4], ids=["whole", "share"])
@pytest.mark.parametrize("kind,place", [("parallel_block", 1),
                                        ("mlp_block", 2)])
def test_a_block_and_its_gradients_are_the_references(kind, place, shares):
    """Whole (8 query heads over 4, 8 Mamba heads in 2 groups: the gated
    norm a group at a time) and as chip 0's share of four.  Tolerances:
    float32 against float32 at ``highest``; the chunked scans and the
    group norms sum in other orders (1e-4 of the larger value)."""
    cfg = config(shares)
    leaves, x = _leaves(cfg, place), _stream()
    ref = reference.make_blocks(cfg)[REF_KINDS[kind]]
    call = fused.SEQUENCE_FWD[kind]
    fwd = kind_cfg(cfg, kind)
    err = jax.random.normal(jax.random.key(9), x.shape)
    want, want_g = _value_and_grads(ref, leaves, x, err)
    got, got_g = _value_and_grads(lambda ls, h: call(ls, h, fwd)[0],
                                  leaves, x, err)
    added = float(jnp.max(jnp.abs(want - x)))
    assert added > 1e-3
    np.testing.assert_allclose(got - x, want - x, rtol=1e-4,
                               atol=1e-4 * added)
    counters = jax.jit(lambda ls, h: call(ls, h, fwd)[1])(leaves, x)
    assert {k: int(v) for k, v in counters.items()} == (
        {"parallel_tokens": 2 * 64} if kind == "parallel_block" else {})
    for g_got, g_want in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(
            g_got, g_want, rtol=2e-4,
            atol=1e-4 * float(jnp.max(jnp.abs(g_want))) + 1e-12)


#: the reference's sound block of each kind on ``_leaves``, made once
_SOUND: dict = {}


@pytest.mark.parametrize("fault,kind,place", [
    ("sequential", "parallel", 1), ("no_ssm_multipliers", "parallel", 1),
    ("no_key_multiplier", "parallel", 1), ("norm_before_gate", "parallel", 1),
    ("no_out_multipliers", "parallel", 1), ("rotary_fault", "parallel", 1),
    ("gate_multiplier_outside", "mlp", 2)])
def test_each_planted_fault_of_the_reference_changes_its_block(fault, kind,
                                                               place):
    """The reference's faults (its ``VARIANTS``, which the chip's study of
    the limits reads) change what they say they change; the boundary
    fault is held by the scan's own test above."""
    cfg = config()
    leaves, x = _leaves(cfg, place), _stream()
    if kind not in _SOUND:
        _SOUND[kind] = jax.jit(reference.make_blocks(cfg)[kind])(leaves, x)
    want = _SOUND[kind]
    got = jax.jit(reference.make_blocks(cfg, **{fault: True})[kind])(leaves,
                                                                    x)
    added = float(jnp.max(jnp.abs(want - x)))
    assert float(jnp.max(jnp.abs(got - want))) > 1e-3 * added, fault


#: each multiplier of the model and where the program's config holds it
MULTIPLIERS = [
    ("parallel_block", ("ssm", "in_scales", 0)),
    ("parallel_block", ("ssm", "in_scales", 1)),
    ("parallel_block", ("ssm", "in_scales", 2)),
    ("parallel_block", ("ssm", "in_scales", 3)),
    ("parallel_block", ("ssm", "in_scales", 4)),
    ("parallel_block", ("attn", "score_scale")),
    ("parallel_block", ("attn_scale",)),
    ("parallel_block", ("ssm_scale",)),
    ("mlp_block", ("gate_scale",)),
    ("mlp_block", ("scale",))]


def _without(cfg: dict, where: tuple) -> dict:
    """The fused config with one multiplier set to 1 (the score scale to
    the plain ``1 / sqrt(head_dim)``: the key multiplier dropped)."""
    cfg = dict(cfg)
    if len(where) == 1:
        cfg[where[0]] = None
        return cfg
    inner = dict(cfg[where[0]])
    if where[1] == "in_scales":
        scales = list(inner["in_scales"])
        scales[where[2]] = 1.0
        inner["in_scales"] = tuple(scales)
    else:
        inner[where[1]] = None
    cfg[where[0]] = tuple(sorted(inner.items()))
    return cfg


@functools.partial(jax.jit, static_argnums=(0, 3))
def _block(kind, leaves, x, cfg_items):
    """A kind's output under a config given as its sorted items: one
    compile a config, whichever test asks."""
    return fused.SEQUENCE_FWD[kind](leaves, x, dict(cfg_items))[0]


@pytest.mark.parametrize("kind,where", MULTIPLIERS,
                         ids=["-".join(map(str, w)) for _, w in MULTIPLIERS])
def test_each_multiplier_is_live(kind, where):
    cfg = config()
    place = 1 if kind == "parallel_block" else 2
    leaves, x = _leaves(cfg, place), _stream()
    full = kind_cfg(cfg, kind)
    want, got = (_block(kind, leaves, x, tuple(sorted(c.items())))
                 for c in (full, _without(full, where)))
    added = float(jnp.max(jnp.abs(want - x)))
    assert float(jnp.max(jnp.abs(got - want))) > 1e-3 * added, where


def test_the_model_multipliers_reach_the_program():
    """The embedding's and the head's multipliers, and each block's, as the
    layer list hands them to the kinds."""
    cfg = config()
    got = {u.KIND: u.fused_config() for u in layer_units(cfg)}
    assert got["embed"]["scale"] == cfg["embedding_multiplier"]
    assert got["lm_head"]["scale"] == cfg["lm_head_multiplier"]
    assert got["mlp_block"]["gate_scale"] == cfg["mlp_multipliers"][0]
    assert got["mlp_block"]["scale"] == cfg["mlp_multipliers"][1]
    par = got["parallel_block"]
    assert par["attn_scale"] == cfg["attention_out_multiplier"]
    assert par["ssm_scale"] == cfg["ssm_out_multiplier"]
    np.testing.assert_allclose(
        dict(par["ssm"])["in_scales"],
        [cfg["ssm_in_multiplier"] * m for m in cfg["ssm_multipliers"]])
    np.testing.assert_allclose(dict(par["attn"])["score_scale"],
                               cfg["key_multiplier"] / 4.0, rtol=1e-15)


# -- what this cut slices: heads, groups, columns --------------------------------
def _share_leaves(whole_leaves, kind: str, share: int, shares: int,
                  cfg_whole: dict):
    """Share ``share`` of ``shares``' slices of the uncut layer's leaves, as
    a chip of a tensor-parallel group holds them."""
    def cut(a, axis, n):
        return jax.lax.slice_in_dim(a, share * n, (share + 1) * n, axis=axis)
    if kind == "mlp_block":
        g2, wg, wu, wd = whole_leaves
        f = wg.shape[1] // shares
        return (g2, cut(wg, 1, f), cut(wu, 1, f), cut(wd, 0, f))
    (g1, wq, wk, wv, wo, w_in, conv_w, conv_b, dt_bias, a_log, d_skip,
     g_m, w_out) = whole_leaves
    hd = int(cfg_whole["head_dim"])
    q = 8 * hd // shares
    kv = 4 * hd // shares
    h, p, n = 8 // shares, int(cfg_whole["mamba_d_head"]), int(
        cfg_whole["mamba_d_state"])
    # a share of half a group reads that group's B and C, and its taps
    d_in, g, first = 8 * p, max(1, 2 // shares), share * h // 4
    z, xs, b, c, dt = jnp.split(w_in, np.cumsum(
        [d_in, d_in, 2 * n, 2 * n]), axis=1)
    taps = [jnp.split(a, np.cumsum([d_in, 2 * n]), axis=-1)
            for a in (conv_w, conv_b)]

    def groups(a, axis):
        return jax.lax.slice_in_dim(a, first * n, (first + g) * n,
                                    axis=axis)

    def held_taps(parts):
        tx, tb, tc = parts
        return jnp.concatenate([cut(tx, -1, h * p), groups(tb, -1),
                                groups(tc, -1)], axis=-1)
    return (g1, cut(wq, 1, q), cut(wk, 1, kv), cut(wv, 1, kv), cut(wo, 0, q),
            jnp.concatenate([cut(z, 1, h * p), cut(xs, 1, h * p),
                             groups(b, 1), groups(c, 1),
                             cut(dt, 1, h)], axis=1),
            held_taps(taps[0]), held_taps(taps[1]), cut(dt_bias, 0, h),
            cut(a_log, 0, h), cut(d_skip, 0, h), cut(g_m, 0, h * p),
            cut(w_out, 0, h * p))


@pytest.mark.parametrize("kind,place", [("parallel_block", 1),
                                        ("mlp_block", 2)])
def test_the_shares_add_up_to_the_uncut_layer(kind, place):
    """Two shares, each a whole group of B and C (4 of the 8 Mamba heads,
    4 of 8 query heads over 2 of 4 key/value heads, 48 of 96 columns): the
    sum of what each adds to the stream, with the residual (which every
    chip computes alike) counted once, is the uncut reference layer's
    output.  Each share's gated norm is then over whole groups; with
    shares of half a group (the cell's) the norm's mean square is the
    held channels' and the parts do not add up without the exchange."""
    whole = config(shares=1)
    leaves, x = _leaves(whole, place), _stream(6)
    call = fused.SEQUENCE_FWD[kind]

    def added_by_shares(shares):
        @jax.jit
        def run(leaves, x):
            with jax.default_matmul_precision("highest"):
                return sum(call(_share_leaves(leaves, kind, s, shares,
                                              whole), x,
                                kind_cfg(config(shares, s), kind))[0] - x
                           for s in range(shares))
        return run(leaves, x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference.make_blocks(whole)[REF_KINDS[kind]])(
            leaves, x) - x
    added = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(added_by_shares(2), want, rtol=1e-4,
                               atol=1e-4 * added)
    if kind == "parallel_block":
        # half a group a share: not the uncut layer (the norm's exchange)
        assert float(jnp.max(jnp.abs(added_by_shares(4) - want))) \
            > 1e-3 * added


def test_a_layout_that_does_not_add_up_is_refused():
    with pytest.raises(ValueError, match="do not divide into 3 groups"):
        ssm.groups_held(8, 2, 3)
    with pytest.raises(ValueError, match="straddle"):
        ssm.groups_held(8, 3, 2)
    with pytest.raises(ValueError, match="straddle"):
        units.ParallelBlock(None, heads=2, kv_heads=1, head_dim=16,
                            ssm_heads=8, ssm_heads_held=6, ssm_head_dim=8,
                            state=16, groups=2)
    with pytest.raises(ValueError, match="3 query heads over 2"):
        units.ParallelBlock(None, heads=3, kv_heads=2, head_dim=16,
                            ssm_heads=8, ssm_head_dim=8, state=16)
    # each mixer has its multiplier: none on their sum
    with pytest.raises(ValueError, match="not their sum"):
        units.ParallelBlock(None, heads=2, kv_heads=1, head_dim=16,
                            ssm_heads=8, ssm_head_dim=8, state=16, scale=0.5)
    cfg = config()
    cfg["deployment"]["mamba_heads_held"] = [3, 2]       # heads 3-4
    with pytest.raises(ValueError, match="straddle the groups"):
        model.sizes(cfg)
    cfg = config()
    cfg["mamba_d_ssm"] = 80
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        model.sizes(cfg)
    cfg = config()
    cfg["published"]["mamba_n_groups"] = 3
    with pytest.raises(ValueError, match="do not divide into 3"):
        model.sizes(cfg)
    cfg = config()
    cfg["mamba_n_groups"] = 2
    with pytest.raises(ValueError, match="mamba_n_groups 2"):
        model.sizes(cfg)
    with pytest.raises(ValueError, match="no multiple of the scan's chunk"):
        layer_units(config())[1].leaf_shapes((2, 40, 64))


# -- the program ------------------------------------------------------------------
def test_three_steps_follow_the_reference():
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


def test_the_counter_and_the_routes_read_what_they_should():
    cfg, spec, weights, x, y = setup()
    got = jax.jit(lambda p, a, b: fused.eval_minibatch(spec, p, a, b))(
        weights, x[0], y[0])
    assert int(got["parallel_tokens"]) == 4 * x[0].size      # every layer
    assert int(got["tokens"]) == x[0].size
    assert "ssm_tokens" not in got and "gdn_tokens" not in got
    assert fused.mixer_routes(spec) == "parallel:4"
    assert fused.attn_routes(spec) == "window:0 full:0"
    assert fused.COUNTERS["parallel_tokens"][0] == "sum"
    # absent, never 0, without the kind
    import test_linear_lm as linear
    _, other, w, lx, ly = linear.setup()
    counted = jax.jit(lambda p, a, b: fused.eval_minibatch(
        other, p, a, b))(w, lx[0], ly[0])
    assert "parallel_tokens" not in counted


def test_the_scopes_are_in_the_compiled_text():
    cfg, spec, weights, x, y = setup()
    text = jax.jit(lambda p, a, b: fused.grad_minibatch(
        spec, p, a, b)).lower(weights, x[0], y[0]).as_text(debug_info=True)
    for scope in ("fwd/L01.parallel_block/parallel_block/attn_mixer/rope",
                  "fwd/L01.parallel_block/parallel_block/attn_mixer/scores",
                  "fwd/L01.parallel_block/parallel_block/mamba_mixer/ssd_scan",
                  "bwd/L07.parallel_block/jvp(parallel_block)/mamba_mixer",
                  "fwd/L02.mlp_block/mlp_block"):
        assert scope in text, scope


def test_the_leaves_are_the_units_and_the_model_files():
    cfg = config()
    for unit, shapes in zip(layer_units(cfg), model.param_shapes(cfg)):
        if unit.KIND == "parallel_block":
            assert unit.LEAVES == hybrid.LEAVES
            got = unit.leaf_shapes((2, 64, 64))
            assert [tuple(got[k]) for k in unit.LEAVES] == list(shapes)
