"""The gated-delta-rule / full-attention hybrid (``gdn_block`` and
``mlp_block`` beside an ``attn_block`` with query/key norms, every block
with its norm on the sublayer's output) at test widths on the CPU: d 64,
three linear layers (4 heads, keys of 8, values of 16, chunk 16) and one
full-attention layer (4 heads of 16), feed-forward 96, vocabulary 256, T 64.
The fused trainer is held against the benchmark's plain reference
(``benchmark/lib/olmo_hybrid_reference.py``: the delta rule a token at a
time; it imports nothing of the program) with seeded weights."""

import copy
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import olmo_hybrid_model as model           # noqa: E402
from benchmark.lib import olmo_hybrid_reference as reference   # noqa: E402
from znicz_tpu.nn import decoder as units                      # noqa: E402
from znicz_tpu.ops import attention, gdn                       # noqa: E402
from znicz_tpu.parallel import fused                           # noqa: E402

TRAFFIC = {"seq_len": 64, "minibatch": 2, "n_train": 12, "n_valid": 4,
           "n_test": 0}
SEED = 20261004
#: the leaves a layer of each kind holds, in the layer list's order
LEAVES = [1, 14, 4, 14, 4, 14, 4, 7, 4, 2]


def config(vocab_shares: int = 8, share: int = 0) -> dict:
    """``tiny-olmo-hybrid`` as the harness's tests have it: an eighth of
    2,048 rows held; ``vocab_shares`` 1: all of them."""
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "tiny-olmo-hybrid.json")) as fh:
        cfg = json.load(fh)
    rows = cfg["published"]["vocab_size"] // vocab_shares
    cfg["vocab_size"] = rows
    cfg["deployment"]["vocab_rows_held"] = [share * rows, rows]
    return cfg


def layer_units(cfg: dict) -> list:
    return units.units_of(model.layer_list(cfg))


def spec_of(cfg: dict) -> fused.ModelSpec:
    """The trainer's spec of the configuration, as ``extract_model`` makes
    it of the units."""
    layers = []
    for la, unit in zip(model.layer_list(cfg), layer_units(cfg)):
        h = la["<-"]
        layers.append(fused.sequence_layer(unit, (
            h["learning_rate"], h["weights_decay"], 0.0,
            h["gradient_moment"])))
    return fused.ModelSpec(tuple(layers), "softmax")


def setup(cfg=None):
    cfg = cfg or config()
    weights = model.make_weights(SEED, model.param_shapes(cfg))
    x, y = model.make_rows(SEED, np.arange(4, 10, dtype=np.uint32), cfg,
                           TRAFFIC)
    return cfg, spec_of(cfg), weights, x.reshape(3, 2, -1), y.reshape(
        3, 2, -1)


def kind_cfg(cfg: dict, kind: str, nth: int = 0) -> dict:
    """The fused config of the ``nth`` layer of ``kind``."""
    return [u for u in layer_units(cfg) if u.KIND == kind][
        nth].fused_config()


# -- the chunked delta rule against the recurrence itself ---------------------
def _rule_case(decay: str, beta: str, t: int = 256):
    """``decay``: a token's ``exp(g)`` near 0 (every token forgets the
    state), near 1 (the state carries over many chunks) or mixed;
    ``beta``: the write strength under 1, over 1 (a negative eigenvalue
    along ``k``) or on both sides.  256 tokens are two groups of eight
    chunks of 16 (``gdn.GROUP``) and one group of four chunks of 64."""
    b, h, dk, dv = 2, 3, 8, 16
    k = jax.random.split(jax.random.key(13), 5)
    lo, hi = {"near_0": (3.0, 9.0), "near_1": (1e-5, 1e-3),
              "mixed": (1e-3, 1.0)}[decay]
    b_lo, b_hi = {"under_1": (0.05, 0.95), "over_1": (1.05, 1.95),
                  "both": (0.05, 1.95)}[beta]
    return (gdn.l2_norm(jax.random.normal(k[0], (b, t, h, dk))) * dk ** -0.5,
            gdn.l2_norm(jax.random.normal(k[1], (b, t, h, dk))),
            jax.random.normal(k[2], (b, t, h, dv)),
            -jnp.exp(jax.random.uniform(k[3], (b, t, h), minval=np.log(lo),
                                        maxval=np.log(hi))),
            jax.random.uniform(k[4], (b, t, h), minval=b_lo, maxval=b_hi))


def _recur(q, k, v, g, beta):
    return jax.vmap(lambda q, k, v, g, beta: reference.recurrence(
        q, k, v, jnp.exp(g), beta))(q, k, v, g, beta)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("decay,beta", [
    ("near_0", "both"), ("near_1", "both"), ("mixed", "under_1"),
    ("mixed", "over_1"), ("mixed", "both")])
def test_the_chunked_delta_rule_is_the_recurrence(decay, beta, chunk):
    args = _rule_case(decay, beta)

    def chunked(*a):
        return gdn.delta_rule(*a, chunk)
    with jax.default_matmul_precision("highest"):
        want, got = _recur(*args), chunked(*args)
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0.01
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * scale)

        def grads(fn):
            return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                            argnums=(0, 1, 2, 3, 4))(*args)
        for got_g, want_g in zip(grads(chunked), grads(_recur)):
            assert np.isfinite(np.asarray(got_g)).all()
            np.testing.assert_allclose(
                got_g, want_g, rtol=2e-4,
                atol=2e-5 * float(jnp.max(jnp.abs(want_g))))


def test_a_length_that_is_no_multiple_of_the_chunk_is_refused():
    args = _rule_case("mixed", "both", t=80)
    with pytest.raises(ValueError, match="no multiple of the delta rule's"):
        gdn.delta_rule(*args, 32)
    unit = units.GatedDeltaBlock(None, heads=4, key_dim=8, value_dim=16,
                                 chunk=24)
    with pytest.raises(ValueError, match="no multiple of the delta rule's"):
        unit.leaf_shapes((2, 32, 64))


# -- each kind against the reference -------------------------------------------
PLACES = {"gdn_block": 1, "mlp_block": 2, "attn_block": 7}
REF_KINDS = {"gdn_block": "linear_attention", "mlp_block": "mlp",
             "attn_block": "full_attention"}


def _block_case(cfg, kind: str):
    """(leaves, x) of one block of ``kind``; a stream of the size the
    blocks' outputs have, so that a block's output shows beside it."""
    leaves = model.make_weights(SEED, model.param_shapes(cfg))[PLACES[kind]]
    x = jax.random.normal(jax.random.key(5), (2, 64, 64), jnp.float32)
    return tuple(leaves), x


@pytest.mark.parametrize("kind,faults", [
    ("gdn_block", ("no_decay", "beta_one", "no_l2norm", "gate_first",
                   "boundary_state", "input_norm")),
    ("attn_block", ("no_qk_norm", "rotary_fault", "input_norm")),
    ("mlp_block", ("input_norm",))])
def test_a_block_is_the_references(kind, faults):
    cfg = config()
    leaves, x = _block_case(cfg, kind)
    want = reference.make_blocks(cfg)[REF_KINDS[kind]](leaves, x)
    got, counters = fused.SEQUENCE_FWD[kind](leaves, x, kind_cfg(cfg, kind))
    assert float(jnp.max(jnp.abs(want - x))) > 0.1
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6)
    assert {k: int(v) for k, v in counters.items()} == (
        {"gdn_tokens": 2 * 64} if kind == "gdn_block" else {})
    # each planted fault of the reference changes what it says it changes
    for fault in faults:
        other = reference.make_blocks(cfg, **{fault: True})[
            REF_KINDS[kind]](leaves, x)
        assert float(jnp.max(jnp.abs(other - want))) > 1e-3, fault
    # the norm on the input is the program's other setting, not a guess
    pre, _ = fused.SEQUENCE_FWD[kind](
        leaves, x, {**kind_cfg(cfg, kind), "norm": "pre"})
    np.testing.assert_allclose(
        pre, reference.make_blocks(cfg, input_norm=True)[REF_KINDS[kind]](
            leaves, x), rtol=1e-4, atol=2e-6)


def test_three_steps_follow_the_reference():
    cfg, spec, weights, x, y = setup()
    ref = reference.follow(cfg, copy.deepcopy(weights), x, y)
    want = jax.grad(lambda ps: jnp.mean(reference.token_losses(
        cfg, ps, x[0], y[0])))([tuple(ls) for ls in weights])
    grads, _ = jax.jit(lambda p, a, b: fused.grad_minibatch(
        spec, p, a, b))(weights, x[0], y[0])
    assert [len(g) for g in grads] == LEAVES
    for got_layer, want_layer, ref_norms in zip(grads, want,
                                                ref["grad_norms"]):
        for got, exp, norm in zip(got_layer, want_layer, ref_norms):
            np.testing.assert_allclose(
                got, exp, rtol=5e-4,
                atol=1e-5 * float(jnp.max(jnp.abs(exp))) + 1e-12)
            # the reference's block-at-a-time backward is its jax.grad
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
    assert int(got["gdn_tokens"]) == 3 * x[0].size       # linear layers
    assert int(got["tokens"]) == x[0].size
    assert "ssm_tokens" not in got and "moe_assignments" not in got
    assert fused.mixer_routes(spec) == "linear:3 full:1"
    assert fused.attn_routes(spec) == "window:0 full:1"
    assert fused.COUNTERS["gdn_tokens"][0] == "sum"
    # absent, never 0, without the kind
    import test_hybrid_lm as hybrid
    _, other, w, hx, hy = hybrid.setup()
    counted = jax.jit(lambda p, a, b: fused.eval_minibatch(
        other, p, a, b))(w, hx[0], hy[0])
    assert "gdn_tokens" not in counted
    assert fused.mixer_routes(other) == "ssm:2 full:1"


def test_the_start_record_names_the_mixers(tmp_path):
    """Through the Launcher's workflow: the start line and every timeline
    row carry ``mixer_routes``, the ``train_step`` rows ``gdn_tokens``, and
    the gauge holds the last epoch's count."""
    from znicz_tpu.backends import Device
    from znicz_tpu.config import root
    from znicz_tpu.models import decoder_lm
    from znicz_tpu.telemetry import flightrecorder
    from znicz_tpu.telemetry.registry import REGISTRY
    saved = root.decoder_lm.to_dict()
    root.decoder_lm.update({
        "layer_types": ["linear", "linear", "linear", "full"],
        "mlp_width": 96, "qk_norm": True, "norm": "post",
        "positional": "nope"})
    try:
        wf = decoder_lm.DecoderLMWorkflow()
        wf.initialize(device=Device.create("xla"))
        path = str(tmp_path / "timeline.jsonl")
        wf.train(fused=True, max_epochs=1, timeline_jsonl=path)
    finally:
        root.decoder_lm.update(saved)
    rows = [json.loads(line) for line in open(path)]
    assert rows and all(r["mixer_routes"] == "linear:3 full:1"
                        and r["gdn_tokens"] == 3 * r["tokens"] > 0
                        for r in rows)
    step = [r for r in flightrecorder.RECORDER.snapshot()["recent"]
            if r.get("kind") == "train_step"][-1]
    assert step["gdn_tokens"] == rows[-1]["gdn_tokens"]
    assert REGISTRY.gauge("train_gdn_tokens").value() == step["gdn_tokens"]


def test_the_scopes_are_in_the_compiled_text():
    cfg, spec, weights, x, y = setup()
    text = jax.jit(lambda p, a, b: fused.grad_minibatch(
        spec, p, a, b)).lower(weights, x[0], y[0]).as_text(debug_info=True)
    for scope in ("fwd/L01.gdn_block/gdn_block/checkpoint/short_conv",
                  "fwd/L01.gdn_block/gdn_block/delta_rule/while",
                  "bwd/L05.gdn_block", "fwd/L02.mlp_block/mlp_block",
                  "fwd/L07.attn_block/qk_norm", "fwd/L07.attn_block/scores"):
        assert scope in text, scope
    assert "L07.attn_block/rope" not in text            # no rotary tables


def test_the_tick_path_trains_what_the_fused_path_trains():
    from znicz_tpu import prng
    from znicz_tpu.backends import Device
    from znicz_tpu.config import root
    from znicz_tpu.models import decoder_lm
    saved = root.decoder_lm.to_dict()
    root.decoder_lm.update({
        "layer_types": ["linear", "full"], "mlp_width": 96,
        "qk_norm": True, "norm": "post", "positional": "nope"})
    losses = {}
    try:
        for fused_path in (True, False):
            prng.seed_all(7)
            wf = decoder_lm.run(device=Device.create("xla"), epochs=2,
                                fused=fused_path)
            losses[fused_path] = [m["train_loss"]
                                  for m in wf.decision.epoch_metrics]
    finally:
        root.decoder_lm.update(saved)
    assert losses[True][1] < losses[True][0]
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)


# -- what this cut slices: the vocabulary ------------------------------------------
SHARES = 8


def test_the_vocabulary_shares_stand_side_by_side():
    """A share's logits are over its columns of the head and a row's
    embedding comes from exactly one share: the eight shares' logits side
    by side are the uncut head's, and the eight lookups (a share gives
    zero for an id it does not hold) add up to the uncut table's row.  The
    test does the joining; the layers are whole, so nothing else adds
    up."""
    whole = config(vocab_shares=1)
    weights = model.make_weights(SEED, model.param_shapes(whole))
    (table,), (gf, w) = weights[0], weights[-1]
    rows = whole["vocab_size"] // SHARES
    x = jax.random.normal(jax.random.key(6), (2, 64, 64), jnp.float32)
    want = jax.vmap(lambda row: reference._dot(
        reference.rms_norm(row, gf, whole["rms_norm_eps"]), w))(x)
    parts = [attention.lm_head_fwd(
        (gf, w[:, rows * s:rows * (s + 1)]), x,
        kind_cfg(config(SHARES, s), "lm_head"))[0] for s in range(SHARES)]
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), want,
                               rtol=1e-4, atol=1e-6)
    ids = jax.random.randint(jax.random.key(7), (2, 64), 0,
                             whole["vocab_size"])
    total, holders = 0.0, 0
    for s in range(SHARES):
        held = (ids >= rows * s) & (ids < rows * (s + 1))
        out, _ = attention.embed_fwd(
            (table[rows * s:rows * (s + 1)],),
            jnp.where(held, ids - rows * s, 0),
            kind_cfg(config(SHARES, s), "embed"))
        total = total + jnp.where(held[..., None], out, 0.0)
        holders = holders + held
    assert (np.asarray(holders) == 1).all()
    np.testing.assert_array_equal(total, reference.embed(table, ids))
    # and the cut's own count: an eighth of the rows, every layer whole
    shapes = model.param_shapes(config())
    assert shapes[0] == ((rows, 64),) and shapes[-1] == ((64,), (64, rows))
    assert [len(ls) for ls in shapes] == LEAVES


# -- the decoders that were there ----------------------------------------------------
#: sha256 of the jaxprs of ``tiny-decoder``'s and ``tiny-hybrid``'s training
#: steps (gradients and update) as the commit before this file's kinds
#: traced them.  ``attn_block`` was widened (query/key norms, the norm on
#: the output) and ``decoder.py`` gained two units: with none of that asked
#: for, ``mellum2``'s and ``granite``'s layer lists have to trace to what
#: they did.  A change that means to alter those programs replaces the
#: digest and says so.  PR 37 meant to, where a list has a later piece of
#: the sorted pairs (a quarter and a half of the experts held: the sum over
#: the pieces is a ``custom_vjp`` whose backward stands under the forward's
#: condition, ``ops/moe.sum_of_pieces``), and replaced those two; a list
#: that holds every expert has one piece and no conditional, and traces to
#: what it did before.
STEPS_BEFORE = {
    "tiny-decoder":
    "e818025f7d20ad50392e2e71b32d9b506dff8125c0f3cd24fab3e0f1fc62f109",
    "tiny-decoder-all-held":
    "d46cc8282b279da6d6793c71774b30ddf3dd3685a871c4fed74b1f747ed15dcd",
    "tiny-hybrid":
    "fce75a833f1367f66dc98e360f4e14eadf93bbd5e8d92e45dceaefffd70efe06"}


@pytest.mark.parametrize("name", sorted(STEPS_BEFORE))
def test_the_other_decoders_build_the_programs_they_did(name):
    if name.startswith("tiny-decoder"):
        import test_decoder_lm as before
        _, spec, weights, x, y = before.setup(
            (0, 8) if name.endswith("all-held") else (2, 2))
    else:
        import test_hybrid_lm as before
        _, spec, weights, x, y = before.setup()
    vels = jax.tree.map(jnp.zeros_like, weights)
    jaxpr = jax.make_jaxpr(lambda p, v, a, b: fused.train_minibatch(
        spec, p, v, a, b))(weights, vels, x[0], y[0])
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() \
        == STEPS_BEFORE[name]
