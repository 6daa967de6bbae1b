"""The Mamba-2 / sparse-expert hybrid (``mamba_block`` beside the four
token-sequence kinds, a shared expert, a tied head, the model's four
multipliers) at test widths on the CPU: d 64, two Mamba layers (heads of
16, state 16, chunk 8) around one attention layer without rotary
embeddings, 8 experts of width 32 at 3 a token beside a shared expert,
vocabulary 128, T 32.  The fused trainer is held against the benchmark's
plain reference (``benchmark/lib/granite_reference.py``: the recurrence a
token at a time; it imports nothing of the program) with seeded weights."""

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

from benchmark.lib import decoder_model                    # noqa: E402
from benchmark.lib import granite_model as model           # noqa: E402
from benchmark.lib import granite_reference as reference   # noqa: E402
from znicz_tpu.nn import decoder as units                  # noqa: E402
from znicz_tpu.ops import attention, moe, ssm, tuning      # noqa: E402
from znicz_tpu.parallel import fused                       # noqa: E402

TRAFFIC = {"seq_len": 32, "minibatch": 2, "n_train": 12, "n_valid": 4,
           "n_test": 0}
SEED = 20261003
#: the keys that count what is held, with the deployment's name for each
HELD = {"mamba_n_heads": "mamba_heads_held",
        "num_attention_heads": "attention_heads_held",
        "num_key_value_heads": "kv_heads_held",
        "num_local_experts": "experts_held", "vocab_size": "vocab_rows_held"}


def config(uncut: bool = False, shares: int = 1, share: int = 0) -> dict:
    """``tiny-hybrid`` as the harness's tests have it (half of every share
    held); ``uncut``: a model of 8 Mamba heads, 16 query over 8 key/value
    heads, 8 experts, 64 shared columns and 128 rows, all held, or share
    ``share`` of ``shares`` equal ones of it."""
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "tiny-hybrid.json")) as fh:
        cfg = json.load(fh)
    if not uncut:
        return cfg
    whole = {"mamba_n_heads": 8, "num_attention_heads": 16,
             "num_key_value_heads": 8, "num_local_experts": 8,
             "vocab_size": 128}
    cfg["published"].update(whole, shared_intermediate_size=64)
    for key, name in HELD.items():
        count = whole[key] // shares
        cfg[key] = count
        cfg["deployment"][name] = [share * count, count]
    cfg["deployment"]["shared_columns_held"] = [share * 64 // shares,
                                                64 // shares]
    return cfg


def layer_units(cfg: dict) -> list:
    """The forward units of the configuration's layer list, tied as the
    workflow ties them."""
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


# -- the chunked scan against the recurrence itself ---------------------------
def _scan_case(decay: str):
    """Five chunks of 16 tokens; ``decay``: a step's ``exp(dt a)`` near 0
    (every token forgets the state), near 1 (the state carries over many
    chunks), or across the Mamba-2 starting range."""
    b, t, h, p, n = 2, 80, 3, 8, 16
    k = jax.random.split(jax.random.key(11), 5)
    lo, hi = {"near_0": (4.0, 9.0), "near_1": (1e-4, 1e-3),
              "mixed": (1e-3, 0.1)}[decay]
    return (jax.random.normal(k[0], (b, t, h, p)),
            jnp.exp(jax.random.uniform(k[1], (b, t, h), minval=np.log(lo),
                                       maxval=np.log(hi))),
            -jnp.asarray([1.0, 4.0, 16.0]),
            jax.random.normal(k[2], (b, t, n)),
            jax.random.normal(k[3], (b, t, n)))


@pytest.mark.parametrize("decay", ["near_0", "near_1", "mixed"])
def test_the_chunked_scan_is_the_recurrence(decay):
    args = _scan_case(decay)

    def recur(x, dt, a, b_in, c_in):
        return jax.vmap(lambda x, dt, b_in, c_in: reference.recurrence(
            dt[..., None] * x, jnp.exp(dt * a), b_in, c_in, 16, False))(
                x, dt, b_in, c_in)

    def chunked(*args):
        return ssm.ssd_scan(*args, 16)
    with jax.default_matmul_precision("highest"):
        want, got = recur(*args), chunked(*args)
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0.01
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * scale)

        def grads(fn):
            return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                            argnums=(0, 1, 2, 3, 4))(*args)
        for got_g, want_g in zip(grads(chunked), grads(recur)):
            assert np.isfinite(np.asarray(got_g)).all()
            np.testing.assert_allclose(
                got_g, want_g, rtol=2e-4,
                atol=2e-5 * float(jnp.max(jnp.abs(want_g))))


def test_a_length_that_is_no_multiple_of_the_chunk_is_refused():
    x, dt, a, b_in, c_in = _scan_case("mixed")
    with pytest.raises(ValueError, match="no multiple of the scan's chunk"):
        ssm.ssd_scan(x, dt, a, b_in, c_in, 32)
    unit = units.MambaBlock(None, heads=4, head_dim=16, state=16, chunk=24)
    with pytest.raises(ValueError, match="no multiple of the scan's chunk"):
        unit.leaf_shapes((2, 32, 64))


# -- each kind against the reference -------------------------------------------
def _block_case(cfg, kind: str):
    """(leaves, x) of one block of ``kind`` with decisive weights: the
    blocks' outputs are made visible beside the stream (the benchmark's
    weights keep them a hundredth of it)."""
    place = {"mamba_block": 1, "moe_block": 2, "attn_block": 3}[kind]
    leaves = list(model.make_weights(SEED, model.param_shapes(cfg))[place])
    leaves[-1] = leaves[-1] * 20.0
    if kind == "moe_block":
        leaves[1] = leaves[1] * 30.0          # a router that decides
        leaves[4] = leaves[4] * 20.0
    x = jax.random.normal(jax.random.key(5), (2, 32, 64), jnp.float32)
    return tuple(leaves), x


@pytest.mark.parametrize("kind", ["mamba_block", "attn_block", "moe_block"])
def test_a_block_is_the_references(kind):
    cfg = config()
    leaves, x = _block_case(cfg, kind)
    ref_kind = {"mamba_block": "mamba", "attn_block": "attention",
                "moe_block": "experts"}[kind]
    want = reference.make_blocks(cfg)[ref_kind](leaves, x)
    got, counters = fused.SEQUENCE_FWD[kind](leaves, x,
                                             kind_cfg(cfg, kind))
    assert float(jnp.max(jnp.abs(want - x))) > 1e-3
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    if kind == "mamba_block":
        assert int(counters["ssm_tokens"]) == 2 * 32
    # each planted fault of the reference changes what it says it changes
    fault = {"mamba_block": "boundary_fault", "attn_block": "rotary_fault",
             "moe_block": "no_shared"}[kind]
    other = reference.make_blocks(cfg, **{fault: True})[ref_kind](leaves, x)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-4 * float(
        jnp.max(jnp.abs(want - x)))


def test_three_steps_follow_the_reference():
    cfg, spec, weights, x, y = setup()
    ref = reference.follow(cfg, copy.deepcopy(weights), x, y)
    want = jax.grad(lambda ps: jnp.mean(reference.token_losses(
        cfg, ps, x[0], y[0])))([tuple(ls) for ls in weights])
    grads, _ = jax.jit(lambda p, a, b: fused.grad_minibatch(
        spec, p, a, b))(weights, x[0], y[0])
    assert [len(g) for g in grads] == [1, 9, 8, 5, 8, 9, 8, 1]
    for got_layer, want_layer, ref_norms in zip(grads, want,
                                                ref["grad_norms"]):
        for got, exp, norm in zip(got_layer, want_layer, ref_norms):
            np.testing.assert_allclose(got, exp, rtol=2e-4, atol=2e-7)
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
        np.testing.assert_allclose(got, exp, rtol=2e-4, atol=1e-9)


def test_counters_are_the_references_own_routing():
    cfg, spec, weights, x, y = setup()
    got = jax.jit(lambda p, a, b: fused.eval_minibatch(spec, p, a, b))(
        weights, x[0], y[0])
    want = reference.routing(cfg, [tuple(ls) for ls in weights], x[0])
    assert {k: int(got[k]) for k in want} == want
    assert want["moe_assignments"] == 3 * 3 * x[0].size      # layers x top_k
    assert int(got["ssm_tokens"]) == 2 * x[0].size           # Mamba layers
    assert int(got["tokens"]) == x[0].size


# -- the tied table ---------------------------------------------------------------
def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses():
    cfg, spec, weights, x, y = setup()
    grads, _ = jax.jit(lambda p, a, b: fused.grad_minibatch(
        spec, p, a, b))(weights, x[0], y[0])
    assert fused.tied_row(spec, len(spec.layers) - 1) == 0
    assert len(weights[-1]) == len(grads[-1]) == 1      # the head: gf alone

    def loss(embedded, headed):
        """The same step with the table's two uses told apart."""
        h = x[0]
        for i, layer in enumerate(spec.layers[:-1]):
            leaves = (embedded,) if i == 0 else weights[i]
            h, _ = fused.SEQUENCE_FWD[layer.kind](leaves, h, layer.cfg)
        logits, _ = attention.lm_head_fwd(
            (weights[-1][0], headed), h, spec.layers[-1].cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[0][..., None], axis=-1))
    table = weights[0][0]
    g_embed, g_head = jax.grad(loss, argnums=(0, 1))(table, table)
    assert float(jnp.linalg.norm(g_embed)) > 1e-6 < float(
        jnp.linalg.norm(g_head))
    np.testing.assert_allclose(grads[0][0], g_embed + g_head, rtol=1e-4,
                               atol=1e-9)
    # one leaf, one velocity, one update
    params, vels = fused.apply_updates(
        spec, weights, jax.tree.map(jnp.zeros_like, weights), grads)
    lr = cfg["assumed"]["learning_rate"]
    np.testing.assert_allclose(vels[0][0], -lr * (g_embed + g_head),
                               rtol=1e-4, atol=1e-10)
    np.testing.assert_allclose(params[0][0], table + vels[0][0])


def test_a_state_that_crowds_the_device_trains_to_the_same_numbers(
        monkeypatch):
    """Where the state leaves the step little room the trainer keeps the
    backward's recomputation apart from the forward and runs a minibatch a
    launch: the same numbers."""
    cfg, spec, weights, x, y = setup()
    vels = jax.tree.map(jnp.zeros_like, weights)
    rows = jnp.concatenate(list(x)), jnp.concatenate(list(y))
    assert not fused.state_crowds_device(spec, weights)         # the CPU
    held = sum(a.size * 4 for a in jax.tree.leaves(weights))

    def three_steps(room):
        monkeypatch.setattr(tuning, "device_memory_bytes", lambda: room)
        trainer = fused.FusedTrainer(
            spec=spec, params=jax.tree.map(jnp.copy, weights),
            vels=jax.tree.map(jnp.copy, vels))
        launches = []
        trainer._build()
        step = trainer._train_epoch_fn
        trainer._train_epoch_fn = lambda *a, **kw: (launches.append(
            a[4].shape[0]), step(*a, **kw))[1]
        ms = trainer.train_epoch(*rows, np.arange(6), 2)
        return trainer, ms, launches
    roomy, ms_roomy, launches = three_steps(5 * held)
    assert not roomy.crowded and not roomy.spec.fresh_backward
    assert launches == [3]
    crowded, ms_crowded, launches = three_steps(4 * held)
    assert crowded.crowded and crowded.spec.fresh_backward
    assert launches == [1, 1, 1]
    for key in ms_roomy:
        np.testing.assert_allclose(ms_crowded[key], ms_roomy[key],
                                   rtol=1e-6)
    for got, want in zip(jax.tree.leaves((crowded.params, crowded.vels)),
                         jax.tree.leaves((roomy.params, roomy.vels))):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-9)
    jaxpr = str(jax.make_jaxpr(lambda p, a, b: fused.grad_minibatch(
        crowded.spec, p, a, b))(weights, x[0], y[0]))
    # a layer's leaves, and in the backward of an expert layer whose sorted
    # pairs have a later piece the weights' gradients (``moe._sum_bwd``)
    later = sum(la.kind == "moe_block" for la in spec.layers)
    assert later == 3
    assert jaxpr.count("optimization_barrier") == len(spec.layers) + later


@pytest.fixture
def hybrid_sample():
    """The zoo's decoder sample as a hybrid: Mamba, attention, Mamba; a
    shared expert, a tied head, the four multipliers."""
    from znicz_tpu.config import root
    saved = root.decoder_lm.to_dict()
    root.decoder_lm.update({
        "layer_types": ["mamba", "full", "mamba"], "shared_width": 16,
        "tied": True, "positional": "nope", "embedding_scale": 12.0,
        "residual_scale": 0.22, "score_scale": 0.0625,
        "logits_scale": 0.125, "top_k": 3})
    yield root.decoder_lm
    root.decoder_lm.update(saved)


def test_the_snapshot_holds_the_tied_table_once(hybrid_sample):
    from znicz_tpu.backends import Device
    from znicz_tpu.models.decoder_lm import DecoderLMWorkflow
    from znicz_tpu.snapshotter import collect_state
    wf = DecoderLMWorkflow()
    wf.initialize(device=Device.create("xla"))
    spec, params, vels = fused.extract_model(wf)
    assert [la.kind for la in spec.layers] == [
        "embed", "mamba_block", "moe_block", "attn_block", "moe_block",
        "mamba_block", "moe_block", "lm_head"]
    assert [len(p) for p in params] == [len(v) for v in vels] == [
        1, 9, 8, 5, 8, 9, 8, 1]
    assert spec.layers[-1].cfg["tied_to"] == 0
    head = wf.forwards[-1]
    assert head.LEAVES == ("gf",) and head.tied_unit is wf.forwards[0]
    arrays, _ = collect_state(wf)
    tables = [k for k, a in arrays.items() if a.shape == (128, 64)]
    assert tables == [f"{wf.forwards[0].name}/table",
                      f"{wf.gds[0].name}/velocity_table"]
    assert f"{head.name}/gf" in arrays and f"{head.name}/w" not in arrays
    moe_unit = wf.forwards[2]
    assert {f"{moe_unit.name}/{leaf}" for leaf in ("sg", "su", "sd")} \
        <= set(arrays)
    # what the trainer learned goes back to the one table
    trainer = fused.FusedTrainer(workflow=wf)
    trainer.params = [tuple(a + 1.0 for a in ls) for ls in trainer.params]
    trainer.write_back()
    np.testing.assert_allclose(wf.forwards[0].table.mem,
                               np.asarray(params[0][0]) + 1.0)


def test_the_hybrid_trains_alike_fused_and_by_ticks(hybrid_sample):
    from znicz_tpu.backends import Device
    from znicz_tpu.models import decoder_lm
    from znicz_tpu import prng
    losses = {}
    for fused_path in (True, False):
        prng.seed_all(7)
        wf = decoder_lm.run(device=Device.create("xla"), epochs=2,
                            fused=fused_path)
        losses[fused_path] = [m["train_loss"]
                              for m in wf.decision.epoch_metrics]
    assert losses[True][1] < losses[True][0]
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)


def test_the_scopes_are_in_the_compiled_text():
    cfg, spec, weights, x, y = setup()
    text = jax.jit(lambda p, a, b: fused.grad_minibatch(
        spec, p, a, b)).lower(weights, x[0], y[0]).as_text(debug_info=True)
    for scope in ("fwd/L01.mamba_block/mamba_block/ssd_scan",
                  "bwd/L05.mamba_block", "fwd/L02.moe_block/shared_expert",
                  "fwd/L03.attn_block/scores"):
        assert scope in text, scope
    assert "L03.attn_block/rope" not in text            # no rotary tables


# -- one chip's share of a layer ---------------------------------------------------
SHARES = 8


def _uncut():
    cfg = config(uncut=True)
    return cfg, model.make_weights(SEED, model.param_shapes(cfg))


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """The ``moe_block`` outputs of the eight shares (an expert and an
    eighth of the shared expert's columns each), the residual (and the
    router, which every chip computes alike) counted once, are the uncut
    layer of the reference."""
    cfg, weights = _uncut()
    g2, wr, wg, wu, wd, sg, su, sd = weights[2]
    wr, wd, sd = wr * 30.0, wd * 20.0, sd * 20.0
    x = jax.random.normal(jax.random.key(3), (2, 32, 64), jnp.float32)
    whole = reference.make_blocks(cfg)["experts"](
        (g2, wr, wg, wu, wd, sg, su, sd), x)
    total = x
    for s in range(SHARES):
        cols = slice(8 * s, 8 * s + 8)
        out, counters = moe.moe_block_fwd(
            (g2, wr, wg[s:s + 1], wu[s:s + 1], wd[s:s + 1], sg[:, cols],
             su[:, cols], sd[cols]), x,
            kind_cfg(config(True, SHARES, s), "moe_block"))
        total = total + (out - x)
        assert int(counters["moe_assignments"]) == 2 * 32 * 3
    assert float(jnp.max(jnp.abs(whole - x))) > 1e-3
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-6)


def test_the_attention_shares_add_up_to_the_uncut_layer():
    """Two query heads over their key/value head a share: the partial
    products through ``Wo`` add up."""
    cfg, weights = _uncut()
    g1, wq, wk, wv, wo = weights[3]
    wo = wo * 20.0
    hd = 64 // 16
    x = jax.random.normal(jax.random.key(4), (2, 32, 64), jnp.float32)
    whole = reference.make_blocks(cfg)["attention"]((g1, wq, wk, wv, wo), x)
    total = x
    for s in range(SHARES):
        q, kv = slice(2 * hd * s, 2 * hd * (s + 1)), slice(hd * s,
                                                          hd * (s + 1))
        out, _ = attention.attn_block_fwd(
            (g1, wq[:, q], wk[:, kv], wv[:, kv], wo[q]), x,
            kind_cfg(config(True, SHARES, s), "attn_block"))
        total = total + (out - x)
    assert float(jnp.max(jnp.abs(whole - x))) > 1e-4
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-6)


def test_the_head_shares_stand_side_by_side():
    """A share's logits are over its rows of the table: the eight slices
    side by side are the uncut head's logits."""
    cfg, weights = _uncut()
    (table,), (gf,) = weights[0], weights[-1]
    x = jax.random.normal(jax.random.key(6), (2, 32, 64), jnp.float32)
    want = jax.vmap(lambda row: reference._dot_t(
        reference.rms_norm(row, gf, cfg["rms_norm_eps"]), table)
        / cfg["logits_scaling"])(x)
    parts = [attention.lm_head_fwd(
        (gf, table[16 * s:16 * s + 16]), x,
        kind_cfg(config(True, SHARES, s), "lm_head"))[0]
        for s in range(SHARES)]
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), want,
                               rtol=1e-4, atol=1e-6)


def test_the_mamba_shares_stand_side_by_side_before_the_norm():
    """A head a share: the gated tensors of the eight shares side by side
    are the uncut mixer's before its norm (whose mean square a deployment
    sums over its chips), and with every head held the block is the uncut
    reference's."""
    cfg, weights = _uncut()
    g1, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, g_m, w_out = \
        weights[1]
    w_out = w_out * 20.0
    p, n, d_in = 16, 16, 8 * 16
    x = jax.random.normal(jax.random.key(8), (2, 32, 64), jnp.float32)
    mcfg = kind_cfg(cfg, "mamba_block")
    xn = attention.rms_norm(x, g1, mcfg["eps"])
    whole = ssm.gated_scan((w_in, conv_w, conv_b, dt_bias, a_log, d_skip),
                           xn, mcfg)
    bc_in = np.arange(2 * d_in, 2 * d_in + 2 * n)      # B and C: whole
    bc_conv = np.arange(d_in, d_in + 2 * n)
    parts = []
    for s in range(SHARES):
        ch = np.arange(p * s, p * (s + 1))
        cols = np.concatenate([ch, d_in + ch, bc_in,
                               [2 * d_in + 2 * n + s]])
        taps = np.concatenate([ch, bc_conv])
        parts.append(ssm.gated_scan(
            (w_in[:, cols], conv_w[:, taps], conv_b[taps], dt_bias[s:s + 1],
             a_log[s:s + 1], d_skip[s:s + 1]), xn,
            kind_cfg(config(True, SHARES, s), "mamba_block")))
    assert float(jnp.max(jnp.abs(whole))) > 1e-3
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), whole,
                               rtol=1e-4, atol=1e-6)
    got, _ = ssm.mamba_block_fwd(
        (g1, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, g_m, w_out), x,
        mcfg)
    want = reference.make_blocks(cfg)["mamba"](
        (g1, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, g_m, w_out), x)
    assert float(jnp.max(jnp.abs(want - x))) > 1e-3
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


# -- the decoder that was there ----------------------------------------------------
#: sha256 of the jaxpr of ``tiny-decoder``'s training step (gradients and
#: update) as the commit before the hybrid's kinds traced it.  The kinds it
#: uses were widened (a scale, an optional shared expert, a block without
#: rotary tables, a tied head): with none of that asked for they have to
#: trace to what they did.  A change that means to alter that model's
#: program replaces the digest and says so: PR 37 did (a quarter of the
#: experts held has a later piece of the sorted pairs, whose backward now
#: stands under its forward's condition; ``tests/test_linear_lm.py`` holds
#: the list with every expert held to the digest it had).
TINY_DECODER_STEP = (
    "e818025f7d20ad50392e2e71b32d9b506dff8125c0f3cd24fab3e0f1fc62f109")


def test_the_decoders_layer_list_builds_the_program_it_did():
    import test_decoder_lm as before
    cfg, spec, weights, x, y = before.setup((2, 2))
    vels = jax.tree.map(jnp.zeros_like, weights)
    jaxpr = jax.make_jaxpr(lambda p, v, a, b: fused.train_minibatch(
        spec, p, v, a, b))(weights, vels, x[0], y[0])
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() \
        == TINY_DECODER_STEP
    assert decoder_model.param_shapes(cfg)[-1] == ((64,), (64, 128))
