"""The ``model`` file of the Kimi-delta-attention / gated-attention hybrids
with sparse experts in every layer (``solar-open2-250b``, and
``tiny-solar-open2`` of the harness's tests): what a row and a parameter tree
are, behind the functions ``benchmark/README.md`` lists.

A row is a sequence of ``traffic["seq_len"]`` token ids with the next token
of every position as its target, from the seeded Markov chain of
``lib/decoder_model.py`` over the vocabulary rows held.  The program is
``StandardWorkflow`` over the layer list :func:`layer_list` makes of the
configuration's keys (``lib/solar_open2_workflow.py``): ``embedding``, then
a hidden layer's mixer, an ``attn_block`` without positional embedding and
with an output gate (a layer of ``gqa_layers``) or a ``kda_block`` (every
other layer), and a ``moe_block`` with its shared expert, then an untied
``lm_head``.

Every count a kind is told is the chip's share, read from the keys
``reduced`` lists, beside the model's own from ``published``: KDA heads,
query and key/value heads, experts held, vocabulary rows; the shared
expert's columns held are ``deployment.shared_columns_held`` (its width is
a published width and stays in its key).  The counts of work are of the
NEEDED operations only: the held share's products, the rule as the
recurrence itself (a decay of the state a channel, ``S k``, the rank-one
correction and ``S q``: about ``7 K V`` a head a token), kept query/key
pairs, the held experts' products for the expected ``top_k * held /
experts`` assignments a token, no recomputation."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import data
from .decoder_model import (install, kept_pairs, make_rows, overrides,
                            traced_rows)
from .falcon_h1_model import _held

__all__ = ["install", "make_rows", "overrides", "traced_rows"]

#: what one row of the data set is, for a person (the result's
#: ``window``); ``tokens`` is the accepted cell's (``traffic["seq_len"]``)
row = {"kind": "sequence", "tokens": 4096}

#: standard deviations of the weights the benchmark makes (the
#: configuration's ``assumed.weights``): the table at unit scale, the
#: projections that write into the residual stream (the mixers' ``Wo``, the
#: experts' and the shared expert's down projections) at 0.02 / sqrt(2 x 48
#: published layers), every other projection and the head at 0.02
STDDEV = 0.02
EMBEDDING_STDDEV = 1.0
OUTPUT_STDDEV = 0.0020412415

KDA_LEAVES, ATTN_LEAVES, MOE_LEAVES = 17, 6, 8
#: leaves of a ``kda_block`` by place (``ops/gdn.KDA_LEAVES``): the output
#: gate's bias, the conv taps, a_log and dt_bias (the Mamba-2 starting
#: point), the projection that writes the stream
_KDA_BG, _KDA_TAPS, _KDA_A_LOG, _KDA_DT_BIAS, _KDA_WO = 9, (10, 11, 12), \
    13, 14, 16
#: the leaves that write the stream in the other blocks: attention's Wo, the
#: experts' Wd and the shared expert's
_WRITES = {ATTN_LEAVES: (4,), MOE_LEAVES: (4, 7)}


def _seq_len(traffic: dict) -> int:
    return int(traffic["seq_len"])


def kinds(cfg: dict) -> list[str]:
    """The mixer of each hidden layer held: ``full_attention`` for a layer
    of ``gqa_layers``, ``kda`` for every other."""
    gqa = {int(i) for i in cfg["gqa_layers"]}
    first = int(cfg["deployment"]["layers_held"][0])
    return ["full_attention" if first + i in gqa else "kda"
            for i in range(int(cfg["num_hidden_layers"]))]


def sizes(cfg: dict) -> dict:
    """The widths (as published) and the counts held here beside the
    model's, checked against what the kinds can do: a layout that does not
    add up is refused."""
    pub, dep = cfg["published"], cfg["deployment"]
    d = int(cfg["hidden_size"])
    for key, want in (("tie_word_embeddings", False), ("use_rope", False),
                      ("use_gqa_gate", True), ("kda_use_full_proj", False),
                      ("kda_allow_neg_eigval", True),
                      ("first_k_dense_replace", 0), ("n_shared_experts", 1),
                      ("routed_scaling_factor", 1), ("norm_topk_prob", True)):
        if cfg[key] != want:
            raise ValueError(f"{key} {cfg[key]!r}: the kinds do {want!r}")
    lin, lin_pub = cfg["linear_attn_config"], pub["linear_attn_config"]
    if lin["num_kv_heads"] is not None:
        raise ValueError(f"linear_attn_config.num_kv_heads "
                         f"{lin['num_kv_heads']}: the kind has one key head "
                         "a value head")
    kda_held = int(lin["num_heads"])
    _held(dep, "kda_heads_held", kda_held, int(lin_pub["num_heads"]))
    q_heads, kv_heads = (int(pub["num_attention_heads"]),
                         int(pub["num_key_value_heads"]))
    q_held, kv_held = (int(cfg["num_attention_heads"]),
                       int(cfg["num_key_value_heads"]))
    _held(dep, "attention_heads_held", q_held, q_heads)
    _held(dep, "kv_heads_held", kv_held, kv_heads)
    if q_heads % kv_heads or q_held * kv_heads != kv_held * q_heads:
        raise ValueError(f"{q_held} query heads over {kv_held} key/value "
                         f"heads are not the model's {q_heads} over "
                         f"{kv_heads}")
    experts = int(pub["n_routed_experts"])
    held = int(cfg["n_routed_experts"])
    first_expert = _held(dep, "experts_held", held, experts)
    shared = int(cfg["moe_intermediate_size"]) * int(
        cfg["n_shared_experts"])
    first_col, columns = (int(v) for v in dep["shared_columns_held"])
    if not 0 <= first_col <= first_col + columns <= shared:
        raise ValueError(f"shared_columns_held {dep['shared_columns_held']} "
                         f"of {shared} columns")
    _held(dep, "vocab_rows_held", int(cfg["vocab_size"]),
          int(pub["vocab_size"]))
    if int(cfg["num_hidden_layers"]) * int(dep["pipeline_stages"]) != int(
            pub["num_hidden_layers"]):
        raise ValueError(
            f"{dep['pipeline_stages']} stages of {cfg['num_hidden_layers']} "
            f"layers are not the {pub['num_hidden_layers']} published")
    return {"d": d, "kda_heads": kda_held, "dk": int(lin["head_dim"]),
            "dv": int(lin["head_dim"]),
            "conv": int(lin["short_conv_kernel_size"]),
            "rank": int(cfg["assumed"]["gate_rank"]),
            "chunk": int(cfg["assumed"]["chunk"]),
            "q_held": q_held, "kv_held": kv_held,
            "head_dim": int(cfg["head_dim"]), "experts": experts,
            "first_expert": first_expert, "experts_held": held,
            "top_k": int(cfg["num_experts_per_tok"]),
            "f": int(cfg["moe_intermediate_size"]), "shared": columns,
            "vocab": int(cfg["vocab_size"])}


def _stddevs(cfg: dict) -> tuple[float, float, float]:
    got = tuple(float(cfg["assumed"][key]) for key in
                ("weights_stddev", "embedding_stddev", "output_stddev"))
    if got != (STDDEV, EMBEDDING_STDDEV, OUTPUT_STDDEV):
        raise ValueError(f"assumed standard deviations {got} are not the "
                         f"{(STDDEV, EMBEDDING_STDDEV, OUTPUT_STDDEV)} that "
                         "make_weights draws")
    return got


# -- the program's layer list -------------------------------------------------
def layer_list(cfg: dict) -> list[dict]:
    """The ``StandardWorkflow`` layer list of the configuration, every
    hyper-parameter explicit."""
    hyp, s = cfg["assumed"], sizes(cfg)
    back = {key: float(hyp[key]) for key in
            ("learning_rate", "gradient_moment", "weights_decay")}
    stddev, _, _ = _stddevs(cfg)
    common = {"rms_norm_eps": float(cfg["rms_norm_eps"]),
              "weights_stddev": stddev}
    layers = [{"type": "embedding", "<-": back, "->": {
        **common, "vocab": s["vocab"], "hidden": s["d"]}}]
    for kind in kinds(cfg):
        if kind == "kda":
            layers.append({"type": "kda_block", "<-": back, "->": {
                **common, "heads": s["kda_heads"], "key_dim": s["dk"],
                "value_dim": s["dv"], "rank": s["rank"], "conv": s["conv"],
                "chunk": s["chunk"]}})
        else:
            layers.append({"type": "attn_block", "<-": back, "->": {
                **common, "heads": s["q_held"], "kv_heads": s["kv_held"],
                "head_dim": s["head_dim"], "window": None,
                "positional": "nope", "output_gate": True}})
        layers.append({"type": "moe_block", "<-": back, "->": {
            **common, "experts": s["experts"],
            "experts_held": [s["first_expert"], s["experts_held"]],
            "expert_width": s["f"], "top_k": s["top_k"],
            "norm_topk_prob": True, "shared_width": s["shared"]}})
    layers.append({"type": "lm_head", "<-": back, "->": {
        **common, "vocab": s["vocab"]}})
    return layers


# -- the parameter tree ---------------------------------------------------------
def param_shapes(cfg: dict) -> list:
    """One entry a layer of :func:`layer_list`: the shapes of its leaves,
    in the trainer's order."""
    s = sizes(cfg)
    d, h, r, c = s["d"], s["kda_heads"], s["rank"], s["conv"]
    qk, vv = h * s["dk"], h * s["dv"]
    kda = ((d,), (d, qk), (d, qk), (d, vv), (d, h), (d, r), (r, qk), (d, r),
           (r, vv), (vv,), (c, qk), (c, qk), (c, vv), (h,), (qk,), (s["dv"],),
           (vv, d))
    q, kv = s["q_held"] * s["head_dim"], s["kv_held"] * s["head_dim"]
    attn = ((d,), (d, q), (d, kv), (d, kv), (q, d), (d, q))
    e, f, sh = s["experts_held"], s["f"], s["shared"]
    moe = ((d,), (d, s["experts"]), (e, d, f), (e, d, f), (e, f, d),
           (d, sh), (d, sh), (sh, d))
    assert (len(kda), len(attn), len(moe)) == (KDA_LEAVES, ATTN_LEAVES,
                                               MOE_LEAVES)
    out = [((s["vocab"], d),)]
    for kind in kinds(cfg):
        out += [kda if kind == "kda" else attn, moe]
    out.append(((d,), (d, s["vocab"])))
    return out


def hypers(cfg: dict) -> list:
    hyp = cfg["assumed"]
    one = {"learning_rate": float(hyp["learning_rate"]),
           "weights_decay": float(hyp["weights_decay"])}
    return [tuple(one for _ in leaves) for leaves in param_shapes(cfg)]


def _draw(key, n_leaves: int, at: int, sh: tuple):
    """Leaf ``at`` of a layer of ``n_leaves`` leaves, which says its kind:
    the table at ``EMBEDDING_STDDEV``; ones for a norm's gain; zeros for the
    output gate's bias; the Mamba-2 starting point for the KDA layer's taps
    and decays (``assumed.kda_init``); normal(0, ``OUTPUT_STDDEV``) for the
    projections that write the stream, normal(0, ``STDDEV``) for every
    other."""
    f32 = jnp.float32

    def normal(stddev):
        return jax.random.normal(key, sh, f32) * np.float32(stddev)
    if n_leaves == 1:
        return normal(EMBEDDING_STDDEV)
    if n_leaves == KDA_LEAVES:
        if at == _KDA_BG:
            return jnp.zeros(sh, f32)
        if at in _KDA_TAPS:               # conv taps: within 1 / sqrt(taps)
            lim = np.float32(1.0 / np.sqrt(sh[0]))
            return jax.random.uniform(key, sh, f32, -lim, lim)
        if at == _KDA_DT_BIAS:            # a step in [1e-3, 0.1]
            dt = jnp.exp(jax.random.uniform(
                key, sh, f32, np.log(1e-3), np.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if at == _KDA_A_LOG:              # -exp(a_log) in [-16, -1]
            return jnp.log(jax.random.uniform(key, sh, f32, 1.0, 16.0))
    if len(sh) == 1:
        return jnp.ones(sh, f32)
    writes = {KDA_LEAVES: (_KDA_WO,), **_WRITES}.get(n_leaves, ())
    return normal(OUTPUT_STDDEV if at in writes else STDDEV)


def make_weights(seed: int, shapes: list) -> list:
    """The weights of :func:`param_shapes`' tree from the seed, float32, on
    the default device, in one jitted call (see :func:`_draw`)."""
    @jax.jit
    def build(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(0x501A2), words[0]), words[1])
        place, out = 0, []
        for leaves in shapes:
            made = []
            for at, sh in enumerate(leaves):
                made.append(_draw(jax.random.fold_in(key, place),
                                  len(leaves), at, tuple(sh)))
                place += 1
            out.append(tuple(made))
        return out
    return build(data.seed_array(seed))


# -- counts ---------------------------------------------------------------------
def rule_flops_per_token(cfg: dict) -> float:
    """Forward operations a token of one KDA layer's rule as the recurrence
    itself, the heads held: the state's decay a channel (``K V``), ``S k``
    (``2 K V``), the rank-one correction (``2 K V``) and ``S q`` (``2 K
    V``)."""
    s = sizes(cfg)
    return 7.0 * s["kda_heads"] * s["dk"] * s["dv"]


def attention_pair_flops(cfg: dict) -> int:
    """Forward operations of one kept query/key pair of a gated attention
    layer: the score and the mix, the query heads held."""
    s = sizes(cfg)
    return 4 * s["head_dim"] * s["q_held"]


def flops_by_part(cfg: dict, traffic: dict) -> dict:
    """Needed forward operations of one row, by part."""
    t, s = _seq_len(traffic), sizes(cfg)
    d, r = s["d"], s["rank"]
    ks = kinds(cfg)
    n_kda = ks.count("kda")
    n_full = len(ks) - n_kda
    qk, vv = s["kda_heads"] * s["dk"], s["kda_heads"] * s["dv"]
    q, kv = s["q_held"] * s["head_dim"], s["kv_held"] * s["head_dim"]
    expected = s["top_k"] * s["experts_held"] / s["experts"]
    return {
        "kda_projections": t * n_kda * 2.0 * (
            d * (2 * qk + vv + s["kda_heads"]) + 2 * d * r + r * (qk + vv)
            + vv * d),
        "kda_conv": t * n_kda * 2.0 * s["conv"] * (2 * qk + vv),
        "kda_rule": t * n_kda * rule_flops_per_token(cfg),
        "attention_projections": t * n_full * 2.0 * d * (3 * q + 2 * kv),
        "attention_scores": n_full * float(attention_pair_flops(cfg))
        * kept_pairs(t, None),
        "router": t * len(ks) * 2.0 * d * s["experts"],
        "shared_expert": t * len(ks) * 2.0 * 3 * d * s["shared"],
        "experts": t * len(ks) * expected * 2.0 * 3 * d * s["f"],
        "head": t * 2.0 * d * s["vocab"]}


def flops(cfg: dict, traffic: dict) -> dict:
    """Per row (one sequence): ``forward`` and ``train_step`` operations
    that are needed, ``params``, and ``matmul_train``."""
    fwd = float(sum(flops_by_part(cfg, traffic).values()))
    params = sum(int(np.prod(sh)) for leaves in param_shapes(cfg)
                 for sh in leaves)
    return {"forward": fwd, "train_step": 3.0 * fwd, "params": params,
            "matmul_train": 3.0 * fwd}


def step_bytes(cfg: dict, traffic: dict, batch: int) -> float:
    """Least HBM bytes of one training step: every float32 parameter read
    forward and backward, its gradient written and read, parameter and
    velocity read and written by the update (8 passes), and each block's
    cached input written and read."""
    blocks = 2 * len(kinds(cfg)) + 1
    return 4.0 * (8 * flops(cfg, traffic)["params"]
                  + 2 * blocks * batch * _seq_len(traffic)
                  * int(cfg["hidden_size"]))


def output_leaf(cfg: dict) -> int:
    """The last ``kda_block``'s ``dt_bias``: a bias a key channel whose
    gradient runs through nothing but that channel's decays, so that a
    decay made the same over a head's channels (or a wrong carry between
    chunks) reads in its own difference where the norms the check compares
    see a mixer that adds a few hundredths of the stream."""
    shapes = param_shapes(cfg)
    last = max(k for k, leaves in enumerate(shapes)
               if len(leaves) == KDA_LEAVES)
    return sum(len(leaves) for leaves in shapes[:last]) + _KDA_DT_BIAS
