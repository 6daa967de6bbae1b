"""The ``model`` file of the parallel hybrids (``falcon-h1-34b-instruct``, and
``tiny-falcon-h1`` of the harness's tests): what a row and a parameter tree
are, behind the functions ``benchmark/README.md`` lists.

A row is a sequence of ``traffic["seq_len"]`` token ids with the next token
of every position as its target, from the seeded Markov chain of
``lib/decoder_model.py`` over the vocabulary rows held.  The program is
``StandardWorkflow`` over the layer list :func:`layer_list` makes of the
configuration's keys (``lib/falcon_h1_workflow.py``): ``embedding`` (times
``embedding_multiplier``), then a ``parallel_block`` (attention and a
Mamba-2 mixer on one normed input, each with its multipliers) and an
``mlp_block`` (its gate multiplier inside the activation, its down
multiplier on what it adds) a hidden layer, then an untied ``lm_head``
times ``lm_head_multiplier``.

Every count a kind is told is the chip's share, read from the keys
``reduced`` lists, beside the model's own from ``published``: query and
key/value heads, Mamba heads and groups, vocabulary rows; the feed-forward's
columns held are ``deployment.mlp_columns_held`` (its width is a published
width and stays in its key).  The counts of work are of the NEEDED
operations only: the held share's products, kept query/key pairs, the scan
in its chunked form at ``mamba_chunk_size`` as ``granite_model`` counts it
(kept pairs inside a chunk, the closing states and the carried term), no
recomputation."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import data
from .decoder_model import (install, kept_pairs, make_rows, overrides,
                            traced_rows)

__all__ = ["install", "make_rows", "overrides", "traced_rows"]

#: what one row of the data set is, for a person (the result's
#: ``window``); ``tokens`` is the accepted cell's (``traffic["seq_len"]``)
row = {"kind": "sequence", "tokens": 4096}

#: standard deviations of the weights the benchmark makes (the
#: configuration's ``assumed.weights``): every projection, the table and the
#: head at ``STDDEV``, the query and key projections at ``QK_STDDEV``, so
#: that the scores, under the key multiplier and ``1 / sqrt(head_dim)``
#: (2^-10 in all), are of order one and not uniform
STDDEV = 0.02
QK_STDDEV = 0.1

PARALLEL_LEAVES, MLP_LEAVES = 13, 4
#: leaves of a ``parallel_block`` by place: wq and wk, the conv taps and
#: bias, dt_bias and a_log (the Mamba-2 starting point)
_QK, _TAPS, _CONV_B, _DT_BIAS, _A_LOG = (1, 2), 6, 7, 8, 9


def _seq_len(traffic: dict) -> int:
    return int(traffic["seq_len"])


def _held(dep: dict, name: str, count: int, of: int) -> int:
    first, held = (int(v) for v in dep[name])
    if held != count or not 0 <= first <= first + held <= of:
        raise ValueError(f"{name} {dep[name]}: not {count} of {of}")
    return first


def sizes(cfg: dict) -> dict:
    """The widths (as published) and the counts held here beside the
    model's, checked against what the kinds can do: a layout that does not
    add up is refused."""
    pub, dep = cfg["published"], cfg["deployment"]
    d = int(cfg["hidden_size"])
    for key, want in (("attention_bias", False), ("mlp_bias", False),
                      ("projectors_bias", False), ("mamba_conv_bias", True),
                      ("mamba_proj_bias", False), ("mamba_rms_norm", True),
                      ("mamba_norm_before_gate", False),
                      ("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("rope_scaling", None), ("attn_layer_indices", None),
                      ("attention_in_multiplier", 1)):
        if cfg[key] != want:
            raise ValueError(f"{key} {cfg[key]!r}: the kinds do {want!r}")
    hd, p = int(cfg["head_dim"]), int(cfg["mamba_d_head"])
    heads, groups = int(pub["mamba_n_heads"]), int(pub["mamba_n_groups"])
    if heads * p != int(cfg["mamba_d_ssm"]):
        raise ValueError(f"{heads} Mamba heads of {p} are not mamba_d_ssm "
                         f"{cfg['mamba_d_ssm']}")
    if heads % groups:
        raise ValueError(f"{heads} Mamba heads do not divide into {groups} "
                         "groups")
    per = heads // groups
    held = int(cfg["mamba_n_heads"])
    first = _held(dep, "mamba_heads_held", held, heads)
    last = first + held - 1
    if first // per != last // per and (first % per or held % per):
        raise ValueError(f"Mamba heads {first}-{last} straddle the groups of "
                         f"{per} heads")
    if int(cfg["mamba_n_groups"]) != max(1, held // per):
        raise ValueError(f"mamba_n_groups {cfg['mamba_n_groups']}: heads "
                         f"{first}-{last} read {max(1, held // per)}")
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
    _held(dep, "vocab_rows_held", int(cfg["vocab_size"]),
          int(pub["vocab_size"]))
    first_col, columns = dep["mlp_columns_held"]
    if not 0 <= first_col <= first_col + columns <= int(
            cfg["intermediate_size"]):
        raise ValueError(f"mlp_columns_held {dep['mlp_columns_held']} of "
                         f"{cfg['intermediate_size']} columns")
    if int(cfg["num_hidden_layers"]) * int(dep["pipeline_stages"]) != int(
            pub["num_hidden_layers"]):
        raise ValueError(
            f"{dep['pipeline_stages']} stages of {cfg['num_hidden_layers']} "
            f"layers are not the {pub['num_hidden_layers']} published")
    return {"d": d, "head_dim": hd, "q_held": q_held, "kv_held": kv_held,
            "mamba_heads": heads, "mamba_held": held, "groups": groups,
            "groups_held": int(cfg["mamba_n_groups"]), "p": p,
            "n": int(cfg["mamba_d_state"]), "conv": int(cfg["mamba_d_conv"]),
            "chunk": int(cfg["mamba_chunk_size"]), "f": int(columns),
            "vocab": int(cfg["vocab_size"]),
            "layers": int(cfg["num_hidden_layers"])}


def _stddevs(cfg: dict) -> tuple[float, float]:
    got = tuple(float(cfg["assumed"][key]) for key in
                ("weights_stddev", "qk_stddev"))
    if got != (STDDEV, QK_STDDEV):
        raise ValueError(f"assumed standard deviations {got} are not the "
                         f"{(STDDEV, QK_STDDEV)} that make_weights draws")
    return got


# -- the program's layer list -------------------------------------------------
def layer_list(cfg: dict) -> list[dict]:
    """The ``StandardWorkflow`` layer list of the configuration, every
    hyper-parameter explicit.  The key multiplier rides on the score scale
    (it meets nothing but the scores); the input multiplier of the Mamba
    mixer on its five piece multipliers (a power of two: the same
    numbers)."""
    hyp, s = cfg["assumed"], sizes(cfg)
    back = {key: float(hyp[key]) for key in
            ("learning_rate", "gradient_moment", "weights_decay")}
    stddev, _ = _stddevs(cfg)
    common = {"rms_norm_eps": float(cfg["rms_norm_eps"]),
              "weights_stddev": stddev}
    ssm_in = float(cfg["ssm_in_multiplier"])
    gate, down = (float(v) for v in cfg["mlp_multipliers"])
    layers = [{"type": "embedding", "<-": back, "->": {
        **common, "vocab": s["vocab"], "hidden": s["d"],
        "scale": float(cfg["embedding_multiplier"])}}]
    for _ in range(s["layers"]):
        layers.append({"type": "parallel_block", "<-": back, "->": {
            **common, "heads": s["q_held"], "kv_heads": s["kv_held"],
            "head_dim": s["head_dim"],
            "rope": {"rope_type": "default",
                     "rope_theta": float(cfg["rope_theta"])},
            "score_scale": float(cfg["key_multiplier"])
            / math.sqrt(s["head_dim"]),
            "ssm_heads": s["mamba_heads"], "ssm_heads_held": s["mamba_held"],
            "ssm_head_dim": s["p"], "state": s["n"], "conv": s["conv"],
            "chunk": s["chunk"], "groups": s["groups"],
            "in_scales": [ssm_in * float(m) for m in cfg["ssm_multipliers"]],
            "attn_scale": float(cfg["attention_out_multiplier"]),
            "ssm_scale": float(cfg["ssm_out_multiplier"])}})
        layers.append({"type": "mlp_block", "<-": back, "->": {
            **common, "width": s["f"], "gate_scale": gate, "scale": down}})
    layers.append({"type": "lm_head", "<-": back, "->": {
        **common, "vocab": s["vocab"],
        "scale": float(cfg["lm_head_multiplier"])}})
    return layers


# -- the parameter tree ---------------------------------------------------------
def param_shapes(cfg: dict) -> list:
    """One entry a layer of :func:`layer_list`: the shapes of its leaves,
    in the trainer's order."""
    s = sizes(cfg)
    d, f, h = s["d"], s["f"], s["mamba_held"]
    q, kv = s["q_held"] * s["head_dim"], s["kv_held"] * s["head_dim"]
    d_in, bc = h * s["p"], 2 * s["groups_held"] * s["n"]
    parallel = ((d,), (d, q), (d, kv), (d, kv), (q, d),
                (d, 2 * d_in + bc + h), (s["conv"], d_in + bc),
                (d_in + bc,), (h,), (h,), (h,), (d_in,), (d_in, d))
    mlp = ((d,), (d, f), (d, f), (f, d))
    assert (len(parallel), len(mlp)) == (PARALLEL_LEAVES, MLP_LEAVES)
    return [((s["vocab"], d),), *[block for _ in range(s["layers"])
                                  for block in (parallel, mlp)],
            ((d,), (d, s["vocab"]))]


def hypers(cfg: dict) -> list:
    hyp = cfg["assumed"]
    one = {"learning_rate": float(hyp["learning_rate"]),
           "weights_decay": float(hyp["weights_decay"])}
    return [tuple(one for _ in leaves) for leaves in param_shapes(cfg)]


def _draw(key, n_leaves: int, at: int, sh: tuple):
    """Leaf ``at`` of a layer of ``n_leaves`` leaves, which says its kind:
    ones for a norm's gain and the Mamba skip ``D``; normal(0, ``STDDEV``)
    projections, table and head, normal(0, ``QK_STDDEV``) the query and key
    projections; the Mamba-2 starting point for the taps and the decay
    (``assumed.mamba_init``)."""
    f32 = jnp.float32

    def normal(stddev):
        return jax.random.normal(key, sh, f32) * np.float32(stddev)
    if n_leaves == PARALLEL_LEAVES:
        if at == _TAPS:                   # conv taps: within 1 / sqrt(taps)
            lim = np.float32(1.0 / np.sqrt(sh[0]))
            return jax.random.uniform(key, sh, f32, -lim, lim)
        if at == _CONV_B:
            return jnp.zeros(sh, f32)
        if at == _DT_BIAS:                # a step in [1e-3, 0.1]
            dt = jnp.exp(jax.random.uniform(
                key, sh, f32, np.log(1e-3), np.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if at == _A_LOG:                  # -exp(a_log) in [-16, -1]
            return jnp.log(jax.random.uniform(key, sh, f32, 1.0, 16.0))
        if at in _QK:
            return normal(QK_STDDEV)
    if len(sh) == 1:
        return jnp.ones(sh, f32)
    return normal(STDDEV)


def make_weights(seed: int, shapes: list) -> list:
    """The weights of :func:`param_shapes`' tree from the seed, float32, on
    the default device, in one jitted call (see :func:`_draw`)."""
    @jax.jit
    def build(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(0xFA1C0), words[0]), words[1])
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
def scan_flops_per_token(cfg: dict) -> float:
    """Forward operations a token of one Mamba mixer's scan in its chunked
    form, for the heads held: ``(L + 1) / 2`` kept pairs a token inside a
    chunk at ``2 G N`` (``C B^T`` a held group) and ``2 d_in`` (times ``dt
    x``) each, and ``2 d_in N`` for the closing state and for the carried
    term."""
    s = sizes(cfg)
    d_in = s["mamba_held"] * s["p"]
    return (s["chunk"] + 1) / 2 * 2 * (s["groups_held"] * s["n"] + d_in) \
        + 4.0 * d_in * s["n"]


def attention_pair_flops(cfg: dict) -> int:
    """Forward operations of one kept query/key pair: the score and the
    mix, the query heads held."""
    s = sizes(cfg)
    return 4 * s["head_dim"] * s["q_held"]


def flops_by_part(cfg: dict, traffic: dict) -> dict:
    """Needed forward operations of one row, by part."""
    t, s = _seq_len(traffic), sizes(cfg)
    d, layers, h = s["d"], s["layers"], s["mamba_held"]
    d_in, bc = h * s["p"], 2 * s["groups_held"] * s["n"]
    q, kv = s["q_held"] * s["head_dim"], s["kv_held"] * s["head_dim"]
    return {
        "mamba_projections": t * layers * 2.0 * d * (
            2 * d_in + bc + h + d_in),
        "mamba_conv": t * layers * 2.0 * s["conv"] * (d_in + bc),
        "mamba_scan": t * layers * scan_flops_per_token(cfg),
        "attention_projections": t * layers * 2.0 * d * (2 * q + 2 * kv),
        "attention_scores": layers * float(attention_pair_flops(cfg))
        * kept_pairs(t, None),
        "feed_forward": t * layers * 2.0 * 3 * d * s["f"],
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
    blocks = 2 * sizes(cfg)["layers"] + 1
    return 4.0 * (8 * flops(cfg, traffic)["params"]
                  + 2 * blocks * batch * _seq_len(traffic)
                  * int(cfg["hidden_size"]))


def output_leaf(cfg: dict) -> int:
    """The last ``parallel_block``'s ``a_log``: the one leaf whose gradient
    runs through nothing but the scan's decays, the state carried from
    chunk to chunk among them.  Beside ``D x`` at the Mamba-2 starting
    point the scan adds little to the stream, so no norm the check
    compares sees a wrong carry; this leaf's own difference does."""
    shapes = param_shapes(cfg)
    last = max(k for k, leaves in enumerate(shapes)
               if len(leaves) == PARALLEL_LEAVES)
    return sum(len(leaves) for leaves in shapes[:last]) + _A_LOG
