"""The ``model`` file of the gated-delta-rule / full-attention hybrids
(``olmo-hybrid-7b``, and ``tiny-olmo-hybrid`` of the harness's tests): what
a row and a parameter tree are, behind the functions ``benchmark/README.md``
lists.

A row is a sequence of ``traffic["seq_len"]`` token ids with the next token
of every position as its target, from the seeded Markov chain of
``lib/decoder_model.py`` over the vocabulary rows held.  The program is
``StandardWorkflow`` over the layer list :func:`layer_list` makes of the
configuration's keys (``lib/olmo_hybrid_workflow.py``): ``embedding``, then
a ``gdn_block`` (``linear_attention``) or an ``attn_block`` with query/key
norms and no rotary tables (``full_attention``) and an ``mlp_block`` a
hidden layer, every block with its norm on the sublayer's OUTPUT, then an
untied ``lm_head``.

Every layer is whole (one chip holds each layer: ``deployment``); what is
held of the model is its first ``num_hidden_layers`` layers (a pipeline
stage) and ``vocab_size`` rows of the table and of the head.  The counts of
work are of the NEEDED operations only: the projections, the convs' taps,
the delta rule as the recurrence itself (a decay of the state, ``S k``, the
rank-one correction and ``S q``: about ``7 K V`` a head a token; the
chunked form's triangular system and its recomputation are not needed
work), kept query/key pairs, no recomputation."""

from __future__ import annotations

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
#: configuration's ``assumed.weights``): every projection, the table and
#: the head at ``STDDEV``; the projections that write into the residual
#: stream (the mixers' ``wo``, the feed-forward's ``wd``) at 0.02 /
#: sqrt(2 x 32 published layers)
STDDEV = 0.02
OUTPUT_STDDEV = 0.0025

GDN_LEAVES, ATTN_LEAVES, MLP_LEAVES = 14, 7, 4
#: leaves of a ``gdn_block`` by place: the conv taps, dt_bias and a_log
#: (the Mamba-2 starting point), the projection that writes the stream
_GDN_TAPS, _GDN_A_LOG, _GDN_DT_BIAS, _GDN_WO = (7, 8, 9), 10, 11, 13


def _seq_len(traffic: dict) -> int:
    return int(traffic["seq_len"])


def kinds(cfg: dict) -> list[str]:
    """The mixer of each hidden layer that runs: ``linear_attention`` |
    ``full_attention``."""
    out = list(cfg["layer_types"][:int(cfg["num_hidden_layers"])])
    for kind in out:
        if kind not in ("linear_attention", "full_attention"):
            raise ValueError(f"layer type {kind!r} is neither "
                             "linear_attention nor full_attention")
    return out


def sizes(cfg: dict) -> dict:
    """The widths (as published) and the rows held, checked against what
    the kinds can do."""
    pub, dep = cfg["published"], cfg["deployment"]
    d = int(cfg["hidden_size"])
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False),
                      ("linear_allow_neg_eigval", True),
                      ("rope_parameters", {"rope_theta": None})):
        if cfg[key] != want:
            raise ValueError(f"{key} {cfg[key]!r}: the kinds do {want!r}")
    heads = int(cfg["linear_num_value_heads"])
    if int(cfg["linear_num_key_heads"]) != heads:
        raise ValueError(f"{cfg['linear_num_key_heads']} key heads under "
                         f"{heads} value heads: the kind has one key head "
                         "a value head")
    q_heads = int(cfg["num_attention_heads"])
    if d % q_heads:
        raise ValueError(f"hidden_size {d} over {q_heads} query heads")
    if int(dep["chips_sharing_a_layer"]) != 1:
        raise ValueError("the layers are whole: one chip holds each")
    first, count = dep["vocab_rows_held"]
    if int(count) != int(cfg["vocab_size"]) or not \
            0 <= first <= first + count <= int(pub["vocab_size"]):
        raise ValueError(f"vocab_size {cfg['vocab_size']} is not the "
                         f"{count} of {pub['vocab_size']} rows the "
                         "deployment holds (vocab_rows_held)")
    if int(cfg["num_hidden_layers"]) * int(dep["pipeline_stages"]) != int(
            pub["num_hidden_layers"]):
        raise ValueError(
            f"{dep['pipeline_stages']} stages of {cfg['num_hidden_layers']} "
            f"layers are not the {pub['num_hidden_layers']} published")
    return {"d": d, "gdn_heads": heads,
            "dk": int(cfg["linear_key_head_dim"]),
            "dv": int(cfg["linear_value_head_dim"]),
            "conv": int(cfg["linear_conv_kernel_dim"]),
            "chunk": int(cfg["assumed"]["chunk"]),
            "q_heads": q_heads, "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": d // q_heads, "f": int(cfg["intermediate_size"]),
            "vocab": int(cfg["vocab_size"])}


def _stddevs(cfg: dict) -> tuple[float, float]:
    got = tuple(float(cfg["assumed"][key]) for key in
                ("weights_stddev", "output_stddev"))
    if got != (STDDEV, OUTPUT_STDDEV):
        raise ValueError(f"assumed standard deviations {got} are not the "
                         f"{(STDDEV, OUTPUT_STDDEV)} that make_weights "
                         "draws")
    return got


# -- the program's layer list -------------------------------------------------
def layer_list(cfg: dict) -> list[dict]:
    """The ``StandardWorkflow`` layer list of the configuration, every
    hyper-parameter explicit."""
    hyp, s = cfg["assumed"], sizes(cfg)
    back = {key: float(hyp[key]) for key in
            ("learning_rate", "gradient_moment", "weights_decay")}
    stddev, _ = _stddevs(cfg)
    common = {"rms_norm_eps": float(cfg["rms_norm_eps"]),
              "weights_stddev": stddev}
    block = {**common, "norm": "post"}
    layers = [{"type": "embedding", "<-": back, "->": {
        **common, "vocab": s["vocab"], "hidden": s["d"]}}]
    for kind in kinds(cfg):
        if kind == "linear_attention":
            layers.append({"type": "gdn_block", "<-": back, "->": {
                **block, "heads": s["gdn_heads"], "key_dim": s["dk"],
                "value_dim": s["dv"], "conv": s["conv"],
                "chunk": s["chunk"]}})
        else:
            layers.append({"type": "attn_block", "<-": back, "->": {
                **block, "heads": s["q_heads"], "kv_heads": s["kv_heads"],
                "head_dim": s["head_dim"], "window": None,
                "positional": "nope", "qk_norm": True}})
        layers.append({"type": "mlp_block", "<-": back, "->": {
            **block, "width": s["f"]}})
    layers.append({"type": "lm_head", "<-": back, "->": {
        **common, "vocab": s["vocab"]}})
    return layers


# -- the parameter tree ---------------------------------------------------------
def param_shapes(cfg: dict) -> list:
    """One entry a layer of :func:`layer_list`: the shapes of its leaves,
    in the trainer's order."""
    s = sizes(cfg)
    d, f, h = s["d"], s["f"], s["gdn_heads"]
    qk, vv = h * s["dk"], h * s["dv"]
    gdn = ((d,), (d, qk), (d, qk), (d, vv), (d, h), (d, h), (d, vv),
           (s["conv"], qk), (s["conv"], qk), (s["conv"], vv), (h,), (h,),
           (s["dv"],), (vv, d))
    q, kv = s["q_heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    attn = ((d,), (d, q), (d, kv), (d, kv), (q, d), (q,), (kv,))
    mlp = ((d,), (d, f), (d, f), (f, d))
    assert (len(gdn), len(attn), len(mlp)) == (GDN_LEAVES, ATTN_LEAVES,
                                               MLP_LEAVES)
    out = [((s["vocab"], d),)]
    for kind in kinds(cfg):
        out += [gdn if kind == "linear_attention" else attn, mlp]
    out.append(((d,), (d, s["vocab"])))
    return out


def hypers(cfg: dict) -> list:
    hyp = cfg["assumed"]
    one = {"learning_rate": float(hyp["learning_rate"]),
           "weights_decay": float(hyp["weights_decay"])}
    return [tuple(one for _ in leaves) for leaves in param_shapes(cfg)]


def _draw(key, n_leaves: int, at: int, sh: tuple):
    """Leaf ``at`` of a layer of ``n_leaves`` leaves, which says its kind:
    ones for a norm's gain; normal(0, ``STDDEV``) projections, table and
    head, normal(0, ``OUTPUT_STDDEV``) for those that write into the
    stream; the Mamba-2 starting point for the linear layer's taps and
    decay (``assumed.gdn_init``)."""
    f32 = jnp.float32

    def normal(stddev):
        return jax.random.normal(key, sh, f32) * np.float32(stddev)
    if n_leaves == GDN_LEAVES:
        if at in _GDN_TAPS:               # conv taps: within 1 / sqrt(taps)
            lim = np.float32(1.0 / np.sqrt(sh[0]))
            return jax.random.uniform(key, sh, f32, -lim, lim)
        if at == _GDN_DT_BIAS:            # a step in [1e-3, 0.1]
            dt = jnp.exp(jax.random.uniform(
                key, sh, f32, np.log(1e-3), np.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if at == _GDN_A_LOG:              # -exp(a_log) in [-16, -1]
            return jnp.log(jax.random.uniform(key, sh, f32, 1.0, 16.0))
    if len(sh) == 1:
        return jnp.ones(sh, f32)
    writes = {GDN_LEAVES: _GDN_WO, ATTN_LEAVES: 4, MLP_LEAVES: 3}
    return normal(OUTPUT_STDDEV if writes.get(n_leaves) == at else STDDEV)


def make_weights(seed: int, shapes: list) -> list:
    """The weights of :func:`param_shapes`' tree from the seed, float32, on
    the default device, in one jitted call (see :func:`_draw`)."""
    @jax.jit
    def build(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(0x01A0B7), words[0]), words[1])
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
def recurrence_flops_per_token(cfg: dict) -> float:
    """Forward operations a token of one linear layer's delta rule as the
    recurrence itself, all heads: the state's decay (``K V``), ``S k`` (``2
    K V``), the rank-one correction (``2 K V``) and ``S q`` (``2 K V``)."""
    s = sizes(cfg)
    return 7.0 * s["gdn_heads"] * s["dk"] * s["dv"]


def attention_pair_flops(cfg: dict) -> int:
    """Forward operations of one kept query/key pair of the full layer:
    the score and the mix, all heads."""
    s = sizes(cfg)
    return 4 * s["head_dim"] * s["q_heads"]


def flops_by_part(cfg: dict, traffic: dict) -> dict:
    """Needed forward operations of one row, by part."""
    t, s = _seq_len(traffic), sizes(cfg)
    d = s["d"]
    ks = kinds(cfg)
    n_lin = ks.count("linear_attention")
    n_full = len(ks) - n_lin
    qk, vv = s["gdn_heads"] * s["dk"], s["gdn_heads"] * s["dv"]
    q, kv = s["q_heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return {
        "linear_projections": t * n_lin * 2.0 * d * (
            2 * qk + 3 * vv + 2 * s["gdn_heads"]),
        "linear_conv": t * n_lin * 2.0 * s["conv"] * (2 * qk + vv),
        "linear_recurrence": t * n_lin * recurrence_flops_per_token(cfg),
        "attention_projections": t * n_full * 2.0 * d * (2 * q + 2 * kv),
        "attention_scores": n_full * float(attention_pair_flops(cfg))
        * kept_pairs(t, None),
        "feed_forward": t * len(ks) * 2.0 * 3 * d * s["f"],
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
    """The head's ``w``: the last leaf of all."""
    return sum(len(leaves) for leaves in param_shapes(cfg)) - 1
