"""The ``model`` file of the Mamba-2 / sparse-expert hybrids
(``granite-4.0-h-small``, and ``tiny-hybrid`` of the harness's tests): what
a row and a parameter tree are, behind the functions ``benchmark/README.md``
lists.

A row is a sequence of ``traffic["seq_len"]`` token ids with the next token
of every position as its target, from the seeded Markov chain of
``lib/decoder_model.py`` over the vocabulary rows held.  The program is
``StandardWorkflow`` over the layer list :func:`layer_list` makes of the
configuration's keys (``lib/granite_workflow.py``): ``embedding`` (times
``embedding_multiplier``), then a ``mamba_block`` or an ``attn_block`` and a
``moe_block`` (with the shared expert's columns held) a hidden layer, each
adding ``residual_multiplier`` times its output, then an ``lm_head`` tied to
the embedding's table, its logits over ``logits_scaling``.

Every count a kind is told is the chip's share, read from the keys
``reduced`` lists, beside the model's own from ``published``: Mamba heads,
query and key/value heads, experts, vocabulary rows; the shared expert's
columns held are ``deployment.shared_columns_held`` (its width is a
published width and stays in its key).  The counts of work are of the NEEDED operations only: the held
share's products, ``num_experts_per_tok * held / experts`` expected
assignments a token, kept query/key pairs, the scan in its chunked form at
``mamba_chunk_size`` (kept pairs inside a chunk, the closing states and
the carried term), no recomputation."""

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
row = {"kind": "sequence", "tokens": 2048}

#: standard deviations of the weights the benchmark makes (the
#: configuration's ``assumed.weights``): every projection and the table at
#: ``STDDEV``; the projections that write into the residual stream
#: (``w_out``, ``wo``, ``wd``, ``sd``) at 0.02 / sqrt(2 x 40 published
#: layers).  With the embedding multiplier of 12 a token's own row then
#: outweighs what twenty blocks add at ``residual_multiplier`` 0.22, so a
#: fresh router sees the token and stays near balance.
STDDEV = 0.02
OUTPUT_STDDEV = 0.0022361

MAMBA_LEAVES, ATTN_LEAVES, MOE_LEAVES = 9, 5, 8


def _seq_len(traffic: dict) -> int:
    return int(traffic["seq_len"])


def kinds(cfg: dict) -> list[str]:
    """The mixer of each hidden layer that runs: ``mamba`` | ``attention``."""
    out = list(cfg["layer_types"][:int(cfg["num_hidden_layers"])])
    for kind in out:
        if kind not in ("mamba", "attention"):
            raise ValueError(f"layer type {kind!r} is neither mamba nor "
                             "attention")
    return out


def sizes(cfg: dict) -> dict:
    """The widths (as published) and the counts held here beside the
    model's, checked against what the kinds can do."""
    pub, dep = cfg["published"], cfg["deployment"]
    d = int(cfg["hidden_size"])
    for key, want in (("mamba_n_groups", 1), ("mamba_conv_bias", True),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("hidden_act", "silu"),
                      ("normalization_function", "rmsnorm"),
                      ("position_embedding_type", "nope"),
                      ("tie_word_embeddings", True)):
        if cfg[key] != want:
            raise ValueError(f"{key} {cfg[key]!r}: the kinds do {want!r}")
    heads, p = int(pub["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if heads * p != int(cfg["mamba_expand"]) * d:
        raise ValueError(f"{heads} Mamba heads of {p} are not "
                         f"mamba_expand {cfg['mamba_expand']} x {d}")
    q_heads = int(pub["num_attention_heads"])
    if d % q_heads:
        raise ValueError(f"hidden_size {d} over {q_heads} query heads")
    held = {"mamba_n_heads": "mamba_heads_held",
            "num_attention_heads": "attention_heads_held",
            "num_key_value_heads": "kv_heads_held",
            "num_local_experts": "experts_held",
            "vocab_size": "vocab_rows_held"}
    for key, name in held.items():
        first, count = dep[name]
        if int(count) != int(cfg[key]) or not \
                0 <= first <= first + count <= int(pub[key]):
            raise ValueError(f"{key} {cfg[key]} is not the {count} of "
                             f"{pub[key]} the deployment holds ({name})")
    # the shared expert's width is never cut: its key stays as published
    # and the deployment says which of its columns are held
    first, shared_held = dep["shared_columns_held"]
    if not 0 <= first <= first + shared_held <= int(
            cfg["shared_intermediate_size"]):
        raise ValueError(f"shared_columns_held {dep['shared_columns_held']}"
                         f" of {cfg['shared_intermediate_size']} columns")
    return {"d": d, "mamba_heads": heads,
            "mamba_held": int(cfg["mamba_n_heads"]), "p": p,
            "n": int(cfg["mamba_d_state"]), "conv": int(cfg["mamba_d_conv"]),
            "chunk": int(cfg["mamba_chunk_size"]),
            "q_held": int(cfg["num_attention_heads"]),
            "kv_held": int(cfg["num_key_value_heads"]),
            "head_dim": d // q_heads,
            "experts": int(pub["num_local_experts"]),
            "experts_held": [int(v) for v in dep["experts_held"]],
            "f": int(cfg["intermediate_size"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "shared_held": int(shared_held),
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
    block = {**common, "scale": float(cfg["residual_multiplier"])}
    layers = [{"type": "embedding", "<-": back, "->": {
        **common, "vocab": s["vocab"], "hidden": s["d"],
        "scale": float(cfg["embedding_multiplier"])}}]
    for kind in kinds(cfg):
        if kind == "mamba":
            layers.append({"type": "mamba_block", "<-": back, "->": {
                **block, "heads": s["mamba_heads"],
                "heads_held": s["mamba_held"], "head_dim": s["p"],
                "state": s["n"], "conv": s["conv"], "chunk": s["chunk"]}})
        else:
            layers.append({"type": "attn_block", "<-": back, "->": {
                **block, "heads": s["q_held"], "kv_heads": s["kv_held"],
                "head_dim": s["head_dim"], "window": None,
                "positional": cfg["position_embedding_type"],
                "score_scale": float(cfg["attention_multiplier"])}})
        layers.append({"type": "moe_block", "<-": back, "->": {
            **block, "experts": s["experts"],
            "experts_held": s["experts_held"], "expert_width": s["f"],
            "top_k": s["top_k"], "norm_topk_prob": True,
            "shared_width": s["shared_held"]}})
    layers.append({"type": "lm_head", "<-": back, "->": {
        **common, "vocab": s["vocab"], "tie": 0,
        "scale": 1.0 / float(cfg["logits_scaling"])}})
    return layers


# -- the parameter tree ---------------------------------------------------------
def param_shapes(cfg: dict) -> list:
    """One entry a layer of :func:`layer_list`: the shapes of its leaves,
    in the trainer's order.  The tied head holds its norm's gain alone:
    the table is the embedding's one leaf."""
    s = sizes(cfg)
    d, n, f, sh = s["d"], s["n"], s["f"], s["shared_held"]
    d_in = s["mamba_held"] * s["p"]
    held = s["experts_held"][1]
    mamba = ((d,), (d, 2 * d_in + 2 * n + s["mamba_held"]),
             (s["conv"], d_in + 2 * n), (d_in + 2 * n,),
             (s["mamba_held"],), (s["mamba_held"],), (s["mamba_held"],),
             (d_in,), (d_in, d))
    q, kv = s["q_held"] * s["head_dim"], s["kv_held"] * s["head_dim"]
    attn = ((d,), (d, q), (d, kv), (d, kv), (q, d))
    moe = ((d,), (d, s["experts"]), (held, d, f), (held, d, f),
           (held, f, d), (d, sh), (d, sh), (sh, d))
    out = [((s["vocab"], d),)]
    for kind in kinds(cfg):
        out += [mamba if kind == "mamba" else attn, moe]
    out.append(((d,),))
    return out


def hypers(cfg: dict) -> list:
    hyp = cfg["assumed"]
    one = {"learning_rate": float(hyp["learning_rate"]),
           "weights_decay": float(hyp["weights_decay"])}
    return [tuple(one for _ in leaves) for leaves in param_shapes(cfg)]


def _draw(key, n_leaves: int, at: int, sh: tuple):
    """Leaf ``at`` of a layer of ``n_leaves`` leaves, which says its kind:
    ones for a norm's gain and the Mamba skip; normal(0, ``STDDEV``)
    projections and table, normal(0, ``OUTPUT_STDDEV``) for those that
    write into the stream; the Mamba-2 starting point for the rest
    (``assumed.mamba_init``)."""
    f32 = jnp.float32

    def normal(stddev):
        return jax.random.normal(key, sh, f32) * np.float32(stddev)
    if n_leaves == MAMBA_LEAVES:
        if at == 2:                       # conv taps: within 1 / sqrt(taps)
            lim = np.float32(1.0 / np.sqrt(sh[0]))
            return jax.random.uniform(key, sh, f32, -lim, lim)
        if at == 3:                       # conv bias
            return jnp.zeros(sh, f32)
        if at == 4:                       # dt_bias: a step in [1e-3, 0.1]
            dt = jnp.exp(jax.random.uniform(
                key, sh, f32, np.log(1e-3), np.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if at == 5:                       # a = -exp(a_log) in [-16, -1]
            return jnp.log(jax.random.uniform(key, sh, f32, 1.0, 16.0))
        return (jnp.ones(sh, f32) if len(sh) == 1
                else normal(OUTPUT_STDDEV if at == 8 else STDDEV))
    if len(sh) == 1:
        return jnp.ones(sh, f32)
    writes = {ATTN_LEAVES: (4,), MOE_LEAVES: (4, 7)}.get(n_leaves, ())
    return normal(OUTPUT_STDDEV if at in writes else STDDEV)


def make_weights(seed: int, shapes: list) -> list:
    """The weights of :func:`param_shapes`' tree from the seed, float32, on
    the default device, in one jitted call (see :func:`_draw`)."""
    @jax.jit
    def build(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(0x6A417E), words[0]), words[1])
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
    """Forward operations a token of one Mamba layer's scan in its chunked
    form, for the heads held: ``(L + 1) / 2`` kept pairs a token inside a
    chunk at ``2 N`` (``C B^T``) and ``2 d_in`` (times ``dt x``) each, and
    ``2 d_in N`` for the closing state and for the carried term."""
    s = sizes(cfg)
    d_in = s["mamba_held"] * s["p"]
    return (s["chunk"] + 1) / 2 * 2 * (s["n"] + d_in) + 4.0 * d_in * s["n"]


def expert_flops_per_assignment(cfg: dict) -> int:
    """Forward operations of one held token-expert pair: three products
    of ``hidden x expert width``."""
    return 2 * 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def flops_by_part(cfg: dict, traffic: dict) -> dict:
    """Needed forward operations of one row, by part."""
    t, s = _seq_len(traffic), sizes(cfg)
    d, n = s["d"], s["n"]
    d_in = s["mamba_held"] * s["p"]
    ks = kinds(cfg)
    n_mamba = ks.count("mamba")
    n_attn = len(ks) - n_mamba
    q, kv = s["q_held"] * s["head_dim"], s["kv_held"] * s["head_dim"]
    expected = s["top_k"] * s["experts_held"][1] / s["experts"]
    return {
        "mamba_projections": t * n_mamba * 2.0 * d * (
            2 * d_in + 2 * n + s["mamba_held"] + d_in),
        "mamba_conv": t * n_mamba * 2.0 * s["conv"] * (d_in + 2 * n),
        "mamba_scan": t * n_mamba * scan_flops_per_token(cfg),
        "attention_projections": t * n_attn * 2.0 * d * (2 * q + 2 * kv),
        "attention_scores": n_attn * 4.0 * s["head_dim"] * s["q_held"]
        * kept_pairs(t, None),
        "router": t * len(ks) * 2.0 * d * s["experts"],
        "experts": t * len(ks) * expected
        * expert_flops_per_assignment(cfg),
        "shared_expert": t * len(ks) * 2.0 * 3 * d * s["shared_held"],
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
    """The tied table, the first leaf of all: the head's product reaches
    it through one product, and the embedding's rows add to it."""
    return 0

