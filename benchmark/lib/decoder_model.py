"""The ``model`` file of the decoder language models (``mellum2-12b-a2.5b``,
and ``tiny-decoder`` of the harness's tests): what a row and a parameter
tree are, behind the functions ``benchmark/README.md`` lists.

A row is a sequence of ``traffic["seq_len"]`` token ids with the next
token of every position as its target, drawn from a seeded first-order
Markov chain over the vocabulary held (a token's ``assumed.branching``
equally likely successors are those of its class ``id % assumed.chain_states``),
so that the loss can fall below ``log(vocabulary)``.  The program is ``StandardWorkflow`` over the layer
list :func:`layer_list` makes of the configuration's published keys
(``lib/decoder_workflow.py``): ``embedding``, then an ``attn_block`` and a
``moe_block`` a hidden layer, then ``lm_head``.

The counts are of the NEEDED work only: the held experts' products for
the expected two (``top_k * held / experts``) assignments a token, score
products of kept query/key pairs only, no recomputation."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import data
from .errors import BenchError

_C2 = data._C2


def _seq_len(traffic: dict) -> int:
    return int(traffic["seq_len"])


def _kinds(cfg: dict) -> list[str]:
    kinds = list(cfg["layer_types"][:int(cfg["num_hidden_layers"])])
    for kind in kinds:
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer type {kind!r} is neither "
                             "sliding_attention nor full_attention")
    return kinds


def _experts(cfg: dict) -> tuple[int, int, int]:
    """(published experts, first held, held)."""
    first, count = cfg["deployment"]["experts_held"]
    if int(count) != int(cfg["num_experts"]):
        raise ValueError(f"num_experts {cfg['num_experts']} is not the "
                         f"{count} experts the deployment holds")
    return int(cfg["published"]["num_experts"]), int(first), int(count)


def _stddevs(cfg: dict) -> tuple[float, float, float]:
    """(weights, embedding table, residual projections): the
    configuration states what :func:`make_weights`, which sees no
    configuration, draws."""
    got = tuple(float(cfg["assumed"][key]) for key in
                ("weights_stddev", "embedding_stddev", "output_stddev"))
    if got != (STDDEV, EMBEDDING_STDDEV, OUTPUT_STDDEV):
        raise ValueError(
            f"assumed standard deviations {got} are not the "
            f"{(STDDEV, EMBEDDING_STDDEV, OUTPUT_STDDEV)} that "
            "make_weights draws")
    return got


#: what one row of the data set is, for a person (the result's
#: ``window``); ``tokens`` is the accepted cell's (``traffic["seq_len"]``)
row = {"kind": "sequence", "tokens": 8192}


# -- the program's layer list -------------------------------------------------
def layer_list(cfg: dict) -> list[dict]:
    """The ``StandardWorkflow`` layer list of the configuration, every
    hyper-parameter explicit."""
    hyp = cfg["assumed"]
    back = {key: float(hyp[key]) for key in
            ("learning_rate", "gradient_moment", "weights_decay")}
    experts, first, held = _experts(cfg)
    stddev, embedding_stddev, _ = _stddevs(cfg)
    common = {"rms_norm_eps": float(cfg["rms_norm_eps"]),
              "weights_stddev": stddev}
    layers = [{"type": "embedding", "<-": back, "->": {
        **common, "weights_stddev": embedding_stddev,
        "vocab": int(cfg["vocab_size"]),
        "hidden": int(cfg["hidden_size"])}}]
    for kind in _kinds(cfg):
        layers.append({"type": "attn_block", "<-": back, "->": {
            **common, "heads": int(cfg["num_attention_heads"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "window": (int(cfg["sliding_window"])
                       if kind == "sliding_attention" else None),
            "rope": dict(cfg["rope_parameters"][kind])}})
        layers.append({"type": "moe_block", "<-": back, "->": {
            **common, "experts": experts, "experts_held": [first, held],
            "expert_width": int(cfg["moe_intermediate_size"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "norm_topk_prob": bool(cfg["norm_topk_prob"])}})
    layers.append({"type": "lm_head", "<-": back, "->": {
        **common, "vocab": int(cfg["vocab_size"])}})
    return layers


def overrides(cfg: dict, traffic: dict, seed: int) -> list[str]:
    """The ``path=value`` lines for the program's config tree."""
    return [
        f"bench.seed={int(seed)}",
        f"bench_decoder.minibatch_size={int(traffic['minibatch'])}",
        f"bench_decoder.seq_len={_seq_len(traffic)}",
        *(f"bench_decoder.sizes.{k}={int(traffic[k])}"
          for k in ("n_train", "n_valid", "n_test")),
        f"common.compute_dtype={cfg['precision']['matmul_operands']}",
        "bench_decoder.decision.max_epochs=1000000000",
        "bench_decoder.decision.fail_iterations=1000000000"]


# -- rows ---------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _make_rows(words, rows, seq_len: int, vocab: int, branching: int,
               states: int):
    rows = jnp.asarray(rows).astype(jnp.uint32)
    chain = data._key(words, 0xC4A1)          # the chain is the seed's
    start = data._mix((rows * _C2) ^ data._key(words, 0x57A7)) \
        % np.uint32(vocab)
    row_key = data._mix((rows * data._C1) ^ data._key(words, 0x91C5))

    def step(cur, t):
        pick = data._mix((t * _C2) ^ row_key) % np.uint32(branching)
        nxt = data._mix(((cur % np.uint32(states) * np.uint32(branching)
                          + pick) * _C2) ^ chain) % np.uint32(vocab)
        return nxt, nxt
    _, tail = jax.lax.scan(step, start,
                           jnp.arange(seq_len, dtype=jnp.uint32))
    ids = jnp.concatenate([start[None], tail], axis=0).T.astype(jnp.int32)
    return ids[:, :-1], ids[:, 1:]


def make_rows(seed: int, rows, cfg: dict, traffic: dict):
    """(ids ``(n, T)``, next ids ``(n, T)``), int32, of the global row
    numbers ``rows``."""
    return _make_rows(data.seed_array(seed), np.asarray(rows, np.uint32),
                      _seq_len(traffic), int(cfg["vocab_size"]),
                      int(cfg["assumed"]["branching"]),
                      int(cfg["assumed"]["chain_states"]))


# -- the parameter tree ---------------------------------------------------------
def param_shapes(cfg: dict) -> list:
    """One entry a layer of :func:`layer_list`: the shapes of its leaves,
    in the trainer's order."""
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    nh, nkv = (int(cfg["num_attention_heads"]),
               int(cfg["num_key_value_heads"]))
    f, v = int(cfg["moe_intermediate_size"]), int(cfg["vocab_size"])
    experts, _, held = _experts(cfg)
    out = [((v, d),)]
    for _ in _kinds(cfg):
        out.append(((d,), (d, nh * hd), (d, nkv * hd), (d, nkv * hd),
                    (nh * hd, d)))
        out.append(((d,), (d, experts), (held, d, f), (held, d, f),
                    (held, f, d)))
    out.append(((d,), (d, v)))
    return out


def hypers(cfg: dict) -> list:
    hyp = cfg["assumed"]
    one = {"learning_rate": float(hyp["learning_rate"]),
           "weights_decay": float(hyp["weights_decay"])}
    return [tuple(one for _ in leaves) for leaves in param_shapes(cfg)]


#: standard deviations of the weights the benchmark makes (the
#: configuration's ``assumed.weights``).  Random weights at one 0.02
#: route as no trained model does: the mean of the values a query attends
#: to, nearly one vector for every late position, outweighs a token's own
#: 0.02 row from the first block on and grows about 1.5-fold a block
#: (``Wo Wv`` has a gain above 1 on it), every token of a row takes the
#: same ``top_k`` experts, and the step's time follows how many of those
#: this chip happens to hold.  So the embedding table is drawn at unit
#: scale, and the projections that write into the residual stream
#: (``Wo``, ``Wd``: leaf 4 of a block's five) at 0.02 / sqrt(2 x 28
#: published layers), the usual scaled init of residual projections.
STDDEV = 0.02
EMBEDDING_STDDEV = 1.0
OUTPUT_STDDEV = 0.0026726


def make_weights(seed: int, shapes: list) -> list:
    """normal(0, ``STDDEV``) weights, a normal(0, ``EMBEDDING_STDDEV``)
    embedding table (the one layer of a single leaf), normal(0,
    ``OUTPUT_STDDEV``) residual projections (leaf 4 of five) and ones for
    the norms' gains, float32, on the default device, in one jitted
    call."""
    @jax.jit
    def build(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(0xDEC0DE), words[0]), words[1])
        place, out = 0, []
        for leaves in shapes:
            made = []
            for at, sh in enumerate(leaves):
                stddev = (EMBEDDING_STDDEV if len(leaves) == 1 else
                          OUTPUT_STDDEV if (len(leaves), at) == (5, 4)
                          else STDDEV)
                made.append(
                    jnp.ones(sh, jnp.float32) if len(sh) == 1 else
                    jax.random.normal(jax.random.fold_in(key, place), sh,
                                      jnp.float32) * np.float32(stddev))
                place += 1
            out.append(tuple(made))
        return out
    return build(data.seed_array(seed))


def install(wf, weights: list) -> None:
    """Put ``weights`` in place of the program's own, one entry a forward
    unit of the workflow, each committed to the device it is on (no
    copy), as the units' own arrays and velocities are: a trainer whose
    first step takes uncommitted parameters beside committed velocities
    builds its one-step program twice."""
    if len(wf.forwards) != len(weights):
        raise BenchError(f"the program built {len(wf.forwards)} forward "
                         f"units, the configuration lists {len(weights)} "
                         "layers")
    for unit, leaves in zip(wf.forwards, weights):
        names = getattr(unit, "LEAVES", None)
        if names is None or len(names) != len(leaves):
            raise BenchError(f"{unit.name}: the program's unit holds "
                             f"{names}, the configuration {len(leaves)} "
                             "leaves")
        for attr, leaf in zip(names, leaves):
            theirs = tuple(getattr(unit, attr).shape)
            if theirs != tuple(leaf.shape):
                raise BenchError(
                    f"{unit.name}: the program's {attr} is {theirs}, the "
                    f"configuration's {tuple(leaf.shape)}")
            getattr(unit, attr).devmem = jax.device_put(
                leaf, next(iter(leaf.devices())))


# -- counts ---------------------------------------------------------------------
def kept_pairs(seq_len: int, window: int | None) -> int:
    """Query/key pairs a causal layer keeps: ``j <= i`` and, with a
    window, ``i - j < window``."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * seq_len - window * (window - 1) // 2


def attention_flops(cfg: dict, seq_len: int) -> dict:
    """Forward score-and-mix operations of one row, by layer type:
    ``4 * head_dim * heads`` a kept pair."""
    per_pair = 4 * int(cfg["head_dim"]) * int(cfg["num_attention_heads"])
    return {"sliding_attention": per_pair * kept_pairs(
                seq_len, int(cfg["sliding_window"])),
            "full_attention": per_pair * kept_pairs(seq_len, None)}


def expert_flops_per_assignment(cfg: dict) -> int:
    """Forward operations of one held token-expert pair: three products
    of ``hidden x expert width``."""
    return 2 * 3 * int(cfg["hidden_size"]) * int(
        cfg["moe_intermediate_size"])


def flops(cfg: dict, traffic: dict) -> dict:
    """Per row (one sequence): ``forward`` and ``train_step`` operations
    that are needed, ``params``, and ``matmul_train``."""
    t = _seq_len(traffic)
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    nh, nkv = (int(cfg["num_attention_heads"]),
               int(cfg["num_key_value_heads"]))
    experts, _, held = _experts(cfg)
    kinds = _kinds(cfg)
    proj = 2 * (2 * d * nh * hd + 2 * d * nkv * hd)
    scores = attention_flops(cfg, t)
    expected = int(cfg["num_experts_per_tok"]) * held / experts
    fwd = t * (len(kinds) * (proj + 2 * d * experts
                             + expected * expert_flops_per_assignment(cfg))
               + 2 * d * int(cfg["vocab_size"])) \
        + sum(scores[kind] for kind in kinds)
    params = sum(int(np.prod(sh)) for leaves in param_shapes(cfg)
                 for sh in leaves)
    return {"forward": float(fwd), "train_step": 3.0 * fwd,
            "params": params, "matmul_train": 3.0 * fwd}


def step_bytes(cfg: dict, traffic: dict, batch: int) -> float:
    """Least HBM bytes of one training step: every float32 parameter read
    forward and backward, its gradient written and read, parameter and
    velocity read and written by the update (8 passes), and each block's
    cached input written and read."""
    blocks = 2 * len(_kinds(cfg)) + 1
    return 4.0 * (8 * flops(cfg, traffic)["params"]
                  + 2 * blocks * batch * _seq_len(traffic)
                  * int(cfg["hidden_size"]))


def traced_rows(run: dict) -> tuple[float, float]:
    """(trained, evaluated) rows of a run's traced epochs, for a kernel's
    reader: the trained ones from the trace's steps; an epoch evaluates
    its deferred tail and the validation and test sets."""
    traffic, batch = run["traffic"], run["batch"]
    trained = batch * (run["trace"] or {}).get("train_steps", 0)
    n_train = int(traffic["n_train"])
    tail = n_train - ((n_train - 1) // batch) * batch
    return trained, trained / n_train * (
        tail + int(traffic["n_valid"]) + int(traffic["n_test"]))


def output_leaf(cfg: dict) -> int:
    """The head's ``w``: the last leaf of all."""
    return sum(len(leaves) for leaves in param_shapes(cfg)) - 1
