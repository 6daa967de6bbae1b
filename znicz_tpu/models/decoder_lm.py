"""DecoderLM: a small decoder-only language model through the zoo's
normal path: embedding, three sliding-window attention blocks and one
full one (YaRN rotary), a sparse expert block after each, RMSNorm head.

The layer kinds are the token-sequence kinds of ``nn/decoder.py``; the
sizes here are test widths (d 64, 4 query heads over 2 key/value heads of
16, window 8, 8 experts of width 32 with 2 a token, vocabulary 128), so
that ``python -m znicz_tpu znicz_tpu.models.decoder_lm --fused`` trains a
decoder through the ``Launcher`` in seconds on a CPU.  A ``"mamba"`` in
``root.decoder_lm.layer_types`` puts a Mamba-2 mixer (``mamba_block``) in
an attention block's place and a ``"linear"`` a gated-delta-rule one
(``gdn_block``); ``shared_width``, ``tied`` and the four multipliers make
the hybrid's other parts, ``mlp_width`` a dense gated feed-forward
(``mlp_block``) in every expert block's place, ``qk_norm`` and
``norm="post"`` the norms of a block that norms its sublayers' outputs
(all off by default).  Rows are drawn
from a seeded first-order Markov chain over the vocabulary, so the loss
can fall below ``log(vocab)``; the target of a position is the next
token.  ``root.decoder_lm.experts_held`` (``[first, count]``) makes every
expert block one chip's share of an expert-parallel deployment.

Run: ``python -m znicz_tpu.models.decoder_lm [--backend=…] [--epochs=N]``
"""

from __future__ import annotations

import numpy as np

from .. import prng
from ..backends import Device
from ..config import root
from ..loader.sequence import SequenceLoader
from ..standard_workflow import (StandardWorkflow,
                                 sample_snapshotter_config)

root.decoder_lm.setdefaults({
    "minibatch_size": 4, "seq_len": 32, "vocab": 128, "hidden": 64,
    "heads": 4, "kv_heads": 2, "head_dim": 16, "window": 8,
    "layer_types": ["sliding", "sliding", "sliding", "full"],
    "experts": 8, "experts_held": [0, 8], "expert_width": 32, "top_k": 2,
    "rope": {
        "sliding": {"rope_type": "default", "rope_theta": 10000.0},
        "full": {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                 "original_max_position_embeddings": 16, "beta_fast": 32,
                 "beta_slow": 1, "attention_factor": 1.1386}},
    "mamba": {"heads": 8, "heads_held": 8, "head_dim": 8, "state": 16,
              "conv": 4, "chunk": 8},
    "gdn": {"heads": 4, "key_dim": 8, "value_dim": 16, "conv": 4,
            "chunk": 8},
    "mlp_width": 0, "qk_norm": False, "norm": "pre",
    "shared_width": 0, "tied": False, "positional": "rope",
    "embedding_scale": None, "residual_scale": None, "score_scale": None,
    "logits_scale": None,
    "learning_rate": 0.05, "gradient_moment": 0.9, "weights_decay": 0.0,
    "decision": {"max_epochs": 5, "fail_iterations": 5},
    "synthetic": {"n_train": 32, "n_valid": 8, "n_test": 0,
                  "branching": 4},
})


def decoder_layers(cfg) -> list[dict]:
    """The ``StandardWorkflow`` layer list of the decoder ``cfg`` (a
    config subtree or dict with the keys of ``root.decoder_lm``)
    describes."""
    get = cfg.get if hasattr(cfg, "get") else cfg.__getitem__

    def group(key) -> dict:
        sub = get(key)
        return sub.to_dict() if hasattr(sub, "to_dict") else dict(sub)
    rope, mamba, gdn = group("rope"), group("mamba"), group("gdn")
    back = {"learning_rate": get("learning_rate"),
            "gradient_moment": get("gradient_moment"),
            "weights_decay": get("weights_decay")}
    block = {"scale": get("residual_scale")}
    normed = {**block, "norm": get("norm")}
    layers = [{"type": "embedding", "<-": back,
               "->": {"vocab": get("vocab"), "hidden": get("hidden"),
                      "scale": get("embedding_scale")}}]
    for kind in get("layer_types"):
        if kind == "mamba":
            layers.append({"type": "mamba_block", "<-": back,
                           "->": {**block, **mamba}})
        elif kind == "linear":
            layers.append({"type": "gdn_block", "<-": back,
                           "->": {**normed, **gdn}})
        else:
            layers.append({"type": "attn_block", "<-": back, "->": {
                **normed, "qk_norm": get("qk_norm"), "heads": get("heads"),
                "kv_heads": get("kv_heads"), "head_dim": get("head_dim"),
                "window": get("window") if kind == "sliding" else None,
                "positional": get("positional"),
                "score_scale": get("score_scale"),
                "rope": (None if get("positional") == "nope"
                         else dict(rope[kind]))}})
        if get("mlp_width"):
            layers.append({"type": "mlp_block", "<-": back, "->": {
                **normed, "width": get("mlp_width")}})
            continue
        layers.append({"type": "moe_block", "<-": back, "->": {
            **block, "experts": get("experts"),
            "experts_held": list(get("experts_held")),
            "expert_width": get("expert_width"), "top_k": get("top_k"),
            "shared_width": get("shared_width")}})
    head = {"vocab": get("vocab"), "scale": get("logits_scale")}
    if get("tied"):
        head["tie"] = 0
    layers.append({"type": "lm_head", "<-": back, "->": head})
    return layers


def markov_rows(gen, n: int, seq_len: int, vocab: int,
                branching: int) -> np.ndarray:
    """``(n, seq_len + 1)`` ids of a first-order chain in which every
    token has ``branching`` equally likely successors."""
    succ = gen.randint(0, vocab, (vocab, branching))
    rows = np.empty((n, seq_len + 1), np.int32)
    rows[:, 0] = gen.randint(0, vocab, n)
    picks = gen.randint(0, branching, (n, seq_len))
    for t in range(seq_len):
        rows[:, t + 1] = succ[rows[:, t], picks[:, t]]
    return rows


class MarkovLoader(SequenceLoader):
    """Rows [test | validation | train] of a seeded Markov chain."""

    def __init__(self, workflow=None, name=None, seq_len=32, vocab=128,
                 sizes=None, **kwargs):
        super().__init__(workflow, name or "markov_loader", **kwargs)
        self.seq_len, self.vocab = int(seq_len), int(vocab)
        self.sizes = dict(sizes or root.decoder_lm.synthetic.to_dict())

    def load_data(self) -> None:
        lengths = [int(self.sizes[k]) for k in
                   ("n_test", "n_valid", "n_train")]
        rows = markov_rows(prng.get("decoder_lm_rows"), sum(lengths),
                           self.seq_len, self.vocab,
                           int(self.sizes.get("branching", 4)))
        self.original_data.mem = np.ascontiguousarray(rows[:, :-1])
        self.original_labels.mem = np.ascontiguousarray(rows[:, 1:])
        self.class_lengths = lengths


class DecoderLMWorkflow(StandardWorkflow):
    def __init__(self, workflow=None, name="DecoderLMWorkflow",
                 layers=None, decision_config=None,
                 snapshotter_config=None, **kwargs):
        cfg = root.decoder_lm
        loader = MarkovLoader(minibatch_size=cfg.get("minibatch_size"),
                              seq_len=cfg.get("seq_len"),
                              vocab=cfg.get("vocab"), **kwargs)
        super().__init__(
            None, name, layers=layers or decoder_layers(cfg),
            loader=loader, loss_function="softmax",
            decision_config=decision_config or cfg.decision.to_dict(),
            snapshotter_config=sample_snapshotter_config(
                cfg, snapshotter_config))


WORKFLOW = DecoderLMWorkflow


def run(device: Device | None = None, epochs: int | None = None,
        fused: bool = False, **kwargs) -> DecoderLMWorkflow:
    wf = DecoderLMWorkflow(**kwargs)
    if epochs is not None:
        wf.decision.max_epochs = epochs
    wf.initialize(device=device or Device.create("auto"))
    wf.train(fused=fused, max_epochs=epochs)
    return wf


def main(argv: list[str] | None = None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--backend", default="auto",
                        choices=("auto", "numpy", "xla"))
    parser.add_argument("--epochs", type=int, default=None)
    parser.add_argument("--fused", action="store_true")
    args = parser.parse_args(argv)
    wf = run(device=Device.create(args.backend), epochs=args.epochs,
             fused=args.fused)
    for m in wf.decision.epoch_metrics[-3:]:
        print(m)


if __name__ == "__main__":
    main()
