"""The token-sequence layer kinds of a decoder language model as units:
``embedding``, ``attn_block``, ``mamba_block``, ``gdn_block``,
``kda_block``, ``parallel_block``, ``moe_block``, ``mlp_block`` and
``lm_head``, each with its gradient unit.

A unit here holds any number of parameter ``Vector``s, named by its
``LEAVES`` (the gradient unit holds ``velocity_<leaf>`` for each), where
the znicz kinds hold ``weights`` and ``bias``.  The math is one pure
function a kind in ``ops/attention.py`` / ``ops/ssm.py`` / ``ops/gdn.py`` /
``ops/hybrid.py`` / ``ops/moe.py``; the fused
trainer (``parallel/fused.py``) calls it directly, and the tick path
(``wf.run()``) calls the same function here and ``jax.vjp`` of it in the
gradient unit, followed by the one momentum-SGD update of ``ops/update``.
``"->"`` and ``"<-"`` of a layer are read as for the other kinds: one
learning rate, decay and moment a layer."""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from ..memory import Vector
from ..ops import (attention as attn_ops, gdn as gdn_ops,
                   hybrid as hybrid_ops, moe as moe_ops, softmax,
                   ssm as ssm_ops, update)
from .nn_units import Forward, GradientDescentBase


class SequenceForward(Forward):
    """Base of the sequence kinds' forward units."""

    #: the kind's name in ``parallel/fused.py`` and its ``ops`` function
    KIND = ""
    FWD = None
    #: the parameter Vectors, in the order of the function's ``leaves``
    LEAVES: tuple[str, ...] = ()

    def __init__(self, workflow=None, name=None, weights_stddev=0.02,
                 rms_norm_eps=1e-6, scale=None, **kwargs):
        kwargs.setdefault("weights_filling", "gaussian")
        super().__init__(workflow, name, weights_stddev=weights_stddev,
                         include_bias=False, **kwargs)
        self.rms_norm_eps = float(rms_norm_eps)
        #: a model's multiplier on what the layer hands on (a block: on
        #: what it adds to the stream); None where the model has none
        self.scale = None if scale is None else float(scale)
        #: the earlier unit whose first leaf this one shares, and its
        #: place among the workflow's forward units (``tie``)
        self.tied_unit = self.tied_to = None
        for leaf in self.LEAVES:
            setattr(self, leaf, Vector())

    #: what the snapshotter saves of this unit beside the znicz names
    @property
    def STATE_VECTORS(self) -> tuple[str, ...]:
        return self.LEAVES

    def fused_config(self) -> dict:
        """The kind's static config (hashable values)."""
        return {"eps": self.rms_norm_eps, "scale": self.scale}

    def _norm_at(self, norm: str) -> str:
        """Where a block's norm sits: on the sublayer's input ("pre") or
        on its output ("post")."""
        if norm not in ("pre", "post"):
            raise ValueError(f"{self.name}: norm {norm!r} is neither 'pre' "
                             "nor 'post'")
        return norm

    def leaf_shapes(self, in_shape: tuple) -> dict:
        raise NotImplementedError

    def out_shape(self, in_shape: tuple) -> tuple:
        return tuple(in_shape)

    def _fill_leaf(self, leaf: str, shape: tuple) -> np.ndarray:
        if len(shape) == 1:                     # a norm's gain
            return np.ones(shape, np.float32)
        return np.asarray(self._fill(shape, self.weights_filling,
                                     self.weights_stddev), np.float32)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device, **kwargs)
        in_shape = tuple(self.input.shape)
        for leaf, shape in self.leaf_shapes(in_shape).items():
            vec = getattr(self, leaf)
            if not vec:
                vec.mem = self._fill_leaf(leaf, tuple(shape))
            elif tuple(vec.shape) != tuple(shape):
                raise ValueError(f"{self.name}: leaf {leaf} is "
                                 f"{tuple(vec.shape)}, the layer's "
                                 f"{tuple(shape)}")
        if not self.output:
            # the shape the next unit reads; on the host only, until a
            # tick-path run puts the real output on the device (the fused
            # trainer never does, and a head's logits are 0.8 GB)
            self.output.mem = np.zeros(self.out_shape(in_shape), np.float32)
        self.init_vectors(*(getattr(self, leaf) for leaf in self.LEAVES))
        self.call = functools.partial(type(self).FWD,
                                      cfg=self.fused_config())
        self._fwd_fn = lambda leaves, x: self.call(leaves, x)[0]

    def leaves_dev(self) -> tuple:
        return tuple(getattr(self, leaf).devmem for leaf in self.LEAVES)

    def call_leaves(self) -> tuple:
        """What the kind's function takes: the unit's own leaves and,
        behind them, the leaf a tied unit shares."""
        return self.leaves_dev()

    def numpy_run(self) -> None:
        self.xla_run()

    def xla_run(self) -> None:
        self.output.devmem = self.jit(self._fwd_fn)(self.call_leaves(),
                                                    self.input.devmem)


class Embedding(SequenceForward):
    """ids ``(B, T)`` -> ``(B, T, d)``: rows of ``table (vocab, d)``,
    times ``scale`` where the model multiplies its embeddings."""

    MAPPING = ("embedding",)
    KIND = "embed"
    FWD = staticmethod(attn_ops.embed_fwd)
    LEAVES = ("table",)

    def __init__(self, workflow=None, name=None, vocab=None, hidden=None,
                 **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.vocab, self.hidden = int(vocab), int(hidden)

    def fused_config(self):
        # the width decides how the table's gradient is made
        # (``ops/attention.embed_route``)
        return {**super().fused_config(), "width": self.hidden}

    def leaf_shapes(self, in_shape):
        return {"table": (self.vocab, self.hidden)}

    def out_shape(self, in_shape):
        return (*in_shape, self.hidden)


def attn_mixer_config(name: str, heads, kv_heads, head_dim, window=None,
                      rope=None, positional="rope",
                      score_scale=None) -> dict:
    """The static config of ``ops/attention.attn_mixer`` from a unit's
    arguments, which ``AttentionBlock`` and ``ParallelBlock`` share."""
    heads, kv_heads = int(heads), int(kv_heads)
    if heads % kv_heads:
        raise ValueError(f"{name}: {heads} query heads over {kv_heads} "
                         "key/value heads")
    if positional not in ("rope", "nope"):
        raise ValueError(f"{name}: positional {positional!r} is neither "
                         "'rope' nor 'nope'")
    rope = dict(rope or {"rope_type": "default", "rope_theta": 10000.0})
    return {"heads": heads, "kv_heads": kv_heads, "head_dim": int(head_dim),
            "window": None if window is None else int(window),
            "score_scale": None if score_scale is None else float(score_scale),
            "rope": (None if positional == "nope"
                     else tuple(sorted(rope.items())))}


def attn_leaf_shapes(d: int, mixer: dict) -> dict:
    """``wq``, ``wk``, ``wv`` and ``wo`` of an attention mixer's config
    over a stream of width ``d``."""
    q, kv = (mixer["heads"] * mixer["head_dim"],
             mixer["kv_heads"] * mixer["head_dim"])
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}


class AttentionBlock(SequenceForward):
    """``x + scale * Attn(RMSNorm(x; g1))``: grouped-query attention,
    causal, over a sliding ``window`` or (None) everything before, for
    the ``heads`` and ``kv_heads`` held here; rotary embeddings, or none
    (``positional="nope"``); ``score_scale`` on the scores where the
    model states one (else ``1 / sqrt(head_dim)``).  ``qk_norm``: RMS
    norms on the query and key projections (leaves ``gq``, ``gk``);
    ``norm="post"``: ``g1``'s norm on the mixer's output instead,
    ``x + scale * RMSNorm(Attn(x); g1)``.  ``output_gate``: the mixer's
    heads times ``sigmoid(n Wgate)`` before ``Wo`` (leaf ``wgate``)."""

    MAPPING = ("attn_block",)
    KIND = "attn_block"
    FWD = staticmethod(attn_ops.attn_block_fwd)
    LEAVES = ("g1", "wq", "wk", "wv", "wo")
    QK_LEAVES = ("gq", "gk")
    GATE_LEAVES = ("wgate",)

    def __init__(self, workflow=None, name=None, heads=None, kv_heads=None,
                 head_dim=None, window=None, rope=None, positional="rope",
                 score_scale=None, qk_norm=False, norm="pre",
                 output_gate=False, **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.qk_norm, self.norm = bool(qk_norm), self._norm_at(norm)
        self.output_gate = bool(output_gate)
        for on, extra in ((self.qk_norm, self.QK_LEAVES),
                          (self.output_gate, self.GATE_LEAVES)):
            if on:
                self.LEAVES = self.LEAVES + extra
                for leaf in extra:
                    setattr(self, leaf, Vector())
        self.mixer = attn_mixer_config(self.name, heads, kv_heads, head_dim,
                                       window, rope, positional, score_scale)

    def fused_config(self):
        config = {**super().fused_config(), **self.mixer,
                  "qk_norm": self.qk_norm, "norm": self.norm}
        if self.output_gate:
            config["gate"] = True
        return config

    def leaf_shapes(self, in_shape):
        shapes = {"g1": (in_shape[-1],),
                  **attn_leaf_shapes(in_shape[-1], self.mixer)}
        if self.qk_norm:
            shapes.update(gq=shapes["wq"][1:], gk=shapes["wk"][1:])
        if self.output_gate:
            shapes["wgate"] = shapes["wq"]
        return shapes


class Mamba2Start:
    """The Mamba-2 starting point of a decay's leaves, which the gated
    delta rule's layer shares: ``a = -exp(a_log)`` uniform in ``[-16,
    -1]``, a step ``softplus(dt_bias)`` log-uniform in ``[0.001, 0.1]``,
    conv taps (``conv_*``: ``(taps, channels)``) uniform within ``1 /
    sqrt(taps)`` and no bias; every other leaf as the base fills it."""

    def _fill_leaf(self, leaf, shape):
        gen = self.prng
        if leaf == "a_log":
            return np.log(np.asarray(gen.uniform(1.0, 16.0, shape),
                                     np.float32))
        if leaf == "dt_bias":
            dt = np.exp(np.asarray(gen.uniform(np.log(1e-3), np.log(0.1),
                                               shape), np.float64))
            return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        if leaf == "conv_b":
            return np.zeros(shape, np.float32)
        if leaf.startswith("conv_"):
            lim = 1.0 / np.sqrt(shape[0])
            return np.asarray(gen.uniform(-lim, lim, shape), np.float32)
        return super()._fill_leaf(leaf, shape)


def ssm_mixer_config(name: str, heads, heads_held=None, head_dim=None,
                     state=None, conv=4, chunk=256, groups=1,
                     in_scales=None) -> dict:
    """The static config of ``ops/ssm.mamba_mixer`` from a unit's
    arguments, which ``MambaBlock`` and ``ParallelBlock`` share: the first
    ``heads_held`` of the model's ``heads`` heads, whose B and C come in
    ``groups`` groups (a layout :func:`~..ops.ssm.groups_held` refuses is
    refused); ``in_scales``: the model's five multipliers on ``z | x | B |
    C | dt`` of the input projection, or None."""
    heads = int(heads)
    held = heads if heads_held is None else int(heads_held)
    if not 0 < held <= heads:
        raise ValueError(f"{name}: heads_held {heads_held} of {heads} heads")
    if in_scales is not None and len(in_scales) != 5:
        raise ValueError(f"{name}: in_scales {in_scales} are not five: z, "
                         "x, B, C, dt")
    return {"heads": heads, "heads_held": held, "head_dim": int(head_dim),
            "state": int(state), "conv": int(conv), "chunk": int(chunk),
            "groups_held": ssm_ops.groups_held(heads, held, int(groups)),
            "in_scales": (None if in_scales is None
                          else tuple(float(v) for v in in_scales))}


def ssm_leaf_shapes(name: str, in_shape: tuple, mixer: dict) -> dict:
    """``ops/ssm.LEAVES``' shapes (``g1`` among them) of a Mamba mixer's
    config over an input ``(..., T, d)``; ``T`` has to be a multiple of
    the scan's ``chunk``."""
    if in_shape[-2] % mixer["chunk"]:
        raise ValueError(f"{name}: sequence length {in_shape[-2]} is no "
                         f"multiple of the scan's chunk {mixer['chunk']}")
    return ssm_ops.leaf_shapes(in_shape[-1], mixer["heads_held"],
                               mixer["head_dim"], mixer["state"],
                               mixer["conv"], mixer["groups_held"])


class MambaBlock(Mamba2Start, SequenceForward):
    """``x + scale * Mamba2(RMSNorm(x; g1))`` for the ``heads_held`` first
    of the model's ``heads`` (``ops/ssm.py``; :func:`ssm_mixer_config`): a
    chunked scan over the sequence, whose length has to be a multiple of
    ``chunk``."""

    MAPPING = ("mamba_block",)
    KIND = "mamba_block"
    FWD = staticmethod(ssm_ops.mamba_block_fwd)
    LEAVES = ssm_ops.LEAVES

    def __init__(self, workflow=None, name=None, heads=None,
                 heads_held=None, head_dim=None, state=None, conv=4,
                 chunk=256, **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.mixer = ssm_mixer_config(self.name, heads, heads_held, head_dim,
                                      state, conv, chunk)

    def fused_config(self):
        return {**super().fused_config(), **self.mixer}

    def leaf_shapes(self, in_shape):
        return ssm_leaf_shapes(self.name, in_shape, self.mixer)


class ParallelBlock(Mamba2Start, SequenceForward):
    """``x + attn_scale * Attn(n) + ssm_scale * Mamba2(n)``, ``n =
    RMSNorm(x; g1)`` (``ops/hybrid.py``): the attention mixer of
    :func:`attn_mixer_config` (causal over everything before, rotary
    tables of ``rope``) beside the Mamba-2 mixer of
    :func:`ssm_mixer_config` (``ssm_heads``, ``ssm_heads_held``,
    ``ssm_head_dim``, ``state``, ``conv``, ``chunk``, ``groups``,
    ``in_scales``).  Each mixer has its own multiplier, so the block takes
    no ``scale``."""

    MAPPING = ("parallel_block",)
    KIND = "parallel_block"
    FWD = staticmethod(hybrid_ops.parallel_block_fwd)
    LEAVES = hybrid_ops.LEAVES

    def __init__(self, workflow=None, name=None, heads=None, kv_heads=None,
                 head_dim=None, rope=None, score_scale=None, ssm_heads=None,
                 ssm_heads_held=None, ssm_head_dim=None, state=None, conv=4,
                 chunk=256, groups=1, in_scales=None, attn_scale=None,
                 ssm_scale=None, **kwargs):
        super().__init__(workflow, name, **kwargs)
        if self.scale is not None:
            raise ValueError(f"{self.name}: a parallel block scales each "
                             "mixer (attn_scale, ssm_scale), not their sum")
        self.attn = attn_mixer_config(self.name, heads, kv_heads, head_dim,
                                      rope=rope, score_scale=score_scale)
        self.ssm = ssm_mixer_config(self.name, ssm_heads, ssm_heads_held,
                                    ssm_head_dim, state, conv, chunk, groups,
                                    in_scales)
        self.attn_scale = None if attn_scale is None else float(attn_scale)
        self.ssm_scale = None if ssm_scale is None else float(ssm_scale)

    def fused_config(self):
        return {"eps": self.rms_norm_eps,
                "attn": tuple(sorted(self.attn.items())),
                "ssm": tuple(sorted(self.ssm.items())),
                "attn_scale": self.attn_scale, "ssm_scale": self.ssm_scale}

    def leaf_shapes(self, in_shape):
        ssm = ssm_leaf_shapes(self.name, in_shape, self.ssm)
        return {"g1": ssm.pop("g1"),
                **attn_leaf_shapes(in_shape[-1], self.attn), **ssm}


class GatedDeltaBlock(Mamba2Start, SequenceForward):
    """``x + scale * RMSNorm(GatedDeltaNet(x); g1)`` (``norm="post"``; or
    the norm on the mixer's input): the gated-delta-rule linear-attention
    mixer (``ops/gdn.py``), ``heads`` heads with keys of ``key_dim`` and
    values of ``value_dim``, in chunks of ``chunk`` tokens, of which the
    sequence length has to be a multiple."""

    MAPPING = ("gdn_block",)
    KIND = "gdn_block"
    FWD = staticmethod(gdn_ops.gdn_block_fwd)
    LEAVES = gdn_ops.LEAVES

    def __init__(self, workflow=None, name=None, heads=None, key_dim=None,
                 value_dim=None, conv=4, chunk=64, norm="pre", **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.heads, self.key_dim = int(heads), int(key_dim)
        self.value_dim = int(value_dim)
        self.conv, self.chunk = int(conv), int(chunk)
        self.norm = self._norm_at(norm)

    def fused_config(self):
        return {**super().fused_config(), "heads": self.heads,
                "key_dim": self.key_dim, "value_dim": self.value_dim,
                "conv": self.conv, "chunk": self.chunk, "norm": self.norm}

    def leaf_shapes(self, in_shape):
        if in_shape[-2] % self.chunk:
            raise ValueError(
                f"{self.name}: sequence length {in_shape[-2]} is no "
                f"multiple of the delta rule's chunk {self.chunk}")
        return gdn_ops.leaf_shapes(in_shape[-1], self.heads, self.key_dim,
                                   self.value_dim, self.conv)


class KDABlock(Mamba2Start, SequenceForward):
    """``x + scale * KDA(RMSNorm(x; g1))`` (or ``norm="post"``): Kimi Delta
    Attention (``ops/gdn.kda_mixer``), the delta rule with a decay a key
    channel, ``heads`` heads with keys of ``key_dim`` and values of
    ``value_dim``, the decay's and the output gate's low-rank products of
    ``rank`` columns, in chunks of ``chunk`` tokens, of which the sequence
    length has to be a multiple.  ``a_log`` a head and ``dt_bias`` a key
    channel start as Mamba-2's; the output gate's bias ``bg`` at 0."""

    MAPPING = ("kda_block",)
    KIND = "kda_block"
    FWD = staticmethod(gdn_ops.kda_block_fwd)
    LEAVES = gdn_ops.KDA_LEAVES

    def __init__(self, workflow=None, name=None, heads=None, key_dim=None,
                 value_dim=None, rank=None, conv=4, chunk=64, norm="pre",
                 **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.heads, self.key_dim = int(heads), int(key_dim)
        self.value_dim, self.rank = int(value_dim), int(rank)
        self.conv, self.chunk = int(conv), int(chunk)
        self.norm = self._norm_at(norm)

    def fused_config(self):
        return {**super().fused_config(), "heads": self.heads,
                "key_dim": self.key_dim, "value_dim": self.value_dim,
                "conv": self.conv, "chunk": self.chunk, "norm": self.norm}

    def _fill_leaf(self, leaf, shape):
        if leaf == "bg":
            return np.zeros(shape, np.float32)
        return super()._fill_leaf(leaf, shape)

    def leaf_shapes(self, in_shape):
        if in_shape[-2] % self.chunk:
            raise ValueError(
                f"{self.name}: sequence length {in_shape[-2]} is no "
                f"multiple of the delta rule's chunk {self.chunk}")
        return gdn_ops.kda_leaf_shapes(in_shape[-1], self.heads,
                                       self.key_dim, self.value_dim,
                                       self.conv, self.rank)


class MoEBlock(SequenceForward):
    """``x + scale * (MoE(n) + Shared(n))``, ``n = RMSNorm(x; g2)``, for
    the ``experts_held = [first, count]`` of the model's ``experts``:
    routed over all of them, the terms of the held ones added
    (``ops/moe.py``); ``shared_width``: the columns held of a shared
    expert every token takes (0: the model has none)."""

    MAPPING = ("moe_block",)
    KIND = "moe_block"
    FWD = staticmethod(moe_ops.moe_block_fwd)
    LEAVES = ("g2", "wr", "wg", "wu", "wd")
    SHARED_LEAVES = ("sg", "su", "sd")

    def __init__(self, workflow=None, name=None, experts=None,
                 experts_held=None, expert_width=None, top_k=None,
                 norm_topk_prob=True, shared_width=0, **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.shared_width = int(shared_width)
        if self.shared_width:
            self.LEAVES = type(self).LEAVES + self.SHARED_LEAVES
            for leaf in self.SHARED_LEAVES:
                setattr(self, leaf, Vector())
        self.experts = int(experts)
        first, count = experts_held or (0, self.experts)
        self.experts_held = (int(first), int(count))
        if not 0 <= first <= first + count <= self.experts:
            raise ValueError(f"{self.name}: experts_held {experts_held} "
                             f"of {self.experts} experts")
        self.expert_width, self.top_k = int(expert_width), int(top_k)
        self.norm_topk_prob = bool(norm_topk_prob)

    def fused_config(self):
        return {**super().fused_config(), "experts": self.experts,
                "experts_held": self.experts_held, "top_k": self.top_k,
                "norm_topk_prob": self.norm_topk_prob,
                "shared": self.shared_width}

    def leaf_shapes(self, in_shape):
        d, f, held = in_shape[-1], self.expert_width, self.experts_held[1]
        shapes = {"g2": (d,), "wr": (d, self.experts), "wg": (held, d, f),
                  "wu": (held, d, f), "wd": (held, f, d)}
        if self.shared_width:
            s = self.shared_width
            shapes.update(sg=(d, s), su=(d, s), sd=(s, d))
        return shapes


class MLPBlock(SequenceForward):
    """``x + scale * MLP(RMSNorm(x; g2))`` (or, ``norm="post"``, the norm
    on the output): the dense gated feed-forward of ``width`` columns,
    ``MLP(n) = (silu(gate_scale * n Wg) * n Wu) Wd``
    (``ops/moe.mlp_block_fwd``; ``gate_scale`` None: 1)."""

    MAPPING = ("mlp_block",)
    KIND = "mlp_block"
    FWD = staticmethod(moe_ops.mlp_block_fwd)
    LEAVES = ("g2", "wg", "wu", "wd")

    def __init__(self, workflow=None, name=None, width=None, norm="pre",
                 gate_scale=None, **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.width, self.norm = int(width), self._norm_at(norm)
        #: a model's multiplier on ``n Wg`` inside the activation
        self.gate_scale = None if gate_scale is None else float(gate_scale)

    def fused_config(self):
        config = {**super().fused_config(), "norm": self.norm}
        if self.gate_scale is not None:
            config["gate_scale"] = self.gate_scale
        return config

    def leaf_shapes(self, in_shape):
        d, f = in_shape[-1], self.width
        return {"g2": (d,), "wg": (d, f), "wu": (d, f), "wd": (f, d)}


class LMHead(SequenceForward):
    """``scale * RMSNorm(x; gf) @ w``: ``(B, T, vocab)`` logits.  On the
    tick path the unit's output is their softmax, with ``max_idx``, as
    ``All2AllSoftmax`` gives the evaluator.  Tied to an ``embedding``
    (``"tie"`` in the layer list), it holds ``gf`` alone and multiplies
    by that unit's table: one leaf with two uses, whose two gradients are
    summed before its one update."""

    MAPPING = ("lm_head",)
    KIND = "lm_head"
    FWD = staticmethod(attn_ops.lm_head_fwd)
    LEAVES = ("gf", "w")

    def __init__(self, workflow=None, name=None, vocab=None, **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.vocab = int(vocab)
        self.max_idx = Vector()

    def tie(self, embedding, index: int | None = None) -> None:
        """``index``: the embedding's place among the forward units (the
        workflow's, where the unit has one)."""
        if not isinstance(embedding, Embedding) \
                or embedding.vocab != self.vocab:
            raise ValueError(f"{self.name}: tied to {embedding.name}, "
                             f"which is no embedding of {self.vocab} rows")
        self.tied_unit = embedding
        self.tied_to = (self.workflow.forwards.index(embedding)
                        if index is None else int(index))
        self.LEAVES = ("gf",)

    def fused_config(self):
        return {**super().fused_config(), "tied_to": self.tied_to}

    def call_leaves(self):
        if self.tied_unit is None:
            return self.leaves_dev()
        return (*self.leaves_dev(), self.tied_unit.table.devmem)

    def leaf_shapes(self, in_shape):
        if self.tied_unit is not None:
            return {"gf": (in_shape[-1],)}
        return {"gf": (in_shape[-1],), "w": (in_shape[-1], self.vocab)}

    def out_shape(self, in_shape):
        return (*in_shape[:-1], self.vocab)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device, **kwargs)
        self.init_vectors(self.max_idx)

        def probs(leaves, x):
            logits = self.call(leaves, x)[0]
            y, idx = softmax.xla_softmax(logits.reshape(-1, self.vocab))
            return y.reshape(logits.shape), idx.reshape(logits.shape[:-1])
        self._probs_fn = probs

    def xla_run(self) -> None:
        y, idx = self.jit(self._probs_fn)(self.call_leaves(),
                                          self.input.devmem)
        self.output.devmem = y
        self.max_idx.devmem = idx.astype(jnp.int32)


def units_of(layers: list, workflow=None) -> list:
    """The forward units of a layer list of the sequence kinds (``type``
    and ``"->"`` as ``StandardWorkflow`` reads them), tied as the
    workflow ties them (``"tie"``: the index of an earlier layer); they
    are not initialized.  For code that wants a model's spec or static
    configs without a workflow (``fused.sequence_layer``)."""
    kinds = {cls.MAPPING[0]: cls for cls in (
        Embedding, AttentionBlock, MambaBlock, GatedDeltaBlock, KDABlock,
        ParallelBlock, MoEBlock, MLPBlock, LMHead)}
    made = []
    for la in layers:
        forward = dict(la["->"])
        tie = forward.pop("tie", None)
        unit = kinds[la["type"]](workflow, **forward)
        if tie is not None:
            unit.tie(made[tie], tie)
        made.append(unit)
    return made


class SequenceGD(GradientDescentBase):
    """Gradient unit of a sequence kind: ``jax.vjp`` of the forward
    unit's function at its input, then momentum SGD on every leaf with
    the layer's one learning rate, decay and moment."""

    MAPPING = ("embedding", "attn_block", "mamba_block", "gdn_block",
               "kda_block", "parallel_block", "moe_block", "mlp_block",
               "lm_head")

    def setup_from_forward(self, fwd):
        super().setup_from_forward(fwd)
        for leaf in fwd.LEAVES:
            setattr(self, "velocity_" + leaf, Vector())
        return self

    @property
    def STATE_VECTORS(self) -> tuple[str, ...]:
        return tuple("velocity_" + leaf
                     for leaf in self.forward_unit.LEAVES)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device, **kwargs)
        if self.accumulate_gradient or not self.apply_gradient:
            raise NotImplementedError(
                f"{self.name}: gradient accumulation schedules are not "
                "offered for the sequence kinds")
        fwd = self.forward_unit
        for leaf in fwd.LEAVES:
            vel = getattr(self, "velocity_" + leaf)
            if not vel:
                vel.mem = np.zeros(getattr(fwd, leaf).shape, np.float32)
            self.init_vectors(vel)

        def step(leaves, vels, x, err, hypers, shared_grad):
            """``leaves``: the unit's own and, behind them, a tied
            unit's; the gradient of that one is handed on
            (``shared``), and ``shared_grad``, what a later unit handed
            this one for its first leaf, is added before the update."""
            grads, err_in = attn_ops.block_vjp(fwd.call, leaves, x, err)
            own, shared = grads[:len(vels)], grads[len(vels):]
            if shared_grad is not None:
                own = (own[0] + shared_grad, *own[1:])
            new = [update.sgd_update_h(w, g, v, hypers)
                   for w, g, v in zip(leaves, own, vels)]
            return (tuple(w for w, _ in new), tuple(v for _, v in new),
                    err_in, shared)
        self._step_fn = step
        #: the gradient a unit tied to this one's forward left for its
        #: first leaf, until this unit's update takes it
        self.shared_grad = None

    def numpy_run(self) -> None:
        self.xla_run()

    def xla_run(self) -> None:
        fwd = self.forward_unit
        vels = tuple(getattr(self, "velocity_" + leaf).devmem
                     for leaf in fwd.LEAVES)
        hypers = jnp.asarray((self.learning_rate, self.weights_decay,
                              self.l1_vs_l2, self.gradient_moment),
                             jnp.float32)
        # the evaluator's error is with respect to the logits, as for
        # the softmax layer of the znicz kinds
        leaves, vels, err_in, shared = self.jit(self._step_fn)(
            fwd.call_leaves(), vels, self.input.devmem,
            self.err_output.devmem, hypers, self.shared_grad)
        self.shared_grad = None
        if shared:
            # the tied unit's gradient unit runs later in this tick
            self.workflow.gds[fwd.tied_to].shared_grad = shared[0]
        for leaf, w, v in zip(fwd.LEAVES, leaves, vels):
            getattr(fwd, leaf).devmem = w
            getattr(self, "velocity_" + leaf).devmem = v
        if self.need_err_input and err_in is not None:
            self.err_input.devmem = err_in
