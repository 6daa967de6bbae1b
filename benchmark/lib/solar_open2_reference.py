"""The plain reference of a Kimi-delta-attention hybrid with sparse experts
in every layer (``solar-open2-250b``): forward, loss, gradients by
``jax.vjp`` a block and the momentum update in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision.  No kernel, no
chunked form, no sort, no grouped product, nothing imported from the
program.

The equations, from the configuration's published keys (``cfg`` is the
configuration's ``.json``; Kimi Linear, arXiv:2510.26692 section 3, and the
fla library's ``KimiDeltaAttention`` are the published description of the
linear layers):

  RMSNorm(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g
  model:  h0 = table[ids]
          layer:  u  = h + Mixer(RMSNorm(h; g1))
                  h' = u + MoE(n) + Shared(n),  n = RMSNorm(u; g2)
          logits = RMSNorm(h_L; gf) W_head (untied); loss = mean over all
          positions of the cross-entropy against the next token, over the
          vocabulary rows held.
  Mixer "kda" (a layer not in gqa_layers; H heads of K = V =
  linear_attn_config.head_dim):
          q, k, v = silu(causal depthwise conv(n Wq | n Wk | n Wv)), no bias
          q <- q / |q|_2 * K^-1/2,  k <- k / |k|_2     (over a head's K)
          beta_t = 2 sigmoid(n Wb)                     (kda_allow_neg_eigval)
          g_t = -exp(A_log[h]) softplus((n Wfa) Wfb + dt_bias)   (K a head)
          S_t = S_{t-1} Diag(a_t) + beta_t (v_t - S_{t-1} Diag(a_t) k_t) k_t^T,
                a_t = exp(g_t),  S in R^{V x K},  S_0 = 0
          o_t = S_t q_t
          out = (RMSNorm(o; g_o over a head's V) * sigmoid((n Wga) Wgb + bg)) Wo
          The rule runs as THE RECURRENCE ITSELF, a token at a time
          (``lax.scan`` over time, rematerialised by segments).
  Mixer "attention" (a layer of gqa_layers): q, k, v = n Wq, n Wk, n Wv, no
          positional embedding (use_rope false); causal softmax of q k^T /
          sqrt(head_dim); query head h reads key/value head h // (heads /
          kv heads); out = ((P v) * sigmoid(n Wgate)) Wo, the gate
          elementwise (use_gqa_gate).
  MoE:    p = softmax(n Wr) over ALL published experts (no bias), the
          num_experts_per_tok largest, renormalised (norm_topk_prob), times
          routed_scaling_factor; sum over the chosen experts THAT ARE HELD of
          p_e (silu(n Wg_e) * n Wu_e) Wd_e, width moe_intermediate_size.
  Shared: the same gated form over the columns held, every token, ungated.
  update: v <- m*v - lr*(g + wd*p);  p <- p + v, every leaf.

Departures from the published model, each the configuration's and listed in
its file: one period of the layer pattern (stage 0 of 12 pipeline stages);
the chip's share of a deployment in which 40 chips share each layer
(``deployment``): the first ``linear_attn_config.num_heads`` KDA heads,
``num_attention_heads`` query heads over ``num_key_value_heads`` key/value
heads, ``n_routed_experts`` of the published experts, ``shared_columns_held``
columns of the shared expert, ``vocab_size`` rows of the table and of the
head; what the absent shares would have added through Wo, Wd and the shared
down-projection is left out.  The gates' rank and the output gate's bias,
the gate's granularity and the norms' places are the configuration's
``assumed``.

Memory: a training step is a forward pass that keeps each block's input,
then a block at a time backwards: ``jax.vjp`` of the block, the norms and
sketches of its gradients, its update in place (the leaves donated).  The
start of the run is kept on the host for the change.  Attention runs a block
of queries at a time, the experts one at a time over all tokens with their
gates, the head a block of positions at a time.

``operand`` is the controls' hook: a function applied to both operands of
every matrix product but the router's, forward and backward, and to the
recurrence's ``q``, ``k`` and ``v``."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import data

HIGHEST = jax.lax.Precision.HIGHEST
#: queries a block of attention, positions a block of the head, tokens a
#: segment of the recurrence: each is rematerialised by itself backwards
QUERY_BLOCK = 256
HEAD_BLOCK = 2048
SEGMENT = 64
#: under the square root of the L2 norms (the fla layer's)
L2_EPS = 1e-6

#: the faults a block can carry, as keywords of :func:`make_blocks`
BLOCK_FAULTS = ("head_mean_decay", "beta_one", "no_kda_gate",
                "no_attn_gate", "no_shared")


# -- products ----------------------------------------------------------------
def _bilinear(op, operand):
    """``op(a, b)`` with ``operand`` applied to a, b and, in the backward
    pass, to the incoming error as well."""
    if operand is None:
        return op

    @jax.custom_vjp
    def f(a, b):
        return op(operand(a), operand(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        _, vjp = jax.vjp(op, operand(a), operand(b))
        return vjp(operand(g))
    f.defvjp(fwd, bwd)
    return f


def _rounded(operand, x):
    """``operand(x)`` with the gradient of ``x`` (the recurrence's operands:
    its products are no single ``op`` to wrap)."""
    if operand is None:
        return x
    return x + jax.lax.stop_gradient(operand(x) - x)


def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


def _scores(q, k):             # (h, q, d), (h, s, d) -> (h, q, s)
    return jnp.einsum("hqd,hsd->hqs", q, k, precision=HIGHEST)


def _mix_values(p, v):         # (h, q, s), (h, s, d) -> (h, q, d)
    return jnp.einsum("hqs,hsd->hqd", p, v, precision=HIGHEST)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


def layer_kinds(cfg) -> list[str]:
    """``attention`` for a layer of ``gqa_layers``, ``kda`` for every other,
    of the layers held."""
    gqa = {int(i) for i in cfg["gqa_layers"]}
    first = int(cfg["deployment"]["layers_held"][0])
    return ["attention" if first + i in gqa else "kda"
            for i in range(int(cfg["num_hidden_layers"]))]


# -- the rule ------------------------------------------------------------------
def recurrence(q, k, v, decay, beta):
    """``o_t = S_t q_t`` of ``S_t = S_{t-1} Diag(decay_t) + beta_t (v_t -
    S_{t-1} Diag(decay_t) k_t) k_t^T``, a token at a time, for one
    sequence: ``q``, ``k``, ``decay (T, H, K)``, ``v (T, H, V)``, ``beta (T,
    H)`` -> ``o (T, H, V)``.  Segments of ``SEGMENT`` tokens are
    rematerialised by themselves backwards (the states a segment starts
    from are kept)."""
    t, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, inp):
        q_t, k_t, v_t, a_t, b_t = inp
        s = s * a_t[:, None, :]
        pred = jnp.einsum("hvk,hk->hv", s, k_t, precision=HIGHEST)
        s = s + (b_t[:, None] * (v_t - pred))[:, :, None] * k_t[:, None, :]
        return s, jnp.einsum("hvk,hk->hv", s, q_t, precision=HIGHEST)

    seg = SEGMENT if t % SEGMENT == 0 else t

    @jax.checkpoint
    def segment(s, inps):
        return jax.lax.scan(step, s, inps)
    _, o = jax.lax.scan(segment, jnp.zeros((h, dv, dk), jnp.float32), tuple(
        a.reshape(t // seg, seg, *a.shape[1:])
        for a in (q, k, v, decay, beta)))
    return o.reshape(t, h, dv)


# -- the mixers ----------------------------------------------------------------
def kda(cfg, leaves, n_x, operand, *, head_mean_decay=False, beta_one=False,
        no_kda_gate=False):
    """KDA of one sequence ``n_x (T, d)``, already normalised."""
    (wq, wk, wv, wb, wfa, wfb, wga, wgb, bg, conv_q, conv_k, conv_v, a_log,
     dt_bias, g_o, wo) = leaves
    t = n_x.shape[0]
    lin = cfg["linear_attn_config"]
    h, dk = int(lin["num_heads"]), int(lin["head_dim"])
    taps = int(lin["short_conv_kernel_size"])
    dot = _bilinear(_dot, operand)

    def conv(x, w):            # causal depthwise, no bias, then silu
        padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(w[j] * padded[j:j + t] for j in range(taps)))
    q = l2_norm(conv(dot(n_x, wq), conv_q).reshape(t, h, dk)) * dk ** -0.5
    k = l2_norm(conv(dot(n_x, wk), conv_k).reshape(t, h, dk))
    v = conv(dot(n_x, wv), conv_v).reshape(t, h, dk)
    beta = (1.0 if beta_one else 2.0) * jax.nn.sigmoid(dot(n_x, wb))
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        (dot(dot(n_x, wfa), wfb) + dt_bias).reshape(t, h, dk))
    if head_mean_decay:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    o = recurrence(_rounded(operand, q), _rounded(operand, k),
                   _rounded(operand, v), jnp.exp(g), beta)
    out = rms_norm(o, g_o, float(cfg["rms_norm_eps"]))
    if not no_kda_gate:
        out = out * jax.nn.sigmoid(dot(dot(n_x, wga), wgb) + bg).reshape(
            t, h, dk)
    return dot(out.reshape(t, h * dk), wo)


def attention(cfg, leaves, n_x, operand, *, no_attn_gate=False):
    """Gated NoPE attention of one sequence ``n_x (T, d)``, already
    normalised."""
    wq, wk, wv, wo, wgate = leaves
    t = n_x.shape[0]
    nh, nkv = int(cfg["num_attention_heads"]), int(
        cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    dot = _bilinear(_dot, operand)
    q = dot(n_x, wq).reshape(t, nh, hd) / math.sqrt(hd)
    k = dot(n_x, wk).reshape(t, nkv, hd)
    v = dot(n_x, wv).reshape(t, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1).swapaxes(0, 1)     # (h, T, d)
    v = jnp.repeat(v, nh // nkv, axis=1).swapaxes(0, 1)
    bq = min(QUERY_BLOCK, t)

    @jax.checkpoint
    def block(args):
        q_blk, q0 = args
        s = _bilinear(_scores, operand)(q_blk.swapaxes(0, 1), k)
        keep = jnp.arange(t)[None, :] <= q0 + jnp.arange(bq)[:, None]
        prob = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return _bilinear(_mix_values, operand)(prob, v).swapaxes(0, 1)
    out = jax.lax.map(block, (q.reshape(t // bq, bq, nh, hd),
                              jnp.arange(0, t, bq))).reshape(t, nh * hd)
    if not no_attn_gate:
        out = out * jax.nn.sigmoid(dot(n_x, wgate))
    return dot(out, wo)


def route(cfg, n_x, wr):
    """``(gates, experts)``, each ``(T, num_experts_per_tok)``: softmax over
    all the router's logits, the largest, renormalised; float32 at
    ``highest`` always (a flipped expert is another function)."""
    p = jax.nn.softmax(_dot(n_x, wr), axis=-1)
    top_p, top_e = jax.lax.top_k(p, int(cfg["num_experts_per_tok"]))
    return (float(cfg["routed_scaling_factor"]) * top_p
            / jnp.sum(top_p, axis=-1, keepdims=True)), top_e


def experts(cfg, leaves, n_x, operand, *, no_shared=False):
    """``MoE(n_x) + Shared(n_x)`` of one sequence, for the experts and the
    shared columns held."""
    wr, wg, wu, wd, sg, su, sd = leaves
    first, count = cfg["deployment"]["experts_held"]
    dot = _bilinear(_dot, operand)
    gates, top_e = route(cfg, n_x, wr)

    @jax.checkpoint
    def expert(out, held_expert):
        e, gate, up, down = held_expert
        w_e = jnp.sum(jnp.where(top_e == e, gates, 0.0), axis=-1)
        y = dot(jax.nn.silu(dot(n_x, gate)) * dot(n_x, up), down)
        return out + w_e[:, None] * y, None
    out, _ = jax.lax.scan(expert, jnp.zeros_like(n_x), (
        first + jnp.arange(count), wg, wu, wd))
    if not no_shared:
        out = out + dot(jax.nn.silu(dot(n_x, sg)) * dot(n_x, su), sd)
    return out


# -- blocks: (leaves, h (B, T, d)) -> h ------------------------------------------
def make_blocks(cfg, *, operand=None, head_mean_decay=False, beta_one=False,
                no_kda_gate=False, no_attn_gate=False, no_shared=False):
    """``{kind: block}`` for ``kda``, ``attention`` and ``moe``: each the
    residual block ``h + f(RMSNorm(h; leaves[0]))`` over a minibatch ``h
    (B, T, d)``."""
    eps = float(cfg["rms_norm_eps"])
    inner = {
        "kda": lambda ls, n_x: kda(cfg, ls, n_x, operand,
                                   head_mean_decay=head_mean_decay,
                                   beta_one=beta_one,
                                   no_kda_gate=no_kda_gate),
        "attention": lambda ls, n_x: attention(cfg, ls, n_x, operand,
                                               no_attn_gate=no_attn_gate),
        "moe": lambda ls, n_x: experts(cfg, ls, n_x, operand,
                                       no_shared=no_shared)}

    def block(kind):
        def run(leaves, h):
            return jax.vmap(lambda row: row + inner[kind](
                leaves[1:], rms_norm(row, leaves[0], eps)))(h)
        return run
    return {kind: block(kind) for kind in inner}


def embed(table, ids):
    return jnp.take(table, ids, axis=0)


def head_losses(cfg, gf, w, h, targets, operand):
    """Per-position cross-entropy ``(B, T)`` of ``h (B, T, d)``."""
    @jax.checkpoint
    def some(h, target):
        logits = _bilinear(_dot, operand)(
            rms_norm(h, gf, cfg["rms_norm_eps"]), w)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, target[:, None], axis=1)[:, 0]

    def one(h, target):
        return jnp.concatenate([
            some(h[t0:t0 + HEAD_BLOCK], target[t0:t0 + HEAD_BLOCK])
            for t0 in range(0, h.shape[0], HEAD_BLOCK)])
    return jax.vmap(one)(h, targets)


def block_kinds(cfg) -> list[str]:
    """The kind of every block between the embedding and the head."""
    out = []
    for kind in layer_kinds(cfg):
        out += [kind, "moe"]
    return out


def token_losses(cfg, params, ids, targets, **variant):
    """Per-position cross-entropy ``(B, T)`` of ``ids (B, T)`` against
    ``targets (B, T)`` in one differentiable piece (tests at small sizes);
    ``params``: ``[(table,), <a mixer's leaves>, <an expert block's>, ...,
    (gf, w)]``."""
    operand = variant.pop("operand", None)
    blocks = make_blocks(cfg, operand=operand, **variant)
    h = embed(params[0][0], ids)
    for kind, leaves in zip(block_kinds(cfg), params[1:-1]):
        h = blocks[kind](leaves, h)
    return head_losses(cfg, *params[-1], h, targets, operand)


# -- three steps -------------------------------------------------------------
class _Steps:
    """The jitted pieces of a training step, one a kind of block (a layer
    of the same kind runs the same program): forward; backward with the
    norms and sketches of the gradients and the update in place."""

    def __init__(self, cfg, *, operand, half_tokens, frozen, **block_faults):
        hyp = cfg["assumed"]
        self.cfg, self.operand = cfg, operand
        self.half_tokens, self.frozen = half_tokens, frozen
        self.lr, self.mom, self.wd = (np.float32(hyp[key]) for key in (
            "learning_rate", "gradient_moment", "weights_decay"))
        blocks = make_blocks(cfg, operand=operand, **block_faults)
        self.fwd = {kind: jax.jit(fn) for kind, fn in blocks.items()}
        self.bwd = {kind: jax.jit(functools.partial(self._back, fn),
                                  donate_argnums=(0, 1))
                    for kind, fn in blocks.items()}
        self.head = jax.jit(self._head, donate_argnums=(0, 1))
        self.table = jax.jit(self._table, donate_argnums=(0, 1))

    def _update(self, leaves, vels, grads, place):
        """-> (leaves, vels, gradient norms, gradient sketches); ``place``:
        the first leaf's place among all leaves (the sketches' key)."""
        norms = tuple(jnp.sqrt(jnp.sum(g * g)) for g in grads)
        sketches = tuple(data.sketch(g, place + j)
                         for j, g in enumerate(grads))
        if not self.frozen:
            vels = tuple(self.mom * v - self.lr * (g + self.wd * p)
                         for p, g, v in zip(leaves, grads, vels))
            leaves = tuple(p + v for p, v in zip(leaves, vels))
        return leaves, vels, norms, sketches

    def _back(self, fn, leaves, vels, h_in, g_out, place):
        _, vjp = jax.vjp(fn, leaves, h_in)
        grads, g_in = vjp(g_out)
        return (*self._update(leaves, vels, grads, place), g_in)

    def _head(self, leaves, vels, h, targets, place):
        """The loss, the head's update and the gradient of ``h``."""
        def loss_of(leaves, h):
            per_token = head_losses(self.cfg, *leaves, h, targets,
                                    self.operand)
            t = per_token.shape[1]
            return jnp.mean(per_token[:, :t // 2] if self.half_tokens
                            else per_token)
        loss, (grads, g_h) = jax.value_and_grad(loss_of, argnums=(0, 1))(
            leaves, h)
        return (*self._update(leaves, vels, grads, place), loss, g_h)

    def _table(self, leaves, vels, ids, g_h0, place):
        _, vjp = jax.vjp(lambda table: embed(table, ids), leaves[0])
        return self._update(leaves, vels, vjp(g_h0), place)


def follow(cfg, params, inputs, targets, *, seed: int = 0, epoch: int = 0,
           steps: int = 3, operand=None, half_tokens: bool = False,
           frozen: bool = False, **block_faults) -> dict:
    """Train ``steps`` minibatches (``inputs``, ``targets``: ``(steps,
    batch, T)`` ids, as the model file's ``make_rows`` made them) from
    ``params`` (donated: they are not there afterwards) with zero
    velocities.  ``seed`` and ``epoch`` key nothing: the model has no
    dropout.  Returns the losses, the per-leaf norms of the first
    gradient and of the parameters' change, and the first gradient's
    sketches, one tuple a layer in the trainer's order.

    The planted faults the check has to catch: ``half_tokens`` (the loss
    over the first half of the positions only), ``frozen`` (a step that
    returns its state unchanged) and :data:`BLOCK_FAULTS`:
    ``head_mean_decay`` (a KDA head's decay the mean of its channels', the
    gated delta rule's scalar decay in place of the per-channel one),
    ``beta_one`` (``beta = sigmoid(n Wb)``: no negative eigenvalues),
    ``no_kda_gate`` and ``no_attn_gate`` (an output gate left out) and
    ``no_shared`` (the shared expert left out)."""
    unknown = set(block_faults) - set(BLOCK_FAULTS)
    if unknown:
        raise TypeError(f"follow() has no keyword {sorted(unknown)}")
    run = _Steps(cfg, operand=operand, half_tokens=half_tokens,
                 frozen=frozen, **block_faults)
    params = [tuple(ls) for ls in params]
    kinds = block_kinds(cfg)
    if len(params) != len(kinds) + 2:
        raise ValueError(f"{len(params)} layers of parameters for "
                         f"{len(kinds)} blocks, a table and a head")
    # the start, for the change: kept on the host, beside nothing
    p0 = [tuple(np.asarray(a) for a in ls) for ls in params]
    vels = [tuple(jnp.zeros_like(a) for a in ls) for ls in params]
    places = np.cumsum([0] + [len(ls) for ls in params])
    last = len(params) - 1
    losses, grad_norms, grad_sketches = [], None, None
    for s in range(steps):
        x, y = jnp.asarray(inputs[s]), jnp.asarray(targets[s])
        hs = [embed(params[0][0], x)]
        for k, kind in enumerate(kinds):
            hs.append(run.fwd[kind](params[k + 1], hs[-1]))
        norms, sketches = [None] * len(params), [None] * len(params)
        (params[last], vels[last], norms[last], sketches[last], loss,
         g) = run.head(params[last], vels[last], hs.pop(), y,
                       jnp.uint32(places[last]))
        for k in reversed(range(len(kinds))):
            (params[k + 1], vels[k + 1], norms[k + 1], sketches[k + 1],
             g) = run.bwd[kinds[k]](params[k + 1], vels[k + 1], hs.pop(), g,
                                    jnp.uint32(places[k + 1]))
        params[0], vels[0], norms[0], sketches[0] = run.table(
            params[0], vels[0], x, g, jnp.uint32(0))
        losses.append(float(loss))
        if s == 0:
            grad_norms = [tuple(float(n) for n in ns) for ns in norms]
            grad_sketches = [tuple(np.asarray(a).tolist() for a in ss)
                             for ss in sketches]
    change = [tuple(float(jnp.sqrt(jnp.sum(jnp.square(a - jnp.asarray(a0)))))
                    for a, a0 in zip(ls, ls0))
              for ls, ls0 in zip(params, p0)]
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "grad_sketches": grad_sketches}


def fp8_operand(t):
    """The control's precision: float8 (e4m3) operands with a per-tensor
    scale, the step below the configuration's bfloat16 operands."""
    amax = jnp.maximum(jnp.max(jnp.abs(t)), np.float32(1e-30))
    scale = amax / np.float32(448.0)
    return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16_operand(t):
    """What the configuration states: one bfloat16 pass."""
    return t.astype(jnp.bfloat16).astype(jnp.float32)


#: what ``tests/limits_study.py`` reads beside the reference itself, as
#: keywords of ``follow``: the control, the stated precision, the faults
VARIANTS = {"control_fp8": {"operand": fp8_operand},
            "stated_bf16": {"operand": bf16_operand},
            "fault_head_mean_decay": {"head_mean_decay": True},
            "fault_beta_one": {"beta_one": True},
            "fault_no_kda_gate": {"no_kda_gate": True},
            "fault_no_attn_gate": {"no_attn_gate": True},
            "fault_no_shared": {"no_shared": True},
            "fault_half_tokens": {"half_tokens": True},
            "fault_frozen": {"frozen": True}}
