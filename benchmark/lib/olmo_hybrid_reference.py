"""The plain reference of a gated-delta-rule / full-attention hybrid decoder
(``olmo-hybrid-7b``): forward, loss, gradients by ``jax.vjp`` a block and
the momentum update in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision.  No kernel, no chunked form, no triangular
system, nothing imported from the program.

The equations, from the configuration's published keys and its ``assumed``
(``cfg`` is the configuration's ``.json``):

  RMSNorm(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g
  model:  h0 = table[ids]
          layer:  u  = h + RMSNorm(Mixer(h); g_a)      (the norm on each
                  h' = u + RMSNorm(MLP(u); g_f)         sublayer's OUTPUT)
          MLP(x) = (silu(x Wg) * x Wu) Wd
          logits = RMSNorm(h_L; g) W_head  (untied); loss = mean over all
          positions of the cross-entropy against the next token, over the
          vocabulary rows held.
  Mixer "linear_attention" (H = linear_num_value_heads heads, keys of K =
  linear_key_head_dim, values of V = linear_value_head_dim; n = h):
          q, k, v = n Wq, n Wk, n Wv, each through silu(causal depthwise
          conv, linear_conv_kernel_dim taps, no bias)
          q <- q / sqrt(|q|^2 + 1e-6) * K^(-1/2),  k <- k / sqrt(|k|^2 + 1e-6)
          beta_t = 2 sigmoid(n Wb)     (2: linear_allow_neg_eigval)
          a_t = exp(-exp(A_log) softplus(n Wa + dt_bias))
          S_t = a_t S_{t-1} + beta_t (v_t - a_t S_{t-1} k_t) k_t^T
          o_t = S_t q_t                 (S in R^{V x K}, S_0 = 0)
          out = (RMSNorm(o_t; g_o over a head's V) * silu(n Wg)) Wo
          The delta rule runs as THE RECURRENCE ITSELF, a token at a time
          (``lax.scan`` over time, rematerialised by segments).
  Mixer "full_attention": q = RMSNorm(n Wq; g_q), k = RMSNorm(n Wk; g_k)
          over all the channels, then heads of hidden_size /
          num_attention_heads; v = n Wv; no rotary (rope_theta null);
          causal softmax of q k^T / sqrt(head size); out = (P v) Wo.
  update: v <- m*v - lr*(g + wd*p);  p <- p + v, every leaf.

Departures from the published model, each the configuration's and listed in
its file: one period of the layer pattern (stage 0 of an 8-stage pipeline)
and ``vocab_size`` rows of the table and of the head (its eighth of a
vocabulary divided over the pipeline's chips).

Memory: the state (3.7 GB of parameters and as much of velocities at the
cell's size) leaves room for no whole-model gradient, so a training step is
a forward pass that keeps each block's input, then a block at a time
backwards: ``jax.vjp`` of the block, the norms and sketches of its
gradients, its update in place (the leaves donated).  The start of the run
is kept on the host for the change.  Attention runs a block of queries at a
time, the head a block of positions at a time.

``operand`` is the controls' hook: a function applied to both operands of
every matrix product, forward and backward, and to the operands of the
recurrence's products (``q``, ``k``, ``v``)."""

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
L2_EPS = 1e-6
#: the planted rotary fault's base (the model has none: rope_theta null)
FAULT_ROPE_THETA = 10000.0

#: the faults a block can carry, as keywords of :func:`make_blocks`
BLOCK_FAULTS = ("no_decay", "beta_one", "no_l2norm", "gate_first",
                "boundary_state", "input_norm", "no_qk_norm", "rotary_fault")


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
    """``operand(x)`` with the gradient of ``x`` (the recurrence's
    operands: its products are no single ``op`` to wrap)."""
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


def layer_kinds(cfg) -> list[str]:
    return list(cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def _head_dim(cfg) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


# -- the mixers ----------------------------------------------------------------
def recurrence(q, k, v, alpha, beta, chunk: int = 0):
    """``o_t = S_t q_t`` of ``S_t = a_t S_{t-1} + beta_t (v_t - a_t S_{t-1}
    k_t) k_t^T``, a token at a time: ``q``, ``k (T, H, K)``, ``v (T, H,
    V)``, ``alpha``, ``beta (T, H)``.  ``chunk`` > 0 plants what a chunked
    form gets wrong when it does not carry the state across chunks: the
    state starts from nothing every ``chunk`` tokens."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    seg = math.gcd(SEGMENT, t)

    def step(s, inp):
        q_t, k_t, v_t, a_t, b_t, at = inp
        if chunk:
            s = jnp.where(at % chunk == 0, 0.0, s)
        s = a_t[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hvk,hk->hv", s, k_t,
                                             precision=HIGHEST))
        s = s + u[:, :, None] * k_t[:, None, :]
        return s, jnp.einsum("hvk,hk->hv", s, q_t, precision=HIGHEST)

    @jax.checkpoint
    def segment(carry, inps):
        return jax.lax.scan(step, carry, inps)
    inps = tuple(a.reshape(t // seg, seg, *a.shape[1:])
                 for a in (q, k, v, alpha, beta, jnp.arange(t)))
    _, o = jax.lax.scan(segment, jnp.zeros((h, dv, dk), jnp.float32), inps)
    return o.reshape(t, h, dv)


def linear_attention(cfg, leaves, n_x, operand, *, no_decay=False,
                     beta_one=False, no_l2norm=False, gate_first=False,
                     boundary_state=False):
    """``GatedDeltaNet(n_x)`` of one sequence ``n_x (T, d)``; ``leaves``
    without the block's norm gain."""
    (wq, wk, wv, wa, wb, wg, conv_q, conv_k, conv_v, a_log, dt_bias, g_o,
     wo) = leaves
    t = n_x.shape[0]
    h, dk, dv = (int(cfg["linear_num_value_heads"]),
                 int(cfg["linear_key_head_dim"]),
                 int(cfg["linear_value_head_dim"]))
    taps = int(cfg["linear_conv_kernel_dim"])
    dot = _bilinear(_dot, operand)

    def conv(x, w):
        padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(w[j] * padded[j:j + t] for j in range(taps)))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + L2_EPS)
    q = conv(dot(n_x, wq), conv_q).reshape(t, h, dk)
    k = conv(dot(n_x, wk), conv_k).reshape(t, h, dk)
    v = conv(dot(n_x, wv), conv_v).reshape(t, h, dv)
    if not no_l2norm:
        q, k = unit(q), unit(k)
    q = q * dk ** -0.5
    beta = jax.nn.sigmoid(dot(n_x, wb))
    if not beta_one:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(a_log) * jax.nn.softplus(dot(n_x, wa)
                                                      + dt_bias))
    if no_decay:
        alpha = jnp.ones_like(alpha)
    o = recurrence(_rounded(operand, q), _rounded(operand, k),
                   _rounded(operand, v), alpha, beta,
                   int(cfg["assumed"]["chunk"]) if boundary_state else 0)
    gate = jax.nn.silu(dot(n_x, wg)).reshape(t, h, dv)
    eps = cfg["rms_norm_eps"]
    gated = rms_norm(o * gate, g_o, eps) if gate_first \
        else rms_norm(o, g_o, eps) * gate
    return dot(gated.reshape(t, h * dv), wo)


def _rotary(x, theta: float):
    """The planted fault: half-rotation rotary embeddings on ``x (T,
    heads, head_dim)``."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv
    ang = np.concatenate([ang, ang], axis=1)
    cos, sin = (jnp.asarray(f(ang), jnp.float32)[:, None, :]
                for f in (np.cos, np.sin))
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * cos + rot * sin


def full_attention(cfg, leaves, n_x, operand, *, no_qk_norm=False,
                   rotary_fault=False):
    """``Attn(n_x)`` of one sequence ``n_x (T, d)``; ``leaves`` without the
    block's norm gain."""
    wq, wk, wv, wo, g_q, g_k = leaves
    t = n_x.shape[0]
    nh, nkv = int(cfg["num_attention_heads"]), int(
        cfg["num_key_value_heads"])
    hd = _head_dim(cfg)
    dot = _bilinear(_dot, operand)
    q, k, v = dot(n_x, wq), dot(n_x, wk), dot(n_x, wv)
    if not no_qk_norm:
        q = rms_norm(q, g_q, cfg["rms_norm_eps"])
        k = rms_norm(k, g_k, cfg["rms_norm_eps"])
    q, k, v = q.reshape(t, nh, hd), k.reshape(t, nkv, hd), v.reshape(
        t, nkv, hd)
    if rotary_fault:
        q, k = _rotary(q, FAULT_ROPE_THETA), _rotary(k, FAULT_ROPE_THETA)
    q = q / math.sqrt(hd)
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
                              jnp.arange(0, t, bq)))
    return dot(out.reshape(t, nh * hd), wo)


def mlp(leaves, n_x, operand):
    wg, wu, wd = leaves
    dot = _bilinear(_dot, operand)
    return dot(jax.nn.silu(dot(n_x, wg)) * dot(n_x, wu), wd)


# -- blocks: (leaves, h (B, T, d)) -> h ------------------------------------------
def make_blocks(cfg, *, operand=None, input_norm=False, no_qk_norm=False,
                rotary_fault=False, **linear_faults):
    """``{kind: block}`` for ``linear_attention``, ``full_attention``,
    ``mlp``: each the residual block ``h + RMSNorm(f(h); leaves[0])`` over
    a minibatch ``h (B, T, d)`` (``input_norm``, the planted fault: ``h +
    f(RMSNorm(h; leaves[0]))``, the norm on the sublayer's input)."""
    eps = float(cfg["rms_norm_eps"])
    inner = {
        "linear_attention": lambda ls, n_x: linear_attention(
            cfg, ls, n_x, operand, **linear_faults),
        "full_attention": lambda ls, n_x: full_attention(
            cfg, ls, n_x, operand, no_qk_norm=no_qk_norm,
            rotary_fault=rotary_fault),
        "mlp": lambda ls, n_x: mlp(ls, n_x, operand)}

    def block(kind):
        def one(leaves, row):
            g, *rest = leaves
            if input_norm:
                return row + inner[kind](rest, rms_norm(row, g, eps))
            return row + rms_norm(inner[kind](rest, row), g, eps)

        def run(leaves, h):
            return jax.vmap(lambda row: one(leaves, row))(h)
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
        out += [kind, "mlp"]
    return out


def token_losses(cfg, params, ids, targets, **variant):
    """Per-position cross-entropy ``(B, T)`` of ``ids (B, T)`` against
    ``targets (B, T)`` in one differentiable piece (tests at small sizes);
    ``params``: ``[(table,), <a mixer's leaves>, <a feed-forward's>, ...,
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
    returns its state unchanged) and :data:`BLOCK_FAULTS`: ``no_decay``
    (``a_t = 1``), ``beta_one`` (``beta = sigmoid``, the 2 dropped),
    ``no_l2norm`` (q and k as the convs left them), ``gate_first`` (the
    gated norm in Mamba-2's order), ``boundary_state`` (the state not
    carried across chunks), ``input_norm`` (the blocks' norms on the
    sublayers' inputs), ``no_qk_norm`` and ``rotary_fault`` (rotary
    embeddings on q and k of the full-attention layer)."""
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
            "fault_no_decay": {"no_decay": True},
            "fault_beta_one": {"beta_one": True},
            "fault_no_l2norm": {"no_l2norm": True},
            "fault_gate_first": {"gate_first": True},
            "fault_boundary_state": {"boundary_state": True},
            "fault_input_norm": {"input_norm": True},
            "fault_no_qk_norm": {"no_qk_norm": True},
            "fault_rotary": {"rotary_fault": True},
            "fault_half_tokens": {"half_tokens": True},
            "fault_frozen": {"frozen": True}}
