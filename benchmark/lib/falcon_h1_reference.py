"""The plain reference of a parallel hybrid decoder (``falcon-h1-34b-instruct``):
forward, loss, gradients by ``jax.vjp`` a block and the momentum update in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision.
No kernel, nothing imported from the program.

The equations, from the configuration's published keys (``cfg`` is the
configuration's ``.json``; Hugging Face's ``FalconH1DecoderLayer``,
``FalconH1Mixer``, ``FalconH1RMSNormGated``, ``compute_mup_vector``,
``FalconH1MLP`` and ``FalconH1Attention`` are the published description):

  RMSNorm(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g
  model:  h0 = embedding_multiplier * table[ids]
          layer:  n  = RMSNorm(h; g1)
                  u  = h + attention_out_multiplier * Attn(attention_in_multiplier * n)
                         + ssm_out_multiplier * Mamba2(n)
                  m  = RMSNorm(u; g2)
                  h' = u + mlp_multipliers[1] * (silu(mlp_multipliers[0] * m Wg) * m Wu) Wd
          logits = lm_head_multiplier * RMSNorm(h_L; gf) W_head (untied);
          loss = mean over all positions of the cross-entropy against the
          next token, over the vocabulary rows held.
  Attn:   q = x Wq, k = key_multiplier * x Wk, v = x Wv (heads of head_dim);
          rotary tables (rope_theta, half-rotation, every channel) on q, k;
          causal softmax of q k^T / sqrt(head_dim); query head h reads
          key/value head h // (heads / kv heads); out = (P v) Wo.
  Mamba2: [z | x | B | C | dt] = (ssm_in_multiplier * n) W_in, the five
          pieces times ssm_multipliers (widths d_in, d_in, G N, G N, H)
          xBC <- silu(causal depthwise conv, mamba_d_conv taps, with bias)
          D_t = softplus(dt_t + dt_bias),  a = -exp(A_log), a head
          head j reads group j // (H / G) of B and C (state N each)
          S_t = exp(D_t a) S_{t-1} + D_t x_t (x) B_t  (S in R^{P x N}, S_0 = 0)
          y_t = S_t C_t + D x_t
          out = GroupRMSNorm(y * silu(z); g_m) W_out, the mean square over
          each group's channels (gate first: mamba_norm_before_gate false)
  update: v <- m*v - lr*(g + wd*p);  p <- p + v, every leaf.

The scan runs in a chunked form of its own (:func:`chunked_scan`: inside a
chunk the masked decays times ``C B^T`` times ``D x``, then the chunks'
states one after another), checked against the recurrence a token at a
time (:func:`recurrence`) by the repo's tests; a token at a time takes
minutes on the chip at the cell's 8,192 tokens.

Departures from the published model, each the configuration's and listed in
its file: four of the 72 layers (stage 0 of 18 pipeline stages); the chip's
share of a 4-way tensor-parallel deployment (``deployment``): the first
``num_attention_heads`` query heads over ``num_key_value_heads`` key/value
heads, the first ``mamba_n_heads`` Mamba heads reading ``mamba_n_groups``
groups, ``mlp_columns_held`` columns of the feed-forward, ``vocab_size``
rows of the table and of the head; what the absent shares would have added
through Wo, W_out and Wd is left out, and the gated norm's mean square runs
over the channels held.

Memory: a training step is a forward pass that keeps each block's input,
then a block at a time backwards: ``jax.vjp`` of the block, the norms and
sketches of its gradients, its update in place (the leaves donated).  The
start of the run is kept on the host for the change.  Attention runs a block
of queries at a time, the head a block of positions at a time.

``operand`` is the controls' hook: a function applied to both operands of
every matrix product, forward and backward, and to the operands of the
scan's products (``dt x``, ``B``, ``C``)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import data

HIGHEST = jax.lax.Precision.HIGHEST
#: queries a block of attention, positions a block of the head: each is
#: rematerialised by itself backwards
QUERY_BLOCK = 256
HEAD_BLOCK = 2048
#: the planted rotary fault's base
FAULT_ROPE_THETA = 10000.0

#: the faults a block can carry, as keywords of :func:`make_blocks`
BLOCK_FAULTS = ("sequential", "no_ssm_multipliers", "no_key_multiplier",
                "gate_multiplier_outside", "norm_before_gate",
                "no_out_multipliers", "rotary_fault", "boundary_state")


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
    """``operand(x)`` with the gradient of ``x`` (the scan's operands: its
    products are no single ``op`` to wrap)."""
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


# -- the scan ------------------------------------------------------------------
def recurrence(dx, decay, b_in, c_in):
    """``y_t = S_t C_t`` of ``S_t = decay_t S_{t-1} + dx_t (x) B_t``, a token
    at a time: ``dx (T, H, P)``, ``decay (T, H)``, ``b_in``/``c_in (T, H,
    N)`` (each head's own B and C)."""
    h, p = dx.shape[1:]
    n = b_in.shape[-1]

    def step(s, inp):
        dx_t, decay_t, b_t, c_t = inp
        s = decay_t[:, None, None] * s + dx_t[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=HIGHEST)
    _, ys = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                         (dx, decay, b_in, c_in))
    return ys


def chunked_scan(dx, log_decay, b_in, c_in, chunk: int,
                 boundary_fault: bool = False):
    """:func:`recurrence` with ``decay = exp(log_decay)``, ``chunk`` tokens
    at a time: inside a chunk ``y_t = sum_{s <= t} exp(l_t - l_s) (C_t .
    B_s) dx_s`` (``l``: the running sum of ``log_decay`` in the chunk); the
    state a chunk closes with, the states carried from chunk to chunk, and
    their term ``exp(l_t) C_t S``.  ``boundary_fault`` plants what a chunked
    form gets wrong when the state it carries over a chunk is not decayed
    by that chunk."""
    t, h, p = dx.shape
    n, nc = b_in.shape[-1], t // chunk
    dx = dx.reshape(nc, chunk, h, p)
    b_in, c_in = (a.reshape(nc, chunk, h, n) for a in (b_in, c_in))
    run = jnp.cumsum(log_decay.reshape(nc, chunk, h), axis=1)   # (c, l, h)
    keep = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]
    gap = run[:, :, None, :] - run[:, None, :, :]               # (c, l, s, h)
    decay = jnp.where(keep, jnp.exp(jnp.where(keep, gap, 0.0)), 0.0)
    cb = jnp.einsum("clhn,cshn->clsh", c_in, b_in, precision=HIGHEST)
    y = jnp.einsum("clsh,cshp->clhp", cb * decay, dx, precision=HIGHEST)
    to_end = jnp.exp(run[:, -1:, :] - run)                       # (c, s, h)
    closing = jnp.einsum("cshp,cshn->chpn", to_end[..., None] * dx, b_in,
                         precision=HIGHEST)
    whole = jnp.exp(run[:, -1, :])                               # (c, h)

    def carry(state, inp):
        g, s = inp
        return (state if boundary_fault else g[:, None, None] * state) + s, \
            state
    _, start = jax.lax.scan(carry, jnp.zeros((h, p, n), jnp.float32),
                            (whole, closing))
    y = y + jnp.einsum("clhn,chpn->clhp", c_in, start, precision=HIGHEST) \
        * jnp.exp(run)[..., None]
    return y.reshape(t, h, p)


# -- the mixers ----------------------------------------------------------------
def _mamba_sizes(cfg):
    h, p, n = (int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
               int(cfg["mamba_d_state"]))
    return h, p, n, int(cfg["mamba_n_groups"])


def in_multipliers(cfg, none: bool = False) -> np.ndarray:
    """``ssm_in_multiplier`` times the mup vector (``compute_mup_vector``)
    over the columns of ``W_in`` held; ``none``: ones."""
    h, p, n, g = _mamba_sizes(cfg)
    widths = (h * p, h * p, g * n, g * n, h)
    if none:
        return np.ones(sum(widths), np.float32)
    return float(cfg["ssm_in_multiplier"]) * np.concatenate(
        [np.full(w, m, np.float32)
         for w, m in zip(widths, cfg["ssm_multipliers"])])


def mamba(cfg, leaves, n_x, operand, *, no_ssm_multipliers=False,
          norm_before_gate=False, boundary_state=False):
    """``Mamba2(n_x)`` of one sequence ``n_x (T, d)``, already normalised."""
    w_in, conv_w, conv_b, dt_bias, a_log, d_skip, g_m, w_out = leaves
    t = n_x.shape[0]
    h, p, n, g = _mamba_sizes(cfg)
    d_in, taps = h * p, int(cfg["mamba_d_conv"])
    dot = _bilinear(_dot, operand)
    mult = in_multipliers(cfg, none=no_ssm_multipliers)
    zxbcdt = dot(n_x, w_in) * mult
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * g * n], axis=1)
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(conv_b + sum(conv_w[j] * padded[j:j + t]
                                   for j in range(taps)))
    xs, b_in, c_in = jnp.split(xbc, [d_in, d_in + g * n], axis=1)
    xs = xs.reshape(t, h, p)

    def per_head(a):           # (T, G N) -> (T, H, N): head j, group j // (H/G)
        return jnp.repeat(a.reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + dt_bias)
    y = chunked_scan(_rounded(operand, dt[:, :, None] * xs),
                     dt * -jnp.exp(a_log),
                     _rounded(operand, per_head(b_in)),
                     _rounded(operand, per_head(c_in)),
                     int(cfg["mamba_chunk_size"]), boundary_state)
    y = (y + d_skip[:, None] * xs).reshape(t, g, d_in // g)
    gate = jax.nn.silu(z).reshape(t, g, d_in // g)
    gains, eps = g_m.reshape(g, d_in // g), cfg["rms_norm_eps"]
    out = rms_norm(y, gains, eps) * gate if norm_before_gate \
        else rms_norm(y * gate, gains, eps)
    return dot(out.reshape(t, d_in), w_out)


def _rotary(x, theta: float):
    """Half-rotation rotary embeddings on ``x (T, heads, head_dim)``, the
    tables made in float64."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv
    ang = np.concatenate([ang, ang], axis=1)
    cos, sin = (jnp.asarray(f(ang), jnp.float32)[:, None, :]
                for f in (np.cos, np.sin))
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * cos + rot * sin


def attention(cfg, leaves, n_x, operand, *, no_key_multiplier=False,
              rotary_fault=False):
    """``Attn(n_x)`` of one sequence ``n_x (T, d)``, already normalised."""
    wq, wk, wv, wo = leaves
    t = n_x.shape[0]
    nh, nkv = int(cfg["num_attention_heads"]), int(
        cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    dot = _bilinear(_dot, operand)
    x = n_x * float(cfg["attention_in_multiplier"])
    q = dot(x, wq).reshape(t, nh, hd)
    k = dot(x, wk).reshape(t, nkv, hd)
    v = dot(x, wv).reshape(t, nkv, hd)
    if not no_key_multiplier:
        k = k * float(cfg["key_multiplier"])
    theta = FAULT_ROPE_THETA if rotary_fault else float(cfg["rope_theta"])
    q, k = _rotary(q, theta), _rotary(k, theta)
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


def mlp(cfg, leaves, m_x, operand, *, gate_multiplier_outside=False):
    wg, wu, wd = leaves
    gate, down = (float(v) for v in cfg["mlp_multipliers"])
    dot = _bilinear(_dot, operand)
    act = jax.nn.silu(dot(m_x, wg)) * gate if gate_multiplier_outside \
        else jax.nn.silu(gate * dot(m_x, wg))
    return down * dot(act * dot(m_x, wu), wd)


# -- blocks: (leaves, h (B, T, d)) -> h ------------------------------------------
def make_blocks(cfg, *, operand=None, sequential=False,
                no_ssm_multipliers=False, no_key_multiplier=False,
                gate_multiplier_outside=False, norm_before_gate=False,
                no_out_multipliers=False, rotary_fault=False,
                boundary_state=False):
    """``{kind: block}`` for ``parallel`` (the norm, both mixers, their sum
    into the stream) and ``mlp``, each over a minibatch ``h (B, T, d)``.
    ``sequential``, a planted fault: the Mamba mixer reads the normed
    stream with the attention's term added (one mixer after the other)."""
    eps = float(cfg["rms_norm_eps"])
    a_out, s_out = ((1.0, 1.0) if no_out_multipliers else
                    (float(cfg["attention_out_multiplier"]),
                     float(cfg["ssm_out_multiplier"])))

    def parallel(leaves, row):
        g1, wq, wk, wv, wo, *ssm = leaves
        n = rms_norm(row, g1, eps)
        a = attention(cfg, (wq, wk, wv, wo), n, operand,
                      no_key_multiplier=no_key_multiplier,
                      rotary_fault=rotary_fault)
        if sequential:
            n = rms_norm(row + a_out * a, g1, eps)
        m = mamba(cfg, ssm, n, operand,
                  no_ssm_multipliers=no_ssm_multipliers,
                  norm_before_gate=norm_before_gate,
                  boundary_state=boundary_state)
        return row + a_out * a + s_out * m

    def feed_forward(leaves, row):
        g2, *rest = leaves
        return row + mlp(cfg, rest, rms_norm(row, g2, eps), operand,
                         gate_multiplier_outside=gate_multiplier_outside)

    def batched(one):
        def run(leaves, h):
            return jax.vmap(lambda row: one(leaves, row))(h)
        return run
    return {"parallel": batched(parallel), "mlp": batched(feed_forward)}


def embed(cfg, table, ids):
    return float(cfg["embedding_multiplier"]) * jnp.take(table, ids, axis=0)


def head_losses(cfg, gf, w, h, targets, operand):
    """Per-position cross-entropy ``(B, T)`` of ``h (B, T, d)``."""
    @jax.checkpoint
    def some(h, target):
        logits = float(cfg["lm_head_multiplier"]) * _bilinear(_dot, operand)(
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
    return ["parallel", "mlp"] * int(cfg["num_hidden_layers"])


def token_losses(cfg, params, ids, targets, **variant):
    """Per-position cross-entropy ``(B, T)`` of ``ids (B, T)`` against
    ``targets (B, T)`` in one differentiable piece (tests at small sizes);
    ``params``: ``[(table,), <a parallel block's leaves>, <a
    feed-forward's>, ..., (gf, w)]``."""
    operand = variant.pop("operand", None)
    blocks = make_blocks(cfg, operand=operand, **variant)
    h = embed(cfg, params[0][0], ids)
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
        _, vjp = jax.vjp(lambda table: embed(self.cfg, table, ids),
                         leaves[0])
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
    returns its state unchanged) and :data:`BLOCK_FAULTS`: ``sequential``
    (the Mamba mixer reads ``h + A``), ``no_ssm_multipliers`` (the input
    and the five piece multipliers of the Mamba mixer dropped),
    ``no_key_multiplier``, ``gate_multiplier_outside`` (``gate *
    silu(m Wg)``), ``norm_before_gate`` (the gated norm in the other
    order), ``no_out_multipliers`` (both mixers summed at 1),
    ``rotary_fault`` (rotary tables at theta 1e4) and ``boundary_state``
    (the state carried over a chunk not decayed by it)."""
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
        hs = [embed(cfg, params[0][0], x)]
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
            "fault_sequential": {"sequential": True},
            "fault_no_ssm_multipliers": {"no_ssm_multipliers": True},
            "fault_no_key_multiplier": {"no_key_multiplier": True},
            "fault_gate_multiplier_outside": {"gate_multiplier_outside": True},
            "fault_norm_before_gate": {"norm_before_gate": True},
            "fault_no_out_multipliers": {"no_out_multipliers": True},
            "fault_rotary": {"rotary_fault": True},
            "fault_boundary_state": {"boundary_state": True},
            "fault_half_tokens": {"half_tokens": True},
            "fault_frozen": {"frozen": True}}
