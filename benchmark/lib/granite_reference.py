"""The plain reference of a Mamba-2 / sparse-expert hybrid decoder
(``granite-4.0-h-small``): forward, loss, gradients by ``jax.vjp`` a block
and the momentum update in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision.  No kernel, no chunked scan, no sort, no
grouped product, nothing imported from the program.

The equations, from the configuration's published keys (``cfg`` is the
configuration's ``.json``; Hugging Face's ``GraniteMoeHybrid*`` is the
published description):

  RMSNorm(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g
  model:  h0 = embedding_multiplier * table[ids]
          layer:  u  = h + residual_multiplier * Mixer(RMSNorm(h; g1))
                  n  = RMSNorm(u; g2)
                  h' = u + residual_multiplier * (MoE(n) + Shared(n))
          logits = RMSNorm(h_L; gf) table^T / logits_scaling   (tied: ONE
          table, two uses, its two gradients summed); loss = mean over all
          positions of the cross-entropy against the next token, over the
          vocabulary rows held.
  Mixer "mamba" (H heads of P = mamba_d_head, state N = mamba_d_state, one
  group, d_in = H P):
          [z | xBC | dt] = n W_in      (widths d_in, d_in + 2N, H; no bias)
          xBC <- silu(causal depthwise conv, mamba_d_conv taps, with bias)
          x | B | C = split(xBC)       (d_in, N, N); B, C shared by the heads
          D_t = softplus(dt_t + dt_bias) a head;  a = -exp(A_log) a head
          S_t = exp(D_t a) S_{t-1} + D_t x_t (x) B_t   (S in R^{P x N}, S_0 = 0)
          y_t = S_t C_t + D x_t
          y <- RMSNorm(y * silu(z); g_m) over the channels (gate, then norm)
          out = y W_out
          The state-space layer runs as THE RECURRENCE ITSELF, a token at a
          time (``lax.scan`` over time, rematerialised by segments).
  Mixer "attention": q, k, v = n Wq, n Wk, n Wv (no bias, NO rotary:
          position_embedding_type "nope"); causal softmax of q k^T *
          attention_multiplier (1/128, not 1/sqrt(128)); query head h reads
          key/value head h // (heads / kv); out = (P v) Wo.
  MoE:    l = n Wr over ALL published experts (no bias); the
          num_experts_per_tok largest; gates = softmax over those logits;
          sum over the chosen experts THAT ARE HELD of
          gate_e * Wd_e(silu(Wg_e n) * Wu_e n), width intermediate_size.
  Shared: the same gated form over the columns held, every token, ungated.
  update: v <- m*v - lr*(g + wd*p);  p <- p + v, every leaf.

Departures from the published model, each the configuration's and listed in
its file: one period of the layer pattern; the chip's share of an 8-way
tensor- and expert-parallel deployment (``deployment``): the first
``mamba_n_heads`` Mamba heads, ``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads, ``num_local_experts`` of the
published experts, ``shared_columns_held`` columns of the shared expert,
``vocab_size`` rows of the table; what the absent shares would have added
through W_out, Wo, Wd and the shared down-projection is left out; the gated
norm's mean square runs over the channels held.

Memory: the state (4.2 GB of parameters and as much of velocities at the
cell's size) leaves room for no whole-model gradient, so a training step is
a forward pass that keeps each block's input, then a block at a time
backwards: ``jax.vjp`` of the block, the norms and sketches of its
gradients, its update in place (the leaves donated).  The start of the run
is kept on the host for the change.  Attention runs a block of queries at a
time, the experts one at a time over a mask of the tokens routed to them,
the head a block of positions at a time.

``operand`` is the controls' hook: a function applied to both operands of
every matrix product but the router's, forward and backward, and to the
operands of the recurrence's two products (``dt x``, ``B``, ``C``)."""

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


def _dot_t(a, b):              # (T, d), (V, d) -> (T, V)
    return jnp.einsum("td,vd->tv", a, b, precision=HIGHEST)


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
    return int(cfg["hidden_size"]) // int(
        cfg["published"]["num_attention_heads"])


# -- the mixers ----------------------------------------------------------------
def recurrence(dx, decay, b_in, c_in, chunk: int, boundary_fault: bool):
    """``y_t = S_t C_t`` of ``S_t = decay_t S_{t-1} + dx_t (x) B_t``, a token
    at a time: ``dx (T, H, P)``, ``decay (T, H)``, ``b_in``/``c_in (T, N)``.

    ``boundary_fault`` plants what a chunked scan gets wrong when the state
    it carries over a chunk is not decayed by that chunk: the state is
    kept in two parts, the chunk's own ``S`` (from nothing at the chunk's
    start) and what was carried in, ``R``, which decays inside the chunk
    (``E``) but comes out of it undecayed."""
    t, h, p = dx.shape
    n = b_in.shape[-1]
    seg = math.gcd(SEGMENT, t)

    def step(carry, inp):
        dx_t, decay_t, b_t, c_t = inp
        s = decay_t[:, None, None] * carry + dx_t[:, :, None] * b_t
        return s, jnp.einsum("hpn,n->hp", s, c_t, precision=HIGHEST)

    def faulty_step(carry, inp):
        s, r, e = carry
        dx_t, decay_t, b_t, c_t, at = inp
        first = at % chunk == 0
        r = jnp.where(first, r + s, r)        # carried out undecayed
        s = jnp.where(first, 0.0, s)
        e = jnp.where(first, 1.0, e) * decay_t
        s = decay_t[:, None, None] * s + dx_t[:, :, None] * b_t
        y = jnp.einsum("hpn,n->hp", s + e[:, None, None] * r, c_t,
                       precision=HIGHEST)
        return (s, r, e), y

    @jax.checkpoint
    def segment(carry, inps):
        return jax.lax.scan(faulty_step if boundary_fault else step, carry,
                            inps)
    zero = jnp.zeros((h, p, n), jnp.float32)
    inps = (dx, decay, b_in, c_in)
    carry = zero
    if boundary_fault:
        inps += (jnp.arange(t),)
        carry = (zero, zero, jnp.ones((h,), jnp.float32))
    inps = tuple(a.reshape(t // seg, seg, *a.shape[1:]) for a in inps)
    _, ys = jax.lax.scan(segment, carry, inps)
    return ys.reshape(t, h, p)


def mamba(cfg, leaves, n_x, operand, boundary_fault: bool):
    """``Mamba(n_x)`` of one sequence ``n_x (T, d)``, already normalised."""
    _, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, g_m, w_out = leaves
    t = n_x.shape[0]
    h, p, n = (int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
               int(cfg["mamba_d_state"]))
    d_in, taps = h * p, int(cfg["mamba_d_conv"])
    dot = _bilinear(_dot, operand)
    z, xbc, dt = jnp.split(dot(n_x, w_in), [d_in, 2 * d_in + 2 * n], axis=1)
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(conv_b + sum(conv_w[j] * padded[j:j + t]
                                   for j in range(taps)))
    xs, b_in, c_in = jnp.split(xbc, [d_in, d_in + n], axis=1)
    xs = xs.reshape(t, h, p)
    dt = jax.nn.softplus(dt + dt_bias)
    y = recurrence(_rounded(operand, dt[:, :, None] * xs),
                   jnp.exp(dt * -jnp.exp(a_log)),
                   _rounded(operand, b_in), _rounded(operand, c_in),
                   int(cfg["mamba_chunk_size"]), boundary_fault)
    y = (y + d_skip[:, None] * xs).reshape(t, d_in)
    return dot(rms_norm(y * jax.nn.silu(z), g_m, cfg["rms_norm_eps"]),
               w_out)


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


def attention(cfg, leaves, n_x, operand, rotary_fault: bool):
    """``Attn(n_x)`` of one sequence ``n_x (T, d)``, already normalised."""
    _, wq, wk, wv, wo = leaves
    t = n_x.shape[0]
    nh, nkv = int(cfg["num_attention_heads"]), int(
        cfg["num_key_value_heads"])
    hd = _head_dim(cfg)
    dot = _bilinear(_dot, operand)
    q = dot(n_x, wq).reshape(t, nh, hd)
    k = dot(n_x, wk).reshape(t, nkv, hd)
    v = dot(n_x, wv).reshape(t, nkv, hd)
    if rotary_fault:
        q, k = (_rotary(a, float(cfg["rope_theta"])) for a in (q, k))
    q = q * float(cfg["attention_multiplier"])
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


def route(cfg, n_x, wr):
    """``(gates, experts)``, each ``(T, num_experts_per_tok)``: the
    largest logits' experts and the softmax over those logits; float32 at
    ``highest`` always (a flipped expert is another function)."""
    top_l, top_e = jax.lax.top_k(_dot(n_x, wr),
                                 int(cfg["num_experts_per_tok"]))
    return jax.nn.softmax(top_l, axis=-1), top_e


def experts(cfg, leaves, n_x, operand, no_shared: bool):
    """``MoE(n_x) + Shared(n_x)`` of one sequence, for the experts and the
    shared columns held."""
    _, wr, wg, wu, wd, sg, su, sd = leaves
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
def make_blocks(cfg, *, operand=None, boundary_fault=False,
                no_shared=False, residual_one=False, rotary_fault=False):
    """``{kind: block}`` for ``mamba``, ``attention``, ``experts``: each the
    residual block ``h + residual_multiplier * f(RMSNorm(h; leaves[0]))``
    over a minibatch ``h (B, T, d)``."""
    scale = 1.0 if residual_one else float(cfg["residual_multiplier"])
    eps = float(cfg["rms_norm_eps"])
    inner = {
        "mamba": lambda ls, n_x: mamba(cfg, ls, n_x, operand,
                                       boundary_fault),
        "attention": lambda ls, n_x: attention(cfg, ls, n_x, operand,
                                               rotary_fault),
        "experts": lambda ls, n_x: experts(cfg, ls, n_x, operand,
                                           no_shared)}

    def block(kind):
        def run(leaves, h):
            return jax.vmap(lambda row: row + scale * inner[kind](
                leaves, rms_norm(row, leaves[0], eps)))(h)
        return run
    return {kind: block(kind) for kind in inner}


def embed(cfg, table, ids):
    return float(cfg["embedding_multiplier"]) * jnp.take(table, ids, axis=0)


def head_losses(cfg, gf, table, h, targets, operand):
    """Per-position cross-entropy ``(B, T)`` of ``h (B, T, d)``."""
    @jax.checkpoint
    def some(h, target):
        logits = _bilinear(_dot_t, operand)(
            rms_norm(h, gf, cfg["rms_norm_eps"]), table) / float(
                cfg["logits_scaling"])
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
        out += [kind, "experts"]
    return out


def token_losses(cfg, params, ids, targets, **variant):
    """Per-position cross-entropy ``(B, T)`` of ``ids (B, T)`` against
    ``targets (B, T)`` in one differentiable piece (tests at small sizes);
    ``params``: ``[(table,), <a mixer's leaves>, <an expert block's>, ...,
    (gf,)]``; the table is used twice."""
    operand = variant.pop("operand", None)
    blocks = make_blocks(cfg, operand=operand, **variant)
    h = embed(cfg, params[0][0], ids)
    for kind, leaves in zip(block_kinds(cfg), params[1:-1]):
        h = blocks[kind](leaves, h)
    return head_losses(cfg, params[-1][0], params[0][0], h, targets,
                       operand)


def routing(cfg, params, ids) -> dict:
    """The reference's own routing counts over ``ids (B, T)``, as the
    program's counters count them: token-expert pairs chosen, those of
    held experts, and the largest load of one held expert in one layer
    (a step's: all rows of the minibatch together)."""
    first, count = cfg["deployment"]["experts_held"]
    blocks = make_blocks(cfg)
    pairs = held_pairs = load_max = 0
    h = embed(cfg, params[0][0], ids)
    for kind, leaves in zip(block_kinds(cfg), params[1:-1]):
        if kind == "experts":
            top = route(cfg, rms_norm(h, leaves[0], cfg["rms_norm_eps"]
                                      ).reshape(-1, h.shape[-1]),
                        leaves[1])[1].reshape(-1)
            held = (top >= first) & (top < first + count)
            pairs += int(top.size)
            held_pairs += int(jnp.sum(held))
            counts = jnp.bincount(jnp.where(held, top - first, count),
                                  length=count + 1)[:count]
            load_max = max(load_max, int(counts.max()))
        h = blocks[kind](leaves, h)
    return {"moe_assignments": pairs, "moe_assignments_held": held_pairs,
            "moe_expert_load_max": load_max}


# -- three steps -------------------------------------------------------------
class _Steps:
    """The jitted pieces of a training step, one a kind of block (a layer
    of the same kind runs the same program): forward; backward with the
    norms and sketches of the gradients and the update in place."""

    def __init__(self, cfg, *, operand, half_tokens, frozen, untied,
                 **block_faults):
        hyp = cfg["assumed"]
        self.cfg, self.operand = cfg, operand
        self.half_tokens, self.frozen, self.untied = (half_tokens, frozen,
                                                      untied)
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

    def _head(self, leaves, vels, table, h, targets, place):
        """The loss, the head's update, and the gradients of the table
        (the head's use of it) and of ``h``."""
        def loss_of(gf, table, h):
            per_token = head_losses(self.cfg, gf, table, h, targets,
                                    self.operand)
            t = per_token.shape[1]
            return jnp.mean(per_token[:, :t // 2] if self.half_tokens
                            else per_token)
        loss, (g_gf, g_table, g_h) = jax.value_and_grad(
            loss_of, argnums=(0, 1, 2))(leaves[0], table, h)
        if self.untied:          # the planted fault: the head's use of
            g_table = jnp.zeros_like(g_table)      # the table trains nothing
        return (*self._update(leaves, vels, (g_gf,), place), loss, g_table,
                g_h)

    def _table(self, leaves, vels, ids, g_h0, g_table, place):
        """The embedding's use of the table: its rows' gradients added to
        the head's, then the table's one update."""
        _, vjp = jax.vjp(lambda table: embed(self.cfg, table, ids),
                         leaves[0])
        return self._update(leaves, vels, (vjp(g_h0)[0] + g_table,), place)


def follow(cfg, params, inputs, targets, *, seed: int = 0, epoch: int = 0,
           steps: int = 3, operand=None, half_tokens: bool = False,
           frozen: bool = False, untied: bool = False,
           boundary_fault: bool = False, no_shared: bool = False,
           residual_one: bool = False, rotary_fault: bool = False) -> dict:
    """Train ``steps`` minibatches (``inputs``, ``targets``: ``(steps,
    batch, T)`` ids, as the model file's ``make_rows`` made them) from
    ``params`` (donated: they are not there afterwards) with zero
    velocities.  ``seed`` and ``epoch`` key nothing: the model has no
    dropout.  Returns the losses, the per-leaf norms of the first
    gradient and of the parameters' change, and the first gradient's
    sketches, one tuple a layer in the trainer's order.

    The planted faults the check has to catch: ``half_tokens`` (the loss
    over the first half of the positions only), ``frozen`` (a step that
    returns its state unchanged), ``untied`` (the head's use of the table
    trains nothing), ``boundary_fault`` (the state carried over a chunk
    comes out of it undecayed), ``no_shared`` (the shared expert left
    out), ``residual_one`` (blocks added at 1.0 for residual_multiplier)
    and ``rotary_fault`` (rotary embeddings on q and k)."""
    run = _Steps(cfg, operand=operand, half_tokens=half_tokens,
                 frozen=frozen, untied=untied,
                 boundary_fault=boundary_fault, no_shared=no_shared,
                 residual_one=residual_one, rotary_fault=rotary_fault)
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
         g_table, g) = run.head(params[last], vels[last], params[0][0],
                                hs.pop(), y, jnp.uint32(places[last]))
        for k in reversed(range(len(kinds))):
            (params[k + 1], vels[k + 1], norms[k + 1], sketches[k + 1],
             g) = run.bwd[kinds[k]](params[k + 1], vels[k + 1], hs.pop(), g,
                                    jnp.uint32(places[k + 1]))
        params[0], vels[0], norms[0], sketches[0] = run.table(
            params[0], vels[0], x, g, g_table, jnp.uint32(0))
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
            "fault_boundary_decay": {"boundary_fault": True},
            "fault_no_shared": {"no_shared": True},
            "fault_residual_one": {"residual_one": True},
            "fault_untied": {"untied": True},
            "fault_rotary": {"rotary_fault": True},
            "fault_half_tokens": {"half_tokens": True},
            "fault_frozen": {"frozen": True}}
