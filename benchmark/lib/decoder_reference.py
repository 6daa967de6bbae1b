"""The plain reference of a decoder language model with sliding-window and
full attention and sparse experts (``mellum2-12b-a2.5b``): forward, loss,
``jax.grad`` and the momentum update in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision.  No kernel, no sort, no grouped
product, nothing imported from the program.

The equations, from the configuration's published keys (``cfg`` is the
configuration's ``.json``):

  RMSNorm(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g
  block:  h = x + Attn(RMSNorm(x; g1));  y = h + MoE(RMSNorm(h; g2))
  Attn:   q = x Wq (T, heads, head_dim), k = x Wk, v = x Wv (T, kv, head_dim);
          rotary on q and k (half-rotation); query head h reads key/value
          head h // (heads / kv); scores q.k / sqrt(head_dim), kept where
          j <= i and, in a sliding layer, i - j < sliding_window; softmax;
          (P v) Wo.  Sliding layers: inv_freq = theta^(-2i/head_dim).  Full
          layers: YaRN (see ``inv_freq``), cos and sin times
          attention_factor.
  MoE:    r = x Wr over ALL published experts; p = softmax(r); the
          num_experts_per_tok largest; w = p_top / sum(p_top)
          (norm_topk_prob); sum over the chosen experts THAT ARE HELD of
          w_k * (silu(x Wg_e) * x Wu_e) Wd_e.
  head:   logits = RMSNorm(y; gf) W over the vocabulary held; loss = mean
          over all positions of the cross-entropy against the next token.
  update: v <- m*v - lr*(g + wd*p);  p <- p + v, every leaf.

Departures from the published model, each the configuration's and listed
in its file: one period of the layer pattern; ``num_experts`` of the
published experts held (``deployment.experts_held``), what the absent
ones would have added left out; a slice of the vocabulary; pre-norm, no
query/key norm, no balance loss, no multi-token-prediction head
(``assumed``).

Attention runs a block of queries at a time and the experts one at a
time over a mask of the tokens routed to them, each block of the model
rematerialised in the backward pass, so that the reference fits beside
nothing else at the cell's size.

``operand`` is the controls' hook: a function applied to both operands
of every matrix product but the router's, forward and backward."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import data

HIGHEST = jax.lax.Precision.HIGHEST
#: queries a block of attention, and positions a block of the head: each
#: block is rematerialised by itself in the backward pass
QUERY_BLOCK = 256
HEAD_BLOCK = 2048


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


def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


def _scores(q, k):             # (h, q, d), (h, s, d) -> (h, q, s)
    return jnp.einsum("hqd,hsd->hqs", q, k, precision=HIGHEST)


def _mix_values(p, v):         # (h, q, s), (h, s, d) -> (h, q, d)
    return jnp.einsum("hqs,hsd->hqd", p, v, precision=HIGHEST)


# -- rotary ------------------------------------------------------------------
def inv_freq(head_dim: int, rope: dict) -> np.ndarray:
    """``default``: theta^(-2i/head_dim).  ``yarn``: between the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
    original length a linear ramp from 1/pos_freq (kept) to
    1/(factor * pos_freq) (interpolated), at every sequence length."""
    i = np.arange(0, head_dim, 2, dtype=np.float64)
    pos_freq = float(rope["rope_theta"]) ** (i / head_dim)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / pos_freq
    base = float(rope["rope_theta"])
    orig = float(rope["original_max_position_embeddings"])

    def dim_of(rotations):
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    return (1.0 / (float(rope["factor"]) * pos_freq)) * ramp \
        + (1.0 / pos_freq) * (1 - ramp)


def rotary_tables(cfg, t: int) -> dict:
    """``{layer type: (cos, sin)}``, each ``(t, head_dim)`` float32 made in
    float64 on the host, times the type's ``attention_factor``.  A jitted
    step takes them as an ARGUMENT: as constants of the program they are
    4 MB each at 8,192 positions, at every use."""
    hd, tables = int(cfg["head_dim"]), {}
    for kind in set(layer_kinds(cfg)):
        rope = cfg["rope_parameters"][kind]
        ang = np.arange(t, dtype=np.float64)[:, None] * inv_freq(hd, rope)
        ang = np.concatenate([ang, ang], axis=1)
        f = float(rope.get("attention_factor", 1.0))
        tables[kind] = ((np.cos(ang) * f).astype(np.float32),
                        (np.sin(ang) * f).astype(np.float32))
    return tables


def rotary(x, cos, sin):
    """``x``: (T, heads, head_dim); ``cos``, ``sin``: (T, head_dim)."""
    hd = x.shape[-1]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


# -- layers ------------------------------------------------------------------
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def attention(cfg, leaves, x, kind: str, operand, no_window: bool, tables):
    """One sequence ``x (T, d)`` -> ``x + Attn(RMSNorm(x))``; ``tables``:
    :func:`rotary_tables` of its length."""
    g1, wq, wk, wv, wo = leaves
    t = x.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    dot = _bilinear(_dot, operand)
    xn = rms_norm(x, g1, cfg["rms_norm_eps"])
    q = rotary(dot(xn, wq).reshape(t, nh, hd), *tables[kind]) / math.sqrt(hd)
    k = rotary(dot(xn, wk).reshape(t, nkv, hd), *tables[kind])
    v = dot(xn, wv).reshape(t, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1).swapaxes(0, 1)     # (h, T, d)
    v = jnp.repeat(v, nh // nkv, axis=1).swapaxes(0, 1)
    window = (None if kind == "full_attention" or no_window
              else int(cfg["sliding_window"]))
    bq = min(QUERY_BLOCK, t)

    @jax.checkpoint
    def block(args):
        q_blk, q0 = args
        s = _bilinear(_scores, operand)(q_blk.swapaxes(0, 1), k)
        i = q0 + jnp.arange(bq)[:, None]
        j = jnp.arange(t)[None, :]
        keep = j <= i
        if window is not None:
            keep &= i - j < window
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return _bilinear(_mix_values, operand)(p, v).swapaxes(0, 1)
    out = jax.lax.map(block, (q.reshape(t // bq, bq, nh, hd),
                              jnp.arange(0, t, bq)))
    return x + dot(out.reshape(t, nh * hd), wo)


def experts(cfg, leaves, x, operand, held_renorm: bool):
    """``x (T, d)`` -> ``x + MoE(RMSNorm(x))`` for the experts held."""
    g2, wr, wg, wu, wd = leaves
    first, count = cfg["deployment"]["experts_held"]
    dot = _bilinear(_dot, operand)
    xn = rms_norm(x, g2, cfg["rms_norm_eps"])
    p = jax.nn.softmax(_dot(xn, wr), axis=-1)     # the router: always f32
    top_p, top_e = jax.lax.top_k(p, int(cfg["num_experts_per_tok"]))
    held = (top_e >= first) & (top_e < first + count)
    if held_renorm:          # the planted fault: the absent experts'
        top_p = jnp.where(held, top_p, 0.0)       # weight given to ours
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.maximum(
            jnp.sum(top_p, axis=-1, keepdims=True), 1e-30)
    @jax.checkpoint
    def expert(out, held_expert):
        e, gate, up, down = held_expert
        w_e = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)
        y = dot(jax.nn.silu(dot(xn, gate)) * dot(xn, up), down)
        return out + w_e[:, None] * y, None
    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        first + jnp.arange(count), wg, wu, wd))
    return x + out, (top_e, held)


def layer_kinds(cfg) -> list[str]:
    return list(cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def token_losses(cfg, params, ids, targets, *, tables=None, operand=None,
                 no_window=False, held_renorm=False):
    """Per-position cross-entropy ``(B, T)`` of ``ids (B, T)`` against
    ``targets (B, T)``; ``params``: ``[(table,), (g1, wq, wk, wv, wo),
    (g2, wr, wg, wu, wd), ..., (gf, w)]``; ``tables``:
    :func:`rotary_tables` of T (made here where not given)."""
    kinds = layer_kinds(cfg)
    if tables is None:
        tables = rotary_tables(cfg, ids.shape[-1])

    def one(row, target):
        h = jnp.take(params[0][0], row, axis=0)
        for n, kind in enumerate(kinds):
            h = jax.checkpoint(lambda ls, h, kind=kind: attention(
                cfg, ls, h, kind, operand, no_window, tables))(
                    params[1 + 2 * n], h)
            h = jax.checkpoint(lambda ls, h: experts(
                cfg, ls, h, operand, held_renorm)[0])(params[2 + 2 * n], h)
        @jax.checkpoint
        def head(h, target, gf, w):
            logits = _bilinear(_dot, operand)(
                rms_norm(h, gf, cfg["rms_norm_eps"]), w)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, target[:, None], axis=1)[:, 0]
        return jnp.concatenate([
            head(h[t0:t0 + HEAD_BLOCK], target[t0:t0 + HEAD_BLOCK],
                 *params[-1]) for t0 in range(0, len(row), HEAD_BLOCK)])
    return jnp.stack([one(ids[b], targets[b]) for b in range(len(ids))])


def routing(cfg, params, ids) -> dict:
    """The reference's own routing counts over ``ids (B, T)``, as the
    program's counters count them: token-expert pairs chosen, those of
    held experts, and the largest load of one held expert in one layer."""
    first, count = cfg["deployment"]["experts_held"]
    pairs = held_pairs = load_max = 0
    hs = [jnp.take(params[0][0], row, axis=0) for row in ids]
    tables = rotary_tables(cfg, len(ids[0]))
    for n, kind in enumerate(layer_kinds(cfg)):
        hs = [attention(cfg, params[1 + 2 * n], h, kind, None, False, tables)
              for h in hs]
        outs = [experts(cfg, params[2 + 2 * n], h, None, False) for h in hs]
        hs = [out for out, _ in outs]
        top = jnp.concatenate([top_e.reshape(-1) for _, (top_e, _) in outs])
        held = (top >= first) & (top < first + count)
        pairs += int(top.size)
        held_pairs += int(jnp.sum(held))
        # the load is a step's: all rows of the minibatch together
        counts = jnp.bincount(jnp.where(held, top - first, count),
                              length=count + 1)[:count]
        load_max = max(load_max, int(counts.max()))
    return {"moe_assignments": pairs, "moe_assignments_held": held_pairs,
            "moe_expert_load_max": load_max}


# -- three steps -------------------------------------------------------------
def follow(cfg, params, inputs, targets, *, seed: int = 0, epoch: int = 0,
           steps: int = 3, operand=None, half_tokens: bool = False,
           frozen: bool = False, no_window: bool = False,
           held_renorm: bool = False) -> dict:
    """Train ``steps`` minibatches (``inputs``, ``targets``: ``(steps,
    batch, T)`` ids, as the model file's ``make_rows`` made them) from
    ``params`` (donated: they are not there afterwards) with zero
    velocities.  ``seed`` and ``epoch`` key nothing: the model has no
    dropout.  Returns the losses, the per-leaf norms of the first
    gradient and of the parameters' change, and the first gradient's
    sketches, one tuple a layer in the trainer's order.

    The planted faults the check has to catch: ``half_tokens`` (the loss
    over the first half of the positions only), ``frozen`` (a step that
    returns its state unchanged), ``no_window`` (sliding layers attend to
    everything causal) and ``held_renorm`` (the chosen weights normalised
    over the held experts only)."""
    step = make_step(cfg, operand=operand, half_tokens=half_tokens,
                     frozen=frozen, no_window=no_window,
                     held_renorm=held_renorm)
    params = [tuple(ls) for ls in params]
    # the start, for the change: kept on the host, beside nothing
    p0 = [tuple(np.asarray(a) for a in ls) for ls in params]
    vels = [tuple(jnp.zeros_like(a) for a in ls) for ls in params]
    tables = jax.device_put(rotary_tables(cfg, np.shape(inputs)[-1]))
    losses, grad_norms, grad_sketches = [], None, None
    for s in range(steps):
        params, vels, loss, norms, sketches = step(
            params, vels, jnp.asarray(inputs[s]), jnp.asarray(targets[s]),
            tables)
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


def make_step(cfg, *, operand=None, half_tokens=False, frozen=False,
              no_window=False, held_renorm=False):
    """The jitted training step of :func:`follow`: ``(params, vels, x, y,
    tables) -> (params, vels, loss, first-gradient norms, sketches)``, the
    state donated; ``tables``: :func:`rotary_tables` of the rows' length."""
    hyp = cfg["assumed"]
    lr, mom, wd = (np.float32(hyp["learning_rate"]),
                   np.float32(hyp["gradient_moment"]),
                   np.float32(hyp["weights_decay"]))

    def loss_of(ps, x, y, tables):
        t = x.shape[-1]
        per_token = token_losses(cfg, ps, x, y, tables=tables,
                                 operand=operand,
                                 no_window=no_window,
                                 held_renorm=held_renorm)
        return jnp.mean(per_token[:, :t // 2] if half_tokens
                        else per_token)

    def step(params, vels, x, y, tables):
        loss, grads = jax.value_and_grad(loss_of)(params, x, y, tables)
        norms = [tuple(jnp.sqrt(jnp.sum(g * g)) for g in gs)
                 for gs in grads]
        place, sketches = 0, []
        for gs in grads:
            sketches.append(tuple(data.sketch(g, place + j)
                                  for j, g in enumerate(gs)))
            place += len(gs)
        if frozen:
            return params, vels, loss, norms, sketches
        new_v = [tuple(mom * v - lr * (g + wd * p)
                       for p, g, v in zip(ps, gs, vs))
                 for ps, gs, vs in zip(params, grads, vels)]
        new_p = [tuple(p + v for p, v in zip(ps, vs))
                 for ps, vs in zip(params, new_v)]
        return new_p, new_v, loss, norms, sketches
    return jax.jit(step, donate_argnums=(0, 1))


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
            "fault_half_tokens": {"half_tokens": True},
            "fault_frozen": {"frozen": True},
            "fault_no_window": {"no_window": True},
            "fault_held_renorm": {"held_renorm": True}}
