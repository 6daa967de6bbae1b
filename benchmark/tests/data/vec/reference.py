"""The plain reference of ``tiny-vec``: relu(x @ w + b) twice, x @ w + b,
mean cross-entropy, ``jax.grad``, the momentum update
v <- m*v - lr*(g + wd*p); p <- p + v, in float32 at ``highest``.  Nothing
of the program, and nothing of ``benchmark/lib/reference.py``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import data

HIGHEST = jax.lax.Precision.HIGHEST
LEAVES = ("", "_bias")


def mean_loss(params, x, y, rows=None):
    h = x
    for i, (w, b) in enumerate(params):
        h = jnp.dot(h, w, precision=HIGHEST) + b
        if i < len(params) - 1:
            h = jnp.maximum(h, 0.0)
    per_row = -jnp.take_along_axis(jax.nn.log_softmax(h, axis=1),
                                   y[:, None], axis=1)[:, 0]
    return jnp.mean(per_row if rows is None else per_row[:rows])


def follow(cfg, params, inputs, targets, *, seed: int = 0, epoch: int = 0,
           steps: int = 3, half_batch: bool = False,
           frozen: bool = False) -> dict:
    """Train ``steps`` minibatches (``inputs``: (steps, batch, features))
    from ``params`` with zero velocities; ``seed`` and ``epoch`` key
    nothing here (no dropout)."""
    hyp = [la["<-"] for la in cfg["layers"]]
    batch = inputs.shape[1]

    @jax.jit
    def step(params, vels, x, y):
        loss, grads = jax.value_and_grad(lambda ps: mean_loss(
            ps, x, y, batch // 2 if half_batch else None))(params)
        new_v = [tuple(h["gradient_moment" + s] * v
                       - h["learning_rate" + s] * (
                           g + h["weights_decay" + s] * p)
                       for s, p, g, v in zip(LEAVES, ps, gs, vs))
                 for h, ps, gs, vs in zip(hyp, params, grads, vels)]
        new_p = [tuple(p + v for p, v in zip(ps, vs))
                 for ps, vs in zip(params, new_v)]
        return new_p, new_v, loss, grads

    p0 = params = [tuple(p) for p in params]
    vels = [tuple(jnp.zeros_like(a) for a in ps) for ps in params]
    losses, first = [], None
    for s in range(steps):
        new_p, new_v, loss, grads = step(params, vels, inputs[s],
                                         targets[s])
        if not frozen:
            params, vels = new_p, new_v
        losses.append(float(loss))
        if s == 0:
            first = grads

    def norms(tree):
        return [tuple(float(jnp.sqrt(jnp.sum(jnp.square(a)))) for a in ls)
                for ls in tree]
    return {
        "losses": losses, "grad_norms": norms(first),
        "change_norms": norms([tuple(a - a0 for a, a0 in zip(ls, ls0))
                               for ls, ls0 in zip(params, p0)]),
        "grad_sketches": [tuple(
            np.asarray(data.sketch(a, 2 * k + j)).tolist()
            for j, a in enumerate(ls)) for k, ls in enumerate(first)]}


#: what ``tests/limits_study.py`` would read beside the reference itself
VARIANTS = {"fault_half_batch": {"half_batch": True},
            "fault_frozen": {"frozen": True}}
