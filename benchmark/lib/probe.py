"""What the benchmark reads off the trainer while ``train()`` runs.

The trainer is built inside ``train()``, so the benchmark reaches it by
wrapping ``FusedTrainer.train_epoch`` / ``eval_epoch`` for the length of
one run.  The wrapper changes no argument and no result.  It does two
things:

* it counts the rows every call feeds (``calls``), which gives the
  window's training and evaluation work and lets the check see that each
  epoch fed every training row exactly once;
* it cuts the very first training call (epoch 0's head, inside the
  warm-up) into its first three minibatches, one call each, and the
  rest, through the same ``train_epoch`` with ``ctr_base`` moved on, as
  the epoch loop itself does for its deferred tail.  Between them it
  reads the state: the losses of steps 1 to 3, the per-leaf norm of the
  first gradient (from the velocities after one step) and of the
  parameters' change after three."""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from . import data

FOLLOWED = 3


def _norms(tree) -> list:
    return [None if pair is None else tuple(
        None if a is None else jnp.sqrt(jnp.sum(jnp.square(a)))
        for a in pair) for pair in tree]


def _summaries(tree) -> tuple[list, list]:
    """Per leaf: its norm, and its sketch (``data.sketch``), numbered as
    the reference numbers its leaves."""
    sketches, leaf = [], 0
    for pair in tree:
        if pair is None:
            sketches.append(None)
            continue
        sketches.append(tuple(
            None if a is None else data.sketch(a, leaf + j)
            for j, a in enumerate(pair)))
        leaf += len(pair)
    return _norms(tree), sketches


@jax.jit
def _first_grad_norms(p0, v1, hypers):
    """v1 = -lr * (g + wd * w0) with v0 = 0, so g = -v1 / lr - wd * w0."""
    out = []
    for (w, b), (vw, vb), (hw, hb) in zip(p0, v1, hypers):
        if w is None:
            out.append(None)
            continue
        gw = -vw / hw[0] - hw[1] * w
        gb = None if b is None else -vb / hb[0] - hb[1] * b
        out.append((gw, gb))
    return _summaries(out)


@jax.jit
def _change_norms(p0, p3):
    return _norms([None if w0 is None else
                   (w3 - w0, None if b0 is None else b3 - b0)
                   for (w0, b0), (w3, b3) in zip(p0, p3)])


def _host(tree) -> list:
    return [None if pair is None else tuple(
        None if a is None else np.asarray(a).tolist() for a in pair)
        for pair in tree]


class TrainerProbe:
    def __init__(self):
        self.calls: list[dict] = []    # every train/eval call, in order
        self.first: dict | None = None

    # -- the first three steps --------------------------------------------
    def _followed_head(self, orig, trainer, data, target, indices, batch,
                       kw):
        indices = np.asarray(indices)
        if len(indices) < (FOLLOWED + 1) * batch:
            raise ValueError("the first training call is too short to "
                             f"follow {FOLLOWED} minibatches")
        if np.ndim(kw.get("lr_scale", 1.0)) or kw.get("ctr_base", 0):
            raise ValueError("unexpected first training call: "
                             f"{sorted(kw)}")
        spec = trainer.spec
        hypers = [((la.hypers[0], la.hypers[1]),
                   (la.hypers_bias[0], la.hypers_bias[1]))
                  for la in spec.layers]
        p0 = jax.tree.map(jnp.copy, trainer.params)
        v0 = _host(_norms(trainer.vels))
        parts, first = [], {"rows": indices[:FOLLOWED * batch].copy(),
                            "batch": batch, "epoch": kw.get("epoch")}
        for s in range(FOLLOWED):
            part = orig(trainer, data, target,
                        indices[s * batch:(s + 1) * batch], batch,
                        **{**kw, "ctr_base": s * batch, "sync": True})
            parts.append(part)
            if s == 0:
                first["grad_norms"], first["grad_sketches"] = map(
                    _host, _first_grad_norms(p0, trainer.vels, hypers))
        first["change_norms"] = _host(_change_norms(p0, trainer.params))
        first["velocity0_norms"] = v0
        del p0
        parts.append(orig(trainer, data, target,
                          indices[FOLLOWED * batch:], batch,
                          **{**kw, "ctr_base": FOLLOWED * batch,
                             "sync": True}))
        out = {k: np.concatenate([np.asarray(p[k]).reshape(-1)
                                  for p in parts]) for k in parts[0]}
        first["losses"] = [float(v) for v in out["loss"][:FOLLOWED]]
        self.first = first
        return out

    # -- installation -----------------------------------------------------
    @contextlib.contextmanager
    def installed(self):
        from znicz_tpu.parallel.fused import FusedTrainer
        orig_train, orig_eval = (FusedTrainer.train_epoch,
                                 FusedTrainer.eval_epoch)
        probe = self

        def train_epoch(trainer, data, target, indices, batch, **kw):
            probe.calls.append({"kind": "train", "indices": indices,
                                "epoch": kw.get("epoch"),
                                "ctr_base": kw.get("ctr_base", 0)})
            if probe.first is None:
                return probe._followed_head(orig_train, trainer, data,
                                            target, indices, batch, kw)
            return orig_train(trainer, data, target, indices, batch, **kw)

        def eval_epoch(trainer, data, target, indices, batch, **kw):
            probe.calls.append({"kind": "eval", "indices": indices})
            return orig_eval(trainer, data, target, indices, batch, **kw)

        FusedTrainer.train_epoch = train_epoch
        FusedTrainer.eval_epoch = eval_epoch
        try:
            yield self
        finally:
            FusedTrainer.train_epoch = orig_train
            FusedTrainer.eval_epoch = orig_eval

    def mark(self) -> int:
        """Position in ``calls`` (taken at the window's start)."""
        return len(self.calls)
