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
  parameters' change after three.

The trainer's ``params`` and ``vels`` are lists, one entry a layer, of
tuples of any number of leaves (a leaf, or all of a layer's, may be
None); each leaf's learning rate and decay come from the
configuration's model file, not from the trainer."""

from __future__ import annotations

import contextlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from . import data
from .errors import BenchError

FOLLOWED = 3


def _empty(layer) -> bool:
    return layer is None or all(a is None for a in layer)


def _map_leaves(fn, *trees) -> list:
    """``fn`` over the leaves of trees that are lists, one entry a
    layer, of None or a tuple of any number of leaves (a leaf may be
    None: a layer without a bias)."""
    return [None if _empty(layers[0]) else tuple(
        None if leaves[0] is None else fn(*leaves)
        for leaves in zip(*layers)) for layers in zip(*trees)]


def _norms(tree) -> list:
    return _map_leaves(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), tree)


def _summaries(tree) -> tuple[list, list]:
    """Per leaf: its norm, and its sketch (``data.sketch``), numbered as
    the reference numbers its leaves: by their place among all leaves,
    in order."""
    place = itertools.count()
    return _norms(tree), _map_leaves(
        lambda a: data.sketch(a, next(place)), tree)


@jax.jit
def _first_grad_norms(p0, v1, hypers):
    """The program's update is momentum SGD: v1 = -lr * (g + wd * w0)
    with v0 = 0, so g = -v1 / lr - wd * w0, leaf by leaf; ``hypers``
    holds each leaf's (lr, wd)."""
    return _summaries(_map_leaves(
        lambda w, v, h: -v / h[0] - h[1] * w, p0, v1, hypers))


@jax.jit
def _change_norms(p0, p3):
    return _norms(_map_leaves(lambda w0, w3: w3 - w0, p0, p3))


def _host(tree) -> list:
    return _map_leaves(lambda a: np.asarray(a).tolist(), tree)


class TrainerProbe:
    """``hypers``: the model file's ``hypers(cfg)``, one entry a layer of
    the configuration: None, or for each leaf its ``{"learning_rate",
    "weights_decay"}``."""

    def __init__(self, hypers: list):
        self.hypers = [h for h in hypers if h is not None]
        self.calls: list[dict] = []    # every train/eval call, in order
        self.first: dict | None = None

    def _leaf_hypers(self, trainer) -> list:
        """Each leaf's (lr, wd), laid out as the trainer's ``params``:
        the configuration's parameterised layers, in order, are the
        trainer's (which may have merged layers without parameters)."""
        if getattr(trainer, "vels", None) is None:
            raise BenchError(
                "the probe reads the first gradient off the velocities of "
                "the program's momentum SGD (v1 = -lr * (g + wd * w0)); "
                f"{type(trainer).__name__} holds none")
        layers = [la for la in trainer.params if not _empty(la)]
        if ([len(la) for la in layers] != [len(h) for h in self.hypers]
                or len(trainer.vels) != len(trainer.params)):
            raise BenchError(
                "the trainer's parameterised layers hold "
                f"{[len(la) for la in layers]} leaves, the configuration's "
                f"hypers {[len(h) for h in self.hypers]}")
        mine = iter(self.hypers)
        return [None if _empty(la) else tuple(
            (h["learning_rate"], h["weights_decay"]) for h in next(mine))
            for la in trainer.params]

    # -- the first three steps --------------------------------------------
    def _followed_head(self, orig, trainer, data, target, indices, batch,
                       kw):
        indices = np.asarray(indices)
        if len(indices) < (FOLLOWED + 1) * batch:
            raise BenchError("the first training call is too short to "
                             f"follow {FOLLOWED} minibatches")
        if np.ndim(kw.get("lr_scale", 1.0)) or kw.get("ctr_base", 0):
            raise BenchError("unexpected first training call: "
                             f"{sorted(kw)}")
        hypers = self._leaf_hypers(trainer)
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
