"""Kohonen self-organizing-map units (the non-gradient training path).

Parity target: the reference ``veles/znicz/kohonen.py`` (mount empty —
surveyed contract, SURVEY.md §2.2 [baseline Kohonen] and §3.5 call stack):
``KohonenForward`` (winner-take-all over the distance matrix),
``KohonenTrainer`` (neighborhood-decayed weight pull toward each sample —
no gradient chain), ``KohonenDecision`` (weight-change-threshold stop).

TPU-first: the whole step is matmul-shaped (``ops.kohonen``); the trainer
and forward share one weights Vector, and schedules (σ, lr exponential
decay per epoch) stay host-side between jitted steps (SURVEY.md §7 hard
part (b))."""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..accelerated_units import AcceleratedUnit
from ..loader.base import TRAIN
from ..memory import Vector
from ..mutable import Bool, DerivedBool
from ..ops import kohonen as som_ops
from ..units import Unit
from .nn_units import Forward


class KohonenForward(Forward):
    """Winner-take-all forward: output = (B,) winner indices; also exposes
    the distance matrix and a per-neuron hit histogram (KohonenHits
    parity)."""

    MAPPING = ("kohonen",)

    def __init__(self, workflow=None, name=None, shape=None, **kwargs):
        kwargs["include_bias"] = False
        kwargs.setdefault("weights_filling", "uniform")
        super().__init__(workflow, name, **kwargs)
        if shape is None:
            raise ValueError("shape=(sy, sx) is required")
        self.shape = (int(shape[0]), int(shape[1]))
        self.n_neurons = self.shape[0] * self.shape[1]
        self.distances = Vector()
        self.hits = Vector()

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device, **kwargs)
        n_features = int(np.prod(self.input.shape[1:]))
        self.create_weights((self.n_neurons, n_features), ())
        if not self.output:
            self.output.mem = np.zeros((self.input.shape[0],), np.int32)
        if not self.hits:
            self.hits.mem = np.zeros((self.n_neurons,), np.int64)
        self.init_vectors(self.weights, self.output, self.distances,
                          self.hits)

    def _x2d(self, mem):
        return mem.reshape(len(mem), -1)

    def numpy_run(self) -> None:
        win, d = som_ops.np_forward(self._x2d(self.input.mem),
                                    self.weights.mem)
        self.output.mem, self.distances.mem = win, d
        bs = self.current_batch_size
        self.hits.map_write()
        np.add.at(self.hits.mem, win[:bs], 1)

    def xla_run(self) -> None:
        if not hasattr(self, "_fwd_fn"):
            def fwd(x, w, hits, bs):
                win, d = som_ops.xla_forward(x.reshape(len(x), -1), w)
                # hits accumulate on device: a host np.add.at here would
                # force a device→host fetch EVERY minibatch — readers
                # map_read once per epoch instead
                live = (jnp.arange(win.shape[0]) < bs).astype(hits.dtype)
                return win, d, hits.at[win].add(live)

            self._fwd_fn = self.jit(fwd)
        win, d, hits = self._fwd_fn(self.input.devmem,
                                    self.weights.devmem,
                                    self.hits.devmem,
                                    self.current_batch_size)
        self.output.devmem, self.distances.devmem = win, d
        self.hits.devmem = hits


class KohonenTrainer(AcceleratedUnit):
    """Neighborhood-decayed weight pull (no gradients, SURVEY.md §3.5).

    σ and lr decay exponentially per epoch:
    ``σ(e) = max(σ₀·exp(−e/τ), σ_min)``, ``lr(e) = lr₀·exp(−e/τ)``.
    Publishes ``weights_diff`` (mean |Δw| of the last step) for
    KohonenDecision."""

    def __init__(self, workflow=None, name=None, learning_rate=0.5,
                 sigma0=None, sigma_min=0.5, decay_epochs=20.0, **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.learning_rate = learning_rate
        self.sigma0 = sigma0          # default: grid radius (set below)
        self.sigma_min = sigma_min
        self.decay_epochs = decay_epochs
        self.weights_diff = np.inf
        self.forward_unit: KohonenForward | None = None

    def setup_from_forward(self, fwd: KohonenForward) -> "KohonenTrainer":
        self.forward_unit = fwd
        self.link_attrs(fwd, "weights", "input", ("winners", "output"))
        self.grid_shape = fwd.shape
        if self.sigma0 is None:
            self.sigma0 = max(fwd.shape) / 2.0
        return self

    def _epoch(self) -> int:
        loader = getattr(self.workflow, "loader", None)
        return loader.epoch_number if loader is not None else 0

    def schedules(self) -> tuple[float, float]:
        e = self._epoch()
        decay = np.exp(-e / self.decay_epochs)
        return (self.learning_rate * decay,
                max(self.sigma0 * decay, self.sigma_min))

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device, **kwargs)
        self._coords = som_ops.grid_coords(*self.grid_shape)

    def numpy_run(self) -> None:
        lr, sigma = self.schedules()
        x = self.input.mem.reshape(len(self.input.mem), -1)
        bs = self.current_batch_size
        w, diff = som_ops.som_update(
            self.weights.mem, x[:bs], self.winners.mem[:bs],
            self._coords, lr, sigma, np)
        self.weights.mem = w.astype(np.float32)
        self.weights_diff = float(diff)

    def xla_run(self) -> None:
        import jax.numpy as jnp
        if not hasattr(self, "_step_fn"):
            coords = jnp.asarray(self._coords)

            def step(w, x, win, lr, sigma):
                x2 = x.reshape(len(x), -1)
                return som_ops.som_update(w, x2, win, coords, lr, sigma,
                                          jnp)
            self._step_fn = self.jit(step)
        lr, sigma = self.schedules()
        bs = self.current_batch_size
        # short final batches: recompute on the valid slice only (static
        # shapes per (bs) bucket; at most 2 compiled variants per run)
        w, diff = self._step_fn(self.weights.devmem,
                                self.input.devmem[:bs],
                                self.winners.devmem[:bs],
                                jnp.float32(lr), jnp.float32(sigma))
        self.weights.devmem = w
        self.weights_diff = float(diff)


class KohonenDecision(Unit):
    """Stop when the epoch-mean weight change drops under ``epsilon`` or
    after ``max_epochs`` (reference KohonenDecision contract)."""

    def __init__(self, workflow=None, name=None, max_epochs=None,
                 epsilon=1e-4, **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.max_epochs = max_epochs
        self.epsilon = epsilon
        self.complete = Bool(False)
        self.epoch_metrics: list[dict] = []
        self._diff_sum = 0.0
        self._diff_n = 0

    def link_loader(self, loader) -> None:
        self.loader = loader

    def link_trainer(self, trainer: KohonenTrainer) -> None:
        self.trainer = trainer

    def run(self) -> None:
        if self.loader.minibatch_class == TRAIN:
            # the trainer is gate-skipped on test/valid minibatches — its
            # stale weights_diff must not poison the epoch mean
            self._diff_sum += self.trainer.weights_diff
            self._diff_n += 1
        if bool(self.loader.last_minibatch):
            mean_diff = self._diff_sum / max(self._diff_n, 1)
            self.epoch_metrics.append(
                {"epoch": self.loader.epoch_number,
                 "weights_diff": mean_diff})
            self._diff_sum, self._diff_n = 0.0, 0
            done = (mean_diff < self.epsilon
                    or (self.max_epochs is not None
                        and self.loader.epoch_number + 1
                        >= self.max_epochs))
            if done:
                self.complete.set(True)
            writer = getattr(self.workflow, "metrics_writer", None)
            if writer is not None:
                writer.write(kind="epoch", **self.epoch_metrics[-1])


def make_train_only_gate(loader, decision) -> DerivedBool:
    """gate_skip predicate: run only on train minibatches, stop once
    complete (mirrors StandardWorkflow's GD gating)."""
    return DerivedBool(
        lambda: loader.minibatch_class != TRAIN
        or bool(decision.complete), ())
