"""Streaming fused trainer: disk-backed datasets at fused-path speed.

Counterpart of :class:`parallel.fused.FusedTrainer` for datasets that do
NOT fit in HBM (SURVEY.md §2.2 "Znicz loaders" row — the reference's
on-the-fly/LMDB pipelines).  The resident trainer scans a whole epoch on
device; here the epoch is a host loop over a jitted per-minibatch step,
with :class:`loader.streaming.BatchPrefetcher` double-buffering the
host read/decode + host→HBM transfer under the previous step's compute
(JAX async dispatch keeps the device queue full as long as the host
keeps up).

RNG/math contract: identical to the resident path — the same
``train_minibatch`` body, the same (epoch, samples-consumed) counters —
so a dataset that *does* fit in HBM trains bit-for-bit identically
through either trainer (asserted in tests/test_streaming.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..loader.streaming import BatchPrefetcher, StreamingLoader
from ..telemetry import compilestats
from .fused import FusedTrainer, eval_minibatch, train_minibatch


class StreamTrainer(FusedTrainer):
    """FusedTrainer drop-in whose epoch drivers stream minibatches from
    a :class:`StreamingLoader` instead of indexing a resident tensor.

    ``train_epoch(data, target, ...)`` keeps the resident signature so
    ``StandardWorkflow.run_fused`` treats both trainers uniformly;
    ``data``/``target`` are ignored (pass ``None``)."""

    def __init__(self, workflow=None, spec=None, params=None, vels=None,
                 mesh=None, loader: StreamingLoader | None = None,
                 prefetch_depth: int = 2, mse_target: str = "input",
                 accum_steps: int = 1, augment=None,
                 step_callback=None, device_augment: bool = False):
        if augment is not None:
            # streaming augmentation lives on the LOADER (host-side in
            # the prefetch stage) — a trainer-level augment here would
            # double-apply
            raise ValueError("StreamTrainer: set augment on the "
                             "StreamingLoader, not the trainer")
        super().__init__(workflow, spec=spec, params=params, vels=vels,
                         mesh=mesh, accum_steps=accum_steps)
        self.loader = loader if loader is not None \
            else getattr(workflow, "loader", None)
        if not isinstance(self.loader, StreamingLoader):
            raise TypeError("StreamTrainer needs a StreamingLoader")
        self.prefetch_depth = prefetch_depth
        #: for MSE heads: "input" reconstructs x (the autoencoder
        #: default — streaming loaders serve no separate target tensor);
        #: "labels" regresses on the record's label block (arbitrary
        #: label_shape/dtype in .znr shards, e.g. denoising targets)
        if mse_target not in ("input", "labels"):
            raise ValueError(f"mse_target {mse_target!r}")
        self.mse_target = mse_target
        #: x doubles as the target: skip the label decode+transfer too
        self._x_is_target = (self.spec.loss == "mse"
                             and mse_target == "input")
        #: optional ``callback(epoch, step_index)`` invoked after every
        #: streamed micro-step (between accumulation micro-steps too) —
        #: progress reporting, watchdogs, and the failure-parity tests'
        #: mid-group kill point
        self.step_callback = step_callback
        #: move the loader's augmentation policy onto the DEVICE: the
        #: prefetcher ships raw decode-size rows and the jitted step
        #: applies ``policy.device_apply`` (bit-identical pixels to the
        #: host application — same counter-RNG — but the crop runs on
        #: the idle VPU instead of the loader-bound host CPU, which the
        #: --loader bench measured as the augmented pipeline's
        #: bottleneck)
        self.device_augment = bool(device_augment)
        if self.device_augment and getattr(self.loader, "augment",
                                           None) is None:
            raise ValueError("device_augment=True needs an augment "
                             "policy on the StreamingLoader")
        self._step_fn = None
        self._eval_fn = None

    # -- per-minibatch compiled steps -------------------------------------
    def _build_steps(self):
        spec = self.spec
        x_is_target = self._x_is_target
        aug = self.loader.augment if self.device_augment else None

        @jax.named_scope("input")
        def placed(x, rows, epoch, train):
            """The streamed minibatch laid over the mesh's ``data``
            axis, through the on-device augmentation."""
            if self._batch_sharding is not None:
                x = jax.lax.with_sharding_constraint(
                    x, self._batch_sharding)
            if aug is not None:
                x = aug.device_apply(x, rows, epoch, train=train)
            return x

        def step(params, vels, x, t, mask, epoch, ctr, lr_scale,
                 lr_scale_bias, rows):
            x = placed(x, rows, epoch, True)
            return train_minibatch(spec, params, vels, x,
                                   x if x_is_target else t, mask,
                                   epoch=epoch, ctr=ctr,
                                   lr_scale=lr_scale,
                                   lr_scale_bias=lr_scale_bias)

        def estep(params, x, t, mask, rows):
            x = placed(x, rows, 0, False)
            return eval_minibatch(spec, params, x,
                                  x if x_is_target else t, mask)

        # mesh runs pin out_shardings exactly like FusedTrainer._build:
        # params/vels (and accumulated grads) keep their TP layout
        # across steps, metrics come back replicated; meshless passes
        # nothing and stays the identical single-device jit
        jit_kw: dict = {}
        ejit_kw: dict = {}
        psh = None
        if self._batch_sharding is not None:
            psh = [tuple(s) for s in self._param_shardings]
            jit_kw["out_shardings"] = (psh, psh, self._repl)
            ejit_kw["out_shardings"] = self._repl
        # compile accounting: same contract as FusedTrainer._build —
        # every streamed step call that builds an executable is
        # recorded, under its own site so resident and streaming runs
        # are separable in compile_time_ms
        self._step_fn = compilestats.build_timed(
            jax.jit(self._mesh_scoped(step), donate_argnums=(0, 1),
                    **jit_kw),
            site="train.stream", cause="cold")
        self._eval_fn = compilestats.build_timed(
            jax.jit(self._mesh_scoped(estep), **ejit_kw),
            site="train.stream", cause="cold")
        if self.accum_steps > 1:
            # gradient accumulation over the streamed step loop: grads
            # per micro-batch, one update per group — the host-loop
            # mirror of FusedTrainer's in-scan grouping (same flush-at-
            # call-end contract)
            from .fused import apply_updates, grad_minibatch

            def gstep(params, x, t, mask, epoch, ctr, rows):
                x = placed(x, rows, epoch, True)
                return grad_minibatch(spec, params, x,
                                      x if x_is_target else t, mask,
                                      epoch=epoch, ctr=ctr)

            @jax.named_scope("accum")
            def gapply(params, vels, acc, lr_scale, lr_scale_bias):
                return apply_updates(spec, params, vels, acc, lr_scale,
                                     lr_scale_bias)

            @jax.named_scope("accum")
            def gadd(acc, grads):
                return jax.tree_util.tree_map(jnp.add, acc, grads)

            gkw: dict = {}
            akw: dict = {}
            ckw: dict = {}
            if psh is not None:
                # grads shard like their params (tied-deconv rows were
                # remapped onto the shared encoder's sharding already)
                # — but gradient-LESS rows are a bare None, not a
                # (None, None) tuple, so the sharding tree must carry
                # None there too (pytree prefix structures must match)
                from .fused import _grad_slot
                gsh = [None if _grad_slot(la, self.params, i) is None
                       else psh[i]
                       for i, la in enumerate(spec.layers)]
                gkw["out_shardings"] = (gsh, self._repl)
                akw["out_shardings"] = (psh, psh)
                ckw["out_shardings"] = gsh
            self._grad_fn = jax.jit(self._mesh_scoped(gstep), **gkw)
            # donate only the velocity/accumulator buffers: params are
            # read by every layer's decay term before their new value
            # exists, so XLA can't reuse them and warns
            self._apply_fn = jax.jit(gapply, donate_argnums=(1, 2),
                                     **akw)
            self._acc_add_fn = jax.jit(gadd, donate_argnums=(0,),
                                       **ckw)

    def _device_put(self, a):
        if self._batch_sharding is not None:
            return jax.device_put(a, self._batch_sharding)
        return jax.device_put(a)

    # -- epoch drivers -----------------------------------------------------
    def train_epoch(self, data, target, indices, batch: int,
                    sync: bool = True, epoch: int | None = None,
                    lr_scale=1.0, ctr_base: int = 0,
                    lr_scale_bias=None) -> dict:
        if epoch is None:
            epoch = self._auto_epoch
        self._auto_epoch = epoch + 1
        if self._step_fn is None:
            self._build_steps()
        idx, mask, ctrs = self._idx_matrix(np.asarray(indices), batch,
                                           ctr_base)
        pf = BatchPrefetcher(self.loader, idx, depth=self.prefetch_depth,
                             device_put=self._device_put,
                             skip_labels=self._x_is_target, epoch=epoch,
                             raw=self.device_augment)
        losses, n_errs = [], []
        ep = jnp.uint32(epoch)
        scales, scales_b = self._step_scales(lr_scale, lr_scale_bias,
                                             idx.shape[0])
        accum = self.accum_steps
        acc = None
        n_steps = idx.shape[0]
        for step_i, (x, t) in enumerate(pf):
            ls = jnp.float32(scales[step_i])
            lsb = jnp.float32(scales_b[step_i])
            rows = jnp.asarray(idx[step_i], jnp.int32)
            if accum == 1:
                self.params, self.vels, m = self._step_fn(
                    self.params, self.vels, x, t,
                    jnp.asarray(mask[step_i]), ep,
                    jnp.uint32(ctrs[step_i]), ls, lsb, rows,
                    role="train.step")
            else:
                grads, m = self._grad_fn(self.params, x, t,
                                         jnp.asarray(mask[step_i]), ep,
                                         jnp.uint32(ctrs[step_i]), rows)
                # a group's first grads ARE the accumulator (right
                # structure, dtype and sharding — no zeros round-trip)
                acc = grads if acc is None \
                    else self._acc_add_fn(acc, grads)
                if (step_i + 1) % accum == 0 or step_i + 1 == n_steps:
                    self.params, self.vels = self._apply_fn(
                        self.params, self.vels, acc, ls, lsb)
                    acc = None
            losses.append(m["loss"])
            n_errs.append(m["n_err"])
            if self.step_callback is not None:
                self.step_callback(epoch, step_i)
        ms = {"loss": jnp.stack(losses), "n_err": jnp.stack(n_errs)}
        return {k: np.asarray(v) for k, v in ms.items()} if sync else ms

    def eval_epoch(self, data, target, indices, batch: int,
                   sync: bool = True, role: str = "eval") -> dict:
        if self._eval_fn is None:
            self._build_steps()
        idx, mask, _ = self._idx_matrix(np.asarray(indices), batch)
        pf = BatchPrefetcher(self.loader, idx, depth=self.prefetch_depth,
                             device_put=self._device_put,
                             skip_labels=self._x_is_target,
                             raw=self.device_augment)
        losses, n_errs = [], []
        for step_i, (x, t) in enumerate(pf):
            m = self._eval_fn(self.params, x, t,
                              jnp.asarray(mask[step_i]),
                              jnp.asarray(idx[step_i], jnp.int32),
                              role=role)
            losses.append(m["loss"])
            n_errs.append(m["n_err"])
        ms = {"loss": jnp.stack(losses), "n_err": jnp.stack(n_errs)}
        return {k: np.asarray(v) for k, v in ms.items()} if sync else ms
