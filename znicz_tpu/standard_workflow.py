"""StandardWorkflow: declarative model assembly.

Parity target: the reference ``veles/znicz/standard_workflow.py`` (mount
empty — surveyed contract, SURVEY.md §2.2 [baseline]): a declarative
``layers=[{"type": ..., "->": {...}, "<-": {...}}, ...]`` config expands to
the forward chain + evaluator + decision + mirrored GD chain + snapshotter,
via the ``link_loader / link_forwards / link_evaluator / link_decision /
link_gds / link_snapshotter`` family.

Control graph (reconstructed reference shape, SURVEY.md §3.1)::

    start → loader → fwd₁ → … → fwdₙ → evaluator → decision
    decision → gdₙ → … → gd₁ ─(loop back-edge)→ loader
    decision → snapshotter ;  decision → end_point [gate: ~complete]

GD units gate_skip on non-train minibatches; the loop runs until Decision
sets ``complete``.

TPU-first: this unit graph is the assembly + per-unit-testing surface; for
the hot path the same chain is compiled into ONE jitted train step (forward
+ evaluator + backward + update, optionally mesh-sharded) by
``znicz_tpu.parallel.compile_fused_step`` — eliminating the per-minibatch
Python overhead the reference suffered (SURVEY.md §3.1 hot-loop note)."""

from __future__ import annotations

import time

import numpy as np

from .accelerated_units import AcceleratedWorkflow
from .logger import MetricsWriter
from .telemetry import flightrecorder as _flightrecorder
from .telemetry import profiler as _profiler
from .telemetry import tracing as _tracing
from .telemetry.registry import REGISTRY
from .mutable import DerivedBool
from .loader.base import TRAIN
from .nn import all2all, gd
from .nn.decision import DecisionGD, DecisionMSE
from .nn.evaluator import EvaluatorMSE, EvaluatorSoftmax
from .snapshotter import SnapshotterToFile


def _build_registries():
    fwd_map, gd_map = {}, {}
    modules = [all2all, gd]
    try:
        from .nn import conv, gd_conv, pooling, gd_pooling  # noqa
        from .nn import normalization, dropout, activation  # noqa
        from .nn import cutter, deconv, gd_deconv, depooling  # noqa
        from .nn import decoder  # noqa
        modules += [conv, gd_conv, pooling, gd_pooling, normalization,
                    dropout, activation, deconv, gd_deconv, depooling,
                    cutter, decoder]
    except ImportError:
        pass
    from .nn.nn_units import Forward, GradientDescentBase
    for mod in modules:
        for obj in vars(mod).values():
            if isinstance(obj, type) and issubclass(obj, Forward):
                for key in obj.MAPPING:
                    fwd_map[key] = obj
            if isinstance(obj, type) \
                    and issubclass(obj, GradientDescentBase):
                for key in obj.MAPPING:
                    gd_map[key] = obj
    return fwd_map, gd_map


class StandardWorkflowBase(AcceleratedWorkflow):
    """Builds the forward chain from a ``layers`` list."""

    def __init__(self, workflow=None, name=None, layers=None,
                 loss_function="softmax", **kwargs):
        super().__init__(workflow, name, **kwargs)
        self.layers_config = list(layers or [])
        self.loss_function = loss_function
        self.forwards = []
        self.gds = []
        self.lr_adjuster = None
        self.metrics_writer = MetricsWriter()
        self.fwd_map, self.gd_map = _build_registries()

    # -- link_* family (reference API) ------------------------------------
    def link_loader(self, loader) -> None:
        self.loader = loader
        self.add_unit(loader)   # membership: stop()/time_table()/graph/state
        loader.link_from(self.start_point)

    def link_forwards(self) -> None:
        prev = self.loader
        for i, spec in enumerate(self.layers_config):
            ltype = spec["type"]
            cls = self.fwd_map.get(ltype)
            if cls is None:
                raise ValueError(f"unknown layer type {ltype!r}; known: "
                                 f"{sorted(self.fwd_map)}")
            kwargs = dict(spec.get("->", {}))
            # decoder units tie to an earlier forward by index: depooling
            # needs the winner offsets of its paired pooling, deconv may
            # share (and co-train) the encoder conv's weight Vector
            tie_idx = kwargs.pop("tie", None)
            unit = cls(self, name=f"fwd{i}_{ltype}", **kwargs)
            if tie_idx is not None:
                unit.tie(self.forwards[tie_idx])
            if prev is self.loader:
                unit.link_attrs(self.loader, ("input", "minibatch_data"))
            else:
                unit.link_attrs(prev, ("input", "output"))
            unit.link_from(prev)
            self.forwards.append(unit)
            prev = unit

    def link_evaluator(self) -> None:
        last = self.forwards[-1]
        if self.loss_function == "softmax":
            ev = EvaluatorSoftmax(self, name="evaluator")
            ev.link_attrs(last, "output", "max_idx")
            ev.link_attrs(self.loader, ("labels", "minibatch_labels"))
        elif self.loss_function == "mse":
            ev = EvaluatorMSE(self, name="evaluator")
            ev.link_attrs(last, "output")
            ev.link_attrs(self.loader, ("target", "minibatch_targets"))
        else:
            raise ValueError(self.loss_function)
        ev.link_loader(self.loader)
        ev.link_from(last)
        self.evaluator = ev

    def link_decision(self, **config) -> None:
        cls = DecisionGD if self.loss_function == "softmax" else DecisionMSE
        self.decision = cls(self, name="decision", **config)
        self.decision.link_loader(self.loader)
        self.decision.link_evaluator(self.evaluator)
        self.decision.link_from(self.evaluator)
        self.end_point.link_from(self.decision)
        self.end_point.gate_block = ~self.decision.complete

    def link_lr_adjuster(self, **config) -> None:
        """Insert a LearningRateAdjust between decision and the GD chain
        (call before link_gds; the reference's lr_adjust wiring)."""
        from .nn.lr_adjust import LearningRateAdjust
        self.lr_adjuster = LearningRateAdjust(self, **config)
        self.lr_adjuster.link_from(self.decision)
        self.lr_adjuster.gate_skip = DerivedBool(
            lambda: bool(self.decision.complete), ())

    def link_gds(self, **defaults) -> None:
        """Mirrored gradient chain, last layer first (reference link_gds)."""
        prev = self.lr_adjuster if self.lr_adjuster is not None \
            else self.decision
        loader = self.loader
        decision = self.decision
        # skip backprop on valid/test minibatches and once training is
        # complete (so the final weights equal the last snapshot)
        train_only = DerivedBool(
            lambda: loader.minibatch_class != TRAIN
            or bool(decision.complete), ())
        first = True
        for i in reversed(range(len(self.forwards))):
            spec = self.layers_config[i]
            cls = self.gd_map.get(spec["type"])
            if cls is None:
                raise ValueError(
                    f"no gradient unit for layer type {spec['type']!r}")
            kwargs = {**defaults, **spec.get("<-", {})}
            unit = cls(self, name=f"gd{i}_{spec['type']}",
                       need_err_input=(i > 0), **kwargs)
            unit.setup_from_forward(self.forwards[i])
            if first:
                unit.link_attrs(self.evaluator, "err_output")
                first = False
            else:
                unit.link_attrs(prev, ("err_output", "err_input"))
            unit.link_from(prev)
            unit.gate_skip = train_only
            self.gds.insert(0, unit)
            prev = unit
        if self.lr_adjuster is not None:
            self.lr_adjuster.link_gds(self.gds)
        # close the minibatch loop
        self.loader.link_from(self.gds[0])

    def link_snapshotter(self, **config) -> None:
        self.snapshotter = SnapshotterToFile(self, **config)
        self.snapshotter.link_from(self.decision)

    # -- fused execution (the TPU hot path) -------------------------------
    def train(self, fused: bool = False, mesh=None,
              mesh_shape=None,
              max_epochs: int | None = None,
              compute_dtype: str | None = None,
              storage_dtype: str | None = None,
              profile_dir: str | None = None,
              profile_every: int | None = None,
              mse_target: str | None = None,
              checkpoint_dir: str | None = None,
              checkpoint_every: int | None = None,
              checkpointer=None,
              timeline_jsonl: str | None = None):
        """One entry point over both execution paths (the samples' and
        launcher's ``--fused`` plumbing): the compiled fused step when
        requested AND the device supports it, else the unit-graph tick
        loop — with a log line instead of a silent fallback.

        ``compute_dtype``/``storage_dtype`` default from the config
        tree (``root.common.compute_dtype``/``storage_dtype``) so every
        sample and the two-file CLI reach the mixed-precision knobs via
        config files or ``--set`` without per-sample plumbing.

        ``mesh_shape`` = ``(dp, tp)`` (or ``"dp,tp"``) lays the fused
        step out over a ``("data", "model")`` device mesh —
        data-parallel batches, Megatron-paired tensor-parallel weights,
        gradient all-reduce inserted by XLA (docs/distributed.md).  It
        defaults from ``root.common.mesh_shape`` (the CLI ``--mesh``
        lands there), and ``(1, 1)``/unset degenerates to exactly
        today's single-device jit.  An explicit prebuilt ``mesh`` still
        wins.

        Profiling (znicz_tpu.telemetry.profiler): ``profile_dir`` alone
        captures the whole run; with ``profile_every=N`` it captures a
        one-step window every N steps instead (long runs).  Both
        default from ``$ZNICZ_PROFILE_DIR`` / ``$ZNICZ_PROFILE_EVERY``
        so a deployed run can be profiled without code changes.

        Device checkpoints (fused path only): ``checkpoint_dir``
        creates a :class:`~znicz_tpu.parallel.checkpoint.
        TrainerCheckpointer` there and saves the live device state
        every ``checkpoint_every`` epochs (default 1) plus at the end
        — the asynchronous save overlaps the next epoch, and each
        step's durability manifest is committed as soon as the IO
        lands, which is what makes the step *blessed* for a promotion
        watcher (docs/promotion.md).  Pass an existing
        ``checkpointer`` (e.g. one with an ``on_blessed`` callback)
        to keep ownership of its lifecycle.

        Timeline (fused path only): ``timeline_jsonl`` (default
        ``$ZNICZ_TIMELINE_JSONL``, CLI ``--timeline-jsonl``) appends
        one JSON line per host step with the wall / device / host time
        split — the host-stall evidence the MFU work reads
        (docs/observability.md, docs/performance.md)."""
        from .config import root
        if compute_dtype is None:
            compute_dtype = root.common.get("compute_dtype")
        if storage_dtype is None:
            storage_dtype = root.common.get("storage_dtype")
        if profile_dir is None:
            profile_dir = _profiler.dir_from_env()
        if profile_every is None:
            profile_every = _profiler.every_from_env()
        if timeline_jsonl is None:
            timeline_jsonl = _flightrecorder.timeline_path_from_env()
        if fused:
            if self.device.is_xla:
                return self.run_fused(mesh=mesh, mesh_shape=mesh_shape,
                                      max_epochs=max_epochs,
                                      compute_dtype=compute_dtype,
                                      storage_dtype=storage_dtype,
                                      profile_dir=profile_dir,
                                      profile_every=profile_every,
                                      mse_target=mse_target,
                                      checkpoint_dir=checkpoint_dir,
                                      checkpoint_every=checkpoint_every,
                                      checkpointer=checkpointer,
                                      timeline_jsonl=timeline_jsonl)
            self.warning("fused path needs an XLA device; falling back "
                         "to the unit-graph tick loop")
        if mesh is not None or mesh_shape is not None:
            self.warning("mesh-sharded execution is a fused-path "
                         "feature; the tick loop runs single-device")
        if timeline_jsonl is not None:
            self.warning("the per-step timeline (timeline_jsonl) is a "
                         "fused-path feature; the tick loop records "
                         "nothing there")
        if checkpoint_dir is not None or checkpointer is not None:
            # also reached with fused=False: silently dropping the
            # training half of the promotion loop would leave a
            # watcher waiting on blessed steps that never come
            self.warning("device checkpoints (checkpoint_dir/"
                         "checkpointer) are a fused-path feature; "
                         "the tick loop keeps its snapshotter")
        if max_epochs is not None:
            self.decision.max_epochs = max_epochs
        return self.run()

    def run_fused(self, mesh=None, mesh_shape=None,
                  max_epochs: int | None = None,
                  compute_dtype: str | None = None,
                  storage_dtype: str | None = None,
                  profile_dir: str | None = None,
                  profile_every: int | None = None,
                  mse_target: str | None = None,
                  step_callback=None,
                  checkpoint_dir: str | None = None,
                  checkpoint_every: int | None = None,
                  checkpointer=None,
                  timeline_jsonl: str | None = None):
        """Train via the compiled fused step instead of the unit-graph
        tick loop: whole epochs run as one device-side ``lax.scan``
        (optionally mesh-sharded), with Decision's improvement/stop logic
        applied between epochs on host.  Weights are written back into
        the unit Vectors afterwards, so snapshotting/inspection work
        unchanged.  ``profile_dir`` wraps the run in a ``jax.profiler``
        trace (SURVEY.md §5 tracing row — the device-level complement to
        ``time_table()``), landing next to the JSONL metrics; with
        ``profile_every=N`` the capture is instead a windowed
        :class:`~znicz_tpu.telemetry.profiler.StepTraceHook` firing
        every N host steps (= epochs here: the whole epoch is one
        device-side scan).  Returns the FusedTrainer (kept for further
        use)."""
        import contextlib
        hook = None
        if profile_dir is not None and profile_every:
            hook = _profiler.StepTraceHook(profile_dir,
                                           every=int(profile_every))
            ctx = contextlib.nullcontext()
        elif profile_dir is not None:
            ctx = _profiler.trace(profile_dir)
        else:
            ctx = contextlib.nullcontext()
        if mesh is None:
            # mesh adoption policy (parallel/mesh.resolve_mesh): an
            # explicit mesh wins; else a (dp, tp) shape — argument or
            # the config tree's root.common.mesh_shape, which is where
            # the CLI --mesh lands — builds one; (1, 1)/unset stays
            # the single-device jit so plain-CPU tier-1 never changes
            from .config import root as _root
            from .parallel import mesh as _mesh_lib
            mesh = _mesh_lib.resolve_mesh(
                mesh_shape if mesh_shape is not None
                else _root.common.get("mesh_shape"), site="train")
        try:
            with ctx:
                return self._run_fused_body(mesh, max_epochs,
                                            compute_dtype,
                                            storage_dtype, mse_target,
                                            step_callback, hook,
                                            checkpoint_dir,
                                            checkpoint_every,
                                            checkpointer,
                                            timeline_jsonl)
        finally:
            if hook is not None:
                hook.close()

    def _run_fused_body(self, mesh, max_epochs, compute_dtype,
                        storage_dtype=None, mse_target=None,
                        step_callback=None, profile_hook=None,
                        checkpoint_dir=None, checkpoint_every=None,
                        checkpointer=None, timeline_jsonl=None):
        import dataclasses

        from .config import root

        from .loader.base import TEST, TRAIN, VALID
        from .parallel import FusedTrainer, fused

        assert self.initialized, "initialize() first"
        spec, params, vels = fused.extract_model(
            self, storage_dtype or "float32")
        if compute_dtype is not None:
            spec = dataclasses.replace(spec, compute_dtype=compute_dtype)
        from .loader.streaming import StreamingLoader
        if isinstance(self.loader, StreamingLoader):
            # disk-backed dataset: stream minibatches through the
            # double-buffered prefetcher instead of scanning a resident
            # tensor (same step math/RNG — parallel/stream.py).  MSE
            # heads: an explicit ``mse_target`` wins; otherwise a FLOAT
            # label block (denoising shards, regression targets of any
            # shape) is the target, and int labels mean the autoencoder
            # contract — reconstruct the input
            from .parallel.stream import StreamTrainer
            if mse_target is None:
                mse_target = "input"
                if self.loss_function == "mse":
                    ldt = np.dtype(getattr(self.loader, "label_dtype",
                                           np.int32))
                    if ldt.kind == "f":
                        mse_target = "labels"
            trainer = StreamTrainer(spec=spec, params=params, vels=vels,
                                    mesh=mesh, loader=self.loader,
                                    mse_target=mse_target,
                                    accum_steps=int(
                                        root.common.get("accum_steps")
                                        or 1),
                                    step_callback=step_callback,
                                    # bit-identical pixels to the host
                                    # application, but the crop rides
                                    # the device step instead of the
                                    # loader-bound host CPU; custom
                                    # policies without a device twin
                                    # keep the host prefetch path
                                    device_augment=hasattr(
                                        getattr(self.loader, "augment",
                                                None), "device_apply"))
        else:
            trainer = FusedTrainer(spec=spec, params=params, vels=vels,
                                   mesh=mesh,
                                   accum_steps=int(
                                       root.common.get("accum_steps")
                                       or 1))
        trainer.workflow = self
        loader = self.loader
        batch = loader.max_minibatch_size
        if isinstance(loader, StreamingLoader):
            data = target = None       # StreamTrainer reads the loader
        else:
            data = loader.original_data.devmem
            target = (loader.original_targets.devmem
                      if self.loss_function == "mse"
                      else loader.original_labels.devmem)
            # the resident set in the form every epoch program reads it
            # in, made here so that the start line can say which.  Where
            # that is a form of the trainer's own, no program of this
            # run reads the loader's rows again (the unit graph does not
            # run under run_fused; the trainer's memo answers for the
            # source by its identity): their device buffer, the larger
            # of the two, goes now and not at the run's end
            if isinstance(trainer.hold(data, batch), fused.HeldSet) \
                    and target is not data:
                loader.original_data.release_device()
        # where this run executes, stated by the program itself: once
        # here and in every timeline row, so a run that came up on the
        # wrong platform, mesh or kernel tier cannot pass for another
        from .backends import device_report
        from .ops import tuning
        from .parallel.mesh import mesh_shape_of
        where = {**device_report(),
                 "mesh": "x".join(str(d) for d in mesh_shape_of(mesh)),
                 # distinct devices the parameters are laid out over
                 "param_devices": max(
                     len(leaves[0].sharding.device_set)
                     for leaves in trainer.params
                     if leaves[0] is not None),
                 "kernel_tier": tuning.kernel_tier(),
                 # pooling rows on the one-pass windowed kernels, and on
                 # the tap stack (ops/pooling.py)
                 "pool_routes": fused.pool_routes(spec, self.forwards,
                                                  mesh),
                 # merged LRN+pool rows on the window kernels, and on
                 # the composed XLA ops (ops/lrn_pool.py)
                 "pair_routes": fused.pair_routes(spec, self.forwards,
                                                  mesh),
                 # how the trainer holds the resident set: as given, or
                 # once in the dtype and layout its programs gather from
                 "set_form": trainer.set_form,
                 # how the trainer's rule on a state that crowds the
                 # device went, and the numbers it compared: crowded,
                 # state_bytes, crowd_limit_bytes (fused.crowding; a
                 # streamed trainer has no such rule)
                 **getattr(trainer, "crowding", {})}
        if any(la.kind == "attn_block" for la in spec.layers):
            # attention rows over a sliding window, and over everything
            # before (ops/attention.py)
            where["attn_routes"] = fused.attn_routes(spec)
        if any(la.kind in fused.MIXERS for la in spec.layers):
            # the hidden layers by the mixer each runs
            where["mixer_routes"] = fused.mixer_routes(spec)
        if any(la.kind in ("gdn_block", "kda_block") for la in spec.layers):
            # the linear ones by the form their delta rule takes
            # (ops/gdn.py)
            where["rule_routes"] = fused.rule_routes(spec)
        if any(la.kind == "embed" for la in spec.layers):
            # how the embedding's table gradient is made (ops/attention.py)
            where["embed_routes"] = fused.embed_routes(spec, mesh)
        if any(la.kind == "moe_block" for la in spec.layers):
            # how the expert rows go back to token space (ops/moe.py)
            where["combine_routes"] = fused.combine_routes(
                spec, self.forwards, mesh)
        self.info("fused trainer on %s",
                  " ".join(f"{k}={v!r}" for k, v in where.items()))
        # host-vs-device time split (telemetry): every call of
        # trainer.train_epoch/eval_epoch is one child span of the
        # epoch's ``train.epoch`` span, and the time inside them is
        # device-bound work (prep + dispatch + compute + readback, which
        # the trainer's own grandchild spans split; an epoch that built
        # an executable carries a ``compile`` span too); the rest of
        # the epoch's wall up to its row is host work — the loader's
        # shuffle and plan, the learning-rate scales, the metrics'
        # arithmetic.  A host-dominated step is a pipeline problem no
        # profiler trace is needed to see; with a profiler on, the same
        # spans lie in the trace's host plane (telemetry/tracing.py).
        eval_spans = {VALID: "train.eval.validation",
                      TEST: "train.eval.test"}

        timeline = (_flightrecorder.TimelineWriter(timeline_jsonl)
                    if timeline_jsonl else None)
        # device-state checkpoints (parallel/checkpoint.py): the
        # training half of the promotion loop — every blessed step is
        # a candidate a promotion watcher may export and canary
        # (docs/promotion.md).  A caller-provided checkpointer keeps
        # its own lifecycle (and on_blessed subscribers); a bare
        # checkpoint_dir gets one owned (and closed) here.
        ckpt, own_ckpt = checkpointer, False
        if ckpt is None and checkpoint_dir is not None:
            from .parallel.checkpoint import TrainerCheckpointer
            ckpt = TrainerCheckpointer(checkpoint_dir)
            own_ckpt = True
        ckpt_every = max(1, int(checkpoint_every or 1))
        decision = self.decision
        bounds = np.cumsum([0] + list(loader.class_lengths))
        cls_idx = {k: np.arange(bounds[k], bounds[k + 1])
                   for k in (TEST, VALID, TRAIN)}
        # the targets of one row: 1 for a label a row, T for a token
        # sequence (n_err counts targets, so *_err_pct divides by them)
        row_targets = (int(np.prod(target.shape[1:]))
                       if self.loss_function == "softmax"
                       and target is not None else 1)
        # an explicit 0 means "stop after the first evaluation", exactly
        # like the unit-graph decision — only None falls through
        epochs = max_epochs if max_epochs is not None \
            else decision.max_epochs
        if epochs is None:
            epochs = 10
        from .loader.base import CLASS_NAMES
        lr_policy = bias_policy = None
        lr_by_epoch = True
        if self.lr_adjuster is not None:
            adj = self.lr_adjuster
            lr_policy = adj.policy
            lr_by_epoch = adj.by_epoch
            if adj.bias_policy is not adj.policy:
                bias_policy = adj.bias_policy   # separate bias schedule
        first = True
        # Unit-graph parity for the stop tick: in the tick where Decision
        # sets ``complete`` the GD units are gate-skipped, so the LAST
        # train minibatch of the final epoch never updates weights.  The
        # fused loop reproduces this by deferring each epoch's last
        # minibatch update until it knows training continues.
        pending = None   # (tail_idx, epoch, lr_scale, ctr_base,
        #            lr_scale_bias)
        # training throughput gauges (telemetry): one registry, so the
        # web status page and any /metrics scraper see live step time
        # and examples/sec next to the serving numbers
        g_step_ms = REGISTRY.gauge(
            "train_step_time_ms",
            "mean per-minibatch wall time over the last epoch, "
            "milliseconds (fused loop: epoch wall / steps)")
        g_eps = REGISTRY.gauge(
            "train_examples_per_sec",
            "training examples consumed per second over the last epoch")
        g_epoch = REGISTRY.gauge("train_epoch",
                                 "last completed training epoch index")
        g_dev_ms = REGISTRY.gauge(
            "train_device_ms",
            "wall time of the last host step spent inside device "
            "calls (prep + dispatch + compute + readback; a step that "
            "built an executable also carries that compile — see "
            "compile_time_ms)")
        g_host_ms = REGISTRY.gauge(
            "train_host_ms",
            "wall time of the last host step NOT inside device calls, "
            "up to the step's row (loader shuffle and plan, lr scales, "
            "metric arithmetic; the metrics writer, the decision and "
            "snapshot/checkpoint run after the row is cut: the next "
            "row's prev_tail_ms) — host-dominated steps are a pipeline "
            "problem")
        # the metrics writer, the decision and the saves of the epoch
        # before: they run after that epoch's row is cut, so the next
        # row carries them (None in a run's first row)
        prev_tail_ms = None

        def run_epoch(epoch, epoch_span, spans) -> bool:
            """One epoch under its ``train.epoch`` span; ``spans`` fills
            with the epoch's finished spans.  True ends the run."""
            nonlocal first, pending
            loader.epoch_number = epoch
            if not first:   # initialize() already built epoch 0's plan —
                loader._build_epoch_plan()   # reuse the loader's shuffle
            first = False                    # stream (unit-graph parity)
            metrics = {"epoch": epoch}
            perm = loader._shuffled[TRAIN]
            n_train = len(cls_idx[TRAIN])
            steps_per_epoch = max(1, -(-n_train // batch))

            def _scales(policy):
                """(head scales, tail scale) for one policy; iteration
                counting matches LearningRateAdjust._minibatches on
                the tick path."""
                if policy is None:
                    return 1.0, 1.0
                if lr_by_epoch:
                    s = policy.scale(epoch)
                    return s, s
                base_it = epoch * steps_per_epoch
                head_s = np.asarray(
                    [policy.scale(base_it + i)
                     for i in range(steps_per_epoch - 1)], np.float32)
                return head_s, policy.scale(base_it + steps_per_epoch
                                            - 1)
            scale, tail_scale = _scales(lr_policy)
            scale_b, tail_scale_b = (_scales(bias_policy)
                                     if bias_policy is not None
                                     else (None, None))
            if pending is not None:
                with _tracing.span("train.tail_update"):
                    trainer.train_epoch(data, target, pending[0], batch,
                                        epoch=pending[1],
                                        lr_scale=pending[2],
                                        ctr_base=pending[3], sync=False,
                                        lr_scale_bias=pending[4])
            split = ((n_train - 1) // batch) * batch
            head, tail = perm[:split], perm[split:]
            if len(head):
                with _tracing.span("train.head"):
                    tm = trainer.train_epoch(data, target, head, batch,
                                             epoch=epoch, lr_scale=scale,
                                             lr_scale_bias=scale_b)
            else:
                tm = {"loss": np.zeros((0,)), "n_err": np.zeros((0,))}
            # the tail minibatch's metrics come from a forward pass over
            # the post-head weights — same weights the unit graph's
            # evaluator saw before the (skipped-or-deferred) update.
            # Caveat: this forward runs in eval mode, so for nets with
            # stochastic layers (dropout) the tail step's train metrics
            # differ slightly from the unit graph's dropout-active ones;
            # weights stay exactly equal either way
            with _tracing.span("train.eval_tail"):
                em_tail = trainer.eval_epoch(data, target, tail, batch,
                                             role="eval.train")
            pending = (tail, epoch, tail_scale, split, tail_scale_b)
            metrics["train_loss"] = float(
                np.concatenate([tm["loss"], em_tail["loss"]]).mean())
            metrics["train_n_err"] = int(tm["n_err"].sum()
                                         + em_tail["n_err"].sum())
            metrics["train_err_pct"] = 100.0 * metrics["train_n_err"] \
                / max(n_train * row_targets, 1)
            # the step's device counters (a model with the sequence
            # kinds only), folded over the epoch's training rows
            counted = {
                name: int(getattr(np, fused.COUNTERS[name][0])(
                    np.concatenate([np.ravel(tm.get(name, ())),
                                    np.ravel(em_tail[name])])))
                for name in em_tail if name not in ("loss", "n_err")}
            for k in (VALID, TEST):
                if len(cls_idx[k]) == 0:
                    continue
                name = CLASS_NAMES[k]
                with _tracing.span(eval_spans[k]):
                    em = trainer.eval_epoch(data, target, cls_idx[k],
                                            batch, role=f"eval.{name}")
                metrics[f"{name}_loss"] = float(em["loss"].mean())
                metrics[f"{name}_n_err"] = int(em["n_err"].sum())
                metrics[f"{name}_err_pct"] = (
                    100.0 * metrics[f"{name}_n_err"]
                    / (len(cls_idx[k]) * row_targets))
            if self.loss_function == "mse":
                metrics["train_mse"] = metrics["train_loss"]
                if "validation_loss" in metrics:
                    metrics["validation_mse"] = metrics["validation_loss"]
            decision.epoch_metrics.append(metrics)
            loader.epoch_number = epoch + 1
            # the row is cut here, from the epoch's spans
            epoch_s = epoch_span.elapsed_ms() / 1e3
            parts = _flightrecorder.train_breakdown(spans)
            device_s = parts.pop("device_ms") / 1e3
            host_s = max(0.0, epoch_s - device_s)
            if epoch_s > 0:
                # gauges only — the metrics dict stays timing-free so
                # fused-vs-tick parity comparisons keep holding
                g_step_ms.set(epoch_s / steps_per_epoch * 1e3)
                g_eps.set(n_train / epoch_s)
                g_dev_ms.set(device_s * 1e3)
                g_host_ms.set(host_s * 1e3)
            g_epoch.set(epoch)
            # the flight recorder keeps the per-step record a scraper
            # of aggregate gauges can't reconstruct; the timeline file
            # is the same split as durable JSONL for the MFU analysis
            step_row = {"epoch": epoch, "steps": steps_per_epoch,
                        "examples": n_train,
                        "wall_ms": round(epoch_s * 1e3, 3),
                        "device_ms": round(device_s * 1e3, 3),
                        "host_ms": round(host_s * 1e3, 3),
                        "examples_per_sec": (round(n_train / epoch_s, 1)
                                             if epoch_s > 0 else None),
                        **parts, "prev_tail_ms": prev_tail_ms, **counted}
            for name, value in counted.items():
                fused.COUNTERS[name][1]().set(value)
            _flightrecorder.RECORDER.record(
                "train_step", duration_ms=epoch_s * 1e3, **step_row)
            if timeline is not None:
                timeline.write({"at": time.time(), **step_row, **where})
            with _tracing.span("train.decision"):
                self.metrics_writer.write(kind="epoch", **metrics)
                if self.lr_adjuster is not None:
                    # keep the tick-path iteration counter current so
                    # snapshots persist the TRUE schedule position (a
                    # tick-path resume of a fused run must continue the
                    # by_epoch=False schedule, not restart it)
                    self.lr_adjuster._minibatches = \
                        (epoch + 1) * steps_per_epoch
                improved = decision.better_than_best(metrics)
                if improved:
                    decision.improved.set(True)
                    decision._fails = 0
                else:
                    decision._fails += 1
            snap = getattr(self, "snapshotter", None)
            # Deferred-tail correctness: a mid-training snapshot OR
            # device checkpoint must include this epoch's tail update
            # (a continuous run applies it at the next epoch's start;
            # resume starts with pending=None, so saving without it
            # would silently drop one update).  On the FINAL epoch the
            # unit graph's stop tick gate-skips that update, so the
            # tail stays pending and the save matches the unit path's
            # final snapshot exactly.
            is_final = (epoch == epochs - 1
                        or decision._fails >= decision.fail_iterations)

            def _sync_weights():
                nonlocal pending
                if not is_final and pending is not None:
                    trainer.train_epoch(
                        data, target, pending[0], batch,
                        epoch=pending[1], lr_scale=pending[2],
                        ctr_base=pending[3], sync=False,
                        lr_scale_bias=pending[4])
                    pending = None
                trainer.write_back()

            ckpt_due = ckpt is not None and ((epoch + 1) % ckpt_every == 0
                                             or is_final)
            if snap is not None or ckpt_due:
                with _tracing.span("train.save"):
                    if snap is not None:
                        snap.epoch_end(improved, before_save=_sync_weights)
                    if ckpt_due:
                        # async device-state save: IO overlaps the next
                        # epoch, and the step's manifest (its bless
                        # mark) commits at the next save/wait/close
                        # once the bytes are down
                        _sync_weights()
                        ckpt.save(trainer, epoch, block=False)
            return decision._fails >= decision.fail_iterations

        for epoch in range(loader.epoch_number, epochs):
            if profile_hook is not None:
                profile_hook.on_step(epoch)
            # one request id an epoch: every span of the epoch carries
            # it, and collect() hands the finished ones to the row
            with _tracing.request() as rid, _tracing.collect(rid) as spans:
                with _tracing.span("train.epoch", step_num=epoch,
                                   epoch=epoch) as epoch_span:
                    stop = run_epoch(epoch, epoch_span, spans)
            prev_tail_ms = _flightrecorder.train_tail_ms(spans)
            if stop:
                break
        decision.complete.set(True)
        trainer.write_back()
        if timeline is not None:
            timeline.close()
        if ckpt is not None:
            # flush in-flight async saves and bless their manifests; a
            # borrowed checkpointer stays open for its owner
            if own_ckpt:
                ckpt.close()
            else:
                ckpt.wait()
        return trainer


def sample_snapshotter_config(tree, explicit):
    """THE defaulting rule every sample uses for its snapshotter:
    an explicit argument (even ``{}`` = all defaults) wins; otherwise
    the sample's config tree (``root.<name>.snapshotter``, reachable
    from config files and ``--set``) provides it."""
    return explicit if explicit is not None else tree.get("snapshotter")


class StandardWorkflow(StandardWorkflowBase):
    """One-call assembly (the reference's usual entry point)."""

    def __init__(self, workflow=None, name=None, layers=None,
                 loader=None, loss_function="softmax", decision_config=None,
                 snapshotter_config=None, lr_adjuster_config=None,
                 **kwargs):
        super().__init__(workflow, name, layers=layers,
                         loss_function=loss_function, **kwargs)
        if loader is not None:
            self.create_workflow(loader, decision_config or {},
                                 snapshotter_config, lr_adjuster_config)

    def create_workflow(self, loader, decision_config: dict,
                        snapshotter_config: dict | None,
                        lr_adjuster_config: dict | None = None) -> None:
        # configs may arrive as Config subtrees (samples defaulting from
        # root.<name>.snapshotter etc., --set-created nodes) — coerce
        def as_dict(c):
            return c.to_dict() if hasattr(c, "to_dict") else c
        decision_config = as_dict(decision_config)
        snapshotter_config = as_dict(snapshotter_config)
        lr_adjuster_config = as_dict(lr_adjuster_config)
        self.link_loader(loader)
        self.link_forwards()
        self.link_evaluator()
        self.link_decision(**decision_config)
        if lr_adjuster_config is not None:
            self.link_lr_adjuster(**lr_adjuster_config)
        self.link_gds()
        if snapshotter_config is not None:
            self.link_snapshotter(**snapshotter_config)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        # classifier sanity: a loader-derived class count that exceeds
        # the softmax width would one-hot to all-zero rows and train
        # silently wrong (ops/softmax.py one_hot semantics) — fail loud
        if self.loss_function == "softmax" and self.forwards:
            n_out = int(self.forwards[-1].output.shape[-1])
            n_cls = getattr(self.loader, "n_classes", None)
            if n_cls is not None and int(n_cls) > n_out:
                raise ValueError(
                    f"{self.name}: loader serves {n_cls} classes but the "
                    f"softmax layer is {n_out}-wide — labels ≥ {n_out} "
                    "would train silently wrong")
