"""Launcher: the two-file workflow+config entry point.

Parity target: the reference ``veles/launcher.py`` + ``veles/__main__.py``
(mount empty — surveyed contract, SURVEY.md §2.1 Launcher/CLI row, §3.1
call stack): ``python -m veles <workflow.py> <config.py>`` with
standalone / master / slave modes, ``--snapshot`` resume, backend choice,
and CLI config-path overrides.

TPU-first redesign (SURVEY.md §2.4): the master/slave star (Twisted +
ZeroMQ job protocol) collapses into **multi-process SPMD** — every
process runs the same program over a global device mesh, coordinated by
``jax.distributed.initialize`` (DCN); gradient aggregation is the mesh
all-reduce inside the fused step, not a job queue.  So the launcher's
"distributed mode" is a coordinator address + process count/index, not a
role split."""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import inspect
import os
import runpy

from .backends import Device
from .config import apply_overrides, root
from . import prng


def load_workflow_module(spec: str):
    """Import a workflow module from a file path or dotted module name."""
    if spec.endswith(".py") or os.path.sep in spec:
        name = os.path.splitext(os.path.basename(spec))[0]
        mod_spec = importlib.util.spec_from_file_location(name, spec)
        if mod_spec is None:
            raise ImportError(f"cannot load workflow file {spec!r}")
        module = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(module)
        return module
    return importlib.import_module(spec)


def exec_config_file(path: str) -> None:
    """Run a config file: plain Python mutating the global ``root``
    (reference config-file UX)."""
    runpy.run_path(path, init_globals={"root": root})


class Launcher:
    """Builds and runs one workflow according to CLI-ish options."""

    def __init__(self, workflow: str, config: str | None = None,
                 backend: str = "auto", snapshot: str | None = None,
                 epochs: int | None = None, fused: bool = False,
                 seed: int | None = None, overrides=(),
                 coordinator: str | None = None, num_processes: int = 1,
                 process_id: int = 0, profile: str | None = None,
                 timeline_jsonl: str | None = None,
                 mesh: str | None = None,
                 compile_cache_dir: str | None = None):
        self.workflow_spec = workflow
        self.config_path = config
        self.backend = backend
        self.snapshot = snapshot
        self.epochs = epochs
        self.fused = fused
        self.seed = seed
        self.overrides = list(overrides)
        self.coordinator = coordinator
        self.num_processes = num_processes
        self.process_id = process_id
        self.profile = profile
        self.timeline_jsonl = timeline_jsonl
        self.mesh = mesh
        self.compile_cache_dir = compile_cache_dir
        self.workflow = None

    @contextlib.contextmanager
    def _timeline_env(self):
        """``--timeline-jsonl`` scoped to THIS run: the env var is the
        channel StandardWorkflowBase.train defaults from (module.run()
        signatures stay untouched, same pattern as $ZNICZ_PROFILE_DIR),
        but it must not outlive the run — a later in-process Launcher
        without the flag would silently append its steps to the first
        run's file."""
        if not self.timeline_jsonl:
            yield
            return
        prev = os.environ.get("ZNICZ_TIMELINE_JSONL")
        os.environ["ZNICZ_TIMELINE_JSONL"] = self.timeline_jsonl
        try:
            yield
        finally:
            if prev is None:
                os.environ.pop("ZNICZ_TIMELINE_JSONL", None)
            else:
                os.environ["ZNICZ_TIMELINE_JSONL"] = prev

    def _trace_ctx(self):
        """A ``jax.profiler`` trace around the whole run when --profile
        DIR is set (SURVEY.md §5 tracing row: the TPU-level complement
        to the per-unit wall-clock time table, which is kept)."""
        from .telemetry import profiler
        return profiler.trace(self.profile)

    # -- distributed bootstrap (replaces Server/Client) --------------------
    def init_distributed(self) -> None:
        if self.coordinator is None:
            return
        import jax
        jax.distributed.initialize(
            coordinator_address=self.coordinator,
            num_processes=self.num_processes,
            process_id=self.process_id)

    def build(self):
        """Import module + config, seed, construct the workflow.

        Order matters: config file first (its values beat the module's
        ``setdefaults``), then the module import (defaults fill the
        gaps), then ``--set`` overrides LAST — they must win over both,
        and deep paths (``mnist.layers.0.<-.learning_rate``) can only
        resolve once the module's default structures exist."""
        self.init_distributed()
        # the persistent XLA compile cache must activate before any
        # jit compile of the run
        from . import compilecache
        compilecache.enable(self.compile_cache_dir)
        if self.config_path:
            exec_config_file(self.config_path)
        module = load_workflow_module(self.workflow_spec)
        self.module = module
        apply_overrides(self.overrides)
        if self.mesh is not None:
            # --mesh lands in the config tree, where run_fused's mesh
            # adoption defaults from — samples' run() signatures stay
            # untouched; wins over config files like --set does
            from .parallel.mesh import parse_mesh_arg
            root.common.mesh_shape = parse_mesh_arg(self.mesh)
        prng.seed_all(self.seed if self.seed is not None
                      else root.common.get("seed", 1234))
        if not hasattr(module, "run"):
            raise AttributeError(
                f"workflow module {self.workflow_spec!r} defines no "
                "run() entry point")
        return module

    def run(self):
        """Execute end-to-end; returns the finished workflow."""
        with self._timeline_env():
            return self._run()

    def _run(self):
        module = self.build()
        device = Device.create(self.backend)
        sig = inspect.signature(module.run)
        kwargs = {}
        if "device" in sig.parameters:
            kwargs["device"] = device
        if "epochs" in sig.parameters and self.epochs is not None:
            kwargs["epochs"] = self.epochs
        if "fused" in sig.parameters:
            kwargs["fused"] = self.fused
        if self.snapshot:
            # resume: build + initialize without training, load arrays,
            # then continue — run(load, main) style split
            wf = self._build_workflow_only(module, device)
            from .snapshotter import SnapshotterToFile
            SnapshotterToFile.load(wf, self.snapshot)
            if self.epochs is not None:
                wf.decision.max_epochs = self.epochs
            with self._trace_ctx():
                if hasattr(wf, "train"):
                    # one path-selection policy for both entry points
                    # (non-XLA devices fall back with a warning)
                    wf.train(fused=self.fused)
                else:
                    wf.run()
            self.workflow = wf
            return wf
        with self._trace_ctx():
            self.workflow = module.run(**kwargs)
        return self.workflow

    def _build_workflow_only(self, module, device):
        """Construct + initialize the module's workflow class without
        running it (the resume path needs state loaded in between).

        Resolution order (ADVICE r1: dir() picking an arbitrary class was
        unsafe for multi-workflow modules):
        1. an explicit ``WORKFLOW`` attribute (class or zero-arg factory);
        2. the module's sole ``*Workflow`` class — more than one is an
           error directing the author to convention 1."""
        target = getattr(module, "WORKFLOW", None)
        if target is None:
            found = [getattr(module, name) for name in dir(module)
                     if isinstance(getattr(module, name), type)
                     and name.endswith("Workflow")
                     and getattr(getattr(module, name), "__module__", "")
                     == module.__name__]
            if len(found) > 1:
                raise AttributeError(
                    f"workflow module {self.workflow_spec!r} defines "
                    f"{len(found)} *Workflow classes; set WORKFLOW = "
                    f"<class or factory> to pick the resume target")
            if not found:
                raise AttributeError(
                    f"workflow module {self.workflow_spec!r} has no "
                    "*Workflow class to resume into")
            target = found[0]
        wf = target()
        wf.initialize(device=device)
        return wf
