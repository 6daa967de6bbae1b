"""Checkpoint/resume: pytree snapshots of workflow state.

Parity target: the reference ``veles/snapshotter.py`` (mount empty —
surveyed contract, SURVEY.md §2.1/§3.4/§5): periodic + on-improvement
snapshots, "best" snapshot kept separately, compression, CLI resume.

TPU-first redesign (SURVEY.md §5): instead of pickling live Python objects
(units, device buffers), snapshots are *data*: an ``.npz`` of every
parameter/optimizer array addressed by ``unit_name/vector_name``, plus a
JSON sidecar of host-side counters (epoch, best error, decision state).
Restore rebuilds the workflow from code and loads arrays in — robust across
code changes, and exactly how Orbax-style TPU checkpointing treats state."""

from __future__ import annotations

import bz2
import glob
import gzip
import io
import json
import lzma
import os
import time

import numpy as np

from . import durability
from .resilience import faults
from .units import Unit

#: external compressors (reference parity: gz/bz2/xz snapshot files);
#: the default .npz is already zip-deflated, so these wrap a RAW .npz
#: (compressing deflate twice wastes cycles for ~0 gain)
_OPENERS = {"gz": gzip.open, "bz2": bz2.open, "xz": lzma.open}

#: Vector attributes captured per unit, in precedence order.
_STATE_VECTORS = ("weights", "bias", "velocity_weights", "velocity_bias",
                  "gradient_weights", "gradient_bias")


def _state_vectors(unit) -> tuple[str, ...]:
    """The znicz names, then the unit's own (a sequence kind's leaves
    and their velocities: ``nn/decoder.py``)."""
    return _STATE_VECTORS + tuple(getattr(unit, "STATE_VECTORS", ()))


def collect_state(workflow) -> tuple[dict[str, np.ndarray], dict]:
    """(arrays keyed unit/vector, host-side counters)."""
    arrays: dict[str, np.ndarray] = {}
    seen_vectors: set[int] = set()
    for unit in workflow.units:
        for attr in _state_vectors(unit):
            vec = unit.__dict__.get(attr)   # skip link_attrs aliases
            if vec is None or not vec:
                continue
            if id(vec) in seen_vectors:
                continue
            seen_vectors.add(id(vec))
            arrays[f"{unit.name}/{attr}"] = np.asarray(vec.mem)
    meta = {"time": time.time()}
    from . import prng
    # stream positions make resume bit-reproducible (the loader's
    # shuffle stream continues instead of restarting from the seed)
    meta["prng_state"] = prng.state()
    loader = getattr(workflow, "loader", None)
    if loader is not None:
        meta["epoch_number"] = loader.epoch_number
    decision = getattr(workflow, "decision", None)
    if decision is not None:
        meta["best_n_err"] = float(getattr(decision, "best_n_err",
                                           np.inf))
        meta["best_mse"] = float(getattr(decision, "best_mse", np.inf))
        meta["epoch_metrics"] = decision.epoch_metrics
        # early-stop state: a resume that reset the fail counter would
        # train past where the continuous run stopped
        meta["decision_fails"] = int(getattr(decision, "_fails", 0))
    adj = getattr(workflow, "lr_adjuster", None)
    if adj is not None:
        # by_epoch=False schedules key on this counter — resume must
        # continue the schedule, not restart it from iteration 0
        meta["lr_adjust_minibatches"] = int(adj._minibatches)
    snap = getattr(workflow, "snapshotter", None)
    if snap is not None:
        # resume must keep the periodic cadence aligned with the
        # continuous run (interval>1: saves land at the same epochs)
        meta["snapshotter_epochs_seen"] = snap._epochs_seen
    return arrays, meta


def restore_state(workflow, arrays: dict, meta: dict) -> None:
    for unit in workflow.units:
        for attr in _state_vectors(unit):
            key = f"{unit.name}/{attr}"
            vec = unit.__dict__.get(attr)
            if key in arrays and vec is not None:
                vec.mem = arrays[key]
                if getattr(unit, "device", None) is not None \
                        and unit.device is not None and unit.device.is_xla:
                    vec.unmap()
    if "prng_state" in meta:
        from . import prng
        prng.set_state(meta["prng_state"])
    loader = getattr(workflow, "loader", None)
    if loader is not None and "epoch_number" in meta:
        loader.epoch_number = int(meta["epoch_number"])
        loader.reset_state()
    decision = getattr(workflow, "decision", None)
    if decision is not None:
        if "best_n_err" in meta:
            decision.best_n_err = meta["best_n_err"]
        if "best_mse" in meta and hasattr(decision, "best_mse"):
            decision.best_mse = meta["best_mse"]
        if "epoch_metrics" in meta:
            decision.epoch_metrics = list(meta["epoch_metrics"])
        if "decision_fails" in meta:
            decision._fails = int(meta["decision_fails"])
    adj = getattr(workflow, "lr_adjuster", None)
    if adj is not None and "lr_adjust_minibatches" in meta:
        adj._minibatches = int(meta["lr_adjust_minibatches"])
    snap = getattr(workflow, "snapshotter", None)
    if snap is not None and "snapshotter_epochs_seen" in meta:
        snap._epochs_seen = int(meta["snapshotter_epochs_seen"])


class SnapshotterBase(Unit):
    def __init__(self, workflow=None, name=None, prefix="snapshot",
                 directory="snapshots", interval=1, keep_best=True,
                 compression: str | None = None, **kwargs):
        super().__init__(workflow, name or "snapshotter", **kwargs)
        self.prefix = prefix
        self.directory = directory
        self.interval = interval
        self.keep_best = keep_best
        if compression not in (None, "none", *_OPENERS):
            raise ValueError(f"compression {compression!r}; pick one of "
                             f"{sorted(_OPENERS)} or None")
        self.compression = None if compression == "none" else compression
        self._epochs_seen = 0
        self.last_path: str | None = None
        self.best_path: str | None = None

    def epoch_end(self, improved: bool, before_save=None) -> None:
        """One epoch's snapshot cadence — THE single definition shared
        by the unit tick path (run()) and the fused epoch loop: save
        "current" every ``interval`` epochs and on improvement, plus
        "best" on improvement.  ``before_save`` runs only when a save
        will actually happen (the fused path syncs weights there)."""
        self._epochs_seen += 1
        if self._epochs_seen % self.interval == 0 or improved:
            if before_save is not None:
                before_save()
            self.last_path = self.save("current")
            if improved and self.keep_best:
                self.best_path = self.save("best")


class SnapshotterToFile(SnapshotterBase):
    """Writes ``<dir>/<prefix>_current.npz`` every ``interval`` epochs and
    ``<prefix>_best.npz`` whenever Decision reports improvement."""

    def run(self) -> None:
        decision = self.workflow.decision
        if not bool(self.workflow.loader.last_minibatch):
            return
        improved = bool(decision.snapshot_suggested)
        if improved:
            decision.snapshot_suggested.set(False)
        self.epoch_end(improved)

    def save(self, tag: str) -> str:
        """Crash-safe save: the metadata rides INSIDE the .npz (a
        JSON-bytes array under ``__meta_json__``), so arrays and
        counters commit in one os.replace() — an unclean death (SIGKILL,
        preemption — the very case restart-from-snapshot exists for)
        can never pair save-N arrays with save-N±1 meta.  A ``.json``
        sidecar is still written for human inspection, but load() never
        reads it.

        Commit ordering is PINNED (tests/test_durability.py):
        manifest invalidate first, then the blob rename, then the new
        sha256 manifest (:func:`durability.write_manifest`), then the
        human sidecar.  A crash anywhere in that window leaves a
        manifest-LESS blob (old or new, both self-consistent) which
        verify-on-load deep-parses, loads, and re-blesses; it can never
        leave a live manifest over bytes it does not describe — which
        is exactly what lets a digest mismatch mean "rot, quarantine"
        unambiguously.  The reverse order (manifest before blob) would
        bless a blob that was never written.

        Fault sites: ``checkpoint.save`` fires BEFORE any filesystem
        mutation (a preemption landing at the worst moment — the
        retry/atomic-rename story, see CheckpointRecovery);
        ``checkpoint.write_torn`` fires INSIDE the torn window between
        the blob and manifest renames (error = die torn, latency = hold
        the window open for the SIGKILL crash tests);
        ``artifact.bitflip`` (durability.chaos_bitflip) rots one byte
        of the committed blob AFTER its manifest is written."""
        faults.inject("checkpoint.save")
        os.makedirs(self.directory, exist_ok=True)
        arrays, meta = collect_state(self.workflow)
        meta_blob = np.frombuffer(
            json.dumps(meta, default=float).encode(), dtype=np.uint8)
        base = os.path.join(self.directory, f"{self.prefix}_{tag}.npz")
        if self.compression:
            path = f"{base}.{self.compression}"
            buf = io.BytesIO()
            np.savez(buf, __meta_json__=meta_blob,
                     **arrays)              # raw; outer codec compresses
            with _OPENERS[self.compression](path + ".tmp", "wb") as fh:
                fh.write(buf.getbuffer())   # zero-copy view: snapshots
                #                            can be GBs of params
        else:
            path = base
            with open(path + ".tmp", "wb") as fh:
                np.savez_compressed(fh, __meta_json__=meta_blob, **arrays)
        with open(path + ".json.tmp", "w") as fh:
            json.dump(meta, fh, default=float)
        durability.invalidate_manifest(path)
        os.replace(path + ".tmp", path)
        faults.inject("checkpoint.write_torn")
        durability.write_manifest(path, kind="snapshot")
        durability.chaos_bitflip(path)
        os.replace(path + ".json.tmp", path + ".json")
        self.debug("snapshot → %s", path)
        return path

    @staticmethod
    def load(workflow, path: str, verify: bool = True) -> dict:
        """Restore a snapshot into an *initialized* workflow; returns
        meta.  Compression is detected from the extension
        (``.npz[.gz|.bz2|.xz]`` — the reference's CLI-resume UX).
        ``checkpoint.load`` is the matching chaos fault site.

        ``verify`` (default) runs :func:`durability.verify_or_heal`
        first: a truncated or bit-flipped snapshot raises the typed
        :class:`durability.ArtifactCorrupt` instead of an opaque
        zipfile/CRC error mid-restore, a torn-save stale manifest is
        healed, and a pre-durability snapshot (no manifest) still gets
        the deep format parse.  Pass ``verify=False`` only when the
        caller verified already (:meth:`restore`'s scan)."""
        faults.inject("checkpoint.load")
        if verify:
            durability.verify_or_heal(path)
        ext = path.rsplit(".", 1)[-1]
        if ext in _OPENERS:
            with _OPENERS[ext](path, "rb") as fh:
                buf = io.BytesIO(fh.read())
            arrays = dict(np.load(buf, allow_pickle=False))
        else:
            arrays = dict(np.load(path, allow_pickle=False))
        if "__meta_json__" in arrays:       # atomic format (meta inside)
            meta = json.loads(arrays.pop("__meta_json__").tobytes())
        else:                               # pre-atomic snapshots
            with open(path + ".json") as fh:
                meta = json.load(fh)
        restore_state(workflow, arrays, meta)
        return meta

    @classmethod
    def restore(cls, workflow, directory: str = "snapshots",
                prefix: str = "snapshot", owner: bool = True
                ) -> tuple[dict, str] | None:
        """Last-good-fallback resume: scan this prefix's snapshots
        newest→oldest, quarantine corrupt entries (``*.corrupt`` +
        structured log + ``artifacts_quarantined_total``), and restore
        the newest one that verifies.  Returns ``(meta, path)`` or None
        when nothing usable exists — a corrupt ``current`` falls back
        to ``best`` (or an older tagged save) instead of crashing the
        resume, the contract ElasticRunner workers rely on.
        ``owner=False`` (non-zero processes of a fleet) verifies
        read-only: no quarantine renames, no manifest heals — process
        0 owns the writes, everyone lands on the same survivor."""
        path = durability.newest_verified(
            snapshot_candidates(directory, prefix),
            on_corrupt="quarantine" if owner else "skip", heal=owner)
        if path is None:
            return None
        return cls.load(workflow, path, verify=False), path


def snapshot_candidates(directory: str, prefix: str = "snapshot"
                        ) -> list[str]:
    """This prefix's snapshot blobs under ``directory``, newest first
    (mtime).  Sidecars (``.json``/``.manifest.json``), temporaries, and
    already-quarantined ``*.corrupt*`` entries are excluded."""
    out = []
    for path in glob.glob(os.path.join(
            directory, glob.escape(prefix) + "_*.npz*")):
        name = os.path.basename(path)
        if name.endswith((".json", ".tmp")) or ".corrupt" in name:
            continue
        if not (name.endswith(".npz")
                or name.rsplit(".", 1)[-1] in _OPENERS):
            continue
        try:
            out.append((os.path.getmtime(path), path))
        except OSError:          # raced a quarantine/cleanup
            continue
    return [p for _, p in sorted(out, reverse=True)]
