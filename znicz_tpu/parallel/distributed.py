"""Multi-host SPMD bootstrap + data distribution + failure recovery.

Parity target: the reference's distributed runtime (SURVEY.md §2.1
Master server / Slave client rows; §2.4; §3.2 job-loop call stack;
§5 failure detection): a Twisted TCP + ZeroMQ master–slave star shipping
pickled minibatches and gradients, with disconnect-requeue recovery.

TPU-first redesign (the north star): every host runs the SAME program;
``jax.distributed`` (DCN coordination service) replaces the Twisted
control plane; the data plane is XLA collectives over ICI/DCN inside the
compiled step — no pickled tensors, no job queue.  This module holds the
glue the reference put in server.py/client.py:

* :func:`initialize` — process bootstrap (the master/slave handshake).
* :func:`global_mesh` — a ("data", "model") mesh over ALL processes'
  devices (the slave roster).
* :func:`shard_dataset` — per-process dataset slice → one global sharded
  array (the reference's ``generate_data_for_slave`` minibatch split,
  done once per dataset instead of per job).
* :class:`CheckpointRecovery` — crash/preemption recovery: periodic
  snapshots + resume (the reference's requeue becomes restart-from-
  checkpoint, SURVEY.md §5 failure row).
"""

from __future__ import annotations

import os

import jax
import numpy as np

from . import mesh as mesh_lib


def initialize(coordinator: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None,
               retry: "RetryPolicy | None" = None) -> None:
    """Bootstrap multi-host JAX (idempotent).  Arguments may come from
    the environment (JAX_COORDINATOR_ADDRESS / NUM_PROCESSES /
    PROCESS_ID) — the launcher passes CLI flags through here.

    The coordinator handshake is the ``relay.connect`` fault site and
    retries under ``retry`` (default: 3 attempts, 0.5–5 s backoff) —
    on a preempted pod the coordinator routinely comes up seconds
    after its workers, and one refused TCP connect must not kill a
    worker the ElasticRunner would only restart anyway."""
    from ..resilience import faults
    from ..resilience.retry import RetryPolicy
    # a deployment address, not a route of the step
    # zlint: disable=env-routing
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if coordinator is None:
        return   # single-process: nothing to negotiate
    kwargs = dict(coordinator_address=coordinator)
    if num_processes is not None:
        kwargs["num_processes"] = int(num_processes)
    if process_id is not None:
        kwargs["process_id"] = int(process_id)
    policy = retry if retry is not None else RetryPolicy(
        max_attempts=3, base_delay_s=0.5, max_delay_s=5.0)

    def _connect():
        faults.inject("relay.connect")
        jax.distributed.initialize(**kwargs)

    policy.call(_connect)


def global_mesh(n_model: int = 1) -> "jax.sharding.Mesh":
    """("data", "model") mesh over every device of every process."""
    devices = jax.devices()
    return mesh_lib.make_mesh(n_data=len(devices) // n_model,
                              n_model=n_model, devices=devices)


def process_shard(n: int) -> slice:
    """This process's contiguous row range of an n-sample dataset."""
    p, np_ = jax.process_index(), jax.process_count()
    per = -(-n // np_)
    return slice(p * per, min((p + 1) * per, n))


def shard_dataset(local_rows: np.ndarray, mesh, total_rows: int
                  ) -> jax.Array:
    """Assemble one global batch-sharded array from per-process rows.

    ``local_rows`` are THIS process's samples (``process_shard`` of the
    global set); the result is a global jax.Array sharded over the mesh's
    ``data`` axis — the TPU equivalent of the master shipping each slave
    its minibatch slice, paid once per dataset."""
    sharding = mesh_lib.shard_batch(mesh)
    global_shape = (total_rows,) + tuple(local_rows.shape[1:])
    if jax.process_count() == 1:
        return jax.device_put(local_rows, sharding)
    return jax.make_array_from_process_local_data(
        sharding, np.ascontiguousarray(local_rows), global_shape)


def distribute(workflow, mesh) -> dict:
    """Distribute an initialized workflow's per-shard state over ``mesh``
    through the **Distributable protocol** — the SPMD rendition of the
    reference master loop (SURVEY.md §2.1 Distributable row; §3.2):

    for each unit, ``generate_data_for_slave()`` publishes the shard of
    every per-shard array this process owns (``{name: (local_rows,
    total_rows)}``; ``None`` = unit owns only replicated state); the
    'master' role — here just this function, since every process runs
    it symmetrically — assembles one globally batch-sharded jax.Array
    per entry (:func:`shard_dataset`); ``apply_data_from_master``
    installs them back into the unit.  Gradient aggregation (the
    reference's ``apply_data_from_slave`` fold) stays inside the jitted
    step as a psum over the data axis.

    Returns ``{unit_name: [vector names sharded]}`` for logging."""
    out = {}
    for unit in workflow.units:
        payload = unit.generate_data_for_slave()
        if not payload:
            continue
        installed = {
            name: shard_dataset(local, mesh, int(total))
            for name, (local, total) in sorted(payload.items())}
        unit.apply_data_from_master(installed)
        out[unit.name] = sorted(installed)
    return out


class CheckpointRecovery:
    """Failure recovery loop: snapshot every N epochs, resume after a
    crash (reference: master requeued a lost slave's job; with SPMD the
    whole program restarts from the last snapshot — SURVEY.md §5).

    Save and resume retry under ``retry`` (default 3 attempts, short
    backoff): a transient filesystem blip mid-checkpoint is common on
    network mounts, and the atomic single-rename save makes a retry
    always safe — a failed attempt can never leave a torn snapshot
    behind for the retry to trip on."""

    def __init__(self, workflow, directory="snapshots",
                 prefix="recovery", interval=1,
                 retry: "RetryPolicy | None" = None):
        from ..resilience.retry import RetryPolicy
        from ..snapshotter import SnapshotterToFile
        self.workflow = workflow
        self.snap = SnapshotterToFile(workflow, prefix=prefix,
                                      directory=directory,
                                      interval=interval)
        self.retry = retry if retry is not None else RetryPolicy(
            max_attempts=3, base_delay_s=0.2, max_delay_s=2.0)
        # standalone use: not linked into the control graph
        workflow.units.remove(self.snap) \
            if self.snap in workflow.units else None

    @property
    def path(self) -> str:
        return os.path.join(self.snap.directory,
                            f"{self.snap.prefix}_current.npz")

    def save(self) -> str:
        """Checkpoint now (call between epochs; process 0 writes)."""
        if jax.process_index() != 0:
            return self.path
        return self.retry.call(self.snap.save, "current")

    def resume_if_found(self) -> dict | None:
        """Restore the newest *verified* checkpoint into the
        (initialized) workflow; returns its meta or None when starting
        fresh.  Corrupt entries (torn write, bit rot — see
        znicz_tpu.durability) are quarantined to ``*.corrupt`` and the
        scan falls back to the next-newest verified snapshot: a rotten
        ``current`` must cost one checkpoint interval of progress, not
        the whole run.  Transient read blips still retry under
        ``retry`` as before.  Quarantine/heal writes follow the save
        ownership rule (process 0); other processes scan read-only and
        skip the same corrupt entries."""
        from ..snapshotter import SnapshotterToFile

        def _restore():
            found = SnapshotterToFile.restore(
                self.workflow, directory=self.snap.directory,
                prefix=self.snap.prefix,
                owner=jax.process_index() == 0)
            return found[0] if found is not None else None

        return self.retry.call(_restore)
