"""Worker for the true multi-process distributed tests (run via
``subprocess`` from tests/test_distributed.py, N processes × 2 CPU
devices each).

Each process bootstraps through ``parallel.distributed`` exactly the way
a real multi-host deployment would (SURVEY.md §3.2 job-loop redesign):
``initialize`` → ``global_mesh`` over both processes' devices →
``process_shard``/``shard_dataset`` to assemble the global batch from
process-local rows → fused train steps whose gradient all-reduce rides
XLA collectives.  Process 0 saves the final weights for the parent test
to compare against a single-process run of the identical math.

Usage: python _distributed_worker.py PORT PROC_ID NUM_PROCS OUT.npy \
           [plain|phase1|phase2]
(phase1/phase2 select the combined accumulation+bf16+coordinator-restart
scenario; the default "plain" mode runs 5 replicated full-batch steps.)
"""

import os
import sys

import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402


def combined(out: str, phase: str) -> None:
    """The combined scenario (VERDICT r2 items 5 + 6; widened to 4
    processes by VERDICT r3 item 9): N processes × 2 devices each
    (2N-device global mesh), micro-batch gradient ACCUMULATION + BF16
    activation storage, with a TRUE COORDINATOR RESTART between epochs
    — phase1 trains epoch 0, checkpoints, and every process (including
    the jax.distributed coordinator) EXITS; phase2 is a fresh process
    set on a fresh coordinator port that rebuilds from the checkpoint
    and trains epoch 1.  Process 0 writes the final weights for the
    parent to compare against a single-process run of the identical
    math."""
    import dataclasses

    from znicz_tpu.parallel import FusedTrainer, distributed
    from znicz_tpu.parallel.fused import LayerSpec, ModelSpec

    n, feats, classes = 64, 32, 5
    rng = np.random.default_rng(3)
    data = rng.standard_normal((n, feats)).astype(np.float32)
    labels = rng.integers(0, classes, n).astype(np.int32)
    w0 = (rng.standard_normal((feats, classes)) * 0.1).astype(np.float32)
    spec = ModelSpec((LayerSpec(
        kind="fc", activation="linear", include_bias=True,
        hypers=(0.05, 0.0, 0.0, 0.9),
        hypers_bias=(0.05, 0.0, 0.0, 0.9)),), "softmax")
    spec = dataclasses.replace(spec, storage_dtype="bfloat16")
    mesh = distributed.global_mesh()
    # each process must expose exactly 2 local devices (the parent's
    # XLA_FLAGS contract) — device_count() alone would be tautological
    assert dict(mesh.shape)["data"] * dict(mesh.shape)["model"] \
        == 2 * jax.process_count()

    ckpt = out + ".ckpt.npz"
    if phase == "phase1":
        params = [(w0, np.zeros(classes, np.float32))]
        vels = [(np.zeros_like(w0), np.zeros(classes, np.float32))]
        epoch = 0
    else:
        ck = np.load(ckpt)
        params = [(ck["w"], ck["b"])]
        vels = [(ck["vw"], ck["vb"])]
        epoch = 1

    gx = distributed.shard_dataset(
        data[distributed.process_shard(n)], mesh, n)
    gy = distributed.shard_dataset(
        labels[distributed.process_shard(n)], mesh, n)
    tr = FusedTrainer(spec=spec, params=params, vels=vels, mesh=mesh,
                      accum_steps=2)
    tr.train_epoch(gx, gy, np.arange(n), 16, epoch=epoch)  # 4 mb → 2 upd

    host_p = [(np.asarray(w), np.asarray(b)) for w, b in tr.params]
    host_v = [(np.asarray(w), np.asarray(b)) for w, b in tr.vels]
    from jax.experimental import multihost_utils
    if jax.process_index() == 0:
        if phase == "phase1":
            np.savez(ckpt, w=host_p[0][0], b=host_p[0][1],
                     vw=host_v[0][0], vb=host_v[0][1])
        else:
            np.save(out, host_p[0][0])
    multihost_utils.sync_global_devices(f"{phase}-written")
    jax.effects_barrier()


def main() -> None:
    port, pid, nproc, out = (sys.argv[1], int(sys.argv[2]),
                             int(sys.argv[3]), sys.argv[4])
    mode = sys.argv[5] if len(sys.argv) > 5 else "plain"
    from znicz_tpu.parallel import distributed
    distributed.initialize(f"127.0.0.1:{port}", num_processes=nproc,
                           process_id=pid)
    assert jax.process_count() == nproc, jax.process_count()
    if mode in ("phase1", "phase2"):
        combined(out, mode)
        return

    from znicz_tpu.parallel import fused, mesh as mesh_lib
    from znicz_tpu.parallel.fused import LayerSpec, ModelSpec

    n, feats, classes = 64, 32, 5
    rng = np.random.default_rng(0)           # all processes draw the
    data = rng.standard_normal((n, feats)).astype(np.float32)  # same set
    labels = rng.integers(0, classes, n).astype(np.int32)
    w0 = (rng.standard_normal((feats, classes)) * 0.1).astype(np.float32)

    mesh = distributed.global_mesh()
    sl = distributed.process_shard(n)
    gx = distributed.shard_dataset(data[sl], mesh, n)
    gy = distributed.shard_dataset(labels[sl], mesh, n)

    spec = ModelSpec((LayerSpec(
        kind="fc", activation="linear", include_bias=True,
        hypers=(0.05, 0.0, 0.0, 0.9),
        hypers_bias=(0.05, 0.0, 0.0, 0.9)),), "softmax")
    repl = mesh_lib.replicated(mesh)
    put = lambda a: jax.device_put(a, repl)            # noqa: E731
    params = [(put(w0), put(np.zeros(classes, np.float32)))]
    vels = [(put(np.zeros_like(w0)),
             put(np.zeros(classes, np.float32)))]

    step = jax.jit(
        lambda p, v, x, t: fused.train_minibatch(spec, p, v, x, t)[:2])
    for _ in range(5):
        params, vels = step(params, vels, gx, gy)

    final = np.asarray(params[0][0])     # replicated → locally readable
    if pid == 0:
        np.save(out, final)
    jax.effects_barrier()


if __name__ == "__main__":
    main()
