"""Worker for the ElasticRunner end-to-end test: trains a softmax fc
model for --epochs epochs over a 2-device-per-process mesh,
checkpointing after every epoch and RESUMING from the newest checkpoint
on startup (the elastic contract).  Crash injection: process 1 exits 17
at the start of epoch 1 on the FIRST fleet round only (marker file).
"""

import argparse
import os
import sys

import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--coordinator", required=True)
    p.add_argument("--process-id", type=int, required=True)
    p.add_argument("--num-processes", type=int, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--crash-marker", default=None)
    args = p.parse_args()

    from znicz_tpu.parallel import FusedTrainer, distributed
    from znicz_tpu.parallel.fused import LayerSpec, ModelSpec
    distributed.initialize(args.coordinator,
                           num_processes=args.num_processes,
                           process_id=args.process_id)

    n, feats, classes = 64, 32, 5
    rng = np.random.default_rng(3)
    data = rng.standard_normal((n, feats)).astype(np.float32)
    labels = rng.integers(0, classes, n).astype(np.int32)
    w0 = (rng.standard_normal((feats, classes)) * 0.1).astype(np.float32)
    spec = ModelSpec((LayerSpec(
        kind="fc", activation="linear", include_bias=True,
        hypers=(0.05, 0.0, 0.0, 0.9),
        hypers_bias=(0.05, 0.0, 0.0, 0.9)),), "softmax")

    ckpt = args.out + ".ckpt.npz"
    if os.path.exists(ckpt):
        ck = np.load(ckpt)
        params = [(ck["w"], ck["b"])]
        vels = [(ck["vw"], ck["vb"])]
        start_epoch = int(ck["epoch"])
    else:
        params = [(w0, np.zeros(classes, np.float32))]
        vels = [(np.zeros_like(w0), np.zeros(classes, np.float32))]
        start_epoch = 0

    mesh = distributed.global_mesh()
    gx = distributed.shard_dataset(data[distributed.process_shard(n)],
                                   mesh, n)
    gy = distributed.shard_dataset(labels[distributed.process_shard(n)],
                                   mesh, n)
    tr = FusedTrainer(spec=spec, params=params, vels=vels, mesh=mesh)

    from jax.experimental import multihost_utils
    for epoch in range(start_epoch, args.epochs):
        if (args.crash_marker and args.process_id == 1 and epoch == 1
                and not os.path.exists(args.crash_marker)):
            with open(args.crash_marker, "w") as f:
                f.write("crashed at epoch 1\n")
            return 17                      # simulated worker loss
        tr.train_epoch(gx, gy, np.arange(n), 16, epoch=epoch)
        host_p = [(np.asarray(w), np.asarray(b)) for w, b in tr.params]
        host_v = [(np.asarray(w), np.asarray(b)) for w, b in tr.vels]
        if jax.process_index() == 0:
            tmp = ckpt + ".tmp.npz"
            np.savez(tmp, w=host_p[0][0], b=host_p[0][1],
                     vw=host_v[0][0], vb=host_v[0][1], epoch=epoch + 1)
            os.replace(tmp, ckpt)          # crash-safe single rename
        multihost_utils.sync_global_devices(f"ckpt-{epoch}")

    if jax.process_index() == 0:
        np.save(args.out, np.asarray(tr.params[0][0]))
    multihost_utils.sync_global_devices("done")
    jax.effects_barrier()
    return 0


if __name__ == "__main__":
    sys.exit(main())
