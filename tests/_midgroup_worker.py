"""Worker for the mid-accumulation-group SIGKILL bit-exactness test
(tests/test_failure_recovery.py; VERDICT r2 item 6).

Trains a dropout net over .znr shards through ``run_fused`` (streamed
path, accum_steps=2, per-minibatch LR schedule).  In ``victim`` mode the
StreamTrainer's step callback SIGKILLs the process BETWEEN accumulation
micro-steps of a mid-run epoch — the sharpest unclean-death point: a
half-accumulated gradient group is in flight and must be cleanly
discarded by restart-from-snapshot.  The parent then compares ``resume``
against ``continuous``: PRNG streams (dropout masks + shuffle), the LR
schedule's minibatch counter, and the early-stop state must all resume
exactly for the final weights to be bit-identical.

Usage: python _midgroup_worker.py WORKDIR MODE [SNAPSHOT] OUT.npz
"""

import os
import signal
import sys

import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"


def build(workdir: str):
    from znicz_tpu import prng
    from znicz_tpu.backends import Device
    from znicz_tpu.config import root
    from znicz_tpu.loader.records import write_records
    from znicz_tpu.loader.streaming import RecordLoader
    from znicz_tpu.standard_workflow import StandardWorkflow

    root.common.accum_steps = 2
    rng = np.random.default_rng(12)
    data = rng.standard_normal((128, 5, 5, 1)).astype(np.float32)
    labels = rng.integers(0, 4, 128).astype(np.int32)
    tr = write_records(os.path.join(workdir, "tr.znr"), data[32:],
                       labels[32:])
    va = write_records(os.path.join(workdir, "va.znr"), data[:32],
                       labels[:32])
    prng.seed_all(777)
    wf = StandardWorkflow(
        None, "midgroup",
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 12},
                 "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
                {"type": "dropout", "->": {"dropout_ratio": 0.4}},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}}],
        loader=RecordLoader(None, train_paths=tr, validation_paths=va,
                            minibatch_size=16),
        decision_config={"max_epochs": 6, "fail_iterations": 4},
        snapshotter_config={"interval": 1, "directory": workdir},
        lr_adjuster_config={"policy": ("inv", {"gamma": 0.05,
                                               "power": 0.6}),
                            "by_epoch": False})
    wf.initialize(device=Device.create("xla"))
    return wf


def dump(wf, out: str) -> None:
    arrays = {f"w{i}": np.asarray(f.weights.mem)
              for i, f in enumerate(wf.forwards)
              if getattr(f, "weights", None)}
    arrays["losses"] = np.asarray(
        [m["train_loss"] for m in wf.decision.epoch_metrics])
    np.savez(out, **arrays)


def main() -> None:
    workdir, mode = sys.argv[1], sys.argv[2]
    wf = build(workdir)
    if mode == "continuous":
        wf.run_fused()
        dump(wf, sys.argv[3])
    elif mode == "victim":
        def kill_between_microsteps(epoch, step_i):
            # 6 steps/epoch, accum 2 → killing after step 2 leaves
            # group (2,3) half-accumulated, mid-epoch 2
            if epoch == 2 and step_i == 2:
                os.kill(os.getpid(), signal.SIGKILL)
        wf.run_fused(step_callback=kill_between_microsteps)
        raise AssertionError("victim survived the kill point")
    elif mode == "resume":
        from znicz_tpu.snapshotter import SnapshotterToFile
        meta = SnapshotterToFile.load(wf, sys.argv[3])
        print(f"resumed epoch_number={meta['epoch_number']}", flush=True)
        wf.run_fused()
        dump(wf, sys.argv[4])
    else:
        raise SystemExit(f"unknown mode {mode}")


if __name__ == "__main__":
    main()
