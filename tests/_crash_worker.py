"""Worker for the SIGKILL crash-recovery test (run via ``subprocess``
from tests/test_failure_recovery.py).

Trains MNIST through the fused path with an every-epoch snapshotter in
one continuous run; the parent watches the snapshot sidecar grow and
kills the process MID-TRAINING (the unclean death a slice failure or
preemption produces — no atexit, no finally blocks run).  SURVEY.md §5
failure detection/recovery row: restart-from-snapshot is the SPMD
replacement for the reference's master requeueing a lost slave's job.

Usage: python _crash_worker.py WORKDIR [RESUME_SNAPSHOT]
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> None:
    workdir = sys.argv[1]
    resume = sys.argv[2] if len(sys.argv) > 2 else None
    os.chdir(workdir)

    from znicz_tpu import prng
    from znicz_tpu.backends import Device
    from znicz_tpu.config import root
    from znicz_tpu.models.mnist import MnistWorkflow
    from znicz_tpu.snapshotter import SnapshotterToFile

    root.mnist.synthetic.update({"n_train": 4000, "n_valid": 200,
                                 "n_test": 0})
    root.mnist.minibatch_size = 50
    prng.seed_all(4242)
    wf = MnistWorkflow(snapshotter_config={"interval": 1,
                                           "directory": workdir})
    wf.initialize(device=Device.create("xla"))
    if resume:
        meta = SnapshotterToFile.load(wf, resume)
        print(f"resumed epoch_number={meta['epoch_number']}",
              flush=True)
    wf.train(fused=True, max_epochs=10)
    print(f"done epochs={len(wf.decision.epoch_metrics)} "
          f"last={wf.decision.epoch_metrics[-1]['epoch']}", flush=True)


if __name__ == "__main__":
    main()
