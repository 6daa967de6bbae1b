"""Worker for the SIGKILL-inside-the-torn-window crash test (run via
``subprocess`` from tests/test_durability.py).

Trains MNIST through the fused path with an every-epoch snapshotter.
The PARENT installs a fault plan through ``$ZNICZ_FAULT_PLAN`` that
injects latency at the ``checkpoint.write_torn`` site — i.e. the save
stalls with the blob already renamed into place but its manifest not
yet written (snapshotter.py's pinned invalidate→blob→manifest
ordering).  The parent detects that window on disk (blob present,
manifest absent) and SIGKILLs the process in it — the exact torn state
an unclean death can produce.  Resume (mode ``resume``) must then land
on the newest VERIFIED snapshot: the committed blob deep-parses, gets
its manifest healed, and training continues from it.

Usage: python _torn_save_worker.py WORKDIR train|resume
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> None:
    workdir, mode = sys.argv[1], sys.argv[2]
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
    if mode == "resume":
        found = SnapshotterToFile.restore(wf, directory=workdir)
        assert found is not None, "no verifiable snapshot to resume"
        meta, path = found
        print(f"resumed epoch_number={int(meta['epoch_number'])} "
              f"path={os.path.basename(path)}", flush=True)
    wf.train(fused=True, max_epochs=6)
    print(f"done last={wf.decision.epoch_metrics[-1]['epoch']}",
          flush=True)


if __name__ == "__main__":
    main()
