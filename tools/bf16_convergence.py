"""Full-AlexNet-geometry bf16-storage convergence evidence (VERDICT r3
item 7): train the real 227×227×3 8-layer AlexNet (conv/LRN-pool pairs/
dropout/fc, ~61M params) on the seeded synthetic ImageNet stand-in under
``storage_dtype='bfloat16'`` AND under f32.

OVERWRITES ``docs/bf16_convergence.json`` with one aggregate record
(epoch losses + validation error for both dtypes, convergence flags),
so the decision to default bf16 storage can cite tracked-vs-f32 numbers
at the real geometry, not the small-conv test model.  Per-run JSON
lines also stream to stdout.

Device: pinned to CPU by default (``JAX_PLATFORMS=cpu``); pass
``--tpu`` to leave the platform to JAX.

Usage: python tools/bf16_convergence.py [--epochs N] [--n-train N]
           [--minibatch N] [--tpu]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if "--tpu" not in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax                                              # noqa: E402
import numpy as np                                      # noqa: E402


def config_meta(config, n_train):
    """(n_classes, geometry label) — WITHOUT building a workflow (a
    throwaway AlexNet construction is real money on the 1-core host)."""
    if config == "alexnet":
        return 16, "AlexNet 227x227x3, 8 layers, n_classes=16"
    return 10, f"MNIST MLP sample, synthetic n_train={n_train}"


def build_workflow(config, n_train, minibatch):
    from znicz_tpu import prng
    from znicz_tpu.config import root
    prng.seed_all(4242)                    # identical init + data draws
    if config == "alexnet":
        from znicz_tpu.models import alexnet
        # n_classes must land in the config tree: the layer head is
        # built from root.alexnet, not the ctor kwarg
        root.alexnet.update({"minibatch_size": minibatch,
                             "n_classes": 16})
        root.alexnet.synthetic.update(
            {"n_train": n_train,
             "n_valid": max(minibatch, n_train // 8), "n_test": 0})
        return alexnet.AlexNetWorkflow(n_classes=16)
    # mnist: the LEARNING-evidence config (ADVICE r4 / VERDICT r4 item
    # 4) — the real AlexNet geometry cannot beat chance in CPU-budget
    # epochs (4 epochs × 96 samples left valid_err at exactly 15/16);
    # the MNIST sample reaches <5% err in 3 epochs in the test suite
    # (tests/test_mnist_functional.py), so the SAME run under bf16
    # storage is honest beats-chance evidence, not just tracking
    from znicz_tpu.models import mnist
    # minibatch_size must land in the tree or the run silently uses
    # the config default while the evidence JSON claims args.minibatch
    root.mnist.update({"minibatch_size": minibatch})
    root.mnist.synthetic.update({"n_train": n_train, "n_valid": 200,
                                 "n_test": 200, "noise": 0.35})
    return mnist.MnistWorkflow()


def run_one(config, storage, epochs, n_train, minibatch):
    from znicz_tpu.backends import Device

    wf = build_workflow(config, n_train, minibatch)
    wf.decision.max_epochs = epochs
    wf.initialize(device=Device.create("auto"))
    t0 = time.time()
    wf.run_fused(storage_dtype=storage)
    ms = wf.decision.epoch_metrics
    return {
        "storage": storage or "float32",
        "epochs": len(ms),
        "train_loss": [round(float(m["train_loss"]), 5) for m in ms],
        "valid_err_pct": [
            round(float(m["validation_err_pct"]), 2)
            if "validation_err_pct" in m else None for m in ms],
        "wall_s": round(time.time() - t0, 1),
        "weights_f32": bool(
            wf.forwards[0].weights.mem.dtype == np.float32),
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="alexnet",
                   choices=("alexnet", "mnist"))
    p.add_argument("--epochs", type=int, default=6)
    p.add_argument("--n-train", type=int, default=256)
    p.add_argument("--minibatch", type=int, default=32)
    p.add_argument("--tpu", action="store_true",
                   help="leave the JAX platform unpinned (consumed "
                        "before argparse; listed for --help)")
    args = p.parse_args()

    n_classes, geometry = config_meta(args.config, args.n_train)
    out = {"geometry": geometry, "n_train": args.n_train,
           "minibatch": args.minibatch,
           "device": str(jax.devices()[0])}
    for storage in (None, "bfloat16"):
        r = run_one(args.config, storage, args.epochs, args.n_train,
                    args.minibatch)
        out[r["storage"]] = r
        print(json.dumps(r), flush=True)

    f32, bf16 = out["float32"], out["bfloat16"]
    out["final_loss_ratio"] = round(
        bf16["train_loss"][-1] / f32["train_loss"][-1], 4)
    # two SEPARATE claims (ADVICE r4: the old "both_converged" flag
    # conflated them): (a) the bf16 loss trajectory tracks f32 — true
    # whenever the ratios stay near 1 even if nothing was learned;
    # (b) each run actually LEARNED — validation error meaningfully
    # below chance for the class count (0.8× chance), which loss
    # deltas alone cannot show
    # relative match with an absolute floor: late epochs can round to
    # 0.0 (the MNIST run hits 7.8e-4 by epoch 5), and a trajectory
    # already at ~zero loss in both dtypes matches by any standard
    out["loss_trajectories_match"] = all(
        abs(b - a) <= 0.05 * max(abs(a), 1e-6)
        for a, b in zip(f32["train_loss"], bf16["train_loss"]))
    chance = 100.0 * (1.0 - 1.0 / n_classes)
    out["chance_err_pct"] = round(chance, 2)
    out["beats_chance"] = {
        k: (out[k]["valid_err_pct"][-1] is not None
            and out[k]["valid_err_pct"][-1] < 0.8 * chance)
        for k in ("float32", "bfloat16")}
    name = ("bf16_convergence.json" if args.config == "alexnet"
            else f"bf16_convergence_{args.config}.json")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "docs", name)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"final_loss_ratio": out["final_loss_ratio"],
                      "loss_trajectories_match":
                          out["loss_trajectories_match"],
                      "beats_chance": out["beats_chance"]}))


if __name__ == "__main__":
    main()
