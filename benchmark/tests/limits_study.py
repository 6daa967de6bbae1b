#!/usr/bin/env python3
"""Readings for the limits of a cell's ``correct``, on the chip at the
cell's own size, in one process:

    python3 benchmark/tests/limits_study.py --workload <cell> --seeds 1 2 3

For each seed it follows three minibatches with the plain reference
(float32, ``highest``), then again as the control (float8 operands), at
the configuration's stated precision (bfloat16 operands) and with each
planted fault, and prints every number the check compares, each against
the reference.  The program's own readings (the lower ones) come from the
runs of ``benchmark/run.py``, whose result lines carry them."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import numpy as np
    from benchmark import run as runner
    from benchmark.lib import correct, data, reference
    bench = runner.load_json(os.path.join(ROOT, "BENCHMARK.json"), "bench")
    found = runner.find_cell(bench, args.workload, ROOT)
    cfg, traffic = found["config"], found["traffic"]
    b = int(traffic["minibatch"])
    shape = (cfg["input_size"], cfg["input_size"], cfg["input_channels"])
    shapes = data.param_shapes(cfg["layers"], *shape[1:])
    lo = int(traffic["n_test"]) + int(traffic["n_valid"])
    for seed in args.seeds:
        rows = lo + np.random.default_rng(seed).permutation(
            int(traffic["n_train"]))[:3 * b]
        x, y = data.make_rows(seed, rows.astype(np.uint32), *shape[1:],
                              cfg["n_classes"],
                              float(cfg["assumed"]["noise"]))
        x, y = x.reshape(3, b, *shape), y.reshape(3, b)

        def follow(**kw):
            return reference.follow(cfg["layers"],
                                    data.make_weights(seed, shapes), x, y,
                                    seed=cfg["assumed"]["program_seed"], **kw)
        ref = follow()
        variants = {
            "control_fp8": follow(operand=reference.fp8_operand),
            "stated_bf16": follow(operand=reference.bf16_operand),
            "fault_half_batch": follow(half_batch=True),
            "fault_frozen": follow(frozen=True)}
        print(json.dumps({"seed": seed, "variant": "reference", **ref}),
              flush=True)
        for name, other in variants.items():
            print(json.dumps({"seed": seed, "variant": name,
                              **correct.first_steps_numbers(other, ref),
                              **{k: other[k] for k in ref}}), flush=True)


if __name__ == "__main__":
    main()
