#!/usr/bin/env python3
"""Readings for the limits of a cell's ``correct``, on the chip at the
cell's own size, in one process:

    python3 benchmark/tests/limits_study.py --workload <cell> --seeds 1 2 3

For each seed it follows three minibatches with the configuration's
plain reference, then again under each of that reference's ``VARIANTS``
(``lib/reference.py``: the control at float8 operands, the stated
precision at bfloat16 operands, and each planted fault), and prints every
number the check compares, each against the reference.  Rows, weights and
the output leaf come from the configuration's model file.  The program's
own readings (the lower ones) come from the
runs of ``benchmark/run.py``, whose result lines carry them."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None, base: str = ROOT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import numpy as np
    from benchmark import run as runner
    from benchmark.lib import correct
    bench = runner.load_json(os.path.join(base, "BENCHMARK.json"), "bench")
    found = runner.find_cell(bench, args.workload, base)
    cfg, traffic = found["config"], found["traffic"]
    model = runner.import_file(found["files"]["model"])
    reference = runner.import_file(found["files"]["reference"])
    b = int(traffic["minibatch"])
    shapes = model.param_shapes(cfg)
    output_leaf = model.output_leaf(cfg)
    lo = int(traffic["n_test"]) + int(traffic["n_valid"])
    for seed in args.seeds:
        rows = lo + np.random.default_rng(seed).permutation(
            int(traffic["n_train"]))[:3 * b]
        x, y = model.make_rows(seed, rows.astype(np.uint32), cfg, traffic)
        x = x.reshape(3, b, *x.shape[1:])
        y = y.reshape(3, b, *y.shape[1:])

        def follow(**kw):
            return reference.follow(cfg, model.make_weights(seed, shapes),
                                    x, y,
                                    seed=cfg["assumed"]["program_seed"], **kw)
        ref = follow()
        variants = {name: follow(**kw)
                    for name, kw in reference.VARIANTS.items()}
        print(json.dumps({"seed": seed, "variant": "reference", **ref}),
              flush=True)
        for name, other in variants.items():
            print(json.dumps({"seed": seed, "variant": name,
                              **correct.first_steps_numbers(other, ref,
                                                            output_leaf),
                              **{k: other[k] for k in ref}}), flush=True)


if __name__ == "__main__":
    main()
