#!/usr/bin/env python3
"""Compile the fused trainer's whole training and evaluation programs of
a token-sequence configuration of the benchmark (its model file is the one
the configuration names) for a DESCRIBED v5e (no chip:
``on-chip-measurement`` guide, section 2) and print each program's
``memory_analysis``, as gigabytes and as the ``plan`` the trainer's
register of executables keeps (``plan_temp_bytes``: the figure a
``train_step`` row's ``plan_temp_bytes_max`` and the benchmark's
``step_plan_temp_gb`` read on the chip).  Nothing runs; this says what
the TPU's compiler accepts and how many bytes the program needs beside
its arguments.

    JAX_PLATFORMS=cpu python3 tools/compile_decoder_step.py \\
        [--config benchmark/configs/mellum2-12b-a2.5b.json] \\
        [--traffic benchmark/traffic/resident-s8192-b1.json] [--steps 1]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmark/configs/mellum2-12b-a2.5b.json"))
    ap.add_argument("--traffic", default=os.path.join(
        ROOT, "benchmark/traffic/resident-s8192-b1.json"))
    ap.add_argument("--text-dir", default=None, metavar="DIR",
                    help="write each program's compiled text there (its "
                         "instructions carry the layer scopes a device "
                         "trace's events lack)")
    ap.add_argument("--lower-only", action="store_true",
                    help="stop before the compile and print a hash of each "
                         "program's lowered text (two processes that print "
                         "the same hash share a compile-cache entry)")
    ap.add_argument("--steps", type=int, default=None,
                    help="minibatches of the training program (default: "
                         "an epoch's head call; 1: the program the "
                         "benchmark's probe follows three steps with)")
    ap.add_argument("--crowded", action="store_true",
                    help="the programs of a trainer whose state crowds "
                         "the device (fused.state_crowds_device): give it "
                         "--steps 1 too, it runs one minibatch a launch")
    ap.add_argument("--reference", action="store_true",
                    help="compile the plain reference's training step "
                         "instead (benchmark/lib/decoder_reference.py)")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import importlib
    from znicz_tpu.nn import decoder
    from znicz_tpu.ops import tuning
    from znicz_tpu.parallel import fused
    from znicz_tpu.telemetry import programs

    with open(args.config) as fh:
        cfg = json.load(fh)
    with open(args.traffic) as fh:
        traffic = json.load(fh)
    model = importlib.import_module(
        os.path.splitext(cfg["model"])[0].replace("/", "."))
    tuning.on_tpu = lambda: True        # the dispatch a TPU process takes
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    layers, listed = [], model.layer_list(cfg)
    for la, unit in zip(listed, decoder.units_of(listed)):
        h = la["<-"]
        layers.append(fused.sequence_layer(unit, (
            h["learning_rate"], h["weights_decay"], 0.0,
            h["gradient_moment"])))
    spec = fused.ModelSpec(tuple(layers), "softmax",
                           compute_dtype=cfg["precision"]["matmul_operands"],
                           fresh_backward=args.crowded)

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)
    params = [tuple(shaped(sh) for sh in leaves)
              for leaves in model.param_shapes(cfg)]
    n = sum(int(traffic[k]) for k in ("n_train", "n_valid", "n_test"))
    t, b = int(traffic["seq_len"]), int(traffic["minibatch"])
    if args.reference:
        from benchmark.lib import decoder_reference
        tables = jax.tree.map(
            lambda a: shaped(a.shape), decoder_reference.rotary_tables(cfg, t))
        compiled = decoder_reference.make_step(cfg).lower(
            params, params, shaped((b, t), jnp.int32),
            shaped((b, t), jnp.int32), tables).compile()
        m = compiled.memory_analysis()
        print(json.dumps({
            "program": "reference step",
            "plan": programs.plan_of(compiled),
            "argument_gb": m.argument_size_in_bytes / 1e9,
            "temp_gb": m.temp_size_in_bytes / 1e9,
            "arguments_plus_temporaries_gb": (
                m.argument_size_in_bytes + m.temp_size_in_bytes) / 1e9}),
            flush=True)
        return 0
    rows = shaped((n, t), jnp.int32)
    trainer = fused.FusedTrainer.__new__(fused.FusedTrainer)
    trainer.spec, trainer.mesh, trainer.accum_steps = spec, None, 1
    trainer.augment = trainer._batch_sharding = None
    trainer.crowded, trainer.params = args.crowded, params
    trainer._build()
    steps = args.steps or (int(traffic["n_train"]) - 1) // b   # the head
    for name, fn, call in (
            ("train_epoch", trainer._train_epoch_fn.fn, (
                params, params, rows, rows, shaped((steps, b), jnp.int32),
                shaped((steps, b)), shaped((steps,), jnp.uint32),
                shaped((), jnp.uint32), shaped((steps,)),
                shaped((steps,)))),
            ("eval_epoch", trainer._eval_epoch_fn.fn, (
                params, rows, rows,
                shaped((int(traffic["n_valid"]) // b, b), jnp.int32),
                shaped((int(traffic["n_valid"]) // b, b))))):
        t0 = time.monotonic()
        lowered = fn.lower(*call)
        t1 = time.monotonic()
        if args.lower_only:
            import hashlib
            text = lowered.as_text()
            if args.text_dir:
                os.makedirs(args.text_dir, exist_ok=True)
                with open(os.path.join(args.text_dir,
                                       name + ".stablehlo.txt"), "w") as fh:
                    fh.write(text)
            print(json.dumps({"program": name, "trace_and_lower_s": t1 - t0,
                              "stablehlo_sha256": hashlib.sha256(
                                  text.encode()).hexdigest()}), flush=True)
            continue
        compiled = lowered.compile()
        t2 = time.monotonic()
        if args.text_dir:
            os.makedirs(args.text_dir, exist_ok=True)
            with open(os.path.join(args.text_dir, name + ".hlo.txt"),
                      "w") as fh:
                fh.write(compiled.as_text())
        m = compiled.memory_analysis()
        # the plan under the names the trainer's register gives it
        # (telemetry/programs.py): `plan_temp_bytes` is the word in a
        # `trainer.dispatch` span and, as its epoch's largest, in a
        # `train_step` row (`plan_temp_bytes_max`)
        plan = programs.plan_of(compiled)
        print(json.dumps({
            "program": name,
            "role": ("eval" if name == "eval_epoch" else
                     "train.step" if steps == 1 else "train.head"),
            "plan": plan, "plan_temp_bytes": plan.get("temp"),
            "argument_gb": m.argument_size_in_bytes / 1e9,
            "output_gb": m.output_size_in_bytes / 1e9,
            "alias_gb": m.alias_size_in_bytes / 1e9,
            "temp_gb": m.temp_size_in_bytes / 1e9,
            "arguments_plus_temporaries_gb": (
                m.argument_size_in_bytes + m.temp_size_in_bytes) / 1e9,
            "kernels": compiled.as_text().count("tpu_custom_call"),
            "trace_and_lower_s": t1 - t0, "compile_s": t2 - t1}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
