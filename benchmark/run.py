#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one ``StandardWorkflow.train(fused=True)`` call reached
through the program's ``Launcher``; the measured window is cut out of it
by ``lib/window.py``.  A cell is found by name in ``BENCHMARK.json``: its
configuration in ``configs/``, its traffic in ``traffic/``, its limits in
``limits/``, each per-layer metric's reader in ``metrics/``.  What a row
and a parameter tree are is the business of the ``model`` file the
configuration names, beside its ``workflow`` and its ``reference``.  The
last line of standard output is the result (see ``README.md``)."""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse                  # noqa: E402
import gc                        # noqa: E402
import importlib                 # noqa: E402
import importlib.util            # noqa: E402
import json                      # noqa: E402
import math                      # noqa: E402
import os                        # noqa: E402
import shutil                    # noqa: E402
import sys                       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.lib.errors import BenchError     # noqa: E402


#: what a configuration's ``model`` file offers (``README.md`` has the
#: types)
MODEL_OFFERS = ("row", "overrides", "make_rows", "param_shapes", "hypers",
                "make_weights", "install", "flops", "step_bytes",
                "output_leaf")


# -- finding things by name -------------------------------------------------
def load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"{what}: file {os.path.relpath(path, ROOT)} "
                         "is missing")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_module(path: str, what: str):
    """A module of the benchmark found by its file, named in a data file."""
    if not os.path.isfile(path):
        raise BenchError(f"{what}: file {os.path.relpath(path, ROOT)} "
                         "is missing")
    name = "bench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def import_file(path: str):
    """A file a configuration names (its model file, its reference),
    imported under its dotted name from the root of the checkout, so
    that it can import its neighbours."""
    return importlib.import_module(os.path.splitext(
        os.path.relpath(path, ROOT))[0].replace(os.sep, "."))


def find_cell(bench: dict, workload: str, base: str) -> dict:
    """The cell's entry, configuration, traffic, limits and metrics, with
    every file it names checked before anything heavy starts."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise BenchError(f"workload {workload!r} names configuration "
                         f"{cell['config']!r}, which BENCHMARK.json lacks")
    cfg_json = os.path.join(base, configs[cell["config"]]["file"])
    cfg_py = os.path.splitext(cfg_json)[0] + ".py"
    if not os.path.isfile(cfg_py):
        raise BenchError(f"configuration {cell['config']!r}: file "
                         f"{os.path.relpath(cfg_py, base)} is missing")
    bdir = os.path.join(base, bench["paths"][0])
    traffic = load_json(os.path.join(bdir, "traffic",
                                     cell["traffic"] + ".json"),
                        f"traffic {cell['traffic']!r}")
    limits = load_json(os.path.join(bdir, "limits", workload + ".json"),
                       f"limits of {workload!r}")

    def in_cell(m):
        return "workloads" not in m or workload in m["workloads"]
    readers = {}
    for m in bench["per_layer"]:
        if not in_cell(m):
            continue
        mod = load_module(os.path.join(bdir, "metrics", m["name"] + ".py"),
                          f"per-layer metric {m['name']!r}")
        readers[m["name"]] = (mod.read, m["unit"])
    config = load_json(cfg_json, "configuration")
    # the configuration names its workflow file, its plain reference and
    # its model file (what a row and a parameter tree are)
    files = {}
    for key in ("workflow", "reference", "model"):
        if key not in config:
            raise BenchError(f"configuration {cell['config']!r} names no "
                             f"{key} file")
        files[key] = os.path.join(ROOT, config[key])
        if not os.path.isfile(files[key]):
            raise BenchError(f"configuration {cell['config']!r}: its "
                             f"{key} file {config[key]} is missing")
    return {"cell": cell, "config": config, "files": files,
            "config_py": cfg_py, "traffic": traffic,
            "limits": limits["limits"], "readers": readers,
            "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)]}


# -- the trace ---------------------------------------------------------------
class Tracer:
    """``jax.profiler`` around the traced epochs: python tracer off, host
    tracer at its lowest, so the file is the device's story."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self) -> None:
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()


def reduce_trace(directory: str, probe_calls: list, batch: int,
                 keep: str | None) -> dict:
    from benchmark.lib import xplane
    path = xplane.find_xplane(directory)
    planes = xplane.load(path)
    if keep:
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, "trace_describe.txt"), "w") as fh:
            fh.write(xplane.describe(path))
        import gzip
        with gzip.open(os.path.join(keep, "trace_planes.json.gz"),
                       "wt") as fh:
            json.dump(planes, fh)
    out = xplane.reduce(planes)
    train = {k: v for k, v in out["modules"].items() if "train_epoch" in k}
    out["train_exec_s"] = sum(v["total_s"] for v in train.values()) \
        / max(out["planes"], 1)
    out["train_steps"] = sum(
        -(-len(c["indices"]) // batch) for c in probe_calls
        if c["kind"] == "train")
    shutil.rmtree(directory, ignore_errors=True)
    return out


# -- one run -------------------------------------------------------------------
def run_cell(args, bench: dict, base: str = ROOT,
             require_chip: bool = True) -> tuple[int, dict | None]:
    """Drive one cell.  ``require_chip=False`` (tests, rehearsals on the
    CPU) skips the look for a chip and reports no share of a peak."""
    found = find_cell(bench, args.workload, base)
    cell, cfg, traffic = found["cell"], found["config"], found["traffic"]
    chips = int(cell["chips"])
    if traffic.get("mesh") is None and chips != 1:
        raise BenchError(f"traffic {traffic['name']!r} has no mesh but "
                         f"the cell asks for {chips} chips")
    seed = int(args.seed)
    batch = int(traffic["minibatch"])
    model = import_file(found["files"]["model"])
    lacks = [name for name in MODEL_OFFERS if not hasattr(model, name)]
    if lacks:
        raise BenchError(f"model file {cfg['model']} offers no "
                         f"{', '.join(lacks)}")

    def of_model(name, *a):
        """A function of the model file; a count or a shape it cannot
        give (``ValueError``) is a fault of the configuration."""
        try:
            return getattr(model, name)(*a)
        except ValueError as e:
            raise BenchError(f"configuration {cell['config']!r}, "
                             f"{name} of {cfg['model']}: {e}") from e
    # what needs no device first: a configuration the model file cannot
    # count ends the run here, before the chip is taken
    shapes = of_model("param_shapes", cfg)
    counts = {"flops": of_model("flops", cfg, traffic),
              "step_bytes": of_model("step_bytes", cfg, traffic, batch)}
    output_leaf = of_model("output_leaf", cfg)
    program_overrides = of_model("overrides", cfg, traffic, seed)

    import jax
    import numpy as np
    devices = jax.devices()
    marks_s = {"chip_reached": time.monotonic() - T_START}
    platform, kind = devices[0].platform, devices[0].device_kind
    peaks = None
    if require_chip:
        if platform == "cpu" or len(devices) < chips:
            print(f"benchmark: JAX finds {len(devices)} {platform} "
                  f"device(s); {args.workload} needs {chips} chip(s)",
                  file=sys.stderr)
            return 3, None
        from benchmark.lib import peaks as peaks_lib
        peaks = peaks_lib.peaks_of(kind)

    compile_events: list = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_events.append((time.monotonic(), float(duration),
                                   kw.get("fun_name")))
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    from znicz_tpu.backends import Device
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.telemetry import flightrecorder
    from benchmark.lib import correct
    from benchmark.lib.probe import TrainerProbe
    from benchmark.lib.window import EpochClock

    # The program bakes its dropout stream's seed into the compiled step,
    # so a new Launcher seed is a new compile of every training program.
    # The Launcher keeps one seed; --seed makes the rows, the weights and
    # the shuffle, which are arguments of the programs.
    program_seed = int(cfg["assumed"]["program_seed"])
    sizes = {k: int(traffic[k]) for k in ("n_train", "n_valid", "n_test")}
    overrides = [*program_overrides, *traffic.get("overrides", []),
                 *args.override]
    launcher = Launcher(
        workflow=found["files"]["workflow"],
        config=found["config_py"], backend="xla", fused=True,
        seed=program_seed,
        overrides=overrides, mesh=traffic.get("mesh"),
        compile_cache_dir=os.path.join(base, ".cache", "xla"))
    module = launcher.build()
    wf = module.WORKFLOW()
    t0 = time.monotonic()
    wf.initialize(device=Device.create("xla"))
    dataset_s = time.monotonic() - t0
    marks_s["initialized"] = time.monotonic() - T_START

    # the benchmark's weights, from the seed, in place of the program's
    of_model("install", wf, of_model("make_weights", seed, shapes))

    probe = TrainerProbe(of_model("hypers", cfg))
    tracer = (Tracer(os.path.join(base, ".cache", "bench_trace",
                                  args.workload))
              if args.trace else None)
    marks = {}
    clock = EpochClock(
        wf, seconds=args.seconds, warm_epochs=int(traffic["warm_epochs"]),
        recorder=flightrecorder.RECORDER, tracer=tracer,
        traced_epochs=int(traffic.get("traced_epochs", 2)),
        on_trace_start=lambda: marks.setdefault("trace", probe.mark()),
        on_window_start=lambda: marks.setdefault("window", probe.mark()))
    wf.metrics_writer = clock
    marks_s["train_called"] = time.monotonic() - T_START
    with probe.installed():
        wf.train(fused=True)
    if clock.window_start is None or clock.window_epochs < 1:
        raise BenchError("the run ended before its window opened")

    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices[:chips])
    n_test, n_valid, n_train = (sizes["n_test"], sizes["n_valid"],
                                sizes["n_train"])
    window_calls = probe.calls[marks["window"]:]
    train_rows = sum(len(c["indices"]) for c in window_calls
                     if c["kind"] == "train")
    eval_rows = sum(len(c["indices"]) for c in window_calls
                    if c["kind"] == "eval")
    epoch_metrics = wf.decision.epoch_metrics[-clock.window_epochs:]
    steps = -(-n_train // batch)
    bad_epochs = sum(
        1 for m in epoch_metrics
        if not all(math.isfinite(m.get(k, 0.0))
                   for k in ("train_loss", "validation_loss")))
    # every call of the run, the warm-up's too: an epoch's rows are whole
    # only with its deferred tail, which the next epoch feeds
    numbers = {"rows_misfed": correct.rows_misfed(
        probe.calls, (n_test + n_valid, n_test + n_valid + n_train),
        (n_test, n_test + n_valid))}
    first = probe.first
    trace_calls = (probe.calls[marks["trace"]:marks["window"]]
                   if tracer is not None else [])

    # free the program's state before the reference takes the chip: the
    # peak has been read, and every number the check needs is on the host
    wf.metrics_writer = clock.workflow = None
    del wf, module, launcher
    for array in jax.live_arrays():
        array.delete()
    jax.clear_caches()          # cached programs may hold deleted constants
    gc.collect()
    in_use = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                 for d in devices[:chips])
    print(f"benchmark: {in_use / 1e9:.3f} GB still in use on the device "
          "as the reference starts", file=sys.stderr)

    window = {"t0": clock.ends[clock.window_start], "t1": clock.ends[-1],
              "seconds": clock.window_s, "epochs": clock.window_epochs,
              "epoch_walls_s": clock.epoch_walls_s(),
              "rows": clock.window_rows(),
              "train_rows": train_rows, "eval_rows": eval_rows}
    run = {"window": window, "compile_events": compile_events,
           "dataset_s": dataset_s, **counts, "peaks": peaks,
           "chips": chips, "batch": batch, "config": cfg,
           "traffic": traffic, "memory_peak_bytes": peak_bytes,
           "trace": None}
    if tracer is not None:
        run["trace"] = reduce_trace(tracer.directory, trace_calls, batch,
                                    args.keep_trace)

    # the plain reference over the first three minibatches
    t_ref = time.monotonic()
    reference_loss = None
    if first is not None:
        reference = import_file(found["files"]["reference"])
        rows = first["rows"]
        lo = n_test + n_valid
        if (len(set(rows.tolist())) != len(rows) or rows.min() < lo
                or rows.max() >= lo + n_train):
            numbers["rows_misfed"] += len(rows)
        inputs, targets = of_model("make_rows", seed,
                                   rows.astype(np.uint32), cfg, traffic)
        nb = first["batch"]
        ref = reference.follow(
            cfg, of_model("make_weights", seed, shapes),
            inputs.reshape(-1, nb, *inputs.shape[1:]),
            targets.reshape(-1, nb, *targets.shape[1:]),
            seed=program_seed, epoch=int(first["epoch"] or 0))
        numbers.update(correct.first_steps_numbers(first, ref,
                                                   output_leaf))
        print("\n".join(correct.leaf_table(first, ref)), file=sys.stderr)
        print("readings: " + json.dumps({
            side: {k: v for k, v in got.items() if k in ref
                   and not k.endswith("_sketches")}
            for side, got in (("program", first), ("reference", ref))}),
            file=sys.stderr)
        reference_loss = ref["losses"][0]
    ok, checks = correct.judge(numbers, found["limits"])
    reference_s = time.monotonic() - t_ref

    metrics = {}
    if args.trace:
        for name, (read, unit) in found["readers"].items():
            value = read(run)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": unit}
    else:
        values = {
            "train_images_per_s": n_train * window["epochs"]
            / window["seconds"],
            "setup_s": window["t0"] - T_START}
        for m in found["end_to_end"]:
            if m["name"] not in values:
                raise BenchError(f"end-to-end metric {m['name']!r} is "
                                 "not one benchmark/run.py measures")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": chips,
              "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": bool(ok and bad_epochs == 0),
              "attempted": steps * window["epochs"],
              "failed": steps * bad_epochs, "metrics": metrics,
              "device": device}
    if run["trace"] is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": run["trace"]["device_ops"],
            "idle_gaps": run["trace"]["idle_gaps"]}
        result["executables"] = [[k, v["total_s"], v["count"]] for k, v in
                                 run["trace"]["modules"].items()]
    result["window"] = {"seconds": window["seconds"],
                        "epochs": window["epochs"],
                        "row": model.row,
                        # for a person who looks for a slow epoch's or
                        # a slow set-up's cause
                        "setup_marks_s": marks_s,
                        "t0_unix": time.time() - time.monotonic()
                        + window["t0"],
                        "epoch_ms": [[round(1e3 * wall, 3),
                                      row.get("device_ms"),
                                      row.get("host_ms")]
                                     for wall, row in zip(
                                         window["epoch_walls_s"],
                                         window["rows"])],
                        "reference_s": reference_s,
                        "reference_loss": reference_loss,
                        "last_train_loss": epoch_metrics[-1].get(
                            "train_loss"),
                        "last_validation_loss": epoch_metrics[-1].get(
                            "validation_loss")}
    result["checks"] = checks
    return 0, result


def main(argv=None, base: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--override", action="append", default=[],
                    help="one more path=value for the program's config "
                         "tree (studies of the limits; never the driver)")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="write the trace's description and its device "
                         "planes as JSON into DIR")
    ap.add_argument("--rehearse", action="store_true",
                    help="run where there is no chip; reports no share "
                         "of a peak (never the driver)")
    args = ap.parse_args(argv)
    try:
        bench = load_json(os.path.join(base, "BENCHMARK.json"),
                          "BENCHMARK.json")
        rc, result = run_cell(args, bench, base=base,
                              require_chip=not args.rehearse)
    except (BenchError, KeyError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if result is None:
        return rc
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return rc


if __name__ == "__main__":
    sys.exit(main())
