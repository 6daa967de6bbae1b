#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Drives the main path once, through the entry points a user would call,
at the full width of the shipped AlexNet (227×227×3, widths
96/256/384/384/256/4096/4096, 1000 classes, minibatch 128, synthetic
``n_train=512``, weights from the seed):

1. **train** — ``Launcher`` (the class behind ``python -m znicz_tpu``)
   on ``znicz_tpu.models.alexnet`` with ``--fused`` for two epochs: 8
   train minibatches plus the evaluation passes.  Finite losses, the
   timeline rows stating ``platform: tpu`` and ``kernel_tier: pallas``,
   then ``export_workflow`` of the trained workflow.
2. **serve** — ``python -m znicz_tpu serve --model … --warmup-shape
   227,227,3`` answers ``/predict`` on the binary wire.  Rows are
   softmax distributions that agree with the C++ CPU engine
   (``export.NativeEngine``) on the same ``.znn`` within
   :data:`SERVE_RTOL` / :data:`SERVE_ATOL` and
   :data:`SERVE_SPREAD_FRACTION`; ``/healthz`` reports
   ``backend: jax`` / ``platform: tpu``; ``/metrics`` shows zero
   native-fallback predictions and a closed breaker.

One process holds the chip at a time: this parent imports nothing but
the standard library, and each phase is a child that exits (and so
frees the chip) before the next starts.  The client that talks to the
serve child runs with ``JAX_PLATFORMS=cpu``.

Exit code 0 and, as the last line of stdout,
``{"ok": true, "device": {"platform": "tpu", "kind": …, "count": …}}``
only when every phase passed on a TPU.  Anything else — no accelerator,
a failed phase, a directory without the rest of the repo — exits
non-zero and prints no result line.

Options (all for the builder; the driver passes none):
``--mesh DP[,TP]`` and ``--minibatch N`` lay the train phase out over a
device mesh (e.g. ``--mesh 4 --minibatch 512`` on a four-chip host) and
add per-device residency to its report; ``--out DIR`` moves the work
directory (default ``chiprun_out/chip_smoke``, ignored by git).
"""

import argparse
import json
import math
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

#: serve cross-check tolerance on the softmax rows, TPU vs the f32 C++
#: engine.  XLA runs the f32 convs and dots as single bf16 MXU passes
#: (8 mantissa bits, f32 accumulation) through 8 parameter layers; the
#: C++ engine multiplies in f32.  Measured on a v5e at PR 21: largest
#: relative deviation 4.9e-4, largest absolute 5.0e-7, 0.3% of the
#: reference row's spread.
SERVE_RTOL = 1e-2
SERVE_ATOL = 1e-6
#: after 7 updates the rows are still close to uniform (1e-3 per
#: class), which alone would let a server that answers the uniform
#: distribution pass an rtol check — so the deviation must also stay
#: within this fraction of the reference row's own spread across
#: classes (max − min)
SERVE_SPREAD_FRACTION = 0.05

#: request batch sizes of the serve phase: one row, a padded bucket, a
#: full bucket
REQUEST_ROWS = (1, 4, 8)

TRAIN_TIMEOUT_S = 900
SERVE_BOOT_TIMEOUT_S = 420
CLIENT_TIMEOUT_S = 300


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


# -- children ---------------------------------------------------------------
def _import_package():
    """Import the ``znicz_tpu`` that sits beside this script — never
    one found elsewhere on the path."""
    sys.path.insert(0, ROOT)
    import znicz_tpu
    here = os.path.dirname(os.path.abspath(znicz_tpu.__file__))
    if os.path.dirname(here) != ROOT:
        raise ImportError(f"znicz_tpu resolved to {here}, not to the "
                          f"checkout of {ROOT}")


def _versions() -> dict:
    from importlib import metadata
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def _write_report(path: str, report: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def phase_train(args) -> int:
    """Child: holds the chip for the train phase; writes train.json."""
    _import_package()
    from znicz_tpu.backends import device_report
    report = {"phase": "train", "ok": False, "versions": _versions(),
              "device": device_report()}
    report_path = os.path.join(args.out, "train.json")
    if report["device"]["platform"] != "tpu":
        report["error"] = ("no accelerator: JAX reports platform "
                           f"{report['device']['platform']!r}")
        _write_report(report_path, report)
        log(report["error"])
        return 3
    import numpy as np

    from znicz_tpu.export import export_workflow
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.telemetry import compilestats

    timeline = os.path.join(args.out, "timeline.jsonl")
    if os.path.exists(timeline):
        os.unlink(timeline)
    launcher = Launcher(
        "znicz_tpu.models.alexnet", fused=True, epochs=2,
        timeline_jsonl=timeline, mesh=args.mesh,
        overrides=[f"alexnet.minibatch_size={args.minibatch}"])
    t0 = time.monotonic()
    wf = launcher.run()
    report["train_wall_s"] = round(time.monotonic() - t0, 1)
    with open(timeline) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    metrics = wf.decision.epoch_metrics
    report["epoch_metrics"] = metrics
    report["timeline"] = rows
    losses = [m[k] for m in metrics for k in m if k.endswith("_loss")]
    compile_cost = compilestats.snapshot()["compile_cost"]
    report["compile_train_fused"] = compile_cost.get("train.fused")
    n_train = wf.loader.class_lengths[2]
    problems = []
    if len(metrics) != 2 or len(rows) != 2:
        problems.append(f"expected 2 epochs, got {len(metrics)} metric "
                        f"rows and {len(rows)} timeline rows")
    steps = sum(r.get("steps", 0) for r in rows)
    report["train_minibatches"] = steps
    if steps != 2 * math.ceil(n_train / args.minibatch):
        problems.append(f"{steps} train minibatches over 2 epochs of "
                        f"{n_train} samples at batch {args.minibatch}")
    if not losses or not all(math.isfinite(v) for v in losses):
        problems.append(f"non-finite loss in {losses}")
    for r in rows:
        if r.get("platform") != "tpu":
            problems.append(f"timeline row states platform "
                            f"{r.get('platform')!r}")
        if r.get("kernel_tier") != "pallas":
            problems.append(f"timeline row states kernel_tier "
                            f"{r.get('kernel_tier')!r}")
    if args.mesh:
        import jax
        want = math.prod(int(d) for d in args.mesh.split(","))
        report["memory_per_device"] = {
            str(d.id): {k: v for k, v in (d.memory_stats() or {}).items()
                        if k in ("peak_bytes_in_use", "bytes_in_use")}
            for d in jax.devices()}
        held = [d for d, m in report["memory_per_device"].items()
                if m.get("peak_bytes_in_use", 0) > 0]
        for r in rows:
            if r.get("param_devices") != want:
                problems.append(f"parameters on {r.get('param_devices')} "
                                f"device(s), mesh {args.mesh} wants "
                                f"{want}")
        if len(held) < want:
            problems.append(f"only devices {held} held memory, mesh "
                            f"{args.mesh} wants {want}")
    model = os.path.join(args.out, "alexnet.znn")
    export_workflow(wf, model)
    report["model"] = model
    report["final_train_loss"] = metrics[-1]["train_loss"] if metrics \
        else None
    widths = [int(np.shape(f.weights.mem)[-1]) for f in wf.forwards
              if getattr(f, "weights", None)]
    report["widths"] = widths
    if widths != [96, 256, 384, 384, 256, 4096, 4096, 1000]:
        problems.append(f"not the full-width AlexNet: {widths}")
    report["problems"] = problems
    report["ok"] = not problems
    _write_report(report_path, report)
    for p in problems:
        log(f"train: {p}")
    return 0 if report["ok"] else 1


def phase_client(args) -> int:
    """Child (``JAX_PLATFORMS=cpu``): drives the serve child over HTTP
    and checks its answers against the C++ engine; writes serve.json."""
    _import_package()
    import numpy as np

    from znicz_tpu.export import NativeEngine
    from znicz_tpu.serving import wire

    def get(path):
        with urllib.request.urlopen(args.url + path, timeout=30) as r:
            return json.loads(r.read())

    report = {"phase": "serve", "ok": False, "health": get("healthz"),
              "rtol": SERVE_RTOL, "atol": SERVE_ATOL,
              "spread_fraction": SERVE_SPREAD_FRACTION}
    report_path = os.path.join(args.out, "serve.json")
    model = os.path.join(args.out, "alexnet.znn")
    native = NativeEngine().load(model)
    rng = np.random.default_rng(1234)
    problems = []
    max_abs = max_rel = max_of_spread = 0.0
    answered = 0
    for rows in REQUEST_ROWS:
        x = rng.standard_normal((rows, 227, 227, 3)).astype(np.float32)
        req = urllib.request.Request(
            args.url + "predict", wire.encode_tensor(x),
            {"Content-Type": wire.CONTENT_TYPE,
             "Accept": wire.CONTENT_TYPE})
        with urllib.request.urlopen(req, timeout=120) as r:
            if r.status != 200:
                problems.append(f"/predict answered {r.status}")
                continue
            got = np.asarray(wire.decode_tensor(r.read()), np.float32)
        answered += 1
        if got.shape != (rows, 1000) or not np.isfinite(got).all():
            problems.append(f"{rows} rows: bad answer shape "
                            f"{got.shape} or non-finite values")
            continue
        if (got < 0).any() or not np.allclose(got.sum(axis=1), 1.0,
                                              atol=1e-3):
            problems.append(f"{rows} rows: not softmax distributions "
                            f"(row sums {got.sum(axis=1)})")
        want = native.infer(x, 1000)
        max_abs = max(max_abs, float(np.max(np.abs(got - want))))
        max_rel = max(max_rel, float(np.max(
            np.abs(got - want) / np.maximum(np.abs(want), SERVE_ATOL))))
        of_spread = float(np.max(np.max(np.abs(got - want), axis=1)
                                 / np.ptp(want, axis=1)))
        max_of_spread = max(max_of_spread, of_spread)
        if not np.allclose(got, want, rtol=SERVE_RTOL, atol=SERVE_ATOL) \
                or of_spread > SERVE_SPREAD_FRACTION:
            problems.append(
                f"{rows} rows: answers deviate from the C++ engine "
                f"beyond rtol={SERVE_RTOL} atol={SERVE_ATOL} or by "
                f"{of_spread:.3g} of the reference row's spread "
                f"(limit {SERVE_SPREAD_FRACTION})")
    report["requests_answered"] = answered
    report["max_abs_dev"] = max_abs
    report["max_rel_dev"] = max_rel
    report["max_dev_over_row_spread"] = max_of_spread
    health = report["health"]
    engine = get("metrics").get("engine") or {}
    report["engine"] = {k: engine.get(k) for k in (
        "backend", "forward_calls", "forward_failures", "fallback_calls",
        "retries", "resilience_state", "breaker", "device_ms_total")}
    if health.get("backend") != "jax":
        problems.append(f"healthz backend {health.get('backend')!r}")
    if health.get("platform") != "tpu":
        problems.append(f"healthz platform {health.get('platform')!r}")
    if engine.get("fallback_calls") != 0:
        problems.append(f"{engine.get('fallback_calls')} native-fallback "
                        f"prediction(s)")
    if (engine.get("breaker") or {}).get("state") != "closed":
        problems.append(f"breaker {engine.get('breaker')}")
    report["problems"] = problems
    report["ok"] = not problems
    _write_report(report_path, report)
    for p in problems:
        log(f"serve: {p}")
    return 0 if report["ok"] else 1


# -- parent -----------------------------------------------------------------
def _require_no_jax() -> None:
    """A parent that has touched JAX may hold the chip its children
    need; this one never imports it."""
    if "jax" in sys.modules:
        raise RuntimeError("chip_smoke's parent imported jax")


def _child(phase: str, args, extra_env=None, extra=(), timeout=None) -> int:
    """Run one phase of this script as a child; its output goes to
    ``<out>/<phase>.log``."""
    _require_no_jax()
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--out", args.out, "--minibatch", str(args.minibatch), *extra]
    if args.mesh:
        cmd += ["--mesh", args.mesh]
    with open(os.path.join(args.out, phase + ".log"), "w") as logf:
        proc = subprocess.Popen(cmd, stdout=logf, stderr=subprocess.STDOUT,
                                cwd=ROOT,
                                env={**os.environ, **(extra_env or {})})
        try:
            return proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            log(f"{phase} phase killed after {timeout}s")
            return 124


def _tail(path: str, n: int = 25) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def _read(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_serve_phase(args) -> bool:
    """Boot the real serve CLI (it holds the chip), run the client
    child against it, then SIGTERM it and require a clean exit."""
    _require_no_jax()
    port = _free_port()
    url = f"http://127.0.0.1:{port}/"
    serve_log = os.path.join(args.out, "serve_process.log")
    with open(serve_log, "w") as logf:
        serve = subprocess.Popen(
            [sys.executable, "-m", "znicz_tpu", "serve",
             "--model", os.path.join(args.out, "alexnet.znn"),
             "--warmup-shape", "227,227,3", "--buckets", "1,8",
             "--max-batch", "8", "--port", str(port)],
            stdout=logf, stderr=subprocess.STDOUT, cwd=ROOT)
    ok = False
    try:
        deadline = time.monotonic() + SERVE_BOOT_TIMEOUT_S
        up = False
        while time.monotonic() < deadline and serve.poll() is None:
            try:
                with urllib.request.urlopen(url + "healthz", timeout=2):
                    up = True
                    break
            except OSError:
                time.sleep(1.0)
        if not up:
            log(f"serve never answered /healthz (rc={serve.poll()}):\n"
                + _tail(serve_log))
            return False
        rc = _child("client", args, extra_env={"JAX_PLATFORMS": "cpu"},
                    extra=("--url", url), timeout=CLIENT_TIMEOUT_S)
        ok = rc == 0
        if not ok:
            log(f"client phase failed (rc={rc}):\n"
                + _tail(os.path.join(args.out, "client.log")))
    finally:
        if serve.poll() is None:
            serve.send_signal(signal.SIGTERM)
            try:
                serve.wait(timeout=60)
            except subprocess.TimeoutExpired:
                serve.kill()
                serve.wait()
                log("serve ignored SIGTERM for 60 s; killed")
                ok = False
        if serve.returncode != 0:
            log(f"serve exited rc={serve.returncode}:\n"
                + _tail(serve_log))
            ok = False
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--phase", choices=("train", "client"), default=None,
                   help="internal: run one phase as a child")
    p.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "chip_smoke"))
    p.add_argument("--url", default=None, help="internal: client phase")
    p.add_argument("--mesh", default=None, metavar="DP[,TP]")
    p.add_argument("--minibatch", type=int, default=128)
    args = p.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    if args.phase == "train":
        return phase_train(args)
    if args.phase == "client":
        return phase_client(args)

    t0 = time.monotonic()
    for name in ("train.json", "serve.json"):
        if os.path.exists(os.path.join(args.out, name)):
            os.unlink(os.path.join(args.out, name))
    log("train phase: AlexNet, 2 epochs, through Launcher")
    rc = _child("train", args, timeout=TRAIN_TIMEOUT_S)
    train = _read(os.path.join(args.out, "train.json"))
    if train.get("versions"):
        print("versions: " + json.dumps(train["versions"], sort_keys=True),
              flush=True)
    if rc != 0 or not train.get("ok"):
        log(f"train phase failed (rc={rc}): "
            f"{train.get('error') or train.get('problems') or ''}\n"
            + _tail(os.path.join(args.out, "train.log")))
        return 1
    device = train["device"]
    log(f"train ok on {device}: {train['train_minibatches']} minibatches, "
        f"final train loss {train['final_train_loss']:.4f}, train.fused "
        f"first calls {train['compile_train_fused']}")
    print("train: " + json.dumps(
        {"device": device, "minibatches": train["train_minibatches"],
         "final_train_loss": train["final_train_loss"],
         "kernel_tier": train["timeline"][-1]["kernel_tier"],
         "mesh": train["timeline"][-1]["mesh"],
         "compile_train_fused": train["compile_train_fused"],
         "wall_s": train["train_wall_s"]}, sort_keys=True), flush=True)
    log("serve phase: python -m znicz_tpu serve, binary /predict")
    if not run_serve_phase(args):
        return 1
    serve = _read(os.path.join(args.out, "serve.json"))
    health = serve.get("health") or {}
    served_on = {k: health.get(k) for k in
                 ("platform", "device_kind", "device_count")}
    if served_on != device:
        log(f"serve ran on {served_on}, train on {device}")
        return 1
    print("serve: " + json.dumps(
        {"device": served_on, "backend": health.get("backend"),
         "requests": serve["requests_answered"],
         "max_abs_dev": serve["max_abs_dev"],
         "max_rel_dev": serve["max_rel_dev"],
         "max_dev_over_row_spread": serve["max_dev_over_row_spread"],
         "rtol": SERVE_RTOL, "atol": SERVE_ATOL,
         "spread_fraction": SERVE_SPREAD_FRACTION,
         "engine": serve["engine"]}, sort_keys=True), flush=True)
    log(f"all phases passed in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
