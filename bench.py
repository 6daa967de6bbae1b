#!/usr/bin/env python
"""Benchmark harness — prints ONE JSON line on stdout.

Headline metric (BASELINE.json `metric`): **ImageNet AlexNet
images/sec/chip** — the real 227×227×3 geometry (seeded synthetic data;
ImageNet itself is unavailable in this environment), trained through
the fused TPU path (whole train step jitted, dataset HBM-resident).

``vs_baseline`` is the speedup over the *unit-graph per-op dispatch path
on the same device* — the reference's execution model (one kernel enqueue
per unit per minibatch, Python between ops; SURVEY.md §3.1 hot-loop
note), which is the only reference-equivalent baseline measurable here
(the reference's own CUDA numbers are unrecoverable).

Device contract: the training and ``--kernels`` modes measure the
TPU.  Every row names the device as JAX reports it
(``platform`` / ``device_kind`` / ``device_count``); where JAX finds no
TPU the row carries an ``error`` and no value.  A row with an ``error``
field — no chip, a kernel that does not compile, a failed measurement,
a failed ``--kernels`` case — still prints where it can, and the
process exits non-zero.  There is no CPU fallback.

Extra modes (not used by the driver):

* ``--kernels`` — run every Pallas kernel on the current device against
  its XLA twin, assert allclose, and time both.
* ``--config NAME`` — bench a non-flagship BASELINE config
  (cifar/autoencoder/kohonen/mnist) instead of AlexNet.
* ``serve`` / ``--serve`` — the request-path twin of the headline: a
  real ``python -m znicz_tpu serve`` subprocess under closed-loop HTTP
  load, stamping req/s/core + p50/p99 + device-ms/request transcript
  rows (rev-stamped like every other row) so the ROADMAP's
  request-path speed arc is a measured trajectory.
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def _emit(obj) -> int:
    """Print the row; the exit code is non-zero iff it carries an
    ``error``."""
    print(json.dumps(obj))
    sys.stdout.flush()
    return 1 if obj.get("error") else 0


def _require_tpu(result) -> bool:
    """Name the device in the row (as JAX reports it) and say whether
    it is the TPU the device modes measure; off-TPU the row gets its
    ``error`` here."""
    from znicz_tpu.backends import device_report
    result["device"] = device_report()
    if result["device"]["platform"] == "tpu":
        return True
    result["error"] = ("no accelerator: JAX reports platform "
                       f"{result['device']['platform']!r}; this mode "
                       "measures the TPU and has no CPU fallback")
    return False


#: config name → (module, workflow class, config-tree attr).
_CONFIGS = {
    "alexnet": ("alexnet", "AlexNetWorkflow", "alexnet"),
    "cifar": ("cifar", "CifarWorkflow", "cifar"),
    "mnist": ("mnist", "MnistWorkflow", "mnist"),
    "autoencoder": ("autoencoder", "MnistAEWorkflow", "mnist_ae"),
    "kohonen": ("kohonen", "KohonenWorkflow", "kohonen"),
}


def _build(config: str, minibatch, n_train):
    from znicz_tpu import prng
    prng.seed_all(1234)
    import importlib

    from znicz_tpu.backends import Device
    from znicz_tpu.config import root

    mod_name, cls, tree_name = _CONFIGS[config]
    mod = importlib.import_module(f"znicz_tpu.models.{mod_name}")
    tree = getattr(root, tree_name)
    if minibatch:
        tree.update({"minibatch_size": minibatch})
    if n_train:
        tree.synthetic.update({"n_train": n_train, "n_valid": 0,
                               "n_test": 0})
    wf = getattr(mod, cls)()
    wf.initialize(device=Device.create("xla"))
    return wf


def measure_fused(wf, epochs: int, warm: int = 2, dtype: str | None = None,
                  storage: str | None = None, mesh=None):
    """(images/sec, spec, params) of the fused whole-step path;
    ``mesh`` (a (dp, tp) shape for parallel.mesh.resolve_mesh) lays
    the step out over the device mesh.  The returned rate is
    PER-DEVICE (aggregate / mesh size), so the ``_per_chip`` metric
    and the MFU/TFLOPs derived from it stay truthful on mesh rows —
    the sharding stamp keys pairing, it does not excuse the absolute
    number."""
    import dataclasses

    from znicz_tpu.parallel import fused, FusedTrainer
    from znicz_tpu.parallel.mesh import mesh_shape_of, resolve_mesh

    mesh = resolve_mesh(mesh)
    spec, params, vels = fused.extract_model(wf, storage or "float32")
    if dtype and dtype != spec.compute_dtype:
        spec = dataclasses.replace(spec, compute_dtype=dtype)
    dp, tp = mesh_shape_of(mesh)
    n_devices = dp * tp
    tr = FusedTrainer(spec=spec, params=params, vels=vels, mesh=mesh)
    ld = wf.loader
    data = ld.original_data.devmem
    # MSE heads (autoencoder) regress on target tensors, not labels
    target = (ld.original_targets.devmem
              if getattr(wf, "loss_function", "softmax") == "mse"
              else ld.original_labels.devmem)
    n = ld.class_lengths[2]
    idx = np.arange(ld.total_samples - n, ld.total_samples)
    batch = ld.max_minibatch_size
    # two warm epochs: the first compiles, the second recompiles once
    # more when the donated params come back with device-chosen layouts
    for _ in range(warm):
        tr.train_epoch(data, target, idx, batch, sync=True)
    t0 = time.perf_counter()
    last = None
    for _ in range(epochs):
        last = tr.train_epoch(data, target, idx, batch, sync=False)
    np.asarray(last["loss"])                     # one sync at the end
    dt = time.perf_counter() - t0
    return epochs * n / dt / n_devices, spec, params


def measure_stream(wf, epochs: int, warm: int = 2,
                   dtype: str | None = None, storage: str | None = None):
    """Images/sec of the streaming fused path: the SAME model/arrays as
    measure_fused, but served from .znr shards on disk through the
    double-buffered prefetcher (VERDICT item 4 done-criterion: disk-backed
    must reach >=90% of the HBM-resident number)."""
    import dataclasses
    import shutil
    import tempfile

    from znicz_tpu.loader import RecordLoader, write_records
    from znicz_tpu.parallel import fused
    from znicz_tpu.parallel.stream import StreamTrainer
    from znicz_tpu.workflow import Workflow

    spec, params, vels = fused.extract_model(
        wf, storage_dtype=storage or "float32")
    if dtype and dtype != spec.compute_dtype:
        spec = dataclasses.replace(spec, compute_dtype=dtype)
    ld = wf.loader
    n = ld.class_lengths[2]
    data = np.asarray(ld.original_data.mem)
    # MSE configs: reconstruct-the-input (AE contract — label block
    # unused, its IO skipped) vs distinct targets (denoising-style —
    # targets ride the shards' label block); mirror of the
    # run_fused auto-detection so resident and stream regress on the
    # SAME target tensor
    mse_target = "input"
    label_block = np.asarray(ld.original_labels.mem)
    if getattr(wf, "loss_function", "softmax") == "mse":
        targets = np.asarray(ld.original_targets.mem)
        if not np.array_equal(targets, data):
            mse_target = "labels"
            label_block = targets
    tmp = tempfile.mkdtemp(prefix="znicz_bench_znr_")
    try:
        paths = write_records(
            tmp + "/train.znr", data, label_block,
            shard_size=max(64, n // 4))
        sld = RecordLoader(Workflow(name="bench_stream"),
                           train_paths=paths,
                           minibatch_size=ld.max_minibatch_size)
        from znicz_tpu.backends import NumpyDevice
        sld.initialize(NumpyDevice())
        tr = StreamTrainer(spec=spec, params=params, vels=vels,
                           loader=sld, mse_target=mse_target)
        idx = np.arange(ld.total_samples - n, ld.total_samples)
        batch = ld.max_minibatch_size
        for _ in range(warm):
            tr.train_epoch(None, None, idx, batch, sync=True)
        t0 = time.perf_counter()
        last = None
        for _ in range(epochs):
            last = tr.train_epoch(None, None, idx, batch, sync=False)
        np.asarray(last["loss"])
        dt = time.perf_counter() - t0
        return epochs * n / dt
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure_augmented(spec, params, epochs: int, warm: int = 2,
                      decode: int = 256, crop: int = 227,
                      n_train: int = 512, batch: int = 128):
    """Images/sec of the resident fused path WITH on-device
    augmentation (RandomCropFlip.device_apply inside the scan): data
    lives at decode size in HBM, random crop+mirror to the net's input
    size rides the jitted step — the ImageNet-realistic variant of the
    headline number."""
    import jax.numpy as jnp

    from znicz_tpu import prng
    from znicz_tpu.loader import RandomCropFlip
    from znicz_tpu.parallel import FusedTrainer

    gen = prng.get("bench_augment")
    data = jnp.asarray(gen.normal(0.0, 1.0, (n_train, decode, decode,
                                             3)).astype(np.float32))
    labels = jnp.asarray(gen.randint(0, 1000, n_train).astype(np.int32))
    vels = [(np.zeros_like(w) if w is not None else None,
             np.zeros_like(b) if b is not None else None)
            for w, b in params]
    tr = FusedTrainer(spec=spec, params=params, vels=vels,
                      augment=RandomCropFlip((crop, crop), seed=1234))
    idx = np.arange(n_train)
    for _ in range(warm):
        tr.train_epoch(data, labels, idx, batch, sync=True)
    t0 = time.perf_counter()
    last = None
    for _ in range(epochs):
        last = tr.train_epoch(data, labels, idx, batch, sync=False)
    np.asarray(last["loss"])
    dt = time.perf_counter() - t0
    return epochs * n_train / dt


def bench_loader(args) -> int:
    """``--loader``: disk→gather→(augment)→host-batch throughput of the
    .znr pipeline with NO device in the loop — quantifies whether the
    data plane can sustain the chip's demand (the headline 3340 img/s
    at 227×227×3 implies ~1.9 GB/s of delivered pixels; VERDICT r2
    item 4).  Writes an AlexNet-geometry dataset to a temp dir, then
    drives the BatchPrefetcher for full epochs at several decode worker
    counts, reporting img/s and GB/s per count."""
    import shutil
    import tempfile

    from znicz_tpu.loader import RandomCropFlip
    from znicz_tpu.loader.records import write_records
    from znicz_tpu.loader.streaming import BatchPrefetcher, RecordLoader
    from znicz_tpu.workflow import Workflow

    result = {"metric": "alexnet_loader_images_per_sec", "value": None,
              "unit": "images/sec", "vs_baseline": None}
    try:
        # the loader bench measures the HOST pipeline: pin CPU, so no
        # accelerator is in the loop (device_put goes to CPU)
        import jax
        jax.config.update("jax_platforms", "cpu")
        from znicz_tpu.backends import device_report
        result["device"] = device_report()
        n, size = args.n_train, 227 + 29 if args.augment else 227
        rng = np.random.default_rng(5)
        data = rng.standard_normal((n, size, size, 3)).astype(np.float32)
        labels = rng.integers(0, 1000, n).astype(np.int32)
        row_gb = data.nbytes / n / 1e9
        tmp = tempfile.mkdtemp(prefix="znicz_bench_loader_")
        try:
            paths = write_records(tmp + "/ds.znr", data, labels,
                                  shard_size=max(64, n // 4))
            aug = (RandomCropFlip((227, 227), seed=7)
                   if args.augment else None)
            rows, fetch_rows = {}, {}
            for workers in (1, 2, 4, 8):
                os.environ["ZNICZ_TPU_IO_WORKERS"] = str(workers)
                sld = RecordLoader(Workflow(name="ldbench"),
                                   train_paths=paths,
                                   minibatch_size=args.minibatch,
                                   augment=aug)
                from znicz_tpu.backends import NumpyDevice
                sld.initialize(NumpyDevice())
                mb = args.minibatch
                steps = n // mb              # whole minibatches only
                mat = np.arange(steps * mb).reshape(steps, mb)
                for _ in range(getattr(args, "warm", 2)):  # warm the page
                    for x, t in BatchPrefetcher(sld, mat, epoch=0):
                        pass                                # cache + pool
                t0 = time.perf_counter()
                count = 0
                for ep in range(args.epochs):
                    for x, t in BatchPrefetcher(sld, mat, epoch=ep):
                        count += len(x)
                dt = time.perf_counter() - t0
                rows[workers] = round(count / dt, 1)
                # disk→host-batch alone (no device transfer): the
                # number that bounds what an overlapped DMA can be fed
                t0 = time.perf_counter()
                for ep in range(args.epochs):
                    for row in mat:
                        sld.fetch(row, epoch=ep)
                fetch_rows[workers] = round(
                    args.epochs * steps * mb
                    / (time.perf_counter() - t0), 1)
            result["rows_by_workers"] = rows
            result["fetch_by_workers"] = fetch_rows
            result["fetch_value"] = max(fetch_rows.values())
            if aug is not None:
                # device-augment streaming (StreamTrainer
                # device_augment=True) ships RAW decode-size rows; its
                # host-side bound is the un-augmented gather
                t0 = time.perf_counter()
                for ep in range(args.epochs):
                    for row in mat:
                        sld.read_batch(row)
                result["raw_fetch_value"] = round(
                    args.epochs * mat.size
                    / (time.perf_counter() - t0), 1)
            best = max(rows.values())
            result["value"] = best
            result["gb_per_sec"] = round(best * row_gb, 2)
            result["augment"] = bool(args.augment)
            # demand side: the resident step's img/s in the builders'
            # 2026-07-29 on-chip transcript — not re-measured on this
            # code (ROADMAP Speed 5)
            result["chip_demand_img_per_sec"] = 3340
            result["feeds_chip"] = bool(best >= 3340)
        finally:
            os.environ.pop("ZNICZ_TPU_IO_WORKERS", None)
            shutil.rmtree(tmp, ignore_errors=True)
    except Exception as e:
        result.setdefault("error", "")
        result["error"] = (result["error"]
                           + f" loader bench failed: {e!r}").strip()[:600]
    return _emit(result)


def _serve_row(latencies_ms, codes, duration_s, cores,
               device_ms_total) -> dict:
    """The serve-mode transcript row's measured core (pure function —
    tests pin the schema without booting a server).  ``codes`` is a
    {status: count} map over every answer; throughput counts 200s only
    (a 429 storm must not inflate req/s), latency quantiles cover every
    answered request (a refusal's latency is real client experience).

    ``req_per_sec_per_core`` divides by the host's core count — the
    cross-machine-comparable figure the ROADMAP's request-path arc
    tracks, exactly like images/sec/chip on the training side."""
    n = sum(codes.values())
    n_ok = codes.get(200, 0)
    lat = sorted(latencies_ms)
    dur = max(1e-9, float(duration_s))
    cores = max(1, int(cores))
    row = {"requests": int(n), "ok": int(n_ok),
           "codes": {str(k): int(v) for k, v in sorted(codes.items())},
           "duration_s": round(dur, 3), "cores": cores,
           "req_per_sec": round(n_ok / dur, 2),
           "req_per_sec_per_core": round(n_ok / dur / cores, 3),
           "device_ms_total": round(float(device_ms_total), 1),
           "device_ms_per_request": (
               round(float(device_ms_total) / n_ok, 3) if n_ok
               else None)}
    if lat:
        row["p50_ms"] = round(lat[len(lat) // 2], 3)
        row["p99_ms"] = round(
            lat[min(len(lat) - 1, int(len(lat) * 0.99))], 3)
    else:
        row["p50_ms"] = row["p99_ms"] = None
    return row


def bench_serve(args) -> int:
    """``bench.py serve`` (or ``--serve``): drive a REAL
    ``python -m znicz_tpu serve`` process and stamp a rev-stamped
    transcript row with req/s/core, p50/p99 and device-ms/request —
    the request-path speed arc measured exactly like the on-chip one
    (ROADMAP "raw request-path speed").  The server is a subprocess
    (its threads, signal handling and JSON parse costs are all IN the
    measurement — an in-process shortcut would flatter the number);
    the client side is N threads of closed-loop traffic."""
    import collections
    import shutil
    import signal
    import socket
    import subprocess
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    n_fleet = max(0, getattr(args, "fleet", 0))
    place = bool(getattr(args, "placement", False))
    ext_urls = [u if u.endswith("/") else u + "/"
                for u in (getattr(args, "router_url", None) or [])]
    result = {"metric": "serve_requests_per_sec_per_core",
              "value": None, "unit": "req/s/core",
              "vs_baseline": None}
    tmp = tempfile.mkdtemp(prefix="znicz_bench_serve_")
    proc = None
    fleet_procs = []
    backend_urls = []
    if place and not n_fleet:
        result["error"] = "--placement needs --fleet N (it shards a " \
                          "zoo over a fleet)"
        return _emit(result)
    if ext_urls and (n_fleet or place):
        result["error"] = "--router-url drives an EXISTING fleet; " \
                          "it excludes --fleet/--placement"
        return _emit(result)
    try:
        model = args.serve_model
        width = args.serve_width
        if ext_urls:
            # external mode boots nothing — the payload just has to
            # match the EXISTING servers' model (demo width default)
            width = width or 4
        elif model is None:
            from znicz_tpu.resilience.chaos import _write_demo_znn
            model = os.path.join(tmp, "demo.znn")
            width = 4
            _write_demo_znn(model)

        def free_port() -> int:
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        zoo_dir = os.path.join(tmp, "zoo")
        if place:
            # placement mode shards a multi-tenant zoo, not N copies
            # of one model — that IS the footprint being measured
            from znicz_tpu.serving import zoo as zoo_mod
            zoo_mod.make_demo_zoo(zoo_dir)

        def boot_serve(serve_port: int) -> subprocess.Popen:
            if place:
                return subprocess.Popen(
                    [sys.executable, "-m", "znicz_tpu", "serve",
                     "--zoo", zoo_dir, "--port", str(serve_port),
                     "--max-wait-ms", "1"],
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            return subprocess.Popen(
                [sys.executable, "-m", "znicz_tpu", "serve",
                 "--model", model, "--port", str(serve_port),
                 "--max-wait-ms", "1", "--warmup-shape", str(width)]
                # repeat traffic only pays off with the response cache
                # on; a pure-unique run serves WITHOUT memoization so
                # the two trajectories measure different levers
                + (["--memoize", "4096"]
                   if args.repeat_fraction > 0 else []),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

        def wait_health(wait_url: str, wait_proc,
                        what: str) -> dict | None:
            for _ in range(240):
                try:
                    with urllib.request.urlopen(wait_url + "healthz",
                                                timeout=2) as r:
                        return json.loads(r.read())
                except Exception:
                    if wait_proc.poll() is not None:
                        out = wait_proc.stdout.read().decode(
                            errors="replace")
                        result["error"] = (
                            f"{what} exited "
                            f"rc={wait_proc.returncode}: " + out[-400:])
                        return None
                    time.sleep(0.5)
            result["error"] = f"{what} never answered /healthz"
            return None

        if ext_urls:
            # external mode: drive EXISTING router(s) instead of
            # booting a fleet here — several urls name an HA pair
            # (primary + hot standbys) and the clients fail over
            # between them on transport error (docs/fleet.md "Router
            # high availability")
            url = None
            health = None
            deadline = time.monotonic() + 30
            while health is None and time.monotonic() < deadline:
                for u in ext_urls:
                    try:
                        with urllib.request.urlopen(u + "healthz",
                                                    timeout=2) as r:
                            health = json.loads(r.read())
                            url = u
                            break
                    except Exception:
                        continue
                if health is None:
                    time.sleep(0.5)
            if health is None:
                result["error"] = ("no router of "
                                   f"{', '.join(ext_urls)} answered "
                                   "/healthz")
                return _emit(result)
            # put the answering router first so the warm lap and the
            # clients start against a live frontend
            ext_urls = [url] + [u for u in ext_urls if u != url]
        elif n_fleet:
            # fleet mode: N serve backends behind a REAL route
            # process — the router's forwarding overhead is IN the
            # measurement, which is the point (the fleetxN trajectory
            # prices the fabric against the single-process rows)
            ports = [free_port() for _ in range(n_fleet)]
            port = free_port()
            backend_urls = [f"http://127.0.0.1:{pt}/" for pt in ports]
            fleet_procs = [boot_serve(pt) for pt in ports]
            health = None
            for burl, bproc in zip(backend_urls, fleet_procs):
                health = wait_health(burl, bproc, "fleet backend")
                if health is None:
                    return _emit(result)
            proc = subprocess.Popen(
                [sys.executable, "-m", "znicz_tpu", "route",
                 "--port", str(port)]
                + (["--placement", "1",
                    "--probe-interval-s", "0.3"] if place else [])
                + [f for i, u in enumerate(backend_urls)
                   for f in ("--backend", f"{u},name=b{i}")],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            url = f"http://127.0.0.1:{port}/"
            if wait_health(url, proc, "route") is None:
                return _emit(result)
            if place:
                # measure the PLACED steady state, not the discovery
                # transient: wait for the map to cover the zoo
                from znicz_tpu.serving.zoo import DEMO_FAMILIES
                for _ in range(80):
                    h = wait_health(url, proc, "route")
                    amap = ((h or {}).get("placement") or {}) \
                        .get("assignments") or {}
                    if set(amap) >= set(DEMO_FAMILIES):
                        break
                    time.sleep(0.25)
                else:
                    result["error"] = ("placement never covered the "
                                       "demo zoo")
                    return _emit(result)
        else:
            port = free_port()
            proc = boot_serve(port)
            url = f"http://127.0.0.1:{port}/"
            health = wait_health(url, proc, "serve")
            if health is None:
                return _emit(result)
        import http.client

        import numpy as np
        from znicz_tpu.serving import wire as wire_mod

        rows = max(1, args.serve_rows)
        base = np.full((rows, width), 0.1, dtype=np.float32)
        binary = args.payload == "binary"
        headers = ({"Content-Type": wire_mod.CONTENT_TYPE,
                    "Accept": wire_mod.CONTENT_TYPE} if binary
                   else {"Content-Type": "application/json"})
        tenants: list = []
        tenant_bodies: dict = {}
        if place:
            # cycle the zoo's tenants: placement routing (X-Model →
            # the tenant's placed backend) is the path under test
            from znicz_tpu.serving.zoo import DEMO_SHAPES
            tenants = sorted(DEMO_SHAPES)
            for name in tenants:
                tx = np.full((rows, DEMO_SHAPES[name]), 0.1,
                             dtype=np.float32)
                tenant_bodies[name] = (
                    wire_mod.encode_tensor(tx) if binary
                    else json.dumps({"inputs": tx.tolist()}).encode())

        def body_for(i: int) -> bytes:
            # i < 0 = the FIXED repeat payload; unique bodies perturb
            # one element deterministically (no RNG on a bench path)
            x = base
            if i >= 0:
                x = base.copy()
                x[0, 0] = 0.1 + (i % 100003) * 1e-4
            if binary:
                return wire_mod.encode_tensor(x)
            return json.dumps({"inputs": x.tolist()}).encode()

        fixed_body = body_for(-1)
        repeat_pct = int(round(args.repeat_fraction * 100))
        n_clients = max(1, args.serve_clients)
        traced = bool(getattr(args, "trace_breakdown", False))
        if traced:
            from znicz_tpu.telemetry import tracestore as ts_mod
            from znicz_tpu.telemetry import tracing as tracing_mod
        trace_mu = threading.Lock()
        trace_collect = threading.Event()
        stage_samples: dict = collections.defaultdict(list)
        trace_pairs: list = []       # (e2e_ms, sum-of-stages_ms)

        def _note_trace(tr, resp, data, e2e_ms):
            # the stage split comes back in-band: router-assembled
            # ("stages" present) in fleet mode, or the single server's
            # raw span summary — assembled locally with pick=0 and
            # the measured wall as the forward envelope — otherwise.
            # A spilled wire trailer beats the header when present.
            raw = resp.getheader(ts_mod.SPANS_HEADER)
            summary = ts_mod.decode_summary(raw)
            if binary:
                _clean, trailer = wire_mod.split_trailer(data)
                if trailer is not None:
                    summary = ts_mod.decode_summary(trailer)
            if summary is None:
                return
            stages = summary.get("stages")
            if isinstance(stages, dict):
                # router-assembled split: the residual between the
                # client's wall and the router's measured total is the
                # client<->router network leg — fold it into net.hop
                # so the seven stages cover the FULL e2e path
                rt = summary.get("total_ms")
                if isinstance(rt, (int, float)):
                    residual = max(0.0, e2e_ms - float(rt))
                    stages = dict(stages)
                    stages["net.hop"] = round(
                        float(stages.get("net.hop") or 0.0)
                        + residual, 3)
            else:
                stages = ts_mod.assemble(
                    trace_id=tr.trace_id, request_id="",
                    model="default", backend="local", outcome="ok",
                    total_ms=e2e_ms, pick_ms=0.0, forward_ms=e2e_ms,
                    summary=summary,
                    started_at=time.time())["stages"]
            present = {k: float(v) for k, v in stages.items()
                       if v is not None}
            if not present:
                return
            with trace_mu:
                for name, ms in present.items():
                    stage_samples[name].append(ms)
                trace_pairs.append((e2e_ms, sum(present.values())))

        def post_conn(conn, body, hdrs=None):
            hh = hdrs if hdrs is not None else headers
            tr = None
            if traced:
                # every driven request carries its own root context —
                # the breakdown wants the full population, not the
                # router's head-sampled fraction
                tr = tracing_mod.TraceContext(
                    tracing_mod.new_trace_id(),
                    tracing_mod.new_span_id())
                hh = dict(hh)
                hh[ts_mod.TRACE_HEADER] = \
                    tracing_mod.format_traceparent(tr)
            t_req = time.monotonic()
            conn.request("POST", "/predict", body, hh)
            r = conn.getresponse()
            data = r.read()
            if traced and trace_collect.is_set() and r.status == 200:
                try:
                    _note_trace(tr, r, data,
                                (time.monotonic() - t_req) * 1e3)
                except Exception:
                    pass          # a torn summary never fails a bench
            return r.status

        if ext_urls:
            from urllib.parse import urlsplit
            targets = [((urlsplit(u).hostname or "127.0.0.1"),
                        (urlsplit(u).port or 80)) for u in ext_urls]
        else:
            targets = [("127.0.0.1", port)]
        warm = http.client.HTTPConnection(*targets[0], timeout=60)
        if place:                     # one warm lap per tenant
            for name in tenants:
                post_conn(warm, tenant_bodies[name],
                          dict(headers, **{"X-Model": name}))
        else:
            post_conn(warm, fixed_body)
        warm.close()
        trace_collect.set()           # warm-lap compiles stay out
        answers = []                  # (latency_ms, code)
        mu = threading.Lock()
        stop = threading.Event()

        def client(ci: int):
            # one persistent connection per closed-loop client — the
            # HTTP/1.1 keep-alive contract is part of what's measured;
            # a dropped connection re-opens (that request's latency
            # carries the reconnect, like a real client's would).
            # With several --router-url targets (an HA pair) a
            # transport error ALSO rotates to the next router; an HTTP
            # answer never does — a 503 + Retry-After refusal during a
            # takeover is an answer, and shows up in the codes map
            active = 0

            def connect():
                return http.client.HTTPConnection(
                    *targets[active % len(targets)], timeout=30)

            conn = connect()
            i = ci
            while not stop.is_set():
                if place:
                    name = tenants[i % len(tenants)]
                    body = tenant_bodies[name]
                    hdrs = dict(headers, **{"X-Model": name})
                else:
                    body = (fixed_body if (i % 100) < repeat_pct
                            else body_for(i))
                    hdrs = None
                t0 = time.monotonic()
                try:
                    code = post_conn(conn, body, hdrs)
                except Exception:
                    conn.close()
                    active += 1
                    conn = connect()
                    code = -1
                dt_ms = (time.monotonic() - t0) * 1e3
                with mu:
                    answers.append((dt_ms, code))
                i += n_clients
            conn.close()

        def device_ms_now() -> float:
            # fleet mode: the chip time lives in the BACKENDS — sum
            # their ledgers (the router itself runs no device code);
            # a zoo backend's ledger is per-tenant, so placement mode
            # sums the healthz model rows instead of the engine total
            if ext_urls:
                # external routers: the backends aren't ours to
                # scrape — device-ms is reported as 0, not guessed
                return 0.0
            if place:
                return sum(_scrape_zoo_device_ms(u)
                           for u in backend_urls)
            if n_fleet:
                return sum(_scrape_device_ms(u) for u in backend_urls)
            return _scrape_device_ms(url)

        dev0 = device_ms_now()
        threads = [threading.Thread(target=client, args=(ci,),
                                    daemon=True)
                   for ci in range(n_clients)]
        t_start = time.monotonic()
        for t in threads:
            t.start()
        stop.wait(args.serve_duration_s)
        stop.set()
        for t in threads:
            t.join(30.0)
        duration_s = time.monotonic() - t_start
        device_ms = device_ms_now() - dev0
        fleet_resident = zoo_total = None
        if place:
            # the footprint claim, measured at the end of the burst:
            # fleet resident bytes vs one zoo's total weight bytes
            fleet_resident = 0
            zoo_total = 0
            for u in backend_urls:
                try:
                    with urllib.request.urlopen(u + "healthz",
                                                timeout=10) as r:
                        snap = json.loads(r.read())
                except Exception:
                    continue
                fleet_resident += int(snap.get("resident_bytes") or 0)
                zoo_total = max(zoo_total, sum(
                    int(row.get("weight_bytes") or 0)
                    for row in snap.get("models") or []))
        own_procs = ([proc] if proc is not None else []) + fleet_procs
        for p_ in own_procs:
            p_.send_signal(signal.SIGINT)
        for p_ in own_procs:
            try:
                p_.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p_.kill()
        proc = None
        fleet_procs = []
        codes = collections.Counter(c for _l, c in answers)
        # quantiles cover ANSWERED requests only (the _serve_row
        # contract): a hung/dropped request's "latency" is just the
        # client timeout and would corrupt p99 for the whole row — it
        # is reported through the codes map and the error note instead
        row = _serve_row([latency for latency, c in answers if c != -1],
                         codes, duration_s, os.cpu_count() or 1,
                         device_ms)
        result.update(row)
        result["value"] = row["req_per_sec_per_core"]
        # the device the (last-probed) server reports for itself; an
        # external router's healthz has none
        result["device"] = {k: health.get(k) for k in
                            ("platform", "device_kind", "device_count")}
        result["backend"] = health.get("backend")
        result["clients"] = args.serve_clients
        result["rows_per_request"] = max(1, args.serve_rows)
        # wire-format + repeat-mix provenance: trajectories only pair
        # like-for-like when the row says WHICH path was driven
        result["payload"] = args.payload
        result["repeat_fraction"] = args.repeat_fraction
        if traced:
            # the p99 decomposition: per-stage quantiles over every
            # assembled trace, plus the honesty check — the stage sum
            # must track the measured e2e wall (the acceptance gate
            # wants the medians within ~10%)
            def _q(sorted_vals, frac):
                return round(sorted_vals[min(len(sorted_vals) - 1,
                                             int(len(sorted_vals)
                                                 * frac))], 3)
            br: dict = {}
            for name in ts_mod.STAGES:
                vals = sorted(stage_samples.get(name) or [])
                if vals:
                    br[name] = {"p50_ms": _q(vals, 0.5),
                                "p99_ms": _q(vals, 0.99)}
            sums = sorted(s for _e, s in trace_pairs)
            e2es = sorted(e for e, _s in trace_pairs)
            result["trace_breakdown"] = {
                "traces": len(trace_pairs),
                "stages": br,
                "stage_sum_p50_ms": _q(sums, 0.5) if sums else None,
                "e2e_p50_ms": _q(e2es, 0.5) if e2es else None,
                "stage_sum_p99_ms": _q(sums, 0.99) if sums else None,
                "e2e_p99_ms": _q(e2es, 0.99) if e2es else None,
                "sum_over_e2e": (
                    round(_q(sums, 0.5) / max(1e-9, _q(e2es, 0.5)), 3)
                    if sums and e2es else None)}
        rev = _git_rev()
        if rev:
            result["rev"] = rev
        # the topology is part of a serve measurement's identity,
        # exactly like the mesh scheme on the training side: fleetxN
        # rows only pair with fleetxN rows in decide_levers
        result["sharding"] = (f"externalx{len(ext_urls)}" if ext_urls
                              else f"fleetx{n_fleet}+place" if place
                              else f"fleetx{n_fleet}" if n_fleet
                              else "1x1")
        if n_fleet:
            result["fleet"] = n_fleet
        if place:
            result["placement"] = 1     # the replication factor
            result["fleet_resident_bytes"] = fleet_resident
            result["zoo_total_bytes"] = zoo_total
        result["ts"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime())
        if codes.get(-1):
            result.setdefault("error", "")
            result["error"] = (result["error"] + f" {codes[-1]} "
                               f"request(s) hung/dropped").strip()
    except Exception as e:
        result.setdefault("error", "")
        result["error"] = (result["error"]
                           + f" serve bench failed: {e!r}").strip()[:600]
    finally:
        for p_ in ([proc] if proc is not None else []) + fleet_procs:
            p_.kill()
        shutil.rmtree(tmp, ignore_errors=True)
    return _emit(result)


def _scrape_zoo_device_ms(url: str) -> float:
    """A multi-tenant backend's device-ms, summed over its healthz
    model rows (the per-tenant ledger; 0.0 when unreachable)."""
    import urllib.request
    try:
        with urllib.request.urlopen(url + "healthz", timeout=10) as r:
            snap = json.loads(r.read())
        return sum(float(row.get("device_ms") or 0.0)
                   for row in snap.get("models") or [])
    except Exception:
        return 0.0


def _scrape_device_ms(url: str) -> float:
    """The server's measured engine device-ms total from the JSON
    /metrics view (0.0 when unreachable — the delta then honestly
    reads as 'unmeasured', not a crash)."""
    import urllib.request
    try:
        with urllib.request.urlopen(url + "metrics", timeout=10) as r:
            m = json.loads(r.read())
        return float((m.get("engine") or {}).get("device_ms_total", 0.0))
    except Exception:
        return 0.0


def measure_unit_graph(wf, ticks: int) -> float:
    """Images/sec of the per-unit dispatch path (reference execution
    model) on the same device and weights."""
    wf.run(max_ticks=1)                          # compile+warm all units
    t0 = time.perf_counter()
    wf.run(max_ticks=ticks)
    dt = time.perf_counter() - t0
    return ticks * wf.loader.max_minibatch_size / dt


def measure_som_fused(wf, epochs: int):
    """(samples/sec, flops/sample) of the fused SOM epoch scan."""
    from znicz_tpu.loader.base import TRAIN
    from znicz_tpu.parallel.som import FusedSOMTrainer

    ld = wf.loader
    tr = FusedSOMTrainer(np.asarray(wf.forward.weights.mem),
                         wf.forward.shape, workflow=wf)
    data = ld.original_data.devmem
    perm = ld.train_permutation(ld.epoch_number)
    batch = ld.max_minibatch_size
    n = ld.class_lengths[TRAIN]
    lr, sigma = wf.trainer.schedules()
    tr.train_epoch(data, perm, batch, lr, sigma)       # compile
    tr.train_epoch(data, perm, batch, lr, sigma)
    t0 = time.perf_counter()
    for _ in range(epochs):
        tr.train_epoch(data, perm, batch, lr, sigma)
    dt = time.perf_counter() - t0
    n_neurons = int(np.prod(wf.forward.shape))
    dim = int(np.prod(ld.original_data.shape[1:]))
    return epochs * n / dt, 6.0 * n_neurons * dim


def _append_note(result, note: str) -> None:
    """The ONE way a bench result accumulates advisory notes."""
    result["note"] = (result["note"] + "; " + note
                      if "note" in result else note)


def _git_rev() -> str | None:
    """Short git sha of the checkout the bench ran from, suffixed
    ``-dirty.<hash-of-diff>`` when the CODE has uncommitted edits —
    two runs straddling an uncommitted kernel tweak are NOT the same
    code, and two *different* tweaks must not share a stamp either.
    None when not a repo / no git.  Stamped into every transcript row
    so decide_levers.py can refuse to average or pair rows measured on
    different code revisions (ADVICE r5 medium: cross-revision rows
    contaminate keep/revert verdicts).

    The implementation lives in ``znicz_tpu.telemetry.buildinfo`` so
    the serving ``/metrics`` endpoint stamps the identical ``rev``
    (scraped metrics and transcript rows must attribute to the same
    build string).  The CODE-paths rule (no ``tests``: a test-only
    edit cannot change a measurement) is the shared default there."""
    from znicz_tpu.telemetry import buildinfo
    return buildinfo.git_rev(
        root=os.path.dirname(os.path.abspath(__file__)))


def _record_run_config(args, result, mesh_applies: bool = False) -> None:
    """Stamp the transcript row with what ran: the ``ZNICZ_TPU_*``
    environment, the code revision, the sharding and the minibatch."""
    levers = {k: v for k, v in sorted(os.environ.items())
              if k.startswith("ZNICZ_TPU_")}
    if levers:
        result["levers"] = levers
    else:
        result.pop("levers", None)
    rev = _git_rev()
    if rev:
        result["rev"] = rev
    else:
        # an unstamped row pools with pre-round-6 legacy history in
        # decide_levers — that must never happen silently
        print("warning: no git revision available; transcript row is "
              "unstamped and will pair with legacy (rev-less) rows",
              file=sys.stderr)
    # the sharding scheme is part of a measurement's identity exactly
    # like the minibatch: a "4x2"-mesh row and a single-device "1x1"
    # row measure different programs, so decide_levers must only pair
    # like-for-like (its headline key includes this field).  Only the
    # training path actually lays work over the mesh (mesh_applies);
    # the kernel/loader modes measure single-device regardless
    # of the flag and must say so
    if mesh_applies and getattr(args, "mesh", None):
        from znicz_tpu.parallel.mesh import parse_mesh_arg
        dp, tp = parse_mesh_arg(args.mesh)
        result["sharding"] = f"{dp}x{tp}"
    else:
        result["sharding"] = "1x1"
        if getattr(args, "mesh", None) and not mesh_applies:
            _append_note(result, "--mesh does not apply to this bench "
                                 "mode; measured single-device")
    result["ts"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    result["minibatch"] = args.minibatch


def bench_training(args) -> int:
    result = {"metric": f"{args.config}_train_images_per_sec_per_chip",
              "value": None, "unit": "images/sec", "vs_baseline": None}
    if not _require_tpu(result):
        return _emit(result)
    _record_run_config(args, result, mesh_applies=True)
    try:
        from znicz_tpu.ops import flops as flops_mod

        wf = _build(args.config, args.minibatch, args.n_train)
        if args.config == "kohonen":
            # the SOM has no gradient chain; its fused path is the
            # dedicated epoch scan in parallel.som
            if result.get("sharding", "1x1") != "1x1":
                # the SOM scan has no mesh path: measured single-
                # device, and the row must say so instead of pairing
                # with genuine mesh rows
                result["sharding"] = "1x1"
                _append_note(result,
                             "--mesh is not implemented for the "
                             "kohonen SOM path; measured single-"
                             "device (sharding restamped 1x1)")
            ips, flops_img = measure_som_fused(wf, args.epochs)
            result["value"] = round(ips, 1)
            result["flops_per_image"] = flops_img
            result["tflops_per_sec"] = round(ips * flops_img / 1e12, 4)
            if args.ticks > 0:
                unit_graph = measure_unit_graph(wf, args.ticks)
                result["vs_baseline"] = round(ips / unit_graph, 2)
            return _emit(result)
        fused_ips, spec, params = measure_fused(
            wf, args.epochs, getattr(args, "warm", 2),
            dtype=args.dtype, storage=args.storage, mesh=args.mesh)
        result["path"] = "fused"
        result["compute_dtype"] = (args.dtype or "float32")
        if args.storage:
            result["storage_dtype"] = args.storage
        result["value"] = round(fused_ips, 1)
        # a mesh row records ONLY mesh measurements: the unit-graph /
        # stream / augment comparators below run meshless, and pairing
        # a meshless aggregate with a per-device mesh number (or
        # landing it in a sharding-stamped row) is exactly the
        # cross-program mixing the sharding key exists to forbid
        meshed = result.get("sharding", "1x1") != "1x1"
        if meshed and (args.ticks > 0 or args.stream or args.augment):
            _append_note(result,
                         "mesh run: the unit-graph/stream/augment "
                         "comparators are meshless and were skipped "
                         "(measure them without --mesh)")
        fl = flops_mod.model_flops(
            spec, params, wf.loader.original_data.shape[1:])
        achieved = fused_ips * fl["train_step"] / 1e12
        result["tflops_per_sec"] = round(achieved, 2)
        result["flops_per_image"] = fl["train_step"]
        # MFU over the published bf16 peak: XLA runs these f32 convs
        # and dots as bf16 MXU passes at default precision
        peak = flops_mod.peak_tflops(result["device"]["device_kind"])
        result["mfu"] = round(achieved / peak, 4)
        result["peak_tflops"] = peak
        # MSE heads stream too: StreamTrainer's mse_target="input"
        # default reconstructs x (the AE contract) and skips the
        # label block's IO entirely
        if args.stream and not meshed:
            stream_ips = measure_stream(wf, args.epochs,
                                        getattr(args, "warm", 2),
                                        dtype=args.dtype,
                                        storage=args.storage)
            result["stream_value"] = round(stream_ips, 1)
            result["stream_vs_resident"] = round(
                stream_ips / fused_ips, 3)
        if args.augment and args.config == "alexnet" and not meshed:
            size = int(wf.loader.original_data.shape[1])
            aug_ips = measure_augmented(
                spec, params, args.epochs,
                getattr(args, "warm", 2),
                decode=size + 29, crop=size,
                n_train=args.n_train, batch=args.minibatch)
            result["augment_value"] = round(aug_ips, 1)
            result["augment_vs_plain"] = round(
                aug_ips / fused_ips, 3)
        if args.ticks > 0 and not meshed:
            unit_graph = measure_unit_graph(wf, args.ticks)
            result["vs_baseline"] = round(fused_ips / unit_graph, 2)
        # a requested measurement must never quietly not run (the
        # meshed skips above carry their own note)
        if args.augment and "augment_value" not in result \
                and not meshed:
            _append_note(result,
                         "--augment requested but not measured (only "
                         "implemented for the alexnet config)")
    except Exception as e:
        result["error"] = f"measure failed: {e!r}"[:600]
    return _emit(result)


# -- per-kernel Pallas-vs-XLA validation (VERDICT item 3) ------------------
def _kernel_cases():
    """[(name, pallas_thunk, xla_thunk, compare)] on bench-scale shapes."""
    import jax.numpy as jnp
    from znicz_tpu.ops import (activations, dropout as drop_ops,
                               elementwise, kohonen as som_ops,
                               lrn_pool as lrn_pool_ops, matmul,
                               normalization as lrn_ops,
                               softmax, update)

    rng = np.random.default_rng(1234)

    def f32(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    a, b = f32(512, 1024), f32(1024, 768)
    logits = f32(1024, 1000)
    labels = jnp.asarray(rng.integers(0, 1000, size=1024), jnp.int32)
    x4 = f32(32, 28, 28, 64)
    err4 = f32(32, 28, 28, 64)
    xact = f32(1024, 4096)
    yact, eact = f32(1024, 4096), f32(1024, 4096)
    w = f32(4096, 1024)
    grad, vel = f32(4096, 1024), f32(4096, 1024)
    seed, ctrs = 1234, (7, 3, 11)
    taps = f32(9, 32 * 14 * 14, 64)          # (window taps, rows, C)
    xsom, wsom = f32(256, 784), f32(400, 784)   # 20x20 SOM on MNIST dims
    perr = f32(32 * 14 * 14, 64)
    poff = jnp.asarray(rng.integers(0, 9, size=(32 * 14 * 14, 64)),
                       jnp.int32)
    hypers = jnp.asarray([0.01, 1e-4, 0.0, 0.9], jnp.float32)
    _, d_lrn = lrn_ops.xla_lrn(x4)
    xlp = f32(32, 55, 55, 96)               # AlexNet L1 LRN+pool geometry
    _, olp = lrn_pool_ops.xla_lrn_maxpool(xlp, 5, 1e-4, 0.75, 2.0,
                                          (3, 3), (2, 2), 0)
    elp = f32(*olp.shape)

    cases = [
        ("matmul", lambda: matmul.pallas_matmul(a, b),
         lambda: matmul.xla_matmul(a, b), "close"),
        ("softmax", lambda: softmax.pallas_softmax(logits),
         lambda: softmax.xla_softmax(logits), "close"),
        ("softmax_ce",
         lambda: softmax.pallas_softmax_ce_from_logits(logits, labels),
         lambda: softmax.xla_softmax_ce_from_logits(logits, labels),
         "close"),
        ("act_bwd_tanh",
         lambda: elementwise.pallas_act_bwd("tanh", eact, yact),
         lambda: activations.BY_NAME["tanh"].bwd(eact, yact, None, jnp),
         "close"),
        ("dropout",
         lambda: elementwise.pallas_dropout(xact, seed, ctrs, 0.4),
         lambda: xact * drop_ops.make_mask(seed, ctrs, xact.shape, 0.4,
                                           jnp), "exact"),
        ("lrn", lambda: elementwise.pallas_lrn(x4)[0],
         lambda: lrn_ops.xla_lrn(x4)[0], "close"),
        ("gd_lrn",
         lambda: elementwise.pallas_gd_lrn(err4, x4, d_lrn),
         lambda: lrn_ops.xla_gd_lrn(err4, x4, d_lrn), "close"),
        ("lrn_y", lambda: elementwise.pallas_lrn_y(x4),
         lambda: lrn_ops.xla_lrn(x4)[0], "close"),
        ("gd_lrn_x",
         lambda: elementwise.pallas_gd_lrn_x(err4, x4),
         lambda: lrn_ops.xla_gd_lrn_x(err4, x4), "close"),
        ("pool_select",
         lambda: elementwise.pallas_pool_select(taps)[0],
         lambda: jnp.max(taps, axis=0), "close"),
        ("pool_scatter",
         lambda: elementwise.pallas_pool_scatter(perr, poff, 9),
         lambda: jnp.stack([perr * (poff == t) for t in range(9)]),
         "exact"),
        ("pool_gather",
         lambda: elementwise.pallas_pool_gather(taps, poff),
         lambda: sum(taps[t] * (poff == t) for t in range(9)), "close"),
        ("kohonen_argmin",
         lambda: som_ops.pallas_distance_argmin(xsom, wsom)[0],
         lambda: som_ops.xla_forward(xsom, wsom)[0], "exact"),
        # the fused LRN+max-pool pair on the convolutions' own layout,
        # at AlexNet L1-like geometry (stride-2 3x3 pool, cross-channel
        # LRN)
        ("lrn_maxpool_window",
         lambda: lrn_pool_ops.pallas_lrn_maxpool_window(
             xlp, 5, 1e-4, 0.75, 2.0, (3, 3), (2, 2), 0)[0],
         lambda: lrn_pool_ops.xla_lrn_maxpool(
             xlp, 5, 1e-4, 0.75, 2.0, (3, 3), (2, 2), 0)[0], "close"),
        ("gd_lrn_maxpool_window",
         lambda: lrn_pool_ops.pallas_gd_lrn_maxpool_window(
             elp, olp, xlp, 5, 1e-4, 0.75, 2.0, (3, 3), (2, 2), 0),
         lambda: lrn_pool_ops.xla_gd_lrn_maxpool(
             elp, olp, xlp, 5, 1e-4, 0.75, 2.0, (3, 3), (2, 2), 0),
         "close"),
        ("sgd_update",
         lambda: update.pallas_sgd_update(w, grad, vel, hypers),
         lambda: update.xla_sgd_update(w, grad, vel, 0.01, 1e-4, 0.0,
                                       0.9), "close"),
    ]
    for act in ("tanh", "relu", "sigmoid"):
        cases.append((
            f"act_fwd_{act}",
            lambda act=act: elementwise.pallas_act_fwd(act, xact),
            lambda act=act: activations.BY_NAME[act].fwd(xact, jnp),
            "close"))
    return cases


def _time_thunk(thunk, iters=20):
    import jax
    out = thunk()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = thunk()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6    # µs


def bench_kernels(args) -> int:
    import jax

    result = {"metric": "pallas_kernel_validation", "value": None,
              "unit": "kernels_passed", "vs_baseline": None}
    if not _require_tpu(result):
        return _emit(result)
    _record_run_config(args, result)
    from znicz_tpu.ops import tuning
    if not tuning.use_pallas():
        result["error"] = "Pallas tier disabled (ZNICZ_TPU_NO_PALLAS)"
        return _emit(result)
    rows, passed = [], 0
    for name, pallas_t, xla_t, mode in _kernel_cases():
        row = {"kernel": name}
        try:
            got = [np.asarray(g)
                   for g in jax.tree_util.tree_leaves(pallas_t())]
            ref = [np.asarray(r)
                   for r in jax.tree_util.tree_leaves(xla_t())]
            ok = len(got) == len(ref)
            err = 0.0
            for g, r in zip(got, ref):       # every output must match
                if mode == "exact":
                    ok = ok and bool(np.array_equal(g, r))
                else:
                    ok = ok and bool(np.allclose(g, r, rtol=2e-3,
                                                 atol=2e-3))
                err = max(err, float(np.max(np.abs(
                    g.astype(np.float64) - r.astype(np.float64)))))
            row["pass"] = ok
            row["max_abs_err"] = err
            row["pallas_us"] = round(_time_thunk(pallas_t), 1)
            row["xla_us"] = round(_time_thunk(xla_t), 1)
            passed += ok
        except Exception as e:
            row["pass"] = False
            row["error"] = str(e)[:300]
        rows.append(row)
        print(f"  {name:16s} pass={row.get('pass')} "
              f"pallas={row.get('pallas_us', '-')}us "
              f"xla={row.get('xla_us', '-')}us "
              f"err={row.get('max_abs_err', row.get('error', '-'))}",
              file=sys.stderr)
    result["value"] = passed
    result["total"] = len(rows)
    result["rows"] = rows
    if passed != len(rows):
        result["error"] = (
            f"{len(rows) - passed} of {len(rows)} kernel case(s) failed: "
            + ", ".join(r["kernel"] for r in rows if not r["pass"]))
    return _emit(result)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "serve":
        # `bench.py serve ...` reads like the serve CLI it drives;
        # normalize to the flag form argparse speaks
        argv = ["--serve", *argv[1:]]
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="alexnet")
    p.add_argument("--minibatch", type=int, default=128)
    p.add_argument("--n-train", type=int, dest="n_train", default=512)
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--ticks", type=int, default=4)
    p.add_argument("--dtype", default=None,
                   choices=(None, "float32", "bfloat16"),
                   help="compute dtype for the fused path's MXU operands"
                        " (params/accumulation stay f32)")
    p.add_argument("--storage", default=None,
                   choices=(None, "float32", "bfloat16"),
                   help="dtype activations are stored in between layers"
                        " (bfloat16 halves activation HBM traffic;"
                        " params/grads/loss stay f32)")
    p.add_argument("--kernels", action="store_true")
    p.add_argument("--loader", action="store_true",
                   help="disk→batch loader throughput, no device in "
                        "the loop (combine with --augment for the "
                        "decode→crop variant)")
    p.add_argument("--stream", action="store_true",
                   help="also measure the disk-backed streaming path")
    p.add_argument("--augment", action="store_true",
                   help="also measure with on-device RandomCropFlip in"
                        " the scan (alexnet: decode+29 -> crop)")
    p.add_argument("--mesh", default=None, metavar="DP[,TP]",
                   help="lay the fused step out over a (data, model) "
                        "device mesh, e.g. '4,2'; the row stamps the "
                        "scheme as sharding='dpxtp' so decide_levers "
                        "pairs like-for-like (omitted = '1x1')")
    p.add_argument("--serve", action="store_true",
                   help="request-path bench: boot a real `serve` "
                        "subprocess, drive closed-loop HTTP traffic, "
                        "and stamp a rev-stamped transcript row with "
                        "req/s/core + p50/p99 + device-ms/request "
                        "(`bench.py serve` works too; ROADMAP "
                        "request-path speed arc)")
    p.add_argument("--serve-model", default=None, metavar="PATH",
                   help="serve bench: .znn to serve (default: the "
                        "tiny built-in demo model)")
    p.add_argument("--serve-width", type=int, default=4,
                   help="serve bench: flat input feature count of "
                        "--serve-model (ignored for the demo model)")
    p.add_argument("--serve-clients", type=int, default=4,
                   help="serve bench: concurrent closed-loop client "
                        "threads")
    p.add_argument("--serve-rows", type=int, default=1,
                   help="serve bench: rows per /predict request")
    p.add_argument("--serve-duration-s", type=float, default=5.0,
                   help="serve bench: measured traffic window")
    p.add_argument("--payload", default="json",
                   choices=("json", "binary"),
                   help="serve bench: wire format of the driven "
                        "traffic — json (the historical contract) or "
                        "binary (application/x-znicz-tensor, the "
                        "zero-copy path); stamped into the transcript "
                        "row so trajectories pair like-for-like")
    p.add_argument("--fleet", type=int, default=0, metavar="N",
                   help="serve bench: boot N serve backends behind a "
                        "real `route` process and drive the traffic "
                        "through the ROUTER — the row stamps "
                        "sharding='fleetxN' (device-ms summed across "
                        "backends), so the fabric's forwarding "
                        "overhead vs the single-process rows is a "
                        "measured trajectory (docs/fleet.md)")
    p.add_argument("--router-url", action="append", default=[],
                   metavar="URL",
                   help="serve bench: drive EXISTING router(s) "
                        "instead of booting a fleet — repeatable to "
                        "name an HA pair (primary + hot standbys): "
                        "clients fail over to the next url on "
                        "transport error, a 503 + Retry-After "
                        "takeover refusal stays an answer; the row "
                        "stamps sharding='externalxN' and device-ms "
                        "0 (the backends aren't ours to scrape) "
                        "(docs/fleet.md 'Router high availability')")
    p.add_argument("--placement", action="store_true",
                   help="serve bench with --fleet N: backends serve "
                        "the demo ZOO and the router runs "
                        "--placement 1 — traffic cycles the tenants, "
                        "the row stamps sharding='fleetxN+place' plus "
                        "fleet_resident_bytes/zoo_total_bytes, so the "
                        "footprint win of placement over N-clones is "
                        "measured, not asserted (docs/fleet.md)")
    p.add_argument("--trace-breakdown", action="store_true",
                   help="serve bench: stamp a traceparent on every "
                        "driven request and report the per-stage "
                        "p50/p99 latency decomposition (router-"
                        "assembled in --fleet mode, assembled locally "
                        "from the server's in-band span summary "
                        "otherwise), plus the stage-sum vs e2e "
                        "honesty ratio (docs/observability.md)")
    p.add_argument("--repeat-fraction", type=float, default=0.0,
                   help="serve bench: fraction [0,1] of requests "
                        "reusing ONE fixed input (the rest are "
                        "unique per request) — drives the response-"
                        "memoization hit rate; > 0 boots the server "
                        "with --memoize, and the fraction is stamped "
                        "into the transcript row")
    args = p.parse_args(argv)
    if not 0.0 <= args.repeat_fraction <= 1.0:
        p.error(f"--repeat-fraction must be in [0, 1], "
                f"got {args.repeat_fraction}")
    try:
        if args.serve:
            return bench_serve(args)
        if args.kernels:
            return bench_kernels(args)
        if args.loader:
            return bench_loader(args)
        return bench_training(args)
    except Exception as e:              # the row still prints; rc != 0
        return _emit({"metric": "bench_error", "value": None,
                      "unit": "images/sec", "vs_baseline": None,
                      "error": repr(e)[:600]})


if __name__ == "__main__":
    sys.exit(main())
