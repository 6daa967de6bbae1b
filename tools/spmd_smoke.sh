#!/bin/bash
# SPMD smoke (ISSUE 8 acceptance, operator-runnable): on 8 forced host
# devices,
#   1. a mesh-sharded fused train step (dp=4 x tp=2) through the PUBLIC
#      StandardWorkflow.train(mesh_shape=...) entry point matches the
#      single-device loss trajectory, with params genuinely laid out
#      over all 8 devices;
#   2. the REAL `python -m znicz_tpu serve --replicas 2 --tp 2` CLI
#      serves a concurrent burst with ZERO non-200s, /healthz reports
#      the mesh + per-replica breaker state, and /statusz carries the
#      replica table.
#
# Registered beside tools/metrics_smoke.sh / tools/chaos_smoke.sh;
# tier-1 twin: tests/test_spmd.py.
#
# Usage:  bash tools/spmd_smoke.sh [burst_requests]
set -u -o pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - "${1:-24}" <<'PY'
import json, os, socket, subprocess, sys, tempfile, threading, time
import urllib.error, urllib.request

import jax
jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8, "expected the 8-device virtual mesh"

n_burst = int(sys.argv[1])
fails = []


def check(cond, msg):
    print(("ok  " if cond else "FAIL") + " " + msg)
    if not cond:
        fails.append(msg)


# -- 1. mesh-sharded fused train step vs single device ----------------------
import numpy as np
from znicz_tpu import prng
from znicz_tpu.backends import Device
from znicz_tpu.config import root
from znicz_tpu.models import mnist

root.mnist.synthetic.update({"n_train": 400, "n_valid": 100,
                             "n_test": 100, "noise": 0.35})


def train(mesh_shape):
    prng.seed_all(1234)
    wf = mnist.MnistWorkflow()
    wf.initialize(device=Device.create("xla"))
    tr = wf.train(fused=True, mesh_shape=mesh_shape, max_epochs=2)
    return wf, tr


wf1, _ = train(None)
wf8, tr8 = train((4, 2))
for m1, m8 in zip(wf1.decision.epoch_metrics,
                  wf8.decision.epoch_metrics):
    check(abs(m1["train_loss"] - m8["train_loss"])
          <= 1e-5 * abs(m1["train_loss"]),
          f"epoch {m1['epoch']}: 4x2 train_loss {m8['train_loss']:.6f} "
          f"matches single-device {m1['train_loss']:.6f}")
w8 = tr8.params[0][0]
check(len(w8.sharding.device_set) == 8,
      "fused params laid out over all 8 devices")
check(np.allclose(wf8.forwards[0].weights.mem,
                  wf1.forwards[0].weights.mem, rtol=1e-4, atol=1e-5),
      "written-back weights match single-device within BASELINE tol")

# -- 2. replicated + tensor-parallel serve burst ----------------------------
with tempfile.TemporaryDirectory(prefix="znicz_spmd_smoke_") as tmp:
    model = os.path.join(tmp, "demo.znn")
    from znicz_tpu.resilience.chaos import _write_demo_znn
    _write_demo_znn(model)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, "-m", "znicz_tpu", "serve", "--model", model,
         "--port", str(port), "--max-wait-ms", "1",
         "--replicas", "2", "--tp", "2", "--warmup-shape", "4"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    url = f"http://127.0.0.1:{port}/"
    try:
        for _ in range(240):                    # wait for the listener
            try:
                urllib.request.urlopen(url + "healthz", timeout=2)
                break
            except Exception:
                if proc.poll() is not None:
                    out = proc.stdout.read().decode(errors="replace")
                    sys.exit(f"serve exited rc={proc.returncode}:\n"
                             + out[-2000:])
                time.sleep(0.5)
        else:
            sys.exit("serve never answered /healthz")

        codes, lock = [], threading.Lock()

        def hit(i):
            req = urllib.request.Request(
                url + "predict",
                json.dumps({"inputs": [[0.1, -0.2, 0.3, 0.4]]
                            * (1 + i % 4)}).encode(),
                {"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as r:
                    code = r.status
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception as e:
                code = repr(e)
            with lock:
                codes.append(code)

        threads = [threading.Thread(target=hit, args=(i,))
                   for i in range(n_burst)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        check(len(codes) == n_burst and set(codes) == {200},
              f"burst of {n_burst} concurrent predicts -> all 200 "
              f"(got {sorted(set(codes))})")

        health = json.loads(urllib.request.urlopen(
            url + "healthz", timeout=10).read())
        check(health.get("mesh") == "1x2",
              f"healthz reports the 1x2 serving mesh "
              f"(got {health.get('mesh')!r})")
        reps = health.get("replicas") or []
        check(len(reps) == 2
              and all(r["breaker"] == "closed" for r in reps),
              f"healthz lists 2 replicas, breakers closed ({reps})")
        page = urllib.request.urlopen(url + "statusz",
                                      timeout=10).read().decode()
        check("replicas=2" in page and "tp=2" in page,
              "/statusz carries the mesh/replica topology")
        from znicz_tpu import compilecache
        check("compile_cache: " + compilecache.resolve_dir() in page,
              "/statusz names the persistent compile cache")
    finally:
        proc.terminate()
        try:
            proc.wait(10)
        except subprocess.TimeoutExpired:
            proc.kill()

print()
if fails:
    print(f"SPMD SMOKE FAILED ({len(fails)}):")
    for f in fails:
        print("  - " + f)
    sys.exit(1)
print("SPMD SMOKE PASSED")
PY
