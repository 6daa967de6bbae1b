"""No fallback that hides the device (ISSUE 21): device detection,
the serving backend choice, bench.py's exit codes, chip_smoke.py's
refusal to pass off-TPU, and one process per chip."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(REPO, "bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)

_TPU = {"platform": "tpu", "device_kind": "TPU v5 lite",
        "device_count": 1}


class TestDeviceDetection:
    def test_cpu_is_not_a_tpu(self):
        from znicz_tpu.backends import Device, XLADevice
        from znicz_tpu.ops import tuning
        assert not tuning.on_tpu()
        assert not XLADevice().is_tpu
        assert tuning.kernel_tier() == "xla"
        with pytest.raises(RuntimeError, match="'cpu'"):
            Device.create("tpu")

    def test_peak_table_is_bf16_and_refuses_unknown_devices(self):
        from znicz_tpu.ops import flops
        assert flops.peak_tflops("TPU v5 lite") == 197.0
        with pytest.raises(ValueError, match="cpu"):
            flops.peak_tflops("cpu")


class TestServingBackendChoice:
    def test_jaxless_host_takes_the_native_engine(self, monkeypatch):
        from znicz_tpu.serving import engine
        monkeypatch.setitem(sys.modules, "jax", None)   # import fails
        assert engine._jax_usable() is False

    def test_backend_init_failure_is_an_error(self, monkeypatch):
        """JAX imports but its backend will not initialise (the chip
        is held elsewhere): that must raise, not select the CPU
        engine."""
        import jax

        from znicz_tpu.serving import engine

        def held(*a, **k):
            raise RuntimeError("Unable to initialize backend 'tpu'")
        monkeypatch.setattr(jax, "devices", held)
        with pytest.raises(RuntimeError, match="initialize backend"):
            engine._jax_usable()

    def test_serve_exits_nonzero_when_the_backend_is_unavailable(
            self, tmp_path):
        from znicz_tpu.resilience.chaos import _write_demo_znn
        model = str(tmp_path / "demo.znn")
        _write_demo_znn(model)
        proc = subprocess.run(
            [sys.executable, "-m", "znicz_tpu", "serve", "--model",
             model, "--port", "0"],
            capture_output=True, text=True, timeout=120, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "no_such_platform"})
        assert proc.returncode != 0
        assert "serving" not in proc.stdout, proc.stdout[-500:]


class TestBenchExitCodes:
    @pytest.fixture
    def on_chip(self, monkeypatch):
        """Past the device gate, as on a TPU (the measurement itself
        is faked by each test)."""
        def gate(result):
            result["device"] = dict(_TPU)
            return True
        monkeypatch.setattr(bench, "_require_tpu", gate)

    def test_no_accelerator_is_nonzero_and_names_the_device(self,
                                                            capsys):
        assert bench.main(["--config", "mnist"]) == 1
        row = json.loads(capsys.readouterr().out.strip())
        assert row["value"] is None
        assert row["device"]["platform"] == "cpu"
        assert "no accelerator" in row["error"]

    def test_failed_measurement_is_nonzero(self, on_chip, monkeypatch,
                                           capsys):
        def boom(*a, **k):
            raise RuntimeError("kernel does not compile")
        monkeypatch.setattr(bench, "_build", boom)
        assert bench.main(["--config", "mnist"]) == 1
        row = json.loads(capsys.readouterr().out.strip())
        assert row["device"] == _TPU and row["value"] is None
        assert "kernel does not compile" in row["error"]

    def test_failed_kernel_case_is_nonzero(self, on_chip, monkeypatch,
                                           capsys):
        from znicz_tpu.ops import tuning
        one = np.ones((4, 4), np.float32)
        monkeypatch.setattr(tuning, "use_pallas", lambda: True)
        monkeypatch.setattr(bench, "_kernel_cases", lambda: [
            ("good", lambda: one, lambda: one, "exact"),
            ("bad", lambda: one, lambda: one * 2, "close")])
        assert bench.main(["--kernels"]) == 1
        row = json.loads(capsys.readouterr().out.strip())
        assert (row["value"], row["total"]) == (1, 2)
        assert "bad" in row["error"]


class TestChipSmoke:
    def test_refuses_to_pass_off_tpu(self, tmp_path):
        """The no-fallback property itself: on a CPU-only host the
        smoke exits non-zero, names the platform it found and prints
        no result line."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"),
             "--out", str(tmp_path)],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode != 0
        assert "platform 'cpu'" in proc.stderr
        assert '"ok"' not in proc.stdout


_PARENTS_PROBE = r"""
import importlib.util, json, os, subprocess, sys
repo, out = sys.argv[1], sys.argv[2]
seen = {}

def load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(repo, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

class Spawned(Exception):
    pass

def popen(key, probe):
    def fake(*a, **k):
        seen.setdefault(key, []).append(probe())
        raise Spawned(key)
    return fake

smoke = load("chip_smoke")
subprocess.Popen = popen("chip_smoke", lambda: "jax" in sys.modules)
try:
    smoke.main(["--out", out])
except Spawned:
    pass

bench = load("bench")
from jax._src import xla_bridge
subprocess.Popen = popen("bench_serve",
                         xla_bridge.backends_are_initialized)
bench.main(["serve", "--fleet", "2", "--placement"])
bench.main(["serve"])
print(json.dumps(seen))
"""


class TestOneProcessPerChip:
    def test_parents_hold_no_backend_when_children_start(self,
                                                         tmp_path):
        """chip_smoke's parent never imports jax; bench serve's parent
        (demo model, demo zoo) has initialised no backend by the time
        it boots its serve children."""
        proc = subprocess.run(
            [sys.executable, "-c", _PARENTS_PROBE, REPO, str(tmp_path)],
            capture_output=True, text=True, timeout=120, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr[-2000:]
        seen = json.loads(proc.stdout.strip().splitlines()[-1])
        assert seen["chip_smoke"] == [False]
        assert seen["bench_serve"] == [False, False]
