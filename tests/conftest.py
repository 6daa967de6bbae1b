"""Test env: force CPU JAX with an 8-device virtual mesh BEFORE jax import.

Mirrors the reference test strategy (SURVEY.md §4): numpy is the golden
backend always available in CI; accelerated paths are cross-checked against
it; distributed paths run on a virtual multi-device CPU mesh.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", "tests must run on CPU"
assert len(jax.devices()) == 8, "virtual 8-device CPU mesh expected"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # registered here (no pytest.ini in this repo) so `-m 'not slow'`
    # tier-1 and `-m chaos` run without unknown-marker warnings
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")
    config.addinivalue_line(
        "markers", "chaos: seeded fault-injection tests driven by "
                   "znicz_tpu.resilience.FaultPlan (deterministic, "
                   "in-process; part of tier-1)")
    config.addinivalue_line(
        "markers", "lint: zlint static-analysis gate "
                   "(znicz_tpu.analysis over the whole package; part "
                   "of tier-1, runnable standalone via `pytest -m "
                   "lint`)")
    config.addinivalue_line(
        "markers", "san: zsan runtime concurrency-sanitizer lane "
                   "(znicz_tpu.sanitizer around real lock traffic; "
                   "part of tier-1, runnable standalone via `pytest "
                   "-m san` — tools/san_smoke.sh)")


@pytest.fixture(autouse=True)
def _seeded():
    """Every test starts from the same global seed (reference StandardTest
    pins seeds, SURVEY.md §4)."""
    from znicz_tpu import prng
    prng.seed_all(1234)
    np.random.seed(1234)
    yield


@pytest.fixture
def numpy_device():
    from znicz_tpu.backends import NumpyDevice
    return NumpyDevice()


@pytest.fixture
def xla_device():
    from znicz_tpu.backends import XLADevice
    return XLADevice()
