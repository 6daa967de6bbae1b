"""Device abstraction.

Capability parity with the reference's ``veles/backends.py`` (mount empty —
surveyed contract, SURVEY.md §2.1): a ``Device`` family units dispatch on.
The reference had NumpyDevice / OpenCLDevice / CUDADevice plus a device-info
database of tuned BLOCK_SIZEs.  TPU-first redesign:

* ``NumpyDevice`` — the golden, always-available host path (kept 1:1).
* ``XLADevice``  — JAX/XLA path; wraps the PJRT-visible device set (TPU on
  hardware, CPU in tests).  There is no kernel build/queue management to
  expose: XLA owns compilation and scheduling; what the reference's
  device-info DB did (pick BLOCK_SIZE per device/dtype/op) lives in
  ``znicz_tpu.ops.tuning`` for Pallas kernels.
* Backend selection: ``Device.create("auto"|"numpy"|"xla")`` mirrors the
  reference's CLI backend flag.
"""

from __future__ import annotations

import jax
import numpy as np

from .logger import Logger


def device_report() -> dict:
    """The process's own devices as JAX reports them — the one
    definition behind the trainer's start line and timeline rows,
    ``/healthz``, the bench rows and ``chip_smoke.py``.  Initialises
    the backend."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


class Device(Logger):
    """Base device; knows how to move arrays and run compute."""

    backend_name = "abstract"

    #: True when compute runs through JAX/XLA (accelerated path).
    is_xla = False

    @staticmethod
    def create(backend: str = "auto") -> "Device":
        if backend == "auto":
            backend = "xla"
        if backend == "numpy":
            return NumpyDevice()
        if backend in ("xla", "jax"):
            return XLADevice()
        if backend == "tpu":
            device = XLADevice()
            if not device.is_tpu:
                raise RuntimeError(
                    f"backend 'tpu' requested but JAX's default device "
                    f"is {device.platform!r}")
            return device
        raise ValueError(f"unknown backend {backend!r}")

    def put(self, array):
        raise NotImplementedError

    def get(self, array) -> np.ndarray:
        raise NotImplementedError

    def synchronize(self) -> None:
        pass


class NumpyDevice(Device):
    """Host numpy execution — the reference's golden path, kept as such."""

    backend_name = "numpy"
    is_xla = False

    def put(self, array):
        return np.asarray(array)

    def get(self, array) -> np.ndarray:
        return np.asarray(array)


class XLADevice(Device):
    """JAX/XLA execution (TPU on hardware; CPU backend in CI).

    Replaces the reference's OpenCLDevice/CUDADevice + opencl4py/cuda4py
    bindings: device discovery, memory, compilation and queues are all PJRT's
    job; this class only pins a default device and moves host arrays.
    """

    backend_name = "xla"
    is_xla = True

    def __init__(self, device: "jax.Device | None" = None):
        self.jax_device = device or jax.devices()[0]
        self.platform = self.jax_device.platform

    @property
    def is_tpu(self) -> bool:
        return self.platform == "tpu"

    def put(self, array):
        return jax.device_put(array, self.jax_device)

    def get(self, array) -> np.ndarray:
        return np.asarray(jax.device_get(array))

    def synchronize(self) -> None:
        jax.block_until_ready(
            jax.device_put(np.zeros((), np.float32), self.jax_device))
