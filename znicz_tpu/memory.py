"""``Vector``: the framework's tensor buffer.

Capability parity with the reference's ``veles/memory.py`` (mount empty —
surveyed contract, SURVEY.md §2.1 "[baseline: Vector buffers]"): a host numpy
array paired with a device buffer, with the ``map_read / map_write /
map_invalidate / unmap`` coherence protocol and ``initialize(device)``.

TPU-first redesign: the device buffer is a ``jax.Array`` (HBM-resident on
TPU).  JAX arrays are immutable and functionally updated, so the reference's
hand-managed coherence collapses to a two-state ownership flag:

* host-owned: ``mem`` (numpy) is authoritative; device copy is stale/absent.
* device-owned: ``devmem`` (jax.Array) is authoritative.

``map_write`` pulls to host and marks host-owned; ``unmap`` pushes to device.
The protocol methods are kept — unit code and tests written against the
reference API read naturally — but misuse cannot corrupt memory the way it
could with raw OpenCL buffers; the flag just avoids needless transfers.
"""

from __future__ import annotations

import jax
import numpy as np

from .backends import Device, NumpyDevice


class Vector:
    """Host+device tensor with explicit (but safe) coherence."""

    def __init__(self, data=None, dtype=None):
        self._mem: np.ndarray | None = None
        self._devmem = None          # jax.Array when device-owned
        self._device: Device | None = None
        self._host_owned = True
        if data is not None:
            self._mem = np.asarray(data, dtype=dtype)

    # -- construction ------------------------------------------------------
    def reset(self, data=None) -> "Vector":
        self._mem = None if data is None else np.asarray(data)
        self._devmem = None
        self._host_owned = True
        return self

    def initialize(self, device: Device | None) -> "Vector":
        """Bind to a device; upload if the device is an XLA device."""
        self._device = device or NumpyDevice()
        if self._mem is not None and self._device.is_xla:
            self.unmap()
        return self

    # -- properties --------------------------------------------------------
    @property
    def mem(self) -> np.ndarray:
        """Host view.  Implicitly maps for read (reference allowed direct
        ``.mem`` access after an explicit map; we keep it safe either way)."""
        if self._mem is None or not self._host_owned:
            self.map_read()
        return self._mem

    @mem.setter
    def mem(self, value):
        self._mem = None if value is None else np.asarray(value)
        self._devmem = None
        self._host_owned = True

    @property
    def devmem(self):
        """Device (jax) array; implicitly unmaps."""
        self.unmap()
        return self._devmem if self._devmem is not None else self._mem

    @devmem.setter
    def devmem(self, value):
        """Direct device-side store (used by xla_run bodies).  The host
        copy, if any, is another value's from here on: dropped, so that
        ``_mem`` is never stale."""
        self._devmem = value
        self._mem = None
        self._host_owned = False

    @property
    def shape(self):
        src = self._mem if self._host_owned or self._devmem is None \
            else self._devmem
        return tuple(src.shape) if src is not None else None

    @property
    def dtype(self):
        src = self._mem if self._host_owned or self._devmem is None \
            else self._devmem
        return src.dtype if src is not None else None

    @property
    def size(self) -> int:
        sh = self.shape
        return 0 if sh is None else int(np.prod(sh))

    def __bool__(self) -> bool:
        return self._mem is not None or self._devmem is not None

    def __len__(self) -> int:
        sh = self.shape
        if sh is None:
            return 0
        if len(sh) == 0:
            raise TypeError("len() of a scalar Vector")
        return sh[0]

    # -- coherence protocol (reference API, SURVEY.md §2.1) ---------------
    def map_read(self) -> "Vector":
        if not self._host_owned and self._devmem is not None:
            self._mem = np.asarray(jax.device_get(self._devmem))
            self._host_owned = True   # device copy still valid until write
        return self

    def map_write(self) -> "Vector":
        self.map_read()
        if self._mem is not None and not self._mem.flags.writeable:
            self._mem = self._mem.copy()
        self._devmem = None           # host will mutate: invalidate device
        return self

    def map_invalidate(self) -> "Vector":
        """Host will overwrite entirely — skip the device→host copy."""
        if self._mem is None and self._devmem is not None:
            self._mem = np.empty(self._devmem.shape,
                                 jax.dtypes.canonicalize_dtype(
                                     self._devmem.dtype))
        self._devmem = None
        self._host_owned = True
        return self

    def unmap(self) -> "Vector":
        """Push host data to device (no-op when the device copy is still
        valid, e.g. after a pure map_read)."""
        if self._host_owned and self._mem is not None:
            if (self._devmem is None and self._device is not None
                    and self._device.is_xla):
                self._devmem = self._device.put(self._mem)
            self._host_owned = self._devmem is None
        return self

    def release_device(self) -> "Vector":
        """Free the device buffer NOW, whoever else refers to the array:
        for a caller that holds the values in another form and knows
        that nobody reads this one meanwhile (the fused trainer's held
        set).  Where the host has the values they are the host's again
        and the next ``devmem`` uploads them anew; values that lived on
        the device alone are gone, and a later read of them raises
        JAX's "Array has been deleted"."""
        if self._devmem is not None:
            self._devmem.delete()
            if self._mem is not None:
                self._devmem = None
                self._host_owned = True
        return self

    # -- conveniences ------------------------------------------------------
    def ascontiguous(self) -> np.ndarray:
        return np.ascontiguousarray(self.mem)

    def __getitem__(self, idx):
        return self.mem[idx]

    def __setitem__(self, idx, value):
        self.map_write()
        self._mem[idx] = value

    def __repr__(self):
        own = "host" if self._host_owned else "device"
        return f"Vector(shape={self.shape}, dtype={self.dtype}, owner={own})"


#: Reference alias (upstream also exported ``Array``).
Array = Vector
