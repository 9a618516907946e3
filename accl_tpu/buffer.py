"""Device buffers: host-visible arrays bound to a backend "device memory".

Parity: the reference driver wraps pynq buffers (device DDR/HBM) or
``SimBuffer`` (numpy array + fake 4K-aligned physical address talking to the
emulator over ZMQ, driver/pynq/accl.py:53-104). Calls pass device addresses;
``sync_to_device``/``sync_from_device`` move data across the host/device
boundary.

TPU-native design: a buffer is either
  * an emulator buffer — numpy array registered in the rank daemon's
    devicemem under an integer address (4 KiB aligned, like SimBuffer), or
  * a TPU buffer — a ``jax.Array`` (possibly sharded over the communicator's
    mesh axis); sync_* are device_put/device_get and the "address" is a
    handle the in-process backend resolves back to the array.

Device-resident mode (the reference's ``to_from_fpga=False`` fast path,
test/host/test_tcp_cmac_seq_mpi.py:29-443): pass a live ``jax.Array`` as
``data`` and the buffer keeps it on device — no host mirror is allocated,
and TPU-backend calls operate on the array directly instead of staging
through host numpy. ``.data`` then returns a fresh host *snapshot* (reads
pay one D2H transfer; in-place writes to the snapshot do NOT reach the
device — use ``.jax`` / a new call instead). jax.Arrays are immutable, so
the backend "writes" a result by rebinding ``.jax`` to a new array.
"""

from __future__ import annotations

import math
import threading
from typing import Any

import numpy as np

_ALIGNMENT = 4096
_alloc_lock = threading.Lock()
_next_page = 1


def _alloc_addr(nbytes: int) -> int:
    """Fake physical address allocator, 4 KiB aligned (SimBuffer parity,
    accl.py:61-66). Thread-safe: reserves all pages atomically."""
    global _next_page
    pages = max(1, -(-nbytes // _ALIGNMENT))
    with _alloc_lock:
        page = _next_page
        _next_page += pages
    return page * _ALIGNMENT


def _is_jax_array(x) -> bool:
    """Duck-typed jax.Array check that keeps jax an optional import here."""
    return hasattr(x, "sharding") and hasattr(x, "devices")


class ACCLBuffer:
    """A host array registered with a device backend.

    The backend (device/base.py) decides what ``sync_*`` and ``address``
    mean. Supports slicing into sub-buffers sharing storage — the reference
    relies on address arithmetic for strided collective operands; we expose
    the same capability safely via numpy views.

    When constructed from a ``jax.Array`` the buffer is *device-resident*
    (module docstring): no host mirror, ``.jax`` is the live array.
    """

    def __init__(self, shape, dtype=np.float32, device: Any = None,
                 data=None, address: int | None = None,
                 parent: "ACCLBuffer | None" = None):
        self._jax = None
        if data is not None and _is_jax_array(data):
            self._jax = data
            self._np = None
        else:
            if data is None:
                data = np.zeros(shape, dtype=dtype)
            self._np = data
        # geometry is cached: an array's shape/dtype never change, and
        # the properties sit on the per-call hot path (a rebind refreshes
        # the cache)
        src = self._jax if self._jax is not None else self._np
        self._shape = tuple(src.shape)
        self._dtype = np.dtype(src.dtype)
        self._size = math.prod(self._shape)
        nbytes = self._dtype.itemsize * self._size
        self.device = device
        self.parent = parent
        self.address = address if address is not None else _alloc_addr(nbytes)
        if device is not None and parent is None:
            device.register_buffer(self)

    # -- device-resident surface -------------------------------------------
    @property
    def is_device_resident(self) -> bool:
        return self._jax is not None

    @property
    def jax(self):
        """The live device array (device-resident buffers only)."""
        if self._jax is None:
            raise ValueError("not a device-resident buffer; use .data")
        return self._jax

    def _rebind(self, arr):
        """Backend-side result write: point the buffer at a new array
        (jax.Arrays are immutable — there is no in-place device write)."""
        self._jax = arr
        self._shape = tuple(arr.shape)
        self._dtype = np.dtype(arr.dtype)
        self._size = math.prod(self._shape)

    def _swap(self, arr):
        """:meth:`_rebind` for an array the backend has proven to have
        this buffer's shape and dtype: only the array changes."""
        self._jax = arr

    # -- numpy-ish surface -------------------------------------------------
    @property
    def data(self) -> np.ndarray:
        """The host array (mirror mode) or a fresh host snapshot of the
        device array (device-resident mode — writes to it are lost)."""
        if self._jax is not None:
            return np.asarray(self._jax)
        return self._np

    @property
    def shape(self):
        return self._shape

    @property
    def dtype(self) -> np.dtype:
        return self._dtype

    @property
    def size(self) -> int:
        return self._size

    @property
    def nbytes(self) -> int:
        return self._dtype.itemsize * self._size

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, key) -> "ACCLBuffer":
        """A view sub-buffer; address tracks the byte offset into the parent."""
        if self._jax is not None:
            raise ValueError(
                "device-resident buffers do not support sub-buffer views "
                "(jax.Arrays have no host address arithmetic); slice the "
                "array before wrapping, or use a host-mirror buffer")
        view = self._np[key]
        if view.base is None and view is not self._np:
            raise ValueError("buffer slices must be views (no fancy indexing)")
        if not view.flags["C_CONTIGUOUS"]:
            raise ValueError(
                "buffer slices must be contiguous (the device address model "
                "transfers flat byte ranges); use a copy for strided access")
        offset = view.__array_interface__["data"][0] - \
            self._np.__array_interface__["data"][0]
        return ACCLBuffer(view.shape, view.dtype, device=self.device,
                          data=view, address=self.address + offset, parent=self)

    def __array__(self, dtype=None):
        return np.asarray(self.data, dtype=dtype)

    # -- host/device movement ---------------------------------------------
    def sync_to_device(self):
        """Push host contents to device memory (pynq sync_to_device parity)."""
        if self.device is not None:
            self.device.sync_to_device(self)
        return self

    def sync_from_device(self):
        """Pull device memory into the host array."""
        if self.device is not None:
            self.device.sync_from_device(self)
        return self

    def free_buffer(self):
        if self.device is not None and self.parent is None:
            self.device.deregister_buffer(self)

    def __repr__(self):
        kind = "dev" if self._jax is not None else "host"
        return (f"ACCLBuffer(shape={self.shape}, dtype={self.dtype.name}, "
                f"addr=0x{self.address:x}, {kind})")
