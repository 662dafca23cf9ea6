"""Places — device abstraction.

Reference: paddle/fluid/platform/place.h (CPUPlace/CUDAPlace/CUDAPinnedPlace) and
python/paddle/device.py. Here a Place wraps a jax device; TPUPlace is the
native accelerator place, CUDAPlace is accepted as an alias so reference-era
user code runs unchanged.
"""
from __future__ import annotations

import jax


class Place:
    """Base place: a logical device slot."""

    _kind = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def jax_device(self):
        """The jax device this place names.  A place is something the
        caller asked for explicitly, so a kind this host does not have,
        or an index past its last device, is an error — never another
        device handed back quietly."""
        devs = [d for d in jax.devices() if self._matches(d)]
        if not devs:
            raise RuntimeError(
                f"{self!r}: this host has no {self._kind} device (jax "
                f"found {sorted({d.platform for d in jax.devices()})})")
        if not 0 <= self._device_id < len(devs):
            raise ValueError(
                f"{self!r}: device index {self._device_id} is past the "
                f"last of this host's {len(devs)} {self._kind} device(s)")
        return devs[self._device_id]

    def _matches(self, d) -> bool:
        return True

    def __eq__(self, other):
        return type(self) is type(other) and self._device_id == other._device_id

    def __hash__(self):
        return hash((type(self).__name__, self._device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self._device_id})"


class CPUPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)

    def _matches(self, d):
        return d.platform == "cpu"


class TPUPlace(Place):
    _kind = "tpu"

    def _matches(self, d):
        return d.platform != "cpu"


# Alias: reference code constructing CUDAPlace(i) lands on the accelerator.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace
CUDAPinnedPlace = CPUPlace

_current_device = None


def _accelerator_available() -> bool:
    try:
        return any(d.platform != "cpu" for d in jax.devices())
    except Exception:
        return False


def set_device(device):
    """paddle.set_device: 'cpu', 'tpu', 'tpu:0', 'gpu:0' (alias of tpu)."""
    global _current_device
    if isinstance(device, Place):
        place = device
    else:
        name, _, idx = str(device).partition(":")
        idx = int(idx) if idx else 0
        if name in ("cpu",):
            place = CPUPlace()
        elif name in ("tpu", "gpu", "cuda", "xpu", "npu"):
            place = TPUPlace(idx)
        else:
            raise ValueError(f"unknown device {device!r}")
    place.jax_device()  # an explicitly named device must exist: raises
    _current_device = place
    return place


def get_device():
    p = _expected_place()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"tpu:{p.get_device_id()}"


def _expected_place() -> Place:
    global _current_device
    if _current_device is None:
        _current_device = TPUPlace(0) if _accelerator_available() else CPUPlace()
    return _current_device


def is_compiled_with_cuda() -> bool:  # reference API parity
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_xpu() -> bool:
    return False
