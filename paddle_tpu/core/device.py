"""Device / Place management.

TPU-native analog of the reference's DeviceManager + Place system
(paddle/phi/backends/device_manager.h:134, paddle/phi/common/place.h).  Instead of a
registry of driver shims, a Place maps onto a ``jax.Device``; ``set_device`` selects the
default placement used by creation ops (via ``jax.default_device``).
"""
from __future__ import annotations

import contextlib
import threading

import jax


class Place:
    """Base place. Equality follows (device_type, device_id)."""

    device_type = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.device_type, self._device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self._device_id})"

    __str__ = __repr__

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def is_gpu_place(self):
        return self.device_type == "gpu"

    def is_custom_place(self):
        return self.device_type not in ("cpu", "tpu", "gpu")

    # --- mapping to jax ---
    def jax_device(self):
        kind = self.device_type
        plat = jax.default_backend()
        devices = jax.devices()
        if kind == "cpu":
            try:
                devices = jax.devices("cpu")
            except RuntimeError:
                devices = jax.devices()
        elif kind in ("tpu", "gpu"):
            # API parity: "the accelerator" is whatever non-CPU backend jax
            # runs on; on a CPU-only host (the test platform) accelerator
            # places name CPU devices.  Nothing that measures relies on
            # this — chip_smoke.py and benchmark/run.py assert the platform.
            if plat != "cpu":
                devices = jax.devices()
            else:
                devices = jax.devices("cpu")
        idx = min(self._device_id, len(devices) - 1)
        return devices[idx]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPlace(Place):  # API-parity alias; maps to the accelerator if present
    device_type = "gpu"


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


class CustomPlace(Place):
    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


_state = threading.local()


def _accelerator_available() -> bool:
    return jax.default_backend() != "cpu"


def _default_device_str() -> str:
    return "tpu:0" if _accelerator_available() else "cpu"


def set_device(device: str):
    """paddle.set_device (python/paddle/device/__init__.py).  'tpu', 'tpu:0', 'cpu',
    'gpu:0' (aliased to the accelerator) are accepted."""
    if isinstance(device, Place):
        _state.device = f"{device.device_type}:{device.get_device_id()}"
        return _place_from_str(_state.device)
    device = str(device).lower()
    _state.device = device
    return _place_from_str(device)


def get_device() -> str:
    return getattr(_state, "device", None) or _default_device_str()


def _place_from_str(device: str) -> Place:
    if ":" in device:
        kind, idx = device.split(":", 1)
        idx = int(idx)
    else:
        kind, idx = device, 0
    kind = {"cuda": "gpu"}.get(kind, kind)
    if kind == "cpu":
        return CPUPlace(idx)
    if kind in ("tpu", "xpu"):
        return TPUPlace(idx)
    if kind == "gpu":
        return TPUPlace(idx) if _accelerator_available() else CPUPlace(idx)
    return CustomPlace(kind, idx)


def current_place() -> Place:
    return _place_from_str(get_device())


def current_jax_device():
    return current_place().jax_device()


def device_count(kind: str = None) -> int:
    try:
        return len(jax.devices(kind)) if kind else len(jax.devices())
    except RuntimeError:
        return 0


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_custom_device(name: str) -> bool:
    return False


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return []


@contextlib.contextmanager
def device_guard(device: str):
    old = get_device()
    set_device(device)
    try:
        yield
    finally:
        set_device(old)


def synchronize(device=None):
    """paddle.device.synchronize — block until all queued work is done."""
    jax.effects_barrier()
    jax.block_until_ready(jax.numpy.zeros(()))
