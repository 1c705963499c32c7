"""Device / place abstraction.

Reference parity: paddle.CUDAPlace / CPUPlace / set_device ("gpu:0") — here the accelerator
is whatever jax exposes (TPU on real hardware, CPU in CI). A "place" wraps a jax.Device.
There is no per-op device dispatch: XLA owns placement; `to(device)` is `jax.device_put`.
"""
from __future__ import annotations

import jax


class Place:
    """A device handle. Compares by (platform, index)."""

    def __init__(self, device: "jax.Device | None" = None):
        self._device = device if device is not None else jax.devices()[0]

    @property
    def device(self):
        return self._device

    @property
    def platform(self) -> str:
        return self._device.platform

    def get_device_id(self) -> int:
        return self._device.id

    def is_gpu_place(self) -> bool:
        return self._device.platform == "gpu"

    def is_cpu_place(self) -> bool:
        return self._device.platform == "cpu"

    def is_tpu_place(self) -> bool:
        return self._device.platform not in ("cpu", "gpu")

    def __eq__(self, other):
        return isinstance(other, Place) and other._device == self._device

    def __hash__(self):
        return hash(self._device)

    def __repr__(self):
        return f"Place({self._device.platform}:{self._device.id})"


class CPUPlace(Place):
    def __init__(self):
        cpus = [d for d in jax.devices("cpu")] if _has_platform("cpu") else jax.devices()
        super().__init__(cpus[0])


class TPUPlace(Place):
    """The accelerator with this index. Asking for one that is not there
    raises: a CPU handed back under the name of a TPU hides the device."""

    def __init__(self, device_id: int = 0):
        chips = [d for d in jax.devices() if d.platform != "cpu"]
        if not chips:
            raise RuntimeError(
                f"no accelerator in this process: jax.devices() is "
                f"{jax.devices()}")
        if not 0 <= device_id < len(chips):
            raise ValueError(
                f"device index {device_id} out of range: this process has "
                f"{len(chips)} {chips[0].platform} device(s)")
        super().__init__(chips[device_id])


# Alias so scripts written for the reference's `CUDAPlace(0)` keep running on the accelerator.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace
CustomPlace = TPUPlace


def _has_platform(name: str) -> bool:
    try:
        jax.devices(name)
        return True
    except RuntimeError:
        return False


_current_device: Place | None = None


def set_device(device) -> Place:
    """paddle.device.set_device — accepts 'cpu', 'tpu', 'tpu:0', 'gpu:0' (alias), a Place."""
    global _current_device
    if isinstance(device, Place):
        _current_device = device
        return _current_device
    name = str(device)
    if ":" in name:
        plat, _, idx = name.partition(":")
        idx = int(idx)
    else:
        plat, idx = name, 0
    _current_device = CPUPlace() if plat == "cpu" else TPUPlace(idx)
    return _current_device


def get_device() -> str:
    p = get_place()
    return f"{p.platform}:{p.get_device_id()}"


def get_place() -> Place:
    global _current_device
    if _current_device is None:
        _current_device = Place(jax.devices()[0])
    return _current_device


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_cuda() -> bool:  # reference API; always False on the TPU build
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform not in ("cpu", "gpu") for d in jax.devices())
