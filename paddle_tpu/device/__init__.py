"""paddle.device namespace. Reference: python/paddle/device/."""
from __future__ import annotations

import jax

from ..framework.device import (  # noqa: F401
    CPUPlace, CUDAPlace, Place, TPUPlace, XPUPlace, device_count, get_device, get_place,
    is_compiled_with_cuda, is_compiled_with_tpu, is_compiled_with_xpu, set_device,
)

__all__ = ["set_device", "get_device", "get_all_device_type", "get_all_custom_device_type",
           "get_available_device", "get_available_custom_device", "device_count",
           "synchronize", "cuda", "Stream", "Event", "stream_guard", "current_stream",
           "memory_stats", "memory_allocated", "max_memory_allocated",
           "memory_reserved", "max_memory_reserved", "empty_cache"]


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return [p for p in get_all_device_type() if p not in ("cpu", "gpu")]


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices() if d.platform not in ("cpu", "gpu")]


def synchronize(device=None):
    """Block until all queued device work completes (XLA is async by default)."""
    jax.effects_barrier()
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()


# ------------------------------------------------------------------ memory
def _resolve_device(device=None):
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    if hasattr(device, "memory_stats"):
        return device
    plat, _, idx = str(device).partition(":")
    devs = jax.devices(plat) if plat else jax.devices()
    return devs[int(idx) if idx else 0]


def memory_stats(device=None):
    """Raw PJRT allocator stats (reference: phi memory stats / paddle.device.cuda
    memory API family). TPU returns bytes_in_use / peak_bytes_in_use /
    bytes_limit etc.; backends without an instrumented allocator return {}."""
    d = _resolve_device(device)
    return d.memory_stats() or {}


def _live_bytes(d):
    # fallback accounting: sum of live jax arrays resident on this device
    total = 0
    for arr in jax.live_arrays():
        try:
            for sh in arr.addressable_shards:
                if sh.device == d:
                    total += sh.data.nbytes
        except Exception:
            continue
    return total


def memory_allocated(device=None):
    """Bytes currently allocated on the device (live buffers)."""
    d = _resolve_device(device)
    stats = d.memory_stats() or {}
    if "bytes_in_use" in stats:
        return int(stats["bytes_in_use"])
    return _live_bytes(d)


def max_memory_allocated(device=None):
    """Peak bytes allocated (PJRT peak counter; falls back to current)."""
    d = _resolve_device(device)
    stats = d.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        return int(stats["peak_bytes_in_use"])
    return _live_bytes(d)


def memory_reserved(device=None):
    """Bytes the allocator holds from the system (pool size / HBM limit)."""
    d = _resolve_device(device)
    stats = d.memory_stats() or {}
    for key in ("bytes_reserved", "pool_bytes", "bytes_limit"):
        if key in stats:
            return int(stats[key])
    return memory_allocated(device)


max_memory_reserved = memory_reserved


def empty_cache():
    """Release cached host-side references so XLA can reuse device memory
    (XLA's allocator frees buffers when their arrays are garbage-collected)."""
    import gc

    gc.collect()


class Stream:
    """XLA schedules its own streams; this exists for API parity and ordering is a no-op
    (all work on one device is program-ordered)."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()

    def query(self):
        return True


class Event:
    """Timing events: record() syncs the device then timestamps, so
    a.elapsed_time(b) measures real device wall-clock between the records
    (XLA-async safe). query/synchronize are immediate post-sync."""

    def __init__(self, enable_timing=True, blocking=False, interprocess=False):
        self._ts = None

    def record(self, stream=None):
        synchronize()
        import time

        self._ts = time.perf_counter()

    def query(self):
        return True

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end_event):
        """Milliseconds between this record() and `end_event`'s record()."""
        if self._ts is None or end_event._ts is None:
            raise RuntimeError("both events must be recorded before elapsed_time")
        return (end_event._ts - self._ts) * 1e3


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


import contextlib


@contextlib.contextmanager
def stream_guard(stream):
    yield


class cuda:
    """paddle.device.cuda compat shim — maps to the accelerator device."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def current_stream(device=None):
        return _current_stream

    @staticmethod
    def max_memory_allocated(device=None):
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("peak_bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def memory_allocated(device=None):
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def max_memory_reserved(device=None):
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("peak_bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def memory_reserved(device=None):
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("bytes_limit", 0)
        except Exception:
            return 0

    @staticmethod
    def empty_cache():
        pass


class IPUPlace:
    """Reference: paddle.device.IPUPlace — accepted for script parity; no IPU
    backend exists here (the PJRT plugin ABI is the extension point)."""

    def __repr__(self):
        return "Place(ipu)"


def get_cudnn_version():
    """Reference: device/__init__.py — no CUDA stack on TPU builds."""
    return None


def is_compiled_with_cinn():
    """The Pallas kernel layer plays CINN's role (SURVEY §2 row 11); the CINN
    compiler itself is not part of this build."""
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_custom_device(device_name=None):
    """PJRT plugins are the custom-device mechanism: true iff this process
    runs on a platform other than the builtin cpu/gpu ones (libtpu is one)."""
    import jax

    return jax.devices()[0].platform not in ("cpu", "gpu", "cuda")


def is_compiled_with_distribute():
    return True  # jax.distributed + the store control plane always ship


def set_stream(stream=None):
    """Reference: device/__init__.py set_stream — XLA owns stream assignment;
    accepted and ignored (documented no-op, same as the Config stream knobs
    in inference/__init__.py)."""
    return stream
