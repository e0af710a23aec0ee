"""``paddle_tpu.device`` — device query/control namespace.

Parity with python/paddle/device/ of the reference. The accelerator
here is whatever jax exposes (the TPU on a chip host, CPU in tests); the
CUDA/XPU sub-namespaces exist with honest "not compiled in" answers,
the same shape the reference gives on a CPU-only build.
"""

from __future__ import annotations

import types

import jax

from .core.place import current_place, set_device, get_device  # noqa: F401

__all__ = [
    "set_device", "get_device", "get_all_device_type",
    "get_available_device", "get_device_count", "device_count",
    "synchronize", "is_compiled_with_cuda", "is_compiled_with_rocm",
    "is_compiled_with_xpu", "is_compiled_with_distribute",
    "cuda", "xpu",
]


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()} | {"cpu"})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_device_count() -> int:
    return len(jax.devices())


device_count = get_device_count


def synchronize(device=None):
    """Block until pending work on the device finishes. XLA programs
    synchronize through value dependencies; this drains the async
    dispatch queue (jax.effects_barrier would need a live trace)."""
    jax.block_until_ready(jax.live_arrays())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_distribute() -> bool:
    return True  # jax.distributed + the launcher stack


def _stub_ns(name: str) -> types.ModuleType:
    m = types.ModuleType(f"{__name__}.{name}")
    m.device_count = lambda: 0
    m.current_device = lambda: None
    m.get_device_name = lambda device=None: None
    m.get_device_capability = lambda device=None: None
    m.synchronize = lambda device=None: None
    m.empty_cache = lambda: None
    m.max_memory_allocated = lambda device=None: 0
    m.max_memory_reserved = lambda device=None: 0
    m.memory_allocated = lambda device=None: 0
    m.memory_reserved = lambda device=None: 0
    return m


#: reference paddle.device.cuda / paddle.device.xpu — zero devices here
cuda = _stub_ns("cuda")
xpu = _stub_ns("xpu")
