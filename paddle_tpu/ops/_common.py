"""Shared helpers for the kernel library."""

from __future__ import annotations

import collections
import functools
import re
from typing import Dict

import jax

from ..flags import flag_value


def is_tpu_platform(platform: str) -> bool:
    """Single source of the platform policy (the benchmarks reuse it)."""
    return platform == "tpu"


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    # no try/except: a backend that fails to initialise must fail the
    # caller, not silently select the XLA reference kernels
    return is_tpu_platform(jax.devices()[0].platform)


def use_pallas() -> bool:
    return on_tpu() and flag_value("use_pallas_kernels")


_KERNEL_NAME = re.compile(r'kernel_name = "([^"]+)"')


def mosaic_kernels(lowered) -> Dict[str, int]:
    """Pallas kernels in a ``jax.stages.Lowered``: ``pallas_call`` name ->
    number of call sites (each is one ``tpu_custom_call``). A kernel
    missing here gave way to its XLA reference in that program."""
    return dict(collections.Counter(_KERNEL_NAME.findall(lowered.as_text())))
