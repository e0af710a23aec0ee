"""The one place the repo names the jax APIs that have moved between
releases (``shard_map``'s home and its ``check_vma`` kwarg,
``lax.axis_size``, ``jax.distributed.is_initialized``, ``jax.export``).

The code targets the installed jax (0.9) only, so these are direct
aliases — no resolution logic. The module and its names stay so the next
move is a one-file change; a lint rule (tpu-lint ``layer-shard-map``,
tests/test_serving.py::test_no_direct_shard_map_imports) forbids direct
``jax.shard_map`` imports elsewhere.
"""

from __future__ import annotations

import jax
import jax.export
from jax import lax

shard_map = jax.shard_map
axis_size = lax.axis_size


def distributed_is_initialized() -> bool:
    return bool(jax.distributed.is_initialized())


def jax_export():
    """The ``jax.export`` module."""
    return jax.export
