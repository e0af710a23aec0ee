"""A fixed-size state a row, beside the paged pool: what the recurrent layers
of a model keep.

A paged pool (``ops.paged_attention.PagedKVCacheManager``) grows with a
sequence: a token keeps an entry per attention layer, in pages that are
allocated, shared and freed. A recurrent layer (a selective state-space scan)
keeps the SAME bytes however long the sequence is, so its storage is owned by
the engine's SLOT: one row of each array a slot, no allocation, no refcount,
nothing to free. A slot that is re-used starts from a zero state, which the
step program does itself for a row whose first token is at position 0.

* :class:`StateLayout` — a model's ``state_layout(config)``: which arrays a
  row keeps per recurrent layer, their shapes and dtypes.
* :class:`RowStatePool` — the device arrays for ``rows`` slots, their bytes
  as counted, and the audit the page manager's ``check_conservation`` runs
  over them.

A state cannot be shared by a prefix (a page hit without the state at that
boundary is a wrong answer) nor rolled back (a rejected draft): until a
pool keeps snapshots, the engine refuses ``prefix_cache`` and
``speculative`` for a model with a state layout.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Container, Dict, List, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class StateArray:
    """One array of a row's state: ``(layers,) + lead + (rows,) + trail``.
    ``trail`` ends in the dimension that should lie along the device's 128
    lanes, and ``rows`` should come before a short dimension, not after it:
    the device pads the last dimension to 128 and the one before it to 8
    (16 for two-byte numbers), so ``(rows, 3, d)`` holds ``(rows, 16, d)``
    and ``(3, rows, d)`` holds what it counts."""
    name: str
    lead: Tuple[int, ...]
    trail: Tuple[int, ...]
    dtype: Any

    @property
    def row_nbytes(self) -> int:
        """Bytes one row keeps in this array, one layer."""
        return (math.prod(self.lead) * math.prod(self.trail)
                * jnp.dtype(self.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class StateLayout:
    """What a row keeps in the model's ``layers`` recurrent layers."""
    layers: int
    arrays: Tuple[StateArray, ...]

    @property
    def row_layer_nbytes(self) -> int:
        """Bytes of one row's state in ONE layer."""
        return sum(a.row_nbytes for a in self.arrays)

    @property
    def row_nbytes(self) -> int:
        """Bytes of one row's state, every layer."""
        return self.layers * self.row_layer_nbytes


class RowStatePool:
    """The state arrays of ``rows`` slots, zeros at the start. ``arrays`` is
    what the engine's step takes and returns (donated with the pages)."""

    def __init__(self, layout: StateLayout, rows: int,
                 owners: Optional[List] = None):
        """``owners``: per slot the sequence that holds it, None where free;
        the engine's own list, which it keeps current."""
        self.layout = layout
        self.rows = int(rows)
        self.owners = owners if owners is not None else [None] * self.rows
        self.arrays: Tuple = tuple(
            jnp.zeros((layout.layers,) + a.lead + (self.rows,) + a.trail,
                      a.dtype) for a in layout.arrays)

    @property
    def nbytes(self) -> int:
        """Bytes as counted: rows x what the layout says a row keeps."""
        return self.rows * self.layout.row_nbytes

    def check_conservation(self, sequences: Optional[Container] = None
                           ) -> None:
        """The arrays hold exactly ``rows`` states of the layout's size, one
        slot a row; no sequence owns two rows; and, given the ``sequences``
        that hold pages, every row's owner is one of them."""
        held = sum(int(a.nbytes) for a in self.arrays)
        if held != self.nbytes or len(self.owners) != self.rows:
            raise RuntimeError(
                f"state conservation violated: the arrays hold {held} "
                f"bytes for {len(self.owners)} slots, {self.rows} rows x "
                f"{self.layout.row_nbytes} = {self.nbytes}")
        live = [o for o in self.owners if o is not None]
        if len(live) != len(set(live)):
            raise RuntimeError("a sequence owns two rows of the state pool")
        if sequences is not None:
            lost = [o for o in live if o not in sequences]
            if lost:
                raise RuntimeError(
                    f"rows of state held by sequences without pages: {lost}")

    def snapshot(self) -> Dict[str, int]:
        return {"rows": self.rows, "layers": self.layout.layers,
                "row_bytes": self.layout.row_nbytes, "bytes": self.nbytes,
                "rows_held": self.rows - self.owners.count(None)}
