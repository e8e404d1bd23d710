"""Columnar, block-structured tables.

A :class:`BlockTable` is the TPU-native analogue of a DBMS heap file: every
column is one contiguous 1-D array of length ``num_blocks * block_rows`` and a
*block* — the paper's "minimum unit of data accessing in the storage layer" —
is a contiguous ``block_rows`` slab of every column.  Block sampling therefore
touches only the sampled slabs (HBM→VMEM DMA granularity), while row-level
Bernoulli sampling must stream every slab (mask-based).  This reproduces the
system-efficiency asymmetry of Fig. 1/Fig. 4 on-device.

Rows carry two pieces of lineage that BSAP needs:

* ``valid``    — row liveness (filters/joins clear bits instead of compacting,
                 keeping shapes static for jit),
* ``block_id`` — the *origin* block index in the base table.  Relational
                 operators preserve it (Props. 4.4–4.6: block sampling commutes
                 with selection/join/union), so per-block pilot statistics can
                 be computed after arbitrary plan suffixes.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np


_VALID_BYTES_LOCK = threading.Lock()


@dataclasses.dataclass
class BlockTable:
    """A columnar table with a fixed physical block size."""

    name: str
    columns: Dict[str, jnp.ndarray]  # each shape (num_blocks * block_rows,)
    block_rows: int
    num_rows: int  # logical rows (<= padded length)
    valid: Optional[jnp.ndarray] = None  # bool, same shape as columns
    block_id: Optional[jnp.ndarray] = None  # int32 origin block per row
    num_origin_blocks: Optional[int] = None  # blocks in the *base* table
    # ``valid`` as int8, made on first use (see :meth:`valid_bytes`)
    _valid_bytes: Optional[jnp.ndarray] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def __post_init__(self):
        n = self.padded_rows
        for cname, col in self.columns.items():
            if col.shape != (n,):
                raise ValueError(
                    f"column {cname!r} has shape {col.shape}, expected ({n},)")
        if self.valid is None:
            valid = np.zeros(n, dtype=bool)
            valid[: self.num_rows] = True
            self.valid = jnp.asarray(valid)
        if self.block_id is None:
            self.block_id = jnp.asarray(
                np.repeat(np.arange(self.num_blocks, dtype=np.int32), self.block_rows))
        if self.num_origin_blocks is None:
            self.num_origin_blocks = self.num_blocks

    # -- geometry ----------------------------------------------------------
    @property
    def padded_rows(self) -> int:
        some = next(iter(self.columns.values()))
        return int(some.shape[0])

    @property
    def num_blocks(self) -> int:
        return self.padded_rows // self.block_rows

    @property
    def column_names(self):
        return list(self.columns.keys())

    def valid_bytes(self) -> jnp.ndarray:
        """``valid`` as int8 (1 live, 0 not), made once and kept with the
        table: a Pallas kernel cannot take a bool operand without a cast of
        the whole column to int32 on every call.  A table made by
        :meth:`with_valid` (or any other copy) makes its own."""
        if self._valid_bytes is None:
            # worker threads meet a table's first exact scan together: one
            # makes the copy, the others take it
            with _VALID_BYTES_LOCK:
                if self._valid_bytes is None:
                    self._valid_bytes = self.valid.astype(jnp.int8)
        return self._valid_bytes

    def row_bytes(self) -> int:
        return sum(int(np.dtype(c.dtype).itemsize) for c in self.columns.values())

    def total_bytes(self) -> int:
        return self.row_bytes() * self.padded_rows

    # -- constructors --------------------------------------------------------
    @staticmethod
    def from_numpy(name: str, columns: Dict[str, np.ndarray], block_rows: int) -> "BlockTable":
        num_rows = len(next(iter(columns.values())))
        pad = (-num_rows) % block_rows
        cols = {}
        for cname, col in columns.items():
            col = np.asarray(col)
            if pad:
                col = np.concatenate([col, np.zeros(pad, dtype=col.dtype)])
            cols[cname] = jnp.asarray(col)
        return BlockTable(name=name, columns=cols, block_rows=block_rows, num_rows=num_rows)

    # -- derived tables -----------------------------------------------------
    def with_valid(self, valid: jnp.ndarray) -> "BlockTable":
        return dataclasses.replace(self, valid=valid)

    def with_columns(self, columns: Dict[str, jnp.ndarray]) -> "BlockTable":
        return dataclasses.replace(self, columns=columns)

    def gather_blocks(self, block_indices: np.ndarray) -> "BlockTable":
        """Materialize only the given blocks (the block-sampling fast path).

        The result re-labels physical blocks 0..k-1 but keeps ``block_id``
        pointing at the *origin* block indices so BSAP statistics stay valid.
        """
        block_indices = np.asarray(block_indices, dtype=np.int32)
        row_idx = (block_indices[:, None] * self.block_rows
                   + np.arange(self.block_rows, dtype=np.int32)[None, :]).reshape(-1)
        row_idx_j = jnp.asarray(row_idx)
        cols = {c: v[row_idx_j] for c, v in self.columns.items()}
        return BlockTable(
            name=self.name,
            columns=cols,
            block_rows=self.block_rows,
            num_rows=len(row_idx),
            valid=self.valid[row_idx_j],
            block_id=self.block_id[row_idx_j],
            num_origin_blocks=self.num_origin_blocks,
        )

    def to_numpy(self) -> Dict[str, np.ndarray]:
        mask = np.asarray(self.valid)
        return {c: np.asarray(v)[mask] for c, v in self.columns.items()}
