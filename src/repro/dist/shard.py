"""Partitioned tables: disjoint block-range shards of a :class:`BlockTable`.

A :class:`ShardedTable` splits a block table into N contiguous block-range
partitions.  Blocks — the paper's minimum unit of data accessing — are the
atomic placement unit and are never split across shards, which is what makes
every per-block BSAP statistic *mergeable*: block sampling commutes with
selection/join/union (Props. 4.4-4.6), so pilot and final aggregation state
computed independently per shard combines by concatenation/summation without
weakening the a-priori error guarantees (the same observation VerdictDB and
BlinkDB exploit to scale out).

Placement.  Each shard's column slices are materialized as their own device
arrays; with more than one JAX device available they are placed round-robin
(``jax.device_put``), otherwise they stay host-local (the CPU-hosts case).
Shard rows keep their GLOBAL origin ``block_id`` labels, so merged per-block
statistics index the same block space as the monolithic table.

Sampling.  ``shard_block_ids`` restricts the table's ONE content-derived
Bernoulli realization (``sampling.draw_block_ids`` — a pure function of the
query-content seed) to each shard's block range.  Every shard can compute
its own sub-draw locally from the shared seed, and the union of the
sub-draws *is* the monolithic draw — so the sampled block set is
bit-identical regardless of shard count.  (Independent per-shard seeds
would also yield a valid Bernoulli sample but a *different* realization per
shard count, silently breaking equal-seed replay.)
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.engine.sampling import draw_block_ids, restrict_block_ids
from repro.engine.table import BlockTable


@dataclasses.dataclass(frozen=True)
class Shard:
    """One block-range partition: blocks ``[start_block, end_block)`` of the
    base table, materialized as a standalone :class:`BlockTable` whose
    ``block_id`` column carries the *global* origin block indices."""

    index: int
    start_block: int
    end_block: int
    table: BlockTable

    @property
    def num_blocks(self) -> int:
        return self.end_block - self.start_block

    def local_ids(self, global_ids: np.ndarray) -> np.ndarray:
        """Global sampled block ids restricted to this shard, re-based to
        the shard's local block space (see ``sampling.restrict_block_ids``
        for why restriction — not independent seeding — is load-bearing)."""
        return restrict_block_ids(global_ids, self.start_block,
                                  self.end_block)


@dataclasses.dataclass
class ShardedTable:
    """N disjoint, contiguous block-range partitions of one block table."""

    name: str
    shards: List[Shard]
    num_blocks: int          # global block count (== base table's)
    block_rows: int
    row_bytes: int

    @property
    def num_shards(self) -> int:
        return len(self.shards)

    @staticmethod
    def from_table(table: BlockTable, num_shards: int,
                   devices: Optional[Sequence] = None) -> "ShardedTable":
        """Partition ``table`` into ``num_shards`` contiguous block ranges.

        ``devices`` (default: ``jax.devices()``) receive the shard arrays
        round-robin when more than one is available; on a single-device
        host every shard stays local and "distribution" degenerates to
        independent dispatches over disjoint slices — the semantics (and
        the bit-identity guarantees) are placement-independent.
        """
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        n_blocks = table.num_blocks
        if num_shards > n_blocks:
            raise ValueError(
                f"cannot split {n_blocks} blocks into {num_shards} shards "
                "(blocks are the atomic placement unit)")
        if devices is None:
            import jax
            devices = jax.devices()
        bounds = _shard_bounds(n_blocks, num_shards)
        shards: List[Shard] = []
        for i, (lo, hi) in enumerate(bounds):
            dev = devices[i % len(devices)] if len(devices) > 1 else None
            shards.append(Shard(index=i, start_block=lo, end_block=hi,
                                table=_slice_blocks(table, lo, hi, dev)))
        return ShardedTable(name=table.name, shards=shards,
                            num_blocks=n_blocks, block_rows=table.block_rows,
                            row_bytes=table.row_bytes())

    def partition_ids(self, global_ids: np.ndarray) -> List[Tuple[Shard, np.ndarray]]:
        """Split a global sampled-id set into non-empty per-shard sub-draws
        (ascending shard order; ascending local ids within each shard —
        concatenating the per-shard results therefore recovers the global
        ascending order, which the merge layer relies on)."""
        out: List[Tuple[Shard, np.ndarray]] = []
        for shard in self.shards:
            local = shard.local_ids(global_ids)
            if len(local):
                out.append((shard, local))
        return out


def _shard_bounds(n_blocks: int, num_shards: int) -> List[Tuple[int, int]]:
    """Contiguous near-even block ranges (``np.array_split`` semantics)."""
    base, extra = divmod(n_blocks, num_shards)
    bounds, lo = [], 0
    for i in range(num_shards):
        hi = lo + base + (1 if i < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def _slice_blocks(table: BlockTable, lo: int, hi: int, device) -> BlockTable:
    """Materialize blocks ``[lo, hi)`` as a standalone BlockTable with
    GLOBAL ``block_id`` labels (optionally placed on ``device``)."""
    import jax

    br = table.block_rows
    sl = slice(lo * br, hi * br)

    def place(arr):  # device None: the default device
        return jax.device_put(arr, device)

    n_rows = min(hi * br, table.num_rows) - min(lo * br, table.num_rows)
    return BlockTable(
        name=table.name,
        columns={c: place(v[sl]) for c, v in table.columns.items()},
        block_rows=br,
        num_rows=max(n_rows, 0),
        valid=place(table.valid[sl]),
        block_id=place(np.repeat(np.arange(lo, hi, dtype=np.int32), br)),
        # origin ids are global: merged per-block statistics index the
        # monolithic block space
        num_origin_blocks=table.num_origin_blocks,
    )


def shard_block_ids(num_blocks: int, rate: float, seed: int,
                    sharded: ShardedTable) -> Tuple[np.ndarray, List[Tuple[Shard, np.ndarray]]]:
    """The distributed TABLESAMPLE decision: ONE global Bernoulli
    realization (the same stream the monolithic samplers consume — see
    :func:`repro.engine.sampling.draw_block_ids`), restricted per shard.

    Returns ``(global_ids, [(shard, local_ids), ...])`` with empty shards
    omitted.  The union of the per-shard sub-draws equals the monolithic
    draw exactly, for any shard count — the cornerstone of the dist layer's
    bit-identity guarantees.
    """
    global_ids = draw_block_ids(num_blocks, rate, seed)
    return global_ids, sharded.partition_ids(global_ids)
