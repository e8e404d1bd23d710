"""Physical relational operators on BlockTables (pure jnp, static shapes)."""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.engine.expr import Expr, eval_expr
from repro.engine.table import BlockTable

_BIG = np.int32(2**31 - 1)  # keys must be < 2^31-1 (x64 is off)


def filter_table(table: BlockTable, pred: Expr) -> BlockTable:
    mask = eval_expr(pred, table.columns)
    return table.with_valid(table.valid & mask)


def join_unique(left: BlockTable, right: BlockTable, left_key: str,
                right_key: str, rblock_col: Optional[str] = None) -> BlockTable:
    """Equi-join where ``right_key`` is unique among valid right rows.

    Preserves the left table's physical layout and block lineage (Prop. 4.5).
    Right columns are appended; optionally the right row's *origin block id*
    is exported as ``rblock_col`` — the pair lineage Lemma 4.8 needs.
    """
    lkey = left.columns[left_key].astype(jnp.int32)
    rkey = jnp.where(right.valid, right.columns[right_key].astype(jnp.int32), _BIG)
    order = jnp.argsort(rkey)
    sorted_keys = rkey[order]
    pos = jnp.searchsorted(sorted_keys, lkey)
    pos_c = jnp.clip(pos, 0, sorted_keys.shape[0] - 1)
    found = sorted_keys[pos_c] == lkey
    match = order[pos_c]
    valid = left.valid & found

    new_cols = dict(left.columns)
    for cname, col in right.columns.items():
        if cname == right_key:
            continue
        if cname in new_cols:
            raise ValueError(f"column name collision in join: {cname}")
        new_cols[cname] = col[match]
    if rblock_col is not None:
        new_cols[rblock_col] = right.block_id[match].astype(jnp.int32)
    return dataclasses.replace(left, columns=new_cols, valid=valid)


def union_all(tables: list[BlockTable]) -> BlockTable:
    """Bag union; block ids are offset so origins stay distinct (Prop. 4.6)."""
    if not tables:
        raise ValueError("empty union")
    br = tables[0].block_rows
    names = set(tables[0].columns)
    offset = 0
    cols = {c: [] for c in names}
    valids, bids = [], []
    rows = 0
    for t in tables:
        if set(t.columns) != names or t.block_rows != br:
            raise ValueError("union inputs must share schema and block size")
        for c in names:
            cols[c].append(t.columns[c])
        valids.append(t.valid)
        bids.append(t.block_id + offset)
        offset += t.num_origin_blocks
        rows += t.num_rows
    return BlockTable(
        name="union",
        columns={c: jnp.concatenate(v) for c, v in cols.items()},
        block_rows=br,
        num_rows=rows,
        valid=jnp.concatenate(valids),
        block_id=jnp.concatenate(bids),
        num_origin_blocks=offset,
    )


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------

def grouped_totals(table: BlockTable, exprs, group_by: Optional[str],
                   max_groups: int):
    """(sums (num_aggs, max_groups), counts (max_groups,)): each expr's sum
    and the valid rows per group, summed as the compiled route sums them
    (``physical.blockwise_totals``, the counts as a trailing COUNT
    channel), so that the two routes agree bit for bit."""
    from repro.engine import physical

    totals = physical.blockwise_totals(
        table.columns, table.valid, tuple(exprs) + (None,), group_by,
        max_groups, table.block_rows, None)
    return totals[:-1], totals[-1]


def block_group_sums(table: BlockTable, exprs, group_by: Optional[str],
                     max_groups: int, block_ids: np.ndarray) -> np.ndarray:
    """Per-(origin-block, group) sums: shape (len(block_ids), max_groups, num_aggs).

    This is the pilot query's "GROUP BY physical block" (§3.3 step 2) — the
    statistics BSAP consumes.  ``block_ids`` lists the sampled origin blocks;
    blocks without surviving rows contribute zeros (they are real population
    units with zero contribution).

    Built on the physical layer's fused multi-channel scatter: all channels
    run in one jitted graph and the device→host transfer happens exactly once
    at this boundary (the compiled pilot path in ``engine.physical`` avoids
    even that — this entry point serves the eager executor and direct users).
    """
    from repro.engine import physical

    dense = physical.dense_block_group_sums(
        table.columns, table.valid, table.block_id,
        exprs=tuple(exprs), group_by=group_by, max_groups=max_groups,
        n_origin=int(table.num_origin_blocks))
    stacked = np.asarray(dense).transpose(1, 2, 0)  # (n_origin, groups, aggs)
    return stacked[np.asarray(block_ids, dtype=np.int64)]


def block_pair_sums(table: BlockTable, exprs, lblock_ids: np.ndarray,
                    rblock_col: str, n_right_blocks: int) -> np.ndarray:
    """Per-(left origin block, right origin block) sums for Lemma 4.8.

    Returns shape (len(lblock_ids), n_right_blocks, num_aggs).  Left origin
    blocks are compacted to their position among ``lblock_ids`` before the
    scatter so the dense buffer is n_p × N2, not N1 × N2.  The compaction
    LUT, channel evaluation, and scatter are one jitted graph in the physical
    layer; the single host transfer happens here.
    """
    from repro.engine import physical

    lblock_ids = np.asarray(lblock_ids, dtype=np.int64)
    dense = physical.dense_block_pair_sums(
        table.columns, table.valid, table.block_id,
        jnp.asarray(lblock_ids, jnp.int32),
        exprs=tuple(exprs), rblock_col=rblock_col,
        n_right=n_right_blocks, n_origin=int(table.num_origin_blocks))
    return np.asarray(dense).transpose(1, 2, 0)
