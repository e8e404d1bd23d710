"""Sampled-block aggregation kernel — the TPU realization of BSAP's scan.

The grid ranges over *sampled* blocks only.  The sampled block ids arrive via
scalar prefetch and drive the BlockSpec index_map, so each grid step DMAs
exactly one block slab of the column from HBM into VMEM — non-sampled slabs
never move.  This is `TABLESAMPLE SYSTEM` as a memory system primitive: the
cost is θ·bytes, not bytes.

Layout: a column arrives as ``(num_blocks, rows // 128, 128)`` — one block is
a ``(rows // 128, 128)`` slab whose trailing dims equal the array's, which is
what the TPU lowering requires of a block shape (a ``(1, block_rows)`` block
is refused: its second-last dim is neither a multiple of 8 nor the array's).
At 1024 rows a block is exactly one (8, 128) f32 tile.

Output per sampled block: (count, sum, sum-of-squares, min, max, 0, 0, 0) —
exactly the per-block statistics the pilot query groups by `ctid` (§3.3) and
that BSAP's bounds consume (count/sum/sumsq) plus min/max for future outlier
indexes.  Stored as a ``(1, 8)`` row per block: lane-padded for clean stores.

Empty-block sentinel: a sampled block with zero valid rows reports
count=0, sum=0, sumsq=0 and **min=max=NaN** (not the float32 ±3.4e38 extremes
of the masked reduction).  Consumers must mask min/max on count>0; sums are
safe to use unmasked.  The oracle in ``ref.py`` follows the same convention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

STATS = 8  # count, sum, sumsq, min, max, pad, pad, pad
LANE = 128  # TPU lane width: a block slab is (rows // LANE, LANE)


def stats_row(*stats) -> jax.Array:
    """Pack (1, 1) statistics into one (1, STATS) row, zero-padded.

    A lane select rather than a stack of scalars: it lowers to plain vector
    selects on the TPU."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, STATS), 1)
    row = jnp.zeros((1, STATS), jnp.float32)
    for k, s in enumerate(stats):
        row = jnp.where(lane == k, s, row)
    return row


def _kernel(ids_ref, vals_ref, valid_ref, out_ref):
    # One body for the solo (n_sampled,) and batched (batch, n_sampled)
    # grids: both squeeze their block to a (rows // 128, 128) slab and a
    # (1, STATS) output row, so a batched lane is bit-identical to solo.
    v = vals_ref[...].astype(jnp.float32)
    m = valid_ref[...].astype(jnp.float32)
    cnt = jnp.sum(m, keepdims=True)
    s = jnp.sum(v * m, keepdims=True)
    ss = jnp.sum(v * v * m, keepdims=True)
    big = jnp.float32(3.4e38)
    nan = jnp.float32(jnp.nan)
    mn = jnp.where(cnt > 0, jnp.min(jnp.where(m > 0, v, big), keepdims=True), nan)
    mx = jnp.where(cnt > 0, jnp.max(jnp.where(m > 0, v, -big), keepdims=True), nan)
    out_ref[...] = stats_row(cnt, s, ss, mn, mx)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_agg_batched_kernel(values: jax.Array, valid: jax.Array,
                             ids: jax.Array, *,
                             interpret: bool = False) -> jax.Array:
    """values/valid: (num_blocks, rows // 128, 128); ids: (batch, n_sampled).

    One launch, megacore-style batched grid: lane b's sampled blocks are
    driven by row b of the stacked scalar-prefetch id table.  Returns
    (batch, n_sampled, 8) per-block stats, each lane bit-identical to the
    solo ``block_agg_kernel`` on its id row.
    """
    batch, n_sampled = ids.shape
    slab = (None,) + values.shape[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, n_sampled),
        in_specs=[
            pl.BlockSpec(slab, lambda b, i, ids: (ids[b, i], 0, 0)),
            pl.BlockSpec(slab, lambda b, i, ids: (ids[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, 1, STATS),
                               lambda b, i, ids: (b, i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, n_sampled, 1, STATS), jnp.float32),
        interpret=interpret,
    )(ids, values, valid)
    return out[:, :, 0, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_agg_kernel(values: jax.Array, valid: jax.Array, ids: jax.Array,
                     *, interpret: bool = False) -> jax.Array:
    """values/valid: (num_blocks, rows // 128, 128); ids: (n_sampled,) int32.

    Returns (n_sampled, 8) per-block stats.
    """
    n_sampled = ids.shape[0]
    slab = (None,) + values.shape[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_sampled,),
        in_specs=[
            pl.BlockSpec(slab, lambda i, ids: (ids[i], 0, 0)),
            pl.BlockSpec(slab, lambda i, ids: (ids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, STATS), lambda i, ids: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_sampled, 1, STATS), jnp.float32),
        interpret=interpret,
    )(ids, values, valid)
    return out[:, 0, :]
