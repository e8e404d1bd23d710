"""Fused predicate + sampled-block aggregation (TPC-H Q6 shape).

Computes, over sampled blocks only (scalar-prefetched ids):

  SUM(x*y), COUNT(*)  WHERE  lo1<=f1<=hi1 AND lo2<=f2<=hi2 AND f3<c

in a single HBM pass: five column slabs stream HBM→VMEM per block, the
predicate evaluates in VREGs, and only 8 lanes per block are stored.  This is
the paper's "data scanning is the latency bottleneck" (§1) case: fusing the
filter avoids materializing a mask column and a second pass.

Predicate bounds are *runtime scalars* riding the same scalar-prefetch path
as the sampled block ids (SMEM, available before the grid body runs).  One
compiled kernel therefore serves every constant variant of the shape — the
serve-layer case of a dashboard sweeping its date range — instead of
recompiling per constant set as the earlier static-bounds lowering did.

Columns arrive in ``block_agg``'s slab layout, ``(num_blocks, rows // 128,
128)``, and each block writes one ``(1, 8)`` stats row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.block_agg.kernel import STATS, stats_row

BOUNDS = 5  # lo1, hi1, lo2, hi2, c3


def _block_stats(bounds, x_ref, y_ref, f1_ref, f2_ref, f3_ref, valid_ref,
                 out_ref):
    lo1, hi1, lo2, hi2, c3 = bounds
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    f1 = f1_ref[...].astype(jnp.float32)
    f2 = f2_ref[...].astype(jnp.float32)
    f3 = f3_ref[...].astype(jnp.float32)
    m = valid_ref[...].astype(jnp.float32)
    keep = ((f1 >= lo1) & (f1 <= hi1) & (f2 >= lo2) & (f2 <= hi2)
            & (f3 < c3)).astype(jnp.float32) * m
    prod = x * y
    cnt = jnp.sum(keep, keepdims=True)
    s = jnp.sum(prod * keep, keepdims=True)
    ss = jnp.sum(prod * prod * keep, keepdims=True)
    out_ref[...] = stats_row(cnt, s, ss)


def _kernel(ids_ref, bounds_ref, *refs):
    _block_stats([bounds_ref[k] for k in range(BOUNDS)], *refs)


def _kernel_batched(ids_ref, bounds_ref, *refs):
    # Megacore-style batched grid (batch, n_sampled): lane b scans ITS
    # sampled blocks (ids_ref[b, i]) under ITS predicate bounds
    # (bounds_ref[b]); per-block math is the solo _kernel's.
    b = pl.program_id(0)
    _block_stats([bounds_ref[b, k] for k in range(BOUNDS)], *refs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def filtered_agg_batched_kernel(x, y, f1, f2, f3, valid, ids, bounds, *,
                                interpret: bool = False) -> jax.Array:
    """Batched lanes over shared column slabs.

    Columns: (num_blocks, rows // 128, 128).  ids: (batch, n_sampled) int32
    — each lane's sampled block ids; bounds: (batch, BOUNDS) f32 — each
    lane's predicate bounds.  Both ride scalar prefetch (stacked tables).
    One kernel launch covers a whole drain group's finals: out (batch,
    n_sampled, STATS).
    """
    batch, n_sampled = ids.shape
    slab = (None,) + x.shape[1:]
    col_spec = pl.BlockSpec(slab, lambda b, i, ids, bounds: (ids[b, i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # stacked block-id table + stacked bounds table
        grid=(batch, n_sampled),
        in_specs=[col_spec] * 6,
        out_specs=pl.BlockSpec((None, None, 1, STATS),
                               lambda b, i, ids, bounds: (b, i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel_batched,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, n_sampled, 1, STATS), jnp.float32),
        interpret=interpret,
    )(ids, jnp.asarray(bounds, jnp.float32), x, y, f1, f2, f3, valid)
    return out[:, :, 0, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def filtered_agg_kernel(x, y, f1, f2, f3, valid, ids, bounds, *,
                        interpret: bool = False) -> jax.Array:
    """Columns: (num_blocks, rows // 128, 128); ids: (n_sampled,) int32;
    bounds: (BOUNDS,) f32.  Returns (n_sampled, STATS)."""
    n_sampled = ids.shape[0]
    slab = (None,) + x.shape[1:]
    col_spec = pl.BlockSpec(slab, lambda i, ids, bounds: (ids[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # sampled block ids + predicate bounds
        grid=(n_sampled,),
        in_specs=[col_spec] * 6,
        out_specs=pl.BlockSpec((None, 1, STATS), lambda i, ids, bounds: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_sampled, 1, STATS), jnp.float32),
        interpret=interpret,
    )(ids, jnp.asarray(bounds, jnp.float32), x, y, f1, f2, f3, valid)
    return out[:, 0, :]
