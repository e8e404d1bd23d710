"""Exact filtered aggregation: every row of a table in one streaming pass.

An exact scan reads every block, so it needs no block-id table: the grid
runs over contiguous groups of rows in order, ``STEP_ROWS`` rows of 128 a
step (2,048, which is 256 blocks of 1,024 rows, about 4.4 MB a step for
Q6's five columns).  Each column streams HBM→VMEM in its own dtype,
double-buffered by the pipeline, and is cast in VMEM; ``valid``
arrives as an int8 copy (Mosaic has no bool memrefs, and a bool operand
would be cast to int32 over the whole column on every call).

Inside a step the slab goes through in chunks of 32 rows of 128 (one
packed int8 tile).  ``channels_fn`` turns a chunk's columns and its
liveness mask into the aggregate channels' values, masked with ``where``;
each channel adds them elementwise in float32 into an (8, 128) partial, so
every element of a partial adds ``STEP_ROWS // 8`` rows (256, and never
more than one 1,024-row block's worth: ``STEP_ROWS`` is at most 8,192).
The partials are reduced across lanes once, at the end of the step, into
one row of per-channel sums: output (n_steps, channels).

The last step is ragged when the rows are not a multiple of ``STEP_ROWS``:
the pipeline leaves whatever the buffer held beyond the table's end, which
may be NaN, so rows past the end are masked out of the liveness mask with
``where``-style selection, never by multiplying with a 0/1 mask.

Runtime constants (the predicate bounds) ride scalar prefetch: one compile
serves every constant variant of the shape.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
CHUNK = 32          # rows of 128 per inner step: one packed int8 tile
STEP_ROWS = 2048    # rows of 128 per grid step
MAX_CHANNELS = LANE  # one output lane per channel


def _lane_row(sums: Sequence[jax.Array]) -> jax.Array:
    """Pack (1, 1) sums into one (1, LANE) row, lane k holding sums[k]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    row = jnp.zeros((1, LANE), jnp.float32)
    for k, s in enumerate(sums):
        row = jnp.where(lane == k, s, row)
    return row


def _kernel(params_ref, *refs, channels_fn: Callable, n_channels: int,
            step_rows: int, total_rows: int):
    *col_refs, valid_ref, out_ref = refs
    step = pl.program_id(0)
    last = pl.num_programs(0) - 1

    def accumulate(limit):
        # ``limit``: rows of this step inside the table, or None when all are
        def body(j, accs):
            r = pl.multiple_of(j * CHUNK, CHUNK)
            cols = [ref[pl.ds(r, CHUNK), :] for ref in col_refs]
            # widened first: a mask compared in int8's packed layout does not
            # combine with the 32-bit columns' masks
            ok = valid_ref[pl.ds(r, CHUNK), :].astype(jnp.int32) != 0
            if limit is not None:
                row = r + jax.lax.broadcasted_iota(jnp.int32, (CHUNK, LANE), 0)
                ok = ok & (row < limit)
            vals = channels_fn(cols, ok, params_ref)
            return tuple(
                a + v.reshape(CHUNK // SUBLANE, SUBLANE, LANE).sum(axis=0)
                for a, v in zip(accs, vals))

        zero = jnp.zeros((SUBLANE, LANE), jnp.float32)
        accs = jax.lax.fori_loop(0, step_rows // CHUNK, body,
                                 (zero,) * n_channels)
        out_ref[...] = _lane_row([jnp.sum(a, keepdims=True) for a in accs])

    tail = total_rows % step_rows
    if tail == 0:
        accumulate(None)
        return

    @pl.when(step < last)
    def _():
        accumulate(None)

    @pl.when(step == last)
    def _():
        accumulate(tail)


def exact_agg_kernel(cols: Sequence[jax.Array], valid: jax.Array,
                     params: jax.Array, *, channels_fn: Callable,
                     n_channels: int, interpret: bool = False) -> jax.Array:
    """cols: (rows, 128) arrays in their own dtypes; valid: (rows, 128)
    int8; params: (P,) f32, P >= 1.  Returns (n_steps, n_channels) f32,
    row i the channel sums of rows [i * STEP_ROWS, (i + 1) * STEP_ROWS)."""
    step_rows = STEP_ROWS
    # an accumulator element adds step_rows / 8 rows: at most one block's
    assert step_rows % CHUNK == 0 and step_rows <= SUBLANE * 1024, step_rows
    rows = valid.shape[0]
    n_steps = pl.cdiv(rows, step_rows)
    spec = pl.BlockSpec((step_rows, LANE), lambda i, params: (i, 0))
    step_bytes = step_rows * LANE * (
        sum(c.dtype.itemsize for c in cols) + valid.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the runtime constants
        grid=(n_steps,),
        in_specs=[spec] * (len(cols) + 1),
        out_specs=pl.BlockSpec((None, 1, LANE), lambda i, params: (i, 0, 0)),
    )
    kernel = functools.partial(
        _kernel, channels_fn=channels_fn, n_channels=n_channels,
        step_rows=step_rows, total_rows=rows)
    out = pl.pallas_call(
        kernel,
        # the op shows in a device trace under the enclosing jit module
        # (``jit_run_exact`` on the exact plan), which is what
        # ``exact_scan_device_ms`` reads
        name="exact_agg_kernel",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_steps, 1, LANE), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # double-buffered input slabs plus room for the chunk temporaries
            vmem_limit_bytes=2 * step_bytes + (8 << 20)),
        interpret=interpret,
    )(params, *cols, valid)
    return out[:, 0, :n_channels]
