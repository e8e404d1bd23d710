"""Public wrapper: 1-D columns in, per-sampled-block stats out.

On CPU containers the Pallas TPU lowering is unavailable, so the wrapper
selects interpret mode automatically (`interpret=None` -> True off-TPU);
production TPU binaries pass interpret=False and get the compiled kernel.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.block_agg.kernel import (LANE, block_agg_batched_kernel,
                                            block_agg_kernel)
from repro.kernels.block_agg.ref import block_agg_ref


# Sampled block ids ride scalar prefetch into SMEM, 1 MiB on a v5e; past it
# the TPU compiler refuses the kernel.  One launch takes at most this many
# ids (512 KiB); larger id tables split into several launches whose
# per-block rows are concatenated, so the answer does not depend on the split.
MAX_PREFETCH_IDS = 1 << 17


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def over_id_chunks(launch, ids: jax.Array) -> jax.Array:
    """``launch(lanes, ids_part) -> (lanes, n_part, STATS)`` over pieces of a
    (B, n) id table that fit :data:`MAX_PREFETCH_IDS`, reassembled to
    (B, n, STATS).  ``lanes`` is the slice of the B lanes a piece covers."""
    batch, n = ids.shape
    step_n = min(n, MAX_PREFETCH_IDS)
    step_b = max(1, MAX_PREFETCH_IDS // step_n)
    rows = []
    for b0 in range(0, batch, step_b):
        lanes = slice(b0, min(b0 + step_b, batch))
        parts = [launch(lanes, ids[lanes, j:j + step_n])
                 for j in range(0, n, step_n)]
        rows.append(parts[0] if len(parts) == 1
                    else jnp.concatenate(parts, axis=1))
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)


def to_slabs(column, block_rows: int) -> jax.Array:
    """A 1-D column as f32 block slabs ``(num_blocks, rows // 128, 128)``.

    ``rows`` is ``block_rows`` padded up to a multiple of the 128-lane width;
    pad rows are zero (and invalid, since ``valid`` is padded the same way).
    """
    col = jnp.asarray(column)
    n_blocks = col.shape[0] // block_rows
    c = col.reshape(n_blocks, block_rows).astype(jnp.float32)
    pad = (-block_rows) % LANE
    if pad:
        c = jnp.pad(c, ((0, 0), (0, pad)))
    return c.reshape(n_blocks, -1, LANE)


def block_agg(column: jax.Array, valid: jax.Array, block_rows: int,
              ids: np.ndarray, *, interpret: Optional[bool] = None,
              use_ref: bool = False) -> jax.Array:
    """Per-sampled-block (count, sum, sumsq, min, max) for a 1-D column.

    column/valid: (num_blocks * block_rows,); ids: sampled block indices.
    Blocks with zero valid rows report min=max=NaN with count=0 (the
    empty-block sentinel; mask min/max on count>0 downstream).
    """
    v = to_slabs(column, block_rows)
    m = to_slabs(valid, block_rows)
    ids = jnp.asarray(ids, dtype=jnp.int32)
    if use_ref:
        n_blocks = v.shape[0]
        out = block_agg_ref(v.reshape(n_blocks, -1), m.reshape(n_blocks, -1),
                            ids, block_rows=v.shape[1] * LANE)
    else:
        interp = _auto_interpret(interpret)
        out = over_id_chunks(
            lambda _, part: block_agg_kernel(v, m, part[0],
                                             interpret=interp)[None],
            ids[None])[0]
    return out[:, :5]


def block_agg_batched(column: jax.Array, valid: jax.Array, block_rows: int,
                      ids, *, interpret: Optional[bool] = None) -> jax.Array:
    """Batched per-sampled-block stats: B lanes share the column slabs.

    column/valid: (num_blocks * block_rows,); ids: (B, n_sampled) per-lane
    sampled block indices.  One launch serves a whole drain group; returns
    (B, n_sampled, 5), each lane bit-identical to its solo ``block_agg``.
    """
    v = to_slabs(column, block_rows)
    m = to_slabs(valid, block_rows)
    interp = _auto_interpret(interpret)
    out = over_id_chunks(
        lambda _, part: block_agg_batched_kernel(v, m, part, interpret=interp),
        jnp.asarray(ids, dtype=jnp.int32))
    return out[:, :, :5]
