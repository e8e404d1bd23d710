"""Public wrapper for the fused Q6-style scan."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.kernels.block_agg.ops import _auto_interpret, over_id_chunks, to_slabs
from repro.kernels.filtered_agg.kernel import (filtered_agg_batched_kernel,
                                               filtered_agg_kernel)
from repro.kernels.filtered_agg.ref import filtered_agg_ref


def filtered_agg(x, y, f1, f2, f3, valid, block_rows: int, ids: np.ndarray,
                 bounds, *, interpret: Optional[bool] = None,
                 use_ref: bool = False):
    """Fused Q6 scan over sampled blocks of 1-D columns.

    bounds = (lo1, hi1, lo2, hi2, c3) — a tuple or a (5,) device array;
    either way it reaches the kernel as a *runtime* scalar operand (scalar
    prefetch), so constant-varied calls share one compiled kernel.  Returns
    (n_sampled, 3) cnt/sum/sumsq.  Rows failing the predicate are excluded;
    padding rows are invalid.
    """
    cols = [to_slabs(c, block_rows) for c in (x, y, f1, f2, f3, valid)]
    ids = jnp.asarray(ids, dtype=jnp.int32)
    bounds = jnp.asarray(bounds, jnp.float32)
    if use_ref:
        n_blocks = cols[0].shape[0]
        flat = [c.reshape(n_blocks, -1) for c in cols]
        return filtered_agg_ref(*flat[:5], flat[5], ids, bounds=bounds)
    interp = _auto_interpret(interpret)
    out = over_id_chunks(
        lambda _, part: filtered_agg_kernel(*cols, part[0], bounds,
                                            interpret=interp)[None],
        ids[None])[0]
    return out[:, :3]


def filtered_agg_batched(x, y, f1, f2, f3, valid, block_rows: int, ids,
                         bounds, *, interpret: Optional[bool] = None):
    """Batched fused Q6 scan: B lanes share the column slabs.

    ids: (B, n_sampled) per-lane sampled block ids; bounds: (B, 5) per-lane
    predicate bounds.  One kernel launch computes every lane's per-block
    stats — the drain-group finals path.  Returns (B, n_sampled, 3)
    cnt/sum/sumsq, each lane bit-identical to its solo ``filtered_agg``.
    """
    cols = [to_slabs(c, block_rows) for c in (x, y, f1, f2, f3, valid)]
    ids = jnp.asarray(ids, dtype=jnp.int32)
    bounds = jnp.asarray(bounds, jnp.float32)
    interp = _auto_interpret(interpret)
    out = over_id_chunks(
        lambda lanes, part: filtered_agg_batched_kernel(
            *cols, part, bounds[lanes], interpret=interp),
        ids)
    return out[:, :, :3]
