"""Pure-jnp oracle for exact_agg."""

from __future__ import annotations

import jax.numpy as jnp


def exact_agg_ref(columns, valid, params, channels_fn, *, block_rows: int):
    """Per-block channel sums, (n_blocks, n_channels) float32: the same
    ``channels_fn`` over whole 1-D columns, each block's rows summed in
    float32.  Add the blocks in a wider type for a reference total."""
    vals = channels_fn(list(columns), valid != 0,
                       jnp.asarray(params, jnp.float32))
    return jnp.stack([v.reshape(-1, block_rows).sum(axis=1) for v in vals],
                     axis=1)
