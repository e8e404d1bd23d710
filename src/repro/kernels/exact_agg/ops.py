"""Public wrapper: 1-D columns in their own dtypes, per-step channel sums
out."""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.block_agg.ops import _auto_interpret
from repro.kernels.exact_agg import kernel as _k


def exact_agg(columns: Sequence[jax.Array], valid: jax.Array, params,
              channels_fn: Callable, n_channels: int, *,
              interpret: Optional[bool] = None) -> jax.Array:
    """Channel sums over every row of 1-D columns, in contiguous steps.

    columns: 1-D arrays of one length, a multiple of 128, in any dtype the
    kernel can cast (they are reshaped to (rows / 128, 128), which moves no
    data); valid: the same length, int8 (non-zero is live).
    ``channels_fn(cols, ok, params)`` maps same-shaped chunks of the
    columns, a bool liveness mask and the runtime constants (``params[i]``
    is a scalar) to ``n_channels`` float32 arrays of the chunk's shape,
    zero where ``ok`` is false.  Returns (n_steps, n_channels) float32 per-
    step sums; add them with a pairwise sum.
    """
    n = valid.shape[0]
    if n % _k.LANE:
        raise ValueError(f"column length {n} is not a multiple of {_k.LANE}")
    if not 1 <= n_channels <= _k.MAX_CHANNELS:
        raise ValueError(f"1 to {_k.MAX_CHANNELS} channels, got {n_channels}")
    if valid.dtype != jnp.int8:
        raise ValueError(f"valid must be int8, got {valid.dtype}")
    params = jnp.asarray(params, jnp.float32).reshape(-1)
    if params.shape[0] == 0:  # scalar prefetch takes no empty table
        params = jnp.zeros(1, jnp.float32)
    slabs = [jnp.asarray(c).reshape(-1, _k.LANE) for c in columns]
    return _k.exact_agg_kernel(
        slabs, valid.reshape(-1, _k.LANE), params, channels_fn=channels_fn,
        n_channels=n_channels, interpret=_auto_interpret(interpret))
