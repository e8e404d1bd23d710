"""Exact filtered aggregation over every row of a table: contiguous groups
of blocks per grid step, each column in its own dtype (TPC-H Q6 answered
exactly)."""

from repro.kernels.exact_agg.ops import exact_agg
from repro.kernels.exact_agg.ref import exact_agg_ref

__all__ = ["exact_agg", "exact_agg_ref"]
