"""Physical plans: compiled query pipelines over the kernel layer.

The engine is layered as::

    logical.Plan  --lower-->  compiled physical executable  --run-->  stats
      (what to compute)        (one jitted XLA graph,              (device
       §2.3 plan algebra        kernels for the scan+agg            arrays)
       + TABLESAMPLE clauses)   hot path)

``PhysicalCompiler`` lowers a :class:`logical.Aggregate` tree into a single
jit-compiled executable and caches it under a *plan signature* — the operator
tree shape with sampling rates/seeds stripped AND predicate/expression
constants hoisted (:func:`logical.extract_constants`), the referenced column
set and dtypes, ``block_rows``, ``max_groups``, and the bucketed
sampled-block count.  Constants enter executables as a runtime operand (the
``params`` vector, device scalars / scalar prefetch), so ONE executable
serves every constant variant of a shape: compile misses are O(distinct
shapes), not O(queries) — a dashboard sweeping its date range runs warm.
Repeated pilot/final queries (and many concurrent users issuing structurally
identical queries, the serve-layer scenario) therefore skip recompilation;
``cache_info()`` exposes the hit/miss counters.

``compile_batched_query`` additionally stacks N same-signature members
(block-id matrices + bounds/params matrix) into ONE executable dispatch via
``lax.map`` — the drain-group batching path: N finals cost one launch, and
each member's lane runs the identical per-member HLO, so batched answers are
bit-identical to solo runs.

Kernel routing.  Block-sampled scans and their downstream aggregations are
routed through the Pallas kernels in ``repro.kernels`` when the plan shape
allows:

* ``pallas_filtered`` — single-table ``Aggregate(Filter*(Scan))`` with a
  conjunctive range predicate and SUM(x*y)/SUM(x)/COUNT channels lowers onto
  :func:`repro.kernels.filtered_agg.filtered_agg` (TPC-H Q6 shape): sampled
  block ids travel by scalar prefetch, so unsampled slabs never leave HBM and
  the scan pays θ·bytes, not bytes.
* ``pallas_block``   — filterless ``Aggregate(Scan)`` with SUM(col)/COUNT
  channels lowers onto :func:`repro.kernels.block_agg.block_agg`.
* ``pallas_exact``   — the ``pallas_filtered`` shape over an *unsampled*
  table (an exact answer) lowers onto
  :func:`repro.kernels.exact_agg.exact_agg`: the grid streams contiguous
  groups of blocks in order, each column in its own dtype (``valid`` as
  the table's int8 copy, :meth:`BlockTable.valid_bytes`), with the
  predicate and channels the ``xla_gather`` route would evaluate; the
  per-step sums are added by :func:`pairwise_sum`.
* ``xla_gather``     — everything else (joins, unions, GROUP BY, composite
  expressions, the other exact scans) lowers to the kernels' XLA twin: a
  device-side slab gather with static (bucketed) shape, per-(block, group)
  channel sums, then a pairwise sum over the blocks
  (:func:`blockwise_totals`).  Same semantics, one graph, no host
  round-trips.

An exact scan (every table unsampled), on either route, is jitted as
``run_exact``, so the device trace names it ``jit_run_exact``.  The batched
and fused lowerings never take a Pallas route of a solo query's
(``allow_kernel=False``).

Pallas routes are selected on TPU backends (``kernel_mode="auto"``) where the
kernels compile to real DMA programs; on CPU containers interpret mode would
run the grid in Python, so ``auto`` falls back to ``xla_gather``.  Tests force
``kernel_mode="pallas"`` at small sizes to pin route equivalence.

Scan-cost attribution lives here too: a compiled executable knows which
tables its kernels stream and charges ``n_real · block_rows · row_bytes`` for
block-sampled scans and full heap bytes for row-sampled/exact scans — the
same row-store accounting the samplers used, now owned by the layer that
actually moves the bytes.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import logical as L
from repro.engine.expr import And, Between, BinOp, Cmp, Col, Expr, eval_expr
from repro.engine.table import BlockTable
from repro.kernels.block_agg import block_agg, block_agg_batched
from repro.kernels.exact_agg import exact_agg
from repro.kernels.exact_agg.kernel import MAX_CHANNELS as exact_agg_max_channels
from repro.kernels.filtered_agg import filtered_agg, filtered_agg_batched
from repro.obs import trace as _trace

_BIG_BOUND = 3.0e38       # "unbounded" predicate slot, f32-safe
_INT_MAX = np.int32(2 ** 31 - 1)
# The XLA route's per-(block, group) partial sums held on the device at
# once, in bytes.  A table whose partials would exceed it is summed run of
# blocks by run of blocks, each run reduced before the next starts.
PARTIALS_BUDGET_BYTES = 64 << 20


# ---------------------------------------------------------------------------
# Scan-cost attribution
# ---------------------------------------------------------------------------

def scan_cost_bytes(table: BlockTable, method: str, n_real: int = 0) -> int:
    """Bytes a scan of ``table`` moves, attributed by the kernel layer.

    Block-sampled scans pay only for real sampled slabs (θ·bytes — the
    padding blocks of the bucketed gather never move in a real storage
    engine); row-sampled and exact scans stream the full heap.  The single
    source of truth for both ``SampleInfo.scanned_bytes`` and compiled
    executables' totals.
    """
    if method == "block":
        return n_real * table.block_rows * table.row_bytes()
    return table.total_bytes()


# ---------------------------------------------------------------------------
# Runtime sampling decisions (the host-side TABLESAMPLE draw)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScanRuntime:
    """Per-table runtime inputs of a compiled executable.

    The Bernoulli *decision* stays host-side (as a DBMS decides pages before
    scanning them); everything downstream of the decision runs on device.
    ``ids`` is padded to the bucketed length ``n_phys`` with zeros — padding
    entries are masked out inside the graph via ``n_real``, so the executable
    shape (and its cache entry) is shared across nearby sample sizes.
    """

    method: str                             # "none" | "block" | "row"
    n_real: int = 0                         # real sampled blocks (block) — host int
    n_phys: int = 0                         # bucketed physical block count
    ids: Optional[np.ndarray] = None        # (n_phys,) int32, zero-padded
    keep_mask: Optional[np.ndarray] = None  # (padded_rows,) bool (row method)
    # Pre-staged device copies of ids/n_real (repro.engine.staged memoizes a
    # sub-draw once and replays it every query): when set, the per-call
    # host->device transfer is skipped.  Values must match ids/n_real.
    ids_dev: Optional[object] = None
    nreal_dev: Optional[object] = None

    def sig(self) -> tuple:
        if self.method == "block":
            return ("block", self.n_phys)
        return (self.method,)


# ---------------------------------------------------------------------------
# Plan signatures
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1024)
def _template_of(plan: L.Plan) -> Tuple[L.Plan, Tuple[float, ...]]:
    """Memoized constant hoisting (plans are frozen/hashable)."""
    return L.extract_constants(plan)


def plan_template(plan: L.Plan) -> L.Plan:
    """The constant-free template of ``plan`` (Params in constant slots)."""
    return _template_of(plan)[0]


def plan_constants(plan: L.Plan) -> np.ndarray:
    """The runtime constant vector of ``plan``, position-aligned with its
    template's Param slots — the ``params`` operand of compiled executables."""
    return np.asarray(_template_of(plan)[1], np.float32)


def plan_signature(plan: L.Plan, runtimes: Optional[Dict[str, ScanRuntime]] = None,
                   extra: tuple = ()) -> tuple:
    """Hashable structural key for the compile cache.

    Sampling rates and seeds are stripped (they are runtime data); which
    tables are sampled, by which method, and at which bucketed size is kept
    (those are shapes).  Predicate/expression *constants* are hoisted out of
    the key too: they reach executables as the runtime ``params`` operand
    (device scalars / kernel scalar prefetch), exactly as a DBMS binds
    placeholders into one prepared plan — so constant-varied re-issues of a
    shape share one compilation.
    """
    rsig = tuple(sorted((t, r.sig()) for t, r in (runtimes or {}).items()))
    return (plan_template(L.strip_samples(plan)), rsig, tuple(extra))


def _referenced_columns(plan: L.Plan) -> set:
    cols: set = set()

    def walk(p: L.Plan):
        if isinstance(p, L.Aggregate):
            for a in p.aggs:
                if a.expr is not None:
                    cols.update(a.expr.columns())
            if p.group_by is not None:
                cols.add(p.group_by)
            walk(p.child)
        elif isinstance(p, L.Filter):
            cols.update(p.pred.columns())
            walk(p.child)
        elif isinstance(p, L.Join):
            cols.add(p.left_key)
            cols.add(p.right_key)
            walk(p.left)
            walk(p.right)
        elif isinstance(p, L.Union):
            for c in p.inputs:
                walk(c)
        elif isinstance(p, L.Scan):
            pass
        else:
            raise TypeError(p)

    walk(plan)
    return cols


def _needed_by_table(plan: L.Plan, catalog: Dict[str, BlockTable]) -> Dict[str, Tuple[str, ...]]:
    """Referenced columns per scanned table (column pruning for the gather).

    Column names are assumed unique across joined tables — the same invariant
    ``ops.join_unique`` enforces with its collision check.
    """
    referenced = _referenced_columns(plan)
    needed: Dict[str, Tuple[str, ...]] = {}
    for s in plan.scans():
        tab = catalog[s.table]
        needed[s.table] = tuple(sorted(referenced.intersection(tab.columns)))
    return needed


# ---------------------------------------------------------------------------
# Fused multi-channel aggregation primitives (the XLA twin of the kernels)
# ---------------------------------------------------------------------------

def channel_values(columns: Dict[str, jnp.ndarray], valid: jnp.ndarray,
                   exprs: Sequence[Optional[Expr]],
                   params=None) -> List[jnp.ndarray]:
    """Every aggregate channel's per-row values, one array of ``valid``'s
    shape each (rows, or a kernel's (rows / 128, 128) chunk).

    ``None`` channels are COUNT (ones).  Invalid rows contribute zeros.
    ``params`` resolves hoisted-constant Param slots in template
    expressions (compiled lowerings); eager callers pass constant-bearing
    exprs and omit it.
    """
    outs = []
    for e in exprs:
        if e is None:
            v = jnp.ones(valid.shape, jnp.float32)
        else:
            v = jnp.broadcast_to(
                eval_expr(e, columns, params).astype(jnp.float32), valid.shape)
        outs.append(jnp.where(valid, v, 0.0))
    return outs


def channel_matrix(columns: Dict[str, jnp.ndarray], valid: jnp.ndarray,
                   exprs: Sequence[Optional[Expr]],
                   params=None) -> jnp.ndarray:
    """:func:`channel_values` stacked, (num_channels, rows), so that a
    single scatter-add over the matrix sums every channel."""
    return jnp.stack(channel_values(columns, valid, exprs, params))


@functools.partial(jax.jit, static_argnames=("exprs", "group_by", "max_groups", "n_origin"))
def dense_block_group_sums(columns, valid, block_id, *, exprs: tuple,
                           group_by: Optional[str], max_groups: int,
                           n_origin: int) -> jnp.ndarray:
    """Per-(origin-block, group) channel sums: (num_channels, n_origin, max_groups).

    One fused scatter-add across all channels; the whole computation is one
    jitted graph with zero host syncs (``ops.block_group_sums`` converts the
    result exactly once at the boundary).
    """
    rows = valid.shape[0]
    if group_by is None:
        gid = jnp.zeros(rows, jnp.int32)
    else:
        gid = jnp.clip(columns[group_by].astype(jnp.int32), 0, max_groups - 1)
    vals = channel_matrix(columns, valid, exprs)
    seg = block_id.astype(jnp.int32) * max_groups + gid
    dense = jnp.zeros((len(exprs), n_origin * max_groups), jnp.float32).at[:, seg].add(vals)
    return dense.reshape(len(exprs), n_origin, max_groups)


@functools.partial(jax.jit, static_argnames=("exprs", "rblock_col", "n_right", "n_origin"))
def dense_block_pair_sums(columns, valid, block_id, lblock_ids, *, exprs: tuple,
                          rblock_col: str, n_right: int, n_origin: int) -> jnp.ndarray:
    """Per-(compact left block, right block) sums: (num_channels, n_p, n_right).

    Left origin blocks compact to their position among ``lblock_ids`` inside
    the graph (scatter-built LUT); rows from unsampled blocks land in a
    scratch slot that is sliced away.
    """
    n_p = lblock_ids.shape[0]
    lut = jnp.full(n_origin, n_p, jnp.int32).at[lblock_ids].set(
        jnp.arange(n_p, dtype=jnp.int32), mode="drop")
    compact = lut[block_id]
    rb = jnp.where(valid, columns[rblock_col].astype(jnp.int32), 0)
    seg = compact * n_right + rb
    vals = channel_matrix(columns, valid, exprs)
    dense = jnp.zeros((len(exprs), (n_p + 1) * n_right), jnp.float32).at[:, seg].add(vals)
    return dense.reshape(len(exprs), n_p + 1, n_right)[:, :n_p]


def pairwise_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over axis 0 as a balanced tree: the axis is zero-padded to a
    power of two and halved until one row is left, so each addend passes
    through log2(n) float32 additions, not up to n as in a running sum."""
    n = x.shape[0]
    size = 1 << max(n - 1, 0).bit_length()
    if size != n:
        x = jnp.concatenate([x, jnp.zeros((size - n,) + x.shape[1:], x.dtype)])
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


def blockwise_totals(columns: Dict[str, jnp.ndarray], valid: jnp.ndarray,
                     exprs: Tuple[Optional[Expr], ...],
                     group_by: Optional[str], max_groups: int,
                     block_rows: int, params) -> jnp.ndarray:
    """Per-group channel totals, (len(exprs), max_groups), of block-aligned
    rows: each block's rows are summed in float32 into per-(block, group)
    partials (no accumulator takes more than one block's rows), and the
    partials are added by :func:`pairwise_sum`.  When the partials would
    exceed :data:`PARTIALS_BUDGET_BYTES`, the blocks go through in runs,
    one run's partials at a time, and the runs' totals are added pairwise
    too."""
    n_ch, mg = len(exprs), max_groups
    nb = valid.shape[0] // block_rows

    def partials(cols, ok):  # k blocks' rows -> (k, n_ch, mg)
        k = ok.shape[0] // block_rows
        if group_by is None:
            # a block as (rows / 128, 128): on a TPU a 1024-row block is one
            # (8, 128) tile of the column, so the reshape moves no data; one
            # variadic reduce sums every channel in a single pass
            lanes = 128 if block_rows % 128 == 0 else block_rows
            vals = [v.reshape(k, block_rows // lanes, lanes)
                    for v in channel_values(cols, ok, exprs, params)]
            sums = jax.lax.reduce(
                vals, [jnp.float32(0)] * n_ch,
                lambda xs, ys: [x + y for x, y in zip(xs, ys)], (1, 2))
            return jnp.stack(sums, axis=1)[:, :, None]
        vals = channel_matrix(cols, ok, exprs, params)
        gid = jnp.clip(cols[group_by].astype(jnp.int32), 0, mg - 1)
        seg = (jnp.arange(k * block_rows, dtype=jnp.int32) // block_rows
               * mg + gid)
        dense = jnp.zeros((n_ch, k * mg), jnp.float32).at[:, seg].add(vals)
        return dense.reshape(n_ch, k, mg).transpose(1, 0, 2)

    run = max(1, min(nb, PARTIALS_BUDGET_BYTES // (n_ch * mg * 4)))
    if run >= nb:
        return pairwise_sum(partials(columns, valid))

    def one(i):
        # the last run is clamped to the table's end; blocks an earlier run
        # already summed are masked out of it
        start = jnp.minimum(i * run, nb - run)

        def cut(x):
            return jax.lax.dynamic_slice_in_dim(x, start * block_rows,
                                                run * block_rows)

        part = partials({c: cut(v) for c, v in columns.items()}, cut(valid))
        fresh = start + jnp.arange(run) >= i * run
        return pairwise_sum(jnp.where(fresh[:, None, None], part, 0.0))

    return pairwise_sum(jax.lax.map(one, jnp.arange(-(-nb // run))))


# ---------------------------------------------------------------------------
# Traced relational pipeline (runs inside jit; static shapes from signatures)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Traced:
    columns: Dict[str, jnp.ndarray]
    valid: jnp.ndarray
    block_id: jnp.ndarray           # origin block id per row
    pblock: Optional[jnp.ndarray]   # compact pilot-block index (pilot lowering)
    block_rows: int
    num_origin_blocks: int


class _Tracer:
    """Evaluates a logical plan symbolically over runtime device arrays.

    Each ``trace`` call happens once per compiled signature (inside
    ``jax.jit``); at runtime the resulting XLA graph executes with no Python
    in the loop and no device→host transfers.
    """

    def __init__(self, catalog: Dict[str, BlockTable],
                 needed: Dict[str, Tuple[str, ...]],
                 methods: Dict[str, str],
                 pilot_table: Optional[str] = None,
                 n_phys_pilot: int = 0,
                 pair_table: Optional[str] = None):
        self.catalog = catalog
        self.needed = needed
        self.methods = methods            # table -> "none" | "block" | "row"
        self.pilot_table = pilot_table
        self.n_phys_pilot = n_phys_pilot  # scratch pblock value == n_phys_pilot
        self.pair_table = pair_table

    # -- scans ---------------------------------------------------------------
    def _scratch_pblock(self, rows: int) -> Optional[jnp.ndarray]:
        if self.pilot_table is None:
            return None
        return jnp.full(rows, self.n_phys_pilot, jnp.int32)

    def _trace_scan(self, plan: L.Scan, rt) -> _Traced:
        name = plan.table
        tab = self.catalog[name]
        cols = {c: rt["cols"][name][c] for c in self.needed[name]}
        valid = rt["valid"][name]
        bid = rt["bid"][name]
        method = self.methods.get(name, "none")
        br = tab.block_rows
        if method == "block":
            ids = rt["ids"][name]
            nreal = rt["nreal"][name]
            n_phys = ids.shape[0]
            row_idx = (ids[:, None].astype(jnp.int32) * br
                       + jnp.arange(br, dtype=jnp.int32)[None, :]).reshape(-1)
            cols = {c: v[row_idx] for c, v in cols.items()}
            real = jnp.repeat(jnp.arange(n_phys, dtype=jnp.int32) < nreal, br)
            valid = valid[row_idx] & real
            bid = bid[row_idx]
            if name == self.pilot_table:
                pblock = jnp.repeat(jnp.arange(n_phys, dtype=jnp.int32), br)
            else:
                pblock = self._scratch_pblock(n_phys * br)
            return _Traced(cols, valid, bid, pblock, br, tab.num_origin_blocks)
        if method == "row":
            valid = valid & rt["mask"][name]
        return _Traced(cols, valid, bid, self._scratch_pblock(tab.padded_rows),
                       br, tab.num_origin_blocks)

    # -- composite operators -------------------------------------------------
    def trace(self, plan: L.Plan, rt) -> _Traced:
        if isinstance(plan, L.Scan):
            return self._trace_scan(plan, rt)
        if isinstance(plan, L.Filter):
            child = self.trace(plan.child, rt)
            mask = eval_expr(plan.pred, child.columns, rt.get("params"))
            return dataclasses.replace(child, valid=child.valid & mask)
        if isinstance(plan, L.Join):
            return self._trace_join(plan, rt)
        if isinstance(plan, L.Union):
            return self._trace_union(plan, rt)
        raise TypeError(plan)

    def _trace_join(self, plan: L.Join, rt) -> _Traced:
        left = self.trace(plan.left, rt)
        right = self.trace(plan.right, rt)
        lkey = left.columns[plan.left_key].astype(jnp.int32)
        rkey = jnp.where(right.valid,
                         right.columns[plan.right_key].astype(jnp.int32), _INT_MAX)
        order = jnp.argsort(rkey)
        sorted_keys = rkey[order]
        pos = jnp.searchsorted(sorted_keys, lkey)
        pos_c = jnp.clip(pos, 0, sorted_keys.shape[0] - 1)
        found = sorted_keys[pos_c] == lkey
        match = order[pos_c]
        valid = left.valid & found
        new_cols = dict(left.columns)
        for cname, col in right.columns.items():
            if cname == plan.right_key:
                continue
            if cname in new_cols:
                raise ValueError(f"column name collision in join: {cname}")
            new_cols[cname] = col[match]
        right_scans = plan.right.scans()
        if (self.pair_table is not None and len(right_scans) == 1
                and right_scans[0].table == self.pair_table):
            new_cols[f"__rblock_{self.pair_table}"] = right.block_id[match].astype(jnp.int32)
        return dataclasses.replace(left, columns=new_cols, valid=valid)

    def _trace_union(self, plan: L.Union, rt) -> _Traced:
        parts = [self.trace(p, rt) for p in plan.inputs]
        names = set(parts[0].columns)
        br = parts[0].block_rows
        offset = 0
        cols = {c: [] for c in names}
        valids, bids, pblocks = [], [], []
        for t in parts:
            if set(t.columns) != names or t.block_rows != br:
                raise ValueError("union inputs must share schema and block size")
            for c in names:
                cols[c].append(t.columns[c])
            valids.append(t.valid)
            bids.append(t.block_id + offset)
            pblocks.append(t.pblock)
            offset += t.num_origin_blocks
        pblock = (jnp.concatenate(pblocks)
                  if self.pilot_table is not None else None)
        return _Traced({c: jnp.concatenate(v) for c, v in cols.items()},
                       jnp.concatenate(valids), jnp.concatenate(bids),
                       pblock, br, offset)


# ---------------------------------------------------------------------------
# Kernel-shape matching (plan suffix -> Pallas lowering)
# ---------------------------------------------------------------------------

def _single_table_chain(child: L.Plan, table: str) -> Optional[List[Expr]]:
    """If ``child`` is Filter*(Scan(table)), return its predicates (maybe [])."""
    preds: List[Expr] = []
    node = child
    while isinstance(node, L.Filter):
        preds.append(node.pred)
        node = node.child
    if isinstance(node, L.Scan) and node.table == table:
        return preds
    return None


def _flatten_conjuncts(pred: Expr) -> List[Expr]:
    if isinstance(pred, And):
        return _flatten_conjuncts(pred.left) + _flatten_conjuncts(pred.right)
    return [pred]


def _match_q6_bounds(preds: List[Expr]) -> Optional[Tuple[Tuple[str, str, str], tuple]]:
    """Map a conjunctive range predicate onto filtered_agg's fixed slots.

    The kernel evaluates ``lo1<=f1<=hi1 AND lo2<=f2<=hi2 AND f3<c3`` with
    *runtime* bounds (scalar prefetch).  Two-sided/non-strict conditions
    fill the f1/f2 slots, a single strict upper bound fills f3; unused slots
    are padded with ±3e38 (never binding for f32 data).  Bound slots are
    either a plain float (the sentinels) or a constant-free :class:`Expr`
    (Param slots of a template plan) evaluated against the params vector at
    trace time.  Returns ((f1,f2,f3) column names, 5 bound slots) or None
    when the predicate doesn't fit.
    """
    conjuncts: List[Expr] = []
    for p in preds:
        conjuncts.extend(_flatten_conjuncts(p))
    two_sided: List[Tuple[str, object, object]] = []
    strict: List[Tuple[str, object]] = []
    for c in conjuncts:
        if isinstance(c, Between) and isinstance(c.arg, Col):
            two_sided.append((c.arg.name, c.lo, c.hi))
        elif isinstance(c, Cmp) and isinstance(c.left, Col) and not c.right.columns():
            v = c.right
            if c.op == "<":
                strict.append((c.left.name, v))
            elif c.op == "<=":
                two_sided.append((c.left.name, -_BIG_BOUND, v))
            elif c.op == ">=":
                two_sided.append((c.left.name, v, _BIG_BOUND))
            else:
                return None
        else:
            return None
    if len(two_sided) > 2 or len(strict) > 1:
        return None
    anchor = (two_sided + [(s[0], -_BIG_BOUND, _BIG_BOUND) for s in strict])
    if not anchor:
        return None  # no predicate at all: the block_agg route handles it
    while len(two_sided) < 2:
        two_sided.append((anchor[0][0], -_BIG_BOUND, _BIG_BOUND))
    if not strict:
        strict.append((anchor[0][0], _BIG_BOUND))
    (f1, lo1, hi1), (f2, lo2, hi2) = two_sided
    f3, c3 = strict[0]
    return (f1, f2, f3), (lo1, hi1, lo2, hi2, c3)


def _bounds_vector(slots: tuple, params) -> jnp.ndarray:
    """Materialize the 5 kernel bound slots as a (5,) runtime f32 vector."""
    vals = []
    for s in slots:
        if isinstance(s, Expr):
            vals.append(jnp.asarray(eval_expr(s, {}, params), jnp.float32))
        else:
            vals.append(jnp.float32(s))
    return jnp.stack(vals)


def _match_channels(exprs: Sequence[Optional[Expr]], *, products: bool):
    """Channels as kernel-computable specs.

    ``products=True`` (filtered route) accepts COUNT / SUM(col) / SUM(a*b);
    ``products=False`` (block route) accepts COUNT / SUM(col).  Returns a
    list of ("count",) | ("prod", x, y|None) specs, or None on mismatch.
    """
    specs = []
    for e in exprs:
        if e is None:
            specs.append(("count",))
        elif isinstance(e, Col):
            specs.append(("prod", e.name, None))
        elif (products and isinstance(e, BinOp) and e.op == "*"
              and isinstance(e.left, Col) and isinstance(e.right, Col)):
            specs.append(("prod", e.left.name, e.right.name))
        else:
            return None
    return specs


# ---------------------------------------------------------------------------
# Compiled executables
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _CompiledBase:
    fn: Callable
    catalog: Dict[str, BlockTable]
    needed: Dict[str, Tuple[str, ...]]
    methods: Dict[str, str]
    route: str
    # The runtime arguments of the latest launch: ``fn.lower(last_args)``
    # re-lowers the program that ran.  Not kept for row-sampled scans,
    # whose per-row masks are too large to hold between launches.
    last_args: Optional[dict] = dataclasses.field(
        default=None, init=False, repr=False)

    def _launch(self, rt: dict):
        if not rt["mask"]:
            self.last_args = rt
        return self.fn(rt)

    def _shared_args(self) -> dict:
        """Per-table inputs that do not vary across a batch: column data,
        validity, block ids (the catalog side of the runtime dict)."""
        rt = {"cols": {}, "valid": {}, "bid": {}, "ids": {}, "nreal": {}, "mask": {}}
        for name in self.needed:
            tab = self.catalog[name]
            rt["cols"][name] = {c: tab.columns[c] for c in self.needed[name]}
            # the exact-scan kernel reads ``valid`` as the table's int8 copy
            rt["valid"][name] = (tab.valid_bytes()
                                 if self.route == "pallas_exact"
                                 else tab.valid)
            rt["bid"][name] = tab.block_id
        return rt

    def _runtime_args(self, runtimes: Dict[str, ScanRuntime],
                      params=()) -> dict:
        # Host inputs stay NumPy: jit then copies them straight to the
        # device the table's columns live on (a dist shard's own device),
        # not through the default device first.
        rt = self._shared_args()
        for name in self.needed:
            r = runtimes.get(name)
            method = self.methods.get(name, "none")
            if method == "block":
                rt["ids"][name] = r.ids_dev if r.ids_dev is not None \
                    else np.asarray(r.ids, np.int32)
                rt["nreal"][name] = r.nreal_dev if r.nreal_dev is not None \
                    else np.int32(r.n_real)
            elif method == "row":
                rt["mask"][name] = jnp.asarray(r.keep_mask)
        rt["params"] = np.asarray(params, np.float32)
        return rt

    def __call__(self, runtimes: Dict[str, ScanRuntime], params=()):
        return self._launch(self._runtime_args(runtimes, params))

    def scanned_bytes(self, runtimes: Dict[str, ScanRuntime]) -> int:
        """Total scan cost of one run (see :func:`scan_cost_bytes`)."""
        total = 0
        for name in self.needed:
            method = self.methods.get(name, "none")
            n_real = runtimes[name].n_real if method == "block" else 0
            total += scan_cost_bytes(self.catalog[name], method, n_real)
        return total


@dataclasses.dataclass
class CompiledQuery(_CompiledBase):
    """fn(rt) -> (sums (num_channels, max_groups), counts (max_groups,))."""


@dataclasses.dataclass
class CompiledPilot(_CompiledBase):
    """fn(rt) -> (block_sums (n_phys, max_groups, num_channels),
                  group_present (max_groups,) bool,
                  pair (n_phys, n_right, num_channels) or None)."""

    has_pair: bool = False


@dataclasses.dataclass
class CompiledBatch(_CompiledBase):
    """A drain-group batch executable: ``lax.map`` over B same-signature
    members inside ONE jitted dispatch.

    Member lanes differ only in their sampled block ids / row masks and
    their hoisted-constant params row; the per-lane computation is the
    member's solo XLA graph, so lane k of the batch is bit-identical to
    running member k alone.  ``call_batch`` stacks the member runtimes
    (block-id matrix, nreal vector, params matrix) and returns
    (sums (B, num_channels, max_groups), counts (B, max_groups)).
    """

    batch: int = 0

    def call_batch(self, runtimes_list: Sequence[Dict[str, ScanRuntime]],
                   params_list: Sequence[np.ndarray]):
        if len(runtimes_list) != self.batch or len(params_list) != self.batch:
            raise ValueError(
                f"batch executable compiled for {self.batch} members, "
                f"got {len(runtimes_list)}")
        rt = self._shared_args()
        for name in self.needed:
            method = self.methods.get(name, "none")
            if method == "block":
                rt["ids"][name] = jnp.stack(
                    [jnp.asarray(r[name].ids, jnp.int32) for r in runtimes_list])
                rt["nreal"][name] = jnp.asarray(
                    [r[name].n_real for r in runtimes_list], jnp.int32)
            elif method == "row":
                rt["mask"][name] = jnp.stack(
                    [jnp.asarray(r[name].keep_mask) for r in runtimes_list])
        rt["params"] = jnp.asarray(
            np.asarray(params_list, np.float32).reshape(self.batch, -1))
        return self._launch(rt)


@dataclasses.dataclass
class CompiledPilotBatch(CompiledBatch):
    """A batched pilot executable: ``lax.map`` over B same-signature pilot
    scans inside ONE jitted dispatch (the shared-pilot drain-group path).

    ``call_batch`` stacks the member pilot runtimes (block-id matrix, nreal
    vector, params matrix) and returns
    (block_sums (B, n_phys, max_groups, num_channels), present (B, max_groups));
    lane k is bit-identical to member k's solo tracer-route pilot."""


def fused_buckets(num_blocks: int) -> Tuple[int, ...]:
    """Static id-length buckets of the fused final stage.

    Mirrors ``sampling.pad_block_ids``: for any real sampled count n in
    [0, num_blocks], ``min(bucket_blocks(max(n, 1)), num_blocks)`` is one of
    these values — so the on-device ``lax.switch`` branch the fused program
    picks has exactly the physical id length the solo path would pad to.
    """
    out: List[int] = []
    b = 64
    while b < num_blocks:
        out.append(b)
        b <<= 1
    out.append(num_blocks)
    return tuple(out)


@dataclasses.dataclass
class CompiledFused(_CompiledBase):
    """The single-launch TAQA program (pilot -> rate solve -> final).

    fn(rt) -> (block_sums (n_phys_p, max_groups, n_ch), present (max_groups,),
               theta f32, flags int32 bitmask (1 no-groups | 2 bad L_mu |
               4 no feasible plan), nsel int32, padded_ids (num_blocks,) int32,
               sums (n_ch, max_groups), counts (max_groups,)).

    ``call_fused`` adds the three fused-only runtime operands to the standard
    runtime dict: the per-constraint quantile table ``solve`` (n_solve, 5)
    rows [t_q, chi_q, z, z_bin, e], the shared scalar vector ``scal`` (6,)
    [N, max_rate, min_rate, cost_a, cost_b, exact_cost], and the final-draw
    uniform vector ``u`` (num_blocks,) — all host-precomputed, none requiring
    a sync between the stages.
    """

    buckets: Tuple[int, ...] = ()

    def call_fused(self, runtimes: Dict[str, ScanRuntime], params,
                   solve, scal, u):
        rt = self._runtime_args(runtimes, params)
        rt["solve"] = jnp.asarray(
            np.asarray(solve, np.float32).reshape(-1, 5))
        rt["scal"] = jnp.asarray(np.asarray(scal, np.float32))
        rt["u"] = jnp.asarray(np.asarray(u, np.float32))
        return self._launch(rt)


@dataclasses.dataclass
class CacheInfo:
    hits: int = 0
    misses: int = 0
    size: int = 0
    # Staged-sample-catalog serving counters (repro.engine.staged), filled
    # in by Executor.compile_cache_info; zero for a bare compiler.
    staged_hits: int = 0
    staged_misses: int = 0
    # Per-kind attribution of the hit/miss totals above.  ``hits``/``misses``
    # remain the grand totals (existing dashboards keep working); these pairs
    # break out pilot lowerings (solo + batched), drain-group batch
    # executables, and fused TAQA programs so stats_payload() can attribute
    # compilation traffic per path.  Plain query compiles are the remainder.
    pilot_hits: int = 0
    pilot_misses: int = 0
    batched_hits: int = 0
    batched_misses: int = 0
    fused_hits: int = 0
    fused_misses: int = 0
    # Local-cache misses that adopted an executable from a cross-shard
    # SharedBuildStore instead of tracing+compiling (still counted in
    # ``misses``: the local cache did miss — the BUILD was deduplicated).
    shared_hits: int = 0


class SharedBuildStore:
    """Cross-compiler executable store keyed by compile signature.

    Dist shards with identical slab geometry produce identical compile keys
    (keys embed block_rows / padded_rows / bucketed block counts and column
    dtypes, never column data — data enters executables as runtime
    operands).  Same-geometry shard compilers therefore adopt each other's
    built executables: the jitted ``fn`` (and its XLA executable cache) is
    shared and only the catalog binding is rebound per shard, so N
    same-shape shards pay ONE trace+compile instead of N.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._store: Dict[tuple, object] = {}

    def get(self, key):
        with self._lock:
            return self._store.get(key)

    def put(self, key, compiled) -> None:
        with self._lock:
            self._store.setdefault(key, compiled)


# key[0] -> CacheInfo counter kind ("query" keys are the untagged remainder)
_KEY_KIND = {"pilot": "pilot", "pilot_batched": "pilot",
             "batched": "batched", "fused": "fused"}


class PhysicalCompiler:
    """Lowers logical plans to compiled executables, with a signature cache."""

    def __init__(self, catalog: Dict[str, BlockTable], kernel_mode: str = "auto",
                 shared_builds: Optional[SharedBuildStore] = None):
        if kernel_mode not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"kernel_mode must be 'auto', 'pallas', or 'xla', got {kernel_mode!r}")
        self.catalog = catalog
        self.kernel_mode = kernel_mode
        # Optional cross-compiler build store (dist shard dedup): consulted
        # on local-cache misses before building, populated after builds.
        self._shared = shared_builds
        # Values are compiled executables, or a pending Future while one
        # worker builds that key.  The concurrent runtime compiles from
        # worker threads: the lock covers only dict bookkeeping and the
        # hit/miss counters (asserted by scheduler/runtime tests), while
        # tracing/XLA compilation happens OUTSIDE it — distinct plan shapes
        # compile in parallel, cache hits never stall behind a build, and a
        # key still compiles at most once (waiters block on its Future).
        self._cache: Dict[tuple, object] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.shared_hits = 0
        self._kind_hits = {"pilot": 0, "batched": 0, "fused": 0}
        self._kind_misses = {"pilot": 0, "batched": 0, "fused": 0}

    def cache_info(self) -> CacheInfo:
        with self._lock:
            size = sum(1 for v in self._cache.values()
                       if not isinstance(v, Future))
            return CacheInfo(
                self.hits, self.misses, size,
                pilot_hits=self._kind_hits["pilot"],
                pilot_misses=self._kind_misses["pilot"],
                batched_hits=self._kind_hits["batched"],
                batched_misses=self._kind_misses["batched"],
                fused_hits=self._kind_hits["fused"],
                fused_misses=self._kind_misses["fused"],
                shared_hits=self.shared_hits)

    def executables(self) -> list:
        """The executables built so far; each names its ``route``."""
        with self._lock:
            return [v for v in self._cache.values()
                    if not isinstance(v, Future)]

    # -- route policy --------------------------------------------------------
    def _use_pallas(self) -> bool:
        if self.kernel_mode == "auto":
            # Interpret mode executes the grid step-by-step in the Pallas
            # interpreter — fine for correctness tests, hopeless as a hot
            # path — so off-TPU the same physical plan lowers to the XLA twin.
            return jax.default_backend() == "tpu"
        return self.kernel_mode == "pallas"

    def _geometry_sig(self, plan: L.Plan, needed) -> tuple:
        out = []
        for t in sorted(needed):
            tab = self.catalog[t]
            out.append((t, tab.block_rows, tab.padded_rows, tab.num_origin_blocks,
                        tuple((c, str(tab.columns[c].dtype)) for c in needed[t])))
        return tuple(out)

    def _lookup(self, key, build):
        kind = _KEY_KIND.get(key[0])
        with self._lock:
            entry = self._cache.get(key)
            if entry is None:  # this thread builds; others wait on the Future
                self.misses += 1
                if kind is not None:
                    self._kind_misses[kind] += 1
                placeholder: Future = Future()
                self._cache[key] = placeholder
            else:
                self.hits += 1  # a waiter did not build — that's a hit
                if kind is not None:
                    self._kind_hits[kind] += 1
        if _trace.active() is not None:  # tag the enclosing stage span
            _trace.annotate_count(
                "compile_misses" if entry is None else "compile_hits")
            _trace.annotate(compile_sig=_trace.sig_hash(key))
        if entry is None:
            try:
                compiled = None
                if self._shared is not None:
                    proto = self._shared.get(key)
                    if proto is not None:
                        # adopt the shared executable: same jitted fn (one
                        # XLA compilation serves all same-geometry shards),
                        # rebound to THIS compiler's catalog for data
                        compiled = dataclasses.replace(proto, catalog=self.catalog)
                        with self._lock:
                            self.shared_hits += 1
                if compiled is None:
                    compiled = build()
                    if self._shared is not None:
                        self._shared.put(key, compiled)
            except BaseException as e:
                with self._lock:  # let a later call retry the build
                    if self._cache.get(key) is placeholder:
                        del self._cache[key]
                placeholder.set_exception(e)
                raise
            with self._lock:
                self._cache[key] = compiled
            placeholder.set_result(compiled)
            return compiled
        if isinstance(entry, Future):
            return entry.result()  # blocks until built; re-raises its error
        return entry

    # -- final / plain queries ----------------------------------------------
    def query_signature(self, plan: L.Aggregate,
                        runtimes: Dict[str, ScanRuntime]) -> tuple:
        """The solo compile key of ``plan`` (constants hoisted) — also the
        bucketing key of the drain-group batch path: members agreeing on it
        share one executable and may share one batched dispatch."""
        needed = _needed_by_table(plan, self.catalog)
        return ("query", self._use_pallas(),
                plan_signature(plan, runtimes, self._geometry_sig(plan, needed)))

    def compile_query(self, plan: L.Aggregate,
                      runtimes: Dict[str, ScanRuntime]) -> CompiledQuery:
        needed = _needed_by_table(plan, self.catalog)
        key = ("query", self._use_pallas(),
               plan_signature(plan, runtimes, self._geometry_sig(plan, needed)))
        return self._lookup(key, lambda: self._build_query(
            plan_template(plan), runtimes, needed))

    def _query_run_fn(self, template, runtimes, needed, allow_kernel=True):
        """The per-member XLA lowering of a (template) query plan: either a
        whole-query Pallas kernel route or the traced gather pipeline.
        Returns (run, route); ``run(rt)`` expects ``rt["params"]``."""
        methods = {t: r.method for t, r in runtimes.items()}
        exprs = tuple(None if a.op == "count" else a.expr for a in template.aggs)
        mg = template.max_groups

        kernel = None
        if allow_kernel and self._use_pallas():
            if all(m == "none" for m in methods.values()):
                kernel = self._match_exact_kernel(template, runtimes, needed,
                                                  exprs)
            else:
                kernel = self._match_query_kernel(template, runtimes, exprs)
        if kernel is not None:
            return kernel

        tracer = _Tracer(self.catalog, needed, methods)

        def run(rt):
            tt = tracer.trace(template.child, rt)
            # the trailing COUNT channel gives the per-group row counts
            totals = blockwise_totals(tt.columns, tt.valid, exprs + (None,),
                                      template.group_by, mg, tt.block_rows,
                                      rt["params"])
            return totals[:-1], totals[-1]

        return run, "xla_gather"

    def _build_query(self, template, runtimes, needed) -> CompiledQuery:
        methods = {t: r.method for t, r in runtimes.items()}
        run, route = self._query_run_fn(template, runtimes, needed)
        if all(m == "none" for m in methods.values()):
            # the exact scan gets a jit name of its own (``jit_run_exact``
            # in the device trace), apart from the sampled scans' ``jit_run``
            def run_exact(rt):
                return run(rt)

            fn = jax.jit(run_exact)
        else:
            fn = jax.jit(run)
        return CompiledQuery(fn=fn, catalog=self.catalog, needed=needed,
                             methods=methods, route=route)

    # -- batched drain-group queries -----------------------------------------
    def compile_batched_query(self, plan: L.Aggregate,
                              runtimes: Dict[str, ScanRuntime],
                              batch: int) -> CompiledBatch:
        """One executable running ``batch`` same-signature members per
        dispatch.  Only the XLA route is batched: the Pallas kernel routes
        own their grids, and off-TPU (where batching matters most — per-call
        dispatch overhead) ``auto`` lowers to XLA anyway.  Callers bucket
        ``batch`` (powers of two) so compile misses stay O(log N)."""
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        needed = _needed_by_table(plan, self.catalog)
        key = ("batched", self._use_pallas(), batch,
               plan_signature(plan, runtimes, self._geometry_sig(plan, needed)))
        return self._lookup(key, lambda: self._build_batched(
            plan_template(plan), runtimes, needed, batch))

    def _build_batched(self, template, runtimes, needed, batch) -> CompiledBatch:
        methods = {t: r.method for t, r in runtimes.items()}
        if self._use_pallas():
            # Megacore-style batched kernel grid: shapes the solo path routes
            # through filtered_agg/block_agg run all B members' finals as ONE
            # kernel launch (grid (B, n_sampled), ids/bounds tables stacked
            # across lanes).  The matcher mirrors _match_query_kernel exactly,
            # so a shape falls through to the lax.map twin below only when
            # the solo route also used xla_gather — lanes stay bit-identical
            # to solo runs either way.
            kb = self._match_batched_query_kernel(template, runtimes)
            if kb is not None:
                run_b, route = kb
                return CompiledBatch(fn=jax.jit(run_b), catalog=self.catalog,
                                     needed=needed, methods=methods,
                                     route=route, batch=batch)
        # lax.map over a Pallas grid is not a supported lowering; shapes the
        # batched kernels cannot take (and every non-pallas route) map the
        # member's XLA graph.
        run, _ = self._query_run_fn(template, runtimes, needed,
                                    allow_kernel=False)

        def run_batched(rt):
            member = {"ids": rt["ids"], "nreal": rt["nreal"],
                      "mask": rt["mask"], "params": rt["params"]}
            shared = {"cols": rt["cols"], "valid": rt["valid"], "bid": rt["bid"]}

            def one(m):
                return run({**shared, **m})

            # lax.map, not vmap: each lane executes the member's own solo
            # graph sequentially inside ONE dispatch, so lane outputs are
            # bit-identical to solo runs (same f32 reduction order).
            return jax.lax.map(one, member)

        return CompiledBatch(fn=jax.jit(run_batched), catalog=self.catalog,
                             needed=needed, methods=methods,
                             route="xla_batched", batch=batch)

    def _match_query_kernel(self, plan, runtimes, exprs):
        """Whole-query kernel route: one block-sampled table, no groups.

        The grouped totals are the per-block kernel stats summed over sampled
        blocks, so the Q6/plain shapes skip the gather entirely.
        """
        if plan.max_groups != 1 or plan.group_by is not None:
            return None
        sampled = [t for t, r in runtimes.items() if r.method != "none"]
        if len(runtimes) != 1 or len(sampled) != 1 or runtimes[sampled[0]].method != "block":
            return None
        table = sampled[0]
        preds = _single_table_chain(plan.child, table)
        if preds is None:
            return None
        lowered = self._lower_block_stats(table, preds, exprs, with_rows=False)
        if lowered is None:
            return None
        stats_fn, route = lowered

        def run(rt):
            ch, cnt = stats_fn(rt)      # (n_phys, n_ch), (n_phys,)
            return ch.sum(axis=0)[:, None], cnt.sum()[None]

        return run, route

    def _match_exact_kernel(self, plan, runtimes, needed, exprs):
        """Whole-query kernel route of an exact scan: one unsampled table,
        no groups, the predicates ``pallas_filtered`` takes
        (:func:`_match_q6_bounds`) and its channels (:func:`_match_channels`,
        one fewer than the kernel's lanes, for the row count), rows a
        multiple of 128.

        The kernel evaluates the plan's own predicates and channels
        (:func:`eval_expr`, :func:`channel_values`) on each chunk, exactly
        as the ``xla_gather`` route does on the whole column: an int32
        column meets its float32 bounds in float32 on both routes.
        """
        if plan.max_groups != 1 or plan.group_by is not None:
            return None
        if len(runtimes) != 1:
            return None
        (table,) = runtimes
        preds = _single_table_chain(plan.child, table)
        if (not preds or _match_q6_bounds(preds) is None
                or _match_channels(exprs, products=True) is None):
            return None
        if (self.catalog[table].padded_rows % 128
                or len(exprs) >= exact_agg_max_channels):
            return None
        names = needed[table]
        channels = exprs + (None,)  # the trailing COUNT gives the row count

        def channels_fn(cols, ok, params):
            cols = dict(zip(names, cols))
            for p in preds:
                ok = ok & eval_expr(p, cols, params)
            return channel_values(cols, ok, channels, params)

        def run(rt):
            cols = rt["cols"][table]
            parts = exact_agg([cols[c] for c in names], rt["valid"][table],
                              rt["params"], channels_fn, len(channels))
            totals = pairwise_sum(parts)
            return totals[:-1, None], totals[-1:]

        return run, "pallas_exact"

    def _match_batched_query_kernel(self, plan, runtimes):
        """Batched whole-query kernel route (the megacore-style grid).

        Same admission conditions as :meth:`_match_query_kernel` — ONE
        block-sampled table, no groups, Filter*(Scan), kernel-computable
        channels — so the batched kernel engages exactly when the solo
        kernel would.  The per-lane reduction (``sum(axis=1)``) runs in the
        same order as the solo route's ``sum(axis=0)``, keeping each lane
        bit-identical to its member's solo kernel run.
        """
        exprs = tuple(None if a.op == "count" else a.expr for a in plan.aggs)
        if plan.max_groups != 1 or plan.group_by is not None:
            return None
        sampled = [t for t, r in runtimes.items() if r.method != "none"]
        if len(runtimes) != 1 or len(sampled) != 1 or runtimes[sampled[0]].method != "block":
            return None
        table = sampled[0]
        preds = _single_table_chain(plan.child, table)
        if preds is None:
            return None
        lowered = self._lower_block_stats_batched(table, preds, exprs)
        if lowered is None:
            return None
        stats_fn, route = lowered

        def run(rt):
            ch, cnt = stats_fn(rt)      # (B, n_phys, n_ch), (B, n_phys)
            return ch.sum(axis=1)[:, :, None], cnt.sum(axis=1)[:, None]

        return run, route

    # -- pilot queries -------------------------------------------------------
    def compile_pilot(self, plan: L.Aggregate, pilot_table: str,
                      runtime: ScanRuntime,
                      pair_table: Optional[str] = None) -> CompiledPilot:
        needed = _needed_by_table(plan, self.catalog)
        key = ("pilot", self._use_pallas(), pilot_table, pair_table,
               plan_signature(plan, {pilot_table: runtime},
                              self._geometry_sig(plan, needed)))
        return self._lookup(key, lambda: self._build_pilot(
            plan_template(plan), pilot_table, runtime.n_phys, pair_table,
            needed))

    def _build_pilot(self, plan, pilot_table, n_phys, pair_table, needed) -> CompiledPilot:
        methods = {pilot_table: "block"}
        mg = plan.max_groups
        # One channel per simple aggregate plus the trailing "__rows" channel
        # (group presence + COUNT/AVG planning), matching PilotStats.
        exprs = tuple([None if a.op == "count" else a.expr for a in plan.aggs] + [None])
        has_pair = pair_table is not None and any(
            isinstance(p, L.Join) and [s.table for s in p.right.scans()] == [pair_table]
            for p in _walk(plan))

        if self._use_pallas() and mg == 1 and not has_pair:
            preds = _single_table_chain(plan.child, pilot_table)
            if preds is not None:
                lowered = self._lower_block_stats(pilot_table, preds, exprs,
                                                  with_rows=True)
                if lowered is not None:
                    stats_fn, route = lowered

                    def run(rt):
                        ch, _ = stats_fn(rt)               # (n_phys, n_ch)
                        block_sums = ch[:, None, :]        # mg == 1
                        present = (ch[:, -1].sum() > 0)[None]
                        return block_sums, present, None

                    return CompiledPilot(fn=jax.jit(run), catalog=self.catalog,
                                         needed=needed, methods=methods,
                                         route=route, has_pair=False)

        run = self._pilot_tracer_run(plan, pilot_table, n_phys, pair_table,
                                     needed, has_pair)
        return CompiledPilot(fn=jax.jit(run), catalog=self.catalog, needed=needed,
                             methods=methods, route="xla_gather", has_pair=has_pair)

    def _pilot_tracer_run(self, plan, pilot_table, n_phys, pair_table, needed,
                          has_pair):
        """The tracer-route pilot body: rt -> (block_sums, present, pair).

        Shared verbatim by the solo pilot lowering, each lane of the batched
        pilot executable, and the pilot half of the fused TAQA program — one
        body, so the three paths cannot drift apart bitwise.
        """
        methods = {pilot_table: "block"}
        mg = plan.max_groups
        exprs = tuple([None if a.op == "count" else a.expr for a in plan.aggs] + [None])
        tracer = _Tracer(self.catalog, needed, methods, pilot_table=pilot_table,
                         n_phys_pilot=n_phys, pair_table=pair_table)
        n_right = self.catalog[pair_table].num_blocks if has_pair else 0
        rcol = f"__rblock_{pair_table}" if has_pair else None

        def run(rt):
            tt = tracer.trace(plan.child, rt)
            rows = tt.valid.shape[0]
            if plan.group_by is None:
                gid = jnp.zeros(rows, jnp.int32)
            else:
                gid = jnp.clip(tt.columns[plan.group_by].astype(jnp.int32), 0, mg - 1)
            vals = channel_matrix(tt.columns, tt.valid, exprs, rt["params"])
            seg = tt.pblock * mg + gid
            dense = jnp.zeros((len(exprs), (n_phys + 1) * mg),
                              jnp.float32).at[:, seg].add(vals)
            bs = dense[:, : n_phys * mg].reshape(len(exprs), n_phys, mg)
            block_sums = bs.transpose(1, 2, 0)
            present = block_sums[:, :, -1].sum(axis=0) > 0
            pair = None
            if has_pair:
                rb = jnp.where(tt.valid, tt.columns[rcol], 0)
                pseg = tt.pblock * n_right + rb
                pdense = jnp.zeros((len(exprs), (n_phys + 1) * n_right),
                                   jnp.float32).at[:, pseg].add(vals)
                pair = pdense[:, : n_phys * n_right].reshape(
                    len(exprs), n_phys, n_right).transpose(1, 2, 0)
            return block_sums, present, pair

        return run

    # -- batched pilots (shared-pilot drain groups) ---------------------------
    def compile_batched_pilot(self, plan: L.Aggregate, pilot_table: str,
                              runtime: ScanRuntime,
                              batch: int) -> "CompiledPilotBatch":
        """One executable running ``batch`` same-signature pilot scans per
        dispatch (``lax.map`` over the solo tracer pilot body).  Pair-table
        shapes and Pallas pilot routes stay solo — callers gate on both."""
        if batch < 2:
            raise ValueError(f"batch must be >= 2, got {batch}")
        needed = _needed_by_table(plan, self.catalog)
        key = ("pilot_batched", batch, pilot_table,
               plan_signature(plan, {pilot_table: runtime},
                              self._geometry_sig(plan, needed)))
        return self._lookup(key, lambda: self._build_batched_pilot(
            plan_template(plan), pilot_table, runtime.n_phys, needed, batch))

    def _build_batched_pilot(self, plan, pilot_table, n_phys, needed,
                             batch) -> "CompiledPilotBatch":
        methods = {pilot_table: "block"}
        run = self._pilot_tracer_run(plan, pilot_table, n_phys, None, needed,
                                     False)

        def run_batched(rt):
            member = {"ids": rt["ids"], "nreal": rt["nreal"],
                      "mask": rt["mask"], "params": rt["params"]}
            shared = {"cols": rt["cols"], "valid": rt["valid"], "bid": rt["bid"]}

            def one(m):
                bs, present, _ = run({**shared, **m})
                return bs, present

            # lax.map, not vmap: lane k executes the solo pilot body
            # sequentially inside ONE dispatch — bit-identical to solo.
            return jax.lax.map(one, member)

        return CompiledPilotBatch(fn=jax.jit(run_batched), catalog=self.catalog,
                                  needed=needed, methods=methods,
                                  route="xla_batched_pilot", batch=batch)

    # -- fused single-launch TAQA ---------------------------------------------
    def compile_fused(self, plan: L.Aggregate, pilot_table: str,
                      runtimes: Dict[str, ScanRuntime],
                      solve_channels: Tuple[int, ...]) -> "CompiledFused":
        """The single-launch TAQA program: pilot scan -> BSAP rate solve ->
        final sampled aggregation, one device dispatch, no host sync between
        the stages.  Gated by callers to the ungrouped / single-sampled-table
        / XLA-route shape; the rate solve on device is ADVISORY (f32) — the
        host re-solves in f64 and verifies the device's final draw before
        trusting its sums (see ``core.taqa.PilotDB.run_fused``)."""
        needed = _needed_by_table(plan, self.catalog)
        num_blocks = self.catalog[pilot_table].num_blocks
        key = ("fused", pilot_table, tuple(solve_channels), num_blocks,
               plan_signature(plan, runtimes, self._geometry_sig(plan, needed)))
        return self._lookup(key, lambda: self._build_fused(
            plan_template(plan), pilot_table, runtimes, needed,
            tuple(solve_channels), num_blocks))

    def _build_fused(self, template, pilot_table, runtimes, needed,
                     solve_channels, num_blocks) -> "CompiledFused":
        methods = {t: r.method for t, r in runtimes.items()}
        n_phys_pilot = runtimes[pilot_table].n_phys
        buckets = fused_buckets(num_blocks)
        pilot_run = self._pilot_tracer_run(template, pilot_table, n_phys_pilot,
                                           None, needed, False)
        # The final body is the member's solo XLA lowering (allow_kernel=False
        # matches the solo path: fused is gated off Pallas routes), traced
        # once per bucket branch with that bucket's static id length.
        final_run, _ = self._query_run_fn(template, runtimes, needed,
                                          allow_kernel=False)
        ch_idx = np.asarray(solve_channels, np.int32)

        def run(rt):
            bs, present, _ = pilot_run(rt)        # (n_phys_p, 1, n_ch), (1,)

            # --- BSAP rate solve, f32 (advisory twin of the f64 host path) --
            # Padding rows of bs are exactly zero, so the moment sums over the
            # full n_phys_p axis equal the n_real-row sums bit-for-bit.
            n = rt["nreal"][pilot_table].astype(jnp.float32)
            solve = rt["solve"]                   # (n_solve, 5) per-constraint
            scal = rt["scal"]                     # (6,) shared scalars
            N, max_rate, min_rate = scal[0], scal[1], scal[2]
            cost_a, cost_b, exact_cost = scal[3], scal[4], scal[5]
            y = bs[:, 0, :][:, ch_idx]            # (n_phys_p, n_solve)
            s1 = y.sum(axis=0)
            s2 = (y * y).sum(axis=0)
            mean = s1 / n
            var = jnp.maximum((s2 - s1 * s1 / n) / jnp.maximum(n - 1.0, 1.0), 0.0)
            t_q, chi_q, z, z_bin, e = (solve[:, i] for i in range(5))
            # L_mu of the population total: N * (block-mean lower bound)
            L_mu = N * (mean - t_q * jnp.sqrt(var) / jnp.sqrt(n))
            var_ub = (n - 1.0) / jnp.maximum(chi_q, 1e-12) * var
            L_ok = jnp.all((L_mu > 0.0) & jnp.isfinite(L_mu))

            def feasible(theta):
                # binomial lower bound on the final sample size, then U_V[θ]
                n_lb = jnp.maximum(
                    N * theta - z_bin * jnp.sqrt(
                        jnp.maximum(N * theta * (1.0 - theta), 0.0)), 0.0)
                u_v = jnp.where(n_lb > 1.0,
                                N * N * (1.0 - theta) * var_ub
                                / jnp.maximum(n_lb, 1e-30), jnp.inf)
                u_v = jnp.where(theta >= 1.0, 0.0, u_v)
                # phi rearranged sync-free: z*sqrt(U_V)/L_mu <= e, L_mu > 0
                ok = (L_mu > 0.0) & (z * jnp.sqrt(jnp.maximum(u_v, 0.0))
                                     <= e * L_mu)
                return jnp.all(ok)

            feas_max = feasible(max_rate)

            def body(_, lohi):
                lo, hi = lohi
                mid = jnp.sqrt(lo * hi)  # geometric: rates span decades
                f = feasible(mid)
                return (jnp.where(f, lo, mid), jnp.where(f, mid, hi))

            _, theta = jax.lax.fori_loop(0, 48, body, (min_rate, max_rate))
            have_plan = feas_max & (cost_a * theta + cost_b < exact_cost)
            go = present[0] & L_ok & have_plan
            flags = (jnp.where(present[0], 0, 1) + jnp.where(L_ok, 0, 2)
                     + jnp.where(have_plan, 0, 4)).astype(jnp.int32)
            theta_eff = jnp.where(go, theta, jnp.float32(0.0))

            # --- final Bernoulli draw + stream compaction (on device) -------
            keep = rt["u"] < theta_eff            # (num_blocks,) f32 uniforms
            nsel = keep.sum().astype(jnp.int32)
            pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
            padded = jnp.zeros(num_blocks, jnp.int32).at[
                jnp.where(keep, pos, num_blocks)].set(
                jnp.arange(num_blocks, dtype=jnp.int32), mode="drop")

            branch_idx = jnp.zeros((), jnp.int32)
            for b in buckets[:-1]:
                branch_idx = branch_idx + (nsel > b).astype(jnp.int32)

            shared = {"cols": rt["cols"], "valid": rt["valid"],
                      "bid": rt["bid"], "mask": rt["mask"],
                      "params": rt["params"]}

            def make_branch(b):
                def br(_):
                    frt = dict(shared)
                    frt["ids"] = {pilot_table: padded[:b]}
                    frt["nreal"] = {pilot_table: nsel}
                    return final_run(frt)
                return br

            sums, counts = jax.lax.switch(
                branch_idx, [make_branch(b) for b in buckets], None)
            return bs, present, theta, flags, nsel, padded, sums, counts

        return CompiledFused(fn=jax.jit(run), catalog=self.catalog,
                             needed=needed, methods=methods, route="xla_fused",
                             buckets=buckets)

    # -- Pallas lowering of per-block stats ----------------------------------
    def _lower_block_stats(self, table: str, preds: List[Expr],
                           exprs: Sequence[Optional[Expr]], *, with_rows: bool):
        """Lower Filter*(Scan) per-block channel stats onto the kernels.

        Returns (stats_fn, route) where ``stats_fn(rt)`` yields
        ``(channel_sums (n_phys, n_ch), counts (n_phys,))`` with padding rows
        (beyond n_real) zeroed, or None when the shape doesn't fit a kernel.
        The sampled block ids reach the kernels via scalar prefetch — and so
        do the predicate bounds, resolved from ``rt["params"]`` at trace
        time, so constant-varied queries share this one kernel compilation.
        """
        tab = self.catalog[table]
        br = tab.block_rows
        if preds:
            q6 = _match_q6_bounds(preds)
            specs = _match_channels(exprs, products=True)
            if q6 is None or specs is None:
                return None
            (f1, f2, f3), slots = q6

            def stats_fn(rt):
                cols = rt["cols"][table]
                valid = rt["valid"][table].astype(jnp.float32)
                ids = rt["ids"][table]
                nreal = rt["nreal"][table]
                n_phys = ids.shape[0]
                bounds = _bounds_vector(slots, rt["params"])
                ones = jnp.ones(tab.padded_rows, jnp.float32)
                outs = {}
                for spec in specs:
                    if spec[0] != "prod" or spec[1:] in outs:
                        continue
                    x = cols[spec[1]]
                    y = ones if spec[2] is None else cols[spec[2]]
                    outs[spec[1:]] = filtered_agg(
                        x, y, cols[f1], cols[f2], cols[f3], valid, br, ids, bounds)
                if not outs:  # COUNT-only query: any column works for cnt
                    c0 = cols[f1]
                    outs[None] = filtered_agg(c0, c0, cols[f1], cols[f2], cols[f3],
                                              valid, br, ids, bounds)
                cnt = next(iter(outs.values()))[:, 0]
                chans = [cnt if s[0] == "count" else outs[s[1:]][:, 1] for s in specs]
                mask = (jnp.arange(n_phys) < nreal).astype(jnp.float32)
                return jnp.stack(chans, axis=1) * mask[:, None], cnt * mask

            return stats_fn, "pallas_filtered"

        specs = _match_channels(exprs, products=False)
        if specs is None:
            return None

        def stats_fn(rt):
            cols = rt["cols"][table]
            valid = rt["valid"][table].astype(jnp.float32)
            ids = rt["ids"][table]
            nreal = rt["nreal"][table]
            n_phys = ids.shape[0]
            outs = {}
            for spec in specs:
                if spec[0] == "prod" and spec[1] not in outs:
                    outs[spec[1]] = block_agg(cols[spec[1]], valid, br, ids)
            if not outs:  # COUNT-only: the cnt lane ignores the value column
                outs[None] = block_agg(valid, valid, br, ids)
            cnt = next(iter(outs.values()))[:, 0]
            chans = [cnt if s[0] == "count" else outs[s[1]][:, 1] for s in specs]
            mask = (jnp.arange(n_phys) < nreal).astype(jnp.float32)
            return jnp.stack(chans, axis=1) * mask[:, None], cnt * mask

        return stats_fn, "pallas_block"

    def _lower_block_stats_batched(self, table: str, preds: List[Expr],
                                   exprs: Sequence[Optional[Expr]]):
        """Batched-lane twin of :meth:`_lower_block_stats`.

        ``rt["ids"][table]`` is (B, n_phys), ``rt["nreal"][table]`` (B,),
        ``rt["params"]`` (B, P).  Returns (stats_fn, route) with
        ``stats_fn(rt)`` yielding ``(channel_sums (B, n_phys, n_ch),
        counts (B, n_phys))`` — per lane exactly the solo stats — or None
        when the shape doesn't fit the kernels.  Per-lane predicate bounds
        resolve from the stacked params matrix (vmapped slot evaluation) and
        ride the scalar-prefetch path next to the stacked block-id table.
        """
        tab = self.catalog[table]
        br = tab.block_rows
        if preds:
            q6 = _match_q6_bounds(preds)
            specs = _match_channels(exprs, products=True)
            if q6 is None or specs is None:
                return None
            (f1, f2, f3), slots = q6

            def stats_fn(rt):
                cols = rt["cols"][table]
                valid = rt["valid"][table].astype(jnp.float32)
                ids = rt["ids"][table]
                nreal = rt["nreal"][table]
                n_phys = ids.shape[1]
                bounds = jax.vmap(lambda p: _bounds_vector(slots, p))(rt["params"])
                ones = jnp.ones(tab.padded_rows, jnp.float32)
                outs = {}
                for spec in specs:
                    if spec[0] != "prod" or spec[1:] in outs:
                        continue
                    x = cols[spec[1]]
                    y = ones if spec[2] is None else cols[spec[2]]
                    outs[spec[1:]] = filtered_agg_batched(
                        x, y, cols[f1], cols[f2], cols[f3], valid, br, ids, bounds)
                if not outs:  # COUNT-only query: any column works for cnt
                    c0 = cols[f1]
                    outs[None] = filtered_agg_batched(
                        c0, c0, cols[f1], cols[f2], cols[f3], valid, br, ids, bounds)
                cnt = next(iter(outs.values()))[:, :, 0]
                chans = [cnt if s[0] == "count" else outs[s[1:]][:, :, 1]
                         for s in specs]
                mask = (jnp.arange(n_phys)[None, :] < nreal[:, None]).astype(jnp.float32)
                return jnp.stack(chans, axis=2) * mask[:, :, None], cnt * mask

            return stats_fn, "pallas_filtered_batched"

        specs = _match_channels(exprs, products=False)
        if specs is None:
            return None

        def stats_fn(rt):
            cols = rt["cols"][table]
            valid = rt["valid"][table].astype(jnp.float32)
            ids = rt["ids"][table]
            nreal = rt["nreal"][table]
            n_phys = ids.shape[1]
            outs = {}
            for spec in specs:
                if spec[0] == "prod" and spec[1] not in outs:
                    outs[spec[1]] = block_agg_batched(cols[spec[1]], valid, br, ids)
            if not outs:  # COUNT-only: the cnt lane ignores the value column
                outs[None] = block_agg_batched(valid, valid, br, ids)
            cnt = next(iter(outs.values()))[:, :, 0]
            chans = [cnt if s[0] == "count" else outs[s[1]][:, :, 1] for s in specs]
            mask = (jnp.arange(n_phys)[None, :] < nreal[:, None]).astype(jnp.float32)
            return jnp.stack(chans, axis=2) * mask[:, :, None], cnt * mask

        return stats_fn, "pallas_block_batched"


def _walk(plan: L.Plan):
    yield plan
    if isinstance(plan, L.Aggregate):
        yield from _walk(plan.child)
    else:
        for c in plan.children():
            yield from _walk(c)
