"""Plan executor.

Executes logical plans through the compiled physical layer
(:mod:`repro.engine.physical`): each plan shape lowers once to a single
jitted executable — block-sampled scans route through the Pallas
block-aggregation kernels (or their XLA twin off-TPU) — and repeated
structurally-identical queries hit the compile cache.  The *scan cost*
(bytes moved HBM→VMEM) is attributed by that layer: block-sampled scans pay
only for sampled slabs, row-sampled and exact scans stream everything
(Fig. 1 / Fig. 4).

The pre-physical eager interpreter is retained (``use_compiled=False``) as
the comparison baseline for tests and benchmarks.

Besides plain execution it produces the two artifacts TAQA needs:

* ``QueryResult``     — per-group aggregate values (+ lineage/cost),
* ``execute_pilot``   — per-block (and per block-pair, for Lemma 4.8) pilot
                        statistics of every simple aggregate, computed with
                        zero host syncs between the scan and the statistics.

A sampled scan that draws zero blocks/rows raises :class:`EmptySampleError`
instead of fabricating an upscale factor — callers (``core.taqa``) take
their exact-execution fallback path explicitly.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.engine import logical as L
from repro.engine import ops
from repro.engine.physical import (PhysicalCompiler, ScanRuntime,
                                   plan_constants, scan_cost_bytes)
from repro.engine.sampling import (SampleInfo, block_sample, draw_block_ids,
                                   pad_block_ids, row_sample)
from repro.engine.staged import (DEFAULT_STAGED_RATES, SampleCatalog,
                                 build_ladder, prepare_mono_subdraw)
from repro.engine.table import BlockTable
from repro.obs import trace as _trace


class EmptySampleError(RuntimeError):
    """A sampled scan produced zero sampled units (blocks or rows).

    No unbiased upscale exists for an empty sample; rather than fabricating a
    scale (the old ``max(n, 1)`` behaviour, which silently degraded the
    estimate), the executor surfaces the condition so the caller can fall
    back to exact execution or re-sample at a higher rate.
    """

    def __init__(self, table: str, method: str, rate: float):
        self.table = table
        self.method = method
        self.rate = rate
        super().__init__(
            f"sampled scan of {table!r} ({method}, rate={rate}) drew 0 units")


@dataclasses.dataclass
class QueryResult:
    agg_names: List[str]
    values: np.ndarray           # (num_aggs, max_groups) float64, upscaled
    raw_sums: np.ndarray         # (num_aggs, max_groups) unscaled sample sums
    group_counts: np.ndarray     # (max_groups,) raw surviving row counts
    group_present: np.ndarray    # (max_groups,) bool
    scanned_bytes: int
    sample_infos: Dict[str, SampleInfo]
    wall_time_s: float
    # the physical route that computed it (``CompiledQuery.route``, or
    # "eager" for the interpreter); None where no single route did
    route: Optional[str] = None

    def scalar(self, name: str, group: int = 0) -> float:
        return float(self.values[self.agg_names.index(name), group])


@dataclasses.dataclass
class PilotStats:
    """Per-block statistics from the pilot query (§3.1, §3.3).

    block_sums: (n_p, max_groups, num_aggs) — sum of each simple aggregate's
        expression within each sampled origin block of the pilot table.
    pair_sums: optional {right_table: (n_p, N_right, num_aggs)} for Lemma 4.8.
    """

    table: str
    theta_p: float
    n_sampled_blocks: int
    n_total_blocks: int
    block_rows: int
    agg_names: List[str]
    block_sums: np.ndarray
    group_present: np.ndarray
    pair_sums: Dict[str, np.ndarray]
    right_total_blocks: Dict[str, int]
    scanned_bytes: int
    wall_time_s: float


def _draw_attrs(runtimes: Dict[str, ScanRuntime]) -> Dict[str, int]:
    """A ``draw`` span's attributes: the block-sampled scans' real and
    padded block counts."""
    block = [r for r in runtimes.values() if r.method == "block"]
    return {"n_blocks": sum(r.n_real for r in block),
            "n_phys": sum(r.n_phys for r in block)}


class Executor:
    def __init__(self, catalog: Dict[str, BlockTable], *,
                 use_compiled: bool = True, kernel_mode: str = "auto",
                 staged_bytes: Optional[int] = None, shared_builds=None):
        self.catalog = dict(catalog)
        self.use_compiled = use_compiled
        # shared_builds: an optional physical.SharedBuildStore letting
        # same-geometry compilers (dist shards) adopt each other's built
        # executables instead of tracing+compiling N times.
        self.physical = PhysicalCompiler(self.catalog, kernel_mode=kernel_mode,
                                         shared_builds=shared_builds)
        # Pre-staged block-sample ladders (repro.engine.staged): tables
        # opted in via register_staged() serve covered sampled scans from
        # materialized rungs; staged_bytes bounds rung-array residency.
        self.staged = SampleCatalog(max_bytes=staged_bytes)
        # Execution counters, lock-guarded: the concurrent runtime
        # (repro.runtime) runs queries from a worker pool, and its tests /
        # benchmarks assert pilot-sharing through exactly these numbers
        # (`+= 1` on an attribute is not atomic under threads).
        # pilots_run counts pilot STAGES (incremented by PilotDB.run_pilot,
        # once per stage regardless of undershoot retries); queries_run
        # counts execute() calls.
        self._counter_lock = threading.Lock()
        self.pilots_run = 0
        self.queries_run = 0
        # device_dispatches counts compiled-executable invocations (solo,
        # staged, batched bucket, pilot, fused) — the launch inventory the
        # fused-TAQA benchmark derives its host-sync count from.
        self.device_dispatches = 0
        # swallowed_failures counts exceptions an optimized route (batched
        # finals / pilots, drain-group finals, fused TAQA) caught before
        # re-running its work another way; last_swallowed names the latest.
        # The answers stay right, but a non-zero count means a route the
        # device should take failed, e.g. a kernel its compiler refused.
        self.swallowed_failures = 0
        self.last_swallowed: Optional[str] = None

    def _count(self, attr: str) -> None:
        with self._counter_lock:
            setattr(self, attr, getattr(self, attr) + 1)

    def note_swallowed(self, site: str, exc: BaseException) -> None:
        """Record an exception an optimized route swallowed at ``site``."""
        with self._counter_lock:
            self.swallowed_failures += 1
            self.last_swallowed = f"{site}: {type(exc).__name__}: {exc}"

    # -- catalog management ---------------------------------------------------
    def register_table(self, name: str, table: BlockTable) -> None:
        """Add (or replace) a catalog table.

        The physical compiler shares this catalog dict, so new tables are
        immediately compilable.  Replacing a table needs no *engine-level*
        cache invalidation: column data enters compiled executables as
        runtime arguments (``_CompiledBase._runtime_args``), and a geometry
        change alters the plan signature, forcing a fresh compilation.
        Higher layers may cache table *statistics* — go through their own
        registration (e.g. ``api.Session.register_table``, which refreshes
        its group-domain cache) rather than calling this directly.
        """
        self.catalog[name] = table
        # Staged lifecycle: the replaced table's ladder holds stale gathered
        # slabs — drop it (re-staging is the registrant's call); other
        # ladders replicate this table in their rung-compiler catalogs and
        # must see the new arrays.
        self.staged.invalidate(name)
        self.staged.refresh_replicated(name, table)

    def register_staged(self, name: str,
                        rates=DEFAULT_STAGED_RATES, *, seed: int = 0) -> None:
        """Materialize a staged sample ladder for catalog table ``name``.

        ``seed`` pins the table's one staging realization: EVERY block draw
        of the table (staged hit or fresh miss, pilot or final) replays it,
        which is what makes staged and fresh answers bit-identical.  The
        eager executor has no physical layer to serve rungs through, so
        staging is a no-op there (``use_compiled=False``).
        """
        if name not in self.catalog:
            raise KeyError(f"unknown table {name!r}")
        if not self.use_compiled:
            return
        self.staged.admit(build_ladder(
            name, self.catalog[name], rates, seed,
            self.physical.kernel_mode, self.catalog))

    # -- table metadata (the "DBMS statistics" TAQA consults) ---------------
    def table_rows(self, name: str) -> int:
        return self.catalog[name].num_rows

    def table_blocks(self, name: str) -> int:
        return self.catalog[name].num_blocks

    def block_rows(self, name: str) -> int:
        return self.catalog[name].block_rows

    def is_sharded(self, name: str) -> bool:
        """Whether ``name`` executes as sharded sub-scans (DistExecutor
        overrides).  A monolithic executor never shards."""
        return False

    def table_bytes(self, name: str) -> int:
        return self.catalog[name].total_bytes()

    def compile_cache_info(self):
        """Hit/miss/size counters of the physical-plan compile cache
        (including every staged rung's compiler) plus staged-path
        hit/miss counters.

        ``hits``/``misses`` are grand totals; pilot lowerings (solo and
        batched), drain-group batch executables, and fused TAQA programs are
        additionally broken out into ``pilot_*`` / ``batched_*`` /
        ``fused_*`` pairs, and ``shared_hits`` counts local misses served by
        adopting another same-geometry compiler's build (dist shard dedup).
        Rung compilers contribute to the totals only (their keys are plain
        query shapes)."""
        info = self.physical.cache_info()
        rung_hits, rung_misses, rung_size = self.staged.compile_totals()
        info.hits += rung_hits
        info.misses += rung_misses
        info.size += rung_size
        info.staged_hits = self.staged.hits
        info.staged_misses = self.staged.misses
        return info

    def staged_info(self) -> Dict[str, object]:
        """Staged-catalog serving counters and per-table ladder state."""
        return self.staged.info()

    # -- host-side sampling decisions ---------------------------------------
    def _scan_runtimes(
        self, plan: L.Plan, exclude: Optional[str] = None,
    ) -> Tuple[Dict[str, ScanRuntime], Dict[str, SampleInfo]]:
        """Draw every scan's TABLESAMPLE decision (host RNG, as a DBMS picks
        pages before scanning) and package it as compiled-executable inputs.

        Uses the same RNG stream as the eager samplers, so the two paths see
        identical samples for identical seeds.  A table with a staged ladder
        draws from its pinned staging seed (one realization per table —
        hits and misses agree bitwise); ``exclude`` skips one table whose
        runtime the staged route supplies itself.
        """
        runtimes: Dict[str, ScanRuntime] = {}
        infos: Dict[str, SampleInfo] = {}
        for s in plan.scans():
            if s.table == exclude:
                continue
            table = self.catalog[s.table]
            if s.sample is None:
                runtimes[s.table] = ScanRuntime("none")
                infos[s.table] = SampleInfo(
                    "none", 1.0, 0, table.num_blocks, table.num_blocks,
                    np.arange(table.num_blocks),
                    scanned_bytes=scan_cost_bytes(table, "none"))
            elif s.sample.method == "block":
                lad = self.staged.ladder(s.table)
                seed = s.sample.seed if lad is None else lad.seed
                if lad is not None and s.sample.rate < 1.0:
                    # a ladder-bearing table drawn fresh: rate uncovered,
                    # rung arrays evicted, or a route that bypasses staging
                    self.staged.note_miss()
                ids = draw_block_ids(table.num_blocks, s.sample.rate, seed)
                phys, n_real, n_phys = pad_block_ids(ids, table.num_blocks)
                runtimes[s.table] = ScanRuntime("block", n_real, n_phys, phys)
                infos[s.table] = SampleInfo(
                    "block", s.sample.rate, seed, n_real,
                    table.num_blocks, ids,
                    scanned_bytes=scan_cost_bytes(table, "block", n_real))
            else:
                rng = np.random.default_rng(s.sample.seed)
                keep = rng.random(table.padded_rows) < s.sample.rate
                n_kept = int((np.asarray(table.valid) & keep).sum())
                runtimes[s.table] = ScanRuntime("row", keep_mask=keep)
                info = SampleInfo("row", s.sample.rate, s.sample.seed, None,
                                  table.num_blocks, None,
                                  scanned_bytes=scan_cost_bytes(table, "row"))
                info.n_sampled_rows = n_kept
                info.n_total_rows = table.num_rows
                infos[s.table] = info
        return runtimes, infos

    @staticmethod
    def _check_empty(infos: Dict[str, SampleInfo]) -> None:
        for name, info in infos.items():
            if info.rate >= 1.0:
                continue
            if info.method == "block" and not info.n_sampled_blocks:
                raise EmptySampleError(name, "block", info.rate)
            if info.method == "row" and not info.n_sampled_rows:
                raise EmptySampleError(name, "row", info.rate)

    @staticmethod
    def _upscale(infos: Dict[str, SampleInfo]) -> float:
        """Upscaling (§3.3 final rewriting step 2).  With exactly one sampled
        table we use the Hájek scale N/n (conditional-SRS estimator matching
        BSAP's Lemma-B.1 bounds); with two or more we use Horvitz–Thompson
        1/∏θ (matching Lemma 4.8's variance expansion).  AVG is the ratio of
        two upscaled sums, so the scale cancels either way.  Empty samples
        raise EmptySampleError before this point — no fabricated scales.
        """
        sampled = [i for i in infos.values()
                   if i.method in ("block", "row") and i.rate < 1.0]
        if len(sampled) == 1:
            info = sampled[0]
            if info.method == "block":
                return info.n_total_blocks / info.n_sampled_blocks
            n = info.n_sampled_rows
            return (info.n_total_rows or n) / n
        scale = 1.0
        for info in sampled:
            scale /= info.rate
        return scale

    @staticmethod
    def _compose_values(plan: L.Aggregate, sums: np.ndarray, counts: np.ndarray,
                        scale: float) -> np.ndarray:
        values = np.zeros_like(sums)
        for i, a in enumerate(plan.aggs):
            if a.op in ("sum", "count"):
                values[i] = sums[i] * scale
            elif a.op == "avg":
                with np.errstate(invalid="ignore", divide="ignore"):
                    values[i] = np.where(counts > 0,
                                         sums[i] / np.maximum(counts, 1), np.nan)
        return values

    # -- eager relational execution (the pre-physical interpreter) -----------
    def _run_relational(
        self, plan: L.Plan, infos: Dict[str, SampleInfo],
        pair_for: Optional[Tuple[str, str]] = None,
    ) -> BlockTable:
        if isinstance(plan, L.Scan):
            table = self.catalog[plan.table]
            if plan.sample is None:
                infos[plan.table] = SampleInfo(
                    "none", 1.0, 0, table.num_blocks, table.num_blocks,
                    np.arange(table.num_blocks),
                    scanned_bytes=table.total_bytes())
                return table
            if plan.sample.method == "block":
                sampled, info = block_sample(table, plan.sample.rate, plan.sample.seed)
            else:
                sampled, info = row_sample(table, plan.sample.rate, plan.sample.seed)
            infos[plan.table] = info
            return sampled
        if isinstance(plan, L.Filter):
            child = self._run_relational(plan.child, infos, pair_for)
            return ops.filter_table(child, plan.pred)
        if isinstance(plan, L.Join):
            left = self._run_relational(plan.left, infos, pair_for)
            right = self._run_relational(plan.right, infos, pair_for)
            rblock_col = None
            if pair_for is not None and pair_for[1] == self._scan_table(plan.right):
                rblock_col = f"__rblock_{pair_for[1]}"
            return ops.join_unique(left, right, plan.left_key, plan.right_key,
                                   rblock_col=rblock_col)
        if isinstance(plan, L.Union):
            return ops.union_all(
                [self._run_relational(p, infos, pair_for) for p in plan.inputs])
        raise TypeError(plan)

    @staticmethod
    def _scan_table(plan: L.Plan) -> Optional[str]:
        scans = plan.scans()
        return scans[0].table if len(scans) == 1 else None

    # -- public API ----------------------------------------------------------
    def execute(self, plan: L.Aggregate) -> QueryResult:
        self._count("queries_run")
        with _trace.span("scan") as sp:
            if self.use_compiled:
                res = self._execute_compiled(plan)
            else:
                res = self._execute_eager(plan)
            sp.set(scanned_bytes=res.scanned_bytes)
        return res

    def _staged_route(self, plan: L.Aggregate):
        """(table, SampleClause, ladder, rung) when ``plan`` can run against
        a monolithic staged rung, else None (the fresh path — which still
        draws under the ladder seed, so both routes agree bitwise).

        Conservative like ``dist._dist_route``: compiled XLA lowering only,
        exactly one block-sampled (rate < 1) scan, and that scan's table
        must hold a resident monolithic rung covering the rate.
        """
        if not self.use_compiled or self.physical._use_pallas():
            return None
        sampled = [s for s in plan.scans()
                   if s.sample is not None and s.sample.rate < 1.0]
        if len(sampled) != 1 or sampled[0].sample.method != "block":
            return None
        target = sampled[0]
        lad = self.staged.ladder(target.table)
        if lad is None or lad.sharded is not None:
            return None
        rung = lad.rung_for(target.sample.rate)
        if rung is None:
            return None
        return target.table, target.sample, lad, rung

    def _execute_staged(self, plan: L.Aggregate, table: str, sample,
                        lad, rung) -> QueryResult:
        """Execute against a staged rung: memoized sub-draw (a restriction
        of the ladder's one realization), block POSITIONS within the rung in
        place of global block ids, and the rung's own compiler — with the
        physical block count forced to the fresh path's value, the compiled
        graph gathers the same rows in the same order from the small staged
        arrays, so the answer is bitwise identical to a fresh draw's.
        """
        t0 = time.perf_counter()
        origin = self.catalog[table]
        _trace.annotate(staged=True, staged_table=table,
                        staged_rate=sample.rate, staged_rung=rung.rate)
        with _trace.span("draw") as sp:
            sub = prepare_mono_subdraw(lad, rung, sample.rate)
            self.staged.note_hit()
            if sub.n_real == 0:
                # a fresh draw under the pinned seed would be empty too
                raise EmptySampleError(table, "block", sample.rate)
            runtimes, infos = self._scan_runtimes(plan, exclude=table)
            self._check_empty(infos)
            runtimes[table] = ScanRuntime("block", sub.n_real, sub.n_phys,
                                          sub.phys, ids_dev=sub.phys_dev,
                                          nreal_dev=sub.nreal_dev)
            infos[table] = SampleInfo(
                "block", sample.rate, lad.seed, sub.n_real, lad.num_blocks,
                sub.sub_ids,
                scanned_bytes=scan_cost_bytes(origin, "block", sub.n_real))
            if sp is not _trace.NULL_SPAN:
                sp.set(**_draw_attrs(runtimes))
        sums, counts, compiled = self._dispatch(rung.compiler, plan,
                                                runtimes)
        values = self._compose_values(plan, sums, counts, self._upscale(infos))
        return QueryResult(
            agg_names=[a.name for a in plan.aggs],
            values=values,
            raw_sums=sums,
            group_counts=counts,
            group_present=counts > 0,
            scanned_bytes=compiled.scanned_bytes(runtimes),
            sample_infos=infos,
            wall_time_s=time.perf_counter() - t0,
            route=compiled.route,
        )

    def _execute_compiled(self, plan: L.Aggregate) -> QueryResult:
        route = self._staged_route(plan)
        if route is not None:
            return self._execute_staged(plan, *route)
        t0 = time.perf_counter()
        runtimes, infos = self._draw(plan)
        self._check_empty(infos)
        sums, counts, compiled = self._dispatch(self.physical, plan,
                                                runtimes)
        values = self._compose_values(plan, sums, counts, self._upscale(infos))
        return QueryResult(
            agg_names=[a.name for a in plan.aggs],
            values=values,
            raw_sums=sums,
            group_counts=counts,
            group_present=counts > 0,
            scanned_bytes=compiled.scanned_bytes(runtimes),
            sample_infos=infos,
            wall_time_s=time.perf_counter() - t0,
            route=compiled.route,
        )

    def _draw(self, plan: L.Aggregate):
        """:meth:`_scan_runtimes` under a ``draw`` span."""
        with _trace.span("draw") as sp:
            runtimes, infos = self._scan_runtimes(plan)
            if sp is not _trace.NULL_SPAN:
                sp.set(**_draw_attrs(runtimes))
        return runtimes, infos

    def _dispatch(self, compiler, plan: L.Aggregate,
                  runtimes: Dict[str, ScanRuntime]):
        """One solo scan executable: look it up, call it, pull its sums;
        returns (sums, counts, compiled)."""
        with _trace.span("dispatch"):
            compiled = compiler.compile_query(plan, runtimes)
            # Predicate/expression constants ride as a runtime operand: the
            # compiled executable is shared across every constant variant.
            self._count("device_dispatches")
            sums_d, counts_d = compiled(runtimes, plan_constants(plan))
        # Single device→host boundary: the whole scan→aggregate pipeline
        # ran as one executable.
        with _trace.span("device_wait", bytes=sums_d.nbytes
                         + counts_d.nbytes):
            sums = np.asarray(sums_d, dtype=np.float64)
            counts = np.asarray(counts_d, dtype=np.float64)
        return sums, counts, compiled

    def _execute_eager(self, plan: L.Aggregate) -> QueryResult:
        t0 = time.perf_counter()
        infos: Dict[str, SampleInfo] = {}
        table = self._run_relational(plan.child, infos)

        exprs, names = [], []
        for a in plan.aggs:
            names.append(a.name)
            exprs.append(None if a.op == "count" else a.expr)
        sums, counts = (np.asarray(x, dtype=np.float64) for x in
                        ops.grouped_totals(table, exprs, plan.group_by,
                                           plan.max_groups))

        self._check_empty(infos)
        values = self._compose_values(plan, sums, counts, self._upscale(infos))
        scanned = sum(info.scanned_bytes for info in infos.values())
        return QueryResult(
            agg_names=names,
            values=values,
            raw_sums=sums,
            group_counts=counts,
            group_present=counts > 0,
            scanned_bytes=scanned,
            sample_infos=infos,
            wall_time_s=time.perf_counter() - t0,
            route="eager",
        )

    # -- batched execution (drain-group finals) ------------------------------
    def _execute_captured(self, plan: L.Aggregate,
                          trace: Optional[_trace.QueryTrace] = None):
        """execute() with ``trace`` active, EmptySampleError returned
        instead of raised (the per-member contract of
        :meth:`execute_batch`)."""
        token = _trace.activate(trace)
        try:
            return self.execute(plan)
        except EmptySampleError as e:
            return e
        finally:
            _trace.deactivate(token)

    def execute_batch(self, plans: List[L.Aggregate],
                      on_result=None, traces=None) -> List[object]:
        """Execute several plans, batching same-signature members into ONE
        device dispatch each (see ``physical.compile_batched_query``).

        ``on_result(i, result)`` (optional) is invoked the moment
        ``plans[i]``'s entry materializes — per member on the solo/fallback
        paths, per bucket chunk on the batched path — so callers can deliver
        early answers while later buckets are still dispatching (the
        progressive-streaming drain).  The callback must not raise; an
        escaping exception is swallowed here — delivery machinery must never
        sink the batch (callers' completion loops still own every entry).

        Members are grouped by their solo compile key — the constant-hoisted
        plan signature including sampling methods and bucketed shapes — and
        every group of two or more runs as one ``lax.map`` executable over
        stacked block-id matrices and params rows; lanes are bit-identical
        to solo runs.  Groups are padded to a power-of-two batch size
        (duplicating the last member; padded lanes are discarded) so batch
        executables recur in log-many sizes.

        Returns one entry per plan, position-aligned: a
        :class:`QueryResult`, or the :class:`EmptySampleError` that member's
        sampled scan raised — callers take their per-member exact fallback,
        matching the serial path's semantics.  Singleton groups and the eager
        executor fall back to per-member execution.  Pallas kernel routes
        batch too: shapes the solo path runs through ``filtered_agg`` /
        ``block_agg`` compile to a megacore-style batched kernel grid (one
        launch for the whole bucket); shapes the kernels cannot take use the
        ``lax.map`` XLA twin, exactly like the solo route's fallback.

        Buckets split greedily into power-of-two chunks (11 members → 8+2+1)
        rather than padding up: batch executables recur in log-many sizes
        with ZERO wasted lanes — padding would recompute up to 2x of the
        device work, which at CPU scale costs more than the dispatches it
        saves.

        ``traces`` (optional, position-aligned, None entries allowed) are
        the members' query traces: each member's draw is a ``draw`` span
        under a ``scan`` span of its own, and a stacked dispatch is a
        :func:`repro.obs.trace.shared_span` over its members.
        """
        results: List[object] = [None] * len(plans)
        traces = traces or [None] * len(plans)
        # each member's open scan span, from its draw to its landing; a
        # member that then runs solo closes it (redrawn=True: the solo
        # path redraws the same content-derived sample under a scan of
        # its own)
        scans = [_trace.NULL_SPAN] * len(plans)

        def _land(i: int, res: object) -> None:
            scans[i].close()
            results[i] = res
            if on_result is not None:
                try:
                    on_result(i, res)
                except Exception:
                    pass

        def _solo(i: int) -> object:
            scans[i].set(redrawn=True)
            scans[i].close()
            return self._execute_captured(plans[i], traces[i])

        if not self.use_compiled or len(plans) < 2:
            for i in range(len(plans)):
                _land(i, _solo(i))
            return results

        drawn: Dict[int, tuple] = {}
        buckets: Dict[tuple, List[int]] = {}
        try:
            for i, plan in enumerate(plans):
                if self._staged_route(plan) is not None:
                    # staged members run solo against their rung arrays —
                    # their dispatch is already the cheap path, and batching
                    # them would redraw fresh (the ladder seed keeps that
                    # bitwise identical, but it forfeits the staged win)
                    _land(i, _solo(i))
                    continue
                scans[i] = _trace.begin(traces[i], "scan")
                token = _trace.activate(traces[i])
                try:
                    runtimes, infos = self._draw(plan)
                finally:
                    _trace.deactivate(token)
                try:
                    self._check_empty(infos)
                except EmptySampleError as e:
                    self._count("queries_run")
                    _land(i, e)
                    continue
                drawn[i] = (runtimes, infos)
                key = self.physical.query_signature(plan, runtimes)
                buckets.setdefault(key, []).append(i)

            for idxs in buckets.values():
                while idxs:
                    take = min(1 << (len(idxs).bit_length() - 1), len(idxs))
                    chunk, idxs = idxs[:take], idxs[take:]
                    if len(chunk) == 1:
                        # the solo path redraws the same content-derived
                        # sample
                        _land(chunk[0], _solo(chunk[0]))
                        continue
                    for i in chunk:
                        scans[i].set(batched=len(chunk))
                    try:
                        self._run_bucket(plans, chunk, drawn, results,
                                         [traces[i] for i in chunk])
                        for i in chunk:
                            scans[i].set(
                                scanned_bytes=results[i].scanned_bytes)
                    except Exception as e:
                        # a batch-level failure (e.g. the batched executable
                        # failing to compile) must not sink the other
                        # buckets — nor these members, who would succeed
                        # solo: fall back to per-member dispatches,
                        # bit-identical by design
                        self.note_swallowed("batched_finals", e)
                        for i in chunk:
                            if results[i] is None:
                                results[i] = _solo(i)
                    # per-bucket landing: the whole chunk materializes in
                    # one device dispatch, so its members are announced
                    # together
                    for i in chunk:
                        _land(i, results[i])
        finally:
            for sp in scans:
                sp.close()
        return results

    def _run_bucket(self, plans, idxs, drawn, results, traces) -> None:
        t0 = time.perf_counter()
        b = len(idxs)
        with _trace.shared_span(traces, "dispatch", batched=b):
            compiled = self.physical.compile_batched_query(
                plans[idxs[0]], drawn[idxs[0]][0], b)
            self._count("device_dispatches")
            sums_d, counts_d = compiled.call_batch(
                [drawn[i][0] for i in idxs],
                [plan_constants(plans[i]) for i in idxs])
        # one device→host boundary for the whole bucket
        with _trace.shared_span(traces, "device_wait", batched=b,
                                bytes=sums_d.nbytes + counts_d.nbytes):
            sums_b = np.asarray(sums_d, dtype=np.float64)
            counts_b = np.asarray(counts_d, dtype=np.float64)
        wall = time.perf_counter() - t0
        for k, i in enumerate(idxs):
            self._count("queries_run")
            runtimes, infos = drawn[i]
            sums, counts = sums_b[k], counts_b[k]
            values = self._compose_values(plans[i], sums, counts,
                                          self._upscale(infos))
            results[i] = QueryResult(
                agg_names=[a.name for a in plans[i].aggs],
                values=values,
                raw_sums=sums,
                group_counts=counts,
                group_present=counts > 0,
                scanned_bytes=compiled.scanned_bytes(runtimes),
                sample_infos=infos,
                wall_time_s=wall,
                route=compiled.route,
            )

    def execute_pilot(
        self,
        plan: L.Aggregate,
        pilot_table: str,
        theta_p: float,
        seed: int,
        pair_tables: Tuple[str, ...] = (),
    ) -> PilotStats:
        """Run the pilot query: block-sample ``pilot_table`` at theta_p and
        compute per-block (and per block-pair) sums of each simple aggregate.

        Not counted here: ``pilots_run`` counts pilot *stages* and is
        incremented by :meth:`repro.core.taqa.PilotDB.run_pilot` — a stage's
        Bernoulli-undershoot retries re-enter this method but are one stage.
        """
        # A staged pilot table draws from its pinned staging seed on EVERY
        # path (compiled, eager, staged rung), so retries and route changes
        # can never fork the realization.
        seed = self.staged.seed_for(pilot_table, seed)
        # One "scan" span per attempt: a stage's undershoot retries show as
        # sibling spans under the handle's "pilot" span.
        with _trace.span("scan", pilot=True, table=pilot_table,
                         theta_pilot=theta_p) as sp:
            # The compiled lowering traces one pair table; the (currently
            # unused by TAQA) multi-pair shape takes the eager path so both
            # paths return pair_sums for every requested table.
            if self.use_compiled and len(pair_tables) <= 1:
                stats = self._execute_pilot_compiled(
                    plan, pilot_table, theta_p, seed, pair_tables)
            else:
                stats = self._execute_pilot_eager(
                    plan, pilot_table, theta_p, seed, pair_tables)
            sp.set(scanned_bytes=stats.scanned_bytes,
                   n_blocks=stats.n_sampled_blocks)
        return stats

    def _execute_pilot_compiled(self, plan, pilot_table, theta_p, seed,
                                pair_tables) -> PilotStats:
        t0 = time.perf_counter()
        table = self.catalog[pilot_table]
        # Staged route: serve the pilot draw as a sub-draw of the table's
        # staged realization (execute_pilot already pinned ``seed`` to the
        # ladder's, so hit and miss replay one realization either way).
        lad = self.staged.ladder(pilot_table)
        rung = None
        if (lad is not None and lad.sharded is None
                and not self.physical._use_pallas()):
            rung = lad.rung_for(theta_p)
        if rung is not None:
            _trace.annotate(staged=True, staged_table=pilot_table,
                            staged_rate=theta_p, staged_rung=rung.rate)
        with _trace.span("draw") as sp:
            if rung is not None:
                sub = prepare_mono_subdraw(lad, rung, theta_p)
                self.staged.note_hit()
                n_real = sub.n_real
                # positions within the rung, padded to the FRESH physical
                # block count — identical graph shapes and masking, smaller
                # gather
                runtime = ScanRuntime("block", sub.n_real, sub.n_phys,
                                      sub.phys, ids_dev=sub.phys_dev,
                                      nreal_dev=sub.nreal_dev)
                compiler = rung.compiler
            else:
                if lad is not None:
                    self.staged.note_miss()
                ids = draw_block_ids(table.num_blocks, theta_p, seed)
                phys, n_real, n_phys = pad_block_ids(ids, table.num_blocks)
                runtime = ScanRuntime("block", n_real, n_phys, phys)
                compiler = self.physical
            sp.set(n_blocks=n_real, n_phys=runtime.n_phys)
        names = [a.name for a in plan.aggs] + ["__rows"]

        if n_real == 0:
            other = {s.table for s in plan.scans() if s.table != pilot_table}
            scanned = sum(self.catalog[t].total_bytes() for t in other)
            return PilotStats(
                table=pilot_table, theta_p=theta_p, n_sampled_blocks=0,
                n_total_blocks=table.num_blocks, block_rows=table.block_rows,
                agg_names=names,
                block_sums=np.zeros((0, plan.max_groups, len(names))),
                group_present=np.zeros(plan.max_groups, bool),
                pair_sums={}, right_total_blocks={}, scanned_bytes=scanned,
                wall_time_s=time.perf_counter() - t0)

        pair_table = pair_tables[0] if pair_tables else None
        with _trace.span("dispatch"):
            compiled = compiler.compile_pilot(plan, pilot_table, runtime,
                                              pair_table)
            # One executable from sampled scan to per-block statistics —
            # zero host syncs in between; the conversions below are the
            # boundary.
            self._count("device_dispatches")
            bs_d, present_d, pair_d = compiled({pilot_table: runtime},
                                               plan_constants(plan))
        pair_sums: Dict[str, np.ndarray] = {}
        right_total: Dict[str, int] = {}
        with _trace.span("device_wait") as sp:
            block_sums = np.asarray(bs_d, dtype=np.float64)[:n_real]
            present = np.asarray(present_d, dtype=bool)
            nbytes = bs_d.nbytes + present_d.nbytes
            if pair_d is not None:
                pair_sums[pair_table] = np.asarray(
                    pair_d, dtype=np.float64)[:n_real]
                right_total[pair_table] = self.catalog[pair_table].num_blocks
                nbytes += pair_d.nbytes
            sp.set(bytes=nbytes)
        return PilotStats(
            table=pilot_table,
            theta_p=theta_p,
            n_sampled_blocks=n_real,
            n_total_blocks=table.num_blocks,
            block_rows=table.block_rows,
            agg_names=names,
            block_sums=block_sums,
            group_present=present,
            pair_sums=pair_sums,
            right_total_blocks=right_total,
            scanned_bytes=compiled.scanned_bytes({pilot_table: runtime}),
            wall_time_s=time.perf_counter() - t0,
        )

    def _execute_pilot_eager(self, plan, pilot_table, theta_p, seed,
                             pair_tables) -> PilotStats:
        t0 = time.perf_counter()
        sampled_plan = L.rewrite_scans(
            plan, {pilot_table: L.SampleClause("block", theta_p, seed)})
        infos: Dict[str, SampleInfo] = {}
        pair_for = (pilot_table, pair_tables[0]) if pair_tables else None
        table = self._run_relational(sampled_plan.child, infos, pair_for)

        # One channel per simple aggregate plus a trailing row-count channel
        # ("__rows") used for group-presence detection and COUNT/AVG planning.
        exprs = [None if a.op == "count" else a.expr for a in plan.aggs] + [None]
        names = [a.name for a in plan.aggs] + ["__rows"]
        info = infos[pilot_table]
        ids = info.sampled_block_ids
        if ids is None or len(ids) == 0:
            ids = np.zeros(0, dtype=np.int64)
            block_sums = np.zeros((0, plan.max_groups, len(exprs)))
        else:
            block_sums = ops.block_group_sums(
                table, exprs, plan.group_by, plan.max_groups, ids)

        pair_sums: Dict[str, np.ndarray] = {}
        right_total: Dict[str, int] = {}
        for rt in pair_tables:
            col = f"__rblock_{rt}"
            if col in table.columns and len(ids) > 0:
                nrb = self.catalog[rt].num_blocks
                pair_sums[rt] = ops.block_pair_sums(table, exprs, ids, col, nrb)
                right_total[rt] = nrb
        scanned = sum(i.scanned_bytes for i in infos.values())
        block_sums = np.asarray(block_sums, dtype=np.float64)
        present = (block_sums[..., -1].sum(axis=0) > 0) if len(ids) \
            else np.zeros(plan.max_groups, bool)
        return PilotStats(
            table=pilot_table,
            theta_p=theta_p,
            n_sampled_blocks=int(len(ids)),
            n_total_blocks=self.catalog[pilot_table].num_blocks,
            block_rows=self.catalog[pilot_table].block_rows,
            agg_names=names,
            block_sums=block_sums,
            group_present=present,
            pair_sums=pair_sums,
            right_total_blocks=right_total,
            scanned_bytes=scanned,
            wall_time_s=time.perf_counter() - t0,
        )

    # -- batched pilots (shared-pilot drain groups) --------------------------
    def execute_pilots_batched(
        self,
        plans: List[L.Aggregate],
        pilot_table: str,
        thetas: List[float],
        runtimes_list: List[Dict[str, ScanRuntime]],
        traces=None,
    ) -> List[PilotStats]:
        """One stacked device dispatch for B same-signature pilot scans.

        Callers (``core.taqa.PilotDB.run_pilots_batched``) have already
        host-resolved each member's Bernoulli draw — including undershoot
        retries, which are a pure host-RNG computation — so every lane
        arrives with its final block ids.  Lane k runs the solo tracer-route
        pilot body under ``lax.map`` and is bit-identical to member k's solo
        ``execute_pilot``.  Pair-table, Pallas-route, staged-ladder and
        sharded pilots never reach here (the caller gates them to solo).

        ``traces`` (optional, position-aligned) are the members' query
        traces: the dispatch and the device wait are
        :func:`repro.obs.trace.shared_span` spans over them.
        """
        batch = len(plans)
        traces = traces or [None] * batch
        names_l = [[a.name for a in p.aggs] + ["__rows"] for p in plans]
        with _trace.shared_span(traces, "dispatch", batched=batch):
            compiled = self.physical.compile_batched_pilot(
                plans[0], pilot_table, runtimes_list[0][pilot_table], batch)
            t0 = time.perf_counter()
            self._count("device_dispatches")
            bs_d, present_d = compiled.call_batch(
                runtimes_list, [plan_constants(p) for p in plans])
        # one device→host boundary for the whole pilot group
        with _trace.shared_span(traces, "device_wait", batched=batch,
                                bytes=bs_d.nbytes + present_d.nbytes):
            bs_b = np.asarray(bs_d, dtype=np.float64)
            present_b = np.asarray(present_d, dtype=bool)
        wall = time.perf_counter() - t0
        table = self.catalog[pilot_table]
        out: List[PilotStats] = []
        for k in range(batch):
            runtime = runtimes_list[k][pilot_table]
            out.append(PilotStats(
                table=pilot_table,
                theta_p=thetas[k],
                n_sampled_blocks=runtime.n_real,
                n_total_blocks=table.num_blocks,
                block_rows=table.block_rows,
                agg_names=names_l[k],
                block_sums=bs_b[k, :runtime.n_real],
                group_present=present_b[k],
                pair_sums={},
                right_total_blocks={},
                scanned_bytes=compiled.scanned_bytes(runtimes_list[k]),
                wall_time_s=wall,
            ))
        return out

    # -- fused single-launch TAQA --------------------------------------------
    def execute_fused(
        self,
        plan: L.Aggregate,
        pilot_table: str,
        runtimes: Dict[str, ScanRuntime],
        solve: np.ndarray,
        scal: np.ndarray,
        u: np.ndarray,
        solve_channels: Tuple[int, ...],
    ):
        """Dispatch the single-launch TAQA program and return its raw device
        outputs (converted at one host boundary).

        The caller (``core.taqa.PilotDB.run_fused``) owns every host-side
        decision: it precomputed the pilot draw, the per-constraint quantile
        table, the cost line, and the final-draw uniforms; it re-solves the
        rate in f64 afterwards and verifies the device's final draw before
        trusting the returned sums.  This method is exactly ONE compiled
        dispatch — no host sync between pilot, solve, and final.
        """
        compiled = self.physical.compile_fused(plan, pilot_table, runtimes,
                                               tuple(solve_channels))
        with _trace.span("scan", fused=True, table=pilot_table) as sp:
            with _trace.span("dispatch"):
                self._count("device_dispatches")
                outs = compiled.call_fused(runtimes, plan_constants(plan),
                                           solve, scal, u)
            # the fused program's single device→host boundary
            with _trace.span("device_wait",
                             bytes=sum(o.nbytes for o in outs)):
                bs_d, present_d, theta_d, flags_d, nsel_d, padded_d, \
                    sums_d, counts_d = outs
                out = {
                    "block_sums": np.asarray(bs_d, dtype=np.float64),
                    "present": np.asarray(present_d, dtype=bool),
                    "theta": float(theta_d),
                    "flags": int(flags_d),
                    "nsel": int(nsel_d),
                    "padded": np.asarray(padded_d),
                    "sums": np.asarray(sums_d, dtype=np.float64),
                    "counts": np.asarray(counts_d, dtype=np.float64),
                }
            sp.set(n_blocks=runtimes[pilot_table].n_real,
                   theta_final=out["theta"], fused_flags=out["flags"])
        return out, compiled
