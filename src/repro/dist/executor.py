"""Shard-parallel plan execution over partitioned tables.

:class:`DistExecutor` extends the engine :class:`Executor` with partitioned
registrations (:meth:`register_sharded`): a table registered with N shards
keeps its monolithic arrays in the catalog (metadata, eager paths and exact
execution are untouched) while block-sampled scans of it fan out as ONE
dispatch per shard holding sampled blocks, each against that shard's own
arrays (placed round-robin across devices by :mod:`repro.dist.shard`), and
re-join through :mod:`repro.dist.merge`.

Route.  Per-shard dispatches reuse the physical layer's *pilot* lowering —
the per-(sampled block, group) channel-sum executable — because per-block
statistics are exactly the mergeable unit (§4: block sampling commutes with
the plan suffix).  Final answers reduce the merged per-block sums in f64
over the global block order; pilot statistics ARE the merged matrix.  Both
are bit-identical for every shard count by construction (see merge.py).
Every shard runs its own compiled executable from its own compile cache, so
a shard geometry compiles once and re-dispatches warm.

Scope (documented, enforced by fallback): the dist route engages for plans
whose SINGLE sharded table carries a block sample at rate < 1; unsharded
tables in the plan (join sides) are replicated to every shard's catalog
view.  Everything else — exact scans, row sampling, multi-table sampling
plans, the eager executor — falls back to the monolithic arrays, which are
shard-count-independent by definition, so the bit-identity guarantee
survives the fallback.  An empty GLOBAL draw raises
:class:`EmptySampleError` exactly as the monolithic samplers do (TAQA's
explicit exact fallback); an empty single shard merely contributes nothing.

Accounting.  Each shard is charged its own sampled slabs
(``shard_scan_info()`` — cumulative per-shard scanned bytes, summing to the
monolithic total for the same draw); replicated tables are charged once per
query, matching the monolithic attribution.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.dist import merge
from repro.dist.shard import Shard, ShardedTable, shard_block_ids
from repro.engine import logical as L
from repro.engine.executor import (EmptySampleError, Executor, PilotStats,
                                   QueryResult)
from repro.engine.physical import (ScanRuntime, SharedBuildStore,
                                   plan_constants, scan_cost_bytes)
from repro.engine.sampling import SampleInfo, pad_block_ids
from repro.engine.staged import (DEFAULT_STAGED_RATES, ShardSubdraw,
                                 build_sharded_ladder, prepare_dist_subdraw)
from repro.engine.table import BlockTable
from repro.obs import trace as _trace


class DistExecutor(Executor):
    """An :class:`Executor` whose catalog may hold partitioned tables."""

    def __init__(self, catalog: Dict[str, BlockTable], *,
                 use_compiled: bool = True, kernel_mode: str = "auto",
                 staged_bytes: Optional[int] = None):
        super().__init__(catalog, use_compiled=use_compiled,
                         kernel_mode=kernel_mode, staged_bytes=staged_bytes)
        self._sharded: Dict[str, ShardedTable] = {}
        # Cross-shard executable store: same-geometry shard compilers (the
        # common case — equal block ranges shard into identical slab
        # shapes) adopt each other's built executables, so N shards pay
        # ONE trace+compile per plan shape.  Adoptions surface as
        # ``shared_hits`` in compile_cache_info().
        self._shared_builds = SharedBuildStore()
        # one engine Executor per shard: its catalog holds the shard slice
        # under the table's name plus every other table's monolithic arrays
        self._shard_executors: Dict[str, List[Executor]] = {}
        self._shard_lock = threading.Lock()
        # cumulative per-shard sampled-slab bytes, per sharded table
        self._shard_scanned: Dict[str, List[int]] = {}

    # -- catalog management ---------------------------------------------------
    def register_sharded(self, name: str, table: BlockTable, shards: int,
                         devices=None) -> ShardedTable:
        """Register ``table`` partitioned into ``shards`` block ranges.

        The monolithic arrays stay in the catalog (metadata / exact /
        fallback paths); block-sampled scans of ``name`` route per shard.
        Re-registering via :meth:`register_table` drops the partitioning.
        """
        sharded = ShardedTable.from_table(table, shards, devices=devices)
        super().register_table(name, table)
        executors = []
        for s in sharded.shards:
            cat = {t: v for t, v in self.catalog.items() if t != name}
            cat[name] = s.table
            executors.append(Executor(cat, use_compiled=self.use_compiled,
                                      kernel_mode=self.physical.kernel_mode,
                                      shared_builds=self._shared_builds))
        with self._shard_lock:
            self._sharded[name] = sharded
            self._shard_executors[name] = executors
            self._shard_scanned[name] = [0] * shards
        self._refresh_shard_catalogs(name, table)
        return sharded

    def register_staged(self, name: str,
                        rates=DEFAULT_STAGED_RATES, *, seed: int = 0) -> None:
        """Materialize a staged ladder; a sharded table stages PER SHARD —
        each shard gathers its restriction of the rung's one global draw, so
        the staged realization is shard-count-independent exactly like a
        fresh ``shard_block_ids`` draw."""
        if not self.use_compiled:
            return
        snap = self._shard_snapshot(name)
        if snap is None:
            return super().register_staged(name, rates, seed=seed)
        sharded, executors = snap
        self.staged.admit(build_sharded_ladder(
            name, sharded, rates, seed, self.physical.kernel_mode,
            [ex.catalog for ex in executors]))

    def register_table(self, name: str, table: BlockTable) -> None:
        """Plain (monolithic) registration; drops any existing sharding of
        ``name`` and refreshes every shard view of it."""
        super().register_table(name, table)
        with self._shard_lock:
            self._sharded.pop(name, None)
            self._shard_executors.pop(name, None)
            self._shard_scanned.pop(name, None)
        self._refresh_shard_catalogs(name, table)

    def _refresh_shard_catalogs(self, name: str, table: BlockTable) -> None:
        """Other sharded tables' shard executors see ``name`` replicated —
        keep those views current when it is (re-)registered."""
        with self._shard_lock:
            items = [(t, exs) for t, exs in self._shard_executors.items()
                     if t != name]
        for _, executors in items:
            for ex in executors:
                ex.register_table(name, table)

    def sharded_tables(self) -> Dict[str, int]:
        with self._shard_lock:
            return {t: st.num_shards for t, st in self._sharded.items()}

    def is_sharded(self, name: str) -> bool:
        """Whether ``name`` currently executes as sharded sub-scans (the
        fused single-launch program gates itself off such tables — its one
        device program cannot span shard dispatches)."""
        with self._shard_lock:
            return name in self._sharded

    def compile_cache_info(self):
        """Aggregate compile-cache counters: the monolithic compiler PLUS
        every shard executor's compiler — dist dispatches compile there, and
        session/gateway/drain stats must see them.  Per-kind breakouts
        (pilot/batched/fused) and cross-shard build adoptions
        (``shared_hits``) aggregate the same way."""
        info = super().compile_cache_info()
        with self._shard_lock:
            executors = [ex for exs in self._shard_executors.values()
                         for ex in exs]
        for ex in executors:
            shard_info = ex.compile_cache_info()
            info.hits += shard_info.hits
            info.misses += shard_info.misses
            info.size += shard_info.size
            info.staged_hits += shard_info.staged_hits
            info.staged_misses += shard_info.staged_misses
            info.pilot_hits += shard_info.pilot_hits
            info.pilot_misses += shard_info.pilot_misses
            info.batched_hits += shard_info.batched_hits
            info.batched_misses += shard_info.batched_misses
            info.fused_hits += shard_info.fused_hits
            info.fused_misses += shard_info.fused_misses
            info.shared_hits += shard_info.shared_hits
        return info

    def shard_scan_info(self) -> Dict[str, Tuple[int, ...]]:
        """Cumulative sampled-slab bytes per shard, per sharded table.
        For any given draw the entries sum to the monolithic scanned-bytes
        attribution of the same sampled block set."""
        with self._shard_lock:
            return {t: tuple(v) for t, v in self._shard_scanned.items()}

    def _note_shard_scan(self, table: str, shard_index: int, nbytes: int) -> None:
        with self._shard_lock:
            if table in self._shard_scanned:
                self._shard_scanned[table][shard_index] += nbytes

    # -- routing --------------------------------------------------------------
    def _dist_route(self, plan: L.Aggregate) -> Optional[Tuple[str, L.SampleClause]]:
        """The (table, block-sample) pair when ``plan`` takes the dist
        route; None -> monolithic execution (shard-count-independent)."""
        if not self.use_compiled or not self._sharded:
            return None
        scans = plan.scans()
        hits = [s for s in scans
                if s.table in self._sharded and s.sample is not None
                and s.sample.method == "block" and s.sample.rate < 1.0]
        if len(hits) != 1:
            return None
        target = hits[0]
        for s in scans:
            if s is not target and s.sample is not None and s.sample.rate < 1.0:
                return None  # multi-table sampling: monolithic fallback
        return target.table, target.sample

    def _shard_snapshot(self, table: str):
        """One consistent (ShardedTable, executors) pair, taken under the
        lock: a concurrent re-registration must never pair one generation's
        shard ranges with another's executors (wrong blocks scanned), nor
        KeyError a query that routed before the sharding was dropped —
        such a query runs against the consistent OLD snapshot and the
        session-level generation guard decides whether its answer is
        deliverable."""
        with self._shard_lock:
            sharded = self._sharded.get(table)
            if sharded is None:
                return None
            return sharded, self._shard_executors[table]

    # -- execution ------------------------------------------------------------
    def execute(self, plan: L.Aggregate) -> QueryResult:
        route = self._dist_route(plan)
        snap = self._shard_snapshot(route[0]) if route is not None else None
        if snap is None:  # unsharded plan, or sharding dropped concurrently
            return super().execute(plan)
        self._count("queries_run")
        return self._execute_dist(plan, route[0], route[1], *snap)

    def execute_batch(self, plans: List[L.Aggregate],
                      on_result=None, traces=None) -> List[object]:
        """Dist-routed members run as per-shard dispatches (bit-identical
        to their solo execution by construction); the rest batch as usual.
        ``on_result`` and ``traces`` keep the base contract: dist members
        announce per member, the rest via the forwarded (index-remapped)
        callback."""
        dist_idx = {i for i, p in enumerate(plans)
                    if self._dist_route(p) is not None}
        if not dist_idx:
            return super().execute_batch(plans, on_result=on_result,
                                         traces=traces)
        traces = traces or [None] * len(plans)
        results: List[object] = [None] * len(plans)
        rest = [i for i in range(len(plans)) if i not in dist_idx]
        if rest:
            remap = (None if on_result is None
                     else (lambda j, r: on_result(rest[j], r)))
            for i, r in zip(rest, super().execute_batch(
                    [plans[i] for i in rest], on_result=remap,
                    traces=[traces[i] for i in rest])):
                results[i] = r
        for i in sorted(dist_idx):
            results[i] = self._execute_captured(plans[i], traces[i])
            if on_result is not None:
                try:
                    on_result(i, results[i])
                except Exception:
                    pass
        return results

    def _replicated_infos(self, plan: L.Aggregate, table: str) -> Dict[str, SampleInfo]:
        infos: Dict[str, SampleInfo] = {}
        for s in plan.scans():
            if s.table == table or s.table in infos:
                continue
            tab = self.catalog[s.table]
            infos[s.table] = SampleInfo(
                "none", 1.0, 0, tab.num_blocks, tab.num_blocks,
                np.arange(tab.num_blocks),
                scanned_bytes=scan_cost_bytes(tab, "none"))
        return infos

    def _staged_dist_rung(self, table: str, rate: float, sharded):
        """(ladder, rung) when the dist draw of ``table`` at ``rate`` can be
        served from per-shard staged rungs; (ladder, None) when the table
        has a ladder but must draw fresh (under the ladder's pinned seed)."""
        lad = self.staged.ladder(table)
        if lad is None:
            return None, None
        if lad.sharded is not sharded or self.physical._use_pallas():
            return lad, None
        return lad, lad.rung_for(rate)

    def _execute_dist(self, plan: L.Aggregate, table: str,
                      sample: L.SampleClause, sharded: ShardedTable,
                      executors: List[Executor]) -> QueryResult:
        t0 = time.perf_counter()
        lad, rung = self._staged_dist_rung(table, sample.rate, sharded)
        seed = sample.seed if lad is None else lad.seed
        stripped = L.strip_samples(plan)
        with _trace.span("shard_fanout", table=table,
                         shards=sharded.num_shards,
                         staged=rung is not None) as sp:
            if rung is not None:
                self.staged.note_hit()
                global_ids, splits = prepare_dist_subdraw(lad, rung,
                                                          sample.rate)
                if len(global_ids) == 0:
                    raise EmptySampleError(table, "block", sample.rate)
                parts = self._dispatch_staged_shards(stripped, table, sharded,
                                                     splits)
            else:
                if lad is not None:
                    self.staged.note_miss()
                global_ids, parts_ids = shard_block_ids(
                    sharded.num_blocks, sample.rate, seed, sharded)
                if len(global_ids) == 0:
                    raise EmptySampleError(table, "block", sample.rate)
                parts = self._dispatch_shards(stripped, table, sharded,
                                              executors, parts_ids)
            sp.set(shards_hit=len(parts),
                   scanned_bytes=sum(p.scanned_bytes for p in parts))
        _, block_sums = merge.merge_block_stats(parts)
        sums, counts = merge.reduce_group_totals(block_sums)

        infos = self._replicated_infos(plan, table)
        infos[table] = SampleInfo(
            "block", sample.rate, seed, int(len(global_ids)),
            sharded.num_blocks, global_ids,
            scanned_bytes=sum(p.scanned_bytes for p in parts))
        values = self._compose_values(plan, sums, counts, self._upscale(infos))
        return QueryResult(
            agg_names=[a.name for a in plan.aggs],
            values=values,
            raw_sums=sums,
            group_counts=counts,
            # counts is the f64-summed "__rows" channel of the same merged
            # matrix: counts > 0 IS the presence bitmap (monolithic form)
            group_present=counts > 0,
            scanned_bytes=sum(i.scanned_bytes for i in infos.values()),
            sample_infos=infos,
            wall_time_s=time.perf_counter() - t0,
        )

    def _dispatch_shards(self, stripped: L.Aggregate, table: str,
                         sharded: ShardedTable, executors: List[Executor],
                         parts_ids: List[Tuple[Shard, np.ndarray]],
                         pair_table: Optional[str] = None) -> List[merge.ShardPart]:
        """One device dispatch per shard holding sampled blocks; results are
        converted to host arrays only after every shard was dispatched, so
        multi-device placements overlap their executions.  ``sharded`` and
        ``executors`` come from one :meth:`_shard_snapshot` — never re-read
        here (see the snapshot's consistency contract)."""
        params = plan_constants(stripped)
        raw = []
        for s, local_ids in parts_ids:
            ex = executors[s.index]
            phys, n_real, _ = pad_block_ids(local_ids, s.num_blocks)
            runtime = ScanRuntime("block", n_real, len(phys), phys)
            compiled = ex.physical.compile_pilot(stripped, table, runtime,
                                                 pair_table)
            raw.append((s, local_ids, n_real,
                        compiled({table: runtime}, params)))
        parts = []
        for s, local_ids, n_real, (bs_d, _present, pair_d) in raw:
            nbytes = n_real * sharded.block_rows * sharded.row_bytes
            self._note_shard_scan(table, s.index, nbytes)
            parts.append(merge.ShardPart(
                shard_index=s.index,
                global_ids=local_ids.astype(np.int64) + s.start_block,
                block_sums=np.asarray(bs_d, np.float64)[:n_real],
                pair_sums=(None if pair_d is None
                           else np.asarray(pair_d, np.float64)[:n_real]),
                scanned_bytes=nbytes))
        return parts

    def _dispatch_staged_shards(self, stripped: L.Aggregate, table: str,
                                sharded: ShardedTable,
                                splits: List[ShardSubdraw],
                                pair_table: Optional[str] = None
                                ) -> List[merge.ShardPart]:
        """The staged twin of :meth:`_dispatch_shards`: each shard's sampled
        blocks are addressed by POSITION within its staged rung and gathered
        from the pre-staged shard-rung arrays, with the physical block count
        forced to the fresh per-shard value — same rows, same shapes, same
        reduction order, so the merged answer is bitwise the fresh one."""
        params = plan_constants(stripped)
        raw = []
        for sd in splits:
            part = sd.part
            runtime = ScanRuntime("block", sd.n_real, sd.n_phys, sd.phys,
                                  ids_dev=sd.phys_dev,
                                  nreal_dev=sd.nreal_dev)
            compiled = part.compiler.compile_pilot(stripped, table, runtime,
                                                   pair_table)
            raw.append((part, sd.local_ids, sd.n_real,
                        compiled({table: runtime}, params)))
        parts = []
        for part, local_ids, n_real, (bs_d, _present, pair_d) in raw:
            nbytes = n_real * sharded.block_rows * sharded.row_bytes
            self._note_shard_scan(table, part.shard_index, nbytes)
            parts.append(merge.ShardPart(
                shard_index=part.shard_index,
                global_ids=local_ids.astype(np.int64) + part.start_block,
                block_sums=np.asarray(bs_d, np.float64)[:n_real],
                pair_sums=(None if pair_d is None
                           else np.asarray(pair_d, np.float64)[:n_real]),
                scanned_bytes=nbytes))
        return parts

    # -- pilot ----------------------------------------------------------------
    def execute_pilot(self, plan: L.Aggregate, pilot_table: str,
                      theta_p: float, seed: int,
                      pair_tables: Tuple[str, ...] = ()) -> PilotStats:
        snap = (self._shard_snapshot(pilot_table)
                if self.use_compiled and len(pair_tables) <= 1 else None)
        if snap is None:
            return super().execute_pilot(plan, pilot_table, theta_p, seed,
                                         pair_tables)
        sharded, executors = snap
        t0 = time.perf_counter()
        lad, rung = self._staged_dist_rung(pilot_table, theta_p, sharded)
        seed = seed if lad is None else lad.seed
        names = [a.name for a in plan.aggs] + ["__rows"]
        pair_table = pair_tables[0] if pair_tables else None
        replicated = sum(
            self.catalog[t].total_bytes()
            for t in {s.table for s in plan.scans()} if t != pilot_table)
        with _trace.span("shard_fanout", table=pilot_table, pilot=True,
                         shards=sharded.num_shards,
                         staged=rung is not None) as sp:
            if rung is not None:
                self.staged.note_hit()
                global_ids, splits = prepare_dist_subdraw(lad, rung, theta_p)
                parts = (self._dispatch_staged_shards(
                    L.strip_samples(plan), pilot_table, sharded, splits,
                    pair_table) if len(global_ids) else [])
            else:
                if lad is not None:
                    self.staged.note_miss()
                global_ids, parts_ids = shard_block_ids(
                    sharded.num_blocks, theta_p, seed, sharded)
                parts = (self._dispatch_shards(L.strip_samples(plan),
                                               pilot_table, sharded,
                                               executors, parts_ids,
                                               pair_table)
                         if len(global_ids) else [])
            sp.set(shards_hit=len(parts),
                   scanned_bytes=sum(p.scanned_bytes for p in parts))
        has_pair = bool(parts) and parts[0].pair_sums is not None
        return merge.merge_pilot_stats(
            table=pilot_table,
            theta_p=theta_p,
            n_total_blocks=sharded.num_blocks,
            block_rows=sharded.block_rows,
            agg_names=names,
            max_groups=plan.max_groups,
            parts=parts,
            pair_table=pair_table if has_pair else None,
            n_right_blocks=(self.catalog[pair_table].num_blocks
                            if pair_table else 0),
            replicated_bytes=replicated,
            wall_time_s=time.perf_counter() - t0,
        )
