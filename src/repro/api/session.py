"""Session — the stateful front door of the PilotDB middleware.

A :class:`Session` owns everything that must persist across queries for the
many-users scenario to pay off:

* the registered tables (the catalog, plus optional per-column string
  dictionaries) and the :class:`Executor` whose physical compile cache makes
  repeated structurally-identical queries run warm (see
  ``engine/physical.py``),
* the concurrent query runtime (:mod:`repro.runtime`): a worker pool that
  overlaps drain groups, one-pilot-per-group statistic sharing, and the
  session result cache,
* deterministic seed derivation (below), and a
  :class:`repro.api.QueryScheduler` for batched submission.

Seed derivation.  Every query's sampling seed is a pure function of
``(session seed, lowered query, ErrorSpec)`` — not of submission order — and
the *pilot* seed is a pure function of ``(session seed, structural
signature, pilot-stage tunables)``.  Consequences, all load-bearing for the
runtime:

* equal-seed sessions replay bit-identical answers for the same queries, in
  ANY submission order and under any scheduler/runtime interleaving;
* a query answered from a group's shared pilot is bit-identical to the same
  query run solo (solo runs derive the identical pilot seed);
* a repeated identical query re-derives the identical ``(query, spec,
  seed)`` triple, which is exactly the result cache's key — repeats are
  cache hits with their original error reports.

Result-cache invalidation contract: see :meth:`Session.register_table`.

``session.sql(...)`` / ``builder.run()`` return a :class:`QueryHandle`
carrying status, the :class:`ApproxAnswer`, the :class:`TaqaReport` and any
fallback reason — execution failures are captured on the handle instead of
raising through the client (`EmptySampleError` in particular is already an
*internal* signal: TAQA answers it with an explicit exact fallback).
Handles are pollable (`poll()`) and waitable (`wait(timeout)`), so clients
of the async runtime never need to block on a drain.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import hashlib
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.api.builder import QueryBuilder
from repro.api.scheduler import QueryScheduler
from repro.api.sql import (HavingClause, LimitClause, UnsupportedSqlError,
                           parse_sql, resolve_string_literals)
from repro.core.spec import ErrorSpec
from repro.dist import DistExecutor
from repro.core.taqa import (ApproxAnswer, PilotDB, Query, TaqaReport,
                             advisory_estimate, pilot_params,
                             structural_signature)
from repro.engine.executor import Executor
from repro.engine.physical import plan_template
from repro.engine.staged import DEFAULT_STAGED_RATES, validate_rates
from repro.engine.table import BlockTable
from repro.obs import audit as _audit
from repro.obs import events as _events
from repro.obs import metrics as _metrics
from repro.obs import slo as _slo
from repro.obs import timeseries as _timeseries
from repro.obs import trace as _trace
from repro.runtime import (AsyncRuntime, CachedAnswer, ResultCache,
                           ResultCacheInfo)
from repro.runtime import shared_pilot as _shared_pilot
from repro.stream import (ErrorFrame, FrameBuffer, final_frame_for,
                          pilot_frame_for)


class QueryStatus:
    PENDING = "pending"
    RUNNING = "running"
    DONE = "done"
    FAILED = "failed"


class QueryFailedError(RuntimeError):
    """Raised by :meth:`QueryHandle.result` when execution failed."""


@dataclasses.dataclass
class _Dictionary:
    """A column's string dictionary: code lookup plus order metadata."""

    codes: Dict[str, int]       # value -> integer code
    values: List[str]           # code -> value (registration order)
    is_sorted: bool             # strictly ascending => code order == lex order


def _content_hash(*parts) -> int:
    """Deterministic 64-bit hash of frozen-dataclass content (their reprs
    are complete and stable — plans, exprs and specs hold only scalars)."""
    digest = hashlib.blake2b(repr(parts).encode(), digest_size=8).digest()
    return int.from_bytes(digest, "big")


@dataclasses.dataclass
class QueryHandle:
    """One submitted query: its lowered form, derived seed, and outcome."""

    query_id: int
    query: Optional[Query]            # None only for parse-failed handles
    spec: Optional[ErrorSpec]         # None -> exact execution was requested
    seed: int
    sql: Optional[str] = None
    # post-aggregation HAVING filter: applied to every delivered answer
    # (fresh or cache-served) but never part of the plan, the seed, or the
    # cache key — the cache stores the unfiltered base answer
    having: Optional[HavingClause] = None
    # post-aggregation [ORDER BY agg] LIMIT n selection: same contract as
    # HAVING (applied after it, never keyed) — LIMIT-varied re-issues all
    # share one cached base answer
    limit: Optional[LimitClause] = None
    status: str = QueryStatus.PENDING
    error: Optional[str] = None
    cached: bool = False              # answered from the session result cache
    _answer: Optional[ApproxAnswer] = None
    # full constant-bearing structural signature, computed once at
    # submission (pilot-seed derivation and pilot-sharing subgroups key off
    # it — pilot statistics depend on predicate constants)
    signature: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)
    # constant-stripped template signature: the scheduler's grouping key —
    # constant-varied queries share compilations and batched final launches
    group_key: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)
    _done_event: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False, compare=False)
    # progressive streaming (repro.stream): None until enable_streaming();
    # the lock serializes terminal-frame emission against late enabling so
    # every stream ends in EXACTLY one terminal frame
    _frames: Optional[FrameBuffer] = dataclasses.field(
        default=None, repr=False, compare=False)
    _frame_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)
    # submission instant (perf_counter): the zero point for every frame's
    # relative `emitted_at` stamp and for the trace's span times
    t_submit: float = dataclasses.field(
        default_factory=time.perf_counter, repr=False, compare=False)
    # query-lifecycle span tree (repro.obs.trace); None unless the session
    # was configured with tracing=True
    _trace: Optional[_trace.QueryTrace] = dataclasses.field(
        default=None, repr=False, compare=False)
    # observed-vs-promised outcome (repro.obs.audit); None unless the
    # session runs in audit mode and this query completed
    audit_record: Optional[_audit.AuditRecord] = dataclasses.field(
        default=None, repr=False, compare=False)
    # the fused single-launch program delivered this answer (set by
    # Session._run_fused; provenance reporting and telemetry read it — the
    # fused span carries the same fact only when tracing is on)
    _fused: bool = dataclasses.field(default=False, repr=False, compare=False)
    # this handle was picked by deterministic trace sampling
    # (SessionConfig.trace_sample); sampled traces land in the flight
    # recorder and the session's recent-traces ring at completion
    _trace_sampled: bool = dataclasses.field(
        default=False, repr=False, compare=False)
    # continuous-telemetry delivery hook (Session._observe_delivery); fired
    # exactly once from _mark_done/_mark_failed, AFTER the done event —
    # None (the default) keeps the completion path byte-for-byte the
    # pre-telemetry code
    _on_complete: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)
    # 12-hex hash of the constant-stripped template signature: the
    # time-series / SLO / flight-recorder key (computed at submission only
    # when telemetry is armed; None otherwise)
    _template_key: Optional[str] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def done(self) -> bool:
        return self.status in (QueryStatus.DONE, QueryStatus.FAILED)

    @property
    def answer(self) -> Optional[ApproxAnswer]:
        return self._answer

    @property
    def report(self) -> Optional[TaqaReport]:
        return self._answer.report if self._answer is not None else None

    @property
    def fallback(self) -> Optional[str]:
        """Reason exact execution was used, if TAQA fell back (else None)."""
        r = self.report
        return r.fallback if r is not None else None

    # -- async observation ----------------------------------------------------
    def poll(self) -> str:
        """Non-blocking status probe: pending / running / done / failed."""
        return self.status

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the query finished (done OR failed); False on
        timeout.  Returns immediately for handles that never entered a
        runtime (synchronous paths complete before returning)."""
        if self.done:
            return True
        return self._done_event.wait(timeout)

    # -- progressive streaming (repro.stream) ---------------------------------
    @property
    def streaming(self) -> bool:
        return self._frames is not None

    def enable_streaming(self) -> "QueryHandle":
        """Attach a frame buffer to this handle (idempotent).

        Queries submitted with ``stream=True`` arrive pre-enabled; enabling
        later still works — frames emitted before the buffer existed are
        simply not observed (they are advisory), and enabling on an
        already-finished handle synthesizes its terminal frame so late
        subscribers always observe a complete stream.
        """
        with self._frame_lock:
            if self._frames is None:
                self._frames = FrameBuffer(self.query_id, t0=self.t_submit)
                if self.status == QueryStatus.DONE:
                    self._frames.push(final_frame_for(
                        self.query_id, self._answer, cached=self.cached))
                elif self.status == QueryStatus.FAILED:
                    self._frames.push(ErrorFrame(
                        query_id=self.query_id,
                        error=self.error or "query failed"))
        return self

    def stream(self, timeout: Optional[float] = None):
        """Blocking frame iterator: advisory :class:`repro.stream.PilotFrame`
        estimates as they materialize, then exactly one terminal frame — a
        :class:`FinalFrame` carrying the SAME answer object ``result()``
        returns (bitwise identity with the non-streaming path is structural),
        an :class:`ExactFrame` on fallback, or an :class:`ErrorFrame` on
        captured failure.  Implicitly enables streaming; ``timeout`` bounds
        each wait for the next frame."""
        return self.enable_streaming()._frames.stream(timeout)

    def on_frame(self, cb) -> "QueryHandle":
        """Register ``cb(frame)`` for every frame of this query; frames
        already emitted are replayed first, in order (late subscription
        never loses frames).  Implicitly enables streaming."""
        self.enable_streaming()._frames.add_callback(cb)
        return self

    def frames(self) -> list:
        """Snapshot of the frames emitted so far ([] when not streaming)."""
        return [] if self._frames is None else self._frames.frames()

    def _emit(self, frame) -> None:
        """Push an advisory frame if this handle streams (no-op otherwise);
        terminal frames go through _mark_done/_mark_failed instead."""
        if self._frames is not None:
            self._frames.push(frame)

    # -- observability (repro.obs) --------------------------------------------
    def trace(self, fmt: str = "json"):
        """The query's span tree: a JSON-able dict (``fmt="json"``) or a
        Chrome trace-event list (``fmt="chrome"``, load in chrome://tracing).
        None when the session ran with tracing off."""
        if self._trace is None:
            return None
        if fmt == "chrome":
            return self._trace.to_chrome()
        if fmt == "json":
            return self._trace.to_dict()
        raise ValueError(f"unknown trace format {fmt!r} "
                         "(expected 'json' or 'chrome')")

    def explain(self) -> str:
        """EXPLAIN-style report: promised guarantee, solved rates, pilot
        inputs, scanned bytes, provenance (see :mod:`repro.obs.audit`)."""
        return _audit.explain(self)

    # -- completion (runtime-internal) ----------------------------------------
    def _mark_running(self) -> None:
        if not self.done:
            self.status = QueryStatus.RUNNING
            if self._trace is not None:
                # the cross-thread wait-in-queue span submit() opened
                self._trace.close_span("schedule")

    def _mark_done(self, answer: ApproxAnswer, cached: bool = False) -> None:
        with self._frame_lock:
            self._answer = answer
            self.cached = cached
            self.status = QueryStatus.DONE
            if self._frames is not None:
                self._frames.push(final_frame_for(
                    self.query_id, answer, cached=cached))
        if self._trace is not None:
            self._trace.finish(
                "ok", cached=cached,
                fallback=answer.report.fallback if answer is not None else None)
        self._done_event.set()
        self._fire_on_complete()

    def _fire_on_complete(self) -> None:
        """Run the telemetry delivery hook exactly once; it observes only
        (time-series row, SLO evaluation, flight-recorder event) and must
        never raise into the completion path."""
        cb, self._on_complete = self._on_complete, None
        if cb is not None:
            try:
                cb(self)
            except Exception:
                pass

    def _mark_failed(self, error: str) -> None:
        with self._frame_lock:
            self.status = QueryStatus.FAILED
            self.error = error
            if self._frames is not None:
                # the failure-capture contract extends to streams: execution
                # failures become a terminal frame, never an exception
                # raised through a streaming client
                self._frames.push(ErrorFrame(query_id=self.query_id,
                                             error=error))
        if self._trace is not None:
            self._trace.finish("error", error=error)
        self._done_event.set()
        self._fire_on_complete()

    def result(self) -> ApproxAnswer:
        """The answer; raises if the query failed or has not run yet."""
        if self.status == QueryStatus.FAILED:
            raise QueryFailedError(self.error or "query failed")
        if self._answer is None:
            raise RuntimeError(
                f"query {self.query_id} is {self.status}; drain the "
                "scheduler it was submitted to (session.drain(), or "
                "gateway.run() for gateway tickets) — or wait() on the "
                "handle after an async drain — before reading results")
        return self._answer

    def scalar(self, name: str, group: int = 0) -> float:
        return self.result().scalar(name, group)


@dataclasses.dataclass(frozen=True)
class SessionConfig:
    large_table_rows: int = 50_000     # sampling threshold (§3.1)
    default_error: float = 0.05        # builder .error() defaults
    default_confidence: float = 0.95
    use_compiled: bool = True
    kernel_mode: str = "auto"
    spec_kwargs: Optional[Dict] = None  # TAQA tunable overrides for SQL specs
    # The physical layer sizes dense per-(block, group) buffers by
    # max_groups; an id-cardinality GROUP BY through the public front door
    # would otherwise allocate process-killing buffers in a shared server.
    max_groups_limit: int = 4096
    # -- concurrent runtime (repro.runtime) ----------------------------------
    # Worker threads draining signature groups concurrently; 0 restores the
    # synchronous-cooperative loop (groups run inline on the draining
    # thread).  None sizes the pool from os.cpu_count(): capped at the core
    # count (a pool wider than the machine only contends on jit dispatch —
    # the BENCH_runtime.json async regression was 4 workers on 2 cores) with
    # a serial fallback on single-core hosts where no overlap exists to
    # win.  Answers never depend on this — only wall-clock does.
    async_workers: Optional[int] = None
    # One pilot per (full signature, pilot-params) subgroup, statistics
    # fanned out to every member (off: each query runs its own —
    # bit-identical — pilot; the switch trades pilot scans for nothing
    # else).  Never shared across predicate constants: selectivity shapes
    # the pilot statistics the §4 guarantees are computed from.
    share_pilots: bool = True
    # Stack a drain group's same-bucket final scans into ONE batched device
    # dispatch (lax.map over member lanes — bit-identical to solo runs).
    # Rides the shared-pilot group path, so share_pilots=False also
    # disables it.
    batch_finals: bool = True
    # Worker threads fanning a drain group's pilot SUBGROUPS out (the
    # constant-varied herd whose N per-constant pilot stages previously ran
    # serially on the group's one worker — see runtime/shared_pilot.py).
    # The pilot pool is separate from the group pool, so group workers
    # blocking on pilot futures can never deadlock it.  None auto-sizes
    # (min(4, cores), serial on one core); 0 restores serial pilot stages.
    pilot_workers: Optional[int] = None
    # Session result-cache capacity in answers; 0 disables caching.
    result_cache_size: int = 128
    # Optional byte budget for the result cache: entries are stored compact
    # (values + error report + packed group-present bitmap, never the full
    # ApproxAnswer graph) and evicted LRU-first once the budget is hit.
    # None = entry-count bound only.
    result_cache_bytes: Optional[int] = None
    # Optional byte budget for the staged sample catalog (tables registered
    # with staged_rates=...): rung arrays of cold ladders are evicted
    # LRU-first past the budget; the ladder's pinned staging seed survives
    # eviction, so answers stay bit-identical across the hit/miss boundary.
    # None = unbounded residency.
    staged_bytes: Optional[int] = None
    # -- observability (repro.obs) -------------------------------------------
    # Per-query span trees (handle.trace()).  Off by default: the untraced
    # path carries no trace objects and is byte-for-byte the pre-tracing
    # code; ON only observes (never touches seeds, plans, or reductions),
    # so answers stay bit-identical either way.
    tracing: bool = False
    # Audit mode: after each approximate answer is DELIVERED, run the exact
    # query alongside and record observed vs promised error into the
    # session metrics registry (see repro.obs.audit — never perturbs seeds,
    # cache keys, or delivered answers; adds exact scan cost per query).
    audit: bool = False
    # -- continuous telemetry (repro.obs.timeseries / slo / events) ----------
    # Per-template time-series + SLO evaluation on every delivery: bounded
    # ring buffers keyed by the constant-stripped template signature record
    # latency / pilot wall / scanned bytes / provenance / audit error ratio
    # with streaming windowed p50/p95/p99 (stats_payload()["timeseries"]).
    # Off (default): no store exists, handles carry no completion hook, and
    # the delivery path is byte-for-byte the pre-telemetry code; ON only
    # observes finished handles, so answers stay bit-identical either way.
    telemetry: bool = False
    # Ring-buffer capacity per template series (and the drain-level
    # streaming-latency rings) when telemetry is on.
    timeseries_window: int = 256
    # Initial SLO targets (tuple of repro.obs.slo.SloTarget); more can be
    # added at runtime via session.slo.set_target(...).  Requires
    # telemetry=True (targets evaluate against the time-series).
    slo_targets: Optional[Tuple] = None
    # Flight recorder: path of an append-only JSONL event log (submit /
    # pilot / rate_solve / final / deliver / fallback / fail / audit /
    # slo_breach / sampled-trace records; see repro.obs.events).  The
    # recorder never raises into the query path — an unwritable target
    # only counts drops.  None (default) records nothing.
    flight_recorder: Optional[str] = None
    flight_recorder_max_bytes: int = 1 << 20   # rotate past this size
    flight_recorder_max_files: int = 3         # live file + rotated .1/.2
    # Always-on sampled tracing: attach a full span tree to this fraction
    # of queries, chosen by a content-derived hash of (structural
    # signature, session seed) — never wall-clock RNG, so equal-seed
    # sessions sample the IDENTICAL query set and replay stays
    # deterministic.  Sampled traces land in the flight recorder (when
    # armed) and the session's recent-traces ring.  0.0 (default) samples
    # nothing; tracing=True still traces everything.
    trace_sample: float = 0.0
    # Fuse both TAQA stages into ONE device program per query (pilot scan
    # -> rate solve -> final aggregation with no host sync between stages;
    # see engine/physical.py compile_fused).  Answers stay bit-identical
    # to the two-stage path: the fused program replays the same
    # content-derived draws in the same reduction order, and delivery
    # verifies the device-side final draw against the host oracle before
    # trusting fused sums — any mismatch, fallback decision, or
    # ineligible query shape (groups, joins, kernels, shards) re-routes
    # to the two-stage path.  Off (default) is byte-for-byte today's
    # two-launch execution.
    fused_taqa: bool = False

    def resolve_workers(self) -> int:
        """The worker count ``async_workers=None`` auto-sizes to.

        On <= 2 cores the pool measurably LOSES to the serial loop (GIL-bound
        planning + jit-dispatch contention — the BENCH_runtime.json `async`
        regression), so toy hosts fall back to serial; larger machines get a
        pool one narrower than the core count, capped at 8.
        """
        if self.async_workers is not None:
            return self.async_workers
        cpus = os.cpu_count() or 1
        if cpus <= 2:
            return 0
        return min(8, cpus - 1)  # leave a core for the draining thread

    def resolve_pilot_workers(self) -> int:
        """Pilot-stage fan-out width (``pilot_workers=None`` auto-size).

        Unlike the group pool, pilot stages are device-execution-heavy
        (the scan releases the GIL), so even 2-core hosts profit from a
        2-wide pilot pool; single-core hosts stay serial.
        """
        if self.pilot_workers is not None:
            return self.pilot_workers
        cpus = os.cpu_count() or 1
        return 0 if cpus <= 1 else min(4, cpus)


class Session:
    """A client session against a catalog of block tables."""

    def __init__(self, catalog: Optional[Dict[str, BlockTable]] = None, *,
                 seed: int = 0, config: SessionConfig = SessionConfig(),
                 executor: Optional[Executor] = None):
        self.config = config
        if config.spec_kwargs:
            # fail at construction, not on every client's ERROR clause
            dataclasses.replace(
                ErrorSpec(error=config.default_error,
                          confidence=config.default_confidence),
                **config.spec_kwargs)
        if executor is not None:
            if catalog is not None:
                raise ValueError(
                    "pass either catalog or executor, not both: an explicit "
                    "executor brings its own catalog, and the catalog "
                    "argument would be silently ignored")
            self.executor = executor
        else:
            # DistExecutor behaves exactly like Executor until a table is
            # registered with shards= (see register_table)
            self.executor = DistExecutor(catalog or {},
                                         use_compiled=config.use_compiled,
                                         kernel_mode=config.kernel_mode,
                                         staged_bytes=config.staged_bytes)
        self.db = PilotDB(self.executor,
                          large_table_rows=config.large_table_rows)
        self._entropy = int(seed)
        self._next_id = 0
        self._max_groups_cache: Dict[tuple, int] = {}
        self._dictionaries: Dict[str, "_Dictionary"] = {}
        # Bumped by register_table; snapshotted when a query starts
        # executing so an answer computed against since-replaced data can
        # never be delivered or (re-)enter the result cache.  The lock makes
        # bump+swap atomic with respect to snapshots: a snapshot is taken
        # either wholly before a replacement (the completion check then sees
        # the bump) or wholly after (the query runs on the new data).
        self._table_gen: Dict[str, int] = {}
        self._gen_lock = threading.Lock()
        self.result_cache = ResultCache(config.result_cache_size,
                                        max_bytes=config.result_cache_bytes)
        self.runtime = AsyncRuntime(self, workers=config.resolve_workers(),
                                    pilot_workers=config.resolve_pilot_workers())
        self.scheduler = QueryScheduler(self)
        # unified metrics registry: first-class instruments plus collector
        # views over the caches/runtime this session already tracks
        self.metrics = _metrics.MetricsRegistry()
        # -- continuous telemetry (repro.obs.timeseries / slo / events) ------
        if not 0.0 <= config.trace_sample <= 1.0:
            raise ValueError(
                f"trace_sample must be in [0, 1], got {config.trace_sample}")
        self.recorder = (_events.FlightRecorder(
            config.flight_recorder,
            max_bytes=config.flight_recorder_max_bytes,
            max_files=config.flight_recorder_max_files)
            if config.flight_recorder else None)
        self.timeseries = (_timeseries.TemplateTimeSeries(
            window=config.timeseries_window)
            if config.telemetry else None)
        self.slo = (_slo.SloMonitor(
            self.metrics, self.timeseries, recorder=self.recorder,
            targets=tuple(config.slo_targets or ()))
            if config.telemetry else None)
        if config.slo_targets and not config.telemetry:
            raise ValueError(
                "slo_targets requires telemetry=True (targets evaluate "
                "against the per-template time-series)")
        # last N sampled span trees (dict form), for the ops dashboard
        self.recent_traces: "collections.deque" = collections.deque(maxlen=16)
        # whether handles get the completion hook: any continuous-telemetry
        # surface is on — False (the default config) arms NOTHING, keeping
        # submission and completion byte-for-byte the pre-telemetry path
        self._telemetry_armed = (self.timeseries is not None
                                 or self.recorder is not None
                                 or config.trace_sample > 0.0)
        _metrics.register_session_collectors(self.metrics, self)
        self.auditor = (_audit.GuaranteeAuditor(self.db, self.metrics)
                        if config.audit else None)

    def close(self) -> None:
        """Shut the runtime's worker pool down and close the flight
        recorder (idempotent)."""
        self.runtime.shutdown()
        if self.recorder is not None:
            self.recorder.close()

    # -- catalog -------------------------------------------------------------
    def register_table(self, name: str, table: BlockTable, *,
                       dictionaries: Optional[Dict[str, Sequence[str]]] = None,
                       shards: Optional[int] = None,
                       staged_rates: Optional[Sequence[float]] = None,
                       ) -> None:
        """Add (or replace) a catalog table.

        ``staged_rates=[...]`` additionally materializes a staged
        block-sample ladder for the table (``staged_rates=True`` uses the
        default 1%/4%/16% ladder; per shard for sharded registrations): a
        sampled scan whose rate a rung covers executes against the
        pre-gathered staged arrays as a sub-draw of the table's ONE
        content-derived staging realization — bit-identical to a fresh
        draw, for pilots and finals — skipping the per-query full-table
        gather.  ``staged_rates=None`` (default) stages nothing and
        reproduces the unstaged behavior exactly.  Re-registration always
        drops the old ladder first, so staged arrays can never outlive
        their data.

        ``shards=N`` registers the table *partitioned* into N disjoint
        block ranges (placed round-robin across JAX devices when more than
        one is available): block-sampled scans then execute one dispatch
        per shard, merged through per-block statistics (:mod:`repro.dist`)
        — and answers are bit-identical for EVERY shard count, so
        re-sharding never perturbs equal-seed replay, shared pilots, or the
        result cache.  ``shards=None`` (default) registers monolithic.
        Memory cost: a sharded registration keeps the monolithic arrays
        (exact / row-sample / multi-table fallback paths run on them) AND
        materializes every shard's slices — about 2x the table's bytes
        resident until the plain registration is dropped.

        Cache-invalidation contract: registering ``name`` synchronously
        evicts (a) the cached MAXGROUPS statistics of its columns and
        (b) every result-cache entry whose plan scanned ``name`` — including
        join queries that merely touch it — so no later lookup can return an
        answer (or an error report) computed against the replaced data.
        Entries over other tables survive; compiled *executables* need no
        invalidation (see :meth:`Executor.register_table`: data enters as
        runtime arguments, geometry changes re-key the compile cache).
        A query of ``name`` still in flight on the runtime when the
        replacement lands fails with a retryable error rather than
        delivering a possibly-torn answer (see :meth:`_complete_handle`).

        ``dictionaries`` maps dictionary-encoded column names to their value
        lists (code = list index), enabling string literals for those
        columns in WHERE clauses: ``WHERE l_returnflag = 'A'`` lowers to the
        integer code before planning.
        """
        if shards is not None:
            if not hasattr(self.executor, "register_sharded"):
                raise ValueError(
                    "shards= needs a dist-capable executor (repro.dist."
                    "DistExecutor — the session default); the explicit "
                    "executor passed to this session does not support "
                    "sharding")
            # validate BEFORE the generation bump: a rejected registration
            # must not fail in-flight queries over unchanged data
            if not 1 <= shards <= table.num_blocks:
                raise ValueError(
                    f"shards must be in [1, {table.num_blocks}] (blocks are "
                    f"the atomic placement unit), got {shards}")
        if staged_rates is not None:
            if not hasattr(self.executor, "register_staged"):
                raise ValueError(
                    "staged_rates= needs a staging-capable executor (the "
                    "session default); the explicit executor passed to this "
                    "session does not support staged sample ladders")
            # validate BEFORE the generation bump, like shards= above
            staged_rates = DEFAULT_STAGED_RATES if staged_rates is True \
                else validate_rates(staged_rates)
        # bump+swap under the generation lock: no snapshot can interleave
        # between the new generation and the new data (see _gen_lock above)
        with self._gen_lock:
            self._table_gen[name] = self._table_gen.get(name, 0) + 1
            if shards is None:
                self.executor.register_table(name, table)
            else:
                self.executor.register_sharded(name, table, shards)
            if staged_rates is not None:
                # stage inside the lock: the ladder (and its seed pinning)
                # becomes visible atomically with the table swap, so no
                # query can observe the table staged-rates-on but unstaged
                self.executor.register_staged(
                    name, staged_rates, seed=self._staged_seed_for(name))
        # replacing a table invalidates its cached statistics
        self._max_groups_cache = {k: v for k, v in
                                  self._max_groups_cache.items()
                                  if k[0] != name}
        # eviction after the bump: an in-flight query's cache insert either
        # sees the bump in its put guard (skipped) or lands before this
        # eviction (removed) — the only two orders under the cache lock
        self.result_cache.invalidate_table(name)
        if dictionaries:
            for column, values in dictionaries.items():
                self.register_dictionary(column, values)

    def register_dictionary(self, column: str, values: Sequence[str]) -> None:
        """Declare ``column`` as dictionary-encoded: ``values[i]`` is the
        string for integer code ``i``.  String equality literals comparing
        against ``column`` then lower to the code (see ``api/sql.py``).

        When ``values`` is lexicographically sorted (a *sorted dictionary*
        encoding: code order == string order), order comparisons
        (``WHERE col < 'N'``) lower too, via the bisection boundary — even
        for literals outside the dictionary.  Unsorted dictionaries keep
        rejecting order comparisons: their code order is meaningless.
        """
        values = list(values)
        self._dictionaries[column] = _Dictionary(
            codes={v: i for i, v in enumerate(values)},
            values=values,
            is_sorted=all(a < b for a, b in zip(values, values[1:])))

    def tables(self) -> List[str]:
        return sorted(self.executor.catalog)

    def infer_max_groups(self, tables, column: str) -> int:
        """Group-id domain size for integer-coded group columns, from the
        catalog (the "DBMS statistics" a middleware would consult).

        ``tables`` is the table name — or every table in the query's FROM/
        JOIN chain, since GROUP BY may name a joined table's column.  An
        unknown table or column resolves to 1 rather than raising: the
        inference is advisory, and the real error surfaces at execution
        where it is captured on the handle.
        """
        if isinstance(tables, str):
            tables = (tables,)
        for name in tables:
            tab = self.executor.catalog.get(name)
            if tab is None or column not in tab.columns:
                continue
            key = (name, column)
            if key not in self._max_groups_cache:
                col = np.asarray(tab.columns[column])[np.asarray(tab.valid)]
                if col.size == 0:
                    self._max_groups_cache[key] = 1
                else:
                    # grouping requires non-negative integer group codes;
                    # a float/negative column would silently collapse groups
                    if not (np.issubdtype(col.dtype, np.integer)
                            or np.all(col == np.floor(col))):
                        raise UnsupportedSqlError(
                            f"GROUP BY {column}: column is not integer-coded "
                            f"(dtype {col.dtype}); group columns must hold "
                            "non-negative integer group ids")
                    if col.min() < 0:
                        raise UnsupportedSqlError(
                            f"GROUP BY {column}: negative group ids "
                            "(min {:g}) are not supported".format(col.min()))
                    self._max_groups_cache[key] = int(col.max()) + 1
            return self._max_groups_cache[key]
        return 1

    def compile_cache_info(self):
        return self.executor.compile_cache_info()

    def result_cache_info(self) -> ResultCacheInfo:
        return self.result_cache.info()

    # -- seed derivation ------------------------------------------------------
    def _derive_seed(self, query: Query, spec: Optional[ErrorSpec]) -> int:
        """Per-query seed as a pure function of session seed and query
        content.  Identical resubmissions re-derive the identical seed
        (making them result-cache hits), distinct queries get independent
        streams, and replay is submission-order-independent."""
        seq = np.random.SeedSequence(
            [self._entropy, _content_hash(query, spec)])
        return int(seq.generate_state(1, dtype=np.uint32)[0])

    def _pilot_seed_for(self, handle: QueryHandle) -> int:
        """Pilot seed from (session seed, structural signature, pilot-stage
        tunables) — NOT from the per-query seed.  Every query that could
        share a pilot derives the same value, so a shared pilot's statistics
        are bit-identical to the pilot each member would have run solo."""
        params = None if handle.spec is None else pilot_params(handle.spec)
        seq = np.random.SeedSequence(
            [self._entropy, 0x9E3779B9,
             _content_hash(handle.signature, params)])
        return int(seq.generate_state(1, dtype=np.uint32)[0])

    def _staged_seed_for(self, name: str) -> int:
        """The staging seed pinning table ``name``'s one staged realization.

        Derived from (session seed, table name) ONLY — not from the ladder
        rates — so every ladder configuration of a table stages the same
        realization and answers are bit-identical across re-staging with
        different rungs.  Its own domain constant keeps it off the
        per-query and pilot seed streams."""
        seq = np.random.SeedSequence(
            [self._entropy, 0x5A3D1ED, _content_hash(name)])
        return int(seq.generate_state(1, dtype=np.uint32)[0])

    # -- continuous telemetry (repro.obs.timeseries / slo / events) -----------
    def _trace_sampled(self, signature) -> bool:
        """Deterministic trace-sampling decision: a content-derived hash of
        (session seed, structural signature) against ``trace_sample`` —
        never wall-clock RNG, so equal-seed sessions sample the IDENTICAL
        query set (pinned by tests/test_obs.py).  Its own domain constant
        keeps the hash independent of the per-query/pilot/staged seed
        streams."""
        p = self.config.trace_sample
        if p <= 0.0:
            return False
        if p >= 1.0:
            return True
        h = _content_hash(self._entropy, 0x7E1E5C0F, signature)
        return (h / 2.0 ** 64) < p

    def template_key(self, sql: str) -> str:
        """The 12-hex time-series/SLO key of ``sql``'s constant-stripped
        template — what ``stats_payload()["timeseries"]["templates"]`` and
        :class:`repro.obs.slo.SloTarget.template` key by.  Constant-varied
        re-issues of one dashboard query map to one key."""
        parsed = parse_sql(sql, max_groups_resolver=self.infer_max_groups,
                           spec_kwargs=self.config.spec_kwargs)
        return _trace.sig_hash(
            plan_template(structural_signature(parsed.query)))

    def _emit_event(self, etype: str, **fields) -> None:
        """Append one flight-recorder record (no-op when unarmed; the
        recorder itself never raises into the query path)."""
        if self.recorder is not None:
            self.recorder.emit(etype, **fields)

    def _observe_delivery(self, handle: QueryHandle) -> None:
        """The completion hook (``handle._on_complete``): one time-series
        row, the SLO evaluation, and the flight-recorder terminal event for
        a just-finished handle.  Read-only over the handle — runs AFTER the
        done event, never raises (the hook firer swallows), and never
        touches seeds, answers, or caches."""
        latency = max(0.0, time.perf_counter() - handle.t_submit)
        key = handle._template_key or "_unkeyed"
        rep = handle.report
        failed = handle.status == QueryStatus.FAILED
        fallback = bool(rep.fallback) if rep is not None else False
        pilot_wall = rep.pilot_time_s if rep is not None else 0.0
        if handle.cached or rep is None:
            scanned = 0  # a cache-served delivery scanned nothing now
        elif rep.fallback:
            scanned = rep.pilot_scanned_bytes + rep.exact_scanned_bytes
        else:
            scanned = rep.pilot_scanned_bytes + rep.final_scanned_bytes
        shared = bool(rep.pilot_shared) if rep is not None else False
        staged = False
        if handle._trace is not None:  # staged rungs tag scan spans only
            staged = any(sp.attrs.get("staged")
                         for sp in handle._trace.find("scan"))
        if self.timeseries is not None:
            self.timeseries.record_delivery(
                key, sql=handle.sql, latency_s=latency,
                pilot_wall_s=pilot_wall, scanned_bytes=scanned,
                cached=handle.cached, shared=shared, fused=handle._fused,
                staged=staged, fallback=fallback, failed=failed)
        if self.recorder is not None:
            if failed:
                self._emit_event("fail", qid=handle.query_id, template=key,
                                 latency_s=round(latency, 6),
                                 error=handle.error)
            else:
                self._emit_event(
                    "deliver", qid=handle.query_id, template=key,
                    latency_s=round(latency, 6),
                    pilot_wall_s=round(pilot_wall, 6),
                    scanned_bytes=int(scanned), cached=handle.cached,
                    shared=shared, fused=handle._fused, staged=staged,
                    fallback=fallback)
                if fallback:
                    self._emit_event("fallback", qid=handle.query_id,
                                     template=key, reason=rep.fallback)
        if handle._trace_sampled and handle._trace is not None:
            tree = handle._trace.to_dict()
            self.recent_traces.append(tree)
            self._emit_event("trace", qid=handle.query_id, template=key,
                             trace=tree)
        if self.slo is not None:
            self.slo.evaluate(key)

    def _observe_audit(self, handle: QueryHandle,
                       rec: _audit.AuditRecord) -> None:
        """Feed one audit outcome into the time-series / recorder / SLO
        (called by :meth:`_complete_handle` after the auditor ran)."""
        key = handle._template_key or "_unkeyed"
        if self.timeseries is not None and rec.skipped is None:
            self.timeseries.record_audit(key, rec.error_ratio, rec.passed)
        self._emit_event("audit", qid=handle.query_id, template=key,
                         ratio=round(rec.error_ratio, 6), passed=rec.passed,
                         observed=round(rec.observed_error, 6),
                         promised=rec.promised_error, skipped=rec.skipped)
        if self.slo is not None and rec.skipped is None:
            self.slo.evaluate(key)  # violation-rate targets see the record

    # -- front doors ----------------------------------------------------------
    def table(self, name: str) -> QueryBuilder:
        if name not in self.executor.catalog:
            raise KeyError(f"unknown table {name!r}; registered: "
                           f"{self.tables()}")
        return QueryBuilder(self, name)

    def sql(self, text: str, *, stream: bool = False) -> QueryHandle:
        """Parse and execute dialect SQL synchronously.

        Parse-stage rejections — :class:`repro.api.SqlSyntaxError`, and
        :class:`repro.api.UnsupportedSqlError` for semantic violations such
        as GROUP BY on a non-integer-coded column or an unresolvable string
        literal — raise immediately (the query never existed); execution
        failures are captured on the returned handle.

        ``stream=True`` attaches a frame buffer before execution, so the
        handle's :meth:`QueryHandle.stream` / :meth:`QueryHandle.on_frame`
        observe the advisory pilot estimate as well as the terminal frame;
        the default is byte-for-byte today's non-streaming behavior.
        """
        handle = self._parse_to_handle(text, stream=stream)
        self._run_handle(handle)
        return handle

    def prepare(self, text: str, *, stream: bool = False) -> QueryHandle:
        """Parse dialect SQL into a pending handle without scheduling it —
        for callers that run their own :class:`QueryScheduler` (e.g. a
        gateway keeping its queue separate from the session's)."""
        return self._parse_to_handle(text, stream=stream)

    def submit(self, text: str, *, stream: bool = False) -> QueryHandle:
        """Parse dialect SQL and enqueue it on the session scheduler."""
        return self.scheduler.submit(self.prepare(text, stream=stream))

    def execute(self, query: Query, spec: Optional[ErrorSpec] = None, *,
                stream: bool = False) -> QueryHandle:
        """Execute an already-lowered query synchronously (builder path)."""
        handle = self._make_handle(query, spec, stream=stream)
        self._run_handle(handle)
        return handle

    def submit_query(self, query: Query,
                     spec: Optional[ErrorSpec] = None, *,
                     having: Optional[HavingClause] = None,
                     limit: Optional[LimitClause] = None,
                     stream: bool = False) -> QueryHandle:
        return self.scheduler.submit(
            self._make_handle(query, spec, having=having, limit=limit,
                              stream=stream))

    def drain(self, max_queries: Optional[int] = None) -> List[QueryHandle]:
        return self.scheduler.drain(max_queries)

    def drain_async(self) -> List[QueryHandle]:
        """Dispatch every pending query to the runtime without waiting;
        observe completion per handle via ``poll()`` / ``wait()``."""
        return self.scheduler.drain_async()

    # -- plumbing -------------------------------------------------------------
    def _parse_to_handle(self, text: str, *, stream: bool = False) -> QueryHandle:
        t0 = time.perf_counter()
        parsed = parse_sql(text, max_groups_resolver=self.infer_max_groups,
                           spec_kwargs=self.config.spec_kwargs)
        t_parsed = time.perf_counter()
        # t0 (pre-parse) is the submit epoch: the parse span and every
        # frame's emitted_at stay non-negative relative to it
        handle = self._make_handle(parsed.query, parsed.spec, sql=text,
                                   having=parsed.having, limit=parsed.limit,
                                   stream=stream, t_submit=t0)
        if handle._trace is not None:
            handle._trace.record("parse", duration_s=t_parsed - t0,
                                 t_start=t0)
        return handle

    def _resolve_dictionary(self, column: str, literal: str) -> int:
        d = self._dictionaries.get(column)
        if d is None:
            raise UnsupportedSqlError(
                f"string literal {literal!r} compares against {column!r}, "
                "which has no registered dictionary (see "
                "Session.register_dictionary)")
        if literal not in d.codes:
            raise UnsupportedSqlError(
                f"{literal!r} is not in the dictionary of {column!r} "
                f"(values: {sorted(d.codes)})")
        return d.codes[literal]

    def _resolve_dictionary_order(self, column: str, literal: str,
                                  op: str) -> Tuple[str, int]:
        """Lower an order comparison ``column <op> literal`` against a
        SORTED dictionary to an integer-code comparison.

        Sortedness makes code order equal string order, so the comparison
        becomes a bisection boundary — valid even for literals not in the
        dictionary: ``col < 'N'`` holds exactly for codes below
        ``bisect_left(values, 'N')``.  Returns the lowered ``(op, code)``
        with the column on the left.
        """
        d = self._dictionaries.get(column)
        if d is None:
            raise UnsupportedSqlError(
                f"string literal {literal!r} compares against {column!r}, "
                "which has no registered dictionary (see "
                "Session.register_dictionary)")
        if not d.is_sorted:
            raise UnsupportedSqlError(
                f"dictionary-encoded column {column!r} supports = and != "
                f"only, got {op!r}: its dictionary is not lexicographically "
                "sorted, so code order does not reflect string order "
                "(register a sorted dictionary to enable order comparisons)")
        if op in ("<", ">="):
            boundary = bisect.bisect_left(d.values, literal)
        else:  # "<=", ">": strict/inclusive flip at the right bisection
            boundary = bisect.bisect_right(d.values, literal)
        lowered = {"<": "<", "<=": "<", ">": ">=", ">=": ">="}[op]
        return lowered, boundary

    def _validate_group_domain(self, query: Query) -> None:
        """Reject GROUP BY shapes that would silently misbehave: a
        max_groups above the buffer-size cap (OOM in a shared server) or
        below the column's observed domain (the engine clips overflow group
        ids, silently merging those rows into the last group)."""
        if query.group_by is None:
            return
        limit = self.config.max_groups_limit
        if query.max_groups > limit:
            raise UnsupportedSqlError(
                f"GROUP BY {query.group_by}: max_groups={query.max_groups} "
                f"exceeds the session limit {limit} (per-block group "
                "buffers scale with max_groups)")
        tables = tuple(s.table for s in query.child.scans())
        domain = self.infer_max_groups(tables, query.group_by)
        if domain > query.max_groups:
            raise UnsupportedSqlError(
                f"GROUP BY {query.group_by}: MAXGROUPS {query.max_groups} "
                f"is below the observed group domain ({domain}); overflow "
                "groups would be silently merged into the last group")

    def _make_handle(self, query: Query, spec: Optional[ErrorSpec],
                     sql: Optional[str] = None,
                     having: Optional[HavingClause] = None,
                     limit: Optional[LimitClause] = None,
                     stream: bool = False,
                     t_submit: Optional[float] = None) -> QueryHandle:
        # resolve + validate before deriving a seed: rejected queries never
        # enter the seed/cache keyspace
        query = resolve_string_literals(query, self._resolve_dictionary,
                                        self._resolve_dictionary_order)
        self._validate_group_domain(query)
        if having is not None and having.agg not in {c.name for c in query.aggs}:
            raise UnsupportedSqlError(
                f"HAVING references unknown aggregate {having.agg!r} "
                f"(outputs: {[c.name for c in query.aggs]})")
        if limit is not None and limit.order_by is not None \
                and limit.order_by not in {c.name for c in query.aggs}:
            raise UnsupportedSqlError(
                f"ORDER BY references unknown aggregate {limit.order_by!r} "
                f"(outputs: {[c.name for c in query.aggs]})")
        # one lowering: the group key is the (memoized) constant-stripped
        # template of the signature just computed, not a second lowering
        t_lower0 = time.perf_counter()
        signature = structural_signature(query)
        handle = QueryHandle(query_id=self._next_id, query=query, spec=spec,
                             seed=self._derive_seed(query, spec), sql=sql,
                             having=having, limit=limit, signature=signature,
                             group_key=plan_template(signature),
                             t_submit=(time.perf_counter()
                                       if t_submit is None else t_submit))
        self._next_id += 1
        handle._trace_sampled = self._trace_sampled(signature)
        if self.config.tracing or handle._trace_sampled:
            handle._trace = _trace.QueryTrace(
                handle.query_id, sql=sql, t_start=handle.t_submit)
            handle._trace.record(
                "lower", duration_s=time.perf_counter() - t_lower0,
                t_start=t_lower0,
                seed=handle.seed,
                template=_trace.sig_hash(handle.group_key),
                signature=_trace.sig_hash(signature))
        if self._telemetry_armed:
            handle._template_key = _trace.sig_hash(handle.group_key)
            handle._on_complete = self._observe_delivery
            if self.recorder is not None:
                self.recorder.emit(
                    "submit", qid=handle.query_id,
                    template=handle._template_key, sql=sql,
                    sampled=handle._trace_sampled)
        if stream:
            handle.enable_streaming()
        return handle

    def failed_handle(self, sql: str, error: str) -> QueryHandle:
        """A pre-failed handle for requests that never parsed (gateways use
        this to reject one client's bad SQL without dropping the batch)."""
        handle = QueryHandle(query_id=self._next_id, query=None, spec=None,
                             seed=0, sql=sql, status=QueryStatus.FAILED,
                             error=error)
        handle._done_event.set()
        self._next_id += 1
        return handle

    # -- execution core (shared by sync paths and runtime workers) ------------
    def _cache_key(self, handle: QueryHandle):
        # (structural signature, predicate constants, ErrorSpec, seed): the
        # frozen Query embeds the first two (constants live in its plan) and
        # additionally pins user-facing aggregate names.
        return (handle.query, handle.spec, handle.seed)

    def _serve_cached(self, handle: QueryHandle) -> bool:
        """Answer ``handle`` from the result cache if possible.  A hit
        rebuilds the answer from the compact cached record — values and the
        error report that was guaranteed when it was computed (still valid:
        register_table would have evicted the entry if the data had
        changed)."""
        if handle.query is None:
            return False
        with _trace.span("cache_lookup") as sp:
            entry = self.result_cache.get(self._cache_key(handle))
            sp.set(hit=entry is not None)
        if entry is None:
            return False
        if handle.streaming and isinstance(entry, CachedAnswer) \
                and entry.pilot is not None:
            # replay the compact pilot summary recorded at insert as an
            # advisory frame, so cached re-issues stream the same shape
            # (pilot then final); entries without one stream single-frame
            handle._emit(pilot_frame_for(handle.query_id, entry.pilot,
                                         from_cache=True))
        answer = entry.to_answer() if isinstance(entry, CachedAnswer) else entry
        if handle.having is not None:
            # the cache holds the unfiltered base answer (HAVING is not in
            # the key), so HAVING-varied re-issues all hit one entry
            answer = handle.having.apply(answer)
        if handle.limit is not None:  # same contract; after HAVING
            answer = handle.limit.apply(answer)
        handle._mark_done(answer, cached=True)
        return True

    def _scan_generations(self, query: Query) -> Tuple[int, ...]:
        with self._gen_lock:
            return tuple(self._table_gen.get(s.table, 0)
                         for s in query.child.scans())

    def _complete_handle(self, handle: QueryHandle, answer: ApproxAnswer,
                         gen_snapshot: Optional[tuple] = None,
                         pilot_est=None) -> bool:
        """Finish a handle, guarding against mid-flight table replacement.

        If :meth:`register_table` replaced any scanned table after execution
        started (``gen_snapshot`` mismatch), the answer may be *torn* —
        e.g. pilot statistics from the old data scaling a final scan of the
        new — so its error report is no longer a guarantee.  PilotDB never
        returns an unguaranteed estimate: the handle fails with a retryable
        error instead (a resubmission re-derives the same seed and runs
        cleanly against the new data).  The result-cache insert is guarded
        by the same generation check, under the cache lock.  Returns True
        when the handle completed with the answer.

        ``pilot_est`` (the query's advisory :class:`PilotEstimate`, when its
        pilot produced one) is recorded on the cache entry so cached
        re-issues can replay a provisional frame (see :meth:`_serve_cached`).
        """
        current = self._scan_generations(handle.query)
        if gen_snapshot is not None and gen_snapshot != current:
            handle._mark_failed(
                "table replaced while the query was in flight "
                f"({sorted({s.table for s in handle.query.child.scans()})}); "
                "resubmit to run against the new data")
            return False
        self.result_cache.put(
            self._cache_key(handle),
            CachedAnswer.from_answer(answer, pilot=pilot_est),
            (s.table for s in handle.query.child.scans()),
            guard=None if gen_snapshot is None else
            (lambda: gen_snapshot == self._scan_generations(handle.query)))
        base = answer  # the guarantee covers the pre-HAVING/LIMIT answer
        if handle.having is not None:  # cache keeps the unfiltered answer
            answer = handle.having.apply(answer)
        if handle.limit is not None:   # after HAVING, like _serve_cached
            answer = handle.limit.apply(answer)
        handle._mark_done(answer)
        if self.auditor is not None:
            # AFTER delivery (the client already has its answer; the trace
            # is finished, so the exact run traces nothing) and against the
            # base answer — every group the guarantee covered gets checked
            rec = self.auditor.check(handle, base)
            if rec is not None and self._telemetry_armed:
                try:  # telemetry observes; it must never raise into delivery
                    self._observe_audit(handle, rec)
                except Exception:
                    pass
        return True

    def _run_fused(self, handle: QueryHandle) -> Optional[ApproxAnswer]:
        """Attempt the single-launch fused TAQA program for ``handle``.

        Returns the answer (bit-identical to the two-stage path by the
        fused-path verification contract — see :meth:`PilotDB.run_fused`)
        or None when the query's shape is ineligible, in which case the
        caller falls through to the two-stage path having executed
        nothing."""
        with _trace.span("fused") as sp:
            try:
                ans = self.db.run_fused(
                    handle.query, handle.spec, seed=handle.seed,
                    pilot_seed=self._pilot_seed_for(handle))
            except Exception as e:
                # fusion is an optimization, never a failure mode: the
                # two-stage path re-runs the query from scratch and captures
                # any genuine execution failure on the handle itself
                self.executor.note_swallowed("fused", e)
                ans = None
            sp.set(engaged=ans is not None,
                   fallback=None if ans is None else ans.report.fallback)
        if ans is not None:
            handle._fused = True  # provenance + telemetry read this flag
            rep = ans.report
            self._emit_event("pilot", qid=handle.query_id, fused=True,
                             table=rep.pilot_table,
                             scanned_bytes=rep.pilot_scanned_bytes,
                             wall_s=round(rep.pilot_time_s, 6),
                             fallback=rep.fallback)
            self._emit_event("rate_solve", qid=handle.query_id, fused=True,
                             candidates=rep.candidates, fallback=rep.fallback)
            self._emit_event("final", qid=handle.query_id, fused=True,
                             scanned_bytes=rep.final_scanned_bytes,
                             wall_s=round(rep.final_time_s, 6),
                             fallback=rep.fallback)
        return ans

    def _run_handle(self, handle: QueryHandle) -> QueryHandle:
        if handle.done:
            return handle
        token = _trace.activate(handle._trace)
        try:
            if self._serve_cached(handle):
                return handle
            handle._mark_running()
            gen = self._scan_generations(handle.query)
            try:
                pilot_est = None
                if handle.spec is None:  # db.exact holds the exact span
                    ans = self.db.exact(handle.query)
                elif self.config.fused_taqa and (
                        fused := self._run_fused(handle)) is not None:
                    ans = fused
                else:
                    # run the two TAQA stages separately (instead of
                    # db.query) so the advisory estimate streams the moment
                    # stage 1 returns — before any stage-2 dispatch
                    with _trace.span("pilot", shared=False) as sp:
                        outcome = self.db.run_pilot(
                            handle.query, handle.spec,
                            self._pilot_seed_for(handle))
                        rep = outcome.report
                        sp.set(table=rep.pilot_table,
                               theta_pilot=rep.theta_pilot,
                               n_pilot_blocks=rep.n_pilot_blocks,
                               scanned_bytes=rep.pilot_scanned_bytes,
                               fallback=rep.fallback)
                    self._emit_event(
                        "pilot", qid=handle.query_id, shared=False,
                        table=rep.pilot_table,
                        scanned_bytes=rep.pilot_scanned_bytes,
                        wall_s=round(rep.pilot_time_s, 6),
                        fallback=rep.fallback)
                    pilot_est = advisory_estimate(handle.query, outcome,
                                                  handle.spec.confidence)
                    if pilot_est is not None:
                        handle._emit(pilot_frame_for(handle.query_id,
                                                     pilot_est))
                    # finish_from_pilot == run_final(prepare_final(...));
                    # split here only so each stage gets its own span
                    with _trace.span("rate_solve") as sp:
                        stage = self.db.prepare_final(
                            handle.query, handle.spec, outcome, handle.seed)
                        rep = stage.report
                        sp.set(candidates=rep.candidates,
                               fallback=rep.fallback,
                               rates=dict(rep.plan.rates)
                               if rep.plan is not None else None)
                    self._emit_event("rate_solve", qid=handle.query_id,
                                     candidates=rep.candidates,
                                     fallback=rep.fallback)
                    ans = self.db.run_final(stage)  # its own final span
                    self._emit_event(
                        "final", qid=handle.query_id,
                        scanned_bytes=ans.report.final_scanned_bytes,
                        wall_s=round(ans.report.final_time_s, 6),
                        fallback=ans.report.fallback)
                with _trace.span("deliver"):
                    self._complete_handle(handle, ans, gen,
                                          pilot_est=pilot_est)
            except Exception as e:  # capture, don't raise through the client
                handle._mark_failed(f"{type(e).__name__}: {e}")
            return handle
        finally:
            # worker threads are pooled: a leaked context var would
            # misattribute the next query's spans
            _trace.deactivate(token)

    def _execute_group(self, handles: List[QueryHandle]) -> None:
        """Run one signature group (runtime workers land here): cached
        members answer immediately, the rest share a pilot per
        pilot-params subgroup and finish independently."""
        _shared_pilot.execute_group(self, handles)
