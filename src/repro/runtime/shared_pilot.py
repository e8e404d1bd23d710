"""One pilot, many finals: group execution with shared pilot statistics and
batched final launches.

A drain group holds queries with equal *template* signatures (sampling- and
constant-stripped plan — the compile-cache grouping key).  Within it, pilot
work re-splits on the FULL constant-bearing structural signature plus the
pilot-stage tunables (:func:`repro.core.taqa.pilot_params`): pilot block
statistics depend on predicate selectivity, so two queries differing in a
WHERE constant must never share a pilot — sharing across constants would
silently break the §4 error guarantees.  Members agreeing on both run ONE
pilot and fan its block statistics out: each solves its own sampling-plan
optimization from its own ErrorSpec and draws its own final sample from its
own seed.

Pilot fan-out.  A template group's pilot subgroups are mutually independent
(one per constant/pilot-params combination), so their stage-1 pilots run
concurrently on the runtime's dedicated pilot pool
(:meth:`repro.runtime.AsyncRuntime.map_pilot_subgroups`) and re-join here
before any final launches — a constant-varied herd no longer serializes its
N pilot stages on the group's single worker.  The pool records (wall,
serial-sum) pairs per fan-out; scheduler drains surface them as
``DrainStats.pilot_fanout_*``.

Batched finals.  Stage 2 is split into planning (``PilotDB.prepare_final``)
and execution: every subgroup first plans its members' finals, then the
whole drain group's pending final scans run through
``PilotDB.run_finals_batched`` — same-signature buckets stack their block-id
matrices and hoisted-constant params rows into ONE ``lax.map`` dispatch, so
N finals cost one launch instead of N.  Lanes execute each member's solo XLA
graph, keeping batched answers bit-identical to solo runs.

Bit-identity.  The pilot seed derives from (session seed, structural
signature, pilot params) — not from any member's per-query seed — and the
session uses the *same* derivation when a query runs solo.  A query answered
from a shared pilot and/or a batched final is therefore bit-identical to the
same query run alone on an equal-seed session: same pilot sample, same
constraints, same chosen plan, same final sample, same f32 reduction order.

Failure capture.  A member whose stage 2 raises fails alone; a pilot-stage
exception fails every member that would have used that pilot (each would
have raised identically solo).  Nothing propagates out of the group — the
worker pool relies on that.
"""

from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING, Dict, List, Optional, Tuple

from repro.core.taqa import (FinalStage, PilotOutcome, advisory_estimate,
                             pilot_params)
from repro.obs import trace as _trace
from repro.stream import pilot_frame_for

if TYPE_CHECKING:  # runtime layering: session owns the runtime
    from repro.api.session import QueryHandle, Session


def subgroup_by_pilot(handles: List["QueryHandle"]) -> List[List["QueryHandle"]]:
    """Split a drain group into pilot-sharing subgroups.

    Exact-mode members (no ErrorSpec) run no pilot and each form their own
    singleton; approximate members subgroup by (full constant-bearing
    signature, pilot params) — the template-grouped scheduler may put
    constant-varied queries in one drain group, and those must NOT share
    pilot statistics.  Submission order is kept within and across subgroups
    (first-arrival order).
    """
    subgroups: Dict[Tuple, List["QueryHandle"]] = {}
    for h in handles:
        key = ("exact", h.query_id) if h.spec is None \
            else ("pilot", h.signature) + pilot_params(h.spec)
        subgroups.setdefault(key, []).append(h)
    return list(subgroups.values())


@dataclasses.dataclass
class _Pending:
    """One group member between stage-2 planning and completion."""

    handle: "QueryHandle"
    gen: tuple                              # table-generation snapshot
    outcome: PilotOutcome
    stage: Optional[FinalStage] = None      # None: deferred duplicate
    failed: Optional[str] = None
    est: Optional[object] = None            # advisory PilotEstimate (or None)


def execute_group(session: "Session", handles: List["QueryHandle"]) -> None:
    """Run one drain group: cached members answer immediately, each
    pilot-sharing subgroup runs one pilot — subgroups fan out concurrently
    on the runtime's pilot pool and re-join here — pending finals batch
    into per-bucket single dispatches, members complete independently in
    submission order."""
    shared: List[List["QueryHandle"]] = []
    for members in subgroup_by_pilot(handles):
        live = []
        for h in members:
            if h.done:
                continue
            # per-member trace activation: the cache probe's span must land
            # on ITS handle's tree, not a neighbor's
            token = _trace.activate(h._trace)
            try:
                if not session._serve_cached(h):
                    live.append(h)
            finally:
                _trace.deactivate(token)
        if not live:
            continue
        if live[0].spec is None or not session.config.share_pilots:
            # exact members, or sharing disabled: the legacy solo path
            # (its own pilot, its own final dispatch)
            for h in live:
                session._run_handle(h)
            continue
        if session.config.fused_taqa and len(live) == 1 \
                and _try_fused(session, live[0]):
            continue  # single-launch program delivered the answer
        shared.append(live)

    # Batched pilots: when the group holds several pilot subgroups, their
    # stage-1 scans dispatch FIRST through PilotDB.run_pilots_batched —
    # same-shape pilot scans (same pilot table, same plan signature under
    # the drawn geometry) stack into ONE device launch; ineligible members
    # run their bit-identical solo pilots inside the same call.  Each
    # subgroup's precomputed outcome (or captured exception) then threads
    # into the fan-out below, which keeps only the stage-2 planning.
    # Generation snapshots are taken BEFORE the batched dispatch so the
    # mid-flight table-replacement guard keeps covering the pilot stage.
    pre: List[Optional[object]] = [None] * len(shared)
    gens: List[Optional[tuple]] = [None] * len(shared)
    if len(shared) >= 2:
        for live in shared:
            for h in live:
                h._mark_running()
        gens = [session._scan_generations(live[0].query) for live in shared]
        pre = session.db.run_pilots_batched(
            [(live[0].query, live[0].spec, session._pilot_seed_for(live[0]))
             for live in shared],
            traces=[live[0]._trace for live in shared])

    # Stage-1 fan-out: a template group may hold MANY pilot subgroups (a
    # constant-varied herd runs one pilot per constant — selectivity shapes
    # the §4 bounds), and those stages are independent: fan them out across
    # the pilot pool and re-join before the group-wide batched final
    # launch.  Results come back in submission order, completions below run
    # in submission order, and every subgroup's pilot seed is
    # content-derived — concurrency changes wall-clock, never answers.
    durations: List[float] = []

    def _stage1(args: Tuple[List["QueryHandle"], Optional[object],
                            Optional[tuple]]) -> List[_Pending]:
        live, outcome, gen = args
        t0 = time.perf_counter()
        try:
            return _pilot_and_prepare(session, live, pre=outcome, gen=gen)
        finally:
            durations.append(time.perf_counter() - t0)

    t0 = time.perf_counter()
    pend_lists = session.runtime.map_pilot_subgroups(
        _stage1, list(zip(shared, pre, gens)))
    if len(shared) >= 2:
        session.runtime.record_pilot_fanout(
            time.perf_counter() - t0, sum(durations))
    subgroups = [p for p in pend_lists if p]

    # one batched launch per same-signature bucket across the WHOLE group;
    # each subgroup's pilot-ownership box is shared between the per-bucket
    # early completions and the serial sweep below, so exactly one COMPLETED
    # member per subgroup carries pilot_shared=False whichever path lands it
    boxes = [{"owns": True} for _ in subgroups]
    if session.config.batch_finals:
        by_stage: Dict[int, Tuple[_Pending, dict]] = {}
        for pend, box in zip(subgroups, boxes):
            for p in pend:
                if p.stage is not None and p.failed is None \
                        and p.stage.answer is None:
                    by_stage[id(p.stage)] = (p, box)
        if len(by_stage) >= 2:
            def _on_answer(stage: FinalStage) -> None:
                # a bucket landed: complete its members NOW — streaming
                # clients see their FinalFrames while later buckets are
                # still dispatching (the serial sweep skips done handles)
                p, box = by_stage[id(stage)]
                _complete_one(session, p, box)

            try:
                session.db.run_finals_batched(
                    [pb[0].stage for pb in by_stage.values()],
                    on_answer=_on_answer,
                    traces=[pb[0].handle._trace for pb in by_stage.values()])
            except Exception as e:
                # batching is an optimization, never a failure mode: stages
                # left unanswered execute solo in the completion loop below
                # (run_final), under its per-member exception capture
                session.executor.note_swallowed("drain_finals", e)

    for pend, box in zip(subgroups, boxes):
        _complete_subgroup(session, pend, box)


def _pilot_and_prepare(session: "Session", live: List["QueryHandle"],
                       pre: Optional[object] = None,
                       gen: Optional[tuple] = None) -> List[_Pending]:
    """Run the subgroup's one pilot stage and plan every member's final.

    ``pre`` threads a pilot already executed by the group-wide batched
    dispatch (``PilotDB.run_pilots_batched``) into this subgroup: a
    :class:`PilotOutcome` skips the pilot stage here (the leader's
    ``pilot`` span, opened there, gets the sharing tags), a captured
    exception fails every member —
    exactly what the solo pilot's except-branch below would have done —
    and None runs the pilot as before.  ``gen`` carries the
    table-generation snapshot taken before that batched dispatch.
    """
    leader = live[0]
    pilot_seed = session._pilot_seed_for(leader)
    if gen is None:
        gen = session._scan_generations(leader.query)
    for h in live:
        h._mark_running()
    shared = len(live) > 1
    if isinstance(pre, Exception):
        # every member's solo pilot would have raised identically
        for h in live:
            h._mark_failed(f"{type(pre).__name__}: {pre}")
        return []
    if pre is not None:
        outcome = pre
        rep = outcome.report
        found = leader._trace.find("pilot") if leader._trace else []
        sp = found[-1] if found else _trace.NULL_SPAN
        sp.set(shared=shared, owner=True, members=len(live))
    else:
        # the shared pilot executes ONCE, on the leader's trace: deep tags
        # (staged rung, shard fan-out, compile hit/miss) annotate the
        # leader's open "pilot" span; members get a retroactive summary
        # span below
        token = _trace.activate(leader._trace)
        try:
            with _trace.span("pilot", shared=shared, owner=True,
                             members=len(live)) as sp:
                outcome = session.db.run_pilot(leader.query, leader.spec,
                                               pilot_seed)
                rep = outcome.report
                sp.set(table=rep.pilot_table, theta_pilot=rep.theta_pilot,
                       n_pilot_blocks=rep.n_pilot_blocks,
                       scanned_bytes=rep.pilot_scanned_bytes,
                       fallback=rep.fallback)
        except Exception as e:
            # every member's solo pilot would have raised identically
            for h in live:
                h._mark_failed(f"{type(e).__name__}: {e}")
            return []
        finally:
            _trace.deactivate(token)
    # one flight-recorder record per pilot STAGE (not per member): the
    # leader's qid plus the member count it fanned out to
    session._emit_event("pilot", qid=leader.query_id, shared=shared,
                        members=len(live), table=rep.pilot_table,
                        scanned_bytes=rep.pilot_scanned_bytes,
                        wall_s=round(rep.pilot_time_s, 6),
                        fallback=rep.fallback)
    # members sharing the leader's pilot get a record where it ran
    t_start, wall = ((sp.t0, sp.t1 - sp.t0) if isinstance(sp, _trace.Span)
                     else (None, rep.pilot_time_s))
    for h in live[1:]:
        if h._trace is not None:
            h._trace.record(
                "pilot", duration_s=wall, t_start=t_start, shared=True,
                owner=False, table=rep.pilot_table,
                theta_pilot=rep.theta_pilot,
                n_pilot_blocks=rep.n_pilot_blocks,
                scanned_bytes=rep.pilot_scanned_bytes,
                fallback=rep.fallback)
    # fan the shared pilot's advisory estimate out to EVERY member the
    # moment stage 1 returns — before any stage-2 planning or dispatch.
    # Members share pilot statistics but not necessarily confidence, so
    # the t-interval is computed per distinct confidence level.
    ests: Dict[float, Optional[object]] = {}
    for h in live:
        conf = h.spec.confidence
        if conf not in ests:
            ests[conf] = advisory_estimate(h.query, outcome, conf)
        if ests[conf] is not None:
            h._emit(pilot_frame_for(h.query_id, ests[conf], shared=shared))
    pend: List[_Pending] = []
    seen_keys = set()
    for h in live:
        token = _trace.activate(h._trace)
        try:
            # an earlier drain's completion may have populated the result
            # cache with this member's exact (query, spec, seed) answer
            if session._serve_cached(h):
                continue
            p = _Pending(handle=h, gen=gen, outcome=outcome,
                         est=ests.get(h.spec.confidence))
            key = session._cache_key(h)
            if session.result_cache.enabled and key in seen_keys:
                # identical re-issue inside one drain: the earlier member's
                # completion will cache the answer — defer instead of paying
                # a duplicate final execution
                pend.append(p)
                continue
            seen_keys.add(key)
            try:
                with _trace.span("rate_solve") as sp:
                    p.stage = session.db.prepare_final(h.query, h.spec,
                                                       outcome, seed=h.seed)
                    srep = p.stage.report
                    sp.set(candidates=srep.candidates,
                           fallback=srep.fallback,
                           rates=dict(srep.plan.rates)
                           if srep.plan is not None else None)
                session._emit_event("rate_solve", qid=h.query_id,
                                    candidates=srep.candidates,
                                    fallback=srep.fallback)
            except Exception as e:  # a failing member must not sink peers
                p.failed = f"{type(e).__name__}: {e}"
            pend.append(p)
        finally:
            _trace.deactivate(token)
    return pend


def _try_fused(session: "Session", h: "QueryHandle") -> bool:
    """Attempt the single-launch fused TAQA program for a singleton
    subgroup.  True when the handle completed (answer delivered, or failed
    on the completion guard); False when the query's shape is ineligible —
    the caller then falls through to the shared-pilot path having executed
    nothing (``Session._run_fused`` swallows fused-path exceptions, so a
    False return really means "nothing happened")."""
    token = _trace.activate(h._trace)
    try:
        h._mark_running()
        gen = session._scan_generations(h.query)
        ans = session._run_fused(h)
        if ans is None:
            return False
        with _trace.span("deliver"):
            session._complete_handle(h, ans, gen)
        return True
    finally:
        _trace.deactivate(token)


def _complete_one(session: "Session", p: _Pending, box: dict) -> None:
    """Finish ONE member (idempotent): called early by the batched launch's
    per-bucket callback, and again by the subgroup's serial sweep — whoever
    runs first delivers; the other sees ``handle.done`` and returns.

    ``box["owns"]`` is the subgroup's pilot-ownership flag: the first member
    that actually COMPUTES (not cache-serves) a completed answer owns the
    pilot stage in its report (pilot_shared=False) — drain stats count pilot
    stages by that flag.  Both callers run on the group's worker thread, so
    the box needs no lock.
    """
    h = p.handle
    if h.done:
        return
    token = _trace.activate(h._trace)
    try:
        if p.failed is not None:
            h._mark_failed(p.failed)
            return
        # a peer's completion may have cached this member's answer already
        if session._serve_cached(h):
            return
        try:
            if p.stage is None:  # deferred duplicate whose peer failed
                with _trace.span("rate_solve", deferred=True):
                    p.stage = session.db.prepare_final(h.query, h.spec,
                                                       p.outcome, seed=h.seed)
            # a stage answered before this sweep means the group's batched
            # lax.map dispatch landed it, under its own final span (or a
            # rate-solve fallback short-circuited to exact) — run_final
            # just returns it
            pre_answered = p.stage.answer is not None
            ans = session.db.run_final(p.stage)
            session._emit_event(
                "final", qid=h.query_id,
                batched=pre_answered and ans.report.fallback is None,
                scanned_bytes=ans.report.final_scanned_bytes,
                wall_s=round(ans.report.final_time_s, 6),
                fallback=ans.report.fallback)
            ans.report.pilot_shared = not box["owns"]
            # ownership sticks only to a COMPLETED answer: if completion
            # fails (mid-flight table replacement), the next member carries
            # the non-shared report so drain stats still see the stage.
            # (If every member fails, the stage shows only in
            # executor.pilots_run — drain stats count completed answers.)
            with _trace.span("deliver"):
                if session._complete_handle(h, ans, p.gen, pilot_est=p.est):
                    box["owns"] = False
        except Exception as e:  # a member failing alone must not sink peers
            h._mark_failed(f"{type(e).__name__}: {e}")
    finally:
        _trace.deactivate(token)


def _complete_subgroup(session: "Session", pend: List[_Pending],
                       box: Optional[dict] = None) -> None:
    if box is None:
        box = {"owns": True}
    for p in pend:
        _complete_one(session, p, box)
