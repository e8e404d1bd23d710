"""Observability layer (repro.obs): tracing, metrics registry, auditor.

The hard contracts under test:

* tracing OFF (the default) carries NO trace object anywhere — today's
  path, byte for byte;
* tracing ON changes no answer: bit-identical to the untraced equal-seed
  session across solo / herd / batched / cached / staged / sharded runs
  (spans only observe — perf_counter + attr dicts);
* every COMPLETED, FALLBACK, or FAILED query yields a CLOSED span tree
  (open_spans() == []), including mid-group captured failures, and the
  ErrorFrame path still terminates a blocked stream();
* the metrics registry absorbs the scattered counters (collectors match
  their sources) and renders Prometheus text; collectors die with their
  owners;
* audit mode perturbs nothing (bit-identical answers, untouched cache
  keys) while recording observed <= promised error for honest runs.
"""

import json

import numpy as np
import pytest

from repro.api import ErrorFrame, FinalFrame, PilotFrame, Session, \
    SessionConfig
from repro.core.taqa import PilotDB
from repro.engine.datagen import tpch_catalog
from repro.obs import GLOBAL, GuaranteeAuditor, MetricsRegistry, QueryTrace
from repro.obs import trace as trace_mod
from repro.obs.audit import provenance_of
from repro.serve.sql_gateway import SqlGateway

HERD_SQL = ("SELECT SUM(l_extendedprice * l_discount) AS rev FROM lineitem "
            "WHERE l_quantity < 24 ERROR 8% CONFIDENCE 95%")
GROUPED_SQL = ("SELECT SUM(l_quantity) AS q, COUNT(*) AS n FROM lineitem "
               "WHERE l_quantity < 30 GROUP BY l_returnflag MAXGROUPS 3 "
               "ERROR 10% CONFIDENCE 90%")

SERIAL_CFG = SessionConfig(async_workers=0, share_pilots=False,
                           result_cache_size=0)
NOCACHE_CFG = SessionConfig(async_workers=4, result_cache_size=0)
TRACE_SERIAL = SessionConfig(async_workers=0, share_pilots=False,
                             result_cache_size=0, tracing=True)
TRACE_HERD = SessionConfig(async_workers=4, result_cache_size=0,
                           tracing=True)


@pytest.fixture(scope="module")
def catalog():
    return tpch_catalog(scale_rows=200_000, block_rows=32, seed=0)


def _assert_bitwise(a, b):
    assert np.array_equal(a.values, b.values)
    assert np.array_equal(a.group_present, b.group_present)
    assert list(a.names) == list(b.names)


# ---------------------------------------------------------------------------
# Zero-overhead default: tracing OFF is today's path
# ---------------------------------------------------------------------------

def test_tracing_off_by_default(catalog):
    s = Session(catalog, seed=3, config=SERIAL_CFG)
    h = s.sql(HERD_SQL)
    assert h._trace is None
    assert h.trace() is None and h.trace("chrome") is None
    assert trace_mod.active() is None
    # instrumentation points degrade to the shared no-op span
    assert trace_mod.span("anything") is trace_mod.NULL_SPAN


def test_trace_format_validated(catalog):
    s = Session(catalog, seed=3, config=TRACE_SERIAL)
    h = s.sql(HERD_SQL)
    with pytest.raises(ValueError):
        h.trace(fmt="protobuf")


# ---------------------------------------------------------------------------
# Bit-identity: tracing observes, never steers
# ---------------------------------------------------------------------------

def test_traced_solo_bitwise_identical(catalog):
    plain = Session(catalog, seed=3, config=SERIAL_CFG).sql(HERD_SQL)
    traced = Session(catalog, seed=3, config=TRACE_SERIAL).sql(HERD_SQL)
    assert traced.fallback is None
    _assert_bitwise(traced.answer, plain.answer)


def test_traced_herd_bitwise_identical(catalog):
    solo = Session(catalog, seed=11, config=SERIAL_CFG).sql(HERD_SQL)
    rt = Session(catalog, seed=11, config=TRACE_HERD)
    handles = [rt.submit(HERD_SQL) for _ in range(5)]
    p0 = rt.executor.pilots_run
    rt.drain()
    assert rt.executor.pilots_run - p0 == 1  # tracing kept pilot sharing
    for h in handles:
        _assert_bitwise(h.answer, solo.answer)
        assert h._trace is not None and h._trace.open_spans() == []
    rt.close()


def test_traced_batched_finals_bitwise(catalog):
    template = ("SELECT SUM(l_extendedprice) AS rev FROM lineitem "
                "WHERE l_quantity < {} ERROR 10% CONFIDENCE 90%")
    cuts = [18, 24, 30, 36]
    serial = Session(catalog, seed=9, config=SERIAL_CFG)
    want = {c: serial.sql(template.format(c)).answer for c in cuts}
    rt = Session(catalog, seed=9, config=TRACE_HERD)
    handles = {c: rt.submit(template.format(c)) for c in cuts}
    rt.drain()
    for c, h in handles.items():
        _assert_bitwise(h.answer, want[c])
        assert h._trace.open_spans() == []
    rt.close()


def test_traced_cached_reissue_bitwise_and_provenance(catalog):
    s = Session(catalog, seed=13, config=SessionConfig(tracing=True))
    first = s.sql(HERD_SQL)
    again = s.sql(HERD_SQL)
    assert again.cached
    _assert_bitwise(again.answer, first.answer)
    assert again._trace.open_spans() == []
    hits = [sp for sp in again._trace.find("cache_lookup")
            if sp.attrs.get("hit")]
    assert hits  # the trace recorded the cache serve
    assert provenance_of(again) == "cached"
    s.close()


@pytest.mark.parametrize("shards", [1, 2])
def test_traced_sharded_bitwise_with_fanout_span(catalog, shards):
    mono = Session(catalog, seed=31, config=SERIAL_CFG).sql(GROUPED_SQL)
    s = Session(seed=31, config=TRACE_SERIAL)
    for name, tab in catalog.items():
        s.register_table(name, tab,
                         shards=shards if name == "lineitem" else None)
    h = s.sql(GROUPED_SQL)
    _assert_bitwise(h.answer, mono.answer)
    fanouts = h._trace.find("shard_fanout")
    if mono.fallback is None:
        assert fanouts and fanouts[0].attrs["shards"] == shards
        assert "+dist" in provenance_of(h)


def test_traced_staged_bitwise_with_staged_tags(catalog):
    def _run(rates, cfg):
        s = Session(seed=41, config=cfg)
        for name, tab in catalog.items():
            s.register_table(name, tab,
                             staged_rates=rates if name == "lineitem"
                             else None)
        return s, s.sql(HERD_SQL)

    _, ref = _run([1e-9], SERIAL_CFG)      # ladder that never serves
    s, hot = _run(True, TRACE_SERIAL)      # default ladder, traced
    assert s.executor.staged_info()["hits"] > 0
    _assert_bitwise(hot.answer, ref.answer)
    tagged = [sp for sp in hot._trace.find("scan")
              if sp.attrs.get("staged")]
    assert tagged  # staged-rung serves are visible in the trace
    assert "+staged" in provenance_of(hot)


# ---------------------------------------------------------------------------
# Span tree: vocabulary, closure, export
# ---------------------------------------------------------------------------

def test_solo_span_vocabulary_and_attrs(catalog):
    s = Session(catalog, seed=3, config=TRACE_SERIAL)
    h = s.sql(HERD_SQL)
    tr = h._trace
    assert tr.status == "ok" and tr.open_spans() == []
    names = set(tr.span_names())
    assert {"query", "parse", "lower", "pilot", "rate_solve",
            "final", "deliver"} <= names
    pilot, = tr.find("pilot")
    assert pilot.attrs["table"] == "lineitem"
    assert pilot.attrs["scanned_bytes"] > 0
    assert pilot.attrs["shared"] is False
    final, = tr.find("final")
    assert final.attrs["scanned_bytes"] > 0
    lower, = tr.find("lower")
    assert lower.attrs["seed"] == h.seed
    # nested engine scans attach under their stages
    assert any(c.name == "scan" for c in pilot.children)


def test_scheduled_drain_closes_schedule_span(catalog):
    s = Session(catalog, seed=3, config=TRACE_HERD)
    h = s.submit(HERD_SQL)
    assert "schedule" in h._trace.open_spans()
    s.drain()
    assert h._trace.open_spans() == []
    sched, = h._trace.find("schedule")
    assert sched.t1 is not None
    s.close()


def test_trace_exports_json_and_chrome(catalog):
    s = Session(catalog, seed=3, config=TRACE_SERIAL)
    h = s.sql(HERD_SQL)
    tree = h.trace()
    json.dumps(tree)  # JSON-able throughout
    assert tree["status"] == "ok" and tree["root"]["name"] == "query"
    assert tree["root"]["attrs"]["sql"] == HERD_SQL
    events = h.trace("chrome")
    json.dumps(events)
    assert all(e["ph"] == "X" and e["pid"] == h.query_id for e in events)
    assert {e["name"] for e in events} >= {"query", "pilot", "final"}
    # durations in microseconds, start times relative to the trace
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in events)


def test_failed_query_trace_closed_with_error_status(catalog):
    s = Session(catalog, seed=3, config=TRACE_HERD)
    h = s.submit("SELECT COUNT(*) AS n FROM not_a_table GROUP BY g")
    s.drain()
    assert h.status == "failed"
    assert h._trace.status == "error" and h._trace.open_spans() == []
    assert h.trace()["root"]["attrs"]["error"] == h.error
    s.close()


def test_mid_group_failure_traced_closes_spans_and_error_frame(
        catalog, monkeypatch):
    """Satellite: a mid-group failure under tracing must close the failed
    member's span tree AND emit its terminal ErrorFrame — stream() ends."""
    base = ("SELECT SUM(l_extendedprice) AS rev FROM lineitem "
            "WHERE l_shipdate < 2000 ")
    sqls = [base + f"ERROR {e}% CONFIDENCE 95%" for e in (8, 7, 6)]
    session = Session(catalog, seed=5, config=TRACE_HERD)
    real = PilotDB.prepare_final

    def flaky(self, q, spec, outcome, seed, shared=False):
        if abs(spec.error - 0.07) < 1e-12:
            raise RuntimeError("worker exploded mid-group")
        return real(self, q, spec, outcome, seed, shared=shared)

    monkeypatch.setattr(PilotDB, "prepare_final", flaky)
    handles = [session.submit(s, stream=True) for s in sqls]
    session.drain()
    assert [h.status for h in handles] == ["done", "failed", "done"]
    for h in handles:
        assert h._trace.open_spans() == []  # every tree closed
        frames = list(h.stream())           # terminates, never hangs
        assert frames[-1].terminal
    failed = handles[1]
    assert failed._trace.status == "error"
    assert isinstance(failed.frames()[-1], ErrorFrame)
    # siblings still completed with full span trees and pilot sharing
    assert {"pilot", "final"} <= set(handles[0]._trace.span_names())
    session.close()


def test_trace_mechanics_null_span_after_finish():
    tr = QueryTrace(0)
    with tr.span("a", k=1) as sp:
        assert tr.open_spans() == ["query", "a"]
        sp.set(extra=2)
    assert tr.open_spans() == ["query"]
    tr.finish("ok")
    assert tr.finished and tr.open_spans() == []
    # post-finish instrumentation degrades to no-ops
    assert tr.span("late") is trace_mod.NULL_SPAN
    before = tr.span_names()
    tr.record("late2")
    tr.finish("error")  # idempotent: first status wins
    assert tr.span_names() == before and tr.status == "ok"


def test_trace_span_error_status_on_exception():
    tr = QueryTrace(1)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("bad")
    sp, = tr.find("boom")
    assert sp.status == "error" and "RuntimeError: bad" in sp.attrs["error"]
    assert not sp.open


def test_trace_record_sits_at_its_start_and_live_spans_take_cpu_time():
    tr = QueryTrace(2)
    with tr.span("live") as live:
        pass
    rec = tr.record("past", duration_s=0.25, t_start=tr.t0 + 1.0, k=1)
    assert (rec.t0, rec.t1) == (tr.t0 + 1.0, tr.t0 + 1.25)
    assert rec.attrs == {"k": 1} and "cpu_ms" not in rec.attrs
    assert 0.0 <= live.attrs["cpu_ms"] <= live.duration_s * 1e3 + 1.0
    # a held span closes once, and a held span of an untraced member is
    # the no-op
    held = trace_mod.begin(tr, "held")
    held.close()
    held.close()
    assert tr.open_spans() == ["query"]
    assert trace_mod.begin(None, "x") is trace_mod.NULL_SPAN
    assert trace_mod.shared_span([None, None], "x") is trace_mod.NULL_SPAN


def test_shared_span_owner_live_members_retroactive():
    a, b, c = QueryTrace(0), QueryTrace(1), QueryTrace(2)
    holds = [trace_mod.begin(t, "scan") for t in (a, b, c)]
    with trace_mod.shared_span([None, a, b, c], "dispatch", batched=3) as sp:
        assert trace_mod.active() is a
        sp.set(bytes=8)
    assert trace_mod.active() is None
    for h in holds:
        h.close()
    own, = a.find("dispatch")
    assert own.attrs["owner"] is True and "cpu_ms" in own.attrs
    for t in (b, c):
        rec, = t.find("dispatch")
        assert (rec.t0, rec.t1) == (own.t0, own.t1)
        assert rec.attrs == {"owner": False, "batched": 3, "bytes": 8}
        scan, = t.find("scan")
        assert scan.children == [rec]  # under the member's held span


# ---------------------------------------------------------------------------
# Stage spans of a gateway drain: draw, dispatch, device wait, solve parts
# ---------------------------------------------------------------------------

Q6_SQL = ("SELECT SUM(l_extendedprice * l_discount) AS rev FROM lineitem "
          "WHERE l_shipdate BETWEEN {d} AND {e} "
          "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24 "
          "ERROR 10% CONFIDENCE 95%")
Q6_DAYS = range(100, 900, 100)  # 8 constants: 8 pilot subgroups


@pytest.fixture(scope="module")
def q6_catalog():
    # big enough blocks that Q6 samples (smaller ones fall back to exact)
    return tpch_catalog(scale_rows=2_000_000, block_rows=512, seed=0)


def _q6_drain(catalog, tracing):
    s = Session(catalog, seed=7, config=SessionConfig(
        tracing=tracing, result_cache_size=0))
    g = SqlGateway(s)
    tickets = [g.submit("c", Q6_SQL.format(d=d, e=d + 364)) for d in Q6_DAYS]
    out = g.run()
    s.close()
    return [out[t] for t in tickets]


@pytest.fixture(scope="module")
def q6_drains(q6_catalog):
    """One gateway drain of 8 constant-varied Q6 queries, untraced and
    traced: the stacked pilot and stacked final paths."""
    return _q6_drain(q6_catalog, False), _q6_drain(q6_catalog, True)


def _children(sp, name):
    return [c for c in sp.children if c.name == name]


def test_traced_gateway_q6_drain_bitwise(q6_drains):
    plain, traced = q6_drains
    for a, b in zip(plain, traced):
        assert a.status == b.status == "done" and b.fallback is None
        _assert_bitwise(b.answer, a.answer)
    # the drain took the stacked routes the spans are for
    pilots = [sp for h in traced for sp in h._trace.find("pilot")]
    finals = [sp for h in traced for sp in h._trace.find("final")]
    assert any(sp.attrs.get("batched", 0) >= 2 for sp in pilots)
    assert all(sp.attrs["batched"] is True for sp in finals)
    assert any(sc.attrs.get("batched", 0) >= 2
               for sp in finals for sc in _children(sp, "scan"))


def test_drain_trees_hold_every_scan_stage(q6_drains):
    for h in q6_drains[1]:
        tr = h._trace
        assert tr.finished and tr.open_spans() == []
        for stage in ("pilot", "final"):
            sp, = tr.find(stage)
            scans = [sc for sc in _children(sp, "scan")
                     if not sc.attrs.get("redrawn")]
            assert scans, f"{stage} holds no scan"
            for sc in scans:
                assert [c.name for c in sc.children] == [
                    "draw", "dispatch", "device_wait"]
                draw = sc.children[0]
                assert 0 < draw.attrs["n_blocks"] <= draw.attrs["n_phys"]
                assert sc.children[2].attrs["bytes"] > 0
        solve, = tr.find("rate_solve")
        assert [c.name for c in solve.children] == ["bounds", "solve", "pick"]


def test_retroactive_spans_lie_inside_their_owners(q6_drains):
    spans = [sp for h in q6_drains[1] for name in ("dispatch", "device_wait")
             for sp in h._trace.find(name)]
    owners = [sp for sp in spans if sp.attrs.get("owner") is True]
    copies = [sp for sp in spans if sp.attrs.get("owner") is False]
    assert owners and copies
    for c in copies:
        assert any(o.name == c.name and o.t0 <= c.t0 and c.t1 <= o.t1
                   and o.attrs["batched"] == c.attrs["batched"]
                   for o in owners)
    # spans are where the work ran: every child inside its parent
    for h in q6_drains[1]:
        for sp in h._trace.find("scan"):
            assert all(sp.t0 <= c.t0 and c.t1 <= sp.t1 for c in sp.children)


def test_cpu_time_never_exceeds_wall_time(q6_drains):
    seen = 0
    for h in q6_drains[1]:
        for name in set(h._trace.span_names()):
            for sp in h._trace.find(name):
                if "cpu_ms" in sp.attrs:
                    seen += 1
                    assert sp.attrs["cpu_ms"] <= sp.duration_s * 1e3 + 1.0
    assert seen


def test_final_blocks_times_block_bytes_is_final_scanned_bytes(
        q6_catalog, q6_drains):
    t = q6_catalog["lineitem"]
    for h in q6_drains[1]:
        final, = h._trace.find("final")
        assert final.attrs["n_blocks"] * t.block_rows * t.row_bytes() == \
            h.report.final_scanned_bytes == final.attrs["scanned_bytes"]


def test_shared_pilot_members_recorded_inside_the_leader_pilot(catalog):
    rt = Session(catalog, seed=11, config=TRACE_HERD)
    handles = [rt.submit(HERD_SQL) for _ in range(3)]
    rt.drain()
    lead, = [sp for h in handles for sp in h._trace.find("pilot")
             if sp.attrs["owner"]]
    for h in handles:
        sp, = h._trace.find("pilot")
        assert lead.t0 <= sp.t0 and sp.t1 <= lead.t1
    rt.close()


def test_live_spans_appear_as_profiler_annotations(catalog, tmp_path):
    import glob
    import jax
    from jax.profiler import ProfileData

    s = Session(catalog, seed=3, config=TRACE_SERIAL)
    s.sql(HERD_SQL)  # compile outside the profiled call
    jax.profiler.start_trace(str(tmp_path))
    try:
        h = s.sql(HERD_SQL.replace("< 24", "< 25"))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = {e.name for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events}
    assert {"pilotdb.pilot", "pilotdb.rate_solve", "pilotdb.final",
            "pilotdb.draw", "pilotdb.dispatch", "pilotdb.device_wait",
            "pilotdb.bounds", "pilotdb.solve", "pilotdb.pick"} <= names
    # retroactive records and the cross-thread schedule span are not live
    assert not {"pilotdb.parse", "pilotdb.lower"} & names
    assert h.fallback is None


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_instruments_get_or_create_and_kinds():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "help text")
    c.inc()
    c.inc(2)
    assert reg.counter("x_total").value == 3
    g = reg.gauge("x_now")
    g.set(1.5)
    assert g.value == 1.5
    hist = reg.histogram("x_seconds")
    hist.observe(0.003)
    hist.observe(0.3)
    assert hist.count == 2 and hist.max == 0.3
    with pytest.raises(TypeError):
        reg.gauge("x_total")  # kind mismatch is a bug, not a new metric


def test_registry_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(4)
    reg.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.5)
    reg.register_collector("cache", lambda: {"hits": 2, "nested": {"n": 1},
                                             "name": "dropme"})
    text = reg.to_text()
    assert "# TYPE req_total counter" in text
    assert "req_total 4" in text
    assert '# HELP req_total requests' in text
    assert 'lat_seconds_bucket{le="0.1"} 0' in text
    assert 'lat_seconds_bucket{le="1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_count 1" in text
    # collector snapshots flatten to path-joined gauges; strings dropped
    assert "cache_hits 2" in text and "cache_nested_n 1" in text
    assert "dropme" not in text
    assert text.endswith("\n")


def test_registry_collector_dies_with_owner():
    reg = MetricsRegistry()

    class Owner:
        pass

    o = Owner()
    reg.register_collector("mine", lambda: {"v": 1}, owner=o)
    assert reg.tree() == {"mine": {"v": 1}}
    del o
    assert reg.tree() == {}  # pruned at read, never a dead scrape


def test_session_collectors_match_sources(catalog):
    s = Session(catalog, seed=5)
    s.sql(HERD_SQL)
    tree = s.metrics.tree()
    info = s.compile_cache_info()
    assert tree["compile_cache"]["hits"] == info.hits
    assert tree["compile_cache"]["misses"] == info.misses
    rc = s.result_cache_info()
    assert tree["result_cache"]["hits"] == rc.hits
    assert tree["result_cache"]["bytes_used"] == rc.bytes_used
    assert tree["staged"]["tables"] == {}
    assert tree["runtime"]["queries_run"] == s.executor.queries_run
    assert tree["runtime"]["pilots_run"] == s.executor.pilots_run
    assert tree["audit"] == {"runs": 0, "violations": 0, "errors": 0,
                             "max_error_ratio": 0.0}
    s.close()


def test_drain_counters_land_in_registry(catalog):
    s = Session(catalog, seed=5, config=NOCACHE_CFG)
    s.submit(HERD_SQL)
    s.submit(HERD_SQL)
    s.drain()
    assert s.metrics.counter("pilotdb_drains_total").value == 1
    assert s.metrics.counter("pilotdb_drained_queries_total").value == 2
    assert s.metrics.histogram("pilotdb_drain_wall_seconds").count == 1
    s.close()


def test_percentiles_collector_counts_memo_hits(catalog):
    from repro.stats import percentile_cache_info
    s = Session(catalog, seed=5, config=NOCACHE_CFG)
    before = s.metrics.tree()["percentiles"]
    assert set(before) == {"hits", "misses", "size"}
    for _ in range(2):  # the second drain's solves find every percentile
        for _ in range(4):
            s.submit(HERD_SQL)
        s.drain()
    after = s.metrics.tree()["percentiles"]
    assert after == percentile_cache_info()
    assert after["hits"] >= before["hits"] + 4
    s.close()


def test_gateway_metrics_text_includes_gateway_counters(catalog):
    s = Session(catalog, seed=5)
    gw = SqlGateway(s)
    gw.submit("c0", HERD_SQL)
    gw.run()
    text = gw.metrics_text()
    assert f"{gw._collector_name}_requests 1" in text
    assert "compile_cache_hits" in text
    assert "result_cache_bytes_used" in text
    s.close()


# ---------------------------------------------------------------------------
# Guarantee auditor
# ---------------------------------------------------------------------------

def test_audit_mode_bit_identical_and_honest(catalog):
    plain = Session(catalog, seed=7, config=SERIAL_CFG).sql(HERD_SQL)
    audit_cfg = SessionConfig(async_workers=0, share_pilots=False,
                              result_cache_size=0, tracing=True, audit=True)
    s = Session(catalog, seed=7, config=audit_cfg)
    h = s.sql(HERD_SQL)
    # non-perturbation: the audited answer is the unaudited one, bitwise
    _assert_bitwise(h.answer, plain.answer)
    rec = h.audit_record
    assert rec is not None and rec.skipped is None
    assert rec.passed and rec.observed_error <= rec.promised_error
    assert 0.0 <= rec.error_ratio <= 1.0
    assert rec.provenance == "fresh"
    summ = s.auditor.summary()
    assert summ["runs"] == 1 and summ["violations"] == 0
    assert summ["max_error_ratio"] == rec.error_ratio
    # the ratio landed in the registry histogram + gauge
    assert s.metrics.histogram("pilotdb_audit_error_ratio").count == 1
    assert s.metrics.gauge(
        "pilotdb_audit_max_error_ratio").value == rec.error_ratio


def test_audit_skips_exact_answers_without_second_scan(catalog):
    s = Session(catalog, seed=7, config=SessionConfig(audit=True))
    h = s.sql("SELECT COUNT(*) AS n FROM lineitem")  # no spec: exact
    rec = h.audit_record
    assert rec.skipped == "answer is exact"
    assert rec.observed_error == 0.0 and rec.passed
    assert rec.exact_wall_s == 0.0  # no extra scan was paid
    assert s.auditor.summary()["skipped_exact"] == 1
    s.close()


def test_audit_grouped_checks_every_covered_group(catalog):
    cfg = SessionConfig(async_workers=0, share_pilots=False,
                        result_cache_size=0, audit=True)
    s = Session(catalog, seed=21, config=cfg)
    h = s.sql(GROUPED_SQL)
    rec = h.audit_record
    if h.fallback is None:
        assert rec.skipped is None
        assert rec.groups_checked >= 1
        assert rec.passed


def test_audit_never_raises_into_query_path(catalog, monkeypatch):
    cfg = SessionConfig(async_workers=0, share_pilots=False,
                        result_cache_size=0, audit=True)
    s = Session(catalog, seed=7, config=cfg)

    def broken_exact(self, q):
        raise RuntimeError("audit scan died")

    monkeypatch.setattr(PilotDB, "exact", broken_exact)
    h = s.sql(HERD_SQL)
    assert h.status == "done"  # the client still got its answer
    assert h.audit_record is None
    assert s.auditor.summary()["errors"] == 1
    assert s.metrics.counter("pilotdb_audit_errors_total").value == 1


def test_explain_reports_guarantee_and_audit(catalog):
    cfg = SessionConfig(async_workers=0, share_pilots=False,
                        result_cache_size=0, tracing=True, audit=True)
    s = Session(catalog, seed=7, config=cfg)
    h = s.sql(HERD_SQL)
    text = h.explain()
    assert f"Query {h.query_id}:" in text
    assert "ERROR 8% CONFIDENCE 95%" in text
    assert "provenance: fresh" in text
    assert "pilot: table=lineitem" in text
    assert "solved rates" in text
    assert "audit: observed=" in text and "[OK]" in text


def test_explain_failed_handle(catalog):
    s = Session(catalog, seed=3)
    h = s.failed_handle("SELEKT 1", "SqlSyntaxError: nope")
    text = h.explain()
    assert "FAILED" in text and "SqlSyntaxError" in text
    s.close()


def test_global_registry_exists():
    # the process-wide registry is importable and scrapes cleanly even
    # when empty
    assert isinstance(GLOBAL.to_text(), str)


# ---------------------------------------------------------------------------
# Prometheus exposition: HELP escaping + duplicate-name guard (satellite)
# ---------------------------------------------------------------------------

def test_prometheus_help_escaping_and_duplicate_guard():
    reg = MetricsRegistry()
    reg.counter("dup_hits", "line one\nline two with \\ backslash").inc(3)
    # a collector whose flattened path collides with the instrument name
    reg.register_collector("dup", lambda: {"hits": 99, "fresh": 7})
    # a collector colliding with a histogram's synthesized child series
    reg.histogram("lat_seconds", buckets=(0.1,)).observe(0.05)
    reg.register_collector("lat", lambda: {"seconds_count": 42})
    text = reg.to_text()
    # exposition stays valid: comments, or exactly "name value" lines
    for line in text.splitlines():
        assert line.startswith("#") or len(line.split()) == 2, line
    # HELP newline/backslash escaped into one comment line
    assert ("# HELP dup_hits line one\\nline two with \\\\ backslash"
            in text.splitlines())
    # the instrument wins the collision; the collector gauge is skipped
    dup_lines = [ln for ln in text.splitlines()
                 if ln.split()[0] == "dup_hits"]
    assert dup_lines == ["dup_hits 3"]
    # non-colliding collector keys still flatten
    assert "dup_fresh 7" in text
    # the histogram's _count child also guards against collector collisions
    count_lines = [ln for ln in text.splitlines()
                   if ln.split()[0] == "lat_seconds_count"]
    assert count_lines == ["lat_seconds_count 1"]


# ---------------------------------------------------------------------------
# Continuous telemetry: time-series, SLO, flight recorder, sampled tracing
# ---------------------------------------------------------------------------

import dataclasses as _dc

from repro.obs.events import FlightRecorder, replay, rebuild_timeseries
from repro.obs.slo import SloTarget
from repro.obs.timeseries import Ring, TemplateTimeSeries, quantile

TEMPLATE_SQL = ("SELECT SUM(l_extendedprice) AS rev FROM lineitem "
                "WHERE l_quantity < {} ERROR 10% CONFIDENCE 90%")


def _telemetry_cfg(tmp_path=None, **kw):
    base = dict(async_workers=4, result_cache_size=0, telemetry=True)
    if tmp_path is not None:
        base["flight_recorder"] = str(tmp_path / "events.jsonl")
    base.update(kw)
    return SessionConfig(**base)


def test_ring_and_quantile_mechanics():
    r = Ring(4)
    assert r.stats()["window"] == 0 and r.last() == 0.0
    for v in [5.0, 1.0, 3.0]:
        r.push(v)
    assert r.values() == [5.0, 1.0, 3.0] and r.last() == 3.0
    for v in [7.0, 9.0]:
        r.push(v)  # wraps: 5.0 evicted
    assert r.values() == [1.0, 3.0, 7.0, 9.0]
    assert r.last() == 9.0 and r.total == 5
    st = r.stats()
    assert st["p50"] == 3.0 and st["p99"] == 9.0 and st["max"] == 9.0
    assert quantile([], 0.5) == 0.0
    assert quantile([2.0, 1.0], 0.5) == 1.0
    with pytest.raises(ValueError):
        Ring(0)


def test_timeseries_store_eviction_and_slo_stats():
    ts = TemplateTimeSeries(window=8, max_templates=2)
    ts.record_delivery("a", latency_s=0.1, fallback=True)
    ts.record_delivery("b", latency_s=0.2)
    ts.record_delivery("a", latency_s=0.3)
    ts.record_delivery("c", latency_s=0.4)  # evicts b (LRU)
    assert set(ts.keys()) == {"a", "c"}
    st = ts.slo_stats("a")
    assert st["samples"] == 2 and st["fallback_rate"] == 0.5
    ts.record_audit("a", 0.7, passed=False)
    assert ts.slo_stats("a")["violation_rate"] == 1.0
    ts.record_drain(0.01, 0.05)
    ts.record_drain(None, None)
    snap = ts.snapshot()
    assert snap["drains"] == 2 and snap["ttff_s"]["window"] == 1
    json.dumps(snap)


def test_telemetry_off_by_default_and_bit_identical_on(catalog, tmp_path):
    plain = Session(catalog, seed=17, config=NOCACHE_CFG)
    assert plain.timeseries is None and plain.slo is None
    assert plain.recorder is None
    ph = [plain.submit(TEMPLATE_SQL.format(c)) for c in (18, 24, 30)]
    plain.drain()

    cfg = _telemetry_cfg(tmp_path, trace_sample=1.0,
                         slo_targets=(SloTarget(p95_latency_s=3600.0),))
    tele = Session(catalog, seed=17, config=cfg)
    th = [tele.submit(TEMPLATE_SQL.format(c)) for c in (18, 24, 30)]
    tele.drain()
    # full telemetry (time-series + SLO + recorder + sampled tracing)
    # changes no answer: bit-identical to the equal-seed plain session
    for a, b in zip(ph, th):
        _assert_bitwise(a.answer, b.answer)
    assert len(tele.timeseries.keys()) == 1  # one constant-varied template
    key = tele.timeseries.keys()[0]
    assert key == tele.template_key(TEMPLATE_SQL.format(18))
    s = tele.timeseries.series(key)
    assert s.deliveries == 3 and len(s.latency_s) == 3
    assert s.failures == 0
    tele.close()
    plain.close()


def test_timeseries_rides_registry_and_stats_payload(catalog, tmp_path):
    cfg = _telemetry_cfg(tmp_path)
    s = Session(catalog, seed=5, config=cfg)
    gw = SqlGateway(s)
    gw.submit("c0", HERD_SQL)
    gw.submit("c1", HERD_SQL)
    gw.run()
    tree = s.metrics.tree()
    assert tree["timeseries"]["enabled"] is True
    payload = gw.stats_payload()
    ts_section = payload["timeseries"]
    assert ts_section["enabled"] is True and ts_section["drains"] >= 1
    key = s.template_key(HERD_SQL)
    tmpl = ts_section["templates"][key]
    assert tmpl["deliveries"] == 2
    assert tmpl["latency_s"]["window"] == 2
    assert tmpl["latency_s"]["p95"] > 0
    assert tmpl["sql"] == HERD_SQL
    json.dumps(payload)
    # the quantiles flow through Prometheus exposition too
    text = gw.metrics_text()
    for line in text.splitlines():
        assert line.startswith("#") or len(line.split()) == 2, line
    assert "timeseries_enabled 1" in text
    assert f"timeseries_templates_{key}_deliveries 2" in text
    s.close()


def test_slo_breach_round_trip(catalog, tmp_path):
    """Injected impossible target -> breach counter + flight-recorder event
    + slo_report() entry (the acceptance round-trip)."""
    cfg = _telemetry_cfg(
        tmp_path, slo_targets=(SloTarget(p95_latency_s=1e-9),
                               SloTarget(max_fallback_rate=0.99)))
    s = Session(catalog, seed=5, config=cfg)
    gw = SqlGateway(s)
    gw.submit("c0", HERD_SQL)
    gw.run()
    assert s.metrics.counter("pilotdb_slo_breaches_total").value >= 1
    assert s.metrics.counter("pilotdb_slo_evaluations_total").value >= 2
    rows = gw.slo_report()
    breached = [r for r in rows if r["breached"]]
    assert breached and breached[0]["metric"] == "p95_latency_s"
    assert breached[0]["observed"] > breached[0]["target"]
    assert breached[0]["breaches_total"] >= 1
    # the generous fallback-rate target did NOT breach
    ok = [r for r in rows if r["metric"] == "max_fallback_rate"]
    assert ok and not ok[0]["breached"]
    summary = s.slo.summary()
    assert summary["enabled"] and summary["recent_breaches"]
    s.close()
    events = list(replay(str(tmp_path / "events.jsonl")))
    assert any(e["ev"] == "slo_breach"
               and e["metric"] == "p95_latency_s" for e in events)


def test_slo_targets_require_telemetry(catalog):
    with pytest.raises(ValueError, match="telemetry"):
        Session(catalog, seed=5, config=SessionConfig(
            slo_targets=(SloTarget(p95_latency_s=1.0),)))


def test_slo_per_template_rule_matches_only_its_template(catalog, tmp_path):
    cfg = _telemetry_cfg(tmp_path)
    s = Session(catalog, seed=5, config=cfg)
    other = "SELECT COUNT(*) AS n FROM lineitem"
    key = s.template_key(HERD_SQL)
    s.slo.set_target(template=key, p95_latency_s=1e-9)
    s.submit(HERD_SQL)
    s.submit(other)
    s.drain()
    rows = s.slo.report()
    assert all(r["template"] == key for r in rows)
    assert any(r["breached"] for r in rows)
    s.close()


def test_flight_recorder_event_schema_and_replay(catalog, tmp_path):
    path = tmp_path / "events.jsonl"
    cfg = _telemetry_cfg(tmp_path)
    s = Session(catalog, seed=5, config=cfg)
    s.submit(HERD_SQL)
    s.submit("SELECT COUNT(*) AS n FROM lineitem")  # exact: no pilot
    s.drain()
    s.close()
    events = list(replay(str(path)))
    kinds = [e["ev"] for e in events]
    assert kinds.count("submit") == 2
    assert kinds.count("deliver") == 2
    assert "pilot" in kinds and "rate_solve" in kinds and "final" in kinds
    # seq is monotone, every record stamped
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert all(e["t"] > 0 for e in events)
    deliver = [e for e in events if e["ev"] == "deliver"
               and e["template"] == s.template_key(HERD_SQL)]
    assert deliver
    d = deliver[0]
    assert d["latency_s"] > 0 and d["scanned_bytes"] > 0
    assert d["fallback"] is False and d["cached"] is False
    # offline rebuild reproduces the live store's per-template counters
    live = s.timeseries
    rebuilt = rebuild_timeseries(replay(str(path)))
    assert set(rebuilt.keys()) == set(live.keys())
    for key in live.keys():
        a, b = live.series(key), rebuilt.series(key)
        assert (a.deliveries, a.cached, a.shared, a.fused, a.fallbacks,
                a.failures) == (b.deliveries, b.cached, b.shared, b.fused,
                                b.fallbacks, b.failures)
        assert b.latency_s.values() == pytest.approx(
            a.latency_s.values(), abs=1e-6)


def test_flight_recorder_unwritable_target_never_raises(catalog):
    cfg = SessionConfig(
        async_workers=0, share_pilots=False, result_cache_size=0,
        flight_recorder="/nonexistent-dir-for-pilotdb-tests/events.jsonl")
    plain = Session(catalog, seed=7, config=SERIAL_CFG).sql(HERD_SQL)
    s = Session(catalog, seed=7, config=cfg)
    h = s.sql(HERD_SQL)  # the recorder drops, the query answers
    assert h.status == "done"
    _assert_bitwise(h.answer, plain.answer)
    assert s.recorder.stats()["dropped"] > 0
    assert s.recorder.stats()["emitted"] == 0
    s.close()  # close() with a never-opened file is a no-op


def test_flight_recorder_rotation_mid_drain(catalog, tmp_path):
    path = tmp_path / "tiny.jsonl"
    cfg = _telemetry_cfg(None, flight_recorder=str(path),
                         flight_recorder_max_bytes=1024,  # floor
                         flight_recorder_max_files=2)
    plain = Session(catalog, seed=13, config=NOCACHE_CFG)
    ph = [plain.submit(TEMPLATE_SQL.format(c)) for c in (18, 24, 30, 36)]
    plain.drain()
    s = Session(catalog, seed=13, config=cfg)
    th = [s.submit(TEMPLATE_SQL.format(c)) for c in (18, 24, 30, 36)]
    s.drain()
    for a, b in zip(ph, th):
        _assert_bitwise(a.answer, b.answer)
    stats = s.recorder.stats()
    assert stats["rotations"] >= 1 and stats["dropped"] == 0
    s.close()
    # the log's footprint is bounded; surviving records still replay and
    # the LIVE file's terminal events are intact
    assert path.exists() and (tmp_path / "tiny.jsonl.1").exists()
    events = list(replay(str(path)))
    assert events and all("ev" in e for e in events)
    plain.close()


def test_flight_recorder_mid_group_failure_logs_terminal_event(
        catalog, tmp_path, monkeypatch):
    """A mid-group member failure still logs its fail event; siblings'
    answers and deliver events are unaffected, nothing raises."""
    path = tmp_path / "events.jsonl"
    base = ("SELECT SUM(l_extendedprice) AS rev FROM lineitem "
            "WHERE l_shipdate < 2000 ")
    sqls = [base + f"ERROR {e}% CONFIDENCE 95%" for e in (8, 7, 6)]
    cfg = _telemetry_cfg(tmp_path)
    s = Session(catalog, seed=5, config=cfg)
    real = PilotDB.prepare_final

    def flaky(self, q, spec, outcome, seed, shared=False):
        if abs(spec.error - 0.07) < 1e-12:
            raise RuntimeError("worker exploded mid-group")
        return real(self, q, spec, outcome, seed, shared=shared)

    monkeypatch.setattr(PilotDB, "prepare_final", flaky)
    handles = [s.submit(x) for x in sqls]
    s.drain()
    assert [h.status for h in handles] == ["done", "failed", "done"]
    key = s.template_key(sqls[0])
    series = s.timeseries.series(key)
    assert series.deliveries == 3 and series.failures == 1
    s.close()
    events = list(replay(str(path)))
    fails = [e for e in events if e["ev"] == "fail"]
    assert len(fails) == 1
    assert fails[0]["qid"] == handles[1].query_id
    assert "worker exploded" in fails[0]["error"]
    assert sum(1 for e in events if e["ev"] == "deliver") == 2


def test_trace_sampling_deterministic_and_content_derived(catalog):
    cuts = list(range(10, 40, 3))
    cfg = SessionConfig(async_workers=0, share_pilots=False,
                        result_cache_size=0, trace_sample=0.5)

    def sampled_set(seed):
        s = Session(catalog, seed=seed, config=cfg)
        out = {}
        for c in cuts:
            h = s.sql(TEMPLATE_SQL.format(c))
            out[c] = h._trace_sampled
            # sampling implies a trace (tracing flag is off); unsampled
            # queries carry none — today's path byte for byte
            assert (h._trace is not None) == h._trace_sampled
        s.close()
        return out

    first = sampled_set(23)
    again = sampled_set(23)
    assert first == again  # equal seeds sample the IDENTICAL query set
    assert any(first.values()) and not all(first.values())  # p=0.5 mixes
    other = sampled_set(24)
    assert other != first  # the decision hashes the session seed too


def test_trace_sample_bounds_and_edges(catalog):
    with pytest.raises(ValueError, match="trace_sample"):
        Session(catalog, seed=3, config=SessionConfig(trace_sample=1.5))
    s0 = Session(catalog, seed=3, config=SessionConfig(
        async_workers=0, share_pilots=False, result_cache_size=0,
        trace_sample=0.0))
    assert s0.sql(HERD_SQL)._trace is None
    s1 = Session(catalog, seed=3, config=SessionConfig(
        async_workers=0, share_pilots=False, result_cache_size=0,
        trace_sample=1.0))
    h = s1.sql(HERD_SQL)
    assert h._trace_sampled and h._trace is not None
    # the sampled span tree landed in the session's recent-traces ring
    assert len(s1.recent_traces) == 1
    assert s1.recent_traces[0]["query_id"] == h.query_id
    s0.close()
    s1.close()


def test_sampled_traces_land_in_flight_recorder(catalog, tmp_path):
    path = tmp_path / "events.jsonl"
    cfg = SessionConfig(async_workers=0, share_pilots=False,
                        result_cache_size=0, trace_sample=1.0,
                        flight_recorder=str(path))
    s = Session(catalog, seed=3, config=cfg)
    h = s.sql(HERD_SQL)
    s.close()
    events = list(replay(str(path)))
    traces = [e for e in events if e["ev"] == "trace"]
    assert len(traces) == 1
    tree = traces[0]["trace"]
    assert tree["query_id"] == h.query_id
    assert tree["root"]["name"] == "query"
    subs = [e for e in events if e["ev"] == "submit"]
    assert subs and subs[0]["sampled"] is True


def test_audit_feeds_timeseries_and_recorder(catalog, tmp_path):
    path = tmp_path / "events.jsonl"
    cfg = SessionConfig(async_workers=0, share_pilots=False,
                        result_cache_size=0, telemetry=True, audit=True,
                        flight_recorder=str(path))
    s = Session(catalog, seed=7, config=cfg)
    h = s.sql(HERD_SQL)
    rec = h.audit_record
    assert rec is not None and rec.skipped is None
    key = s.template_key(HERD_SQL)
    series = s.timeseries.series(key)
    assert series.audited == 1
    assert series.error_ratio.last() == pytest.approx(rec.error_ratio)
    assert series.audit_violations == (0 if rec.passed else 1)
    s.close()
    audits = [e for e in list(replay(str(path))) if e["ev"] == "audit"]
    assert len(audits) == 1
    assert audits[0]["passed"] == rec.passed
    assert audits[0]["ratio"] == pytest.approx(rec.error_ratio, abs=1e-6)


def test_fused_provenance_in_explain_and_timeseries(catalog):
    """Satellite: audit-mode + fused_taqa interplay — explain() reports the
    fused span, provenance gains +fused, the time-series counts the fused
    delivery, and the audit still passes on the fused answer."""
    cfg = SessionConfig(async_workers=0, result_cache_size=0,
                        telemetry=True, audit=True, tracing=True,
                        fused_taqa=True)
    s = Session(catalog, seed=7, config=cfg)
    h = s.submit(HERD_SQL)
    s.drain()
    assert h.status == "done"
    fused_spans = h._trace.find("fused")
    text = h.explain()
    if fused_spans and fused_spans[0].attrs.get("engaged"):
        assert "+fused" in provenance_of(h)
        assert "fused: engaged" in text
        key = s.template_key(HERD_SQL)
        assert s.timeseries.series(key).fused == 1
    elif fused_spans:
        assert "fused: attempted" in text
    rec = h.audit_record
    assert rec is not None and rec.passed
    s.close()


def test_dashboard_renders_self_contained_html(catalog, tmp_path):
    from repro.serve import render_dashboard, write_dashboard
    cfg = _telemetry_cfg(tmp_path, trace_sample=1.0,
                         slo_targets=(SloTarget(p95_latency_s=1e-9),))
    s = Session(catalog, seed=5, config=cfg)
    s.submit(HERD_SQL)
    s.submit(HERD_SQL)
    s.drain()
    html_doc = render_dashboard(s, title="test run")
    assert html_doc.startswith("<!doctype html>")
    assert "test run" in html_doc
    key = s.template_key(HERD_SQL)
    assert key in html_doc                      # template table row
    assert "BREACHED" in html_doc               # the impossible SLO
    assert "svg" in html_doc                    # sparkline present
    assert "pilotdb_slo_breaches_total" in html_doc  # registry text
    assert "http://" not in html_doc and "https://" not in html_doc
    out = write_dashboard(str(tmp_path / "dash.html"), s)
    assert out is not None
    assert (tmp_path / "dash.html").read_text(
        encoding="utf-8").startswith("<!doctype html>")
    # write failures degrade to None, never raise
    assert write_dashboard("/nonexistent-dir-for-pilotdb-tests/d.html",
                           s) is None
    # a telemetry-off session still renders (empty-state sections)
    plain = Session(catalog, seed=5)
    doc = render_dashboard(plain)
    assert "Telemetry is off" in doc
    plain.close()
    s.close()
