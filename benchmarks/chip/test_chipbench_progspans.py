"""The readers of the program's stage spans (``chipbench/progspans.py``):
hand counts on synthetic windows, and the line-up of span times with the
profiler's clock on a trace recorded on the CPU."""

import glob
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import pytest  # noqa: E402

from chipbench import cell, devtrace, progspans, spec  # noqa: E402
from chipbench import traffic as gen  # noqa: E402


def node(name, t, d, *children, **attrs):
    return {"name": name, "t_start_s": t, "duration_s": d, "attrs": attrs,
            "children": list(children)}


def scan(t, draw, dispatch, wait, owner=True):
    return node("scan", t, draw + dispatch + wait,
                node("draw", t, draw, n_blocks=3, n_phys=4),
                node("dispatch", t + draw, dispatch, owner=owner),
                node("device_wait", t + draw + dispatch, wait, owner=owner,
                     bytes=8))


def rec(t_submit, *children, status="done"):
    root = node("query", 0.0, 1.0, *children)
    handle = types.SimpleNamespace(status=status, cached=False,
                                   t_submit=t_submit, _trace=True,
                                   trace=lambda: {"root": root})
    return cell.Rec(gen.Query(0, {}, ""), 0.0, 1.0, handle)


@pytest.fixture(scope="module")
def q6_cell():
    return spec.load_cell("tpch-sf20-uniform.q6-slider")


def read(name, w):
    return spec.metric_reader(name)(w)


def test_span_readers_by_hand(q6_cell):
    a = rec(0.0,
            node("pilot", 0.0, 0.01, scan(0.0, 0.001, 0.002, 0.003)),
            node("rate_solve", 0.01, 0.005, cpu_ms=1.5),
            node("final", 0.02, 0.01, scan(0.02, 0.0005, 0.004, 0.001)))
    b = rec(0.0,
            node("pilot", 0.0, 0.01,
                 scan(0.0, 0.002, 0.002, 0.003, owner=False)),
            node("rate_solve", 0.01, 0.005, cpu_ms=2.5),
            node("rate_solve", 0.02, 0.005, cpu_ms=0.5))
    failed = rec(0.0, node("pilot", 0.0, 1.0, scan(0.0, 1.0, 1.0, 1.0)),
                 status="failed")
    w = cell.Window(q6_cell, [a, b, failed], 0.0)
    # draw + dispatch: a 1 + 2 + 0.5 + 4 ms, b 2 ms (its copy of the
    # shared dispatch skipped), over 2 answered queries
    assert read("scan_prep_ms", w) == pytest.approx(9.5 / 2)
    assert read("device_wait_ms", w) == pytest.approx((3 + 1) / 2)
    assert read("rate_solve_cpu_ms", w) == pytest.approx((1.5 + 3.0) / 2)


def test_idle_share_under_rate_solve_by_hand(q6_cell):
    # profiler clock = perf_counter in ns - 1e11; query k is submitted at
    # 100 s + 10 us k, inside its bench.submit annotation
    subs = [["bench.submit", k * 1e4 - 100, 500] for k in range(4)]
    recs = [rec(100.0, node("rate_solve", 3e-4, 2e-4)),      # 3e5..5e5 ns
            rec(100.00001, node("rate_solve", 5.9e-4, 1e-4)),  # 6e5..7e5
            rec(100.00002, node("rate_solve", 3.3e-4, 7e-5)),  # inside a's
            rec(100.00003, node("rate_solve", 1.5e-4, 1e-4),   # not answered
                status="failed")]
    w = cell.Window(q6_cell, recs, 0.0)
    w.trace = {"device": {"/device:TPU:0": [["f", "jit_run", 0.0, 1e5],
                                            ["f", "jit_run", 4e5, 1e5]]},
               "host": [["bench.window", 0.0, 1e6]] + subs}
    w.t0_ns, w.t1_ns = 0.0, 1e6
    assert progspans.clock_offset_ns(w) == pytest.approx(-1e11 - 100)
    # idle: 1e5..4e5 and 5e5..1e6 (8e5 ns); under a rate_solve: 3e5..4e5
    # and 6e5..7e5, each 100 ns early by the offset's estimate
    assert read("idle_solving_pct", w) == pytest.approx(
        100 * (2e5 + 100) / 8e5)


def test_nothing_to_read_gives_none(q6_cell):
    # a program whose spans carry no stage children and no CPU time
    old = rec(0.0, node("pilot", 0.0, 0.01, node("scan", 0.0, 0.01)),
              node("rate_solve", 0.01, 0.005))
    w = cell.Window(q6_cell, [old], 0.0)
    for name in ("scan_prep_ms", "device_wait_ms", "rate_solve_cpu_ms",
                 "idle_solving_pct"):
        assert read(name, w) is None
    w.trace = {"device": {"/device:TPU:0": [["f", "jit_run", 0.0, 1.0]]},
               "host": []}
    w.t1_ns = 10.0
    assert read("idle_solving_pct", w) is None  # no bench.submit to line up
    w.trace["host"] = [["bench.submit", 0.0, 1.0]] * 2
    assert progspans.clock_offset_ns(w) is None  # 2 annotations, 1 query


def test_overlap_of_interval_lists():
    assert progspans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert progspans.overlap_ns([(0, 1)], [(2, 3)]) == 0
    assert progspans.overlap_ns([], [(0, 1)]) == 0


def test_spans_line_up_with_the_program_annotations_on_cpu(tmp_path):
    """A traced gateway drain under jax.profiler on the CPU: the program's
    live spans write pilotdb.* annotations, which ``devtrace.extract``
    keeps out of ``host``; each rate_solve span, moved to the profiler's
    clock by the bench.submit line-up, starts where its annotation does."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    from repro.api import Session, SessionConfig
    from repro.engine.datagen import tpch_catalog
    from repro.serve.sql_gateway import SqlGateway

    sql = ("SELECT SUM(l_extendedprice * l_discount) AS rev FROM lineitem "
           "WHERE l_shipdate BETWEEN {} AND {} AND l_quantity < 24 "
           "ERROR 10% CONFIDENCE 95%")
    s = Session(tpch_catalog(scale_rows=400_000, block_rows=128, seed=0),
                seed=5, config=SessionConfig(tracing=True))
    g = SqlGateway(s)
    days = [100, 400, 700, 1000]
    for d in days:  # compile outside the profiled window
        g.submit("c", sql.format(d + 1, d + 365))
    g.run()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            recs = []
            for d in days:
                with TraceAnnotation("bench.submit"):
                    recs.append((g.submit("c", sql.format(d, d + 364)), d))
            with TraceAnnotation("bench.run"):
                out = g.run()
    finally:
        jax.profiler.stop_trace()
    s.close()
    trace = devtrace.extract(str(tmp_path))
    assert {n for n, _, _ in trace["host"]} == {
        "bench.window", "bench.submit", "bench.run"}
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    raw = sorted(e.start_ns for p in ProfileData.from_file(path).planes
                 for ln in p.lines for e in ln.events
                 if e.name == "pilotdb.rate_solve")
    w = cell.Window(spec.load_cell("tpch-sf20-uniform.q6-slider"),
                    [cell.Rec(gen.Query(0, {}, ""), 0.0, time.perf_counter(),
                              out[t]) for t, _ in recs], 0.0)
    w.trace = trace
    offset = progspans.clock_offset_ns(w)
    assert offset is not None
    mapped = sorted(a for a, _ in progspans.span_intervals(
        w, "rate_solve", offset))
    assert len(mapped) == len(raw) == len(days)
    # the span opens a few microseconds before its annotation; the offset
    # is read from the submissions, some tens of microseconds late
    for m, r in zip(mapped, raw):
        assert abs(m - r) < 1e6
