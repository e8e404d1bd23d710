"""The XLA route's exact answers, summed block by block.

An exact scan of a large table adds every row; one float32 accumulator per
group drifts by about 1e-4 relative over a few million rows.  The route
sums each block's rows in float32 and adds the per-block partials as a
balanced tree, so its answers sit within a few float32 roundings of the
plain reference: per-block float32 sums added in float64 (numpy, below).
The exact scan also has a jit name of its own, and TAQA's exact answers
carry an ``exact`` span and counters by reason and by route.  With the
kernels on, an exact scan of Q6's shape takes the ``pallas_exact`` route
(the exact-scan kernel); other exact shapes keep the XLA route.
"""


import numpy as np
import pytest

from repro.api import Session, SessionConfig
from repro.core import CompositeAgg, PilotDB, Query
from repro.engine import logical as L
from repro.engine import physical
from repro.engine.datagen import tpch_catalog
from repro.engine.executor import Executor
from repro.engine.expr import And, Col
from repro.engine.table import BlockTable
from repro.obs import QueryTrace
from repro.obs import trace as trace_mod

BR = 1024
ROWS = 4 * 1024 * 1024 - 300   # 4,096 blocks, the last one partly filled
GROUPS = 3
PRED = And(Col("l_shipdate").between(100, 2000), Col("l_quantity") < 40)


@pytest.fixture(scope="module")
def data():
    """Q6's columns over a few million rows, sorted on l_shipdate as a
    clustered lineitem is."""
    rng = np.random.default_rng(16)
    qty = rng.integers(1, 51, ROWS).astype(np.float32)
    return {
        "l_quantity": qty,
        "l_extendedprice": (qty * rng.uniform(900, 1100, ROWS)).astype(
            np.float32),
        "l_discount": rng.integers(0, 11, ROWS).astype(np.float32)
        / np.float32(100),
        "l_shipdate": np.sort(rng.integers(0, 2526, ROWS)).astype(np.int32),
        "l_returnflag": rng.integers(0, GROUPS, ROWS).astype(np.int32),
    }


@pytest.fixture(scope="module")
def catalog(data):
    return {"lineitem": BlockTable.from_numpy("lineitem", data, BR)}


def _plan(group_by=None, max_groups=1):
    return L.Aggregate(
        child=L.Filter(L.Scan("lineitem"), PRED),
        aggs=(L.AggSpec("sum", Col("l_extendedprice") * Col("l_discount"),
                        "rev"),
              L.AggSpec("count", None, "cnt")),
        group_by=group_by, max_groups=max_groups)


def _reference(data, group_by):
    """(revenue, count) per group: float32 sums within each 1024-row block,
    the blocks added in float64."""
    keep = ((data["l_shipdate"] >= 100) & (data["l_shipdate"] <= 2000)
            & (data["l_quantity"] < 40))
    rev = np.where(keep, data["l_extendedprice"] * data["l_discount"],
                   np.float32(0))
    gid = data[group_by] if group_by else np.zeros(ROWS, np.int32)
    pad = -ROWS % BR
    out = np.zeros((2, GROUPS if group_by else 1))
    for g in range(out.shape[1]):
        r = np.pad(np.where(gid == g, rev, np.float32(0)), (0, pad))
        out[0, g] = r.reshape(-1, BR).sum(axis=1, dtype=np.float32).sum(
            dtype=np.float64)
        out[1, g] = (keep & (gid == g)).sum()
    return out


def test_exact_q6_matches_blockwise_float64_reference(data, catalog):
    res = Executor(catalog).execute(_plan())
    want = _reference(data, None)
    np.testing.assert_allclose(res.values[0], want[0], rtol=1e-6)
    np.testing.assert_array_equal(res.group_counts, want[1])


@pytest.mark.parametrize("budget", [None, 3 * 3 * 4 * 1023 + 5],
                         ids=["under_budget", "past_budget"])
def test_exact_grouped_matches_reference_per_group(data, catalog,
                                                   monkeypatch, budget):
    """Past the budget the partials of 1,024 blocks fit at once: the 4,096
    blocks go through in four runs of 1,023 and a last one clamped to the
    table's end."""
    if budget is not None:
        monkeypatch.setattr(physical, "PARTIALS_BUDGET_BYTES", budget)
    res = Executor(catalog).execute(_plan("l_returnflag", GROUPS))
    want = _reference(data, "l_returnflag")
    np.testing.assert_allclose(res.values[0], want[0], rtol=1e-6)
    np.testing.assert_array_equal(res.group_counts, want[1])


@pytest.mark.parametrize("sampled", [False, True], ids=["exact", "sampled"])
def test_exact_scan_has_its_own_jit_name(sampled):
    cat = tpch_catalog(20_000, 64, seed=3)
    ex = Executor(cat)
    plan = _plan()
    if sampled:
        plan = L.rewrite_scans(
            plan, {"lineitem": L.SampleClause("block", 0.5, seed=4)})
    ex.execute(plan)
    (compiled,) = ex.physical.executables()
    module = compiled.fn.lower(compiled.last_args).as_text()
    if sampled:
        assert compiled.fn.__name__ == "run"
        assert "@jit_run_exact" not in module and "@jit_run" in module
    else:
        assert compiled.fn.__name__ == "run_exact"
        assert "@jit_run_exact" in module


def test_exact_span_carries_reason_blocks_and_bytes():
    cat = tpch_catalog(20_000, 64, seed=3)
    db = PilotDB(Executor(cat), large_table_rows=50_000)
    tr = QueryTrace(1)
    token = trace_mod.activate(tr)
    try:
        ans = db.exact(Query(
            child=L.Filter(L.Scan("lineitem"), PRED),
            aggs=(CompositeAgg("rev", "sum",
                               Col("l_extendedprice") * Col("l_discount")),)))
    finally:
        trace_mod.deactivate(token)
    (sp,) = tr.find("exact")
    assert sp.attrs["reason"] == "requested exact"
    assert sp.attrs["n_blocks"] == cat["lineitem"].num_blocks
    assert sp.attrs["bytes"] == ans.report.final_scanned_bytes > 0
    assert [c.name for c in sp.children] == ["scan"]
    assert sp.attrs["route"] == "xla_gather"
    assert db.exact_answer_counts() == {"requested exact": 1}
    assert db.exact_route_counts() == {"xla_gather": 1}


def test_exact_answers_counted_by_reason_in_the_collector():
    """A table under the sampling cutoff is answered exactly; the
    collector counts each answer under its reason."""
    cat = tpch_catalog(20_000, 64, seed=3)
    s = Session(cat, seed=5, config=SessionConfig(
        async_workers=0, result_cache_size=0, tracing=True))
    assert s.metrics.tree()["exact_answers"] == {}
    sql = ("SELECT SUM(l_extendedprice * l_discount) AS rev FROM lineitem "
           "WHERE l_quantity < {} ERROR 5% CONFIDENCE 95%")
    handles = [s.submit(sql.format(q)) for q in (20, 24, 30)]
    s.drain()
    for h in handles:
        assert h.result().report.fallback == "no large table to sample"
        (sp,) = [x for x in _walk(h.trace()["root"]) if x["name"] == "exact"]
        assert sp["attrs"]["reason"] == "no large table to sample"
    assert s.metrics.tree()["exact_answers"] == {
        "no large table to sample": 3}
    assert "exact_answers_no_large_table_to_sample 3" in s.metrics.to_text()
    s.close()


# -- the pallas_exact route ----------------------------------------------------

def _route_catalog():
    """16 blocks of 1,024 rows: a multiple of 128 rows, as the exact-scan
    kernel needs."""
    return tpch_catalog(16_384, 1024, seed=3)


def _grouped_plan():
    return _plan("l_returnflag", GROUPS)


def _join_plan():
    return L.Aggregate(
        child=L.Filter(L.Join(L.Scan("lineitem"), L.Scan("orders"),
                              "l_orderkey", "o_orderkey"),
                       Col("o_orderdate") < 900),
        aggs=(L.AggSpec("sum", Col("l_extendedprice"), "rev"),))


def _sampled(plan):
    return L.rewrite_scans(
        plan, {"lineitem": L.SampleClause("block", 0.5, seed=4)})


@pytest.mark.parametrize("name,plan,route", [
    ("exact_q6", _plan(), "pallas_exact"),
    ("exact_grouped", _grouped_plan(), "xla_gather"),
    ("exact_join", _join_plan(), "xla_gather"),
    ("sampled_q6", _sampled(_plan()), "pallas_filtered"),
])
def test_pallas_mode_routes_by_plan_shape(name, plan, route):
    """With the kernels on, an exact Q6 scan takes the exact-scan kernel;
    grouped and join exact scans keep the XLA route; a sampled final keeps
    the sampled-block kernel.  The exact scan keeps its jit name."""
    ex = Executor(_route_catalog(), kernel_mode="pallas")
    res = ex.execute(plan)
    assert res.route == route
    (compiled,) = ex.physical.executables()
    assert compiled.route == route
    exact = name.startswith("exact")
    assert compiled.fn.__name__ == ("run_exact" if exact else "run")


@pytest.mark.parametrize("kernel_mode,route", [
    ("xla", "xla_gather"), ("pallas", "pallas_exact")])
def test_exact_span_and_collector_name_the_route(kernel_mode, route):
    cat = _route_catalog()
    s = Session(cat, seed=5, config=SessionConfig(
        async_workers=0, result_cache_size=0, tracing=True,
        kernel_mode=kernel_mode))
    assert s.metrics.tree()["exact_routes"] == {}
    sql = ("SELECT SUM(l_extendedprice * l_discount) AS rev FROM lineitem "
           "WHERE l_shipdate BETWEEN 100 AND {} AND l_quantity < 24 "
           "ERROR 5% CONFIDENCE 95%")
    handles = [s.submit(sql.format(d)) for d in (900, 1200)]
    s.drain()
    for h in handles:
        assert h.result().report.fallback == "no large table to sample"
        (sp,) = [x for x in _walk(h.trace()["root"]) if x["name"] == "exact"]
        assert sp["attrs"]["route"] == route
    assert s.db.exact_route_counts() == {route: 2}
    assert s.metrics.tree()["exact_routes"] == {route: 2}
    assert f"exact_routes_{route} 2" in s.metrics.to_text()
    # the reasons are counted as before, beside the routes
    assert s.metrics.tree()["exact_answers"] == {
        "no large table to sample": 2}
    s.close()


def test_valid_bytes_made_once_when_workers_meet_it_together():
    """The pool's workers reach a table's first exact scan together: every
    one gets the same int8 copy of ``valid``, made once."""
    import sys
    import threading

    tab = _route_catalog()["lineitem"]
    n = 16
    start = threading.Barrier(n)
    got = [None] * n

    def work(i):
        start.wait(timeout=30)
        got[i] = tab.valid_bytes()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len({id(g) for g in got}) == 1
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(tab.valid).astype(np.int8))


def _walk(span):
    yield span
    for c in span["children"]:
        yield from _walk(c)
