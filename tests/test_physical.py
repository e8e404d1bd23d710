"""Compiled physical layer vs legacy eager path vs Pallas kernels.

Parametrized property tests (hypothesis is unavailable in the CPU container)
asserting the three lowerings of the same logical plan agree bit-for-bit-ish
(atol) on grouped sums/counts and per-block pilot statistics across group
counts, block sizes, and filter selectivities including 0% and 100% — plus
the compile-cache and empty-sample contracts of the physical layer.
"""

import numpy as np
import pytest

from repro.core import CompositeAgg, ErrorSpec, PilotDB, Query
from repro.engine import logical as L
from repro.engine.datagen import tpch_catalog
from repro.engine.executor import EmptySampleError, Executor
from repro.engine.expr import And, Col
from repro.engine.physical import ScanRuntime, plan_signature

BR = 64


@pytest.fixture(scope="module")
def catalog():
    return tpch_catalog(6_000, BR, seed=0)  # 94 lineitem blocks: tiny kernels


@pytest.fixture(scope="module")
def executors(catalog):
    return {
        "compiled": Executor(catalog),
        "pallas": Executor(catalog, kernel_mode="pallas"),
        "eager": Executor(catalog, use_compiled=False),
    }


# Selectivity knobs: l_shipdate is uniform on [0, 2526).
SELECTIVITY_PREDS = {
    "0%": Col("l_shipdate") < -1,
    "50%": Col("l_shipdate") < 1263,
    "100%": Col("l_shipdate") < 99_999,
}

Q6_PRED = And(Col("l_shipdate").between(100, 1500),
              And(Col("l_discount").between(0.02, 0.08), Col("l_quantity") < 24))


def _plan(pred=None, group_by=None, max_groups=1):
    child = L.Scan("lineitem") if pred is None else L.Filter(L.Scan("lineitem"), pred)
    return L.Aggregate(
        child=child,
        aggs=(L.AggSpec("sum", Col("l_extendedprice") * Col("l_discount"), "rev"),
              L.AggSpec("count", None, "cnt"),
              L.AggSpec("avg", Col("l_quantity"), "avg_qty")),
        group_by=group_by, max_groups=max_groups)


# -- compiled vs eager: full queries ------------------------------------------

@pytest.mark.parametrize("sel", list(SELECTIVITY_PREDS))
@pytest.mark.parametrize("groups", [None, ("l_returnflag", 3)])
def test_compiled_matches_eager_exact(executors, sel, groups):
    gb, mg = groups if groups else (None, 1)
    plan = _plan(SELECTIVITY_PREDS[sel], group_by=gb, max_groups=mg)
    rc = executors["compiled"].execute(plan)
    re = executors["eager"].execute(plan)
    np.testing.assert_allclose(rc.values, re.values, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(rc.group_counts, re.group_counts)
    assert rc.scanned_bytes == re.scanned_bytes


@pytest.mark.parametrize("rate", [0.1, 0.5])
@pytest.mark.parametrize("method", ["block", "row"])
def test_compiled_matches_eager_sampled(executors, rate, method):
    plan = L.rewrite_scans(_plan(SELECTIVITY_PREDS["50%"]),
                           {"lineitem": L.SampleClause(method, rate, seed=9)})
    rc = executors["compiled"].execute(plan)
    re = executors["eager"].execute(plan)
    np.testing.assert_allclose(rc.values, re.values, rtol=1e-4, atol=1e-4)
    assert rc.scanned_bytes == re.scanned_bytes
    # identical host-side TABLESAMPLE draw
    ic, ie = rc.sample_infos["lineitem"], re.sample_infos["lineitem"]
    assert ic.n_sampled_blocks == ie.n_sampled_blocks
    assert ic.n_sampled_rows == ie.n_sampled_rows


@pytest.mark.parametrize("block_rows", [32, 200])
def test_compiled_matches_eager_across_block_sizes(block_rows):
    cat = tpch_catalog(4_000, block_rows, seed=2)
    rc = Executor(cat).execute(_plan(SELECTIVITY_PREDS["50%"]))
    re = Executor(cat, use_compiled=False).execute(_plan(SELECTIVITY_PREDS["50%"]))
    np.testing.assert_allclose(rc.values, re.values, rtol=1e-5, atol=1e-5)


# -- compiled vs eager: pilot statistics --------------------------------------

@pytest.mark.parametrize("sel", list(SELECTIVITY_PREDS))
@pytest.mark.parametrize("groups", [None, ("l_returnflag", 3)])
def test_pilot_compiled_matches_eager(executors, sel, groups):
    gb, mg = groups if groups else (None, 1)
    plan = _plan(SELECTIVITY_PREDS[sel], group_by=gb, max_groups=mg)
    sc = executors["compiled"].execute_pilot(plan, "lineitem", 0.2, seed=3)
    se = executors["eager"].execute_pilot(plan, "lineitem", 0.2, seed=3)
    assert sc.block_sums.shape == se.block_sums.shape
    np.testing.assert_allclose(sc.block_sums, se.block_sums, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(sc.group_present, se.group_present)
    assert sc.scanned_bytes == se.scanned_bytes


def test_pilot_pair_sums_compiled_matches_eager(executors):
    plan = L.Aggregate(
        child=L.Join(L.Scan("lineitem"), L.Scan("orders"), "l_orderkey", "o_orderkey"),
        aggs=(L.AggSpec("sum", Col("l_extendedprice"), "s"),))
    sc = executors["compiled"].execute_pilot(plan, "lineitem", 0.3, seed=5,
                                             pair_tables=("orders",))
    se = executors["eager"].execute_pilot(plan, "lineitem", 0.3, seed=5,
                                          pair_tables=("orders",))
    assert set(sc.pair_sums) == {"orders"} == set(se.pair_sums)
    np.testing.assert_allclose(sc.pair_sums["orders"], se.pair_sums["orders"],
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(sc.block_sums, se.block_sums, rtol=1e-4, atol=1e-3)


# -- Pallas kernel routes vs the XLA twin -------------------------------------

def test_pallas_filtered_route_matches_xla(executors):
    plan = L.Aggregate(child=L.Filter(L.Scan("lineitem"), Q6_PRED),
                       aggs=(L.AggSpec("sum", Col("l_extendedprice") * Col("l_discount"), "rev"),
                             L.AggSpec("count", None, "cnt")))
    sp = executors["pallas"].execute_pilot(plan, "lineitem", 0.3, seed=3)
    sx = executors["compiled"].execute_pilot(plan, "lineitem", 0.3, seed=3)
    np.testing.assert_allclose(sp.block_sums, sx.block_sums, rtol=1e-4, atol=1e-4)
    routes = {c.route for c in executors["pallas"].physical._cache.values()}
    assert "pallas_filtered" in routes


def test_pallas_block_route_matches_xla(executors):
    plan = L.Aggregate(child=L.Scan("lineitem"),
                       aggs=(L.AggSpec("sum", Col("l_quantity"), "s"),
                             L.AggSpec("count", None, "c")))
    sp = executors["pallas"].execute_pilot(plan, "lineitem", 0.3, seed=4)
    sx = executors["compiled"].execute_pilot(plan, "lineitem", 0.3, seed=4)
    np.testing.assert_allclose(sp.block_sums, sx.block_sums, rtol=1e-4, atol=1e-4)
    fp = L.rewrite_scans(plan, {"lineitem": L.SampleClause("block", 0.4, 11)})
    rp = executors["pallas"].execute(fp)
    rx = executors["compiled"].execute(fp)
    np.testing.assert_allclose(rp.values, rx.values, rtol=1e-4, atol=1e-4)
    routes = {c.route for c in executors["pallas"].physical._cache.values()}
    assert "pallas_block" in routes


# -- compile cache -------------------------------------------------------------

def test_compile_cache_hits_on_repeated_plan(catalog):
    ex = Executor(catalog)
    plan = _plan(SELECTIVITY_PREDS["50%"])
    sampled = L.rewrite_scans(plan, {"lineitem": L.SampleClause("block", 0.3, 1)})
    ex.execute(sampled)
    info0 = ex.compile_cache_info()
    assert info0.misses >= 1 and info0.hits == 0
    # structurally identical query: different seed and nearby rate land in
    # the same bucketed signature — the serve-layer concurrent-users case
    ex.execute(L.rewrite_scans(plan, {"lineitem": L.SampleClause("block", 0.31, 2)}))
    info1 = ex.compile_cache_info()
    assert info1.hits == info0.hits + 1
    assert info1.misses == info0.misses
    # pilots cache across attempts/seeds too
    ex.execute_pilot(plan, "lineitem", 0.2, seed=0)
    ex.execute_pilot(plan, "lineitem", 0.2, seed=99)
    info2 = ex.compile_cache_info()
    assert info2.hits == info1.hits + 1


def test_executable_keeps_latest_launch_args_except_row_masks(catalog):
    ex = Executor(catalog)
    plan = _plan(SELECTIVITY_PREDS["50%"])
    ex.execute(L.rewrite_scans(plan, {"lineitem": L.SampleClause("block", 0.3, 1)}))
    (block,) = ex.physical.executables()
    ids = block.last_args["ids"]["lineitem"]
    # the kept args re-lower the program that ran
    lowered = block.fn.lower(block.last_args)
    assert lowered.in_avals[0][0]["ids"]["lineitem"].shape == np.shape(ids)
    ex.execute(L.rewrite_scans(plan, {"lineitem": L.SampleClause("row", 0.3, 1)}))
    (row,) = [c for c in ex.physical.executables() if c is not block]
    assert row.last_args is None


def test_plan_signature_strips_rates_seeds_and_constants():
    p1 = L.rewrite_scans(_plan(), {"lineitem": L.SampleClause("block", 0.1, 0)})
    p2 = L.rewrite_scans(_plan(), {"lineitem": L.SampleClause("block", 0.7, 42)})
    rt = {"lineitem": ScanRuntime("block", 10, 64, np.zeros(64, np.int32))}
    assert plan_signature(p1, rt) == plan_signature(p2, rt)
    # predicate constants are hoisted out of the key too: they enter
    # executables as the runtime params operand, so constant variants of one
    # shape share one compilation
    assert plan_signature(_plan(SELECTIVITY_PREDS["50%"]), rt) == \
        plan_signature(_plan(SELECTIVITY_PREDS["100%"]), rt)
    # ...while structural differences (Filter present vs absent) still key apart
    assert plan_signature(_plan(SELECTIVITY_PREDS["50%"]), rt) != \
        plan_signature(_plan(), rt)
    # the hoisted constants come back position-aligned with the template
    from repro.engine.physical import plan_constants
    assert plan_constants(_plan(SELECTIVITY_PREDS["50%"])).tolist() != \
        plan_constants(_plan(SELECTIVITY_PREDS["100%"])).tolist()


# -- empty-sample surfacing ----------------------------------------------------

def test_empty_sample_raises_both_paths(catalog):
    plan = L.rewrite_scans(_plan(), {"lineitem": L.SampleClause("block", 1e-9, 0)})
    for ex in (Executor(catalog), Executor(catalog, use_compiled=False)):
        with pytest.raises(EmptySampleError):
            ex.execute(plan)


def test_taqa_falls_back_exact_on_empty_final_sample(catalog, monkeypatch):
    db = PilotDB(Executor(catalog), large_table_rows=1_000)
    q = Query(child=L.Scan("lineitem"),
              aggs=(CompositeAgg("s", "sum", Col("l_quantity")),))
    real_execute = db.ex.execute

    def sabotage(plan):
        scans = plan.scans()
        if any(s.sample is not None and s.sample.method == "block" for s in scans):
            raise EmptySampleError("lineitem", "block", 0.01)
        return real_execute(plan)

    monkeypatch.setattr(db.ex, "execute", sabotage)
    ans = db.query(q, ErrorSpec(error=0.10, confidence=0.9), seed=0)
    assert ans.report.fallback is not None
    assert "final sample empty" in ans.report.fallback
    exact = db.exact(q)
    np.testing.assert_allclose(ans.values, exact.values)
