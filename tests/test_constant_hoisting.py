"""Constant-hoisted executables + batched drain-group launches.

The tentpole invariants of the constant-generic compiled layer:

* sweeping predicate/expression constants over a fixed plan shape produces
  bit-identical answers to the eager baseline while costing exactly ONE
  physical compilation per shape (``Executor.compile_cache_info()``) — the
  constants ride as a runtime operand, not as compile keys;
* a drain group's batched final launches (``lax.map`` lanes) are
  bit-identical to the serial per-member dispatches;
* pilot SHARING stays sub-keyed on the full constant-bearing signature:
  constant-varied queries never share pilot statistics (selectivity shapes
  the §4 bounds), even though they share every compiled executable.
"""

import dataclasses as dc

import numpy as np
import pytest

from repro.api import Session, SessionConfig
from repro.core.taqa import structural_signature, template_signature
from repro.engine import logical as L
from repro.engine.datagen import tpch_catalog
from repro.engine.executor import Executor
from repro.engine.expr import And, Col
from repro.engine.physical import plan_constants, plan_template

BR = 64

SERIAL_CFG = SessionConfig(async_workers=0, share_pilots=False,
                           batch_finals=False, result_cache_size=0)
BATCH_CFG = SessionConfig(async_workers=0, share_pilots=True,
                          batch_finals=True, result_cache_size=0)


@pytest.fixture(scope="module")
def catalog():
    return tpch_catalog(6_000, BR, seed=0)


@pytest.fixture(scope="module")
def big_catalog():
    return tpch_catalog(200_000, 32, seed=0)


# -- shape factories: each sweep varies ONLY constants ------------------------

def _q6_plan(lo, hi, cap):
    pred = And(Col("l_shipdate").between(lo, hi), Col("l_quantity") < cap)
    return L.Aggregate(
        child=L.Filter(L.Scan("lineitem"), pred),
        aggs=(L.AggSpec("sum", Col("l_extendedprice") * Col("l_discount"), "rev"),
              L.AggSpec("count", None, "cnt")))


def _grouped_plan(cut):
    return L.Aggregate(
        child=L.Filter(L.Scan("lineitem"), Col("l_shipdate") < cut),
        aggs=(L.AggSpec("sum", Col("l_quantity"), "qty"),
              L.AggSpec("count", None, "cnt")),
        group_by="l_returnflag", max_groups=3)


def _join_plan(cut):
    return L.Aggregate(
        child=L.Filter(L.Join(L.Scan("lineitem"), L.Scan("orders"),
                              "l_orderkey", "o_orderkey"),
                       Col("o_orderdate") < cut),
        aggs=(L.AggSpec("sum", Col("l_extendedprice"), "rev"),))


SWEEPS = {
    "q6": [_q6_plan(100 + 50 * i, 1500 + 30 * i, 20 + i) for i in range(6)],
    "grouped": [_grouped_plan(400 * (i + 1)) for i in range(6)],
    "join": [_join_plan(300 * (i + 1)) for i in range(6)],
}


# -- template extraction ------------------------------------------------------

def test_templates_unify_constant_variants():
    for name, plans in SWEEPS.items():
        templates = {plan_template(p) for p in plans}
        assert len(templates) == 1, name
        consts = [tuple(plan_constants(p).tolist()) for p in plans]
        assert len(set(consts)) == len(plans), name  # vectors stay distinct
        lengths = {len(c) for c in consts}
        assert len(lengths) == 1, name  # position-aligned slots


# -- property sweep: bit-identity + one compile miss per shape ----------------

@pytest.mark.parametrize("shape", list(SWEEPS))
def test_constant_sweep_one_compile_bit_identical(catalog, shape):
    compiled = Executor(catalog)
    eager = Executor(catalog, use_compiled=False)
    for i, plan in enumerate(SWEEPS[shape]):
        sampled = L.rewrite_scans(
            plan, {"lineitem": L.SampleClause("block", 0.3, seed=7 + i)})
        rc = compiled.execute(sampled)
        re = eager.execute(sampled)
        np.testing.assert_array_equal(rc.values, re.values)
        np.testing.assert_array_equal(rc.group_counts, re.group_counts)
        assert rc.scanned_bytes == re.scanned_bytes
    info = compiled.compile_cache_info()
    assert info.misses == 1, (shape, info)  # ONE executable for the sweep
    assert info.hits == len(SWEEPS[shape]) - 1


@pytest.mark.parametrize("shape", ["q6", "grouped"])
def test_pilot_constant_sweep_one_compile(catalog, shape):
    compiled = Executor(catalog)
    eager = Executor(catalog, use_compiled=False)
    for plan in SWEEPS[shape]:
        sc = compiled.execute_pilot(plan, "lineitem", 0.2, seed=3)
        se = eager.execute_pilot(plan, "lineitem", 0.2, seed=3)
        np.testing.assert_array_equal(sc.block_sums, se.block_sums)
        np.testing.assert_array_equal(sc.group_present, se.group_present)
    assert compiled.compile_cache_info().misses == 1


def test_pallas_kernel_route_shares_compilation_across_constants(catalog):
    """The Pallas filtered_agg route takes bounds by scalar prefetch: a
    constant sweep stays one kernel compilation and matches the XLA twin."""
    pallas = Executor(catalog, kernel_mode="pallas")
    xla = Executor(catalog)
    for plan in SWEEPS["q6"]:
        sp = pallas.execute_pilot(plan, "lineitem", 0.3, seed=5)
        sx = xla.execute_pilot(plan, "lineitem", 0.3, seed=5)
        np.testing.assert_allclose(sp.block_sums, sx.block_sums,
                                   rtol=1e-4, atol=1e-4)
    assert pallas.compile_cache_info().misses == 1
    routes = {c.route for c in pallas.physical._cache.values()}
    assert routes == {"pallas_filtered"}


# -- batched drain groups -----------------------------------------------------

def _herd_sqls():
    # constant-varied dashboard herd (one template, six constant sets) plus
    # spec-varied members of one constant set
    sqls = [(f"SELECT SUM(l_extendedprice * l_discount) AS rev FROM lineitem "
             f"WHERE l_quantity < {c} ERROR 8% CONFIDENCE 95%")
            for c in (18, 21, 24, 27, 30, 33)]
    sqls.append("SELECT SUM(l_extendedprice * l_discount) AS rev FROM "
                "lineitem WHERE l_quantity < 24 ERROR 5% CONFIDENCE 95%")
    return sqls


def test_batched_drain_bit_identical_to_serial(big_catalog):
    serial = Session(big_catalog, seed=21, config=SERIAL_CFG)
    solo = {s: serial.sql(s) for s in _herd_sqls()}
    assert all(h.status == "done" for h in solo.values())

    batched = Session(big_catalog, seed=21, config=BATCH_CFG)
    handles = [batched.submit(s) for s in _herd_sqls()]
    stats_groups = None
    done = batched.drain()
    assert all(h.status == "done" for h in done)
    stats_groups = batched.scheduler.last_drain.n_groups
    # ONE template group: the constant-varied herd drains together
    assert stats_groups == 1
    for h in handles:
        assert np.array_equal(h.result().values, solo[h.sql].result().values)
    batched.close(), serial.close()


def test_constant_varied_herd_never_shares_pilots(big_catalog):
    """Template grouping widens the drain group, but pilot sharing must
    stay keyed on the constant-bearing signature: N distinct constants run
    N pilot stages (selectivity shapes the §4 bounds)."""
    session = Session(big_catalog, seed=9, config=BATCH_CFG)
    sqls = _herd_sqls()
    handles = [session.submit(s) for s in sqls]
    p0 = session.executor.pilots_run
    session.drain()
    distinct_constants = 6  # the ERROR 5% member shares the c=24 pilot
    assert session.executor.pilots_run - p0 == distinct_constants
    assert all(h.status == "done" for h in handles)
    # the spec-varied member reused the c=24 pilot
    shared = [h for h in handles if h.report is not None
              and h.report.pilot_shared]
    assert len(shared) == 1 and "ERROR 5%" in shared[0].sql
    session.close()


def test_group_key_strips_constants_signature_keeps_them(big_catalog):
    session = Session(big_catalog, seed=0, config=BATCH_CFG)
    h1 = session.prepare("SELECT COUNT(*) AS n FROM lineitem "
                         "WHERE l_quantity < 10 ERROR 9% CONFIDENCE 95%")
    h2 = session.prepare("SELECT COUNT(*) AS n FROM lineitem "
                         "WHERE l_quantity < 40 ERROR 9% CONFIDENCE 95%")
    assert h1.group_key == h2.group_key == template_signature(h1.query)
    assert h1.signature != h2.signature
    assert h1.signature == structural_signature(h1.query)
    session.close()


def test_executor_execute_batch_matches_solo(catalog):
    """The batched executable's lanes are bit-identical to solo dispatches,
    across constant variants sharing one bucket."""
    ex_batch = Executor(catalog)
    ex_solo = Executor(catalog)

    def plans_of(n):
        return [L.rewrite_scans(
            _q6_plan(100 + 10 * i, 1600, 20 + i),
            {"lineitem": L.SampleClause("block", 0.3, seed=i)})
            for i in range(n)]

    outs = ex_batch.execute_batch(plans_of(4))
    for plan, out in zip(plans_of(4), outs):
        ref = ex_solo.execute(plan)
        np.testing.assert_array_equal(out.values, ref.values)
        np.testing.assert_array_equal(out.group_counts, ref.group_counts)
        assert out.scanned_bytes == ref.scanned_bytes
    # one batch-of-4 compilation for the whole pow2-sized set
    assert ex_batch.compile_cache_info().misses == 1
    assert ex_batch.queries_run == 4

    # non-pow2 sets chunk greedily (5 -> 4+1): the 4-lane executable is
    # reused, the remainder runs solo — no padded (wasted) lanes ever
    m0 = ex_batch.compile_cache_info().misses
    outs5 = ex_batch.execute_batch(plans_of(5))
    for plan, out in zip(plans_of(5), outs5):
        np.testing.assert_array_equal(out.values, ex_solo.execute(plan).values)
    assert ex_batch.compile_cache_info().misses - m0 == 1  # the solo shape
    assert ex_batch.queries_run == 9


def _plain_plan(seedless_tag):
    # no filter chain: routes to block_agg (the no-predicate kernel); the
    # tag keeps the sweep's plans distinct without changing the template
    return L.Aggregate(
        child=L.Scan("lineitem"),
        aggs=(L.AggSpec("sum", Col("l_extendedprice"), "rev"),
              L.AggSpec("count", None, "cnt")))


@pytest.mark.parametrize("shape,route", [
    ("filtered", "pallas_filtered_batched"),
    ("block", "pallas_block_batched"),
])
def test_pallas_batched_lanes_bitwise_match_solo(catalog, shape, route):
    """Interpret-mode pinning of the batched kernel grid: every lane of the
    megacore-style batched filtered_agg/block_agg launch is BITWISE the
    member's solo kernel run — same per-block partials, same f32 reduction
    order — and the whole pow2 set costs ONE batched kernel compilation."""
    ex_batch = Executor(catalog, kernel_mode="pallas")
    ex_solo = Executor(catalog, kernel_mode="pallas")

    def make(i):
        base = (_q6_plan(100 + 10 * i, 1600, 20 + i) if shape == "filtered"
                else _plain_plan(i))
        return L.rewrite_scans(
            base, {"lineitem": L.SampleClause("block", 0.3, seed=i)})

    plans = [make(i) for i in range(4)]
    outs = ex_batch.execute_batch(plans)
    for plan, out in zip(plans, outs):
        ref = ex_solo.execute(plan)
        np.testing.assert_array_equal(out.values, ref.values)
        np.testing.assert_array_equal(out.raw_sums, ref.raw_sums)
        np.testing.assert_array_equal(out.group_counts, ref.group_counts)
        assert out.scanned_bytes == ref.scanned_bytes
    info = ex_batch.compile_cache_info()
    assert info.misses == info.batched_misses == 1, info
    routes = {c.route for c in ex_batch.physical._cache.values()}
    assert routes == {route}


def test_failed_batch_reruns_solo_and_is_counted(catalog, monkeypatch):
    """A batched launch that fails re-runs its members solo (same answers),
    and the swallowed exception is counted rather than hidden."""
    ex = Executor(catalog)
    plans = [L.rewrite_scans(_q6_plan(100 + 10 * i, 1500, 24),
                             {"lineitem": L.SampleClause("block", 0.4, seed=i)})
             for i in range(2)]

    def refuse(*_args, **_kw):
        raise RuntimeError("batched kernel refused")

    monkeypatch.setattr(ex, "_run_bucket", refuse)
    outs = ex.execute_batch(plans)
    for plan, out in zip(plans, outs):
        np.testing.assert_array_equal(out.values,
                                      Executor(catalog).execute(plan).values)
    assert ex.swallowed_failures == 1
    assert "batched_finals: RuntimeError: batched kernel refused" == \
        ex.last_swallowed


def test_execute_batch_surfaces_empty_samples_per_member(catalog):
    ex = Executor(catalog)
    good = L.rewrite_scans(_q6_plan(100, 1500, 24),
                           {"lineitem": L.SampleClause("block", 0.4, seed=1)})
    empty = L.rewrite_scans(_q6_plan(100, 1500, 24),
                            {"lineitem": L.SampleClause("block", 1e-9, seed=1)})
    from repro.engine.executor import EmptySampleError
    outs = ex.execute_batch([good, empty, good])
    assert isinstance(outs[1], EmptySampleError)
    ref = Executor(catalog).execute(good)
    np.testing.assert_array_equal(outs[0].values, ref.values)
    np.testing.assert_array_equal(outs[2].values, ref.values)


def test_batching_respects_runtime_feature_toggles(big_catalog):
    """batch_finals=False keeps per-member dispatches; answers stay
    bit-identical either way (the invariant every toggle must keep)."""
    sqls = _herd_sqls()[:3]
    on = Session(big_catalog, seed=4, config=BATCH_CFG)
    off = Session(big_catalog, seed=4, config=dc.replace(BATCH_CFG,
                                                         batch_finals=False))
    h_on = [on.submit(s) for s in sqls]
    h_off = [off.submit(s) for s in sqls]
    on.drain(), off.drain()
    for a, b in zip(h_on, h_off):
        assert np.array_equal(a.result().values, b.result().values)
    on.close(), off.close()
