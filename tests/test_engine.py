"""Engine substrate: BlockTable, relational ops, samplers, cost model."""

import numpy as np
import pytest

from repro.engine import logical as L
from repro.engine import ops
from repro.engine.cost import exact_cost, plan_cost
from repro.engine.datagen import make_lineitem, make_orders, make_skewed, tpch_catalog
from repro.engine.executor import Executor
from repro.engine.expr import And, Col, Const, Not, Or, eval_expr
from repro.engine.sampling import block_sample, row_sample
from repro.engine.table import BlockTable


def small_table(n=100, br=8, seed=0, name="t"):
    rng = np.random.default_rng(seed)
    return BlockTable.from_numpy(
        name,
        {"k": np.arange(n, dtype=np.int32),
         "x": rng.normal(10.0, 2.0, n).astype(np.float32),
         "g": rng.integers(0, 3, n).astype(np.int32)},
        br,
    )


# -- BlockTable ---------------------------------------------------------------

def test_blocktable_padding_and_validity():
    t = small_table(n=13, br=8)
    assert t.padded_rows == 16
    assert t.num_blocks == 2
    assert int(np.asarray(t.valid).sum()) == 13
    assert t.num_origin_blocks == 2


def test_blocktable_gather_blocks_keeps_lineage():
    t = small_table(n=64, br=8)
    s = t.gather_blocks(np.array([3, 5]))
    assert s.padded_rows == 16
    bid = np.asarray(s.block_id)
    assert set(bid.tolist()) == {3, 5}
    np.testing.assert_array_equal(
        np.asarray(s.columns["k"])[:8], np.arange(24, 32))


def test_blocktable_rejects_bad_shapes():
    with pytest.raises(ValueError):
        BlockTable(name="bad", columns={"a": np.zeros(8), "b": np.zeros(9)},
                   block_rows=4, num_rows=8)


# -- expressions --------------------------------------------------------------

def test_expr_arithmetic_and_comparisons():
    cols = {"a": np.array([1.0, 2.0, 3.0]), "b": np.array([3.0, 2.0, 1.0])}
    e = (Col("a") * 2 + Col("b")) / 2
    np.testing.assert_allclose(np.asarray(eval_expr(e, cols)), [2.5, 3.0, 3.5])
    m = And(Col("a") >= 2, Or(Col("b") < 2, Not(Col("a").eq(2))))
    np.testing.assert_array_equal(np.asarray(eval_expr(m, cols)), [False, False, True])
    assert set(e.columns()) == {"a", "b"}
    assert Const(3.0).columns() == ()


def test_expr_between():
    cols = {"a": np.array([0.0, 5.0, 10.0])}
    np.testing.assert_array_equal(
        np.asarray(eval_expr(Col("a").between(1, 9), cols)), [False, True, False])


# -- relational ops -----------------------------------------------------------

def test_filter_marks_invalid_not_compacts():
    t = small_table(n=32, br=8)
    f = ops.filter_table(t, Col("x") > 10.0)
    assert f.padded_rows == t.padded_rows
    ref = np.asarray(t.columns["x"])[: t.num_rows] > 10.0
    assert int(np.asarray(f.valid).sum()) == int(ref.sum())


def test_join_unique_matches_numpy():
    rng = np.random.default_rng(3)
    left = BlockTable.from_numpy(
        "l", {"fk": rng.integers(0, 20, 64).astype(np.int32),
              "v": rng.normal(size=64).astype(np.float32)}, 8)
    right = BlockTable.from_numpy(
        "r", {"pk": np.arange(20, dtype=np.int32),
              "w": rng.normal(size=20).astype(np.float32)}, 4)
    j = ops.join_unique(left, right, "fk", "pk")
    lv = np.asarray(left.columns["fk"])[:64]
    rw = np.asarray(right.columns["w"])[:20]
    expect = rw[lv]
    got = np.asarray(j.columns["w"])[:64]
    mask = np.asarray(j.valid)[:64]
    assert mask.all()  # every fk has a match
    np.testing.assert_allclose(got[mask], expect[mask], rtol=1e-6)


def test_join_respects_right_validity():
    left = BlockTable.from_numpy("l", {"fk": np.array([0, 1, 2, 3], np.int32)}, 2)
    right = BlockTable.from_numpy(
        "r", {"pk": np.array([0, 1, 2, 3], np.int32),
              "w": np.arange(4, dtype=np.float32)}, 2)
    # invalidate right block 1 (pk 2,3)
    import jax.numpy as jnp
    rv = np.asarray(right.valid).copy()
    rv[2:] = False
    right = right.with_valid(jnp.asarray(rv))
    j = ops.join_unique(left, right, "fk", "pk")
    np.testing.assert_array_equal(np.asarray(j.valid)[:4], [True, True, False, False])


def test_join_name_collision_raises():
    l = BlockTable.from_numpy("l", {"k": np.zeros(4, np.int32), "v": np.zeros(4, np.float32)}, 2)
    r = BlockTable.from_numpy("r", {"pk": np.zeros(4, np.int32), "v": np.zeros(4, np.float32)}, 2)
    with pytest.raises(ValueError):
        ops.join_unique(l, r, "k", "pk")


def test_union_all_offsets_block_ids():
    a = small_table(n=16, br=8, seed=0)
    b = small_table(n=16, br=8, seed=1)
    u = ops.union_all([a, b])
    assert u.num_origin_blocks == 4
    bid = np.asarray(u.block_id)
    assert bid.min() == 0 and bid.max() == 3
    assert int(np.asarray(u.valid).sum()) == 32


def test_grouped_sums_and_counts():
    t = small_table(n=64, br=8)
    sums, counts = (np.asarray(x) for x in
                    ops.grouped_totals(t, [Col("x")], "g", 3))
    sums = sums[0]
    x = np.asarray(t.columns["x"])[:64]
    g = np.asarray(t.columns["g"])[:64]
    for gid in range(3):
        assert sums[gid] == pytest.approx(float(x[g == gid].sum()), rel=1e-5)
        assert counts[gid] == (g == gid).sum()


def test_block_group_sums_lineage_after_filter():
    t = small_table(n=64, br=8)
    f = ops.filter_table(t, Col("x") > 10.0)
    ids = np.array([1, 3, 6])
    bs = ops.block_group_sums(f, [Col("x")], None, 1, ids)
    x = np.asarray(t.columns["x"])
    for j, b in enumerate(ids):
        seg = x[b * 8:(b + 1) * 8]
        expect = seg[seg > 10.0].sum()
        assert bs[j, 0, 0] == pytest.approx(float(expect), rel=1e-5)


# -- samplers -----------------------------------------------------------------

def test_block_sample_scans_only_sampled_bytes():
    t = make_lineitem(20_000, 64, seed=0)
    s, info = block_sample(t, 0.1, seed=1)
    assert info.n_sampled_blocks == len(info.sampled_block_ids)
    assert info.scanned_bytes == info.n_sampled_blocks * 64 * t.row_bytes()
    assert info.scanned_bytes < t.total_bytes() / 5


def test_row_sample_pays_full_scan():
    t = make_lineitem(20_000, 64, seed=0)
    s, info = row_sample(t, 0.01, seed=1)
    assert info.scanned_bytes == t.total_bytes()
    kept = info.n_sampled_rows
    assert 0 < kept < 20_000 * 0.05


def test_block_sample_empty_outcome():
    t = small_table(n=32, br=8)
    s, info = block_sample(t, 1e-9, seed=0)
    assert info.n_sampled_blocks == 0
    assert int(np.asarray(s.valid).sum()) == 0


def test_sample_clause_validation():
    with pytest.raises(ValueError):
        L.SampleClause("block", 0.0)
    with pytest.raises(ValueError):
        L.SampleClause("shard", 0.5)


# -- executor -----------------------------------------------------------------

def test_executor_exact_matches_numpy():
    cat = tpch_catalog(40_000, 64, seed=0)
    ex = Executor(cat)
    plan = L.Aggregate(
        child=L.Filter(L.Scan("lineitem"), Col("l_discount") > 0.05),
        aggs=(L.AggSpec("sum", Col("l_extendedprice"), "s"),
              L.AggSpec("count", None, "c"),
              L.AggSpec("avg", Col("l_quantity"), "a")),
    )
    res = ex.execute(plan)
    li = cat["lineitem"].to_numpy()
    m = li["l_discount"] > 0.05
    assert res.scalar("s") == pytest.approx(float(li["l_extendedprice"][m].sum()), rel=1e-4)
    assert res.scalar("c") == pytest.approx(float(m.sum()))
    assert res.scalar("a") == pytest.approx(float(li["l_quantity"][m].mean()), rel=1e-4)


def test_executor_hajek_unbiased_single_table():
    cat = tpch_catalog(60_000, 32, seed=1)
    ex = Executor(cat)
    plan = L.Aggregate(child=L.Scan("lineitem"),
                       aggs=(L.AggSpec("sum", Col("l_quantity"), "s"),))
    truth = ex.execute(plan).scalar("s")
    ests = []
    for seed in range(30):
        p = L.rewrite_scans(plan, {"lineitem": L.SampleClause("block", 0.05, seed)})
        ests.append(ex.execute(p).scalar("s"))
    assert np.mean(ests) == pytest.approx(truth, rel=0.01)


def test_executor_ht_two_table_unbiased():
    cat = tpch_catalog(60_000, 32, seed=2)
    ex = Executor(cat)
    plan = L.Aggregate(
        child=L.Join(L.Scan("lineitem"), L.Scan("orders"), "l_orderkey", "o_orderkey"),
        aggs=(L.AggSpec("sum", Col("l_extendedprice"), "s"),))
    truth = ex.execute(plan).scalar("s")
    ests = []
    for seed in range(40):
        p = L.rewrite_scans(plan, {
            "lineitem": L.SampleClause("block", 0.2, seed),
            "orders": L.SampleClause("block", 0.3, seed + 1000)})
        ests.append(ex.execute(p).scalar("s"))
    assert np.mean(ests) == pytest.approx(truth, rel=0.05)


def test_pilot_stats_shapes_and_presence():
    cat = tpch_catalog(40_000, 64, seed=3)
    ex = Executor(cat)
    plan = L.Aggregate(child=L.Scan("lineitem"),
                       aggs=(L.AggSpec("sum", Col("l_quantity"), "s"),),
                       group_by="l_returnflag", max_groups=3)
    st = ex.execute_pilot(plan, "lineitem", 0.1, seed=4)
    assert st.block_sums.shape == (st.n_sampled_blocks, 3, 2)  # +__rows channel
    assert st.group_present.all()
    assert st.agg_names[-1] == "__rows"


def test_pilot_pair_sums_match_join_truth():
    cat = tpch_catalog(30_000, 64, seed=4)
    ex = Executor(cat)
    plan = L.Aggregate(
        child=L.Join(L.Scan("lineitem"), L.Scan("orders"), "l_orderkey", "o_orderkey"),
        aggs=(L.AggSpec("sum", Col("l_extendedprice"), "s"),))
    st = ex.execute_pilot(plan, "lineitem", 0.2, seed=5, pair_tables=("orders",))
    ps = st.pair_sums["orders"]
    assert ps.shape[0] == st.n_sampled_blocks
    assert ps.shape[1] == cat["orders"].num_blocks
    # row sums across right blocks == per-left-block sums
    np.testing.assert_allclose(ps[:, :, 0].sum(axis=1), st.block_sums[:, 0, 0], rtol=1e-4)


# -- cost model ---------------------------------------------------------------

def test_cost_model_sampling_discount():
    cat = tpch_catalog(40_000, 64, seed=5)
    plan = L.Aggregate(child=L.Scan("lineitem"),
                       aggs=(L.AggSpec("sum", Col("l_quantity"), "s"),))
    full = exact_cost(plan, cat)
    tenth = plan_cost(plan, cat, {"lineitem": 0.1})
    assert tenth == pytest.approx(0.1 * full, rel=1e-6)


def test_cost_model_join_counts_both_tables():
    cat = tpch_catalog(40_000, 64, seed=6)
    plan = L.Aggregate(
        child=L.Join(L.Scan("lineitem"), L.Scan("orders"), "l_orderkey", "o_orderkey"),
        aggs=(L.AggSpec("sum", Col("l_extendedprice"), "s"),))
    c = plan_cost(plan, cat, {"lineitem": 0.01})
    li_only = plan_cost(plan, cat, {"lineitem": 0.01, "orders": 0.0})
    assert c > li_only  # orders' scan contributes


def test_datagen_skewed_properties():
    t = make_skewed(30_000, 64, num_groups=5, seed=1)
    d = t.to_numpy()
    sizes = np.bincount(d["s_group"], minlength=5)
    assert sizes[0] > sizes[-1]  # Zipf skew
    assert (d["s_measure"] >= 0).all()
