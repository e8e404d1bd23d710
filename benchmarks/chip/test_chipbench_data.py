"""Tables made on the device from the seed: the same seed gives the same
tables, the distributions are datagen's, and clustering sorts one column."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import data, spec  # noqa: E402


def small(name, sf=0.005):
    c = spec.load_cell(name).config
    c["scale_factor"] = sf
    return c


@pytest.fixture(scope="module")
def uniform():
    cfg = small("tpch-sf20-uniform.q6-slider")
    return cfg, data.make_tables(cfg, 2**31 + 77)


def host(table):
    return {c: np.asarray(v) for c, v in table.columns.items()}


def test_same_seed_same_tables(uniform):
    cfg, tabs = uniform
    again = data.make_tables(cfg, 2**31 + 77)
    other = data.make_tables(cfg, 2**31 + 78)
    for name in tabs:
        a, b, c = host(tabs[name]), host(again[name]), host(other[name])
        assert all(np.array_equal(a[k], b[k]) for k in a)
        assert any(not np.array_equal(a[k], c[k]) for k in a)


def test_columns_follow_datagen(uniform):
    cfg, tabs = uniform
    li = tabs["lineitem"]
    n = li.num_rows
    assert n == 30_000 and li.padded_rows == 30_720
    assert list(li.columns) == list(cfg["tables"]["lineitem"]["columns"])
    c = {k: v[:n] for k, v in host(li).items()}
    assert set(np.unique(c["l_quantity"])) == set(
        np.arange(1, 51, dtype=np.float32))
    assert set(np.unique(c["l_discount"])) == set(
        np.arange(0, 11, dtype=np.float32) / np.float32(100))
    ratio = c["l_extendedprice"] / c["l_quantity"]
    assert ratio.min() >= 899.9 and ratio.max() <= 1100.1
    assert c["l_shipdate"].min() >= 0 and c["l_shipdate"].max() <= 2525
    assert c["l_orderkey"].max() < tabs["orders"].num_rows
    assert np.all(c["l_receiptdate"] - c["l_shipdate"] >= 1)
    assert np.all(c["l_receiptdate"] - c["l_shipdate"] <= 30)
    assert c["l_linenumber"].min() == 1 and c["l_linenumber"].max() == 7
    # padding rows are zero and not valid
    assert not np.asarray(li.valid)[n:].any()
    assert all(not v[n:].any() for v in host(li).values())
    keys = host(tabs["orders"])["o_orderkey"][:tabs["orders"].num_rows]
    assert np.array_equal(np.sort(keys), np.arange(len(keys)))


def test_clustered_layout_sorts_only_its_column():
    cfg = small("tpch-sf20-uniform.q6-slider")
    cfg["cluster_by"] = {"lineitem": "l_shipdate"}
    li = data.make_tables(cfg, 5)["lineitem"]
    c = {k: v[:li.num_rows] for k, v in host(li).items()}
    assert np.all(np.diff(c["l_shipdate"]) >= 0)
    assert c["l_shipdate"][0] < 10 and c["l_shipdate"][-1] > 2515
    assert not np.all(np.diff(c["l_quantity"]) >= 0)
    # a date drawn from l_shipdate follows its order
    assert np.all(c["l_receiptdate"] - c["l_shipdate"] >= 1)
