"""The traffic generator: the same seed gives the same queries, and every
seed the same multiset of work."""

import collections
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import traffic as gen  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic")))


def load(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_queries(mix):
    tr = load(mix)
    if tr["loop"] == "open":
        t1, q1 = gen.open_loop(tr, 3.0, 2**31 + 5)
        t2, q2 = gen.open_loop(tr, 3.0, 2**31 + 5)
        assert np.array_equal(t1, t2)
    else:
        q1 = gen.closed_pool(tr, 3.0, 2**31 + 5)
        q2 = gen.closed_pool(tr, 3.0, 2**31 + 5)
    assert q1 == q2 and len(q1) > 0
    assert gen.warmup(tr, 9) == gen.warmup(tr, 9)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_the_multiset_of_work(mix):
    """Another seed reorders the work: the same template counts, the same
    choice counts and the same strata of every integer constant."""
    tr = load(mix)
    a = gen.queries(tr, 200, 1)
    b = gen.queries(tr, 200, 2)
    assert [q.sql for q in a] != [q.sql for q in b]
    for t, spec in enumerate(tr["templates"]):
        pa = [q.params for q in a if q.template == t]
        pb = [q.params for q in b if q.template == t]
        assert len(pa) == len(pb)
        for name, p in spec["params"].items():
            va, vb = [x[name] for x in pa], [x[name] for x in pb]
            if p["kind"] == "choice":
                assert collections.Counter(va) == collections.Counter(vb)
            elif p["kind"] == "int":
                # the i-th smallest value comes from the i-th stratum
                width = (p["hi"] - p["lo"] + 1) / len(va)
                gaps = [abs(x - y) for x, y in zip(sorted(va), sorted(vb))]
                assert max(gaps) <= np.ceil(width)
                assert min(va) >= p["lo"] and max(va) <= p["hi"]


def test_open_loop_arrivals_do_not_follow_the_seed():
    tr = load("q6-slider-open")
    t1, q1 = gen.open_loop(tr, 5.0, 1)
    t2, q2 = gen.open_loop(tr, 5.0, 2)
    assert np.array_equal(t1, t2) and q1 != q2


def test_arrivals_keep_their_gaps_and_window():
    t1 = gen.arrivals(500, 10.0, 1)
    t2 = gen.arrivals(500, 10.0, 2)
    assert t1[0] == 0.0 and t1[-1] < 10.0 and np.all(np.diff(t1) > 0)
    assert not np.array_equal(t1, t2)
    assert np.allclose(np.sort(np.diff(np.append(t1, 10.0))),
                       np.sort(np.diff(np.append(t2, 10.0))))


def test_rendered_sql_parses_in_the_dialect():
    from repro.api.sql import parse_sql

    for mix in MIXES:
        for q in gen.queries(load(mix), 4, 3):
            parse_sql(q.sql)
