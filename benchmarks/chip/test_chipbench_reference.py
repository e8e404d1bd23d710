"""The plain reference against NumPy float64, at a small size."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import reference, traffic as gen  # noqa: E402

ROWS, BLOCK = 5000, 256  # 20 blocks, the last one partly padding


def load(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def columns():
    rng = np.random.default_rng(0)
    p = -(-ROWS // BLOCK) * BLOCK
    q = rng.integers(1, 51, p).astype(np.float32)
    cols = {
        "l_quantity": q,
        "l_extendedprice": (q * rng.uniform(900, 1100, p)).astype(np.float32),
        "l_discount": rng.integers(0, 11, p).astype(np.float32) / 100.0,
        "l_shipdate": rng.integers(0, 2526, p).astype(np.int32),
        "l_returnflag": rng.integers(0, 3, p).astype(np.int32),
    }
    return cols


def numpy_answer(t, params, cols, rows):
    """Straight float64 evaluation of a template over ``rows``."""
    keep = np.ones(len(rows), bool)
    f32 = lambda c: cols[c][rows]  # noqa: E731
    for pred in t["where"]:
        x = f32(pred[1])
        cast = lambda v: np.asarray(v).astype(x.dtype)  # noqa: E731
        if pred[0] == "between":
            keep &= (x >= cast(params[pred[2]])) & (x <= cast(params[pred[3]]))
        else:
            keep &= x < cast(params[pred[2]])
    groups = t["max_groups"] if t.get("group_by") else 1
    out = np.zeros((len(t["select"]), groups))
    for g in range(groups):
        m = keep if not t.get("group_by") else keep & (f32(t["group_by"]) == g)
        for k, (_, op, e) in enumerate(t["select"]):
            if op == "count":
                out[k, g] = m.sum()
                continue
            v = (f32(e).astype(np.float64) if isinstance(e, str) else
                 f32(e[1]).astype(np.float64) * f32(e[2]).astype(np.float64))
            out[k, g] = v[m].sum() if op == "sum" else v[m].mean()
    return out


@pytest.mark.parametrize("mix", ["q6-slider-open", "q1-backlog"])
def test_exact_and_replay_match_float64(columns, mix):
    t = load(mix)["templates"][0]
    jcols = {c: jnp.asarray(v) for c, v in columns.items()}
    for q in gen.queries(load(mix), 3, 11):
        parts = reference.block_partials(jcols, ROWS, BLOCK, t, q.params)
        want = numpy_answer(t, q.params, columns, np.arange(ROWS))
        assert reference.rel_gap(reference.exact(t, parts), want) < 1e-6
        ids = reference.final_sample(parts.shape[0], 0.4, 123)
        rows = (ids[:, None] * BLOCK + np.arange(BLOCK)).ravel()
        want = numpy_answer(t, q.params, columns, rows[rows < ROWS])
        scale = parts.shape[0] / len(ids)
        for k, (_, op, _) in enumerate(t["select"]):
            if op != "avg":
                want[k] *= scale
        assert reference.rel_gap(reference.replay(t, parts, ids), want) < 1e-6


def test_final_sample_is_the_programs_rule():
    from repro.engine.sampling import draw_block_ids

    for seed, rate in ((5, 0.01), (2**31 + 9, 0.2), (77, 0.9)):
        assert np.array_equal(reference.final_sample(5000, rate, seed),
                              draw_block_ids(5000, rate, seed + 977))


def test_columns_read_and_channels():
    t = load("q1-backlog")["templates"][0]
    assert reference.columns_read(t) == [
        "l_shipdate", "l_quantity", "l_extendedprice", "l_discount",
        "l_returnflag"]
    assert [k for k, _ in reference.channels(t)] == [
        "sum", "sum", "sum", "count", "count"]
    t6 = load("q6-slider-open")["templates"][0]
    assert reference.columns_read(t6) == [
        "l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]


def test_rel_gap():
    assert reference.rel_gap([[1.0, 2.2]], [[1.0, 2.0]]) == pytest.approx(0.1)
    assert reference.rel_gap([[5.0, 0.0]], [[4.0, 0.0]]) == pytest.approx(0.25)
    assert reference.rel_gap([[np.nan]], [[1.0]]) == float("inf")
