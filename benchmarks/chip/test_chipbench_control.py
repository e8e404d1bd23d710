"""The comparison that decides ``correct`` fails what it must: the
bfloat16 control in the program's place, and an answer altered where it
is produced.  The replay needs sampled answers, and at a size a test can
hold only the grouped Q1 mix (``traffic/q1-backlog.json``) gets them from
TAQA: these runs hold 2.4M lineitem rows of the uniform configuration."""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import cell, spec, traffic as gen  # noqa: E402

CELL = "tpch-sf20-uniform.q6-slider"
SEED = 2**31 + 21


def small_cell():
    """The uniform configuration at 2.4M rows under the grouped Q1 mix."""
    c = spec.load_cell(CELL)
    c.config["scale_factor"] = 0.4
    c.traffic = spec.load_json(os.path.join(HERE, "traffic",
                                            "q1-backlog.json"))
    c.traffic["warmup"] = {"drain_sizes": [8], "rounds": 1}
    c.traffic["check_sample"] = 8
    c.end_to_end = [{"name": "qps", "unit": "queries/s"},
                    {"name": "setup_s", "unit": "s"}]
    return c


def limits():
    return spec.load_json(os.path.join(HERE, "limits", CELL + ".json"))[
        "limits"]


def window(c):
    tables, session, gateway = cell.build_session(c, SEED, False)
    recs = cell.closed_loop(gateway, gen.closed_pool(c.traffic, 1.0, SEED),
                            c.traffic["clients"], 1.0, False)[0]
    session.close()
    return tables, recs


def test_control_reads_above_the_limit():
    c = small_cell()
    tables, recs = window(c)
    sampled = [r for r in recs if r.ok and r.handle.report.fallback is None]
    assert sampled, "the test size must make TAQA sample"
    chk = cell.check_answers(c, tables, recs, SEED, control=True)
    assert chk.checked > 0
    assert chk.control_gap > limits()["estimator_gap"]


def _alter_answers(monkeypatch):
    from repro.engine.executor import Executor

    compose = Executor._compose_values

    def altered(plan, sums, counts, scale):
        return compose(plan, sums, counts, scale) * 1.01

    monkeypatch.setattr(Executor, "_compose_values", staticmethod(altered))


def _other_sample(monkeypatch):
    import repro.engine.executor as ex

    draw = ex.draw_block_ids
    monkeypatch.setattr(ex, "draw_block_ids",
                        lambda n, rate, seed: draw(n, rate, seed + 1))


@pytest.mark.parametrize("fault", [_alter_answers, _other_sample],
                         ids=["answer_altered", "sample_not_the_claimed"])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    c = small_cell()
    res = cell.run(c, SEED, 1.0, False, time.perf_counter(), limits(),
                   stage_log=lambda m: None)
    assert res["failed"] == 0
    assert res["correct"] is False
    assert res["checks"]["estimator_gap"]["value"] > \
        res["checks"]["estimator_gap"]["limit"]


def _columns(n):
    rng = np.random.default_rng(0)
    return {"l_shipdate": jnp.asarray(rng.integers(0, 2526, n), jnp.int32),
            "l_quantity": jnp.asarray(rng.integers(1, 51, n), jnp.float32),
            "l_extendedprice": jnp.asarray(rng.uniform(900, 55000, n),
                                           jnp.float32),
            "l_discount": jnp.asarray(rng.integers(0, 11, n) / 100,
                                      jnp.float32)}


def test_lower_precision_reference_differs_from_float32():
    """The control is the reference itself at bfloat16: on the same blocks
    it rounds the discount values, which moves a Q6 revenue by about 1e-3,
    far past the limit."""
    from chipbench import reference

    t = spec.load_cell(CELL).traffic["templates"][0]
    n = 64 * 1024
    cols = _columns(n)
    params = {"d": 85, "d_end": 449, "disc": 0.03, "disc_lo": 0.02,
              "disc_hi": 0.04, "qty": 24}
    hi = reference.exact(t, reference.block_partials(cols, n, 1024, t,
                                                     params))
    lo = reference.exact(t, reference.block_partials(
        cols, n, 1024, t, params, dtype=jnp.bfloat16))
    assert reference.rel_gap(lo, hi) > limits()["estimator_gap"]


def test_lower_precision_keeps_int_columns_exact():
    """Only float columns are rounded: a count under a date filter (int32)
    and a whole-number quantity bound reads the same at bfloat16."""
    from chipbench import reference

    t = {"table": "lineitem", "select": [["n", "count", None]],
         "where": [["between", "l_shipdate", "d", "d_end"],
                   ["<", "l_quantity", "qty"]],
         "group_by": None, "max_groups": 1}
    n = 64 * 1024
    cols = _columns(n)
    params = {"d": 2100, "d_end": 2464, "qty": 24}
    hi = reference.block_partials(cols, n, 1024, t, params)
    lo = reference.block_partials(cols, n, 1024, t, params,
                                  dtype=jnp.bfloat16)
    assert np.array_equal(hi, lo)


@pytest.mark.parametrize("k", [1, 12, 48])
def test_guarantee_miss_limit_is_a_binomial_quantile(k):
    """A program that misses its 5% error with chance exactly 5% exceeds
    the limit with chance at most ``MISS_LEVEL``, and one miss fewer
    would be exceeded more often."""
    from math import comb

    def above(m):
        return sum(comb(k, j) * 0.05 ** j * 0.95 ** (k - j)
                   for j in range(m + 1, k + 1))

    m = cell.miss_limit(k, 0.05)
    assert above(m) <= cell.MISS_LEVEL
    assert m == 0 or above(m - 1) > cell.MISS_LEVEL
