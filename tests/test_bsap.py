"""BSAP statistics: bound validity (coverage), Lemma 3.2/4.1, propagation."""

import math

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed in this container")
from hypothesis import given, settings, strategies as st

from repro.core import bsap, propagation
from repro.core.allocation import allocate


# -- t bounds on population block sums ---------------------------------------

def test_t_bounds_cover_population_total():
    rng = np.random.default_rng(0)
    pop = rng.gamma(2.0, 10.0, 4000)
    total = pop.sum()
    theta_p, delta = 0.05, 0.05
    cover_u = cover_l = 0
    trials = 400
    for _ in range(trials):
        keep = rng.random(4000) < theta_p
        y = pop[keep]
        if len(y) < 2:
            continue
        cover_u += bsap.upper_sum(y, 4000, delta) >= total
        cover_l += bsap.lower_sum(y, 4000, delta) <= total
    assert cover_u / trials >= 1 - delta - 0.03
    assert cover_l / trials >= 1 - delta - 0.03


def test_block_mean_lower_coverage():
    rng = np.random.default_rng(1)
    pop = rng.normal(50.0, 12.0, 3000)
    mean = pop.mean()
    delta = 0.1
    cover = 0
    trials = 500
    for _ in range(trials):
        y = rng.choice(pop, size=60, replace=False)
        cover += bsap.block_mean_lower(y, delta) <= mean
    assert cover / trials >= 1 - delta - 0.03


def test_degenerate_samples_give_infinite_bounds():
    assert bsap.block_mean_lower(np.array([1.0]), 0.05) == -math.inf
    assert bsap.upper_sum(np.array([1.0]), 10, 0.05) == math.inf
    uv = bsap.single_table_var_ub(np.array([1.0]), 0.1, 0.05, n_blocks=10)
    assert uv(0.05) == math.inf


# -- single-table variance bound (Lemma B.1 at block level) -------------------

def test_single_table_var_ub_dominates_empirical_variance():
    """U_V[θ] must upper-bound the true variance of N·ȳ_S w.h.p."""
    rng = np.random.default_rng(2)
    N, theta_p, theta, delta2 = 2000, 0.05, 0.03, 0.05
    pop = rng.gamma(3.0, 5.0, N)
    total = pop.sum()
    # empirical variance of the Hájek total under Bernoulli(theta)
    ests = []
    for _ in range(1500):
        keep = rng.random(N) < theta
        if keep.sum() == 0:
            continue
        ests.append(N * pop[keep].mean())
    emp_var = np.var(ests)
    # bound from pilots
    cover = 0
    trials = 200
    for _ in range(trials):
        keep = rng.random(N) < theta_p
        y = pop[keep]
        if len(y) < 2:
            continue
        uv = bsap.single_table_var_ub(y, theta_p, delta2, n_blocks=N)
        cover += uv(theta) >= emp_var
    assert cover / trials >= 1 - delta2 - 0.05
    assert np.mean(ests) == pytest.approx(total, rel=0.02)


def test_var_ub_monotone_decreasing_in_theta():
    rng = np.random.default_rng(3)
    y = rng.gamma(2.0, 3.0, 100)
    uv = bsap.single_table_var_ub(y, 0.05, 0.05, n_blocks=2000)
    vals = [uv(t) for t in (0.01, 0.02, 0.05, 0.1, 0.5)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))
    assert uv(1.0) == 0.0


# -- join variance bound (Lemma 4.8) ------------------------------------------

def test_join_var_ub_covers_empirical_ht_variance():
    """Two-table HT estimator variance is bounded by Lemma 4.8's U_V."""
    rng = np.random.default_rng(4)
    N1, N2 = 300, 40
    J = rng.gamma(2.0, 2.0, (N1, N2)) * (rng.random((N1, N2)) < 0.3)
    theta1, theta2, theta_p, delta2 = 0.2, 0.3, 0.2, 0.1
    # empirical HT variance
    ests = []
    for _ in range(1200):
        k1 = rng.random(N1) < theta1
        k2 = rng.random(N2) < theta2
        ests.append(J[np.ix_(k1, k2)].sum() / (theta1 * theta2))
    emp_var = np.var(ests)
    assert np.mean(ests) == pytest.approx(J.sum(), rel=0.05)
    cover = 0
    trials = 120
    for _ in range(trials):
        keep = rng.random(N1) < theta_p
        if keep.sum() < 2:
            continue
        uv = bsap.join_var_ub(J[keep], N1, delta2)
        cover += uv(theta1, theta2) >= emp_var
    assert cover / trials >= 1 - delta2 - 0.05


def test_join_var_ub_degenerates_to_single_table():
    rng = np.random.default_rng(5)
    J = rng.gamma(2.0, 2.0, (50, 10))
    uv = bsap.join_var_ub(J, 50, 0.1)
    # theta2 = 1: only the y1 (left-sampling) term remains
    v_left_only = uv(0.05, 1.0)
    assert v_left_only > 0
    # theta1 = 1: only the middle (right-sampling) term remains
    v_right_only = uv(1.0, 0.05)
    assert v_right_only > 0
    assert uv(0.05, 0.05) > max(v_left_only, v_right_only)


# -- Lemma 3.2 group coverage ------------------------------------------------

def test_group_coverage_rate_monte_carlo():
    """At the Lemma 3.2 rate, miss prob of a g-row group is <= p_f."""
    rng = np.random.default_rng(6)
    num_blocks, block_rows, g, p_f = 64, 4, 24, 0.10
    theta = bsap.group_coverage_rate(num_blocks, block_rows, g, p_f)
    assert 0 < theta <= 1
    n0 = math.ceil(g / block_rows)  # blocks the group occupies
    miss = 0
    trials = 3000
    for _ in range(trials):
        keep = rng.random(num_blocks) < theta
        miss += not keep[:n0].any()  # group packed in first n0 blocks
    assert miss / trials <= p_f + 0.02


def test_group_coverage_rate_edges():
    assert bsap.group_coverage_rate(2, 4, 100, 0.05) == 1.0
    r_small_g = bsap.group_coverage_rate(1000, 4, 400, 0.05)
    r_large_g = bsap.group_coverage_rate(1000, 4, 40, 0.05)
    assert r_small_g < r_large_g  # bigger groups are easier to cover


def test_group_miss_prob_inverse_consistency():
    theta = bsap.group_coverage_rate(500, 8, 160, 0.05)
    p = bsap.group_miss_prob_ub(theta, 500, 8, 160)
    assert p <= 0.05 + 1e-9


# -- Lemma 4.1 efficiency ratio ------------------------------------------------

def test_efficiency_ratio_heterogeneous_blocks():
    """Shuffled data: within-block var ≈ total var ⇒ ratio ≈ 0 (block wins)."""
    rng = np.random.default_rng(7)
    vals = rng.normal(0, 1, 64_000)
    r = bsap.efficiency_ratio(vals, 64)
    assert r < 2.0  # ≈ b * (1 - 1) = 0 up to noise


def test_efficiency_ratio_homogeneous_blocks():
    """Sorted data: within-block var ≈ 0 ⇒ ratio ≈ b (blocks redundant)."""
    rng = np.random.default_rng(8)
    vals = np.sort(rng.normal(0, 1, 64_000))
    r = bsap.efficiency_ratio(vals, 64)
    assert r > 50.0


def test_efficiency_ratio_constant_data():
    assert bsap.efficiency_ratio(np.ones(1000), 10) == 0.0


# -- naive row-level bounds (Lemma B.1) ----------------------------------------

def test_naive_row_bounds_valid_for_iid_rows():
    rng = np.random.default_rng(9)
    N = 50_000
    pop = rng.gamma(2.0, 5.0, N)
    theta_p, theta = 0.01, 0.02
    mean = pop.mean()
    # empirical variance of the sample mean at rate theta
    means = [pop[rng.random(N) < theta].mean() for _ in range(300)]
    emp_var = np.var(means)
    cover_L = cover_V = 0
    trials = 150
    for _ in range(trials):
        s = pop[rng.random(N) < theta_p]
        L_mu, U_V = bsap.naive_row_bounds(s.mean(), s.var(ddof=1), len(s),
                                          theta_p, 0.05, 0.05, exact_N=N)
        cover_L += L_mu <= mean
        cover_V += U_V(theta) >= emp_var
    assert cover_L / trials >= 0.9
    assert cover_V / trials >= 0.9


def test_naive_row_bounds_degenerate():
    L_mu, U_V = bsap.naive_row_bounds(1.0, 1.0, 1, 0.01, 0.05, 0.05)
    assert L_mu == -math.inf and U_V(0.5) == math.inf


# -- memoized percentiles: the rate solve is the uncached one, bit for bit ----
#
# The references below spell out the bounds with a fresh scipy evaluation
# at every use, the binomial bound's z once per θ, as before the percentile
# memo and the hoist out of U_V.

def _sps():
    from scipy import stats
    return stats


def _uncached_single_table_var_ub(y, theta_p, delta2, n_blocks):
    sps = _sps()
    n_p = y.shape[0]
    parts = 2.0 if n_blocks is not None else 3.0
    chi = float(sps.chi2.ppf(delta2 / parts, n_p - 1))
    var_ub = (n_p - 1) / max(chi, 1e-12) * float(y.var(ddof=1))
    if n_blocks is not None:
        N = float(n_blocks)
    else:  # population_lower_bound
        z = float(sps.norm.ppf(1.0 - delta2 / parts))
        c = z * z * (1.0 - theta_p) / (4.0 * theta_p)
        root = math.sqrt(n_p / theta_p + c) - math.sqrt(c)
        N = max(root * root, 0.0)

    def U_V(theta):
        if theta >= 1.0:
            return 0.0
        z = float(sps.norm.ppf(1.0 - delta2 / parts))
        n_lb = max(N * theta - z * math.sqrt(max(N * theta * (1.0 - theta),
                                                 0.0)), 0.0)
        if n_lb <= 1.0:
            return math.inf
        return N * N * (1.0 - theta) * var_ub / n_lb

    return U_V


def _rate_solve(pair, n1, theta_p, exact_n, uncached):
    """The constraints TAQA's `bounds` stage builds for one channel (one
    table, or a two-table join when ``pair`` has columns), and the rates
    `solve_candidates` picks from them."""
    from repro.core.planner import Constraint, solve_candidates
    sps = _sps()
    y = pair.sum(axis=1)
    n_p = y.shape[0]
    b = allocate(0.95, 1, 0.05)
    n_blocks = n1 if exact_n else None
    if uncached:
        t = float(sps.t.ppf(1.0 - b.delta1, n_p - 1))
        L_mu = n1 * (float(y.mean()) - t * float(y.std(ddof=1))
                     / math.sqrt(n_p))
        z = float(sps.norm.ppf((1.0 + b.p_prime) / 2.0))
        uv1 = _uncached_single_table_var_ub(y, theta_p, b.delta2, n_blocks)
    else:
        L_mu = n1 * bsap.block_mean_lower(y, b.delta1)
        z = bsap.z_for(b.p_prime)
        uv1 = bsap.single_table_var_ub(y, theta_p, b.delta2,
                                       n_blocks=n_blocks)
    with pytest.MonkeyPatch.context() as m:
        if uncached:  # join_var_ub is unchanged; only its percentile memoized
            m.setattr(bsap, "student_t_ppf",
                      lambda p, df: float(sps.t.ppf(p, df)))
        uv2 = bsap.join_var_ub(pair, n1, b.delta2) if pair.shape[1] > 1 \
            else None

    def var_fn(rates):
        t1, t2 = rates.get("t1", 1.0), rates.get("t2", 1.0)
        if uv2 is not None and t2 < 1.0:
            return uv2(t1, t2)
        return uv1(t1) if t1 < 1.0 else 0.0

    tables = ["t1", "t2"] if uv2 is not None else ["t1"]
    plans = solve_candidates(
        [Constraint("c", z=z, L_mu=L_mu, error=b.error, var_fn=var_fn)],
        tables)
    return (L_mu, z, uv1, uv2), [p.rates for p in plans]


@pytest.mark.parametrize("seed,n_p,n2,exact_n", [
    (11, 30, 1, True), (12, 59, 1, True), (13, 200, 1, True),
    (14, 59, 1, False), (15, 45, 8, True), (16, 120, 24, True)])
def test_rate_solve_bit_identical_to_uncached_percentiles(
        seed, n_p, n2, exact_n):
    rng = np.random.default_rng(seed)
    n1 = 117_188
    pair = rng.gamma(4.0, 1.0, (n_p, n2))
    theta_p = n_p / n1
    new, new_rates = _rate_solve(pair, n1, theta_p, exact_n, False)
    ref, ref_rates = _rate_solve(pair, n1, theta_p, exact_n, True)
    assert new[:2] == ref[:2]
    grid = np.geomspace(1e-6, 0.1, 97).tolist() + [0.5, 1.0]
    assert [new[2](t) for t in grid] == [ref[2](t) for t in grid]
    if n2 > 1:
        assert ([new[3](a, b) for a in grid for b in grid]
                == [ref[3](a, b) for a in grid for b in grid])
    assert new_rates and new_rates == ref_rates


def test_naive_row_bounds_bit_identical_to_uncached_percentiles():
    sps = _sps()
    mean_p, var_p, n_p, theta_p, d1, d2 = 3.5, 9.25, 87, 0.004, 0.01, 0.02
    L_mu, U_V = bsap.naive_row_bounds(mean_p, var_p, n_p, theta_p, d1, d2)
    t = float(sps.t.ppf(1.0 - d1, n_p - 1))
    assert L_mu == mean_p - t * math.sqrt(var_p) / math.sqrt(n_p)
    chi = float(sps.chi2.ppf(d2 / 3.0, n_p - 1))
    var_ub = (n_p - 1) / max(chi, 1e-12) * var_p
    z = float(sps.norm.ppf(1.0 - d2 / 3.0))
    c = z * z * (1.0 - theta_p) / (4.0 * theta_p)
    L_N = (math.sqrt(n_p / theta_p + c) - math.sqrt(c)) ** 2
    for theta in np.geomspace(1e-5, 0.5, 61).tolist():
        z = float(sps.norm.ppf(1.0 - d2 / 3.0))
        n_lb = max(L_N * theta - z * math.sqrt(L_N * theta * (1 - theta)), 0)
        assert U_V(theta) == (math.inf if n_lb <= 1 else var_ub / n_lb)


# -- propagation rules (Table 2) -----------------------------------------------

@settings(max_examples=200, deadline=None)
@given(mu1=st.floats(0.5, 100), mu2=st.floats(0.5, 100),
       e1=st.floats(0.001, 0.5), e2=st.floats(0.001, 0.5),
       s1=st.sampled_from([-1.0, 1.0]), s2=st.sampled_from([-1.0, 1.0]))
def test_propagation_rules_are_upper_bounds(mu1, mu2, e1, e2, s1, s2):
    """For worst-case component estimates at the budget edge, the composite
    relative error never exceeds the Table 2 bound."""
    h1 = mu1 * (1 + s1 * e1)
    h2 = mu2 * (1 + s2 * e2)
    rel = lambda est, tru: abs(est - tru) / abs(tru)
    assert rel(h1 * h2, mu1 * mu2) <= propagation.propagate_product(e1, e2) + 1e-9
    assert rel(h1 / h2, mu1 / mu2) <= propagation.propagate_division(e1, e2) + 1e-9
    assert rel(h1 + h2, mu1 + mu2) <= propagation.propagate_addition(e1, e2) + 1e-9


@settings(max_examples=100, deadline=None)
@given(e=st.floats(0.005, 0.5))
def test_split_budget_inverts_propagation(e):
    for kind, prop in (("product", propagation.propagate_product),
                       ("ratio", propagation.propagate_division)):
        ep = propagation.split_budget(kind, e)
        assert prop(ep, ep) <= e + 1e-9
    assert propagation.split_budget("sum", e) == e
    assert propagation.split_budget("add", e) == e


def test_combine_estimates():
    assert propagation.combine_estimates("ratio", 10.0, 4.0) == 2.5
    assert propagation.combine_estimates("product", 3.0, 4.0) == 12.0
    assert propagation.combine_estimates("add", 3.0, 4.0, (2.0, 1.0)) == 10.0
    assert math.isnan(propagation.combine_estimates("ratio", 1.0, 0.0))


# -- allocation -----------------------------------------------------------------

def test_allocation_boole_arithmetic():
    b = allocate(0.95, 10, 0.05)
    assert b.confidence == pytest.approx(1 - 0.05 / 10)
    assert b.delta1 == pytest.approx((1 - b.confidence) / 3)
    assert b.p_prime == pytest.approx(b.confidence + b.delta1 + b.delta2)
    assert b.p_prime < 1.0


def test_allocation_joint_probability_identity():
    """Boole: sum of per-channel failure budgets equals the total budget."""
    C, p = 7, 0.9
    b = allocate(p, C, 0.1)
    per_channel_failure = 1 - b.confidence
    assert C * per_channel_failure == pytest.approx(1 - p)


def test_allocation_custom_delta_split_validation():
    with pytest.raises(ValueError):
        allocate(0.95, 1, 0.05, delta_split=(0.04, 0.04))
    b = allocate(0.95, 1, 0.05, delta_split=(0.005, 0.04))
    assert b.p_prime == pytest.approx(0.95 + 0.045)


def test_allocation_rejects_bad_inputs():
    with pytest.raises(ValueError):
        allocate(0.95, 0, 0.05)
    with pytest.raises(ValueError):
        allocate(0.7, 3, 0.05, coverage_debit=0.3)
