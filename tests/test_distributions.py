"""stats.distributions: percentile functions and Lemma-B.1 helper bounds."""

import math

import numpy as np
import pytest

from repro.stats import distributions as D


KNOWN_Z = {0.5: 0.0, 0.975: 1.959964, 0.95: 1.644854, 0.995: 2.575829}


def test_normal_ppf_known_values():
    for p, z in KNOWN_Z.items():
        assert D.normal_ppf(p) == pytest.approx(z, abs=1e-4)


def test_normal_ppf_acklam_fallback_accuracy():
    # the hand approximation must agree with scipy (if present) everywhere
    for p in np.linspace(1e-6, 1 - 1e-6, 501):
        assert D._acklam(float(p)) == pytest.approx(D.normal_ppf(float(p)), abs=2e-6)


def test_normal_ppf_rejects_bad_p():
    with pytest.raises(ValueError):
        D._acklam(0.0)
    with pytest.raises(ValueError):
        D._acklam(1.0)


def test_student_t_known_values():
    # classic table values
    assert D.student_t_ppf(0.975, 10) == pytest.approx(2.2281, abs=2e-3)
    assert D.student_t_ppf(0.95, 30) == pytest.approx(1.6973, abs=2e-3)
    assert D.student_t_ppf(0.99, 100) == pytest.approx(2.3642, abs=2e-3)


def test_student_t_fallback_close_to_scipy():
    try:
        from scipy import stats as sps
    except Exception:
        pytest.skip("scipy unavailable")
    import repro.stats.distributions as mod

    for df in (5, 10, 30, 100):
        for p in (0.9, 0.95, 0.975, 0.99):
            z = mod._acklam(p)
            g1 = (z ** 3 + z) / 4.0
            g2 = (5 * z ** 5 + 16 * z ** 3 + 3 * z) / 96.0
            g3 = (3 * z ** 7 + 19 * z ** 5 + 17 * z ** 3 - 15 * z) / 384.0
            g4 = (79 * z ** 9 + 776 * z ** 7 + 1482 * z ** 5 - 1920 * z ** 3 - 945 * z) / 92160.0
            approx = z + g1 / df + g2 / df ** 2 + g3 / df ** 3 + g4 / df ** 4
            exact = float(sps.t.ppf(p, df))
            assert approx == pytest.approx(exact, rel=2e-3)


def test_chi2_known_values():
    assert D.chi2_ppf(0.05, 29) == pytest.approx(17.708, rel=2e-2)
    assert D.chi2_ppf(0.95, 29) == pytest.approx(42.557, rel=2e-2)


def test_degenerate_inputs_raise():
    with pytest.raises(ValueError):
        D.student_t_ppf(0.9, 0)
    with pytest.raises(ValueError):
        D.chi2_ppf(0.9, -1)


def test_binomial_lower_bound_coverage():
    """P[n >= bound] >= 1-delta, checked by Monte Carlo."""
    rng = np.random.default_rng(0)
    N, theta, delta = 5000, 0.02, 0.05
    bound = D.binomial_lower_bound(N, theta, delta)
    draws = rng.binomial(N, theta, size=4000)
    cover = (draws >= bound).mean()
    assert cover >= 1 - delta - 0.02
    assert bound > 0


def test_population_lower_bound_coverage():
    """P[N >= L_N] >= 1-delta when n_p ~ Bin(N, theta_p)."""
    rng = np.random.default_rng(1)
    N, theta_p, delta = 20_000, 0.01, 0.05
    covered = 0
    trials = 2000
    for _ in range(trials):
        n_p = rng.binomial(N, theta_p)
        if n_p == 0:
            continue
        L_N = D.population_lower_bound(n_p, theta_p, delta)
        covered += N >= L_N
    assert covered / trials >= 1 - delta - 0.02


def test_bounds_zero_inputs():
    assert D.binomial_lower_bound(0, 0.5, 0.1) == 0.0
    assert D.population_lower_bound(0, 0.5, 0.1) == 0.0
    assert math.isfinite(D.population_lower_bound(100, 0.01, 0.05))


# -- the percentile memo -------------------------------------------------------

def _bounds_and_solve(y, n_blocks, delta2):
    """What TAQA's `bounds` and `solve` stages ask of the percentiles for a
    one-table, one-channel Q6: L_μ, z, U_V and the bisection over θ."""
    from repro.core import bsap
    from repro.core.planner import Constraint, solve_candidates
    L_mu = n_blocks * bsap.block_mean_lower(y, delta2)
    z = bsap.z_for(1.0 - 2.0 * delta2)
    uv = bsap.single_table_var_ub(y, 0.0005, delta2, n_blocks=n_blocks)
    c = Constraint("c", z=z, L_mu=L_mu, error=0.05,
                   var_fn=lambda r: uv(r["t"]) if r["t"] < 1.0 else 0.0)
    return uv, solve_candidates([c], ["t"])


def test_percentile_memo_second_solve_evaluates_nothing():
    rng = np.random.default_rng(21)
    n_blocks, n_p, delta2 = 117_188, 61, 0.0061234  # a δ no other test uses
    _, plans = _bounds_and_solve(rng.gamma(4.0, 1.0, n_p), n_blocks, delta2)
    assert plans
    before = D.percentile_cache_info()
    uv, plans = _bounds_and_solve(rng.gamma(4.0, 1.0, n_p), n_blocks, delta2)
    after = D.percentile_cache_info()
    assert plans
    assert after["misses"] == before["misses"]
    assert after["size"] == before["size"]
    # t for L_μ, z, chi² and the binomial z for U_V; none in the bisection
    assert after["hits"] - before["hits"] == 4
    for theta in np.geomspace(1e-6, 0.1, 49):
        uv(float(theta))
    assert D.percentile_cache_info() == after


def test_percentile_memo_never_caches_a_bad_argument(monkeypatch):
    size = D.percentile_cache_info()["size"]
    for _ in range(2):
        with pytest.raises(ValueError):
            D.student_t_ppf(0.9, 0)
        with pytest.raises(ValueError):
            D.chi2_ppf(0.9, -1)
    # the closed forms check p as well
    monkeypatch.setattr(D, "_HAVE_SCIPY", False)
    for _ in range(2):
        with pytest.raises(ValueError):
            D.normal_ppf(1.25)
        with pytest.raises(ValueError):
            D.student_t_ppf(-0.5, 10)
        with pytest.raises(ValueError):
            D.chi2_ppf(1.5, 10)
    assert D.percentile_cache_info()["size"] == size


def test_percentile_memo_threads_agree_with_scipy():
    import sys
    import threading
    from concurrent.futures import ThreadPoolExecutor
    sps = pytest.importorskip("scipy.stats")
    args = [(0.9 + k * 1e-3 + 1e-7, 30 + k) for k in range(64)]
    barrier = threading.Barrier(4)

    def work():
        barrier.wait(timeout=30)
        return [(D.normal_ppf(p), D.student_t_ppf(p, df),
                 D.chi2_ppf(1.0 - p, df)) for p, df in args]

    before = D.percentile_cache_info()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(4) as pool:
            futures = [pool.submit(work) for _ in range(4)]
            results = [f.result(timeout=60) for f in futures]
    finally:
        sys.setswitchinterval(interval)
    after = D.percentile_cache_info()
    expected = [(float(sps.norm.ppf(p)), float(sps.t.ppf(p, df)),
                 float(sps.chi2.ppf(1.0 - p, df))) for p, df in args]
    assert all(r == expected for r in results)
    # every lookup counted once: no update of the memo's counters was lost
    assert (after["hits"] + after["misses"]
            - before["hits"] - before["misses"]) == 4 * 3 * len(args)
