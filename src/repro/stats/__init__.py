from repro.stats.distributions import (
    normal_ppf,
    student_t_ppf,
    chi2_ppf,
    binomial_lower_bound,
    population_lower_bound,
    percentile_cache_info,
)

__all__ = [
    "normal_ppf",
    "student_t_ppf",
    "chi2_ppf",
    "binomial_lower_bound",
    "population_lower_bound",
    "percentile_cache_info",
]
