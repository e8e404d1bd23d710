"""TAQA: share of the window's device-idle time in which some query's
rate_solve span was open, percent, from the profiler trace."""

from chipbench import progspans


def read(w):
    return progspans.idle_share_under(w, "rate_solve")
