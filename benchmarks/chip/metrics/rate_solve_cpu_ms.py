"""TAQA: mean per query of its rate_solve spans' thread CPU time, ms."""

from chipbench import progspans


def read(w):
    return progspans.cpu_ms(w, "rate_solve")
