"""TAQA: mean per exact answer from the end of its schedule span to the
start of its exact span (the pilot and the decision), ms."""

from chipbench import exactpath


def read(w):
    return exactpath.fallback_overhead_ms(w)
