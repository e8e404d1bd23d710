"""TAQA: mean per query of its rate_solve span durations, ms."""

from chipbench import layers


def read(w):
    return layers.span_ms(w, ("rate_solve",))
