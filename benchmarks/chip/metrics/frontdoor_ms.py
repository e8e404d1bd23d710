"""Front door: mean per query of the parse and lower span durations, ms."""

from chipbench import layers


def read(w):
    return layers.span_ms(w, ("parse", "lower"))
