"""Physical and sampling: host time of the scans' draw and dispatch spans
(pilot and final; a shared dispatch once) per answered query, ms."""

from chipbench import progspans


def read(w):
    return progspans.owned_ms(w, ("draw", "dispatch"))
