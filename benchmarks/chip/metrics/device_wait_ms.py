"""Physical and kernels: host time blocked on the scans' device results
(device_wait spans; a shared pull once) per answered query, ms."""

from chipbench import progspans


def read(w):
    return progspans.owned_ms(w, ("device_wait",))
