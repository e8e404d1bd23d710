"""Device: share of the traced window in which no op ran, percent."""

from chipbench import layers


def read(w):
    return layers.device_idle_pct(w)
