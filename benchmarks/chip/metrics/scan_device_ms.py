"""Physical and kernels: device time of the scan executables per
answered query, ms, from the profiler trace."""

from chipbench import layers


def read(w):
    return layers.scan_device_ms(w)
