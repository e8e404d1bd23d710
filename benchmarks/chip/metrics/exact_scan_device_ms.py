"""Physical and kernels: device time of the exact scan executable
(jit_run_exact) per exact answer, ms, from the profiler trace."""

from chipbench import exactpath


def read(w):
    return exactpath.scan_device_ms(w)
