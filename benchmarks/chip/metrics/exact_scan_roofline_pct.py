"""Physical and kernels: bytes the exact scans need (every block x 1024
rows x the native widths of the columns read) over (HBM peak times
jit_run_exact's device time), percent."""

from chipbench import exactpath


def read(w):
    return exactpath.scan_roofline_pct(w)
