"""Kernels: bytes the scans need over (HBM peak times the scan
executables' device time), percent."""

from chipbench import layers


def read(w):
    return layers.scan_roofline_pct(w)
