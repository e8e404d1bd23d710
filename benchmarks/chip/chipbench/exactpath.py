"""Readers of the exact path: the answers TAQA gives by scanning the whole
table, their scan executable on the device, and what the pilot before them
cost.

An exact answer is an answered, uncached query whose report names a
``fallback``.  The program jits its exact scan as ``run_exact``, so its
device ops run in the module ``jit_run_exact``; its ``exact`` span (with
``reason``, ``n_blocks`` and ``bytes``) wraps that scan.  A program without
them (no such module in the trace, no such span) gives None, as does a
window with no exact answer.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from chipbench import data, devtrace, layers, reference

EXACT_MODULE = "jit_run_exact"


def exact_recs(w) -> List[object]:
    return [r for r in w.recs if r.ok and not r.handle.cached
            and r.handle.report.fallback is not None]


def exact_device_s(w) -> float:
    if w.trace is None:
        return 0.0
    return devtrace.module_ns(w.trace, (EXACT_MODULE,), w.t0_ns,
                              w.t1_ns) / 1e9


def exact_bytes(w) -> int:
    """Bytes the exact scans of the window need: every block of the
    scanned table times block rows times the native widths of the columns
    the query reads."""
    cfg = w.cell.config
    total = 0
    for r in exact_recs(w):
        t = w.cell.traffic["templates"][r.query.template]
        cols = cfg["tables"][t["table"]]["columns"]
        width = sum(np.dtype(cols[c]["dtype"]).itemsize
                    for c in reference.columns_read(t))
        total += data.padded_rows(cfg, t["table"]) * width
    return total


def scan_device_ms(w) -> Optional[float]:
    """Device time of ``jit_run_exact`` per exact answer, ms."""
    n, s = len(exact_recs(w)), exact_device_s(w)
    return s * 1e3 / n if n and s > 0 else None


def scan_roofline_pct(w) -> Optional[float]:
    """Bandwidth-bound roofline share of ``jit_run_exact``, percent."""
    s = exact_device_s(w)
    if s <= 0 or w.peak is None or not exact_recs(w):
        return None
    return 100.0 * exact_bytes(w) / (w.peak["hbm_bytes_per_s"] * s)


def answer_pct(w) -> Optional[float]:
    """Answered queries whose report has a fallback, over all answered
    queries, percent."""
    done = [r for r in w.recs if r.ok]
    if not done:
        return None
    exact = sum(1 for r in done if r.handle.report.fallback is not None)
    return 100.0 * exact / len(done)


def fallback_overhead_ms(w) -> Optional[float]:
    """Mean per exact answer of the wall time from the end of its
    ``schedule`` span to the start of its ``exact`` span, ms: the pilot and
    the decision that TAQA puts before an exact scan."""
    out = []
    for r in exact_recs(w):
        root = w.spans(r)
        if root is None:
            continue
        sched = list(layers.walk(root, "schedule"))
        exact = list(layers.walk(root, "exact"))
        if sched and exact:
            end = sched[0]["t_start_s"] + sched[0]["duration_s"]
            out.append((exact[0]["t_start_s"] - end) * 1e3)
    return layers.mean(out)
