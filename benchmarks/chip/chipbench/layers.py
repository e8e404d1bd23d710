"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

Each reader takes a :class:`chipbench.cell.Window` and returns a number, or
None when the window holds nothing to read (never 0 for a share).
"""

from __future__ import annotations

from typing import Iterable, List, Optional

import numpy as np

from chipbench import data, devtrace, reference

SCAN_MODULES = ("jit_run", "jit_run_b", "jit_run_batched")


def walk(span: dict, name: str) -> Iterable[dict]:
    if span["name"] == name:
        yield span
    for c in span["children"]:
        yield from walk(c, name)


def mean(xs: List[float]) -> Optional[float]:
    return float(np.mean(xs)) if xs else None


def span_ms(w, names) -> Optional[float]:
    """Mean per query of the summed durations of spans ``names``, in ms,
    over the queries that have one."""
    out = []
    for r in w.recs:
        root = w.spans(r) if r.ok else None
        if root is None:
            continue
        found = [s["duration_s"] for n in names for s in walk(root, n)]
        if found:
            out.append(sum(found) * 1e3)
    return mean(out)


def queue_wait_ms(w) -> Optional[float]:
    """Mean from a query's due time to the end of its ``schedule`` span."""
    out = []
    for r in w.recs:
        root = w.spans(r) if r.ok else None
        sched = list(walk(root, "schedule")) if root is not None else []
        if sched:
            s = sched[0]
            end = r.handle.t_submit + s["t_start_s"] + s["duration_s"]
            out.append((end - (w.origin + r.due)) * 1e3)
    return mean(out)


def _table(w, r) -> str:
    return w.cell.traffic["templates"][r.query.template]["table"]


def _row_bytes(config: dict, table: str) -> int:
    return sum(np.dtype(c["dtype"]).itemsize
               for c in config["tables"][table]["columns"].values())


def final_blocks(w, r) -> int:
    """Blocks the answer's final scan read: the program's scanned-bytes
    count over the bytes of one block; every block for an exact answer."""
    cfg, t = w.cell.config, _table(w, r)
    n_blocks = data.padded_rows(cfg, t) // cfg["block_rows"]
    rep = r.handle.report
    if r.handle.cached:
        return 0
    if rep.fallback is not None or rep.plan is None:
        return n_blocks
    return rep.final_scanned_bytes // (cfg["block_rows"] * _row_bytes(cfg, t))


def sampled_block_pct(w) -> Optional[float]:
    cfg = w.cell.config
    out = [100.0 * final_blocks(w, r)
           / (data.padded_rows(cfg, _table(w, r)) // cfg["block_rows"])
           for r in w.recs if r.ok and not r.handle.cached]
    return mean(out)


def pilot_blocks(w, r) -> int:
    """Blocks of the pilot scans this query's trace owns (a shared pilot
    counts once, on its owner)."""
    root = w.spans(r)
    if root is None:
        return 0
    return sum(int(s["attrs"].get("n_pilot_blocks") or 0)
               for s in walk(root, "pilot")
               if s["attrs"].get("owner", True))


def scan_bytes(w) -> int:
    """Bytes the sampled scans of the window need: realized pilot and final
    blocks times block rows times the native widths of the columns the
    query reads."""
    cfg = w.cell.config
    total = 0
    for r in w.recs:
        if not r.ok:
            continue
        t = w.cell.traffic["templates"][r.query.template]
        width = sum(np.dtype(cfg["tables"][t["table"]]["columns"][c]["dtype"])
                    .itemsize for c in reference.columns_read(t))
        total += ((pilot_blocks(w, r) + final_blocks(w, r))
                  * cfg["block_rows"] * width)
    return total


def scan_device_s(w) -> float:
    if w.trace is None:
        return 0.0
    return devtrace.module_ns(w.trace, SCAN_MODULES, w.t0_ns, w.t1_ns) / 1e9


def scan_device_ms(w) -> Optional[float]:
    n = sum(1 for r in w.recs if r.ok)
    s = scan_device_s(w)
    return s * 1e3 / n if n and s > 0 else None


def scan_roofline_pct(w) -> Optional[float]:
    """Bandwidth-bound roofline share of the scan executables."""
    s = scan_device_s(w)
    if s <= 0 or w.peak is None:
        return None
    return 100.0 * scan_bytes(w) / (w.peak["hbm_bytes_per_s"] * s)


def device_idle_pct(w) -> Optional[float]:
    if w.trace is None or not w.trace["device"]:
        return None
    span = w.t1_ns - w.t0_ns
    return 100.0 * (1.0 - devtrace.busy_ns(w.trace, w.t0_ns, w.t1_ns) / span)
