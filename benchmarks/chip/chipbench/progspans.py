"""Shared arithmetic of the readers of the program's stage spans.

The program's query traces (``SessionConfig(tracing=True)``) split each
device program's host side into ``draw``, ``dispatch`` and ``device_wait``
spans, and record ``cpu_ms`` (the thread's CPU time) on every live span.
A dispatch that serves several queries is held once, on its owner's tree
(``owner`` true); the other members hold retroactive copies (``owner``
false), which the sums below skip.

Span times are ``time.perf_counter`` readings, the device trace's are the
profiler's.  :func:`clock_offset_ns` lines the two up: each query's
submission time lies inside the ``bench.submit`` annotation the window put
around it.

Each reader returns None when what it reads is absent: a program whose
spans lack these stages, or a window without a device trace.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

from chipbench import devtrace, layers

Interval = Tuple[float, float]


def _trees(w) -> Iterable[Tuple[object, dict]]:
    for r in w.recs:
        root = w.spans(r) if r.ok else None
        if root is not None:
            yield r, root


def owned_ms(w, names) -> Optional[float]:
    """Summed wall time of the spans ``names`` over the answered queries'
    trees, retroactive copies skipped, per answered query, in ms."""
    total, found = 0.0, False
    for _, root in _trees(w):
        for name in names:
            for s in layers.walk(root, name):
                if s["attrs"].get("owner") is not False:
                    total += s["duration_s"]
                    found = True
    answered = sum(1 for r in w.recs if r.ok)
    return total * 1e3 / answered if found else None


def cpu_ms(w, name: str) -> Optional[float]:
    """Mean per query of the summed ``cpu_ms`` of its spans ``name``, over
    the queries whose spans carry it."""
    out = []
    for _, root in _trees(w):
        found = [s["attrs"]["cpu_ms"] for s in layers.walk(root, name)
                 if "cpu_ms" in s["attrs"]]
        if found:
            out.append(sum(found))
    return layers.mean(out)


def clock_offset_ns(w) -> Optional[float]:
    """Profiler clock minus ``perf_counter``, in ns.  The k-th
    ``bench.submit`` annotation of the window holds the k-th query's
    submission, so each query bounds the offset from both sides; the
    largest lower bound is kept (a submission is read a few microseconds
    after its annotation opens).  None without annotations, or when the
    bounds contradict each other."""
    subs = sorted((s, s + d) for n, s, d in w.trace["host"]
                  if n == "bench.submit")
    if not subs or len(subs) != len(w.recs):
        return None
    lo, hi = float("-inf"), float("inf")
    for (a, b), r in zip(subs, w.recs):
        if r.handle is None:
            continue
        t = r.handle.t_submit * 1e9
        lo, hi = max(lo, a - t), min(hi, b - t)
    return lo if lo <= hi else None


def span_intervals(w, name: str, offset_ns: float) -> List[Interval]:
    """The spans ``name`` of the answered queries' trees, in the profiler
    clock."""
    out = []
    for r, root in _trees(w):
        for s in layers.walk(root, name):
            a = (r.handle.t_submit + s["t_start_s"]) * 1e9 + offset_ns
            out.append((a, a + s["duration_s"] * 1e9))
    return out


def idle_intervals(trace: dict, t0: float, t1: float) -> List[Interval]:
    """Stretches of [t0, t1] in which no op ran on the first device
    plane."""
    planes = list(trace["device"].values())
    busy = devtrace._union((max(s, t0), min(s + d, t1))
                           for _, _, s, d in planes[0] if s < t1 and s + d > t0)
    out, prev = [], t0
    for a, b in busy:
        if a > prev:
            out.append((prev, a))
        prev = max(prev, b)
    if t1 > prev:
        out.append((prev, t1))
    return out


def overlap_ns(xs: List[Interval], ys: List[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share_under(w, name: str) -> Optional[float]:
    """Percent of the window's device-idle time in which at least one span
    ``name`` of some query was open."""
    if w.trace is None or not w.trace["device"]:
        return None
    offset = clock_offset_ns(w)
    if offset is None:
        return None
    spans = span_intervals(w, name, offset)
    if not spans:
        return None
    idle = idle_intervals(w.trace, w.t0_ns, w.t1_ns)
    total = sum(b - a for a, b in idle)
    if total <= 0:
        return None
    return 100.0 * overlap_ns(idle, devtrace._union(spans)) / total
