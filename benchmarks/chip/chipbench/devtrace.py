"""From a profiler trace to device time, idle share and idle-gap labels.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
compact form: per device plane the XLA op events with the module each ran
in, and the benchmark's own host annotations (names starting ``bench.``).
The reductions below work on that form only, so a small recorded trace
beside the tests checks them without a chip.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PREFIX = "bench."


def _stats(event) -> Dict[str, object]:
    try:
        return dict(event.stats)
    except Exception:  # noqa: BLE001 — an event without readable stats
        return {}


def extract(profile_dir: str) -> dict:
    """The newest trace under ``profile_dir``, in compact form::

        {"device": {plane: [[op, module, start_ns, dur_ns], ...]},
         "host": [[name, start_ns, dur_ns], ...]}
    """
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    device: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              module_name(e.name))
                             for e in (lines["XLA Modules"].events
                                       if "XLA Modules" in lines else ()))
            starts = [m[0] for m in modules]
            ops = []
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                mod = _stats(e).get("hlo_module")
                if mod is None:
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    mod = modules[i][2] if i >= 0 and \
                        e.start_ns < modules[i][1] else ""
                ops.append([op_name(e.name), module_name(str(mod)),
                            e.start_ns, e.duration_ns])
            device[plane.name] = ops
        else:
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, e.start_ns, e.duration_ns])
    return {"device": device, "host": host}


def op_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...), ...`` -> ``fusion.3``: the HLO
    instruction's name without its text."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def module_name(name: str) -> str:
    """``jit_run(123)`` -> ``jit_run``: the program's name without ids."""
    return re.sub(r"\(.*\)$", "", name).strip()


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def window(trace: dict, name: str = "bench.window") -> Tuple[float, float]:
    spans = [(s, s + d) for n, s, d in trace["host"] if n == name]
    if not spans:
        raise ValueError(f"no host annotation {name!r} in the trace")
    return min(a for a, _ in spans), max(b for _, b in spans)


def busy_ns(trace: dict, t0: float, t1: float) -> float:
    """Nanoseconds in [t0, t1] in which some op ran, averaged over the
    device planes (the chips used)."""
    planes = trace["device"]
    if not planes:
        return 0.0
    total = 0.0
    for ops in planes.values():
        for a, b in _union((max(s, t0), min(s + d, t1))
                           for _, _, s, d in ops if s < t1 and s + d > t0):
            total += b - a
    return total / len(planes)


def module_ns(trace: dict, modules: Sequence[str], t0: float,
              t1: float) -> float:
    """Summed device time of the ops of ``modules`` in [t0, t1], averaged
    over the device planes."""
    planes = trace["device"]
    if not planes:
        return 0.0
    want = set(modules)
    total = sum(min(s + d, t1) - max(s, t0)
                for ops in planes.values() for _, mod, s, d in ops
                if mod in want and s < t1 and s + d > t0)
    return total / len(planes)


def top_ops(trace: dict, t0: float, t1: float, k: int = 10) -> List[list]:
    """The ``k`` device ops (``module/op``) that took most seconds."""
    acc: Dict[str, float] = {}
    n = max(len(trace["device"]), 1)
    for ops in trace["device"].values():
        for op, mod, s, d in ops:
            if s < t1 and s + d > t0:
                key = f"{mod}/{op}" if mod else op
                acc[key] = acc.get(key, 0.0) + (min(s + d, t1) - max(s, t0))
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in top]


def idle_gaps(trace: dict, t0: float, t1: float,
              k: int = 10) -> List[list]:
    """Idle device seconds in [t0, t1] by what the host was doing: each
    gap of the first device plane goes to the innermost ``bench.`` host
    annotation open at its midpoint (``bench.window`` when none other is);
    the ``k`` labels with most idle seconds."""
    planes = list(trace["device"].values())
    if not planes:
        return []
    busy = _union((max(s, t0), min(s + d, t1))
                  for _, _, s, d in planes[0] if s < t1 and s + d > t0)
    gaps, prev = [], t0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if t1 > prev:
        gaps.append((prev, t1))
    host = sorted(trace["host"], key=lambda e: e[1])
    acc: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        label = _innermost(host, mid) or "outside annotations"
        acc[label] = acc.get(label, 0.0) + (b - a)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def _innermost(host: List[list], t: float) -> Optional[str]:
    best = None
    for name, s, d in host:
        if s > t:
            break
        if s <= t <= s + d and (best is None or s >= best[0]):
            best = (s, name)
    return None if best is None else best[1]
