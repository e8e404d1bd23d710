"""One run of one cell: set-up, the measured window, then the check.

The window drives ``SqlGateway.submit`` / ``SqlGateway.run`` over a default
``Session`` from one thread: an open loop submits each query when it is
due and drains whatever is queued; a closed loop keeps ``clients`` queries
outstanding.  A query is timed from its due time to the ``run`` call that
hands back its finished handle.
"""

from __future__ import annotations

import dataclasses
import math
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import data, devtrace, reference, spec, traffic as gen

LATE_WAIT_S = 60.0      # an answer due in the window may come this late
MISS_LEVEL = 1e-3       # a program that meets its confidence exactly fails
                        # the guarantee check with at most this chance
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Rec:
    query: gen.Query
    due: float                       # seconds after the window opened
    done: Optional[float] = None
    handle: object = None
    refused: Optional[str] = None

    @property
    def ok(self) -> bool:
        return (self.refused is None and self.handle is not None
                and self.handle.status == "done")

    @property
    def latency(self) -> float:
        return self.done - self.due if self.ok else float("inf")


@dataclasses.dataclass
class Window:
    """What a per-layer metric reads: the queries of the window, their span
    trees, the reduced device trace and the cell."""

    cell: spec.Cell
    recs: List[Rec]
    origin: float                    # perf_counter at the window's start
    trace: Optional[dict] = None     # devtrace.extract form
    t0_ns: float = 0.0               # the window in the trace's clock
    t1_ns: float = 0.0
    peak: Optional[Dict[str, float]] = None

    def spans(self, rec: Rec) -> Optional[dict]:
        h = rec.handle
        if h is None or getattr(h, "_trace", None) is None:
            return None
        return h.trace()["root"]


class _CompileCounter:
    """Counts backend compilations between ``with`` entry and exit."""

    def __init__(self):
        self.count = 0

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory(device, key: str) -> Optional[int]:
    try:
        stats = device.memory_stats() or {}
    except Exception:  # noqa: BLE001 — a backend without memory statistics
        return None
    return stats.get(key)


def log(msg: str) -> None:
    """Progress lines go to standard error: the result is standard
    output's last line."""
    print(msg, file=sys.stderr, flush=True)


# -- set-up -------------------------------------------------------------------

def build_session(cell: spec.Cell, seed: int, tracing: bool):
    from repro.api import Session, SessionConfig
    from repro.serve.sql_gateway import SqlGateway

    tables = data.make_tables(cell.config, seed)
    session = Session(tables, seed=seed,
                      config=SessionConfig(tracing=tracing))
    return tables, session, SqlGateway(session)


def warm_up(cell: spec.Cell, gateway, seed: int) -> int:
    """Run the traffic file's warm-up drains, then the same queries again
    ordered by the bytes each one's final scan read, in drains of each of
    ``by_size_drain_sizes``, cut once from the smallest and once from the
    largest end: neighbours by size share the program's sample-size
    shapes, so a drain of them compiles the batch of its size at that
    shape, and a drain of 7 those of 4, 2 and 1; cutting from both ends
    lines the drains up with the rare sizes at either end.  Last, the
    first 1, 2, ... up to the largest of those sizes from each end, each as
    a drain of its own: a rare size that only a few queries reach gets
    every batch of as many as it has.  A query's sample is a function of
    the session seed and the query's text, so the second time each query
    draws the same blocks.  Returns the queries run."""
    def drain(queries) -> list:
        tickets = {gateway.submit("warmup", q.sql): q for q in queries}
        out = []
        for qid, h in gateway.run().items():
            if h.status != "done":
                raise RuntimeError(f"warm-up query failed: {h.error}")
            out.append((h.report.final_scanned_bytes, tickets[qid]))
        return out

    n, seen = 0, []
    for queries in gen.warmup(cell.traffic, seed):
        seen += drain(queries)
        n += len(queries)
    seen.sort(key=lambda bq: bq[0])
    by_size = [q for _, q in seen]
    sizes = cell.traffic["warmup"].get("by_size_drain_sizes", [])
    for order in (by_size, by_size[::-1]):
        for size in sizes:
            gateway.session.result_cache.clear()
            for i in range(0, len(order) - size + 1, size):
                drain(order[i:i + size])
                n += size
        for size in range(1, min(max(sizes, default=0), len(order)) + 1):
            gateway.session.result_cache.clear()
            drain(order[:size])
            n += size
    gateway.session.result_cache.clear()
    return n


# -- the window ---------------------------------------------------------------

def _annotate(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else _Null()


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _deliver(gateway, recs_by_ticket: Dict[int, Rec], t: float) -> None:
    for qid, h in gateway.run().items():
        rec = recs_by_ticket.pop(qid, None)
        if rec is not None:
            rec.done, rec.handle = t(), h


def open_loop(gateway, due: np.ndarray, queries: List[gen.Query],
              seconds: float, traced: bool) -> tuple:
    """Submit each query when due, drain what is queued; returns
    (records, sleep overshoots in seconds, window length)."""
    from repro.runtime import BackpressureError

    recs = [Rec(q, float(d)) for q, d in zip(queries, due)]
    pending: Dict[int, Rec] = {}
    overshoot: List[float] = []
    origin = time.perf_counter()

    def now() -> float:
        return time.perf_counter() - origin

    i = 0
    deadline = seconds + LATE_WAIT_S
    while (i < len(recs) or pending) and now() < deadline:
        while i < len(recs) and recs[i].due <= now():
            rec = recs[i]
            with _annotate("bench.submit", traced):
                try:
                    pending[gateway.submit("c", rec.query.sql)] = rec
                except BackpressureError as e:
                    rec.refused = str(e)
            i += 1
        if pending:
            with _annotate("bench.run", traced):
                _deliver(gateway, pending, now)
        elif i < len(recs):
            target = recs[i].due
            with _annotate("bench.wait", traced):
                time.sleep(max(target - now(), 0.0))
            overshoot.append(now() - target)
    return recs, overshoot, max(now(), seconds), origin


def closed_loop(gateway, pool: List[gen.Query], clients: int,
                seconds: float, traced: bool) -> tuple:
    """Keep ``clients`` queries outstanding until ``seconds`` have passed;
    returns (records, [], window length = until the last drain ended)."""
    recs: List[Rec] = []
    origin = time.perf_counter()

    def now() -> float:
        return time.perf_counter() - origin

    k = 0
    while now() < seconds:
        if k + clients > len(pool):
            raise RuntimeError("closed-loop query pool exhausted: raise "
                               "pool_qps in the traffic file")
        pending: Dict[int, Rec] = {}
        t = now()
        for q in pool[k:k + clients]:
            rec = Rec(q, t)
            recs.append(rec)
            with _annotate("bench.submit", traced):
                pending[gateway.submit("c", q.sql)] = rec
        k += clients
        with _annotate("bench.run", traced):
            _deliver(gateway, pending, now)
    return recs, [], now(), origin


def _scan_reduction(cell: spec.Cell, rec: Rec) -> float:
    """The paper's speedup over the exact query, as bytes: the scanned
    table's bytes over what the answer scanned (information only)."""
    t = cell.traffic["templates"][rec.query.template]["table"]
    rep = rec.handle.report
    scanned = rep.pilot_scanned_bytes + rep.final_scanned_bytes
    whole = data.padded_rows(cell.config, t) * sum(
        np.dtype(c["dtype"]).itemsize
        for c in cell.config["tables"][t]["columns"].values())
    return whole / scanned if scanned else 1.0


# -- end-to-end metrics ------------------------------------------------------

def end_to_end(cell: spec.Cell, recs: List[Rec], seconds: float,
               setup_s: float) -> Dict[str, float]:
    lat = [r.latency * 1e3 for r in recs]
    done = sum(1 for r in recs if r.ok)
    values = {
        "setup_s": setup_s,
        "latency_p50_ms": float(np.percentile(lat, 50)) if lat else None,
        "latency_p95_ms": float(np.percentile(lat, 95)) if lat else None,
        "qps": done / seconds if seconds > 0 else None,
    }
    out = {}
    for m in cell.end_to_end:
        if values.get(m["name"]) is None:
            raise KeyError(f"no end-to-end reading for {m['name']!r}")
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


# -- the check ----------------------------------------------------------------

@dataclasses.dataclass
class Check:
    checked: int = 0
    estimator_gap: float = 0.0       # program vs the replay of its sample
    misses: int = 0                  # answers outside their promised error
    worst_error_ratio: float = 0.0   # observed / promised error, worst
    control_gap: Optional[float] = None


def check_answers(cell: spec.Cell, tables, recs: List[Rec], seed: int,
                  control: bool = False) -> Check:
    """Compare a seed-drawn sample of the window's answers with the plain
    reference: the exact answer (the guarantee) and the replay of the
    realized final sample (the estimator).  With ``control`` the bfloat16
    reference is also read in the program's place."""
    done = [r for r in recs if r.ok]
    k = min(int(cell.traffic["check_sample"]), len(done))
    pick = gen.rng_for(seed, 7).choice(len(done), size=k, replace=False)
    out = Check()
    for j in sorted(pick):
        rec = done[j]
        t = cell.traffic["templates"][rec.query.template]
        tab = tables[t["table"]]
        parts = reference.block_partials(tab.columns, tab.num_rows,
                                         tab.block_rows, t, rec.query.params)
        exact = reference.exact(t, parts)
        ans = rec.handle.result()
        rep = ans.report
        rate = (rep.plan.rates.get(t["table"], 1.0)
                if rep.fallback is None and rep.plan is not None else 1.0)
        if rate < 1.0:
            ids = reference.final_sample(tab.num_blocks, rate,
                                         rec.handle.seed)
            want = reference.replay(t, parts, ids)
        else:
            ids, want = None, exact
        got = np.asarray(ans.values, float)
        out.estimator_gap = max(out.estimator_gap,
                                reference.rel_gap(got, want))
        err = reference.rel_gap(got, exact)
        promised = t["error_pct"] / 100.0
        out.worst_error_ratio = max(out.worst_error_ratio, err / promised)
        out.misses += err > promised
        out.checked += 1
        if control:
            low = reference.block_partials(
                tab.columns, tab.num_rows, tab.block_rows, t,
                rec.query.params, dtype=jax.numpy.bfloat16)
            lw = reference.exact(t, low) if ids is None else \
                reference.replay(t, low, ids)
            out.control_gap = max(out.control_gap or 0.0,
                                  reference.rel_gap(lw, want))
    return out


def miss_limit(k: int, p: float) -> int:
    """The fewest misses ``m`` among ``k`` answers, each missing its error
    with chance ``p``, that are exceeded with chance at most
    ``MISS_LEVEL``: the upper quantile of Binomial(k, p)."""
    cdf = 0.0
    for m in range(k + 1):
        cdf += math.comb(k, m) * p ** m * (1.0 - p) ** (k - m)
        if 1.0 - cdf <= MISS_LEVEL:
            return m
    return k


def check_lines(cell: spec.Cell, chk: Check, recs: List[Rec],
                limits: dict) -> List[dict]:
    """Each compared number beside its limit; the run is correct when
    every number is within its limit."""
    conf = min(t["confidence_pct"] for t in cell.traffic["templates"])
    unanswered = sum(1 for r in recs if not r.ok)
    return [
        {"name": "unanswered", "value": unanswered, "limit": 0},
        {"name": "estimator_gap", "value": chk.estimator_gap,
         "limit": limits["estimator_gap"]},
        {"name": "guarantee_misses", "value": chk.misses,
         "limit": miss_limit(chk.checked, 1.0 - conf / 100.0)},
    ]


# -- one run ------------------------------------------------------------------

def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, limits: dict, stage_log=log) -> dict:
    """Set up, measure, check; returns the result object (the last line)."""
    devs = jax.devices()
    device = device_info(devs)
    stage_log(f"device: {device}")

    tables, session, gateway = build_session(cell, seed, traced)
    sizes = ", ".join(f"{n} {t.num_rows} rows / {t.num_blocks} blocks"
                      for n, t in tables.items())
    stage_log(f"tables: {sizes}; "
              f"{data.table_bytes(cell.config)} bytes from shapes; "
              f"bytes_in_use after load {memory(devs[0], 'bytes_in_use')} "
              f"({sum(a.nbytes for a in jax.live_arrays())} bytes in live "
              f"arrays)")
    n_warm = warm_up(cell, gateway, seed)
    setup_s = time.perf_counter() - t_start
    stage_log(f"setup: {setup_s:.6f} s ({n_warm} warm-up queries; "
              f"compile cache {session.compile_cache_info()})")

    tr = cell.traffic
    misses0 = session.compile_cache_info().misses
    hits0 = session.result_cache_info().hits
    profile_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
    with _CompileCounter() as counter, _annotate("bench.window", traced):
        if tr["loop"] == "open":
            due, qs = gen.open_loop(tr, seconds, seed)
            recs, overshoot, length, origin = open_loop(
                gateway, due, qs, seconds, traced)
        else:
            recs, overshoot, length, origin = closed_loop(
                gateway, gen.closed_pool(tr, seconds, seed), tr["clients"],
                seconds, traced)
    if traced:
        jax.profiler.stop_trace()
    window_compiles = counter.count
    cache_after = session.compile_cache_info()
    engine_misses = cache_after.misses - misses0
    result_hits = session.result_cache_info().hits - hits0
    peak = memory(devs[0], "peak_bytes_in_use")
    stage_log(f"window: {len(recs)} queries in {length:.6f} s; "
              f"{sum(r.ok for r in recs)} answered; compiles inside the "
              f"window: {window_compiles} (engine compile-cache misses "
              f"{engine_misses}; after the window {cache_after}); "
              f"result-cache hits {result_hits}; "
              f"peak_bytes_in_use {peak}")
    if overshoot:
        stage_log(f"generator lateness: median "
                  f"{statistics.median(overshoot) * 1e3:.6f} ms, max "
                  f"{max(overshoot) * 1e3:.6f} ms over {len(overshoot)} "
                  f"sleeps")
    reduction = [_scan_reduction(cell, r) for r in recs if r.ok]
    if reduction:
        stage_log(f"information: table bytes / bytes scanned (pilot + final), "
                  f"median over answers {statistics.median(reduction):.6f}")

    result = {"attempted": len(recs),
              "failed": sum(1 for r in recs if not r.ok),
              "device": dict(device, memory_peak_bytes=peak)}
    if traced:
        w = Window(cell, recs, origin)
        w.trace = devtrace.extract(profile_dir)
        shutil.rmtree(profile_dir, ignore_errors=True)
        w.t0_ns, w.t1_ns = devtrace.window(w.trace)
        w.peak = spec.peak_for(cell.peaks, device["kind"]) \
            if device["platform"] == "tpu" else None
        busy = devtrace.busy_ns(w.trace, w.t0_ns, w.t1_ns) / 1e9
        result["device"].update(busy_s=busy,
                                window_s=(w.t1_ns - w.t0_ns) / 1e9)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(w.trace, w.t0_ns, w.t1_ns),
            "idle_gaps": devtrace.idle_gaps(w.trace, w.t0_ns, w.t1_ns)}
    else:
        result["metrics"] = end_to_end(cell, recs, length, setup_s)

    # the check runs after the window, with the program's state released
    for r in recs:
        if r.ok:
            r.handle.result()
    session.close()
    del gateway, session
    chk = check_answers(cell, tables, recs, seed)
    lines = check_lines(cell, chk, recs, limits)
    stage_log(f"check: {chk.checked} answers compared; worst observed "
              f"error {chk.worst_error_ratio:.6g} of the promise")
    result["correct"] = all(c["value"] <= c["limit"] for c in lines)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in lines}
    for c in lines:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return result
