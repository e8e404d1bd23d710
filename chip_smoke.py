"""Bring-up smoke run of the served AQP path on a TPU chip.

    python chip_smoke.py                 # TPC-H SF10 on one TPU chip
    python chip_smoke.py --four-chips    # lineitem shards=4 vs shards=1, 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 2000000
                                         # CPU rehearsal (interpret-mode kernels)

One process drives the whole path: ``tpch_catalog`` -> ``Session.sql`` and a
scheduler drain -> TAQA pilot, rate solve, final -> the compiled Pallas scan
kernels.  Every phase prints one line; the last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``ok`` is true only on a TPU, with every phase passed: each query ``done``
with no exact fallback, observed error within its promise against an exact
host (NumPy f64) answer, the expected kernel routes compiled as Mosaic
kernels (``tpu_custom_call``), Pallas and XLA routes agreeing (counts
bitwise, sums to the f32 standard of ``tests/test_physical.py``) and no
swallowed failure on any optimized route.  Off the TPU the phases run only
as a rehearsal at a small ``--rows``, and the run exits nonzero.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import Session, SessionConfig  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.taqa import build_engine_plan  # noqa: E402
from repro.engine import logical as L  # noqa: E402
from repro.engine.datagen import tpch_catalog  # noqa: E402
from repro.engine.physical import ScanRuntime, plan_constants  # noqa: E402
from repro.engine.sampling import pad_block_ids  # noqa: E402

SF10_ROWS = 60_000_000   # TPC-H SF10 lineitem (orders: a quarter of it)
BLOCK_ROWS = 1024        # one (8, 128) f32 tile per column per block
SEED = 0                 # data, sessions and fixed-sample replays
ERROR = 0.05
CLAUSE = "ERROR 5% CONFIDENCE 95%"
SUM_RTOL = 1e-4          # cross-route sum standard (tests/test_physical.py)

Q6 = ("SELECT SUM(l_extendedprice * l_discount) AS revenue FROM lineitem "
      "WHERE l_shipdate BETWEEN 100 AND {hi} "
      "AND l_discount BETWEEN 0.02 AND 0.08 AND l_quantity < 24 " + CLAUSE)
PLAIN = "SELECT SUM(l_extendedprice) AS price FROM lineitem " + CLAUSE
GROUPED = ("SELECT SUM(l_quantity) AS qty, COUNT(*) AS cnt FROM lineitem "
           "GROUP BY l_returnflag " + CLAUSE)
HERD_HIS = (1500, 1501, 1502, 1503)   # (c): constant-slid Q6 drain


class Smoke:
    """Phase bookkeeping: every check prints one line and records failures."""

    def __init__(self):
        self.failures = []

    def check(self, ok: bool, what: str) -> bool:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failures.append(what)
        return ok


# -- exact answers on the host: NumPy f64, independent of the engine ---------

class HostLineitem:
    def __init__(self, table):
        n = table.num_rows
        self.cols = {c: np.asarray(table.columns[c])[:n]
                     for c in ("l_extendedprice", "l_discount", "l_quantity",
                               "l_shipdate", "l_returnflag")}

    def q6(self, hi: int) -> np.ndarray:
        c = self.cols
        disc = c["l_discount"]
        keep = ((c["l_shipdate"] >= 100) & (c["l_shipdate"] <= hi)
                & (disc >= np.float32(0.02)) & (disc <= np.float32(0.08))
                & (c["l_quantity"] < np.float32(24)))
        rev = (c["l_extendedprice"].astype(np.float64)[keep]
               * disc.astype(np.float64)[keep]).sum()
        return np.array([[rev]])

    def plain(self) -> np.ndarray:
        return np.array([[self.cols["l_extendedprice"].sum(dtype=np.float64)]])

    def grouped(self, max_groups: int) -> np.ndarray:
        g = self.cols["l_returnflag"]
        qty = np.bincount(g, self.cols["l_quantity"].astype(np.float64),
                          minlength=max_groups)
        cnt = np.bincount(g, minlength=max_groups).astype(np.float64)
        return np.stack([qty, cnt])


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def table_device_bytes(table) -> int:
    arrays = list(table.columns.values()) + [table.valid, table.block_id]
    return sum(int(a.size) * a.dtype.itemsize for a in arrays)


def memory_stats(device) -> dict:
    try:
        return device.memory_stats() or {}
    except Exception:  # noqa: BLE001 — a backend without memory statistics
        return {}


def executables(session: Session) -> list:
    return session.executor.physical.executables()


def lowered_text(compiled) -> str:
    """The compiled text of the program an executable's latest launch ran."""
    return compiled.fn.lower(compiled.last_args).compile().as_text()


def run_and_check(smoke: Smoke, session: Session, label: str, sqls, exact,
                  *, drain: bool = False):
    """Run ``sqls`` (synchronously, or as one scheduler drain), check each
    handle and its observed error; returns (handles, seconds)."""
    t0 = time.perf_counter()
    if drain:
        handles = [session.submit(s) for s in sqls]
        session.drain()
        for h in handles:
            h.wait(timeout=600)
    else:
        handles = [session.sql(s) for s in sqls]
    # answers are host arrays: the device work behind them has completed
    secs = time.perf_counter() - t0
    for h, ref in zip(handles, exact):
        ok = smoke.check(h.status == "done",
                         f"{label}: status {h.status} {h.error or ''}".rstrip())
        if not ok:
            continue
        smoke.check(h.report.fallback is None,
                    f"{label}: no exact fallback ({h.report.fallback})")
        got = h.result().values
        present = ref != 0
        rel = np.abs(got[present] - ref[present]) / np.abs(ref[present])
        smoke.check(rel.max() <= ERROR,
                    f"{label}: observed rel. error {rel.max():.6g} "
                    f"(promised <= {ERROR}; answer {got.ravel().tolist()}, "
                    f"exact {ref.ravel().tolist()})")
    return handles, secs


def warm_latency(session: Session, sql: str, reps: int = 3) -> float:
    """Median warm latency: compiled programs cached, result cache cleared."""
    times = []
    for _ in range(reps):
        session.result_cache.clear()
        t0 = time.perf_counter()
        h = session.sql(sql)
        h.result()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def fixed_plan(session: Session, sql: str, rate: float, seed: int):
    plan, _ = build_engine_plan(session.prepare(sql).query)
    return L.rewrite_scans(
        plan, {"lineitem": L.SampleClause("block", rate, seed)})


def one_chip(smoke: Smoke, args, on_tpu: bool) -> None:
    rows = args.rows or SF10_ROWS
    print(f"phase data: tpch_catalog(scale_rows={rows}, "
          f"block_rows={BLOCK_ROWS}, seed={SEED})", flush=True)
    t0 = time.perf_counter()
    catalog = tpch_catalog(scale_rows=rows, block_rows=BLOCK_ROWS,
                           seed=SEED)
    jax.block_until_ready([t.columns for t in catalog.values()])
    li = catalog["lineitem"]
    print(f"  built in {time.perf_counter() - t0:.3f} s: lineitem "
          f"{li.num_rows} rows / {li.num_blocks} blocks, orders "
          f"{catalog['orders'].num_rows} rows", flush=True)

    dev = jax.devices()[0]
    print("phase bytes:", flush=True)
    for name, tab in catalog.items():
        print(f"  {name}: {table_device_bytes(tab)} bytes on the device")
    # the Q6 kernel route's per-query f32 operands that are not table
    # columns already: valid (bool), l_shipdate (int32) and a ones column
    print(f"  Q6 route transient f32 operands: {3 * li.padded_rows * 4} bytes")
    print(f"  bytes_in_use after load: "
          f"{memory_stats(dev).get('bytes_in_use', 'not reported')}", flush=True)

    host = HostLineitem(li)
    config = SessionConfig() if on_tpu else SessionConfig(kernel_mode="pallas")
    session = Session(catalog, seed=SEED, config=config)
    grouped_mg = session.infer_max_groups(("lineitem",), "l_returnflag")
    phases = [  # key, label, SQL, exact answers, as a drain?, route
        ("a", "Q6", [Q6.format(hi=1500)], [host.q6(1500)], False,
         "pallas_filtered"),
        ("b", "filterless SUM", [PLAIN], [host.plain()], False,
         "pallas_block"),
        ("c", "4-query Q6 drain", [Q6.format(hi=h) for h in HERD_HIS],
         [host.q6(h) for h in HERD_HIS], True, "pallas_filtered_batched"),
        ("d", "GROUP BY l_returnflag", [GROUPED], [host.grouped(grouped_mg)],
         False, "xla_gather"),
    ]
    answers = {}
    for key, label, sqls, exact, drain, route in phases:
        print(f"phase query ({key}) {label}:", flush=True)
        before = {c.route for c in executables(session)}
        handles, cold = run_and_check(smoke, session, f"({key})", sqls, exact,
                                      drain=drain)
        routes = {c.route for c in executables(session)} - before
        smoke.check(route in routes, f"({key}) routes compiled: "
                    f"{sorted(routes)} (expected {route})")
        answers[key] = (sqls, exact, handles)
        if not drain and all(h.status == "done" for h in handles):
            warm = warm_latency(session, sqls[0])
            print(f"  cold (compile + run) {cold:.6f} s, warm {warm:.6f} s")
        else:
            print(f"  cold (compile + run) {cold:.6f} s")

    print("phase kernels compiled (no interpret mode):", flush=True)
    kernel_routes = [c for c in executables(session)
                     if c.route.startswith("pallas")]
    smoke.check(bool(kernel_routes), "kernel-route executables were built")
    for c in kernel_routes:
        if not smoke.check(c.last_args is not None, f"{c.route}: launched"):
            continue
        if on_tpu:
            smoke.check("tpu_custom_call" in lowered_text(c),
                        f"{c.route}: tpu_custom_call in the compiled program")
    if not on_tpu:
        print("  [skip] tpu_custom_call: not checked off the TPU")

    print("phase cross-route (kernel_mode='xla'):", flush=True)
    xla = Session(catalog, seed=SEED,
                  config=SessionConfig(kernel_mode="xla"))
    for key in ("a", "b", "c"):
        sqls, exact, _ = answers[key]
        run_and_check(smoke, xla, f"xla ({key})", sqls, exact,
                      drain=key == "c")
    for key in ("a", "b", "c"):
        sqls, _, handles = answers[key]
        if not all(h.status == "done" and h.report.plan for h in handles):
            smoke.check(False, f"({key}) cross-route: no sampled plan to replay")
            continue
        plans = [fixed_plan(session, s, h.report.plan.rates["lineitem"],
                            SEED + i)
                 for i, (s, h) in enumerate(zip(sqls, handles))]
        if len(plans) == 1:
            kern = [session.executor.execute(plans[0])]
            ref = [xla.executor.execute(plans[0])]
        else:
            kern = session.executor.execute_batch(plans)
            ref = xla.executor.execute_batch(plans)
        for i, (k, r) in enumerate(zip(kern, ref)):
            smoke.check(np.array_equal(k.group_counts, r.group_counts),
                        f"({key}) member {i}: sampled row counts bitwise "
                        f"equal ({k.group_counts.tolist()})")
            rel = np.abs(k.raw_sums - r.raw_sums) / np.maximum(
                np.abs(r.raw_sums), 1e-30)
            smoke.check(bool(np.allclose(k.raw_sums, r.raw_sums,
                                         rtol=SUM_RTOL, atol=SUM_RTOL)),
                        f"({key}) member {i}: sums agree, max rel. diff "
                        f"{rel.max():.3g} (rtol {SUM_RTOL})")

    print("phase swallowed failures:", flush=True)
    for name, s in (("kernel session", session), ("xla session", xla)):
        ex = s.executor
        smoke.check(ex.swallowed_failures == 0,
                    f"{name}: {ex.swallowed_failures} swallowed "
                    f"({ex.last_swallowed})")
    stats = memory_stats(dev)
    print(f"phase memory: peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    session.close()
    xla.close()


def four_chips(smoke: Smoke, args) -> None:
    devs = jax.devices()
    smoke.check(len(devs) == 4, f"four devices visible ({len(devs)})")
    if len(devs) != 4:
        return
    rows = args.rows or SF10_ROWS
    print(f"phase data: tpch_catalog(scale_rows={rows}, "
          f"block_rows={BLOCK_ROWS}, seed={SEED})", flush=True)
    catalog = tpch_catalog(scale_rows=rows, block_rows=BLOCK_ROWS,
                           seed=SEED)
    li = catalog["lineitem"]
    on_tpu = devs[0].platform == "tpu"
    config = SessionConfig() if on_tpu else SessionConfig(kernel_mode="pallas")
    sessions = {}
    for n in (4, 1):
        s = Session(catalog, seed=SEED, config=config)
        s.register_table("lineitem", li, shards=n)
        sessions[n] = s
    mono = table_device_bytes(li)
    print(f"  monolithic lineitem copy on {devs[0]}: {mono} bytes "
          f"(kept by every sharded registration)")
    for d in devs:
        print(f"  {d}: bytes_in_use "
              f"{memory_stats(d).get('bytes_in_use', 'not reported')}")

    print("phase shard placement (shards=4):", flush=True)
    sharded, executors = sessions[4].executor._shard_snapshot("lineitem")
    stripped = L.strip_samples(fixed_plan(sessions[4], Q6.format(hi=1500),
                                          0.05, SEED))
    for shard, ex in zip(sharded.shards, executors):
        want = {devs[shard.index % 4]}
        placed = all(a.devices() == want for a in
                     list(shard.table.columns.values())
                     + [shard.table.valid, shard.table.block_id])
        smoke.check(placed, f"shard {shard.index}: columns on {want}")
        ids = np.arange(min(8, shard.num_blocks))
        phys, n_real, _ = pad_block_ids(ids, shard.num_blocks)
        rt = ScanRuntime("block", n_real, len(phys), phys)
        compiled = ex.physical.compile_pilot(stripped, "lineitem", rt)
        out = compiled({"lineitem": rt}, plan_constants(stripped))[0]
        smoke.check(out.devices() == want and compiled.route.startswith(
            "pallas"), f"shard {shard.index}: {compiled.route} output on "
            f"{out.devices()}")

    print("phase shards=4 vs shards=1:", flush=True)
    host = HostLineitem(li)
    for label, sql, ref in (("Q6", Q6.format(hi=1500), host.q6(1500)),
                            ("filterless SUM", PLAIN, host.plain())):
        got = {}
        for n, s in sessions.items():
            hs, _ = run_and_check(smoke, s, f"{label} shards={n}", [sql], [ref])
            got[n] = hs[0]
        if all(h.status == "done" for h in got.values()):
            smoke.check(np.array_equal(got[4].result().values,
                                       got[1].result().values),
                        f"{label}: shards=4 and shards=1 answers bitwise "
                        f"equal ({got[4].result().values.ravel().tolist()})")
    for n, s in sessions.items():
        ex = s.executor
        smoke.check(ex.swallowed_failures == 0,
                    f"shards={n}: {ex.swallowed_failures} swallowed "
                    f"({ex.last_swallowed})")
        s.close()
    for d in devs:
        print(f"  {d}: peak_bytes_in_use "
              f"{memory_stats(d).get('peak_bytes_in_use', 'not reported')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="lineitem rows (default: TPC-H SF10, 60M; required "
                         "off the TPU, where the run is a rehearsal)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shards=4 vs shards=1 phase on 4 chips")
    args = ap.parse_args(argv)

    device = device_info()
    on_tpu = device["platform"] == "tpu"
    print(f"device: {device}", flush=True)
    if not on_tpu and args.rows is None:
        print("no TPU found: pass --rows for a CPU rehearsal", file=sys.stderr)
        return 2
    smoke = Smoke()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(smoke, args)
    else:
        one_chip(smoke, args, on_tpu)
    print(f"total {time.perf_counter() - t0:.3f} s; "
          f"{len(smoke.failures)} failed check(s)", flush=True)
    ok = on_tpu and not smoke.failures
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    enable_compile_cache(ROOT)
    sys.exit(main())
