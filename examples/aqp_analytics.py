"""Grouped + join analytics with guarantees, through the Session front door.

    PYTHONPATH=src python examples/aqp_analytics.py

Demonstrates the three client surfaces over one session:
  * plain SQL with `ERROR e% CONFIDENCE p%` (grouped, join, ratio queries),
  * the fluent builder (`session.table(...).where(...).agg(...)`),
  * the concurrent scheduler: a herd of structurally identical queries
    drains as one signature group, compiling once and running warm.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.api import Session, avg_, count_, sum_
from repro.compile_cache import enable_compile_cache
from repro.engine.datagen import tpch_catalog
from repro.engine.expr import Col


def show(name, approx, exact, error_target):
    errs = []
    a, e = approx.result(), exact.result()
    for i in range(len(a.names)):
        for g in range(a.values.shape[1]):
            t = e.values[i, g]
            if e.group_present[g] and np.isfinite(t) and abs(t) > 1e-9:
                errs.append(abs(a.values[i, g] - t) / abs(t))
    r = approx.report
    frac = (r.pilot_scanned_bytes + r.final_scanned_bytes) / r.exact_scanned_bytes
    print(f"[{name}] max err {max(errs):.3%} (target {error_target:.0%}), "
          f"scanned {frac:.1%}, plan={r.plan.rates if r.plan else r.fallback}")


def main():
    rows = int(os.environ.get("EXAMPLE_ROWS", 2_000_000))
    catalog = tpch_catalog(scale_rows=rows, block_rows=32, seed=0)
    session = Session(catalog, seed=7)

    # -- SQL front door ------------------------------------------------------
    grouped = ("SELECT SUM(l_quantity) AS qty, AVG(l_extendedprice) AS avg_price, "
               "COUNT(*) AS orders FROM lineitem GROUP BY l_returnflag "
               "ERROR 5% CONFIDENCE 95%")
    show("grouped Q1", session.sql(grouped),
         session.sql(grouped.split(" ERROR")[0]), 0.05)

    join = ("SELECT SUM(l_extendedprice) AS rev FROM lineitem "
            "JOIN orders ON l_orderkey = o_orderkey WHERE o_orderdate < 1200 "
            "ERROR 5% CONFIDENCE 95%")
    show("join     ", session.sql(join), session.sql(join.split(" ERROR")[0]), 0.05)

    ratio = ("SELECT SUM(l_extendedprice * l_discount * l_linestatus) / "
             "SUM(l_extendedprice * l_discount) AS promo_share FROM lineitem "
             "WHERE l_shipdate BETWEEN 400 AND 2200 ERROR 5% CONFIDENCE 95%")
    show("ratio Q14", session.sql(ratio), session.sql(ratio.split(" ERROR")[0]), 0.05)

    # -- fluent builder (lowers to the identical internal plan) --------------
    builder = (session.table("lineitem")
               .where(Col("l_shipdate") < 2400)
               .group_by("l_returnflag")
               .agg(sum_(Col("l_quantity")).as_("qty"),
                    avg_(Col("l_extendedprice")).as_("avg_price"),
                    count_().as_("orders"))
               .error(0.05, 0.95))
    approx = builder.run()
    exact = (session.table("lineitem")
             .where(Col("l_shipdate") < 2400)
             .group_by("l_returnflag")
             .agg(sum_(Col("l_quantity")).as_("qty"),
                  avg_(Col("l_extendedprice")).as_("avg_price"),
                  count_().as_("orders"))
             .run())
    show("builder  ", approx, exact, 0.05)

    # -- concurrent runtime: one pilot + cached answers for a herd -----------
    herd_sql = ("SELECT SUM(l_extendedprice * l_discount) AS rev FROM lineitem "
                "WHERE l_quantity < 24 ERROR 8% CONFIDENCE 95%")
    warm = session.sql(herd_sql)  # warms compile caches AND the result cache
    handles = [session.submit(herd_sql) for _ in range(16)]
    session.drain()
    stats = session.scheduler.last_drain
    print(f"[runtime] {stats.n_queries} identical queries in "
          f"{stats.n_groups} group(s): {stats.pilots_run} pilot stage(s), "
          f"{stats.compile_misses} new compilations, "
          f"{stats.result_hits} answers from the result cache, "
          f"{stats.wall_time_s*1e3:.0f} ms total")
    assert all(h.status == "done" for h in handles)
    # cached answers are the warm query's original guaranteed answer
    assert all(h.scalar("rev") == warm.scalar("rev") for h in handles)


if __name__ == "__main__":
    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    main()
