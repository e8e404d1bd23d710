"""Quickstart: approximate a SQL query with an a-priori error guarantee.

    PYTHONPATH=src python examples/quickstart.py

Builds a TPC-H-like catalog (EXAMPLE_ROWS rows, default 2M), opens a
:class:`repro.api.Session` — the middleware front door — and answers plain
SQL extended with the paper's `ERROR e% CONFIDENCE p%` clause (§2.4) via
PilotDB's two-stage TAQA algorithm with BSAP block-sampling statistics.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.api import Session
from repro.compile_cache import enable_compile_cache
from repro.engine.datagen import tpch_catalog

SQL = """
SELECT SUM(l_extendedprice * l_discount) AS revenue FROM lineitem
WHERE l_shipdate BETWEEN 100 AND 1500 AND l_discount BETWEEN 0.02 AND 0.08
ERROR 5% CONFIDENCE 95%
"""


def main():
    rows = int(os.environ.get("EXAMPLE_ROWS", 2_000_000))
    print(f"building {rows:,}-row catalog ...")
    catalog = tpch_catalog(scale_rows=rows, block_rows=32, seed=0)
    session = Session(catalog, seed=42)

    t0 = time.perf_counter()
    exact = session.sql(SQL.split("ERROR")[0])  # same query, no ERROR clause
    t_exact = time.perf_counter() - t0

    t0 = time.perf_counter()
    approx = session.sql(SQL)
    t_aqp = time.perf_counter() - t0

    r = approx.report
    err = abs(approx.scalar("revenue") - exact.scalar("revenue")) \
        / exact.scalar("revenue")
    scanned = r.pilot_scanned_bytes + r.final_scanned_bytes
    print(f"exact  : {exact.scalar('revenue'):.6g}   "
          f"({t_exact*1e3:.0f} ms, full scan)")
    print(f"approx : {approx.scalar('revenue'):.6g}   ({t_aqp*1e3:.0f} ms)")
    print(f"achieved error {err:.3%}  (guaranteed <= 5.0% w.p. 95%)")
    print(f"sampling plan  {r.plan.rates if r.plan else r.fallback}")
    print(f"scanned {scanned/r.exact_scanned_bytes:.1%} of the data "
          f"({r.exact_scanned_bytes/scanned:.0f}x fewer bytes)")
    assert approx.status == "done", approx.error
    assert err <= 0.05 or r.fallback is not None  # guarantee held (or exact)


if __name__ == "__main__":
    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    main()
