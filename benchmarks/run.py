"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; detailed JSON lands in
benchmarks/results/.  The ``compiled`` bench additionally emits the
machine-readable ``BENCH_compiled.json`` at the repo root (eager vs compiled
latency, compile-cache hit rate, scanned bytes) for trajectory tracking.
BENCH_ROWS env var scales the data (default 2M rows).

  PYTHONPATH=src python -m benchmarks.run [--only <name>]
"""

import argparse
import json
import os
import sys
import time

from repro.compile_cache import enable_compile_cache

BENCH_COMPILED_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_compiled.json")


def _emit_bench_compiled(payload: dict) -> None:
    """Flatten the compiled-vs-eager payload into the root JSON artifact."""
    from benchmarks.common import SCALE_ROWS  # the size the data was built at
    doc = {"bench": "compiled", "rows": SCALE_ROWS}
    for name, entry in payload.items():
        if name == "constant_sweep":
            doc[name] = dict(entry)  # already flat; misses must stay <= 2
            continue
        doc[name] = {
            "eager_steady_s": entry["eager"]["steady_state_s"],
            "compiled_steady_s": entry["compiled"]["steady_state_s"],
            "compiled_first_call_s": entry["compiled"]["first_call_s"],
            "steady_speedup": entry["steady_speedup"],
            "cache_hit_rate": entry["cache"]["hit_rate"],
            "cache_hits": entry["cache"]["hits"],
            "cache_misses": entry["cache"]["misses"],
            "pilot_scanned_bytes": entry["scanned_bytes"]["pilot"],
            "final_scanned_bytes": entry["scanned_bytes"]["final"],
            "scanned_bytes_equal": entry["scanned_bytes_equal"],
        }
    with open(BENCH_COMPILED_PATH, "w") as f:
        json.dump(doc, f, indent=1, default=float)
    print(f"# wrote {os.path.normpath(BENCH_COMPILED_PATH)}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single bench: guarantees|naive_clt|scan|"
                         "speedup|quickr|ablation|kernels|compiled|runtime|"
                         "dist|staged|stream|obs|fused")
    args = ap.parse_args()

    from benchmarks import (bench_ablation, bench_compiled, bench_dist,
                            bench_fused, bench_guarantees, bench_kernels,
                            bench_naive_clt, bench_obs, bench_quickr,
                            bench_runtime, bench_scan, bench_speedup,
                            bench_staged, bench_stream)

    benches = {
        "scan": bench_scan.run,              # Fig. 4
        "guarantees": bench_guarantees.run,  # Fig. 6/7
        "speedup": bench_speedup.run,        # Fig. 8/9/10
        "quickr": bench_quickr.run,          # Fig. 11/12 + Table 5
        "ablation": bench_ablation.run,      # Tables 4/5, Lemma 4.1, Fig. 13-15
        "naive_clt": bench_naive_clt.run,    # Fig. 16/17 (Appendix A.1)
        "kernels": bench_kernels.run,        # kernel-layer system model
        "compiled": bench_compiled.run,      # eager vs compiled physical layer
        "runtime": bench_runtime.run,        # serving herd: async/share/cache
        "dist": bench_dist.run,              # shard-parallel execution
        "staged": bench_staged.run,          # pre-staged sample-catalog ladders
        "stream": bench_stream.run,          # progressive frames: TTFF vs final
        "obs": bench_obs.run,                # tracing overhead + audit honesty
        "fused": bench_fused.run,            # single-launch TAQA vs two-stage
    }
    todo = [args.only] if args.only else list(benches)
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for name in todo:
        try:
            payload = benches[name]()
            if name == "compiled" and payload:
                _emit_bench_compiled(payload)
        except Exception as e:  # keep the harness going; failures are visible
            print(f"{name},nan,FAILED:{type(e).__name__}:{e}")
            failed.append(name)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        # every bench asserts its own invariants (compile-miss bounds,
        # bit-identity): one that failed fails the run
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache(os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    main()
