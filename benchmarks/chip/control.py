"""Read the compared numbers of sound runs and of the control, many seeds
in one process.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 1,2,3 --seconds <s>

For each seed: the cell's tables and session from that seed, the warm-up,
one window of ``--seconds`` at the cell's own load, then the check on the
seed's sample of answers.  Each seed prints one line: the program's
``estimator_gap`` (a lower reading) and ``control_gap``, the same number
with the bfloat16 reference put in the program's place (an upper reading),
beside the guarantee's misses.  The limits in ``limits/<cell>.json`` are
set from these readings.  The benchmark's own runs do not run the control.
Like ``run.py`` it needs a TPU.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def read_seed(c, seed: int, seconds: float) -> dict:
    from chipbench import cell, traffic as gen

    t0 = time.perf_counter()
    tables, session, gateway = cell.build_session(c, seed, False)
    cell.warm_up(c, gateway, seed)
    tr = c.traffic
    if tr["loop"] == "open":
        due, qs = gen.open_loop(tr, seconds, seed)
        recs = cell.open_loop(gateway, due, qs, seconds, False)[0]
    else:
        recs = cell.closed_loop(gateway, gen.closed_pool(tr, seconds, seed),
                                tr["clients"], seconds, False)[0]
    session.close()
    del gateway, session
    chk = cell.check_answers(c, tables, recs, seed, control=True)
    del tables
    gc.collect()
    return {"seed": seed, "queries": len(recs),
            "unanswered": sum(1 for r in recs if not r.ok),
            "checked": chk.checked, "estimator_gap": chk.estimator_gap,
            "control_gap": chk.control_gap, "misses": chk.misses,
            "worst_error_ratio": chk.worst_error_ratio,
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from chipbench import spec

    c = spec.load_cell(args.workload, ROOT)
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < c.chips:
        print(f"needs {c.chips} TPU chip(s)", file=sys.stderr)
        return 2
    for s in args.seeds.split(","):
        print(json.dumps(read_seed(c, int(s), args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
