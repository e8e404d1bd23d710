"""Find the knee of an open-loop mix: step offered rates in one process.

    python3 benchmarks/chip/knee.py --workload <cell> \\
        --traffic q6-slider-open --seed <n> --seconds <s> --rates 10,20,40

The cell gives the configuration, ``--traffic`` an open-loop mix from
``traffic/``.  One set-up (tables, session, warm-up), then one open-loop
window per rate, in the order given, each drained before the next.  Each
rate prints one line: the offered and completed rates, the queries still
unanswered when the window closed (the backlog), latency p50 / p95 over
every query due in the window, and how far the generator's sleeps
overshot.  The knee is the
highest rate whose backlog does not grow with the window; a cell offers
about four fifths of it, written into the mix's file as ``rate_qps``.
Like ``run.py`` it needs a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def sweep(c, gateway, seed: int, seconds: float, rates) -> list:
    from chipbench import cell, traffic as gen

    rows = []
    for k, rate in enumerate(rates):
        tr = dict(c.traffic, rate_qps=rate)
        due, qs = gen.open_loop(tr, seconds, seed + k)
        recs, overshoot, length, _ = cell.open_loop(gateway, due, qs,
                                                    seconds, False)
        ok = [r for r in recs if r.ok]
        backlog = sum(1 for r in recs if not r.ok or r.done > seconds)
        lat = sorted(r.latency * 1e3 for r in recs)
        row = {"offered_qps": rate,
               "completed_qps": sum(1 for r in ok if r.done <= seconds)
               / seconds,
               "backlog_at_close": backlog, "queries": len(recs),
               "failed": len(recs) - len(ok),
               "p50_ms": lat[len(lat) // 2] if lat else None,
               "p95_ms": lat[int(0.95 * (len(lat) - 1))] if lat else None,
               "drained_s": length,
               "max_overshoot_ms": max(overshoot, default=0.0) * 1e3}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--traffic", required=True,
                    help="an open-loop mix in traffic/, by name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from chipbench import cell, spec

    c = spec.load_cell(args.workload, ROOT)
    c.traffic = spec.load_json(os.path.join(HERE, "traffic",
                                            args.traffic + ".json"))
    if c.traffic["loop"] != "open":
        print(f"{args.traffic} is not an open-loop mix", file=sys.stderr)
        return 2
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < c.chips:
        print(f"needs {c.chips} TPU chip(s)", file=sys.stderr)
        return 2
    print(f"device: {cell.device_info(devs)}", flush=True)
    _, session, gateway = cell.build_session(c, args.seed, False)
    cell.warm_up(c, gateway, args.seed)
    print(f"setup {time.perf_counter() - T_START:.3f} s", flush=True)
    sweep(c, gateway, args.seed, args.seconds,
          [float(r) for r in args.rates.split(",")])
    session.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
