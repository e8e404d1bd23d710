"""The one traffic generator: it reads a traffic file and nothing else.

A traffic file gives a loop kind (``open`` at ``rate_qps``, or ``closed``
with ``clients``), weighted query templates with their constants, and the
warm-up drains.  A template is structured (table, select list, predicates,
grouping, guarantee): the generator renders its SQL and the plain reference
evaluates the same structure, so the two cannot drift apart.

Every seed gets the same multiset of work in another order: the template
counts and each constant's strata are fixed by the count of queries, and
the seed shuffles them.  An open loop's arrivals (the exponential's
quantiles as gaps, in an order drawn once from the mix's ``arrival_seed``)
are the same in every run.  So runs with different seeds differ by which
query comes when, not by how much work they hold or how it bursts.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class Query:
    template: int            # index into the traffic file's templates
    params: Dict[str, float]
    sql: str


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), *stream])


def _fmt(v) -> str:
    if float(v).is_integer():
        return str(int(v))
    return f"{v:.6g}"


def _expr_sql(e) -> str:
    if isinstance(e, str):
        return e
    op, a, b = e
    return f"{_expr_sql(a)} {op} {_expr_sql(b)}"


def render_sql(t: dict, params: Dict[str, float]) -> str:
    items = []
    for name, op, expr in t["select"]:
        arg = "*" if expr is None else _expr_sql(expr)
        items.append(f"{op.upper()}({arg}) AS {name}")
    preds = []
    for p in t["where"]:
        if p[0] == "between":
            preds.append(f"{p[1]} BETWEEN {_fmt(params[p[2]])} "
                         f"AND {_fmt(params[p[3]])}")
        else:
            preds.append(f"{p[1]} {p[0]} {_fmt(params[p[2]])}")
    sql = f"SELECT {', '.join(items)} FROM {t['table']}"
    if preds:
        sql += " WHERE " + " AND ".join(preds)
    if t.get("group_by"):
        sql += f" GROUP BY {t['group_by']} MAXGROUPS {t['max_groups']}"
    return (sql + f" ERROR {_fmt(t['error_pct'])}% "
            f"CONFIDENCE {_fmt(t['confidence_pct'])}%")


def _balanced(values: Sequence, n: int, rng: np.random.Generator) -> list:
    """``n`` picks from ``values``, each as often as ``n`` allows, shuffled."""
    out = [values[i % len(values)] for i in range(n)]
    order = rng.permutation(n)
    return [out[i] for i in order]


def _param_values(spec: dict, n: int, rng: np.random.Generator,
                  done: Dict[str, list]) -> list:
    kind = spec["kind"]
    if kind == "int":  # one draw per stratum of [lo, hi], strata shuffled
        lo, hi = int(spec["lo"]), int(spec["hi"])
        width = (hi - lo + 1) / n
        strata = rng.permutation(n)
        u = rng.random(n)
        return [lo + min(int((s + x) * width), hi - lo)
                for s, x in zip(strata, u)]
    if kind == "choice":
        return _balanced(list(spec["values"]), n, rng)
    if kind == "linear":
        return [round(spec.get("scale", 1) * v + spec.get("add", 0), 9)
                for v in done[spec["of"]]]
    raise ValueError(f"unknown parameter kind {kind!r}")


def _template_counts(templates: List[dict], n: int) -> List[int]:
    w = np.array([t.get("weight", 1.0) for t in templates], float)
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[: n - counts.sum()] += 1  # largest-first is not needed: weights tie
    return counts.tolist()


def queries(traffic: dict, n: int, seed: int, stream: int = 0) -> List[Query]:
    """``n`` queries of ``traffic``: the same multiset for every seed, in a
    seed-drawn order.  ``stream`` separates warm-up from measured queries."""
    templates = traffic["templates"]
    out: List[Query] = []
    for ti, (t, k) in enumerate(zip(templates,
                                    _template_counts(templates, n))):
        rng = rng_for(seed, stream, ti)
        done: Dict[str, list] = {}
        for pname, spec in t["params"].items():
            done[pname] = _param_values(spec, k, rng, done)
        for i in range(k):
            params = {p: done[p][i] for p in done}
            out.append(Query(ti, params, render_sql(t, params)))
    order = rng_for(seed, stream, len(templates)).permutation(len(out))
    return [out[i] for i in order]


def arrivals(n: int, seconds: float, seed: int) -> np.ndarray:
    """Due times of ``n`` Poisson arrivals spread over ``seconds``: the
    gaps are the exponential's ``n`` quantiles in a seed-drawn order."""
    if n == 0:
        return np.zeros(0)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps = gaps[rng_for(seed, 99).permutation(n)]
    times = np.cumsum(gaps) - gaps
    return times * (seconds / gaps.sum())


def open_loop(traffic: dict, seconds: float, seed: int):
    """(due times, queries) of an open-loop window of ``seconds``.  The
    arrivals come from the mix's own ``arrival_seed``: every run offers the
    same bursts, and ``seed`` draws the queries and their order."""
    n = int(round(traffic["rate_qps"] * seconds))
    return (arrivals(n, seconds, traffic["arrival_seed"]),
            queries(traffic, n, seed))


def closed_pool(traffic: dict, seconds: float, seed: int) -> List[Query]:
    """Queries for a closed loop: more than the window can take."""
    n = int(math.ceil(traffic["pool_qps"] * seconds)) + traffic["clients"]
    return queries(traffic, n, seed)


def warmup(traffic: dict, seed: int) -> List[List[Query]]:
    """The warm-up drains: one list of queries per drain, from a stream of
    their own, so that every batch size the window meets is compiled."""
    w = traffic["warmup"]
    sizes = [s for _ in range(w["rounds"]) for s in w["drain_sizes"]]
    pool = queries(traffic, sum(sizes), seed, stream=1)
    out, i = [], 0
    for s in sizes:
        out.append(pool[i:i + s])
        i += s
    return out
