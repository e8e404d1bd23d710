"""The plain reference: exact answers, and a replay of one realized sample.

It evaluates a traffic template's structure (predicates, select list,
grouping) with plain ``jax.numpy`` over the columns the benchmark made, and
imports nothing of the program.  One device pass per query, slab by slab
of blocks so that it fits beside the tables, gives the per-block partial
sums of every (group, channel) in float32 (a block is ``block_rows`` rows,
as in the table); the host adds blocks in float64.
The exact answer adds every block.  The replay adds the blocks of the
program's realized final sample and scales by N / n (the Hajek estimator
of the paper's block sampling), so it recomputes what the program computed
from the same sample.

``dtype=jnp.bfloat16`` gives the control: the same reference with its
float32 columns, the constants compared with them and the arithmetic in
bfloat16, the step below the float32 the program computes in; int32
columns (dates, codes) stay exact, and the per-block sums accumulate in
float32.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The program's final-sample rule (``repro.engine.sampling.draw_block_ids``
# with the seed offset of ``repro.core.taqa.PilotDB.prepare_final``), copied:
# each block is kept when a uniform from ``default_rng(seed + 977)`` falls
# below the rate.
FINAL_SEED_OFFSET = 977


def final_sample(num_blocks: int, rate: float, query_seed: int) -> np.ndarray:
    rng = np.random.default_rng(query_seed + FINAL_SEED_OFFSET)
    return np.nonzero(rng.random(num_blocks) < rate)[0]


def channels(template: dict) -> List[Tuple[str, object]]:
    """Simple channels of the select list: a SUM or COUNT is one channel,
    an AVG two (its sum and its count)."""
    out = []
    for _, op, expr in template["select"]:
        if op == "sum":
            out.append(("sum", expr))
        elif op == "count":
            out.append(("count", None))
        elif op == "avg":
            out += [("sum", expr), ("count", None)]
        else:
            raise ValueError(f"unknown aggregate {op!r}")
    return out


def columns_read(template: dict) -> List[str]:
    """Every column a template's query reads, in first-use order."""
    seen: List[str] = []

    def walk(e):
        if isinstance(e, str):
            if e not in seen:
                seen.append(e)
        elif e is not None:
            walk(e[1])
            walk(e[2])

    for p in template["where"]:
        walk(p[1])
    for _, _, expr in template["select"]:
        walk(expr)
    if template.get("group_by"):
        walk(template["group_by"])
    return seen


def _const_names(template: dict) -> List[str]:
    return [n for p in template["where"] for n in p[2:]]


CHUNK_BLOCKS = 2048  # blocks per step: the reference goes block by block


@functools.lru_cache(maxsize=None)
def _partials_fn(template_json: str, num_rows: int, block_rows: int, dtype):
    t = json.loads(template_json)
    chans = channels(t)
    groups = t["max_groups"] if t.get("group_by") else 1
    names = _const_names(t)

    def partials(cols, consts, keep):
        """(blocks, groups, channels) sums of one slab of blocks."""
        def col(c):
            x = cols[c]
            if dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(dtype)
            return x

        def const(name, like):
            return consts[names.index(name)].astype(like.dtype)

        def expr(e):
            if isinstance(e, str):
                return col(e)
            a, b = expr(e[1]), expr(e[2])
            return {"*": a * b, "+": a + b, "-": a - b, "/": a / b}[e[0]]

        for pred in t["where"]:
            x = col(pred[1])
            if pred[0] == "between":
                keep &= (x >= const(pred[2], x)) & (x <= const(pred[3], x))
            else:
                c = const(pred[2], x)
                keep &= {"<": x < c, "<=": x <= c, ">": x > c, ">=": x >= c,
                         "=": x == c}[pred[0]]
        gcol = cols[t["group_by"]] if t.get("group_by") else None
        out = []
        for g in range(groups):
            m = keep if gcol is None else keep & (gcol == g)
            row = []
            for kind, e in chans:
                v = jnp.ones(m.shape, dtype or jnp.float32) \
                    if kind == "count" else expr(e)
                v = jnp.where(m, v, jnp.zeros((), v.dtype))
                row.append(jnp.sum(v, axis=1, dtype=jnp.float32))
            out.append(jnp.stack(row, axis=1))
        return jnp.stack(out, axis=1)

    def fn(cols, consts):
        nb = next(iter(cols.values())).shape[0] // block_rows
        chunk = min(CHUNK_BLOCKS, nb)
        rows = chunk * block_rows

        def step(i, out):
            # the last slab is clamped to the table's end: it rewrites a
            # few blocks with the same sums
            start = jnp.minimum(i * chunk, nb - chunk)
            slab = {c: jax.lax.dynamic_slice_in_dim(
                v, start * block_rows, rows).reshape(chunk, block_rows)
                for c, v in cols.items()}
            row = ((start + jnp.arange(chunk))[:, None] * block_rows
                   + jnp.arange(block_rows)[None, :])
            part = partials(slab, consts, row < num_rows)
            return jax.lax.dynamic_update_slice_in_dim(out, part, start, 0)

        init = jnp.zeros((nb, groups, len(chans)), jnp.float32)
        return jax.lax.fori_loop(0, -(-nb // chunk), step, init)

    return jax.jit(fn)


def block_partials(columns: Dict[str, jnp.ndarray], num_rows: int,
                   block_rows: int, template: dict, params: Dict[str, float],
                   dtype=None) -> np.ndarray:
    """Per-block (groups, channels) sums of one query, as float64 on the
    host.  ``dtype=None`` computes in each column's own type (float32 and
    int32 here); another dtype rounds the float columns, and the constants
    compared with them, to it first."""
    cols = {c: columns[c] for c in columns_read(template)}
    consts = jnp.asarray([params[n] for n in _const_names(template)],
                         jnp.float64 if jax.config.jax_enable_x64
                         else jnp.float32)
    fn = _partials_fn(json.dumps(template), num_rows, block_rows, dtype)
    return np.asarray(fn(cols, consts), dtype=np.float64)


def combine(template: dict, sums: np.ndarray) -> np.ndarray:
    """(groups, channels) totals -> (select items, groups) answers."""
    out, ch = [], 0
    for _, op, _ in template["select"]:
        if op == "avg":
            s, c = sums[:, ch], sums[:, ch + 1]
            with np.errstate(invalid="ignore", divide="ignore"):
                out.append(np.where(c > 0, s / np.where(c > 0, c, 1), np.nan))
            ch += 2
        else:
            out.append(sums[:, ch])
            ch += 1
    return np.stack(out)


def exact(template: dict, partials: np.ndarray) -> np.ndarray:
    return combine(template, partials.sum(axis=0))


def replay(template: dict, partials: np.ndarray,
           ids: np.ndarray) -> np.ndarray:
    """The Hajek estimate from the sampled blocks ``ids``: their sums
    times N / n (an AVG's scale cancels)."""
    n_blocks = partials.shape[0]
    return combine(template,
                   partials[ids].sum(axis=0) * (n_blocks / max(len(ids), 1)))


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest relative gap over the answers the reference says exist."""
    got, want = np.asarray(got, float), np.asarray(want, float)
    present = np.isfinite(want) & (want != 0)
    if not present.any():
        return 0.0 if np.allclose(got, want, equal_nan=True) else float("inf")
    gap = np.abs(got[present] - want[present]) / np.abs(want[present])
    gap = np.where(np.isfinite(gap), gap, np.inf)
    return float(gap.max())
