"""Tables of a configuration, made on the device from the seed.

The distributions of the columns the program's own generator has are a
copy of ``repro.engine.datagen``'s ``make_lineitem`` / ``make_orders``,
kept here so that a change to the program's generator cannot move the
benchmark; the other TPC-H columns follow dbgen's value rules, as the
configuration file states them.  Each table is one jitted
call from the seed; ``valid`` and ``block_id`` are built by ``BlockTable``,
as the program builds them.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.table import BlockTable


def table_rows(config: dict, name: str) -> int:
    spec = config["tables"][name]
    return int(spec.get("rows")
               or spec["rows_per_sf"] * config["scale_factor"])


def padded_rows(config: dict, name: str) -> int:
    br = config["block_rows"]
    return -(-table_rows(config, name) // br) * br


def _bound(value, config: dict) -> int:
    """A distribution bound: a number, or ``{"rows_of": t, "div": d}``."""
    if isinstance(value, dict):
        rows = table_rows(config, value["rows_of"])
        return max(rows // value.get("div", 1), 1)
    return int(value)


def seed_key(seed: int):
    """A PRNG key from a whole number of any size (the driver's seeds pass
    32 bits): the low 32 bits seed the key, the rest are folded in."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _sorted_cumcounts(seed: int, t: int, c: int, n: int, lo: int,
                      hi: int) -> np.ndarray:
    """Cumulative counts per value of ``n`` uniform draws from ``[lo, hi)``:
    the sorted column follows from them by a running count, so a clustered
    layout costs no sort of the whole column."""
    rng = np.random.default_rng([int(seed) & (2**63 - 1), t, c])
    k = hi - lo
    return np.cumsum(rng.multinomial(n, np.full(k, 1.0 / k))).astype(np.int32)


def _column(spec: dict, key, n: int, p: int, config: dict, done: dict,
            host):
    dtype = jnp.dtype(spec["dtype"])
    dist = spec["dist"]
    if dist == "randint":
        lo, hi = _bound(spec["lo"], config), _bound(spec["hi"], config)
        if host is not None:  # the clustered column: cumulative counts
            # row i holds the number of values whose run ends at or before i
            steps = jnp.zeros((p,), jnp.int32).at[host[:-1]].add(1)
            x = lo + jnp.cumsum(steps)
        else:
            x = jax.random.randint(key, (p,), lo, hi, dtype=jnp.int32)
        if "div" in spec:  # datagen's float32 k / div, rounded on the host
            table = np.arange(lo, hi).astype(dtype) / np.asarray(
                spec["div"], dtype)
            x = jnp.asarray(table)[x - lo]
        x = x.astype(dtype)
    elif dist == "offset":  # another column plus a uniform whole number
        lo, hi = _bound(spec["lo"], config), _bound(spec["hi"], config)
        x = (done[spec["of"]]
             + jax.random.randint(key, (p,), lo, hi, dtype=jnp.int32))
        x = x.astype(dtype)
    elif dist == "times_uniform":
        u = jax.random.uniform(key, (p,), jnp.float32, spec["lo"], spec["hi"])
        x = (done[spec["of"]].astype(jnp.float32) * u).astype(dtype)
    elif dist == "gamma":  # integer shape: a sum of unit exponentials
        e = jax.random.exponential(key, (int(spec["shape"]), p), jnp.float32)
        x = (e.sum(axis=0) * spec["scale"]).astype(dtype)
    elif dist == "permutation":  # host: a multiplier coprime to n
        i = jnp.arange(p, dtype=jnp.int32)
        x = ((i * host) % n).astype(dtype)
    else:
        raise ValueError(f"unknown column distribution {dist!r}")
    return jnp.where(jnp.arange(p) < n, x, jnp.zeros((), dtype))


def _coprime_multiplier(seed: int, t: int, c: int, n: int) -> np.ndarray:
    """A multiplier coprime to ``n`` whose products stay within int32."""
    top = max((2**31 - 1) // max(n, 1), 3)
    cands = [a for a in range(2, top) if math.gcd(a, n) == 1] or [1]
    rng = np.random.default_rng([int(seed) & (2**63 - 1), t, c])
    return np.int32(cands[int(rng.integers(len(cands)))])


@functools.lru_cache(maxsize=None)
def _builder(config_json: str, name: str):
    """The one jitted program that makes table ``name`` (kept per process,
    so the seeds of one process compile it once)."""
    config = json.loads(config_json)
    spec = config["tables"][name]
    n, p = table_rows(config, name), padded_rows(config, name)

    def build(key, host):
        done = {}
        for c, cname in enumerate(spec["columns"]):
            done[cname] = _column(spec["columns"][cname],
                                  jax.random.fold_in(key, c), n, p, config,
                                  done, host.get(cname))
        return done

    return jax.jit(build)


def make_tables(config: dict, seed: int) -> Dict[str, BlockTable]:
    """Every table of ``config``, made on the default device from ``seed``."""
    base = seed_key(seed)
    tables = {}
    cluster = config.get("cluster_by", {})
    for t, (name, spec) in enumerate(config["tables"].items()):
        n, p = table_rows(config, name), padded_rows(config, name)
        cols = list(spec["columns"])
        host = {}  # small host decisions, passed as operands (not baked in)
        for c, cname in enumerate(cols):
            cspec = spec["columns"][cname]
            if cluster.get(name) == cname:
                host[cname] = _sorted_cumcounts(
                    seed, t, c, n, _bound(cspec["lo"], config),
                    _bound(cspec["hi"], config))
            elif cspec["dist"] == "permutation":
                host[cname] = _coprime_multiplier(seed, t, c, n)

        out = _builder(json.dumps(config), name)(
            jax.random.fold_in(base, t),
            {k: jnp.asarray(v) for k, v in host.items()})
        columns = {cname: out[cname] for cname in cols}
        tables[name] = BlockTable(name=name, columns=columns,
                                  block_rows=config["block_rows"], num_rows=n)
    jax.block_until_ready([tb.columns for tb in tables.values()])
    return tables


def table_bytes(config: dict) -> int:
    """Device bytes of the configuration's tables, from shapes: every
    column at its width, plus ``valid`` (1 byte) and ``block_id`` (4)."""
    total = 0
    for name, spec in config["tables"].items():
        width = sum(jnp.dtype(c["dtype"]).itemsize
                    for c in spec["columns"].values())
        total += padded_rows(config, name) * (width + 1 + 4)
    return total
