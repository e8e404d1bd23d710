"""Compile the scan kernels for a TPU v5e at TPC-H SF10 widths, without a chip.

The TPU compiler is installed wherever JAX is, and compiles for a described,
unattached chip.  It refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, scalar-prefetch tables past the 1 MiB of SMEM.  These tests
lower each kernel through its ``ops.py`` wrapper with ``interpret=False`` and
check that the compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.block_agg import block_agg, block_agg_batched
from repro.kernels.filtered_agg import filtered_agg, filtered_agg_batched

SF10_BLOCKS = 58_594   # ceil(60M lineitem rows / 1024)
SF20_BLOCKS = 117_188  # ceil(120M lineitem rows / 1024)
BLOCK_ROWS = 1024      # one (8, 128) f32 tile per column per block
N_SAMPLED = 4096       # a 7% block sample, bucketed to a power of two
BATCH = 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _col(sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((SF10_BLOCKS * BLOCK_ROWS,), dtype,
                                sharding=sharding)


def _ids(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_block_agg_compiles_for_v5e(one_chip, dtype):
    _compile(lambda c, v, i: block_agg(c, v, BLOCK_ROWS, i, interpret=False),
             _col(one_chip, dtype), _col(one_chip, jnp.bool_),
             _ids(one_chip, N_SAMPLED))


def test_block_agg_batched_compiles_for_v5e(one_chip):
    _compile(lambda c, v, i: block_agg_batched(c, v, BLOCK_ROWS, i,
                                               interpret=False),
             _col(one_chip), _col(one_chip, jnp.bool_),
             _ids(one_chip, BATCH, N_SAMPLED))


def _q6_args(sharding, ids, bounds_shape):
    # l_extendedprice, l_discount, l_shipdate (int32), l_discount,
    # l_quantity, valid — the Q6 route's operands
    return (_col(sharding), _col(sharding), _col(sharding, jnp.int32),
            _col(sharding), _col(sharding), _col(sharding, jnp.bool_), ids,
            jax.ShapeDtypeStruct(bounds_shape, jnp.float32, sharding=sharding))


def test_filtered_agg_compiles_for_v5e(one_chip):
    _compile(lambda x, y, a, b, c, v, i, bd: filtered_agg(
        x, y, a, b, c, v, BLOCK_ROWS, i, bd, interpret=False),
        *_q6_args(one_chip, _ids(one_chip, N_SAMPLED), (5,)))


def test_filtered_agg_batched_compiles_for_v5e(one_chip):
    _compile(lambda x, y, a, b, c, v, i, bd: filtered_agg_batched(
        x, y, a, b, c, v, BLOCK_ROWS, i, bd, interpret=False),
        *_q6_args(one_chip, _ids(one_chip, BATCH, N_SAMPLED), (BATCH, 5)))


def test_id_table_past_smem_splits_and_compiles(one_chip):
    """8 lanes x 65,536 ids is 2 MiB of ids, twice the v5e's SMEM: the
    wrapper splits it into launches that each fit, and all compile."""
    _compile(lambda x, y, a, b, c, v, i, bd: filtered_agg_batched(
        x, y, a, b, c, v, BLOCK_ROWS, i, bd, interpret=False),
        *_q6_args(one_chip, _ids(one_chip, 8, 1 << 16), (8, 5)))


def _exact_q6(one_chip, blocks, kernel_mode, valid_dtype):
    """The exact Q6 plan compiled by the physical layer over a described
    lineitem of ``blocks`` 1024-row blocks: (compiled query, lowered)."""
    import types

    from repro.engine import logical as L
    from repro.engine.expr import And, Col
    from repro.engine.physical import PhysicalCompiler, ScanRuntime

    rows = blocks * BLOCK_ROWS

    def col(dtype):
        return jax.ShapeDtypeStruct((rows,), dtype, sharding=one_chip)

    dtypes = {"l_shipdate": jnp.int32, "l_discount": jnp.float32,
              "l_quantity": jnp.float32, "l_extendedprice": jnp.float32}
    table = types.SimpleNamespace(
        block_rows=BLOCK_ROWS, padded_rows=rows, num_blocks=blocks,
        num_origin_blocks=blocks,
        columns={c: col(d) for c, d in dtypes.items()})
    pred = And(Col("l_shipdate").between(100, 464),
               And(Col("l_discount").between(0.05, 0.07),
                   Col("l_quantity") < 24))
    plan = L.Aggregate(L.Filter(L.Scan("lineitem"), pred), (
        L.AggSpec("sum", Col("l_extendedprice") * Col("l_discount"), "rev"),))
    compiled = PhysicalCompiler({"lineitem": table}, kernel_mode=kernel_mode) \
        .compile_query(plan, {"lineitem": ScanRuntime("none")})
    rt = {"cols": {"lineitem": dict(table.columns)},
          "valid": {"lineitem": col(valid_dtype)},
          "bid": {"lineitem": col(jnp.int32)},
          "ids": {}, "nreal": {}, "mask": {},
          "params": jax.ShapeDtypeStruct((5,), jnp.float32,
                                         sharding=one_chip)}
    return compiled, compiled.fn.lower(rt)


def test_exact_scan_kernel_compiles_for_v5e_at_sf20(one_chip, monkeypatch):
    """The exact Q6 plan with the kernels on, at SF20: the scan is the
    exact-scan kernel (``tpu_custom_call``) inside ``jit_run_exact``, and it
    reads each column in its own dtype, ``valid`` as the table's int8 copy
    of the bool column: no temporary of row length (a float32 cast of one
    column would be 480 MB)."""
    from repro.kernels.exact_agg import ops as exact_ops

    # the kernel's own choice of interpret mode asks the backend, which is
    # the CPU here: compile the chip's kernel
    monkeypatch.setattr(exact_ops, "_auto_interpret", lambda _: False)
    compiled, lowered = _exact_q6(one_chip, SF20_BLOCKS, "pallas", jnp.int8)
    assert compiled.route == "pallas_exact"
    assert "@jit_run_exact" in lowered.as_text()
    program = lowered.compile()
    assert "tpu_custom_call" in program.as_text()
    temp = program.memory_analysis().temp_size_in_bytes
    assert temp < 16 << 20, temp


def test_exact_scan_compiles_for_v5e_without_row_temporaries(one_chip):
    """The exact Q6 scan of the XLA route sums each 1024-row block straight
    from the columns: compiled for a v5e, it holds no temporary of row
    length (one float32 channel over SF10 is 240 MB; the block partials and
    their pairwise sum are a few MB)."""
    compiled, lowered = _exact_q6(one_chip, SF10_BLOCKS, "xla", jnp.bool_)
    assert compiled.route == "xla_gather"
    assert "@jit_run_exact" in lowered.as_text()
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp < 16 << 20, temp
