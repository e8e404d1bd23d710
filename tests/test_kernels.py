"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_agg import block_agg, block_agg_batched
from repro.kernels.block_agg import ops as block_agg_ops
from repro.kernels.exact_agg import exact_agg, exact_agg_ref
from repro.kernels.filtered_agg import filtered_agg, filtered_agg_batched
from repro.kernels.flash_attn import flash_attention
from repro.kernels.gla_chunk import gla_chunked


# -- block_agg ----------------------------------------------------------------

@pytest.mark.parametrize("block_rows", [64, 128, 200])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_block_agg_matches_ref(block_rows, dtype):
    rng = np.random.default_rng(0)
    n_blocks = 40
    if dtype == np.int32:
        col = rng.integers(0, 100, n_blocks * block_rows).astype(dtype)
    else:
        col = rng.normal(10, 3, n_blocks * block_rows).astype(dtype)
    valid = (rng.random(n_blocks * block_rows) < 0.7).astype(np.float32)
    ids = rng.choice(n_blocks, size=7, replace=False).astype(np.int32)
    a = np.asarray(block_agg(jnp.asarray(col), jnp.asarray(valid), block_rows, ids))
    b = np.asarray(block_agg(jnp.asarray(col), jnp.asarray(valid), block_rows, ids,
                             use_ref=True))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_block_agg_agrees_with_host_numpy():
    rng = np.random.default_rng(1)
    block_rows, n_blocks = 64, 20
    col = rng.normal(0, 1, n_blocks * block_rows).astype(np.float32)
    valid = np.ones(n_blocks * block_rows, np.float32)
    ids = np.array([2, 9], np.int32)
    out = np.asarray(block_agg(jnp.asarray(col), jnp.asarray(valid), block_rows, ids))
    for j, b in enumerate(ids):
        seg = col[b * block_rows:(b + 1) * block_rows]
        assert out[j, 0] == pytest.approx(block_rows)
        assert out[j, 1] == pytest.approx(seg.sum(), rel=1e-4)
        assert out[j, 2] == pytest.approx((seg ** 2).sum(), rel=1e-4)
        assert out[j, 3] == pytest.approx(seg.min(), rel=1e-5)
        assert out[j, 4] == pytest.approx(seg.max(), rel=1e-5)


def test_block_agg_empty_block_sentinel():
    """A sampled block with zero valid rows reports count=0, sum=sumsq=0 and
    min=max=NaN (the documented sentinel), in kernel and oracle alike."""
    rng = np.random.default_rng(11)
    br, nb = 64, 8
    col = rng.normal(5, 2, nb * br).astype(np.float32)
    valid = np.ones(nb * br, np.float32)
    valid[2 * br:3 * br] = 0.0  # block 2 entirely invalid
    ids = np.array([1, 2, 5], np.int32)
    for use_ref in (False, True):
        out = np.asarray(block_agg(jnp.asarray(col), jnp.asarray(valid), br, ids,
                                   use_ref=use_ref))
        assert out[1, 0] == 0.0 and out[1, 1] == 0.0 and out[1, 2] == 0.0
        assert np.isnan(out[1, 3]) and np.isnan(out[1, 4])
        # non-empty blocks keep real extrema
        assert np.isfinite(out[0, 3:5]).all() and np.isfinite(out[2, 3:5]).all()
        assert out[0, 0] == br


def test_block_agg_single_block_and_all_blocks():
    rng = np.random.default_rng(2)
    col = jnp.asarray(rng.normal(size=6 * 128).astype(np.float32))
    valid = jnp.ones(6 * 128, jnp.float32)
    for ids in (np.array([0]), np.arange(6)):
        a = np.asarray(block_agg(col, valid, 128, ids))
        b = np.asarray(block_agg(col, valid, 128, ids, use_ref=True))
        np.testing.assert_allclose(a, b, rtol=1e-5)


# -- filtered_agg --------------------------------------------------------------

@pytest.mark.parametrize("block_rows", [64, 128])
def test_filtered_agg_matches_ref(block_rows):
    rng = np.random.default_rng(3)
    n_blocks = 30
    mk = lambda: jnp.asarray(rng.normal(1, 1, n_blocks * block_rows).astype(np.float32))
    x, y, f1, f2, f3 = mk(), mk(), mk(), mk(), mk()
    valid = jnp.asarray((rng.random(n_blocks * block_rows) < 0.85).astype(np.float32))
    ids = rng.choice(n_blocks, size=9, replace=False).astype(np.int32)
    bounds = (-0.5, 1.2, 0.0, 2.5, 1.0)
    a = np.asarray(filtered_agg(x, y, f1, f2, f3, valid, block_rows, ids, bounds))
    b = np.asarray(filtered_agg(x, y, f1, f2, f3, valid, block_rows, ids, bounds,
                                use_ref=True))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_filtered_agg_empty_predicate():
    rng = np.random.default_rng(4)
    n, br = 10, 64
    mk = lambda: jnp.asarray(rng.normal(size=n * br).astype(np.float32))
    x, y, f1, f2, f3 = mk(), mk(), mk(), mk(), mk()
    valid = jnp.ones(n * br, jnp.float32)
    out = np.asarray(filtered_agg(x, y, f1, f2, f3, valid, br, np.arange(3),
                                  (5.0, 6.0, 5.0, 6.0, -100.0)))
    np.testing.assert_allclose(out, 0.0, atol=1e-6)


@pytest.mark.parametrize("kernel", ["block", "filtered"])
@pytest.mark.parametrize("batched", [False, True])
def test_id_table_split_across_launches_is_bitwise_one_launch(
        monkeypatch, kernel, batched):
    """An id table larger than one launch's scalar memory splits into
    several launches; the per-block rows are those of one launch, bitwise."""
    rng = np.random.default_rng(9)
    br, nb = 128, 24
    mk = lambda: jnp.asarray(rng.normal(1, 1, nb * br).astype(np.float32))
    x, y, f1, f2, f3 = mk(), mk(), mk(), mk(), mk()
    valid = jnp.asarray((rng.random(nb * br) < 0.8).astype(np.float32))
    ids = rng.integers(0, nb, size=(3, 10)).astype(np.int32)
    bounds = np.array([[-0.5, 1.2, 0.0, 2.5, 1.0 + 0.1 * b] for b in range(3)],
                      np.float32)

    def run():
        if kernel == "block":
            if batched:
                return block_agg_batched(x, valid, br, ids)
            return block_agg(x, valid, br, ids[0])
        if batched:
            return filtered_agg_batched(x, y, f1, f2, f3, valid, br, ids, bounds)
        return filtered_agg(x, y, f1, f2, f3, valid, br, ids[0], bounds[0])

    whole = np.asarray(run())
    monkeypatch.setattr(block_agg_ops, "MAX_PREFETCH_IDS", 4)
    split = np.asarray(run())
    np.testing.assert_array_equal(split, whole)


# -- exact_agg -------------------------------------------------------------------

XBR = 1024                    # rows a block
XROWS = 37 * XBR - 300        # 37 blocks, the last one padded
XSTEP = 64                    # rows of 128 a grid step: 8 blocks, 5 steps


def _xdata(seed=17):
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, XROWS).astype(np.float32)
    return {
        "l_quantity": qty,
        "l_extendedprice": (qty * rng.uniform(900, 1100, XROWS)).astype(
            np.float32),
        "l_discount": rng.integers(0, 11, XROWS).astype(np.float32)
        / np.float32(100),
        "l_shipdate": np.sort(rng.integers(0, 2526, XROWS)).astype(np.int32),
        # order keys past 2^24, where float32 rounds neighbouring ints together
        "l_orderkey": (2 ** 24 + rng.integers(-40, 40, XROWS)).astype(np.int32),
    }


def _q6_channels(cols, ok, p):
    ext, disc, qty, ship = cols
    keep = (ok & (ship >= p[0]) & (ship <= p[1]) & (disc >= p[2])
            & (disc <= p[3]) & (qty < p[4]))
    return [jnp.where(keep, ext * disc, 0.0),
            jnp.where(keep, jnp.ones(keep.shape, jnp.float32), 0.0)]


def _live_channels(cols, ok, p):
    # no predicate: every live row counts, so only the kernel's own mask
    # keeps rows past the table's end out
    return [jnp.where(ok, cols[0], 0.0),
            jnp.where(ok, jnp.ones(ok.shape, jnp.float32), 0.0)]


@pytest.mark.parametrize("channels", [_q6_channels, _live_channels],
                         ids=["q6", "unfiltered"])
@pytest.mark.parametrize("step_rows", [32, XSTEP, 2048],
                         ids=["chunk", "ragged", "one_step"])
def test_exact_agg_matches_ref(monkeypatch, step_rows, channels):
    """Per-step sums against the per-block oracle: a ragged last step (37
    blocks, 8 a step), a step of one chunk, and one step past the table's
    end.  Off the chip the interpreter fills the rows past the end with NaN
    and a live ``valid``: the kernel must mask them out."""
    from repro.kernels.exact_agg import kernel as exact_kernel

    monkeypatch.setattr(exact_kernel, "STEP_ROWS", step_rows)
    d = _xdata()
    pad = -XROWS % XBR
    cols = [jnp.asarray(np.pad(d[c], (0, pad))) for c in
            ("l_extendedprice", "l_discount", "l_quantity", "l_shipdate")]
    valid = jnp.asarray(np.arange(XROWS + pad) < XROWS, jnp.int8)
    params = jnp.asarray([100, 1500, 0.05, 0.07, 24], jnp.float32)
    got = np.asarray(exact_agg(cols, valid, params, channels, 2))
    assert got.shape == (-(-(XROWS + pad) // (step_rows * 128)), 2)
    want = np.asarray(exact_agg_ref(cols, valid, params, channels,
                                    block_rows=XBR))
    np.testing.assert_allclose(got.sum(0, dtype=np.float64),
                               want.sum(0, dtype=np.float64), rtol=1e-6)
    assert got[:, 1].sum() == want[:, 1].sum() > 0


@pytest.fixture(scope="module")
def xcatalog():
    from repro.engine.table import BlockTable
    return {"lineitem": BlockTable.from_numpy("lineitem", _xdata(), XBR)}


def _xcases():
    from repro.engine.expr import And, Col
    ext, disc = Col("l_extendedprice"), Col("l_discount")
    q6 = And(Col("l_shipdate").between(300, 1200),
             And(disc.between(0.05, 0.07), Col("l_quantity") < 24))
    rev = ("sum", ext * disc)
    # l_shipdate is sorted: rows 0 and XROWS - 1 hold its smallest and
    # largest day, so BETWEEN those days keeps both ends of the table
    d = _xdata()
    first, last = int(d["l_shipdate"][0]), int(d["l_shipdate"][-1])
    return {
        "q6": (q6, [rev, ("count", None)], None),
        "bounds_hit_at_both_ends": (
            And(Col("l_shipdate").between(first, last),
                Col("l_quantity") < 25), [rev], None),
        "count_only": (q6, [("count", None)], None),
        "sum_x": (q6, [("sum", ext)], None),
        "cleared_valid": (q6, [rev], 3),
        "int32_past_2_24": (
            And(Col("l_orderkey").between(2 ** 24 - 3, 2 ** 24 + 1),
                Col("l_quantity") < 30), [rev, ("count", None)], None),
        # one strict upper bound on an int32 column: off the chip the rows
        # past the table's end hold int32's least value and pass it
        "int32_upper_bound_only": (
            Col("l_orderkey") < 2 ** 24 + 1, [("sum", ext), ("count", None)],
            None),
    }


def _xref(d, valid, pred_name):
    """Rows kept, as the routes keep them: every column compared with its
    bound in float32, int32 columns cast first."""
    f = {c: v.astype(np.float32) for c, v in d.items()}
    first, last = f["l_shipdate"][0], f["l_shipdate"][-1]
    if pred_name == "int32_upper_bound_only":
        keep = f["l_orderkey"] < np.float32(2 ** 24 + 1)
    elif pred_name == "int32_past_2_24":
        keep = ((f["l_orderkey"] >= np.float32(2 ** 24 - 3))
                & (f["l_orderkey"] <= np.float32(2 ** 24 + 1))
                & (f["l_quantity"] < 30))
    elif pred_name == "bounds_hit_at_both_ends":
        keep = ((f["l_shipdate"] >= first) & (f["l_shipdate"] <= last)
                & (f["l_quantity"] < 25))
    else:
        keep = ((f["l_shipdate"] >= 300) & (f["l_shipdate"] <= 1200)
                & (f["l_discount"] >= np.float32(0.05))
                & (f["l_discount"] <= np.float32(0.07))
                & (f["l_quantity"] < 24))
    return keep & valid[:XROWS]


@pytest.mark.parametrize("case", list(_xcases()))
def test_exact_agg_plan_matches_blockwise_totals_and_float64(
        xcatalog, monkeypatch, case):
    """The exact plan on the ``pallas_exact`` route (interpret mode) against
    the ``xla_gather`` route's blockwise totals and a float64 numpy sum,
    over 37 blocks whose last is padded, 8 blocks a grid step."""
    from repro.engine import logical as L
    from repro.engine.executor import Executor
    from repro.kernels.exact_agg import kernel as exact_kernel

    monkeypatch.setattr(exact_kernel, "STEP_ROWS", XSTEP)
    pred, aggs, clear_every = _xcases()[case]
    cat = dict(xcatalog)
    tab = cat["lineitem"]
    valid = np.asarray(tab.valid).copy()
    if clear_every:
        valid[::clear_every] = False
        cat["lineitem"] = tab.with_valid(jnp.asarray(valid))
    plan = L.Aggregate(L.Filter(L.Scan("lineitem"), pred), tuple(
        L.AggSpec(op, e, f"a{i}") for i, (op, e) in enumerate(aggs)))
    got = Executor(cat, kernel_mode="pallas").execute(plan)
    assert got.route == "pallas_exact"
    xla = Executor(cat, kernel_mode="xla").execute(plan)
    assert xla.route == "xla_gather"
    np.testing.assert_array_equal(got.group_counts, xla.group_counts)
    np.testing.assert_allclose(got.values, xla.values, rtol=1e-6)

    d = _xdata()
    keep = _xref(d, valid, case)
    assert 0 < keep.sum() < XROWS or case == "bounds_hit_at_both_ends"
    assert got.group_counts[0] == keep.sum()
    for i, (op, e) in enumerate(aggs):
        if op == "count":
            want = keep.sum()
        else:
            v = d["l_extendedprice"].astype(np.float64)
            if e is not None and "l_discount" in e.columns():
                v = v * d["l_discount"]
            want = v[keep].sum()
        np.testing.assert_allclose(got.values[i, 0], want, rtol=1e-5)


# -- flash attention -------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq,d", [(64, 32), (96, 64), (128, 128)])
def test_flash_attention_matches_ref(causal, seq, d):
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(0, 1, (1, 2, seq, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (1, 2, seq, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (1, 2, seq, d)).astype(np.float32))
    a = np.asarray(flash_attention(q, k, v, causal=causal, bq=32, bk=32))
    b = np.asarray(flash_attention(q, k, v, causal=causal, use_ref=True))
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_flash_attention_gqa_and_ragged_seq():
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(0, 1, (2, 8, 50, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (2, 2, 70, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (2, 2, 70, 32)).astype(np.float32))
    a = np.asarray(flash_attention(q, k, v, causal=False, bq=32, bk=32))
    b = np.asarray(flash_attention(q, k, v, causal=False, use_ref=True))
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(0, 1, (1, 2, 64, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (1, 2, 64, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (1, 2, 64, 64))).astype(jnp.bfloat16)
    a = np.asarray(flash_attention(q, k, v, causal=True, bq=32, bk=32),
                   dtype=np.float32)
    b = np.asarray(flash_attention(q, k, v, causal=True, use_ref=True),
                   dtype=np.float32)
    np.testing.assert_allclose(a, b, rtol=3e-2, atol=3e-2)


# -- gla_chunk -------------------------------------------------------------------

@pytest.mark.parametrize("T,chunk", [(64, 32), (96, 32), (80, 32), (128, 64)])
@pytest.mark.parametrize("dk,dv", [(16, 32), (64, 64)])
def test_gla_chunked_matches_recurrence(T, chunk, dk, dv):
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(0, 1, (1, 2, T, dk)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (1, 2, T, dk)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (1, 2, T, dv)).astype(np.float32))
    g = jnp.asarray(-rng.uniform(0.001, 0.2, (1, 2, T, dk)).astype(np.float32))
    o1, s1 = gla_chunked(q, k, v, g, chunk=chunk)
    o2, s2 = gla_chunked(q, k, v, g, use_ref=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=3e-3, atol=3e-3)


def test_gla_strong_decay_forgets_prefix():
    """With very strong decay, outputs reduce to (almost) diag-only attention."""
    rng = np.random.default_rng(9)
    T, dk, dv = 64, 8, 8
    q = jnp.asarray(rng.normal(0, 1, (1, 1, T, dk)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (1, 1, T, dk)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (1, 1, T, dv)).astype(np.float32))
    g = jnp.full((1, 1, T, dk), -8.0, jnp.float32)
    o, _ = gla_chunked(q, k, v, g, chunk=32)
    exp = np.einsum("bhtd,bhtd->bht", np.asarray(q), np.asarray(k))[..., None] * np.asarray(v)
    np.testing.assert_allclose(np.asarray(o), exp, rtol=2e-2, atol=2e-2)


def test_gla_zero_decay_is_cumulative_linear_attention():
    rng = np.random.default_rng(10)
    T, d = 32, 8
    q = jnp.asarray(rng.normal(0, 1, (1, 1, T, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (1, 1, T, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (1, 1, T, d)).astype(np.float32))
    g = jnp.zeros((1, 1, T, d), jnp.float32)
    o, s = gla_chunked(q, k, v, g, chunk=16)
    qn, kn, vn = (np.asarray(a)[0, 0] for a in (q, k, v))
    attn = np.tril(qn @ kn.T)
    np.testing.assert_allclose(np.asarray(o)[0, 0], attn @ vn, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s)[0, 0], kn.T @ vn, rtol=2e-3, atol=2e-3)
