"""The bytes function of the roofline share, against hand counts."""

import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import pytest  # noqa: E402

from chipbench import cell, layers, spec, traffic as gen  # noqa: E402


def fake_rec(template, pilot_blocks, final_blocks, row_bytes=64,
             fallback=None, block_rows=1024):
    """A finished query whose trace owns one pilot scan and whose final
    scan read ``final_blocks`` blocks."""
    pilot = {"name": "pilot", "duration_s": 0.0, "t_start_s": 0.0,
             "attrs": {"owner": True, "n_pilot_blocks": pilot_blocks},
             "children": []}
    member = {"name": "pilot", "duration_s": 0.0, "t_start_s": 0.0,
              "attrs": {"owner": False, "n_pilot_blocks": 999},
              "children": []}
    root = {"name": "query", "duration_s": 0.0, "t_start_s": 0.0,
            "attrs": {}, "children": [pilot, member]}
    report = types.SimpleNamespace(
        fallback=fallback, plan=None if fallback else object(),
        final_scanned_bytes=final_blocks * block_rows * row_bytes)
    handle = types.SimpleNamespace(status="done", cached=False,
                                   report=report, _trace=True,
                                   trace=lambda: {"root": root})
    return cell.Rec(gen.Query(template, {}, ""), 0.0, 1.0, handle)


@pytest.fixture(scope="module")
def q6_cell():
    return spec.load_cell("tpch-sf20-uniform.q6-slider")


def test_q6_bytes_by_hand(q6_cell):
    # Q6 reads l_shipdate, l_discount, l_quantity, l_extendedprice: 16 B/row
    w = cell.Window(q6_cell, [fake_rec(0, 100, 300), fake_rec(0, 50, 10)],
                    0.0)
    assert layers.scan_bytes(w) == (100 + 300 + 50 + 10) * 1024 * 16
    n_blocks = 117188  # 120,000,000 rows in 1024-row blocks
    assert layers.sampled_block_pct(w) == pytest.approx(
        100 * (300 / n_blocks + 10 / n_blocks) / 2)


def test_exact_answer_reads_every_block(q6_cell):
    w = cell.Window(q6_cell, [fake_rec(0, 7, 0, fallback="exact")], 0.0)
    assert layers.scan_bytes(w) == (7 + 117188) * 1024 * 16
    assert layers.sampled_block_pct(w) == pytest.approx(100.0)


def test_q1_bytes_by_hand():
    c = spec.load_cell("tpch-sf20-uniform.q6-slider")
    c.traffic = spec.load_json(os.path.join(HERE, "traffic",
                                            "q1-backlog.json"))
    # Q1 reads l_shipdate, l_quantity, l_extendedprice, l_discount and
    # l_returnflag: 20 B/row
    w = cell.Window(c, [fake_rec(0, 64, 2000)], 0.0)
    assert layers.scan_bytes(w) == (64 + 2000) * 1024 * 20


def test_roofline_share_by_hand(q6_cell):
    w = cell.Window(q6_cell, [fake_rec(0, 100, 300)], 0.0)
    w.peak = {"hbm_bytes_per_s": 819e9}
    w.t0_ns, w.t1_ns = 0.0, 1e9
    w.trace = {"device": {"/device:TPU:0": [
        ["fusion.1", "jit_run", 0.0, 2e5],       # 0.2 ms in a scan program
        ["custom-call.2", "jit_run_b", 3e5, 3e5],  # 0.3 ms
        ["fusion.3", "jit_fn", 7e5, 1e6]]},       # not a scan program
        "host": []}
    assert layers.scan_device_ms(w) == pytest.approx(0.5)
    want = 100 * 400 * 1024 * 16 / (819e9 * 0.5e-3)
    assert layers.scan_roofline_pct(w) == pytest.approx(want)


def test_nothing_to_read_gives_none(q6_cell):
    w = cell.Window(q6_cell, [], 0.0)
    assert layers.scan_roofline_pct(w) is None
    assert layers.scan_device_ms(w) is None
    assert layers.device_idle_pct(w) is None
    assert layers.sampled_block_pct(w) is None
