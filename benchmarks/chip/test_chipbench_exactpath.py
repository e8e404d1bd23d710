"""The clustered cell's pieces: its configuration beside the uniform one,
its traffic beside ``q6-dashboard-8``, and the exact path's readers
(``chipbench/exactpath.py``) on synthetic windows."""

import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from chipbench import cell, data, spec  # noqa: E402
from chipbench import traffic as gen  # noqa: E402

CELL = "tpch-sf20-clustered.q6-slider"
NEW = ("exact_scan_device_ms", "exact_scan_roofline_pct", "exact_answer_pct",
       "fallback_overhead_ms")


@pytest.fixture(scope="module")
def clustered():
    return spec.load_cell(CELL, ROOT)


def _config(name):
    return spec.load_json(os.path.join(HERE, "configs", name + ".json"))


def test_clustered_config_is_the_uniform_one_sorted_on_shipdate():
    uni, clu = _config("tpch-sf20-uniform"), _config("tpch-sf20-clustered")
    assert clu["cluster_by"] == {"lineitem": "l_shipdate"}
    assert clu["name"] == "tpch-sf20-clustered" and clu["source"]
    assert clu["assumed"]["layout"] != uni["assumed"]["layout"]
    for c in (uni, clu):
        for key in ("name", "source", "cluster_by"):
            c.pop(key)
        c["assumed"].pop("layout")
    assert clu == uni
    assert data.table_bytes(_config("tpch-sf20-clustered")) == 9_510_040_576


def test_short_warmup_traffic_holds_the_dashboard_queries():
    old = spec.load_json(os.path.join(HERE, "traffic", "q6-dashboard-8.json"))
    new = spec.load_json(os.path.join(HERE, "traffic",
                                      "q6-dashboard-8-short-warmup.json"))
    for key in ("templates", "clients", "loop", "check_sample"):
        assert new[key] == old[key], key
    assert new["pool_qps"] == 400
    seed = 2**31 + 16
    assert gen.queries(new, 400, seed) == gen.queries(old, 400, seed)
    sizes = [len(d) for d in gen.warmup(new, seed)]
    assert 0 < sum(sizes) <= 100


def test_new_metrics_name_only_the_clustered_cell(clustered):
    bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "qps"
    assert [m["name"] for m in clustered.per_layer] == list(NEW)


def node(name, t, d, *children, **attrs):
    return {"name": name, "t_start_s": t, "duration_s": d, "attrs": attrs,
            "children": list(children)}


def rec(fallback, *children):
    root = node("query", 0.0, 1.0, node("schedule", 0.0, 0.002), *children)
    report = types.SimpleNamespace(fallback=fallback)
    handle = types.SimpleNamespace(status="done", cached=False, t_submit=0.0,
                                   report=report, _trace=True,
                                   trace=lambda: {"root": root})
    return cell.Rec(gen.Query(0, {}, ""), 0.0, 1.0, handle)


def read(name, w):
    return spec.metric_reader(name)(w)


def test_readers_on_an_exact_and_a_sampled_answer(clustered):
    exact = rec("no feasible plan cheaper than exact",
                node("pilot", 0.002, 0.003),
                node("rate_solve", 0.005, 0.004,
                     node("exact", 0.0065, 0.0025, reason="x", n_blocks=3)))
    sampled = rec(None, node("pilot", 0.002, 0.003),
                  node("final", 0.006, 0.001))
    w = cell.Window(clustered, [exact, sampled], 0.0)
    w.peak = {"hbm_bytes_per_s": 819e9}
    t0 = 1e9
    w.t0_ns, w.t1_ns = t0, t0 + 1e8
    # jit_run_exact ran 2 + 1 ms, a sampled scan 5 ms; one op outside the
    # window
    w.trace = {"device": {"/device:TPU:0": [
        ["fusion", "jit_run_exact", t0 + 1e6, 2e6],
        ["reduce", "jit_run_exact", t0 + 4e6, 1e6],
        ["kernel", "jit_run", t0 + 6e6, 5e6],
        ["fusion", "jit_run_exact", t0 + 2e8, 1e6]]}, "host": []}
    assert read("exact_answer_pct", w) == pytest.approx(50.0)
    # from the end of schedule (2 ms) to the exact span's start (6.5 ms)
    assert read("fallback_overhead_ms", w) == pytest.approx(4.5)
    assert read("exact_scan_device_ms", w) == pytest.approx(3.0)
    # 120,000,512 padded rows x 16 bytes (l_shipdate, l_discount,
    # l_quantity, l_extendedprice) over 819 GB/s x 3 ms
    want = 100.0 * data.padded_rows(clustered.config, "lineitem") * 16 \
        / (819e9 * 3e-3)
    assert read("exact_scan_roofline_pct", w) == pytest.approx(want)


def test_readers_find_nothing_without_the_exact_path(clustered):
    """A program without jit_run_exact and the exact span (the parent's),
    and a window without a device trace, give None, not 0."""
    exact = rec("no feasible plan cheaper than exact",
                node("rate_solve", 0.005, 0.004))
    w = cell.Window(clustered, [exact], 0.0)
    assert read("exact_scan_device_ms", w) is None
    assert read("exact_scan_roofline_pct", w) is None
    assert read("fallback_overhead_ms", w) is None
    w.peak = {"hbm_bytes_per_s": 819e9}
    w.t0_ns, w.t1_ns = 0.0, 1e9
    w.trace = {"device": {"/device:TPU:0": [["f", "jit_run", 0.0, 1e6]]},
               "host": []}
    assert read("exact_scan_device_ms", w) is None
    assert read("exact_scan_roofline_pct", w) is None
    assert read("exact_answer_pct", w) == pytest.approx(100.0)
    assert read("exact_answer_pct", cell.Window(clustered, [], 0.0)) is None
