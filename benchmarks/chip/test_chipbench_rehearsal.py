"""A tiny rehearsal of whole runs on the CPU: set-up, the window through
``SqlGateway``, the per-layer readers and the check.  The look for a chip
is the only part of ``run.py`` it leaves out."""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import pytest  # noqa: E402

from chipbench import cell, spec  # noqa: E402


def small_cell(name, scale_factor, open_rate=None):
    """A cell at a test's size; with ``open_rate``, under the open-loop
    slider mix (``traffic/q6-slider-open.json``) at that rate."""
    c = spec.load_cell(name)
    c.config["scale_factor"] = scale_factor
    if open_rate is not None:
        c.traffic = spec.load_json(os.path.join(HERE, "traffic",
                                                "q6-slider-open.json"))
        c.traffic["rate_qps"] = open_rate
        c.end_to_end = [{"name": "latency_p50_ms", "unit": "ms"},
                        {"name": "latency_p95_ms", "unit": "ms"},
                        {"name": "setup_s", "unit": "s"}]
    c.traffic["warmup"] = {"drain_sizes": [1, 2], "rounds": 1,
                           "by_size_drain_sizes": [2]}
    c.traffic["check_sample"] = 6
    return c


def limits(name):
    return spec.load_json(os.path.join(HERE, "limits", name + ".json"))[
        "limits"]


@pytest.mark.parametrize("traced", [False, True])
def test_open_loop_cell_runs_through_the_gateway(traced):
    name = "tpch-sf20-uniform.q6-slider"
    c = small_cell(name, 0.05, open_rate=4.0)
    res = cell.run(c, 2**31 + 3, 2.0, traced, time.perf_counter(),
                   limits(name), stage_log=lambda m: None)
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == ["unanswered", "estimator_gap",
                                   "guarantee_misses"]
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    if traced:
        # span and counter readers; the device-trace readers find no TPU
        # plane on the CPU and leave their metrics out
        assert {"frontdoor_ms", "queue_wait_ms", "sampled_block_pct"} <= set(
            res["metrics"])
        assert "scan_roofline_pct" not in res["metrics"]
        assert res["device"]["window_s"] > 0
    else:
        assert set(res["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                       "setup_s"}
        assert res["metrics"]["latency_p95_ms"]["value"] >= \
            res["metrics"]["latency_p50_ms"]["value"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("layout", ["uniform", "clustered"])
def test_closed_loop_cell_runs_through_the_gateway(layout):
    """The uniform cell, and the same cell over lineitem clustered on
    l_shipdate, where every answer is the program's exact scan."""
    name = "tpch-sf20-uniform.q6-slider"
    c = small_cell(name, 0.05)
    if layout == "clustered":
        c.config["cluster_by"] = {"lineitem": "l_shipdate"}
    res = cell.run(c, 2**31 + 4, 1.0, False, time.perf_counter(),
                   limits(name), stage_log=lambda m: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] % c.traffic["clients"] == 0
    assert set(res["metrics"]) == {"qps", "setup_s"}
    assert res["metrics"]["qps"]["value"] > 0
