"""The reduction from a profiler trace to device time, idle share and
idle-gap labels, on a slice of a trace recorded on one TPU v5e
(``testdata/trace_v5e.json``, in ``devtrace.extract``'s compact form)."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import devtrace, layers  # noqa: E402


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "testdata", "trace_v5e.json")) as f:
        return json.load(f)


def timeline(trace, t0, t1, pred=lambda op: True):
    """Busy flags of the first device plane at 100 ns resolution: a
    brute-force second reading of the same trace."""
    ops = next(iter(trace["device"].values()))
    n = int((t1 - t0) // 100) + 1
    busy = np.zeros(n, bool)
    for op in ops:
        if not pred(op):
            continue
        a = int((max(op[2], t0) - t0) // 100)
        b = int((min(op[2] + op[3], t1) - t0) // 100)
        if b > a:
            busy[a:b] = True
    return busy


def test_recorded_trace_has_device_ops_and_window(trace):
    assert list(trace["device"]) == ["/device:TPU:0"]
    t0, t1 = devtrace.window(trace)
    assert t1 > t0
    mods = {op[1] for op in trace["device"]["/device:TPU:0"]}
    assert mods & set(layers.SCAN_MODULES)


def test_busy_time_is_the_union_of_op_intervals(trace):
    t0, t1 = devtrace.window(trace)
    want = timeline(trace, t0, t1).sum() * 100
    assert devtrace.busy_ns(trace, t0, t1) == pytest.approx(want, rel=0.02,
                                                            abs=2000)


def test_scan_module_time_sums_its_ops(trace):
    t0, t1 = devtrace.window(trace)
    ops = trace["device"]["/device:TPU:0"]
    want = sum(min(s + d, t1) - max(s, t0) for _, m, s, d in ops
               if m in layers.SCAN_MODULES and s < t1 and s + d > t0)
    got = devtrace.module_ns(trace, layers.SCAN_MODULES, t0, t1)
    assert got == pytest.approx(want) and got > 0
    assert got <= devtrace.busy_ns(trace, t0, t1) * 1.0001 or \
        len({(s, d) for _, _, s, d in ops}) < len(ops)


def test_idle_gaps_add_up_to_the_idle_time(trace):
    t0, t1 = devtrace.window(trace)
    gaps = devtrace.idle_gaps(trace, t0, t1, k=100)
    idle = (t1 - t0) - devtrace.busy_ns(trace, t0, t1)
    assert sum(s for _, s in gaps) * 1e9 == pytest.approx(idle, rel=1e-6)
    assert all(name.startswith("bench.") for name, _ in gaps)
    top = devtrace.top_ops(trace, t0, t1)
    assert 0 < len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)


def test_module_name_strips_ids():
    assert devtrace.module_name("jit_run(1234)") == "jit_run"
    assert devtrace.module_name("jit_run_batched") == "jit_run_batched"
