"""Every piece of a cell is found by name, from files of its own."""

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from chipbench import data, spec  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    c = spec.load_cell(name, ROOT)
    assert c.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == name)
    assert c.traffic["loop"] in ("open", "closed")
    limits = spec.load_json(os.path.join(HERE, "limits", name + ".json"))
    assert set(limits["limits"]) == {"estimator_gap"}
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] in e2e


def test_every_metric_file_is_named_in_the_benchmark():
    names = {m["name"] for m in BENCH["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(HERE, "metrics"))
             if f.endswith(".py")}
    assert names == files


def test_names_and_keys_follow_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        cfg = spec.load_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) <= set(cfg) and set(c["reduced"]) == set(
            cfg["reduced"])
    for w in BENCH["workloads"]:
        assert os.path.isfile(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json"))
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)


def test_table_bytes_from_shapes():
    """SF20 lineitem (16 columns) and orders (9) at 1024-row blocks, with
    valid and block_id: 117,188 blocks x 1024 x 69 bytes + 29,297 x 1024 x
    41 bytes."""
    c = spec.load_cell("tpch-sf20-uniform.q6-slider", ROOT)
    assert data.table_bytes(c.config) == 8_280_035_328 + 1_230_005_248
    assert len(c.config["tables"]["lineitem"]["columns"]) == 16
    assert len(c.config["tables"]["orders"]["columns"]) == 9


def test_unknown_device_has_no_peaks():
    peaks = spec.load_json(os.path.join(HERE, "peaks.json"))
    assert spec.peak_for(peaks, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peak_for(peaks, "cpu")
