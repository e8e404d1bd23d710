"""``chip_smoke.py`` rehearsed on the CPU, in this process.

The script is the proof that the served path runs on a TPU; these tests keep
its phases working between chip runs.  Off the TPU it runs the same phases
(Pallas kernels in interpret mode) and must still report ``"ok": false``.
"""

import importlib.util
import json
import os

import jax
import pytest

from repro.compile_cache import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_rehearsal_runs_every_phase_and_reports_not_ok(chip_smoke, capsys):
    # 2M rows is the smallest size at which TAQA finds a sampled plan for
    # Q6 at 5%/95% (fewer blocks fall back to the exact query)
    rc = chip_smoke.main(["--rows", "2000000"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "[FAIL]" not in out, out
    for phase in ("data", "bytes", "query (a)", "query (b)", "query (c)",
                  "query (d)", "cross-route", "swallowed failures"):
        assert f"phase {phase}" in out
    for route in ("pallas_filtered", "pallas_block", "pallas_filtered_batched",
                  "xla_gather"):
        assert route in out
    last = _last_json(out)
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def test_four_chips_needs_four_devices(chip_smoke, capsys):
    rc = chip_smoke.main(["--four-chips", "--rows", "100000"])
    out = capsys.readouterr().out
    assert rc == 1
    assert f"[FAIL] four devices visible ({len(jax.devices())})" in out
    assert _last_json(out) == {"ok": False, "device": chip_smoke.device_info()}


def test_no_accelerator_and_no_rows_prints_no_result(chip_smoke, capsys):
    assert chip_smoke.main([]) == 2
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_follows_env_else_fixed_repo_path(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache(ROOT) == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache(ROOT)
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
