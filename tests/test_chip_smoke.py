"""chip_smoke.py off the chip: the rehearsal walks every phase and can
never be taken for a pass, the no-chip gate holds, and a device engine
that fails raises instead of handing back a host result."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, devices: int | None = None, **env_extra):
    env = dict(os.environ, **env_extra)
    if devices is not None:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    r = subprocess.run([sys.executable, SMOKE, *args], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    return r, lines


@pytest.mark.parametrize("chips,devices,phases", [
    (1, 1, ["device", "validator", "host_ref", "hard_cap", "counters",
            "total"]),
    (4, 4, ["device", "mesh", "one_chip_ref", "host_ref", "mesh_samples",
            "counters", "total"]),
])
def test_rehearsal_walks_every_phase_and_never_passes(chips, devices,
                                                      phases):
    r, lines = _run("--rehearse-cpu", "--chips", str(chips),
                    devices=devices)
    assert [x["phase"] for x in lines[:-1]] == phases, r.stderr[-3000:]
    assert lines[-1] == {"ok": False, "rehearsal": "cpu"}
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    by_phase = {x["phase"]: x for x in lines[:-1]}
    assert not any(by_phase["counters"]["fallbacks"].values())
    if chips == 1:
        assert by_phase["validator"]["square_size"] <= 8
        assert by_phase["validator"]["blocks"] == 2
        assert by_phase["host_ref"]["heights_equal"] == 2
        assert by_phase["counters"]["admission_batch_dispatches"] > 0
    else:
        assert by_phase["mesh"]["eds_devices"] == 4
        assert by_phase["mesh"]["data_root"] \
            == by_phase["one_chip_ref"]["data_root"] \
            == by_phase["host_ref"]["data_root"]
        assert by_phase["mesh_samples"]["samples"] == 16
        assert by_phase["mesh_samples"]["level_devices"] == 4


@pytest.mark.parametrize("args,devices", [
    ((), 1),                                # no TPU, no rehearsal flag
    (("--rehearse-cpu", "--chips", "4"), 8),  # a phase's check fails
])
def test_a_failed_run_exits_nonzero_without_a_result(args, devices):
    r, lines = _run(*args, devices=devices)
    assert r.returncode != 0
    assert "AssertionError" in r.stderr
    assert not any("ok" in x for x in lines), r.stdout


def test_device_engine_failure_raises_not_degrades(monkeypatch):
    """engine="device" means the device or an error: a failing device
    program must never come back as a quietly host-built entry (only
    engine="auto" degrades, and it counts app.device_path_fallback)."""
    from celestia_app_tpu.da import eds, edscache
    from celestia_app_tpu.utils import telemetry

    def boom(k):
        raise RuntimeError("device program failed")

    monkeypatch.setattr(eds, "jitted_pipeline", boom)
    ods = np.zeros((2, 2, 512), dtype=np.uint8)
    with pytest.raises(RuntimeError, match="device program failed"):
        edscache.compute_entry(ods, "device")
    before = telemetry.snapshot()["counters"].get(
        "app.device_path_fallback", 0)
    entry = edscache.compute_entry(ods, "auto")
    assert entry.eds.squares.shape == (4, 4, 512)
    assert telemetry.snapshot()["counters"][
        "app.device_path_fallback"] == before + 1


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR wins where set; otherwise the cache sits
    at the fixed <checkout>/.jax_cache. Importing the package decides it
    and must not import JAX (a host-engine process stays off it)."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = ("import sys, celestia_app_tpu; assert 'jax' not in sys.modules; "
            "import jax; print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    want = str(tmp_path / "cc") if placed else os.path.join(REPO,
                                                            ".jax_cache")
    assert out.stdout.strip() == want
