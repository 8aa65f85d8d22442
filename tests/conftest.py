"""Test harness config: CPU JAX with 8 virtual devices.

Tests never need a chip (chip_smoke.py is what runs there): before any
backend initializes, JAX is pinned to the CPU platform with 8 virtual
devices so the mesh tests have something to shard over. Subprocesses
spawned by tests inherit the same environment. The persistent compile
cache stays off under test: runs must not depend on what an earlier run
left in <checkout>/.jax_cache.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--run-slow",
        action="store_true",
        default=False,
        help="run tests marked slow (large square sizes; minutes on CPU)",
    )


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: heavy square sizes, skipped by default")
    config.addinivalue_line(
        "markers", "backend: exercises the jitted device path (CPU backend suffices)"
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--run-slow"):
        return
    skip_slow = pytest.mark.skip(reason="needs --run-slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


@pytest.fixture
def racecheck_guard():
    """CELESTIA_RACE=1 for one test: install the runtime lock-order
    detector (tools/analyze/racecheck), hand it to the test, and FAIL at
    teardown on any recorded ABBA inversion. The chaos and stress tiers
    opt in via a module-local autouse wrapper (subprocesses they spawn
    inherit the env var and install from celestia_app_tpu/__init__)."""
    from celestia_app_tpu.tools.analyze import racecheck

    prev = os.environ.get("CELESTIA_RACE")
    os.environ["CELESTIA_RACE"] = "1"
    newly = racecheck.install()  # False when the env hook already did
    racecheck.reset()
    yield racecheck
    if prev is None:
        os.environ.pop("CELESTIA_RACE", None)
    else:
        os.environ["CELESTIA_RACE"] = prev
    vios = racecheck.violations()
    if newly:
        # leave a session-wide install (CELESTIA_RACE=1 pytest run)
        # alone — uninstalling here would silently stop tracking for
        # every later test
        racecheck.uninstall()
    racecheck.reset()
    assert not vios, (
        "lock-order inversions: "
        + "; ".join(v["message"] for v in vios)
    )


@pytest.fixture(scope="session")
def shard_small():
    """`with shard_small(): ...` — inside, the device engine splits
    squares from k=2 up over the virtual devices (or the first `chips`
    of them), as it splits k >= 256 squares over real chips: the one
    sharding rule's threshold (parallel/mesh_engine.MESH_MIN_K) lowered.
    Session-scoped, so module fixtures can build sharded entries too;
    everything is restored on leaving the block."""
    import contextlib

    from celestia_app_tpu.parallel import mesh_engine

    @contextlib.contextmanager
    def lowered(min_k: int = 2, chips: int | None = None):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(mesh_engine, "MESH_MIN_K", min_k)
            if chips is not None:
                mp.setattr(mesh_engine, "_usable_device_count",
                           lambda: chips)
            yield

    return lowered
