"""Boundary observatory: transfer ledger, residency pins, lock/GIL
profiling and the SLO verdict engine (ISSUE 19).

The acceptance stories:
- every lazy host materialization in the device EDS cache goes through
  the ledger helpers, so the ledger's per-site call counters move in
  lockstep with the pre-existing ``edscache.host_crossings`` counter;
- the warmed produce path's device-residency claim is PINNED:
  ``no_implicit_transfers()`` lets ledger-mediated fetches through and
  raises on a stray ``np.asarray`` of a device value;
- lock contention profiling records waits ONLY for acquires that
  actually blocked, and publishes per-site totals at scrape time;
- the GIL oversleep sampler starts per service label under the
  CELESTIA_OBS gate and lands its histogram + pressure gauge;
- fleetmon evaluates declarative SLO rules against a LIVE HTTP node
  into a deterministic verdict (byte-identical across scrapes of the
  same fleet state).
"""

import threading
import time
import urllib.request

import numpy as np
import pytest

import celestia_app_tpu.obs as obs
from celestia_app_tpu.obs import gil, xfer
from celestia_app_tpu.obs.xfer import ImplicitTransferError, no_implicit_transfers
from celestia_app_tpu.tools import fleetmon
from celestia_app_tpu.tools.analyze import racecheck
from celestia_app_tpu.utils import telemetry

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from test_consensus_multinode import _network  # noqa: E402


def _counter(name: str, **labels) -> float:
    snap = telemetry.snapshot()["counters"]
    if not labels:
        return snap.get(name, 0)
    key = name + "{" + ",".join(
        f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"
    return snap.get(key, 0)


def _ods(k: int = 4, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    ods[..., :29] = 0
    ods[..., 28] = 7
    return ods


# ---------------------------------------------------------------------------
# the transfer ledger vs edscache.host_crossings
# ---------------------------------------------------------------------------


@pytest.mark.backend
def test_ledger_counts_match_host_crossings(shard_small):
    """Each lazy materialization site of a sharded DeviceEntry (host
    square, row levels, col levels) is one ledger d2h call AND one
    host_crossing — the old narrow counter and the universal ledger
    agree."""
    from celestia_app_tpu.da import edscache

    with shard_small():
        entry = edscache.compute_entry(_ods(seed=11), "device")
    assert isinstance(entry, edscache.DeviceEntry) and entry.chips > 1
    passes = _counter("mesh.sharded_level_passes")

    before_cross = _counter("edscache.host_crossings")
    # the host square is the entry's own site; both level stacks come
    # down at the prover's (one name whichever engine built the entry)
    sites = {"edscache.eds": 1, "proof.row_levels": 2}
    before = {site: _counter("xfer.d2h_calls", site=site) for site in sites}
    bytes_before = xfer.totals()["d2h_bytes"]

    _ = entry.eds                       # host square
    entry.get_prover()                  # row levels -> host
    entry.get_col_prover()              # col levels -> host

    assert _counter("mesh.sharded_level_passes") - passes == 2
    for site, calls in sites.items():
        assert _counter("xfer.d2h_calls", site=site) - before[site] == calls, \
            site
    assert _counter("edscache.host_crossings") - before_cross == 3
    assert xfer.totals()["d2h_bytes"] > bytes_before

    # the second read of every site is cached: no further crossings
    snap2 = {site: _counter("xfer.d2h_calls", site=site) for site in before}
    _ = entry.eds
    entry.get_prover()
    entry.get_col_prover()
    for site in before:
        assert _counter("xfer.d2h_calls", site=site) == snap2[site]


@pytest.mark.backend
def test_no_implicit_transfers_pins_warmed_produce_path(shard_small):
    """The acceptance-criterion residency pin: a warmed sharded
    DeviceEntry's produce-side work stays on device inside
    `no_implicit_transfers()`, ledger-mediated fetches stay legal, and a
    stray np.asarray of the device value raises."""
    from celestia_app_tpu.da import edscache

    with shard_small():
        entry = edscache.compute_entry(_ods(seed=12), "device")
    assert isinstance(entry, edscache.DeviceEntry) and entry.chips > 1
    passes = _counter("mesh.sharded_level_passes")
    entry.warm()
    assert _counter("mesh.sharded_level_passes") - passes == 2

    with no_implicit_transfers():
        # the warmed path: device levels exist, nothing crosses
        assert entry.warmed()
        assert entry.residency() == "device"
        entry._device_levels(col=False)
        entry._device_levels(col=True)

        # a ledger-mediated fetch is EXPLICIT and allowed
        host = xfer.to_host(entry._eds_dev, "test.pin")
        assert host.shape[0] == 2 * entry.k

        # the stray read the pin exists to catch
        with pytest.raises(ImplicitTransferError):
            np.asarray(entry._eds_dev)

    # outside the region the probe is gone: plain numpy reads work
    assert np.asarray(entry._eds_dev).shape[0] == 2 * entry.k


def test_nbytes_of_counts_containers_and_scalars():
    assert xfer.nbytes_of(b"abc") == 3
    assert xfer.nbytes_of([b"ab", b"cd"]) == 4
    assert xfer.nbytes_of({"x": np.zeros(4, dtype=np.uint8)}) == 4
    assert xfer.nbytes_of(3.5) == 8
    assert xfer.nbytes_of(None) == 0
    assert xfer.nbytes_of(object()) == 0  # unknown leaf: never raises


# ---------------------------------------------------------------------------
# lock contention profiling (racecheck, CELESTIA_LOCKPROF semantics)
# ---------------------------------------------------------------------------


def test_lock_wait_histogram_only_for_contended_acquires():
    """Uncontended acquires aggregate locally (no telemetry on the hot
    path); a blocked acquire lands in lock.wait{site=...} and in the
    contended count; the scrape-time collector publishes the gauges."""
    racecheck.install()
    racecheck.set_order_tracking(False)
    racecheck.set_profiling(True)
    try:
        lk = threading.Lock()  # created after install -> tracked

        for _ in range(50):
            with lk:
                pass

        def holder():
            with lk:
                time.sleep(0.05)

        t = threading.Thread(target=holder)
        t.start()
        time.sleep(0.01)
        with lk:  # blocks until holder releases
            pass
        t.join()

        stats = racecheck.prof_stats()
        site, st = next((s, v) for s, v in stats.items()
                        if "test_boundary_obs" in s)
        assert st["acquires"] >= 52
        assert st["contended"] >= 1
        assert st["hold_max_s"] >= 0.04  # the holder's sleep

        page = telemetry.prometheus()
        esc = site.replace("\\", "\\\\")
        assert f'celestia_lock_acquires{{site="{esc}"}}' in page
        assert f'celestia_lock_contended{{site="{esc}"}}' in page
        assert f'celestia_lock_wait_seconds_count{{site="{esc}"}}' in page
        # exactly the blocked acquire was observed, not the 50 fast ones
        count_line = next(
            ln for ln in page.splitlines()
            if ln.startswith("celestia_lock_wait_seconds_count")
            and esc in ln)
        assert float(count_line.rsplit(" ", 1)[1]) < 5
    finally:
        racecheck.set_profiling(False)
        racecheck.uninstall()
        racecheck.reset()


def test_lock_profiling_survives_condition_waits():
    """cond.wait hands the lock back and reacquires: the wrapper's
    Condition integration must keep working with profiling armed, and
    the wait inside cond.wait is NOT mutex contention."""
    racecheck.install()
    racecheck.set_order_tracking(False)
    racecheck.set_profiling(True)
    try:
        cond = threading.Condition(threading.Lock())
        got = []

        def waiter():
            with cond:
                got.append(cond.wait(timeout=2.0))

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.02)
        with cond:
            cond.notify()
        t.join()
        assert got == [True]
    finally:
        racecheck.set_profiling(False)
        racecheck.uninstall()
        racecheck.reset()


# ---------------------------------------------------------------------------
# the GIL oversleep sampler
# ---------------------------------------------------------------------------


def test_gil_sampler_gated_started_and_stopped():
    obs.set_enabled(False)
    try:
        assert gil.start("t-gated") is False  # CELESTIA_OBS gate
    finally:
        obs.set_enabled(True)
    try:
        assert gil.start("t-live") is True
        assert gil.start("t-live") is False  # idempotent per label
        assert "t-live" in gil.running()
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if _counter("gil.oversleep", service="t-live") or \
                    telemetry.snapshot()["timers"].get(
                        'gil.oversleep{service="t-live"}'):
                break
            time.sleep(gil.INTERVAL_S)
        snap = telemetry.snapshot()
        assert 'gil.oversleep{service="t-live"}' in snap["timers"]
        assert 'gil.pressure{service="t-live"}' in snap["gauges"]
    finally:
        gil.stop_all()
        obs.set_enabled(None)
    deadline = time.time() + 2.0
    while "t-live" in gil.running() and time.time() < deadline:
        time.sleep(0.01)
    assert "t-live" not in gil.running()


def test_peak_rss_gauge_collected_on_scrape():
    page = telemetry.prometheus()
    line = next(ln for ln in page.splitlines()
                if ln.startswith("celestia_process_peak_rss_bytes "))
    assert float(line.split(" ")[1]) > 0


# ---------------------------------------------------------------------------
# fleetmon: the SLO verdict engine against a live node
# ---------------------------------------------------------------------------


def test_fleetmon_verdict_live_node_deterministic(tmp_path):
    """Scrape a real HTTP validator service, judge rules over metrics
    AND status sources, and require byte-identical verdicts across two
    scrapes of the same (quiesced) fleet state."""
    from celestia_app_tpu.service.validator_server import ValidatorService

    # the SLO rules judge absolute process-global counters: earlier
    # suites in the same pytest process legitimately open breakers /
    # serve 500s, so start from a clean registry
    telemetry.reset()
    net, _signer, _privs = _network(tmp_path, n=1, with_disk=False)
    svc = ValidatorService(net.nodes[0], port=0)
    svc.serve_background()
    url = f"http://127.0.0.1:{svc.port}"
    try:
        rules = fleetmon.normalize_rules({"slo": [
            {"name": "no-500s", "metric": "http.500", "op": "==",
             "value": 0, "agg": "each"},
            {"name": "no-breaker-opens", "metric": "net.breaker_open",
             "op": "==", "value": 0, "agg": "sum"},
            {"name": "height-at-genesis", "source": "status",
             "path": "height", "op": ">=", "value": 0, "agg": "each"},
        ]})
        f1 = fleetmon.scrape_fleet([url], with_availability=False)
        f2 = fleetmon.scrape_fleet([url], with_availability=False)
        v1 = fleetmon.evaluate(rules, f1)
        v2 = fleetmon.evaluate(rules, f2)
        assert v1["pass"] is True and v1["failed"] == []
        assert v1["schema"] == fleetmon.SCHEMA
        assert fleetmon.verdict_bytes(v1) == fleetmon.verdict_bytes(v2)

        # a rule that cannot hold fails loudly, with the rule named
        bad = fleetmon.normalize_rules([
            {"name": "tiny-rss", "metric": "process.peak_rss_bytes",
             "kind": "gauge", "op": "<=", "value": 1, "agg": "each"},
        ])
        vb = fleetmon.evaluate(bad, f1)
        assert vb["pass"] is False and vb["failed"] == ["tiny-rss"]
    finally:
        svc.shutdown()


def test_fleetmon_dark_node_fails_each_rules():
    fleet = {"nodes": {"gone": {"metrics": None, "error": "URLError"}}}
    rules = fleetmon.normalize_rules([
        {"name": "no-500s", "metric": "http.500", "op": "==", "value": 0},
    ])
    v = fleetmon.evaluate(rules, fleet)
    assert v["pass"] is False
    assert v["dark_nodes"] == ["gone"]
    assert v["failed"] == ["no-500s"]


def test_fleetmon_rejects_malformed_rules():
    for doc in (
        [],                                        # empty
        [{"metric": "x"}],                         # no name
        [{"name": "a", "op": "~="}],               # bad op
        [{"name": "a", "metric": "m", "kind": "p42"}],  # bad kind
        [{"name": "a", "source": "status"}],       # status needs path
        [{"name": "a", "metric": "m", "value": "zero"}],  # non-numeric
    ):
        with pytest.raises(ValueError):
            fleetmon.normalize_rules(doc)


# ---------------------------------------------------------------------------
# the per-block boundary gauge on a live chain
# ---------------------------------------------------------------------------


def test_host_bytes_crossed_per_block_gauge_set_on_commit(tmp_path):
    """chain/app.py publishes the per-commit ledger delta as the gauge
    PR 20 optimizes against, and the validator /metrics page serves it."""
    from celestia_app_tpu.service.validator_server import ValidatorService

    net, signer, privs = _network(tmp_path, n=1, with_disk=False)
    net.produce_height(t=1_700_000_010.0)
    gauges = telemetry.snapshot()["gauges"]
    assert "xfer.host_bytes_crossed_per_block" in gauges

    svc = ValidatorService(net.nodes[0], port=0)
    svc.serve_background()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{svc.port}/metrics") as r:
            page = r.read().decode()
        assert "celestia_xfer_host_bytes_crossed_per_block" in page
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# the effect system's fixes (ISSUE 20): every warmed-path boundary
# crossing xfer-reach surfaced now rides the counted helpers — pinned
# here so the static proof and the runtime ledger cannot drift apart
# ---------------------------------------------------------------------------


def test_ensure_host_counts_device_inputs_only():
    """The materialize-if-device helper: a device value comes back
    through the counted d2h path; a host array passes through with NO
    ledger row (a fake row for a zero-copy read would be worse than
    none)."""
    import jax.numpy as jnp

    before = xfer.totals()
    out = xfer.ensure_host(np.arange(16, dtype=np.uint8), "test.ensure")
    assert isinstance(out, np.ndarray)
    mid = xfer.totals()
    assert mid["d2h_calls"] == before["d2h_calls"]
    assert mid["d2h_bytes"] == before["d2h_bytes"]
    out2 = xfer.ensure_host(jnp.arange(16, dtype=jnp.uint8), "test.ensure")
    assert isinstance(out2, np.ndarray)
    after = xfer.totals()
    assert after["d2h_calls"] == mid["d2h_calls"] + 1
    assert after["d2h_bytes"] == mid["d2h_bytes"] + 16


def test_cmt_device_hash_routes_through_ledger():
    """xfer-reach regression pin: the CMT device sha engine's upload
    AND its digest download are both counted (da/cmt.py used raw
    jnp.asarray on the way out before ISSUE 20)."""
    from celestia_app_tpu.da import cmt

    before = xfer.totals()
    digests = cmt._hash_symbols(np.zeros((4, 64), dtype=np.uint8),
                                "device")
    after = xfer.totals()
    assert digests.shape == (4, 32) and isinstance(digests, np.ndarray)
    assert after["h2d_calls"] == before["h2d_calls"] + 1
    assert after["d2h_calls"] == before["d2h_calls"] + 1


@pytest.mark.parametrize("mod_name", ["ldpc", "polar"])
def test_device_encode_routes_through_ledger(mod_name):
    """xfer-reach regression pin: both codec device encoders upload the
    shards and download the coded symbols through the ledger (their
    outputs came back as raw np.asarray(device) before ISSUE 20)."""
    import importlib

    mod = importlib.import_module(f"celestia_app_tpu.ops.{mod_name}")
    data = np.random.RandomState(0).randint(
        0, 256, (8, 64), dtype=np.uint8)
    before = xfer.totals()
    coded = mod.encode(data, engine="device")
    after = xfer.totals()
    assert isinstance(coded, np.ndarray)
    assert after["h2d_calls"] == before["h2d_calls"] + 1
    assert after["d2h_calls"] == before["d2h_calls"] + 1


def test_block_prover_device_levels_cross_counted():
    """xfer-reach regression pin: BlockProver's one device pass crosses
    the boundary exactly twice (EDS up, NMT levels down), and the
    normalized levels land as host ndarrays via ensure_host — no
    uncounted materialization remains on the proof path."""
    from celestia_app_tpu.da import dah, proof_device

    rng = np.random.default_rng(2)
    ods = rng.integers(0, 256, (2, 2, 512), dtype=np.uint8)
    d, eds_obj, _root = dah.new_dah_from_ods(ods)
    before = xfer.totals()
    prover = proof_device.BlockProver(eds_obj, d)
    after = xfer.totals()
    assert after["h2d_calls"] == before["h2d_calls"] + 1
    assert after["d2h_calls"] == before["d2h_calls"] + 1
    assert all(isinstance(arr, np.ndarray)
               for level in prover.levels for arr in level)


def test_started_copy_counts_at_landing_and_times_the_wait():
    """`HostFetch`: bytes and the call are counted when the copy lands,
    on its own thread, whose `xfer.fetch:<site>` span is the copy's
    length; `result()` is the reader's half — the site's d2h span and
    latency sample, for what the reader waited."""
    import jax.numpy as jnp

    site = "test.started_copy"
    value = jnp.arange(4096, dtype=jnp.uint8)
    b0, c0 = _counter("xfer.d2h_bytes", site=site), \
        _counter("xfer.d2h_calls", site=site)
    span_n = f'obs.span_n{{name="xfer.d2h:{site}"}}'
    fetch_n = f'obs.span_n{{name="xfer.fetch:{site}"}}'
    n0, f0 = _counter(span_n), _counter(fetch_n)
    total0 = xfer.totals()["d2h_bytes"]
    fetch = xfer.HostFetch(value, site)
    deadline = time.monotonic() + 30
    while not fetch.ready():
        assert time.monotonic() < deadline
        time.sleep(0.002)
    assert _counter("xfer.d2h_bytes", site=site) - b0 == 4096
    assert _counter("xfer.d2h_calls", site=site) - c0 == 1
    assert xfer.totals()["d2h_bytes"] - total0 >= 4096
    with no_implicit_transfers():      # the read is a ledger read
        out = fetch.result()
    assert isinstance(out, np.ndarray) and out.tobytes() == bytes(
        range(256)) * 16
    assert _counter("xfer.d2h_bytes", site=site) - b0 == 4096   # once
    assert _counter("xfer.d2h_calls", site=site) - c0 == 1
    if obs.spans.enabled():
        assert _counter(span_n) - n0 == 1
        assert _counter(fetch_n) - f0 == 1


def test_started_copy_failure_is_counted_and_raised_in_its_reader():
    e0 = _counter("obs.xfer_fetch_errors")
    b0 = xfer.totals()["d2h_bytes"]

    class NotAnArray:
        def __array__(self, *a, **k):
            raise RuntimeError("device lost")

    fetch = xfer.HostFetch([NotAnArray()], "test.started_copy_fails")
    deadline = time.monotonic() + 30
    while not fetch.ready():
        assert time.monotonic() < deadline
        time.sleep(0.002)
    assert _counter("obs.xfer_fetch_errors") - e0 == 1
    with pytest.raises(RuntimeError, match="device lost"):
        fetch.result()
    assert xfer.totals()["d2h_bytes"] == b0
