"""Block plane: the extend-once lifecycle (da/edscache.py).

Tier-1 pins for ISSUE 8: a proposer's full produce→commit→first-sample
cycle dispatches exactly ONE extend+NMT pipeline run (`da.extend_runs`),
a follower's process→finalize→commit→sample likewise; cached and cold
paths are byte-identical on both engines; eviction recomputes correctly;
a Byzantine data_hash cannot ride the cache past rejection; and
concurrent samplers of a fresh height single-flight through ONE square
build.
"""

import threading
import time

import numpy as np
import pytest

from celestia_app_tpu.chain.app import App
from celestia_app_tpu.chain.crypto import PrivateKey
from celestia_app_tpu.chain.node import Node
from celestia_app_tpu.chain.tx import MsgSend
from celestia_app_tpu.client.tx_client import Signer
from celestia_app_tpu.da import edscache
from celestia_app_tpu.das.server import SampleCore
from celestia_app_tpu.utils import telemetry

CHAIN = "edscache-test"


def _c(name: str) -> int:
    return telemetry.snapshot()["counters"].get(name, 0)


def _ods(k: int = 4, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    ods[..., :29] = 0
    ods[..., 28] = 7  # one user namespace, sorted layout
    return ods


def _app(tmp_path=None, engine: str = "host", n: int = 2):
    privs = [PrivateKey.from_seed(b"edsc-%d" % i) for i in range(n)]
    addrs = [p.public_key().address() for p in privs]
    app = App(chain_id=CHAIN, engine=engine,
              data_dir=str(tmp_path) if tmp_path is not None else None)
    app.init_chain({
        "time_unix": 1_700_000_000.0,
        "accounts": [{"address": a.hex(), "balance": 10**12}
                     for a in addrs],
        "validators": [{"operator": addrs[0].hex(), "power": 10}],
    })
    signer = Signer(CHAIN)
    for i, p in enumerate(privs):
        signer.add_account(p, number=i)
    return app, signer, addrs


def _txs(signer, addrs, amount: int = 1) -> list[bytes]:
    out = []
    for i, a in enumerate(addrs):
        tx = signer.create_tx(
            a, [MsgSend(a, addrs[(i + 1) % len(addrs)], amount)],
            fee=2000, gas_limit=100_000,
        )
        signer.accounts[a].sequence += 1
        out.append(tx.encode())
    return out


# ---------------------------------------------------------------------------
# the telemetry-pinned invariant: one extend per (node, height)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["host", "auto"])
def test_proposer_cycle_dispatches_exactly_one_extend(tmp_path, engine):
    """produce (prepare + process) → commit → first DAS sample: ONE
    `da.extend_runs`, zero `das.square_builds` (the commit seeded the
    serving core from the warmer thread) — on the host engine and the
    jitted device path alike (CPU backend under tier-1)."""
    app, signer, addrs = _app(tmp_path, engine=engine)
    node = Node(app)
    core = node.attach_das_core(SampleCore(app))
    try:
        for raw in _txs(signer, addrs):
            assert node.broadcast_tx(raw).code == 0
        c0 = _c("da.extend_runs")
        node.produce_block(t=1_700_000_001.0)
        assert app.da_warmer.wait_idle(30)
        seeded = _c("edscache.seeded")
        assert seeded >= 1
        b0 = _c("das.square_builds")
        out = core.sample(1, 0, 0)
        assert out["samples"][0]["share"]
        # the whole cycle paid ONE pipeline dispatch; the sample paid none
        assert _c("da.extend_runs") - c0 == 1
        assert _c("das.square_builds") - b0 == 0
        # and the warmer pre-built both provers before the sample landed
        assert core._cache[1].cache_entry.warmed()
    finally:
        app.close()


def test_follower_cycle_dispatches_exactly_one_extend(tmp_path):
    """A follower validating a gossiped proposal: process → finalize →
    commit → first sample = ONE extend, on ITS node. Serving works with
    no block store at all — the seeded entry is the gossip handoff."""
    proposer, signer, addrs = _app(tmp_path, n=2)
    follower, _, _ = _app(None, n=2)  # no data_dir: seeding must suffice
    core = SampleCore(follower)
    follower.add_da_seed_listener(core.seed_cache_entry)
    try:
        raws = _txs(signer, addrs)
        prop = proposer.prepare_proposal(raws, t=1_700_000_001.0)
        c0 = _c("da.extend_runs")
        assert follower.process_proposal(prop.block)
        follower.finalize_block(prop.block)
        follower.commit(prop.block)
        assert follower.da_warmer.wait_idle(30)
        out = core.sample(1, 0, 0)
        assert out["data_root"] == prop.block.header.data_hash.hex()
        assert _c("da.extend_runs") - c0 == 1
    finally:
        proposer.close()


def test_byzantine_data_hash_rejected_despite_warm_cache(tmp_path):
    """A wrong header data_hash must reject even when the honest entry is
    already cached — the cache changes who pays for the truth, never the
    truth: the entry is a pure function of the ODS, and the header is
    compared against it the same way hot or cold."""
    import dataclasses

    proposer, signer, addrs = _app(tmp_path)
    follower, _, _ = _app(None)
    try:
        prop = proposer.prepare_proposal(_txs(signer, addrs),
                                         t=1_700_000_001.0)
        bad_header = dataclasses.replace(prop.block.header,
                                         data_hash=b"\xee" * 32)
        bad_block = dataclasses.replace(prop.block, header=bad_header)
        assert not follower.process_proposal(bad_block)
        # the honest block still validates on the (now warm) cache
        assert follower.process_proposal(prop.block)
    finally:
        proposer.close()


# ---------------------------------------------------------------------------
# differential: cached == cold, host == device, proofs included
# ---------------------------------------------------------------------------


def _entries_equal(a: edscache.EdsCacheEntry, b: edscache.EdsCacheEntry):
    assert a.data_root == b.data_root
    assert a.dah.row_roots == b.dah.row_roots
    assert a.dah.col_roots == b.dah.col_roots
    assert np.array_equal(a.eds.squares, b.eds.squares)


@pytest.mark.backend
def test_cached_cold_and_cross_engine_byte_identical():
    ods = _ods(k=4, seed=3)
    host_cold = edscache.compute_entry(ods, "host")
    dev_cold = edscache.compute_entry(ods, "auto")  # jitted path (CPU backend)
    _entries_equal(host_cold, dev_cold)

    cache = edscache.EdsCache(max_entries=2)
    warm = cache.get_or_compute(ods, "host")
    again = cache.get_or_compute(ods, "host")
    assert again is warm  # a hit returns the SAME object
    _entries_equal(warm, dev_cold)

    # proofs: host-levels prover vs jitted-levels prover, byte for byte
    ph = host_cold.get_prover()
    pd = dev_cold.get_prover()
    for (r, c) in [(0, 0), (3, 7), (7, 2), (5, 5)]:
        sh, prh = ph.prove_cell(r, c)
        sd, prd = pd.prove_cell(r, c)
        assert sh == sd
        assert prh.nodes == prd.nodes
        assert (prh.start, prh.end, prh.total) == (prd.start, prd.end,
                                                   prd.total)
    # col provers too (the BEFP escalation surface)
    ch = host_cold.get_col_prover()
    cd = dev_cold.get_col_prover()
    s1, p1 = ch.prove_cell(2, 6)
    s2, p2 = cd.prove_cell(2, 6)
    assert s1 == s2 and p1.nodes == p2.nodes


def test_eviction_recomputes_byte_identical():
    cache = edscache.EdsCache(max_entries=1)
    o1, o2 = _ods(seed=1), _ods(seed=2)
    e1 = cache.get_or_compute(o1, "host")
    ev0 = _c("edscache.evictions")
    e2 = cache.get_or_compute(o2, "host")
    assert _c("edscache.evictions") - ev0 == 1
    assert len(cache) == 1
    # o1 was evicted: recomputing pays a fresh pipeline run but lands on
    # identical bytes, and the root index followed the eviction
    assert cache.lookup_root(e1.data_root) is None
    assert cache.lookup_root(e2.data_root) is e2
    c0 = _c("da.extend_runs")
    e1b = cache.get_or_compute(o1, "host")
    assert _c("da.extend_runs") - c0 == 1
    _entries_equal(e1, e1b)


def test_cache_key_is_content_addressed():
    o = _ods(seed=4)
    assert edscache.cache_key(o) == edscache.cache_key(o.copy())
    o2 = o.copy()
    o2[0, 0, 100] ^= 1
    assert edscache.cache_key(o) != edscache.cache_key(o2)


# ---------------------------------------------------------------------------
# single-flight serving + warmer behavior
# ---------------------------------------------------------------------------


def test_concurrent_samplers_single_flight(tmp_path, monkeypatch):
    """Two handler threads missing the same fresh height pay ONE square
    build between them (the in-progress map in SampleCore._entry)."""
    from celestia_app_tpu.chain import query as query_mod

    app, signer, addrs = _app(tmp_path)
    node = Node(app)
    try:
        for raw in _txs(signer, addrs):
            node.broadcast_tx(raw)
        node.produce_block(t=1_700_000_001.0)
        app.da_warmer.wait_idle(30)
        core = SampleCore(app)  # NOT seeded: first sample must build

        calls = []
        real = query_mod.build_prover_entry

        def slow_build(app_, height):
            calls.append(height)
            time.sleep(0.15)  # hold the window open for the second thread
            return real(app_, height)

        monkeypatch.setattr(query_mod, "build_prover_entry", slow_build)
        coal0 = _c("das.entry_coalesced")
        results, errors = [], []

        def sample(cell):
            try:
                results.append(core.sample(1, *cell))
            except Exception as e:  # surface, don't deadlock the join
                errors.append(e)

        threads = [threading.Thread(target=sample, args=((0, i),))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors
        assert len(results) == 4
        assert len(calls) == 1  # ONE build for four concurrent samplers
        assert _c("das.entry_coalesced") - coal0 >= 1
        assert len({r["data_root"] for r in results}) == 1
    finally:
        app.close()


def test_warmer_coalesces_to_newest(tmp_path):
    """A burst of commits (the blocksync-batch shape) never queues one
    warm build per height: superseded slots are counted and dropped, and
    the cache itself still guarantees extend-once for the skipped ones."""
    app, signer, addrs = _app(tmp_path)
    node = Node(app)
    core = node.attach_das_core(SampleCore(app))
    try:
        t = 1_700_000_001.0
        for _ in range(5):
            for raw in _txs(signer, addrs):
                node.broadcast_tx(raw)
            node.produce_block(t=t)
            t += 1.0
        assert app.da_warmer.wait_idle(30)
        # the NEWEST height is always seeded once the warmer drains
        tip = app.height
        assert core._cache[tip].cache_entry.warmed()
        # a warm-skipped height inside the content-cache window still
        # serves with at most a square rebuild, never a re-extend
        c0 = _c("da.extend_runs")
        core.sample(tip - 1, 0, 0)
        assert _c("da.extend_runs") - c0 == 0
        # ...while one evicted past the LRU window pays exactly one fresh
        # pipeline run (bounded memory has a price; it is one, not three)
        c0 = _c("da.extend_runs")
        core.sample(1, 0, 0)
        assert _c("da.extend_runs") - c0 == 1
    finally:
        app.close()


def test_validator_service_serves_seeded_das_samples(tmp_path):
    """Validator processes serve /das/* too now: a commit through
    ValidatorNode.apply seeds the service's SampleCore, and the sample
    verifies against the height's DAH."""
    import json as json_mod
    import urllib.request

    from celestia_app_tpu.chain import consensus as cons
    from celestia_app_tpu.da import sampling
    from celestia_app_tpu.da.dah import DataAvailabilityHeader
    from celestia_app_tpu.das.daser import DASer
    from celestia_app_tpu.service.validator_server import ValidatorService

    priv = PrivateKey.from_seed(b"edsc-val")
    addr = priv.public_key().address()
    genesis = {
        "time_unix": 1_700_000_000.0,
        "accounts": [{"address": addr.hex(), "balance": 10**12}],
        "validators": [{"operator": addr.hex(), "power": 10,
                        "pubkey": priv.public_key().compressed.hex()}],
    }
    vnode = cons.ValidatorNode("val0", priv, genesis, CHAIN,
                               data_dir=str(tmp_path / "val0"))
    net = cons.LocalNetwork([vnode])
    svc = ValidatorService(vnode)
    svc.serve_background()
    try:
        net.produce_height(t=1_700_000_001.0)
        assert vnode.app.da_warmer.wait_idle(30)
        url = f"http://127.0.0.1:{svc.port}"
        with urllib.request.urlopen(url + "/das/header?height=1",
                                    timeout=10) as r:
            hdr = json_mod.loads(r.read())
        dah = DataAvailabilityHeader(
            tuple(bytes.fromhex(x) for x in hdr["row_roots"]),
            tuple(bytes.fromhex(x) for x in hdr["col_roots"]),
        )
        with urllib.request.urlopen(
            url + "/das/sample?height=1&row=0&col=0", timeout=10
        ) as r:
            doc = json_mod.loads(r.read())
        share, proof = DASer._decode_sample(doc["samples"][0])
        assert sampling.verify_sample(dah, 0, 0, share, proof)
    finally:
        try:
            svc.httpd.shutdown()
        except Exception:
            pass
        vnode.app.close()


# ---------------------------------------------------------------------------
# ISSUE 32: the single-device engine's entry is resident — only the roots
# cross inside compute_entry, the host copy follows, the prover's level
# passes read the resident array
# ---------------------------------------------------------------------------


def _site(name: str, site: str) -> int:
    return _c(f'{name}{{site="{site}"}}')


def _quadrant_cells(k: int) -> list[tuple[int, int]]:
    """Two cells of each quadrant of the 2k x 2k square (corners and an
    inner one), Q0..Q3."""
    lo, hi = (0, k - 1), (k, 2 * k - 1)
    return [(r, c) for rows in (lo, hi) for cols in (lo, hi)
            for r, c in ((rows[0], cols[0]), (rows[1], cols[1]))]


@pytest.mark.backend
@pytest.mark.parametrize("k", [2, 8, 32])
def test_device_engine_entry_is_resident_and_byte_identical(k):
    ods = _ods(k=k, seed=30 + k)
    host = edscache.compute_entry(ods, "host")
    dev = edscache.compute_entry(ods, "device")
    assert isinstance(dev, edscache.DeviceEntry)
    assert not isinstance(host, edscache.DeviceEntry)
    assert len(dev.dah.row_roots) == len(dev.dah.col_roots) == 2 * k
    _entries_equal(host, dev)
    row_h, row_d = host.get_prover(), dev.get_prover()
    col_h, col_d = host.get_col_prover(), dev.get_col_prover()
    for r, c in _quadrant_cells(k):
        for ph, pd, cell in ((row_h, row_d, (r, c)), (col_h, col_d, (c, r))):
            sh, prh = ph.prove_cell(*cell)
            sd, prd = pd.prove_cell(*cell)
            assert sh == sd == host.eds.squares[r, c].tobytes()
            assert prh.nodes == prd.nodes
            assert (prh.start, prh.end, prh.total) == \
                (prd.start, prd.end, prd.total)
    # the column prover reads the host square through a transposed view
    assert np.shares_memory(col_d.eds.squares, dev.eds.squares)


class _FetchOnDemand:
    """`xfer.HostFetch` with the start taken out: the copy happens
    at `result()`, so a test can say exactly what has crossed when."""

    def __init__(self, value, site):
        self._value, self.site = value, site

    def ready(self) -> bool:
        return False

    def result(self):
        from celestia_app_tpu.obs import xfer

        return xfer.to_host(self._value, self.site)


@pytest.mark.backend
@pytest.mark.parametrize("k", [2, 8])
def test_only_the_roots_cross_inside_compute_entry(k, monkeypatch):
    from celestia_app_tpu.obs import xfer

    monkeypatch.setattr(xfer, "HostFetch", _FetchOnDemand)
    site = "edscache.compute_entry"
    ods = _ods(k=k, seed=40 + k)
    d0, u0 = _site("xfer.d2h_bytes", site), _site("xfer.h2d_bytes", site)
    entry = edscache.compute_entry(ods, "device")
    assert _site("xfer.h2d_bytes", site) - u0 == ods.nbytes
    assert _site("xfer.d2h_bytes", site) - d0 == 4 * k * 90 + 32
    assert entry.residency() == "device"
    # both level passes and the commitments: still nothing more
    entry.warm()
    assert len(entry.data_root) == 32
    assert _site("xfer.d2h_bytes", site) - d0 == 4 * k * 90 + 32
    # the first host read brings the square down, once
    square = (2 * k) ** 2 * 512
    assert entry.eds.squares.nbytes == square
    assert _site("xfer.d2h_bytes", site) - d0 == 4 * k * 90 + 32 + square
    assert entry.residency() == "device+host"
    entry.get_prover()
    entry.get_col_prover()
    _ = entry.eds
    assert _site("xfer.d2h_bytes", site) - d0 == 4 * k * 90 + 32 + square


@pytest.mark.backend
def test_started_copy_lands_and_is_counted_once():
    """The real helper: the copy runs on its own thread, its bytes are
    counted when they land whether or not anyone reads them, and the
    first host read counts ready or waited — never both, never twice."""
    from celestia_app_tpu.obs import xfer

    k, site = 8, "edscache.compute_entry"
    names = ("edscache.eds_fetch_started", "edscache.eds_fetch_waited",
             "edscache.eds_fetch_ready")
    c0 = [_c(n) for n in names]
    d0, n0 = _site("xfer.d2h_bytes", site), _site("xfer.d2h_calls", site)
    entries = [edscache.compute_entry(_ods(k=k, seed=50 + i), "device")
               for i in range(3)]
    fetches = [e._eds_fetch for e in entries]
    assert all(isinstance(f, xfer.HostFetch) for f in fetches)
    deadline = time.monotonic() + 30
    while not all(f.ready() for f in fetches):
        assert time.monotonic() < deadline
        time.sleep(0.005)
    per_entry = 4 * k * 90 + 32 + (2 * k) ** 2 * 512
    assert _site("xfer.d2h_bytes", site) - d0 == 3 * per_entry
    assert _site("xfer.d2h_calls", site) - n0 == 6  # roots + square, each
    for e in entries[:2]:       # the third is never read
        _ = e.eds
        _ = e.eds
    started, waited, ready = (_c(n) - c for n, c in zip(names, c0))
    assert (started, waited, ready) == (3, 0, 2)
    assert _site("xfer.d2h_bytes", site) - d0 == 3 * per_entry
    assert entries[0]._eds_fetch is None


@pytest.mark.backend
def test_a_read_before_the_copy_lands_counts_waited(monkeypatch):
    from celestia_app_tpu.obs import xfer

    gate = threading.Event()
    real_get = xfer.HostFetch._run

    def held(self, value):
        gate.wait(30)
        real_get(self, value)

    monkeypatch.setattr(xfer.HostFetch, "_run", held)
    w0, r0 = _c("edscache.eds_fetch_waited"), _c("edscache.eds_fetch_ready")
    host = edscache.compute_entry(_ods(k=4, seed=60), "host")
    entry = edscache.compute_entry(_ods(k=4, seed=60), "device")
    assert not entry._eds_fetch.ready()
    got = []
    reader = threading.Thread(target=lambda: got.append(entry.eds))
    reader.start()
    # the reader counts itself as waiting before it blocks; only then
    # is the copy let go
    deadline = time.monotonic() + 30
    while _c("edscache.eds_fetch_waited") - w0 < 1:
        assert time.monotonic() < deadline
        time.sleep(0.002)
    assert not got
    gate.set()
    reader.join(timeout=30)
    assert not reader.is_alive()
    assert np.array_equal(got[0].squares, host.eds.squares)
    assert _c("edscache.eds_fetch_waited") - w0 == 1
    assert _c("edscache.eds_fetch_ready") - r0 == 0


@pytest.mark.backend
def test_prover_on_a_resident_entry_uploads_nothing():
    k, site = 8, "proof.row_levels"
    entry = edscache.compute_entry(_ods(k=k, seed=70), "device")
    u0, d0 = _site("xfer.h2d_bytes", site), _site("xfer.d2h_bytes", site)
    up0 = _c("xfer.h2d_calls")
    entry.warm()
    row = entry.get_prover()
    col = entry.get_col_prover()
    assert _site("xfer.h2d_bytes", site) - u0 == 0
    # each orientation's level stack came down at the prover's site, once
    stack = sum(a.nbytes for lvl in row.levels for a in lvl)
    assert stack == sum(a.nbytes for lvl in col.levels for a in lvl)
    assert _site("xfer.d2h_bytes", site) - d0 == 2 * stack
    share, proof = row.prove_cell(2 * k - 1, 3)
    assert share == entry.eds.squares[2 * k - 1, 3].tobytes()


@pytest.mark.backend
def test_warmed_chain_answers_the_sample_from_the_built_prover(tmp_path):
    """produce -> commit -> warm -> sample on the jitted engine: ONE
    extend a block, the level pass of each orientation run once (by the
    warmer, on the resident array) and the light round answered by the
    prover built over it — no rebuild, no second level pass."""
    app, signer, addrs = _app(tmp_path, engine="auto")
    node = Node(app)
    core = node.attach_das_core(SampleCore(app))
    span_n = 'obs.span_n{name="proof.levels.run"}'
    try:
        t = 1_700_000_001.0
        for height in (1, 2):
            for raw in _txs(signer, addrs):
                assert node.broadcast_tx(raw).code == 0
            c0, l0 = _c("da.extend_runs"), _c(span_n)
            b0, s0 = _c("das.square_builds"), _c("edscache.eds_fetch_started")
            node.produce_block(t=t)
            t += 1.0
            assert app.da_warmer.wait_idle(30)
            entry = core._cache[height].cache_entry
            assert isinstance(entry, edscache.DeviceEntry)
            assert entry.warmed()
            levels = entry._levels_dev
            out = core.sample_many(height, [(0, 0), (1, 1)])
            assert len(out["samples"]) == 2
            assert entry.get_prover() is core._cache[height].prover
            assert entry._levels_dev is levels
            assert _c("da.extend_runs") - c0 == 1
            assert _c("edscache.eds_fetch_started") - s0 == 1
            assert _c("das.square_builds") - b0 == 0
            if _c(span_n):  # span totals follow the CELESTIA_OBS gate
                assert _c(span_n) - l0 == 2
    finally:
        app.close()


@pytest.mark.backend
def test_evicted_entry_frees_its_device_arrays_by_refcount():
    """No cycle may hold `_eds_dev`: an entry the LRU drops gives its
    device arrays back at once, not at the next full collection."""
    import gc
    import weakref

    cache = edscache.EdsCache(max_entries=1)
    first = cache.get_or_compute(_ods(k=4, seed=80), "device")
    first.warm()
    first.get_prover()
    first.get_col_prover()
    refs = [weakref.ref(first._eds_dev), weakref.ref(first._levels_dev[0][0]),
            weakref.ref(first)]
    gc.disable()
    try:
        ev0 = _c("edscache.evictions")
        del first
        cache.get_or_compute(_ods(k=4, seed=81), "device")
        assert _c("edscache.evictions") - ev0 == 1
        # the copy's thread lets go of the array as it ends
        deadline = time.monotonic() + 30
        while any(r() is not None for r in refs):
            assert time.monotonic() < deadline, \
                [type(r()).__name__ for r in refs if r() is not None]
            time.sleep(0.005)
    finally:
        gc.enable()
