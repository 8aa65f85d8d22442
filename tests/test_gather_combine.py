"""The gathers that wait together go out as one dispatch.

A copy-less entry (a mesh-engine height on the 8 virtual CPU devices,
no host copy) cuts a request's cells on the devices. Requests for the
same entry and orientation that arrive while a dispatch is in flight
queue (`edscache.GatherQueue`) and go out in ONE following program; a
lone request dispatches at once, on its own thread. The contract under
test: every reply is the host engine's byte for byte; `das.gather_joined`
counts the requests another thread's dispatch carried, so joined +
dispatches = requests; a queue longer than the cap splits; heights and
orientations never share a dispatch; a dispatch that raises raises in
every request it carried and the next one is served; after the first
dispatch's warm no bucket compiles.
"""

import random
import sys
import threading
import time

import jax.monitoring
import numpy as np
import pytest

from celestia_app_tpu.da import edscache, proof_device
from celestia_app_tpu.utils import telemetry

K = 8
WIDTH = 2 * K
HEIGHTS = (3, 4)
WAIT_S = 60


def _counter(name: str) -> int:
    return telemetry.snapshot().get("counters", {}).get(name, 0)


def _random_ods(seed: int) -> np.ndarray:
    ods = np.random.default_rng(seed).integers(
        0, 256, size=(K, K, 512), dtype=np.uint8)
    ods[:, :, 0] = 0
    ods[:, :, 1:19] = 0
    return ods


class _Chain:
    """Two copy-less mesh heights behind one SampleCore, and the host
    engine's core over the same squares."""

    def __init__(self, shard_small):
        from celestia_app_tpu.chain.app import App
        from celestia_app_tpu.das.server import SampleCore

        self.app = App(chain_id="gather-combine")
        self.app.init_chain({"time_unix": 0})
        self.core, self.core_h = SampleCore(self.app), SampleCore(self.app)
        self.entries, self.hosts = {}, {}
        for h in HEIGHTS:
            ods = _random_ods(5100 + h)
            with shard_small():
                entry = edscache.compute_entry(ods, "device")
            assert entry.chips == 8 and entry.residency() == "device"
            self.entries[h] = entry
            self.hosts[h] = edscache.compute_entry(ods, "host")
            self.core.seed_cache_entry(h, entry)
            self.core_h.seed_cache_entry(h, self.hosts[h])
            p0 = _counter("mesh.sharded_level_passes")
            for axis in ("row", "col"):
                # the first dispatch of each orientation, and the host
                # core's first touch: paid before anything is counted
                self.core.sample(h, 0, 0, axis=axis)
                self.core_h.sample(h, 0, 0, axis=axis)
            # both orientations' level passes ran split over the devices
            assert _counter("mesh.sharded_level_passes") - p0 == 2


@pytest.fixture(scope="module")
def chain(shard_small):
    c = _Chain(shard_small)
    yield c
    c.app.close()


class _Held:
    """The gather program as a test double: the real program, except that
    the first call after `hold()` blocks until `release()`, and a call
    counted by `fail(n)` raises instead of running."""

    def __init__(self, monkeypatch):
        self._lock = threading.Lock()
        self._holding = False
        self._failing = 0
        self.entered = threading.Event()
        self._go = threading.Event()
        self.callers: list[int] = []
        self._wrapped = {}
        real = proof_device.sample_gather_program

        def program_for(eds, k, col):
            program, placement = real(eds, k, col)
            if program not in self._wrapped:
                self._wrapped[program] = self._wrap(program)
            return self._wrapped[program], placement

        monkeypatch.setattr(proof_device, "sample_gather_program",
                            program_for)

    def _wrap(self, program):
        def held(eds, levels, index):
            with self._lock:
                hold, self._holding = self._holding, False
                fail = self._failing > 0
                self._failing -= fail
                self.callers.append(threading.get_ident())
            if hold:
                self.entered.set()
                assert self._go.wait(WAIT_S)
            if fail:
                raise RuntimeError("the device went away")
            return program(eds, levels, index)

        return held

    def hold(self) -> None:
        self._holding = True
        self.entered.clear()
        self._go.clear()

    def release(self) -> None:
        self._go.set()

    def fail(self, calls: int) -> None:
        self._failing = calls


def _cells(rng, n: int = 16):
    return [(int(r), int(c)) for r, c in rng.integers(0, WIDTH, size=(n, 2))]


def _await(predicate, what: str) -> None:
    deadline = time.monotonic() + WAIT_S
    while not predicate():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


def _start(target, *args) -> threading.Thread:
    t = threading.Thread(target=target, args=args, daemon=True)
    t.start()
    return t


def _join(threads) -> None:
    for t in threads:
        t.join(WAIT_S)
        assert not t.is_alive()


def _proofs(got):
    return [(s, p.start, p.end, p.total, p.nodes) for s, p in got]


@pytest.mark.parametrize("axis", ["row", "col"])
def test_requests_queued_behind_a_dispatch_go_out_in_one(chain, monkeypatch,
                                                         axis):
    """One request's dispatch is held in flight; N more requests for the
    same height and axis queue behind it and go out together in the
    next: two dispatches, N - 1 joined, and every reply the host
    engine's serial reply byte for byte."""
    held = _Held(monkeypatch)
    h, n = HEIGHTS[0], 12
    col = axis == "col"
    queue = chain.entries[h]._gathers[col]
    rng = np.random.default_rng([7, col])
    asks = [_cells(rng) for _ in range(n + 1)]
    chain.core.sample_many(h, asks[0], axis=axis)  # the warm, unheld
    d0, j0 = _counter("das.gather_dispatches"), _counter("das.gather_joined")
    g0 = _counter("das.samples_gathered")
    got = [None] * (n + 1)
    barrier = threading.Barrier(n)

    def ask(i: int):
        if i:
            barrier.wait(WAIT_S)
        got[i] = chain.core.sample_many(h, asks[i], axis=axis)

    held.hold()
    threads = [_start(ask, 0)]
    assert held.entered.wait(WAIT_S)
    threads += [_start(ask, i) for i in range(1, n + 1)]
    _await(lambda: queue.queued() == n, "the requests did not queue")
    held.release()
    _join(threads)
    assert _counter("das.gather_dispatches") - d0 == 2
    assert _counter("das.gather_joined") - j0 == n - 1
    assert _counter("das.samples_gathered") - g0 == 16 * (n + 1)
    for cells, reply in zip(asks, got):
        assert reply == chain.core_h.sample_many(h, cells, axis=axis)
    assert chain.entries[h].residency() == "device"
    assert queue.queued() == 0


def test_a_lone_request_dispatches_at_once_on_its_own_thread(chain,
                                                             monkeypatch):
    """Nothing in flight, nothing queued: the request is its own
    dispatcher — the program runs on the caller's thread with no wait
    before it, and nothing joins."""
    held = _Held(monkeypatch)
    h = HEIGHTS[1]
    rng = np.random.default_rng(11)
    chain.core.sample_many(h, _cells(rng))  # the warm
    d0, j0 = _counter("das.gather_dispatches"), _counter("das.gather_joined")
    n0 = _counter('obs.span_n{name="das.gather"}')
    w0 = _counter('obs.span_n{name="das.gather_wait"}')
    del held.callers[:]
    for _ in range(3):
        cells = _cells(rng)
        got = chain.entries[h].gather_cells(cells)
        assert _proofs(got) == _proofs(chain.hosts[h].prove_cells(cells))
    assert held.callers == [threading.get_ident()] * 3
    assert _counter("das.gather_dispatches") - d0 == 3
    assert _counter("das.gather_joined") == j0
    if _counter('obs.span_n{name="das.gather"}'):
        # span totals follow the CELESTIA_OBS gate
        assert _counter('obs.span_n{name="das.gather"}') - n0 == 3
        assert _counter('obs.span_n{name="das.gather_wait"}') - w0 == 3


def test_a_queue_longer_than_the_cap_splits(chain, monkeypatch):
    """Five requests of 16 cells queued behind a dispatch, under a cap of
    48 cells: three go out in the first following dispatch, two in the
    next — whole requests, in queue order. A request larger than the cap
    goes alone."""
    held = _Held(monkeypatch)
    h = HEIGHTS[0]
    entry = chain.entries[h]
    queue = entry._gathers[0]
    monkeypatch.setattr(queue, "cap", 48)
    rng = np.random.default_rng(13)
    asks = [_cells(rng) for _ in range(6)]
    entry.gather_cells(asks[0])  # the warm
    d0, j0 = _counter("das.gather_dispatches"), _counter("das.gather_joined")
    got = [None] * 6

    def ask(i: int):
        got[i] = entry.gather_cells(asks[i])

    held.hold()
    threads = [_start(ask, 0)]
    assert held.entered.wait(WAIT_S)
    for i in range(1, 6):
        threads.append(_start(ask, i))
        _await(lambda i=i: queue.queued() == i, "the requests did not queue")
    held.release()
    _join(threads)
    assert _counter("das.gather_dispatches") - d0 == 3
    assert _counter("das.gather_joined") - j0 == 2 + 1
    for cells, proofs in zip(asks, got):
        assert _proofs(proofs) == _proofs(chain.hosts[h].prove_cells(cells))
    big = _cells(rng, 50)
    d1 = _counter("das.gather_dispatches")
    assert _proofs(entry.gather_cells(big)) == \
        _proofs(chain.hosts[h].prove_cells(big))
    assert _counter("das.gather_dispatches") - d1 == 1


def test_heights_and_orientations_never_share_a_dispatch(chain, monkeypatch):
    """With height 3's row dispatch held in flight, requests for height
    3's columns and for height 4 are served meanwhile, each by a dispatch
    of its own queue; height 3's queued rows then go out in one."""
    held = _Held(monkeypatch)
    rng = np.random.default_rng(17)
    a, b = HEIGHTS
    chain.core.sample_many(a, _cells(rng))  # the warm
    asks = {(h, axis): [_cells(rng) for _ in range(4)]
            for h in HEIGHTS for axis in ("row", "col")}
    got = {key: [None] * 4 for key in asks}

    def ask(key, i):
        got[key][i] = chain.core.sample_many(key[0], asks[key][i],
                                             axis=key[1])

    held.hold()
    first = _start(ask, (a, "row"), 0)
    assert held.entered.wait(WAIT_S)
    rows = [_start(ask, (a, "row"), i) for i in range(1, 4)]
    queue = chain.entries[a]._gathers[0]
    _await(lambda: queue.queued() == 3, "the requests did not queue")
    d0 = _counter("das.gather_dispatches")
    others = [_start(ask, key, i) for key in asks if key != (a, "row")
              for i in range(4)]
    _join(others)
    # twelve requests of the other three queues, none of them stuck
    # behind the held dispatch: at least one dispatch a queue
    assert 3 <= _counter("das.gather_dispatches") - d0 <= 12
    assert queue.queued() == 3 and first.is_alive()
    d1, j1 = _counter("das.gather_dispatches"), _counter("das.gather_joined")
    held.release()
    _join([first] + rows)
    assert _counter("das.gather_dispatches") - d1 == 2
    assert _counter("das.gather_joined") - j1 == 2
    for (h, axis), replies in got.items():
        for cells, reply in zip(asks[(h, axis)], replies):
            assert reply == chain.core_h.sample_many(h, cells, axis=axis)


def test_a_raising_dispatch_reaches_every_request_it_carried(chain,
                                                             monkeypatch):
    """The held dispatch raises in its own request; the four queued
    behind it go out together, that dispatch raises too, in every one of
    them; the queue is left idle and the next request is served."""
    held = _Held(monkeypatch)
    h = HEIGHTS[1]
    entry = chain.entries[h]
    queue = entry._gathers[1]
    rng = np.random.default_rng(19)
    entry.gather_cells(_cells(rng), col=True)  # the warm
    errors = [None] * 5

    def ask(i: int):
        try:
            entry.gather_cells(_cells(np.random.default_rng(i)), col=True)
        except RuntimeError as e:
            errors[i] = e

    d0 = _counter("das.gather_dispatches")
    del held.callers[:]
    held.hold()
    held.fail(2)
    threads = [_start(ask, 0)]
    assert held.entered.wait(WAIT_S)
    threads += [_start(ask, i) for i in range(1, 5)]
    _await(lambda: queue.queued() == 4, "the requests did not queue")
    held.release()
    _join(threads)
    assert all(isinstance(e, RuntimeError) for e in errors)
    assert len(held.callers) == 2  # the held dispatch and the one after
    assert _counter("das.gather_dispatches") == d0  # none succeeded
    cells = _cells(rng)
    assert _proofs(entry.gather_cells(cells, col=True)) == \
        _proofs(chain.hosts[h].prove_cells(cells, col=True))
    assert _counter("das.gather_dispatches") - d0 == 1
    assert queue.queued() == 0


def test_no_bucket_compiles_after_the_warm(chain):
    """The first dispatch of an orientation ran every bucket once; a
    dispatch of any size up to the cap then compiles nothing (JAX's own
    compile and cache-load events, as the benchmark counts them)."""
    events = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    seen = []

    def listen(event: str, duration: float, **_kw) -> None:
        if event in events:
            seen.append(event)

    rng = np.random.default_rng(23)
    assert proof_device.gather_buckets()[0] == proof_device.MIN_GATHER_BUCKET
    assert proof_device.gather_buckets()[-1] == proof_device.MAX_GATHER_BUCKET
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for h in HEIGHTS:
            for col in (False, True):
                for bucket in proof_device.gather_buckets():
                    cells = _cells(rng, bucket // 2 + 1 if bucket > 16
                                   else 1)
                    assert proof_device.gather_bucket(len(cells)) == bucket
                    got = chain.entries[h].gather_cells(cells, col=col)
                    assert len(got) == len(cells)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert seen == []


# ---------------------------------------------------------------------------
# the queue alone, with a stand-in dispatch
# ---------------------------------------------------------------------------


def test_queue_under_contention_loses_no_request_and_mixes_no_rows():
    """64 threads x 40 requests through one queue, at a short switch
    interval: every request gets back exactly its own rows, every request
    is carried by exactly one dispatch, and no dispatch exceeds the
    cap."""
    queue = edscache.GatherQueue(cap=24)
    lock = threading.Lock()
    dispatches = []

    def dispatch(requests):
        rows = np.array([cell for r in requests for cell in r],
                        dtype=np.int64)
        with lock:
            dispatches.append([len(r) for r in requests])
        return rows[:, 0], rows[:, 1]

    wrong = []

    def worker(t: int):
        rnd = random.Random(t)
        for i in range(40):
            cells = [(t, i * 100 + j) for j in range(rnd.randint(1, 10))]
            shares, nodes = queue.submit(cells, dispatch)
            if list(zip(shares.tolist(), nodes.tolist())) != cells:
                wrong.append((t, i))

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [_start(worker, t) for t in range(64)]
        _join(threads)
    finally:
        sys.setswitchinterval(was)
    assert wrong == []
    assert sum(len(d) for d in dispatches) == 64 * 40
    assert all(sum(d) <= 24 or len(d) == 1 for d in dispatches)
    assert queue.queued() == 0


def test_queue_hands_the_role_on_after_a_raise():
    """A dispatch that raises gives its exception to each request it
    carried and hands the role on: the requests queued behind it are
    served by the next dispatcher, none is lost."""
    queue = edscache.GatherQueue(cap=1000)
    entered, go = threading.Event(), threading.Event()
    calls = []

    def dispatch(requests):
        calls.append(len(requests))
        if len(calls) == 1:
            entered.set()
            assert go.wait(WAIT_S)
            raise ValueError("first dispatch fails")
        rows = np.arange(sum(len(r) for r in requests))
        return rows, rows

    out = [None] * 4

    def ask(i: int):
        try:
            out[i] = queue.submit([(i, 0)] * (i + 1), dispatch)
        except ValueError as e:
            out[i] = e

    threads = [_start(ask, 0)]
    assert entered.wait(WAIT_S)
    threads += [_start(ask, i) for i in range(1, 4)]
    _await(lambda: queue.queued() == 3, "the requests did not queue")
    go.set()
    _join(threads)
    assert isinstance(out[0], ValueError)
    assert calls == [1, 3]
    assert [len(s) for s, _n in out[1:]] == [2, 3, 4]
    assert queue.queued() == 0
