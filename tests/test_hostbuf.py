"""The held pool of large zeroed host arrays (utils/hostbuf.py): a lease is
zeros, a base comes back only when no holder of its last lease is alive, the
bound holds, small requests are plain `np.zeros`. The size constant is
patched down: no test allocates 32 MiB."""

import gc
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from celestia_app_tpu.utils import hostbuf, telemetry

ROWS, WIDTH = 16, 512  # 8 KiB: at the patched constant


@pytest.fixture
def pool(monkeypatch):
    monkeypatch.setattr(hostbuf, "HELD_FROM_BYTES", ROWS * WIDTH)
    monkeypatch.setattr(hostbuf, "_held", [])
    return hostbuf


def _counts() -> dict:
    snap = telemetry.snapshot()
    return {
        "leases": snap["counters"].get("hostbuf.leases", 0),
        "reuses": snap["counters"].get("hostbuf.reuses", 0),
        "fresh": snap["counters"].get("hostbuf.fresh", 0),
        "held_bytes": snap["gauges"].get("hostbuf.held_bytes", 0),
    }


def _moved(before: dict) -> dict:
    after = _counts()
    return {n: after[n] - before[n] for n in ("leases", "reuses", "fresh")}


def test_a_lease_is_zeros_also_after_the_last_holder_wrote_it_full(pool):
    c0 = _counts()
    first = pool.lease_zeroed(ROWS, WIDTH)
    assert first.shape == (ROWS, WIDTH) and first.dtype == np.uint8
    assert first.flags.c_contiguous and not first.any()
    first[:] = 0xFF
    del first
    second = pool.lease_zeroed(ROWS, WIDTH)
    assert not second.any()
    assert _moved(c0) == {"leases": 2, "reuses": 1, "fresh": 1}


def test_the_same_base_comes_back_once_the_last_view_died(pool):
    first = pool.lease_zeroed(ROWS, WIDTH)
    assert np.shares_memory(first, pool._held[0])
    at = first.ctypes.data
    del first
    c0 = _counts()
    second = pool.lease_zeroed(ROWS, WIDTH)
    assert second.ctypes.data == at
    assert np.shares_memory(second, pool._held[0])
    assert _moved(c0) == {"leases": 1, "reuses": 1, "fresh": 0}
    assert len(pool._held) == 1


def _read_only(a: np.ndarray) -> np.ndarray:
    """What `square._export` does with its lease."""
    a.flags.writeable = False
    return a.reshape(4, 4, WIDTH)


HOLDERS = {
    "view": lambda a: a[3:5],
    "reshape": lambda a: a.reshape(4, 4, WIDTH),
    "view-of-view": lambda a: a.reshape(-1)[7:][::2],
    "read-only": _read_only,
    "memoryview": memoryview,
    "frombuffer": lambda a: np.frombuffer(memoryview(a), dtype=np.uint8),
}


@pytest.mark.parametrize("holder", HOLDERS)
def test_a_base_is_never_handed_out_while_a_holder_is_alive(pool, holder):
    first = pool.lease_zeroed(ROWS, WIDTH)
    first[:] = np.arange(ROWS, dtype=np.uint8)[:, None] + 1
    kept = HOLDERS[holder](first)
    want = np.array(kept).tobytes()
    del first
    c0 = _counts()
    second = pool.lease_zeroed(ROWS, WIDTH)
    second[:] = 0xEE
    assert _moved(c0) == {"leases": 1, "reuses": 0, "fresh": 1}
    assert not np.shares_memory(second, pool._held[0])
    assert np.array(kept).tobytes() == want  # the older array's bytes
    del kept, second
    # the holders gone, both bases serve the next leases
    third, fourth = (pool.lease_zeroed(ROWS, WIDTH) for _ in range(2))
    assert _moved(c0) == {"leases": 3, "reuses": 2, "fresh": 1}
    assert not third.any() and not fourth.any()


def _aligned_owner(nbytes: int, align: int = 64) -> np.ndarray:
    """An array that owns memory at a multiple of `align`: what the CPU
    backend needs to take a host array without a copy (a 32 MiB mapping's
    alignment is the allocator's, so the rule must hold for this case)."""
    rejects = []
    for _ in range(256):
        a = np.zeros(nbytes, dtype=np.uint8)
        if a.ctypes.data % align == 0:
            return a
        rejects.append(a)
    pytest.fail("the allocator gave no aligned array in 256 tries")


def test_a_zero_copy_jnp_asarray_keeps_its_base(pool):
    pool._held.append(_aligned_owner(ROWS * WIDTH))
    c0 = _counts()
    first = pool.lease_zeroed(ROWS, WIDTH)
    first[:] = np.arange(ROWS, dtype=np.uint8)[:, None] + 1
    want = first.tobytes()
    kept = jnp.asarray(first)
    assert kept.unsafe_buffer_pointer() == first.ctypes.data  # no copy
    del first
    second = pool.lease_zeroed(ROWS, WIDTH)
    second[:] = 0xEE
    assert _moved(c0) == {"leases": 2, "reuses": 1, "fresh": 1}
    assert np.asarray(kept).tobytes() == want
    del kept, second
    # jaxlib lets go of a host array it was given at its next call or at the
    # next collection, not when the jax array dies
    gc.collect()
    third, fourth = (pool.lease_zeroed(ROWS, WIDTH) for _ in range(2))
    assert _moved(c0) == {"leases": 4, "reuses": 3, "fresh": 1}
    assert not third.any() and not fourth.any()


def test_the_bound_holds_and_the_gauge_says_what_is_held(pool):
    c0 = _counts()
    live = [pool.lease_zeroed(ROWS, WIDTH) for _ in range(pool.MAX_HELD + 2)]
    assert _moved(c0) == {"leases": pool.MAX_HELD + 2, "reuses": 0,
                          "fresh": pool.MAX_HELD + 2}
    assert len(pool._held) == pool.MAX_HELD
    assert _counts()["held_bytes"] == pool.MAX_HELD * ROWS * WIDTH
    # the two beyond the bound are the callers' own: writing them touches
    # nothing the pool holds
    for extra in live[pool.MAX_HELD:]:
        assert not any(np.shares_memory(extra, b) for b in pool._held)
    del live, extra
    again = [pool.lease_zeroed(ROWS, WIDTH) for _ in range(pool.MAX_HELD)]
    assert _moved(c0)["reuses"] == pool.MAX_HELD
    assert len({a.ctypes.data for a in again}) == pool.MAX_HELD


def test_an_idle_base_of_another_size_makes_room(pool):
    live = [pool.lease_zeroed(ROWS, WIDTH) for _ in range(pool.MAX_HELD)]
    del live
    c0 = _counts()
    big = pool.lease_zeroed(2 * ROWS, WIDTH)
    assert big.shape == (2 * ROWS, WIDTH) and not big.any()
    assert _moved(c0) == {"leases": 1, "reuses": 0, "fresh": 1}
    assert len(pool._held) == pool.MAX_HELD
    del big
    big = pool.lease_zeroed(2 * ROWS, WIDTH)
    assert _moved(c0) == {"leases": 2, "reuses": 1, "fresh": 1}
    assert _counts()["held_bytes"] == (pool.MAX_HELD + 1) * ROWS * WIDTH


@pytest.mark.parametrize("rows,width", [(ROWS - 1, WIDTH), (1, 512), (0, 512)])
def test_a_request_under_the_constant_is_plain_zeros_and_counts_nothing(
        pool, rows, width):
    c0 = _counts()
    a = pool.lease_zeroed(rows, width)
    assert a.shape == (rows, width) and a.dtype == np.uint8 and not a.any()
    assert a.base is None and a.flags.owndata and a.flags.writeable
    assert _moved(c0) == {"leases": 0, "reuses": 0, "fresh": 0}
    assert pool._held == []


def test_the_constant_is_the_allocators_cliff():
    assert hostbuf.HELD_FROM_BYTES == 32 << 20 == 65536 * 512
    assert hostbuf.MAX_HELD == 4


def test_two_threads_leasing_at_once_never_get_one_base(pool):
    rounds, n_threads = 150, 8
    barrier = threading.Barrier(n_threads)
    clashes = []

    def worker(tag: int):
        barrier.wait(timeout=30)
        for _ in range(rounds):
            a = pool.lease_zeroed(ROWS, WIDTH)
            if a.any():
                clashes.append(("not zero", tag))
            a[:] = tag
            time.sleep(0)  # let another thread in between write and check
            if (a != tag).any():
                clashes.append(("rewritten", tag))

    threads = [threading.Thread(target=worker, args=(t + 1,))
               for t in range(n_threads)]
    c0 = _counts()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert clashes == []
    moved = _moved(c0)
    assert moved["leases"] == rounds * n_threads
    assert moved["reuses"] + moved["fresh"] == moved["leases"]
    assert moved["reuses"] > 0
    assert len(pool._held) == pool.MAX_HELD
