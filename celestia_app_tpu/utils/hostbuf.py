"""Large zeroed host arrays, leased from a small held pool and rewritten.

The block path of a k = 256 square asks for exactly 32 MiB of zeroed memory
three times a block (`da/square._export` from Prepare and from Process,
`da/commitment_device._pack`). glibc serves no request of 32 MiB + its
header from a grown heap (`M_MMAP_THRESHOLD` tops out at 32 MiB): unless a
free touched chunk that large happens to lie in the heap it is a fresh
mapping of 8,192 cold pages, and the first touch of each is the cost. On the
chip's host (gVisor; PERF.md §6, PR 38, probes 1–3): a fresh
`np.zeros((65536, 512), uint8)` written once 33.2–36.8 ms every time, 31 MiB
2.2 ms, rewriting a HELD 32 MiB array 1.1–2.2 ms. So from `HELD_FROM_BYTES`
on the allocator is not asked: `lease_zeroed` hands out a view of a base
array this module holds, zeroed by a fill.

**The reuse rule.** No call site releases anything. A held base is handed
out again only when nothing but this module refers to it: every numpy view
(and view of a view, `reshape`, slice), every buffer export and a zero-copy
`jnp.asarray` keeps a reference to the base it reads, so the base's
reference count says whether a holder of its last lease is alive. A
`Square` somebody still holds is therefore never rewritten; a forgotten
holder costs a fresh mapping, never a corrupted square. If every held base
of the size is in use the lease allocates a fresh one and keeps it, up to
`MAX_HELD`; beyond that the fresh array is handed out and not retained. An
idle base of another size makes room for the size now asked for (a square
size that governance moved). Below `HELD_FROM_BYTES` the heap already
recycles touched pages (8 MiB fresh: 0.47–0.60 ms) and a pool would only
hold memory over many shapes: those requests are `np.zeros`, counted
nowhere.

The pool is the process's, like the allocator it stands in for: module
state under one lock, whoever the caller (the proposer's thread, a reader's
`query.rebuild_square`).

Counters (requests at or over the constant only): `hostbuf.leases` =
`hostbuf.reuses` + `hostbuf.fresh`; gauge `hostbuf.held_bytes` at scrape
time.
"""

from __future__ import annotations

import sys
import threading

import numpy as np

from celestia_app_tpu.utils import telemetry

# the allocator's measured cliff (31 MiB: 2.2 ms, 32 MiB: 33–37 ms), which is
# glibc's largest M_MMAP_THRESHOLD
HELD_FROM_BYTES = 32 << 20
# what one block keeps alive at once: the pack buffer, Prepare's square,
# Process's square, one spare
MAX_HELD = 4

telemetry.set_help(
    "hostbuf.leases",
    "zeroed host arrays of 32 MiB or more asked of the held pool")
telemetry.set_help(
    "hostbuf.reuses",
    "leases served by rewriting a held array no holder referred to")
telemetry.set_help(
    "hostbuf.fresh",
    "leases that allocated (no held array of the size was free)")
telemetry.set_help(
    "hostbuf.held_bytes", "bytes of the arrays the pool holds")

_lock = threading.Lock()
_held: list[np.ndarray] = []  # guarded-by: _lock


def _refs(bases: list[np.ndarray], i: int) -> int:
    return sys.getrefcount(bases[i])


# what `_refs` reads of an array only its list refers to
_IDLE_REFS = _refs([np.empty(0, dtype=np.uint8)], 0)


def lease_zeroed(rows: int, width: int = 512) -> np.ndarray:
    """A zeroed C-order (rows, width) uint8 array. The caller drops it like
    any other array; it must not hand out the memory by raw address beyond
    its own references."""
    nbytes = rows * width
    if nbytes < HELD_FROM_BYTES:
        return np.zeros((rows, width), dtype=np.uint8)
    view = None
    with _lock:
        other_size = None
        for i in range(len(_held)):
            if _refs(_held, i) != _IDLE_REFS:
                continue  # a view of its last lease is alive
            if _held[i].size == nbytes:
                # the view is made under the lock: it is the reference that
                # keeps the base from a second thread
                view = _held[i].reshape(rows, width)
                break
            other_size = i
        if view is None:
            fresh = np.zeros(nbytes, dtype=np.uint8)
            if other_size is not None:
                _held[other_size] = fresh
            elif len(_held) < MAX_HELD:
                _held.append(fresh)
    telemetry.incr("hostbuf.leases")
    if view is None:
        telemetry.incr("hostbuf.fresh")
        return fresh.reshape(rows, width)
    telemetry.incr("hostbuf.reuses")
    view.fill(0)
    return view


def _held_collector() -> None:
    with _lock:
        held = sum(base.size for base in _held)
    telemetry.gauge("hostbuf.held_bytes", held)


telemetry.register_collector(_held_collector)
