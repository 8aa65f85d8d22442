"""The host↔device transfer ledger: every boundary crossing, counted.

ROADMAP item 2 (zero-copy blob path) is blocked on one number that no
counter in the tree produces: how many bytes cross the host↔device
boundary per committed block. `edscache.host_crossings` counts one
narrow path (lazy host materialization of a device-resident entry);
the dispatch uploads, commitment fetches, and ops runner round-trips
are all invisible. This module closes that hole the
way arXiv:2108.02692 profiles erasure-coding kernels — measure the
memory traffic first, then optimize:

- **Counted helpers.** `to_device(value, site)` / `to_host(value,
  site)` wrap `jax.device_put` / `jax.device_get` and attribute bytes,
  call count, and latency to the CALL-SITE label: labeled counters
  ``xfer.h2d_bytes{site=…}`` / ``xfer.d2h_bytes{site=…}`` (+ the
  ``_calls`` twins) and latency histograms ``xfer.h2d``/``xfer.d2h``
  land in the telemetry registry, so /metrics exposes the full
  per-site traffic matrix. Every `device_put`/`device_get` in the
  tree (edscache, mesh_engine, ops runners) routes through
  them.
- **Ledger rows.** When span recording is on (CELESTIA_OBS) and a span
  is active, each transfer also writes one row to the ``xfer`` trace
  table of the span's sink, stamped with the span's trace id — so a
  block's transfers merge into its per-height waterfall
  (tools/timeline.py) exactly like its spans do.
- **A pinnable residency claim.** `no_implicit_transfers()` makes any
  boundary crossing the helpers did NOT mediate an error. On
  accelerator backends ``jax.transfer_guard("disallow")`` does this in
  XLA. On the CPU backend a committed array is host memory behind the
  C buffer protocol, so ``np.asarray`` reads it zero-copy and no guard
  can fire; to keep residency claims testable under JAX_PLATFORMS=cpu
  the context ALSO swaps ``numpy.asarray`` for a probe that rejects
  jax.Array arguments on the claiming thread unless the call comes
  from a ledger helper. Tier-1 pins the warmed produce path with it.
- **The per-block gauge.** The cumulative totals (`totals()`,
  `bytes_crossed()`) let chain/app.py compute a per-commit delta —
  gauge ``xfer.host_bytes_crossed_per_block`` — which is PR 20's
  baseline and acceptance gate.

- **Transfers are spans too.** Under the same gate every transfer
  feeds the span totals (obs/spans.py `record_total`) and holds a
  profiler annotation for its lifetime under the name
  ``xfer.h2d:<site>`` / ``xfer.d2h:<site>`` — so the benchmark prices
  the upload and the download of one site beside the spans around
  them, and a profiler trace shows them on the device's clock. They
  time through `spans.begin` / `end`, so the host's account of the
  calling thread (system time, page faults, context switches: on a
  kernel that keeps one) rides along under the same names.

- **Copies started ahead of their need.** `HostFetch(value, site)`
  begins a device→host copy on a thread of its own; whoever needs the
  bytes calls `result()`. The bytes are counted once, when they land;
  the site's d2h span times what the reader waited, and the copy's own
  length as its thread saw it is the `xfer.fetch:<site>` span (da/edscache.py: the extended square follows
  the roots down in the shadow of process → commit).

Counting is always-on (two dict writes under the registry lock — the
same cost class as `edscache.host_crossings`); the ledger ROWS, the
span totals and the annotation follow the CELESTIA_OBS gate (its cost
on the chip's host: PERF.md §6, PR 26).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

import numpy as np

from celestia_app_tpu.obs import spans
from celestia_app_tpu.utils import telemetry

XFER_TABLE = "xfer"


class ImplicitTransferError(RuntimeError):
    """An uncounted host↔device crossing inside `no_implicit_transfers()`:
    a device value was materialized to host memory by a path the ledger
    cannot see (stray ``np.asarray`` instead of `to_host`)."""


_tls = threading.local()

_totals_lock = threading.Lock()
# cumulative process-wide boundary traffic — the source of the
# per-block delta gauge (chain/app.py reads totals() at each commit)
_totals = {
    "h2d_bytes": 0, "d2h_bytes": 0,
    "h2d_calls": 0, "d2h_calls": 0,
}

telemetry.set_help(
    "xfer.h2d_bytes", "host->device bytes through the transfer ledger"
)
telemetry.set_help(
    "xfer.d2h_bytes", "device->host bytes through the transfer ledger"
)
telemetry.set_help(
    "xfer.host_bytes_crossed_per_block",
    "host<->device bytes crossed while committing the last block",
)


def nbytes_of(value) -> int:
    """Byte size of an array, buffer, or (possibly nested) container of
    them — the unit the ledger counts. Unknown leaves count 0 rather
    than raising: the ledger must never take down a transfer."""
    if value is None:
        return 0
    if isinstance(value, (bytes, bytearray, memoryview)):
        return len(value)
    if isinstance(value, (list, tuple)):
        return sum(nbytes_of(v) for v in value)
    if isinstance(value, dict):
        return sum(nbytes_of(v) for v in value.values())
    n = getattr(value, "nbytes", None)
    if n is not None:
        return int(n)
    if isinstance(value, (bool, int, float)):
        return 8  # python scalar -> one device word
    return 0


def totals() -> dict:
    """Snapshot of the cumulative process-wide transfer totals."""
    with _totals_lock:
        return dict(_totals)


def bytes_crossed() -> int:
    """Cumulative h2d+d2h bytes — the monotone the per-block delta
    gauge is computed from."""
    with _totals_lock:
        return _totals["h2d_bytes"] + _totals["d2h_bytes"]


@contextmanager
def _explicit():
    """Mark this thread as inside a ledger helper, so the
    `no_implicit_transfers()` probe lets the mediated numpy read pass."""
    d = getattr(_tls, "explicit", 0)
    _tls.explicit = d + 1
    try:
        yield
    finally:
        _tls.explicit = d


def _open(direction: str, site: str):
    """Start one transfer's clocks: the histogram's t0 and, while span
    recording is on, the transfer as a span (`spans.begin`)."""
    name = f"xfer.{direction}:{site}"
    return (telemetry.start_timer(), name,
            spans.begin(name) if spans.enabled() else None)


def _abandon(opened) -> None:
    """A transfer that raised moved nothing the ledger can name: let go
    of its annotation (an open TraceMe would stay on this thread for
    the rest of a profiler session) and count nothing."""
    as_span = opened[2]
    if as_span is not None:
        spans.close(as_span)


def _count(direction: str, site: str, nbytes: int) -> None:
    """The counters' half of one transfer: its bytes and its call, to
    `site` and to the process-wide totals."""
    telemetry.incr(f"xfer.{direction}_bytes", nbytes, labels={"site": site})
    telemetry.incr(f"xfer.{direction}_calls", labels={"site": site})
    with _totals_lock:
        _totals[f"{direction}_bytes"] += nbytes
        _totals[f"{direction}_calls"] += 1


def _note(direction: str, site: str, nbytes: int, opened) -> None:
    """The clocks' half: close the transfer's span, sample the latency
    histogram and (when a span is active) write one ledger row in the
    span's trace sink."""
    t0, name, as_span = opened
    if as_span is not None:
        spans.end(name, as_span)
    dur_s = telemetry.measure_since(
        f"xfer.{direction}", t0, labels={"site": site}
    )
    ctx = spans.capture() if as_span is not None else None
    if ctx is None:
        return
    tid, sid, sink = ctx
    try:
        sink.write(
            XFER_TABLE,
            trace_id=tid,
            parent_id=sid,
            site=site,
            dir=direction,
            bytes=int(nbytes),
            # wall-clock start so timeline can order the row among the
            # spans of its height; display only, never hashed
            start_unix=round(time.time() - dur_s, 6),  # lint: disable=det-wallclock
            dur_ms=round(dur_s * 1e3, 3),
        )
    except Exception:
        # must never take down the transfer it measures — but count it:
        # a ledger that silently drops rows looks "quiet", not correct
        telemetry.incr("obs.xfer_row_errors")


def to_device(value, site: str, *, placement=None):
    """`jax.device_put` with the boundary accounted to `site`.
    `placement` passes a Device or Sharding through unchanged (the mesh
    plane's sharded uploads)."""
    import jax

    opened = _open("h2d", site)
    try:
        with _explicit():
            if placement is not None:
                out = jax.device_put(value, placement)  # xfer: ledger
            else:
                out = jax.device_put(value)  # xfer: ledger
    except BaseException:
        _abandon(opened)
        raise
    nbytes = nbytes_of(value)
    _count("h2d", site, nbytes)
    _note("h2d", site, nbytes, opened)
    return out


def to_host(value, site: str):
    """`jax.device_get` (blocks until the value is ready) with the
    boundary accounted to `site`. Accepts pytrees; returns numpy."""
    import jax

    opened = _open("d2h", site)
    try:
        with _explicit():
            out = jax.device_get(value)  # xfer: ledger
    except BaseException:
        _abandon(opened)
        raise
    nbytes = nbytes_of(out)
    _count("d2h", site, nbytes)
    _note("d2h", site, nbytes, opened)
    return out


class HostFetch:
    """A device→host copy that was started and is waited for only at its
    first need: the copy runs on a thread of its own —
    ``jax.device_get`` gives the GIL away for as long as the bytes move —
    while the thread that started it goes on with host work.

    The ledger sees both halves. The bytes (and the call) are counted
    once, when they land, wherever the starter is by then; `result()`
    is the site's ``xfer.d2h:<site>`` span and latency sample, and
    times what blocked its caller — about nothing once the copy has
    landed — never the copy's own length. That length is the thread's
    own span, ``xfer.fetch:<site>``: start → landed as this thread saw
    it, so the wait behind other copies and the wait to get the
    interpreter back are in it; its total over a window divided by the
    window is the number of copies in flight."""

    def __init__(self, value, site: str):
        self.site = site
        self._done = threading.Event()
        self._out = None
        self._error: Exception | None = None
        threading.Thread(target=self._run, args=(value,), daemon=True,
                         name="xfer-fetch").start()

    def _run(self, value) -> None:
        import jax

        name = f"xfer.fetch:{self.site}"
        as_span = spans.begin(name) if spans.enabled() else None
        try:
            with _explicit():
                self._out = jax.device_get(value)  # xfer: ledger
        except Exception as e:
            # handed to result(), which raises it; counted here, because
            # a copy nobody reads would otherwise fail in silence
            telemetry.incr("obs.xfer_fetch_errors")
            self._error = e
            if as_span is not None:
                spans.close(as_span)
        else:
            if as_span is not None:
                spans.end(name, as_span)
            _count("d2h", self.site, nbytes_of(self._out))
        finally:
            self._done.set()

    def ready(self) -> bool:
        """True once the copy has landed (or failed): `result()` will
        not block."""
        return self._done.is_set()

    def result(self):
        """The host value; blocks until the copy has landed."""
        opened = _open("d2h", self.site)
        self._done.wait()
        if self._error is not None:
            _abandon(opened)
            raise self._error
        _note("d2h", self.site, nbytes_of(self._out), opened)
        return self._out


def ensure_host(value, site: str):
    """Materialize-if-device: a device value comes back through the
    counted d2h path (`to_host`, attributed to `site`); anything
    already host passes through ``np.asarray`` unchanged and counts
    NOTHING — the helper for boundary-normalization call sites whose
    inputs are only sometimes device-resident (a fake ledger row for a
    zero-copy host read would be worse than none)."""
    try:
        import jax
    except ImportError:
        jax = None
    if jax is not None and isinstance(value, jax.Array):
        return to_host(value, site)
    with _explicit():
        return np.asarray(value)  # xfer: ledger


# -- the residency pin -------------------------------------------------------

_probe_lock = threading.Lock()
_probe_refs = 0           # guarded-by: _probe_lock
_orig_asarray = None      # guarded-by: _probe_lock


def _probe_asarray(a, *args, **kwargs):
    """`numpy.asarray` stand-in while a `no_implicit_transfers()` region
    is active anywhere in the process: on threads inside such a region,
    a jax.Array argument outside a ledger helper is an uncounted
    boundary crossing. All other calls delegate unchanged."""
    if (
        getattr(_tls, "guard", 0) > 0
        and getattr(_tls, "explicit", 0) == 0
    ):
        import jax

        if isinstance(a, jax.Array):
            raise ImplicitTransferError(
                "np.asarray on a device value inside "
                "no_implicit_transfers() — route it through "
                "obs.xfer.to_host(value, site) so the ledger counts it"
            )
    return _orig_asarray(a, *args, **kwargs)


@contextmanager
def no_implicit_transfers():
    """Pin a device-residency claim: any host↔device crossing the ledger
    helpers did not mediate raises inside this context.

    Two mechanisms, because the backends differ: on accelerators,
    ``jax.transfer_guard("disallow")`` makes XLA reject implicit
    transfers while explicit `device_put`/`device_get` (and therefore
    `to_device`/`to_host`) stay legal. On the CPU backend a committed
    array is host memory behind the C buffer protocol — numpy reads it
    zero-copy, so no XLA guard can fire; the context additionally swaps
    ``numpy.asarray`` for a probe that rejects jax.Array arguments on
    the claiming thread (other threads are untouched: the probe checks
    a thread-local flag before doing anything). Without jax installed
    the context is a no-op."""
    try:
        import jax
    except Exception:
        # no jax (or a backend that refuses to init): there IS no
        # device boundary to guard — count the vacuous pin so a tier-1
        # run on a jaxless box shows the claim was not exercised
        telemetry.incr("obs.xfer_guard_noop")
        yield
        return
    global _probe_refs, _orig_asarray
    with _probe_lock:
        if _probe_refs == 0:
            _orig_asarray = np.asarray
            np.asarray = _probe_asarray
        _probe_refs += 1
    _tls.guard = getattr(_tls, "guard", 0) + 1
    try:
        with jax.transfer_guard("disallow"):
            yield
    finally:
        _tls.guard -= 1
        with _probe_lock:
            _probe_refs -= 1
            if _probe_refs == 0:
                np.asarray = _orig_asarray
                _orig_asarray = None
