"""Process-pressure observables: the GIL sampler + the peak-RSS gauge.

ROADMAP item 5 (escaping the GIL for the HTTP serving planes) has so
far rested on an inference — pack-vs-live serving ratios — rather than
a measured contention number. This module produces that number the way
scheduler-latency probes do: an **oversleep-drift sampler**. A daemon
thread asks for a fixed short sleep (`INTERVAL_S`); under CPython, a
thread waking from `sleep()` must reacquire the GIL before it runs
again, so the drift between requested and actual sleep is a direct
sample of how long runnable threads in THIS process wait for the
interpreter (plus OS scheduler noise, which is the same for every
service and cancels in comparisons). Each HTTP service starts one
sampler under its own label, and `chain/node.Node` starts one labelled
``"node"`` in a process that runs none yet — so the in-process node of
the benchmark and the tests has one, and a service process whose
service started first gets no second thread. Each wake publishes:

- histogram ``gil.oversleep{service=…}`` — per-wake drift seconds
  (p50/p99 in /metrics via the registry's bucket ladder);
- gauge ``gil.pressure{service=…}`` — EWMA of drift/interval (0 ≈
  idle interpreter; 1.0 means wakes are delayed by a full interval);
- counters ``gil.samples`` and ``gil.oversleep_us``, WITHOUT the
  label: a process has one interpreter, and a window's delta of the
  second over the first is the mean time a thread that wanted the
  interpreter in that window waited for it (two samplers in one
  process both add, and the ratio is still that mean). A histogram and
  a gauge cannot be read as a window's delta; these can (the
  ``window`` line of every benchmark run carries both deltas). It is
  a process-wide mean, not one span's wait: a span's own
  ``obs.span_vcsw``, where the kernel counts, says how often it let go.

Sampling is ``CELESTIA_OBS``-gated (the spans gate — `start` is a
no-op when observability is off) and costs one mostly-sleeping thread
per label: ~20 wakes/s of a few µs each (the interval sits well
above CPython's 5 ms switch interval on purpose — a probe at the
switch interval competes for the GIL instead of observing it).

The peak-RSS collector rides along because it is the same kind of
process-level pressure number: PR 18 tracked ``peak_rss_bytes`` only
inside scenario verdicts; registering a scrape-time collector here
makes it a proper /metrics gauge (``process.peak_rss_bytes``) for
fleetmon and external scrapers. The collector registers at import —
importing the obs package is enough, no sampler needed.

So does the **full-collection hook**. CPython's oldest-generation
collections stop the validator for tens of milliseconds at a time and
fall wherever allocation crosses the threshold — inside a block, a
light round, a read — and nothing else in the tree sees them. One
``gc.callbacks`` entry (returns at once for the young generations)
records each full collection as a span named ``gc.full`` in the span
totals (obs/spans.py: ``obs.span_n{name="gc.full"}`` counts them,
``obs.span_wall_us`` sums their pauses, and the rest of a span's
account — system time, faults, switches — rides along where the kernel
keeps one) and holds the ``gc.full`` profiler annotation for as long.
Same ``CELESTIA_OBS`` gate; no row
per collection, and no lock: a collection starts inside any
allocation, under any lock of the thread it interrupts.
"""

from __future__ import annotations

import gc
import sys
import threading
import time

from celestia_app_tpu.obs import spans
from celestia_app_tpu.utils import telemetry

# 50 ms: an order of magnitude above CPython's 5 ms switch interval, so
# the probe samples GIL pressure instead of synchronizing with the
# switcher and creating it (a 5 ms probe costs ~10% wall on a busy
# interpreter; 50 ms is noise-level and still ~20 samples/s).
INTERVAL_S = 0.05

_lock = threading.Lock()
_samplers: dict = {}  # service -> _Sampler  # guarded-by: _lock

telemetry.set_help(
    "gil.oversleep",
    "sampler oversleep drift (GIL+scheduler wait) per wake (seconds)",
)
telemetry.set_help(
    "gil.pressure",
    "EWMA of oversleep drift / requested interval (0=idle interpreter)",
)
telemetry.set_help(
    "gil.samples", "wakes of this process's oversleep samplers"
)
telemetry.set_help(
    "gil.oversleep_us",
    "sum of the samplers' oversleep drift (microseconds); over "
    "gil.samples = mean wait for the interpreter",
)
telemetry.set_help(
    "process.peak_rss_bytes", "peak resident set size of this process"
)


def peak_rss_bytes() -> int:
    """Peak resident set of this process in bytes (Linux ru_maxrss is
    KiB, macOS bytes; 0 where getrusage is unavailable)."""
    try:
        import resource
    except ImportError:  # non-POSIX
        return 0
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return int(peak) * (1 if sys.platform == "darwin" else 1024)


def _rss_collector() -> None:
    telemetry.gauge("process.peak_rss_bytes", peak_rss_bytes())


telemetry.register_collector(_rss_collector)


# -- full collections as spans ----------------------------------------------

GC_SPAN = spans.GC_SPAN
_OLDEST_GENERATION = 2
# what spans.begin returned for the collection in flight; a collection
# starts and stops on one thread and the collector never runs two at a
# time, so there is never more than one
_gc_open = None


def _on_gc(phase: str, info: dict) -> None:
    # Runs on whichever thread the collector interrupted, possibly one
    # that holds spans' totals lock or the registry's: takes NO lock
    # (`spans.record_gc`), or that thread would wait for itself.
    global _gc_open
    if info["generation"] != _OLDEST_GENERATION:
        return
    if phase == "start":
        if spans.enabled():
            _gc_open = spans.begin(GC_SPAN)
    elif _gc_open is not None:
        opened, _gc_open = _gc_open, None
        spans.record_gc(spans.close(opened))


gc.callbacks.append(_on_gc)


class _Sampler(threading.Thread):
    """One oversleep probe: sleep INTERVAL_S in a loop, record the
    drift. Daemon — it must never hold a process open."""

    def __init__(self, service: str):
        super().__init__(name=f"gil-sampler-{service}", daemon=True)
        self.service = service
        self._stop = threading.Event()
        self._ewma = 0.0

    def run(self) -> None:
        labels = {"service": self.service}
        while True:
            t0 = time.perf_counter()  # lint: disable=det-wallclock — the probe IS a clock measurement; feeds telemetry only
            if self._stop.wait(INTERVAL_S):
                return
            drift = (time.perf_counter() - t0) - INTERVAL_S  # lint: disable=det-wallclock — probe measurement, telemetry only
            drift = max(drift, 0.0)
            telemetry.observe("gil.oversleep", drift, labels=labels)
            # the window's reading: unlabelled, one key a process
            telemetry.incr("gil.samples")
            telemetry.incr("gil.oversleep_us", round(drift * 1e6))
            self._ewma = 0.9 * self._ewma + 0.1 * (drift / INTERVAL_S)
            telemetry.gauge("gil.pressure", round(self._ewma, 6),
                            labels=labels)

    def stop(self) -> None:
        self._stop.set()


def start(service: str) -> bool:
    """Start the sampler for `service` (idempotent per label). No-op —
    returns False — when observability is gated off (CELESTIA_OBS),
    same gate as span recording."""
    if not spans.enabled():
        return False
    with _lock:
        s = _samplers.get(service)
        if s is not None and s.is_alive():
            return False
        s = _Sampler(service)
        _samplers[service] = s
        s.start()
        return True


def stop_all() -> None:
    """Stop every sampler (tests, bench teardown). Threads exit at
    their next wake (≤ INTERVAL_S)."""
    with _lock:
        samplers = list(_samplers.values())
        _samplers.clear()
    for s in samplers:
        s.stop()


def running() -> list[str]:
    with _lock:
        return sorted(
            name for name, s in _samplers.items() if s.is_alive()
        )
