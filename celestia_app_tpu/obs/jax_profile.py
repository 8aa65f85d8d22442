"""JAX/TPU profiling hooks: the compile-vs-execute split + device gauges.

On a TPU stack the single most important attribution is *XLA compilation
time vs execution time* — a 30 s jit compile hiding inside a "slow
prepare_proposal" is a completely different problem from a slow kernel.
The hooks here make that split a first-class metric without ever forcing
a backend to initialize:

- `note_compile(name, key)` — called from inside the lru-cached jitted
  factories (da/eds.py) so it fires EXACTLY once per cache miss: counts
  ``jax.compilations`` (and per-(fn, k) under ``by_fn``); the live
  jit-cache-size gauge reads the registered factories' cache_info() at
  scrape time (`register_cache`), staying honest across cache_clear().
- `instrument(name, fn)` — wraps the jitted callable; the first call
  (which pays tracing + XLA compilation) lands in the ``jax.compile``
  histogram, every later call in ``jax.dispatch`` (enqueue latency on
  an asynchronous backend, not execution), each labeled with the
  program name. The wrapper proxies attribute access, so
  ``jitted_pipeline.cache_clear()`` / ``.lower()`` keep working.
- `collect_gauges()` — a telemetry collector run at scrape time that
  exports device count, bytes-in-use, and live-buffer gauges. It reads
  ``sys.modules`` and only touches backends that ALREADY initialized:
  a host-engine validator process (which must never initialise an
  accelerator backend it does not own, see service/server.py) serves
  /metrics without waking a backend.
- `capture_profile(out_dir, seconds)` — the /debug/profile endpoint's
  worker: an on-demand ``jax.profiler`` trace capture to a directory
  (open with TensorBoard / xprof). Refuses when jax is not already
  loaded in the process, for the same reason.
"""

from __future__ import annotations

import os
import sys
import threading
import time

from celestia_app_tpu.utils import telemetry


class ProfileError(ValueError):
    """Client-side profiling problem (jax absent, capture in flight,
    bad duration): transports answer 4xx."""


_lock = threading.Lock()
_capturing = False
# lru-cached jitted factories registered for live cache-size accounting
# (reading cache_info() at scrape time stays honest across cache_clear())
_factories: list = []

MAX_CAPTURE_SECONDS = 30.0


def note_compile(name: str, key) -> None:
    """One jitted-factory cache miss == one program compilation coming.
    Call from INSIDE the lru-cached factory body (it only runs on miss);
    `key` is the cache key (the square-size bucket), labeled so compile
    storms attribute to the bucket that caused them."""
    telemetry.incr("jax.compilations")
    telemetry.incr("jax.compilations.by_fn",
                   labels={"fn": name, "k": str(key)})


def register_cache(factory) -> None:
    """Register an lru-cached jitted factory; the scrape-time collector
    sums live cache_info().currsize into the jit-cache-size gauge."""
    with _lock:
        if factory not in _factories:
            _factories.append(factory)


class _Instrumented:
    """Transparent wrapper over a jitted callable: first call -> the
    ``jax.compile`` histogram (tracing + XLA compile + first run), later
    calls -> ``jax.dispatch``. Attribute access proxies to the wrapped
    function so AOT/lowering APIs stay reachable."""

    __slots__ = ("_name", "_fn", "_compiled", "_flag_lock")

    def __init__(self, name: str, fn):
        self._name = name
        self._fn = fn
        self._compiled = False
        self._flag_lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        if self._compiled:
            # steady state measures DISPATCH, deliberately: blocking here
            # would serialize a caller that overlaps host work with
            # device compute. On async backends this is enqueue latency,
            # and named so; the block path's execution time is the span
            # da.extend.run (da/edscache.compute_entry), device-side time
            # comes from /debug/profile (FORMATS §10.2).
            telemetry.measure_since("jax.dispatch", t0,
                                    labels={"fn": self._name})
        else:
            # exactly ONE call may claim the compile observation — two
            # threads racing the first call (reactor + HTTP handler)
            # must not both pollute the compile histogram
            with self._flag_lock:
                first = not self._compiled
                self._compiled = True
            if first:
                # the compile number must include the real first run, not
                # just its dispatch: block before stopping the clock
                # (one-time cost; compile dominates it anyway)
                try:
                    import jax

                    jax.block_until_ready(out)
                except Exception:
                    telemetry.incr("jax.profile_probe_errors")
            telemetry.measure_since(
                "jax.compile" if first else "jax.dispatch", t0,
                labels={"fn": self._name},
            )
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


def instrument(name: str, fn):
    return _Instrumented(name, fn)


# -- device gauges (scrape-time collector) ----------------------------------


def collect_gauges() -> None:
    """Export device gauges IF a jax backend already initialized in this
    process; otherwise do nothing (never triggers backend init — the
    /metrics route on a host-engine process must stay hang-free)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return
    try:
        from jax._src import xla_bridge

        backends = getattr(xla_bridge, "_backends", None)
        if not backends:
            return
        devices = jax.devices()
    except Exception:
        telemetry.incr("jax.profile_probe_errors")
        return
    telemetry.gauge("jax.device_count", len(devices))
    with _lock:
        factories = list(_factories)
    try:
        telemetry.gauge("jax.jit_cache_size", float(sum(
            f.cache_info().currsize for f in factories
        )))
    except Exception:
        telemetry.incr("jax.profile_probe_errors")
    try:
        telemetry.gauge("jax.live_buffers", float(len(jax.live_arrays())))
    except Exception:
        telemetry.incr("jax.profile_probe_errors")
    in_use = peak = 0.0
    seen = False
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            telemetry.incr("jax.profile_probe_errors")
            stats = None
        if not stats:
            continue
        seen = True
        in_use += float(stats.get("bytes_in_use", 0))
        peak += float(stats.get("peak_bytes_in_use", 0))
    if seen:
        telemetry.gauge("jax.device_memory_bytes_in_use", in_use)
        telemetry.gauge("jax.device_memory_peak_bytes", peak)


telemetry.register_collector(collect_gauges)


# -- on-demand profiler capture (/debug/profile) ----------------------------


def capture_profile(out_dir: str | None = None,
                    seconds: float = 0.5) -> dict:
    """Capture a jax.profiler trace for `seconds` into `out_dir` (a fresh
    temp dir when None). Synchronous: the handler thread sleeps through
    the window while OTHER threads' dispatches land in the trace.
    One capture at a time; refuses when jax was never imported here."""
    global _capturing
    if "jax" not in sys.modules:
        raise ProfileError(
            "jax is not loaded in this process (host-engine services "
            "never import it; point /debug/profile at a device-engine "
            "process)"
        )
    try:
        seconds = float(seconds)
    except (TypeError, ValueError):
        raise ProfileError("seconds must be a number") from None
    if not 0.0 < seconds <= MAX_CAPTURE_SECONDS:
        raise ProfileError(
            f"seconds must be in (0, {MAX_CAPTURE_SECONDS:g}]"
        )
    with _lock:
        if _capturing:
            raise ProfileError("a profile capture is already running")
        _capturing = True
    t0 = time.perf_counter()
    # EVERYTHING between the flag set and the finally maps to
    # ProfileError (a 4xx, never a 5xx) and releases the flag — an
    # unwritable out_dir must not wedge the endpoint forever
    try:
        import jax

        if out_dir is None:
            import tempfile

            out_dir = tempfile.mkdtemp(prefix="celestia-jax-profile-")
        else:
            os.makedirs(out_dir, exist_ok=True)
        jax.profiler.start_trace(out_dir)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
    except ProfileError:
        raise
    except Exception as e:
        raise ProfileError(
            f"profiler capture failed: {type(e).__name__}: {e}"
        ) from None
    finally:
        with _lock:
            _capturing = False
    telemetry.incr("jax.profile_captures")
    return {
        "dir": out_dir,
        "seconds": seconds,
        "wall_ms": round((time.perf_counter() - t0) * 1e3, 1),
    }
