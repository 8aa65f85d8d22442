"""Spans on the benchmark's own clock, and the profiler around the window.

Spans are taken from the benchmark's files, around the calls into each layer
(spans inside the program are a later `tracing` PR's). In a traced run each
span is also written into the profiler's trace as `bench.<name>`
(`jax.profiler.TraceAnnotation`), so idle gaps on the device's line can be
laid against what the host was doing on one clock.
"""

from __future__ import annotations

import collections
import contextlib
import glob
import os
import shutil
import time


class Spans:
    def __init__(self, annotate: bool = False):
        self.seconds: dict[str, list[float]] = collections.defaultdict(list)
        self._annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self._annotate:
            import jax

            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield
        self.seconds[name].append(time.perf_counter() - t0)

    def record(self, name: str, seconds: float) -> None:
        """A span that is no single call (it runs across others): kept for
        the metrics, not written into the trace."""
        self.seconds[name].append(seconds)


class CompileCounter:
    """Backend compilations and persistent-cache loads, counted from JAX's
    own monitoring events: a warmed window shows none of either."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.count += 1


class Profile:
    """The JAX profiler around the window, at a fixed place in the checkout
    (emptied first: a trace is tens of MB and only the newest is read)."""

    def __init__(self, out_dir: str):
        self.dir = out_dir

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> str:
        """Stops tracing; returns the .xplane.pb written."""
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if len(found) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {self.dir}, "
                               f"found {found}")
        return found[0]

    def discard(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
