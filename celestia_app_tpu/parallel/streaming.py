"""Streaming PrepareProposal: overlap host layout with device extend+commit.

BASELINE.md config 4/5: at 2 blocks/s the proposer must not serialize
[host: square layout] → [device: RS extend + NMT roots] per block. JAX
dispatch is asynchronous — a jitted call returns device futures immediately
— so a one-deep software pipeline overlaps the device's work on block N with
the host's layout of block N+1 (the reference has no equivalent: rsmt2d
encodes synchronously on the Go heap; SURVEY §2.4 "pipeline parallelism").

`stream_blocks` is the engine; `bench_stream` measures blocks/s plus the
serial (unoverlapped) cost so the overlap win is visible in the output.
"""

from __future__ import annotations

import time

import numpy as np

from celestia_app_tpu.da import eds as eds_mod
from celestia_app_tpu.obs import xfer
from celestia_app_tpu.utils import telemetry


def _fetch_pending(pending):
    """Resolve one in-flight dispatch: ONE ``jax.device_get`` on the
    whole output tuple (a single transfer sync instead of per-leaf
    ``np.asarray`` round-trips), wall-clock routed through the telemetry
    timer so the overlap win is observable in /metrics, not just
    benchable. A fetch that arrives before the device finished counts
    ``streaming.overlap_stalls`` — the host outran the device, so the
    pipeline is device-bound there."""
    ready = getattr(pending[3], "is_ready", None)
    if ready is not None:
        try:
            if not ready():
                telemetry.incr("streaming.overlap_stalls")
        except Exception:
            # a backend may not implement readiness probes; the stall
            # counter just degrades to "unknown" there
            telemetry.incr("streaming.readiness_unsupported")
    t0 = telemetry.start_timer()
    out = xfer.to_host(pending, "streaming.fetch")
    telemetry.measure_since("streaming.fetch", t0)
    return out


def stream_blocks(layout_fn, n_blocks: int, k: int, *, pipeline=None):
    """Run `n_blocks` through the device pipeline with one-deep overlap.

    ``layout_fn(i) -> (k, k, 512) uint8 ODS`` is the HOST work (square
    layout); the device computes block i while the host lays out block i+1.
    Returns the list of 32-byte data roots, in order.
    ``streaming.blocks_in_flight`` gauges the pipeline depth (1 while a
    dispatch is outstanding); see `_fetch_pending` for the fetch-side
    counters."""
    if n_blocks <= 0:
        return []
    run = pipeline if pipeline is not None else eds_mod.jitted_pipeline(k)
    roots: list[bytes] = []
    pending = None
    for i in range(n_blocks):
        ods = layout_fn(i)  # host: lay out block i
        # device: async dispatch (upload counted by the transfer ledger)
        out = run(xfer.to_device(ods, "streaming.dispatch"))
        telemetry.gauge("streaming.blocks_in_flight", 1)
        if pending is not None:
            roots.append(bytes(_fetch_pending(pending)[3]))  # block on i-1
        pending = out
    roots.append(bytes(_fetch_pending(pending)[3]))
    telemetry.gauge("streaming.blocks_in_flight", 0)
    return roots


def _synthetic_layout(k: int, seed: int) -> np.ndarray:
    """Stand-in host layout: generate + namespace-stamp a k×k ODS. Costs
    real host time (RNG + memory traffic) like share packing does."""
    rng = np.random.default_rng(seed)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    ods[..., :29] = 0
    ods[..., 28] = 7
    return ods


def _stream_batches(layout_fn, n_batches: int, run) -> list[bytes]:
    """THE one-deep batch-overlap loop shared by the mesh and the
    single-chip batched modes: host lays out batch i+1 while the device
    works on batch i; returns the flat list of 32-byte data roots."""
    if n_batches <= 0:
        return []
    roots: list[bytes] = []
    pending = None
    for i in range(n_batches):
        batch = layout_fn(i)  # host: lay out batch i
        out = run(batch)  # device/mesh: async dispatch
        telemetry.gauge("streaming.blocks_in_flight", batch.shape[0])
        if pending is not None:
            roots.extend(bytes(r) for r in _fetch_pending(pending)[3])
        pending = out
    roots.extend(bytes(r) for r in _fetch_pending(pending)[3])
    telemetry.gauge("streaming.blocks_in_flight", 0)
    return roots


def stream_blocks_mesh(layout_fn, n_batches: int, mesh, k: int, *,
                       pipeline=None):
    """Mesh-sharded streaming (BASELINE cfg 5): each unit is a BATCH of
    data-axis-many squares through the sharded pipeline
    (parallel/sharded_eds.py) — rows split over ``seq``, blocks over
    ``data`` — with the host laying out batch i+1 while the mesh extends
    and commits batch i. Returns the flat list of 32-byte data roots."""
    from celestia_app_tpu.parallel import sharded_eds

    run = (pipeline if pipeline is not None
           else sharded_eds.jitted_sharded_pipeline(mesh, k))
    return _stream_batches(layout_fn, n_batches, run)


def bench_stream_mesh(k: int | None = None, n_batches: int = 3,
                      n_devices: int = 8) -> dict:
    """Streamed blocks/s on an n-device mesh (BASELINE cfg 5's shape:
    256×256 streaming on 8 devices). On the TPU backend this is the real
    target; on CPU the virtual mesh demonstrates the same program."""
    import jax

    from celestia_app_tpu.parallel import mesh as mesh_mod

    devices = jax.devices()
    n_devices = min(n_devices, len(devices))
    backend = devices[0].platform
    if k is None:
        k = 256 if backend == "tpu" else 32
    mesh = mesh_mod.make_mesh(n_devices, k=k, devices=devices[:n_devices])
    batch = mesh.shape[mesh_mod.DATA_AXIS]

    from celestia_app_tpu.parallel import sharded_eds

    run = sharded_eds.jitted_sharded_pipeline(mesh, k)

    def layout(i: int):
        return np.stack(
            [_synthetic_layout(k, i * batch + j) for j in range(batch)]
        )

    warm = layout(0)
    # a root fetch: the result is on the host when it returns
    xfer.to_host(run(warm)[3], "streaming.warm")
    t0 = time.perf_counter()
    roots = stream_blocks_mesh(layout, n_batches, mesh, k, pipeline=run)
    dt = time.perf_counter() - t0
    n_blocks = n_batches * batch
    assert len(roots) == n_blocks and len(roots[0]) == 32
    return {
        "metric": f"stream_mesh_blocks_per_sec_k{k}",
        "value": round(n_blocks / dt, 3),
        "unit": "blocks/s",
        "backend": backend,
        "devices": n_devices,
        "mesh": dict(mesh.shape),
        "blocks": n_blocks,
        "elapsed_s": round(dt, 2),
    }


def bench_stream_batched(k: int | None = None, batch: int = 4,
                         n_batches: int = 3) -> dict:
    """Single-chip BATCHED streaming: one dispatch per batch of B squares
    (da/eds.jitted_pipeline_batched) with host layout overlapped — the
    one-device throughput mode (amortized launches, fuller MXU) the
    sharded mesh generalizes across chips."""
    import jax

    backend = jax.devices()[0].platform
    if k is None:
        k = 128 if backend == "tpu" else 16
    jitted = eds_mod.jitted_pipeline_batched(k)

    def run(batch_arr):
        return jitted(xfer.to_device(batch_arr, "streaming.dispatch"))

    def layout(i: int):
        return np.stack(
            [_synthetic_layout(k, i * batch + j) for j in range(batch)]
        )

    # warm the compile out of the measurement (fetch: see bench.py)
    xfer.to_host(run(layout(0))[3], "streaming.warm")
    t0 = time.perf_counter()
    roots = _stream_batches(layout, n_batches, run)
    dt = time.perf_counter() - t0
    n_blocks = batch * n_batches
    assert len(roots) == n_blocks and len(roots[0]) == 32
    return {
        "metric": f"stream_batched_blocks_per_sec_k{k}",
        "value": round(n_blocks / dt, 3),
        "unit": "blocks/s",
        "backend": backend,
        "batch": batch,
        "blocks": n_blocks,
        "elapsed_s": round(dt, 2),
    }


def bench_stream(k: int | None = None, n_blocks: int = 6) -> dict:
    """Measure streamed blocks/s vs the serial cost. ONE JSON-able dict."""
    import jax

    backend = jax.devices()[0].platform
    if k is None:
        # k=256 is the BASELINE cfg-5 target on TPU; virtual/CPU runs
        # demonstrate the overlap at a size the host can turn around
        k = 256 if backend == "tpu" else 32

    run = eds_mod.jitted_pipeline(k)
    # warm the compile out of the measurement (a root fetch: the
    # result is on the host when it returns)
    warm = _synthetic_layout(k, 0)
    xfer.to_host(run(xfer.to_device(warm, "streaming.dispatch"))[3],
                 "streaming.warm")

    # serial attribution: host layout cost, device cost
    t0 = time.perf_counter()
    layouts = [_synthetic_layout(k, i) for i in range(n_blocks)]
    host_ms = (time.perf_counter() - t0) * 1000 / n_blocks
    t0 = time.perf_counter()
    for ods in layouts:
        xfer.to_host(run(xfer.to_device(ods, "streaming.dispatch"))[3],
                     "streaming.fetch")
    device_ms = (time.perf_counter() - t0) * 1000 / n_blocks

    # streamed: layout of block i+1 overlaps device work on block i
    t0 = time.perf_counter()
    roots = stream_blocks(
        lambda i: _synthetic_layout(k, i), n_blocks, k, pipeline=run
    )
    streamed_ms = (time.perf_counter() - t0) * 1000 / n_blocks
    assert len(roots) == n_blocks and len(roots[0]) == 32

    return {
        "metric": f"stream_blocks_per_sec_k{k}",
        "value": round(1000.0 / streamed_ms, 2),
        "unit": "blocks/s",
        "backend": backend,
        "host_layout_ms": round(host_ms, 1),
        "device_ms": round(device_ms, 1),
        "serial_ms": round(host_ms + device_ms, 1),
        "streamed_ms": round(streamed_ms, 1),
    }
