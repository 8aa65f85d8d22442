"""The mesh plane: sharded EDS production as a first-class engine.

`parallel/sharded_eds.py` proved the program — row-sharded RS extension
with all-to-all column transposes over a (data, seq) ICI mesh, pinned
bit-identical to the single-device pipeline — but only bench
harnesses ever called it. This module is the production dispatch:

- **Engine selection.** ``edscache.compute_entry(engine="mesh")`` routes
  through here explicitly; under ``engine="auto"``/``"device"`` any
  square of ``k >= mesh_min_k()`` (env ``CELESTIA_MESH_MIN_K``, default
  256 — the SURVEY §2.4 streaming target) takes the mesh automatically
  when two or more devices exist. Lowering the knob (e.g. to 128, or to
  8 in the tier-1 tests' forced-host-device mesh) moves the boundary
  without touching the byte contract: the sharded program is pinned
  bit-identical to the single-device pipeline at every shared size
  (tests/test_sharded_eds.py, tests/test_mesh_plane.py).

- **Device-resident entries.** ``compute_entry_mesh`` returns a
  ``da/edscache.DeviceEntry``: the EDS (and, once warmed, the NMT level
  arrays) stay on the mesh; only the 90-byte axis roots and the 32-byte
  data root come back to host at construction (they ARE the commitment
  every protocol phase compares). Host bytes materialize lazily, only
  when a proof/serve path actually needs them, and every materialization
  counts ``edscache.host_crossings`` — the counter the --mesh bench pins
  at 0 per block on the warmed produce path.

- **Multi-block batched dispatch.** ``compute_entries_batched`` extends
  B squares in ONE dispatch — over the mesh's ``data`` axis when a mesh
  is active for the size, else through the single-chip vmapped program
  (da/eds.jitted_pipeline_batched) — and returns one device-resident
  entry per block. ``chain/producer.py`` feeds it the produce loop's
  speculative block plans.

- **Batch sharding for the repair/prover ops.** ``maybe_shard_batch``
  lets the pow2-bucketed batch runners (ops/rs._RepairAxesRunner,
  ops/nmt.eds_axis_roots) split their batch dimension over the flat
  device list when the mesh plane is active for the square size — the
  fused decode matmuls and the vmapped NMT reductions then run sharded
  with zero change to their programs (jit partitions by input sharding),
  so outputs stay bit-identical by construction.

Design in docs/DESIGN.md "The mesh plane"; knobs and counters in
docs/FORMATS.md §18.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from celestia_app_tpu import obs
from celestia_app_tpu.obs import xfer
from celestia_app_tpu.utils import telemetry

# k=256 is the reference's streaming target (ROADMAP item 4 / SURVEY
# §2.4): squares at or above this size route through the mesh under
# auto/device engines. The env read is an engine-selection knob only —
# both engines are pinned bit-identical, so it can never change the
# bytes, only which silicon computes them (analyze.toml det-reach allow).
DEFAULT_MESH_MIN_K = 256


def mesh_min_k() -> int:
    """Smallest square size the auto/device engines hand to the mesh."""
    try:
        return max(1, int(os.environ.get("CELESTIA_MESH_MIN_K",
                                         str(DEFAULT_MESH_MIN_K))))
    except ValueError:
        return DEFAULT_MESH_MIN_K


def _max_devices() -> int:
    """Optional cap on how many devices the mesh plane claims
    (``CELESTIA_MESH_DEVICES``; 0/absent = all)."""
    try:
        return int(os.environ.get("CELESTIA_MESH_DEVICES", "0"))
    except ValueError:
        return 0


def _usable_device_count() -> int:
    """Devices the mesh plane may claim: jax's view, trimmed by the
    CELESTIA_MESH_DEVICES cap. 0 when no backend is usable (counted —
    the caller's engine fallback handles it)."""
    try:
        import jax

        n = len(jax.devices())
    except Exception:
        telemetry.incr("mesh.unavailable")
        return 0
    cap = _max_devices()
    if cap > 0:
        n = min(n, cap)
    return n


@functools.lru_cache(maxsize=None)
def _mesh_cached(k: int, n_devices: int):
    """(data, seq) mesh for square size k over n_devices (factoring via
    parallel/mesh.make_mesh: seq gets the largest pow2 divisor that still
    divides k). Cached per (k, n): jitted sharded programs key on the
    Mesh object, so it must be stable."""
    from celestia_app_tpu.parallel import mesh as mesh_mod

    return mesh_mod.make_mesh(n_devices, k=k)


def mesh_for(k: int):
    """The mesh the plane would run size-k squares on, or None when the
    square cannot shard across at least two devices (k=1, or a 1-device
    process — a 1-device "mesh" is just the single-chip pipeline with
    extra ceremony) or jax is unavailable. The device count is trimmed
    to the largest power of two whose ``seq`` extent divides k, so a
    single square always lands on a data=1 mesh (batch callers reuse
    the same mesh; its data axis stays 1 and batching rides vmap-style
    over the leading dim)."""
    n = _usable_device_count()
    # largest power-of-two device count <= n that divides k: the seq
    # axis then takes ALL of them (data=1), so any batch size shards
    seq = 1
    while seq * 2 <= n and k % (seq * 2) == 0:
        seq *= 2
    if seq < 2:
        return None
    return _mesh_cached(k, seq)


def mesh_for_batch(k: int, b: int):
    """Mesh for a B-block batched dispatch: the FULL device set when the
    batch divides its ``data`` extent (blocks split over ``data``, rows
    over ``seq`` — the two-axis shape the sharded pipeline was built
    for), else the seq-only single-square mesh."""
    n = _usable_device_count()
    # make_mesh keeps seq a pow2 divisor of k and puts the rest on data
    p = 1
    while p * 2 <= n:
        p *= 2
    if p >= 2:
        full = _mesh_cached(k, p)
        from celestia_app_tpu.parallel.mesh import DATA_AXIS

        if b % full.shape[DATA_AXIS] == 0:
            return full
    return mesh_for(k)


def mesh_active_for(k: int) -> bool:
    """True iff auto/device engines should route size-k squares (and
    their repair/prover batches) through the mesh."""
    return k >= mesh_min_k() and mesh_for(k) is not None


@functools.lru_cache(maxsize=None)
def _flat_mesh(n_devices: int):
    """1-D all-devices mesh for pure batch sharding (repair/root
    batches have no row dimension to split — only the batch)."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n_devices]), ("data",))


def maybe_shard_batch(batch: np.ndarray, k: int):
    """Shard a pow2-bucketed batch over the flat device list when the
    mesh plane is active for square size k and the batch divides evenly;
    otherwise return the input unchanged. The caller's jitted program is
    untouched — jit follows input shardings — so sharded and unsharded
    dispatches are bit-identical by construction."""
    if not mesh_active_for(k):
        return batch
    try:
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        n_dev = _usable_device_count()
        n = batch.shape[0]
        if n_dev < 2 or n < n_dev or n % n_dev != 0:
            return batch
        sharding = NamedSharding(
            _flat_mesh(n_dev), P("data", *([None] * (batch.ndim - 1)))
        )
        out = xfer.to_device(batch, "mesh.shard_batch",
                             placement=sharding)
        telemetry.incr("mesh.batch_shards")
        return out
    except Exception:
        # sharding is an optimization: any placement failure falls back
        # to the single-device dispatch, same bytes
        telemetry.incr("mesh.shard_fallbacks")
        return batch


# ---------------------------------------------------------------------------
# entry construction: the production ODS -> device-resident-entry dispatch
# ---------------------------------------------------------------------------


def _run_sharded(mesh, ods_batch: np.ndarray, k: int):
    """One sharded dispatch over a (B, k, k, 512) batch. Returns device
    (eds, row_roots, col_roots, data_roots) with the EDS left sharded.
    The upload is explicit (the pipeline's own input sharding) so the
    transfer ledger counts it instead of jit doing it silently."""
    import jax

    from celestia_app_tpu.parallel import sharded_eds

    run = sharded_eds.jitted_sharded_pipeline(mesh, k)
    ods_dev = xfer.to_device(
        ods_batch, "mesh.sharded_dispatch",
        placement=sharded_eds.input_sharding(mesh),
    )
    # the mesh's da.extend.run: dispatch -> all four outputs ready
    with obs.span("mesh.extend.run", k=k, blocks=int(ods_batch.shape[0]),
                  chips=mesh.size):
        return jax.block_until_ready(run(ods_dev))


def compute_entry_mesh(ods: np.ndarray):
    """ODS -> device-resident entry through the sharded pipeline. The
    EDS stays on-mesh; roots/data-root (the commitment) come to host
    here — they are needed by every protocol phase and are tiny (4k x
    90 B + 32 B), so they are not host "crossings" in the counter's
    sense. Raises when no mesh is available (callers gate or catch)."""
    k = int(ods.shape[0])
    mesh = mesh_for(k)
    if mesh is None:
        raise RuntimeError("mesh engine needs >= 2 devices")
    eds_dev, rows, cols, roots = _run_sharded(mesh, ods[None], k)
    return _device_entry(eds_dev[0], rows[0], cols[0], roots[0])


def compute_entries_batched(ods_batch: np.ndarray,
                            engine: str = "auto") -> list:
    """The multi-block batched dispatch: (B, k, k, 512) -> B
    device-resident entries from ONE device program launch — the mesh's
    sharded pipeline when active for k (B rides the ``data`` axis), the
    single-chip vmapped pipeline otherwise. Counts ``da.extend_runs``
    once per block (the per-(node, height) accounting every tier-1 pin
    asserts on) plus one ``mesh.batched_dispatches``."""
    b, k = int(ods_batch.shape[0]), int(ods_batch.shape[1])
    mesh = mesh_for_batch(k, b)
    use_mesh = mesh is not None and (engine == "mesh"
                                     or mesh_active_for(k))
    t0 = telemetry.start_timer()
    if use_mesh:
        eds_dev, rows, cols, roots = _run_sharded(mesh, ods_batch, k)
    else:
        from celestia_app_tpu.da import eds as eds_mod

        eds_dev, rows, cols, roots = eds_mod.jitted_pipeline_batched(k)(
            xfer.to_device(ods_batch, "mesh.batched_dispatch")
        )
    # ONE small host fetch for the whole batch's commitments (B x 4k
    # roots + B x 32 data roots); the EDS slabs stay on device
    rows_h, cols_h, roots_h = xfer.to_host(
        (rows, cols, roots), "mesh.batched_commitments"
    )
    telemetry.incr("da.extend_runs", b)
    telemetry.incr("mesh.batched_dispatches")
    telemetry.incr("mesh.batched_blocks", b)
    telemetry.measure_since("mesh.batched_dispatch", t0)
    return [
        _device_entry(eds_dev[i], rows_h[i], cols_h[i], roots_h[i],
                      fetched=True)
        for i in range(b)
    ]


def _device_entry(eds_dev, rows, cols, root, fetched: bool = False):
    from celestia_app_tpu.da import edscache as edscache_mod

    if not fetched:
        rows, cols, root = xfer.to_host(
            (rows, cols, root), "mesh.entry_commitments"
        )
    return edscache_mod.DeviceEntry.from_commitments(
        eds_dev, rows, cols, root)
