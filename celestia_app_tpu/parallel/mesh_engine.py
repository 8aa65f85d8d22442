"""The mesh plane: the sharding rule, and the sharded EDS dispatch.

- **One sharding rule.** ``mesh_active_for(k)`` decides whether a size-k
  square is split over the chips: k at or above ``MESH_MIN_K`` (256, the
  SURVEY §2.4 streaming target) and at least two chips whose ``seq``
  extent divides k (``mesh_for``). ``da/edscache.compute_entry`` asks it
  for every device-engine square, and ``maybe_shard_batch`` for the
  repair and prover batches; nothing else chooses. The sharded program
  is pinned bit-identical to the single-chip one (tests/test_sharded_eds.py,
  tests/test_mesh_plane.py), so the rule moves work, never bytes.

- **Device-resident entries.** ``compute_entry_mesh`` runs
  parallel/sharded_eds.py's row-sharded extend (all-to-all column
  transposes over a (data, seq) mesh) and returns a
  ``da/edscache.DeviceEntry`` whose EDS stays split over the mesh: only
  the axis roots and the data root — the commitment every protocol phase
  compares — come to the host.

- **Batch sharding for the repair/prover ops.** ``maybe_shard_batch``
  splits the batch dimension of the pow2-bucketed batch runners
  (ops/rs._RepairAxesRunner, ops/nmt.eds_axis_roots) over the flat device
  list when the rule holds for the square size; jit partitions by input
  sharding, so their programs are unchanged and their outputs identical.

Design in docs/DESIGN.md "The mesh plane"; counters in docs/FORMATS.md §18.
"""

from __future__ import annotations

import functools

import numpy as np

from celestia_app_tpu import obs
from celestia_app_tpu.obs import xfer
from celestia_app_tpu.utils import telemetry

# the least square size split over the chips: k=256, the reference's
# streaming target (SURVEY §2.4). Below it one chip extends a square.
MESH_MIN_K = 256


def _usable_device_count() -> int:
    """Devices the mesh plane may claim: jax's view. 0 when no backend
    is usable (counted — the caller's single-chip path takes over)."""
    try:
        import jax

        return len(jax.devices())
    except Exception:
        telemetry.incr("mesh.unavailable")
        return 0


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
    square always lands on a data=1 mesh."""
    n = _usable_device_count()
    # largest power-of-two device count <= n that divides k: the seq
    # axis then takes ALL of them (data=1)
    seq = 1
    while seq * 2 <= n and k % (seq * 2) == 0:
        seq *= 2
    if seq < 2:
        return None
    return _mesh_cached(k, seq)


def mesh_active_for(k: int) -> bool:
    """The sharding rule: True iff size-k squares (and their
    repair/prover batches) are split over the chips."""
    return k >= MESH_MIN_K and mesh_for(k) is not None


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
        raise RuntimeError("a sharded square needs >= 2 devices")
    from celestia_app_tpu.da import edscache as edscache_mod

    eds_dev, rows, cols, roots = _run_sharded(mesh, ods[None], k)
    rows, cols, root = xfer.to_host(
        (rows[0], cols[0], roots[0]), "mesh.entry_commitments"
    )
    return edscache_mod.DeviceEntry.from_commitments(
        eds_dev[0], rows, cols, root)
