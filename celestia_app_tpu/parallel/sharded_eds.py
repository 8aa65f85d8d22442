"""Multi-device EDS pipeline: row-sharded RS extension + NMT roots.

The single-chip pipeline (da/eds.py) maps the whole square onto one device.
This module shards it over a (data, seq) mesh (parallel/mesh.py):

- a batch of B squares is split over the ``data`` axis (block parallelism),
- the k rows of each square are split over the ``seq`` axis.

Dataflow per square, all inside one shard_map region (so XLA schedules the
collectives on ICI):

  1. row pass     — each device RS-extends its local rows (local matmul),
  2. all_to_all   — transpose from row-sharding to column-sharding,
  3. column pass  — extend full columns locally; this yields Q2 for original
                    columns and Q3 for parity columns at once (the product
                    code commutes: row-extending Q2 == column-extending Q1,
                    both are E·Q0·Eᵀ — data_structures.md:304-310 semantics),
  4. column NMT roots — each device hashes the column trees it owns,
  5. all_to_all   — transpose back to row-sharding,
  6. row NMT roots — each device hashes its row trees,
  7. data root    — every device all-gathers the 4k axis roots (90 bytes
                    each) and folds the tiny tree itself, still inside the
                    shard_map: the TPU compiler cannot partition a Pallas
                    (Mosaic) kernel that sits outside one.

Collectives used: 2 × all_to_all over ``seq`` (the expensive transposes ride
ICI), plus 2 × all_gather of 90-byte roots. Nothing crosses DCN.

Reference parity: same codewords and roots as rsmt2d + nmt
(pkg/da/data_availability_header.go:65-108) — asserted bit-identical against
the single-device pipeline in tests/test_sharded_eds.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from celestia_app_tpu import appconsts
from celestia_app_tpu.da import namespace as ns_mod
from celestia_app_tpu.ops import leopard, merkle, nmt, rs
from celestia_app_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS

NS = appconsts.NAMESPACE_SIZE
SHARE = appconsts.SHARE_SIZE


def _leaf_ns_local(
    sq_local: jax.Array, k: int, major_start: jax.Array
) -> jax.Array:
    """Leaf namespaces for locally-owned axis trees.

    ``sq_local`` is (B_l, M_l, 2k, SHARE): M_l major-axis entries (rows or
    columns) starting at global index ``major_start``, each a full tree of 2k
    leaves. A leaf keeps its share's own namespace prefix iff it lies in Q0 —
    global major index < k AND minor index < k — else it gets the parity
    namespace (pkg/wrapper/nmt_wrapper.go:93-114 semantics).
    """
    m_l = sq_local.shape[1]
    major = major_start + jnp.arange(m_l)
    minor = jnp.arange(2 * k)
    in_q0 = (major[:, None] < k) & (minor[None, :] < k)  # (M_l, 2k)
    parity = jnp.asarray(np.frombuffer(ns_mod.PARITY_NS_RAW, dtype=np.uint8))
    return jnp.where(in_q0[None, :, :, None], sq_local[..., :NS], parity)


def _leaf_nodes_local(
    sq_local: jax.Array, k: int, major_start: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(B_l, M_l, 2k, SHARE) local axis slabs -> leaf (min, max, v) arrays,
    each (B_l, M_l, 2k, .)."""
    b_l, m_l = sq_local.shape[0], sq_local.shape[1]
    leaf_ns = _leaf_ns_local(sq_local, k, major_start)
    mins, maxs, vs = nmt.leaf_nodes(
        leaf_ns.reshape(b_l * m_l, 2 * k, NS),
        sq_local.reshape(b_l * m_l, 2 * k, SHARE),
    )
    return (
        mins.reshape(b_l, m_l, 2 * k, NS),
        maxs.reshape(b_l, m_l, 2 * k, NS),
        vs.reshape(b_l, m_l, 2 * k, 32),
    )


def _roots_from_leaves_local(
    mins: jax.Array, maxs: jax.Array, vs: jax.Array
) -> jax.Array:
    """(B_l, M_l, 2k, .) leaf nodes -> (B_l, M_l, 90) NMT roots."""
    b_l, m_l, two_k = vs.shape[0], vs.shape[1], vs.shape[2]
    roots = nmt.roots_from_leaf_nodes(
        mins.reshape(b_l * m_l, two_k, NS),
        maxs.reshape(b_l * m_l, two_k, NS),
        vs.reshape(b_l * m_l, two_k, 32),
    )
    return roots.reshape(b_l, m_l, 90)


def _local_pipeline(k: int, n_seq: int):
    """The per-device program run under shard_map."""
    mat, to_bits, from_bits = rs._codec(k)  # field by k
    bit_mat = jnp.asarray(mat)

    def run(ods_local: jax.Array):
        # ods_local: (B_l, k/n, k, SHARE) — this device's slab of original rows.
        seq_idx = lax.axis_index(SEQ_AXIS)

        # 1. Row pass: extend local rows. Mixing is over the share index
        #    within each row, which is fully local.
        row_bits = to_bits(ods_local)
        q1_local = from_bits(rs._gf_mix(bit_mat, row_bits))
        top_local = jnp.concatenate([ods_local, q1_local], axis=2)
        # (B_l, k/n, 2k, S)

        # 2. Transpose to column-sharding: split the 2k columns across the
        #    mesh, gather all k original rows. One all-to-all over ICI.
        cols_local = lax.all_to_all(
            top_local, SEQ_AXIS, split_axis=2, concat_axis=1, tiled=True
        )  # (B_l, k, 2k/n, S): all original rows × this device's columns
        col_major = jnp.swapaxes(cols_local, 1, 2)  # (B_l, 2k/n, k, S)

        # 3. Column pass: extend each owned column over its k data symbols.
        #    Original columns yield Q2; parity columns yield Q3 (== E·Q0·Eᵀ).
        par_major = from_bits(
            rs._gf_mix(bit_mat, to_bits(col_major))
        )  # (B_l, 2k/n, k, S)
        eds_cols = jnp.concatenate([col_major, par_major], axis=2)
        # (B_l, 2k/n, 2k, S): full columns, column-major

        # 4. Column-tree leaf nodes + roots for owned columns. Leaf (r, c)
        #    has the identical preimage (0x00 || ns || share) in row tree r
        #    and column tree c (da/eds.pipeline_fn does the same dedup on
        #    one chip), so hash each leaf ONCE here and ship the 32-byte
        #    digests to the row owners — 1/16 the bytes of re-hashing the
        #    512-byte shares and none of the 9-block SHA work.
        col_start = seq_idx * (2 * k // n_seq)
        col_mins, col_maxs, col_vs = _leaf_nodes_local(eds_cols, k, col_start)
        col_roots_local = _roots_from_leaves_local(col_mins, col_maxs, col_vs)

        # 5. Transpose back to row-sharding for the row trees: split the 2k
        #    rows (axis 2) across devices, gather all columns on axis 1 —
        #    shares for the EDS output, digests for the row-tree leaves.
        #    Shares and digests agree on the leading (B_l, 2k/n cols, 2k
        #    rows) geometry, so they ride ONE all-to-all packed along the
        #    byte axis (one collective instead of two; also dodges an XLA
        #    CPU all-to-all combiner bug that mis-matches the two
        #    operands' layouts at small seq extents).
        packed = jnp.concatenate([eds_cols, col_vs], axis=3)
        packed_back = lax.all_to_all(
            packed, SEQ_AXIS, split_axis=2, concat_axis=1, tiled=True
        )  # (B_l, 2k cols in global order, 2k/n owned rows, S+32)
        rows_back = packed_back[..., :SHARE]
        vs_back = packed_back[..., SHARE:]
        eds_rows = jnp.swapaxes(rows_back, 1, 2)  # (B_l, 2k/n, 2k, S)
        row_vs = jnp.swapaxes(vs_back, 1, 2)  # (B_l, 2k/n, 2k, 32)

        # 6. Row NMT roots for owned rows: namespaces recomputed locally
        #    from the row slab (cheap), digests reused from step 4.
        row_start = seq_idx * (2 * k // n_seq)
        row_ns = _leaf_ns_local(eds_rows, k, row_start)
        row_roots_local = _roots_from_leaves_local(row_ns, row_ns, row_vs)

        # 7. Data root: gather the 4k axis roots over ``seq`` (90 bytes
        #    each) and fold them on every device. Inside the shard_map on
        #    purpose: at 4k >= 1024 leaves the hash is the Pallas kernel,
        #    and the TPU compiler refuses to partition a Mosaic kernel
        #    that sits outside one.
        axis_roots = jnp.concatenate([
            lax.all_gather(row_roots_local, SEQ_AXIS, axis=1, tiled=True),
            lax.all_gather(col_roots_local, SEQ_AXIS, axis=1, tiled=True),
        ], axis=1)  # (B_l, 4k, 90)
        data_roots = jax.vmap(merkle.merkle_root_pow2)(axis_roots)
        return eds_rows, row_roots_local, col_roots_local, data_roots

    return run


def sharded_pipeline_fn(mesh: Mesh, k: int):
    """Build the mesh-sharded block pipeline.

    Returns a jittable fn: (B, k, k, SHARE) u8 batch of original squares ->
    (eds (B, 2k, 2k, SHARE), row_roots (B, 2k, 90), col_roots (B, 2k, 90),
    data_roots (B, 32)), with B sharded over ``data`` and square rows over
    ``seq``.
    """
    n_seq = mesh.shape[SEQ_AXIS]
    if k % n_seq != 0:
        raise ValueError(f"seq axis {n_seq} must divide square size {k}")

    # check_vma=False: the SHA-256 fori_loop carries mix replicated init
    # state (H0) with device-varying data; skip VMA inference rather than
    # thread pvary through every op (outputs are all explicitly sharded).
    return jax.shard_map(
        _local_pipeline(k, n_seq),
        mesh=mesh,
        in_specs=P(DATA_AXIS, SEQ_AXIS, None, None),
        out_specs=(
            P(DATA_AXIS, SEQ_AXIS, None, None),
            P(DATA_AXIS, SEQ_AXIS, None),
            P(DATA_AXIS, SEQ_AXIS, None),
            P(DATA_AXIS, None),  # data roots: replicated over ``seq``
        ),
        check_vma=False,
    )


def input_sharding(mesh: Mesh) -> NamedSharding:
    """THE pipeline input placement — (B over ``data``, rows over
    ``seq``). Exposed so dispatchers (parallel/mesh_engine._run_sharded)
    can upload the batch EXPLICITLY through the transfer ledger instead
    of letting jit move it silently; using the jitted program's own
    in_sharding makes the explicit put a no-op at dispatch time."""
    return NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS, None, None))


@functools.lru_cache(maxsize=None)
def _jitted(mesh: Mesh, k: int):
    fn = sharded_pipeline_fn(mesh, k)

    # named for the trace: jit_mesh_pipeline(...), apart from the one-chip
    # pipeline's and the namespace search's jit_run
    def mesh_pipeline(ods_batch: jax.Array):
        return fn(ods_batch)

    return jax.jit(mesh_pipeline, in_shardings=input_sharding(mesh))


def jitted_sharded_pipeline(mesh: Mesh, k: int):
    """Compiled sharded pipeline, cached per (mesh, k)."""
    return _jitted(mesh, k)
