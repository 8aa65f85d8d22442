"""Batched share/tx proof generation from device-computed row trees.

The host path (da/proof.py) rebuilds one NMT per touched row with recursive
hashlib calls — fine per proof, hopeless for proof *services* (the reference
serves `custom/txInclusionProof` / `custom/shareInclusionProof` ABCI queries,
pkg/proof/querier.go:20-67, over pkg/proof/proof.go:79-202). Here the device
computes EVERY node of EVERY row tree in one jitted pass (ops/nmt.nmt_levels
— the same level-synchronous reduction that produces the DAH roots), the
level arrays come back to the host once (~12 MB for a 128x128 block), and
each proof is then pure index arithmetic: the range proof's nodes are the
maximal out-of-range subtree roots of a perfect binary tree, addressed as
(level, index) — no hashing per proof at all.

Proofs produced are byte-identical to da/proof.py's (cross-checked in
tests/test_proof_device.py) and verify with the same NmtRangeProof/RowProof
machinery.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from celestia_app_tpu import appconsts
from celestia_app_tpu.da import eds as eds_mod
from celestia_app_tpu.da.dah import DataAvailabilityHeader, ExtendedDataSquare
from celestia_app_tpu.da.proof import RowProof, ShareProof
from celestia_app_tpu.da.square import Square
from celestia_app_tpu.da import proof as proof_mod
from celestia_app_tpu.ops import nmt
from celestia_app_tpu.utils import merkle_host, nmt_host

NS = appconsts.NAMESPACE_SIZE


@functools.lru_cache(maxsize=None)
def _jitted_row_levels(k: int):
    """Compiled: (2k, 2k, 512) EDS -> per-level (mins, maxs, vs) node arrays."""

    # named for the trace: the profiler prints jit_prover_levels(...),
    # apart from the pipeline's and the namespace search's jit_run
    def prover_levels(eds: jax.Array):
        leaf_ns = eds_mod._axis_leaf_ns(eds, k)
        return nmt.nmt_levels(leaf_ns, eds)

    return jax.jit(prover_levels)


def rows_sharded_over(eds):
    """(mesh, axis name) when a device square's ROWS are split over one
    named axis of a mesh (what the sharded pipeline leaves resident:
    parallel/sharded_eds), else None — an array one chip holds, which a
    plain jit can read."""
    sharding = getattr(eds, "sharding", None)
    if not isinstance(sharding, NamedSharding) or not sharding.spec:
        return None
    axis = sharding.spec[0]
    if not isinstance(axis, str) or sharding.mesh.shape[axis] < 2 \
            or any(a is not None for a in sharding.spec[1:]):
        return None
    return sharding.mesh, axis


@functools.lru_cache(maxsize=None)
def _jitted_sharded_levels(mesh, axis: str, k: int, col: bool):
    """Compiled: the level pass of `_jitted_row_levels` over a (2k, 2k,
    512) EDS whose rows are split over `axis` of `mesh`, inside a
    shard_map on that mesh — the TPU compiler cannot partition the Pallas
    SHA-256 kernel that hashes the leaves of a plain jit ("Mosaic kernels
    cannot be automatically partitioned"). Each chip hashes the trees of
    the rows it holds; for the column orientation (`col`) one all-to-all
    over `axis` hands it whole columns first, which it reads column-major.
    Every level array stays split over `axis` by tree, in the global order
    a gather to the host restores: bit-identical to the one-chip pass."""
    n = mesh.shape[axis]
    if (2 * k) % n:
        raise ValueError(f"{n} chips do not divide {2 * k} axes")
    per_chip = 2 * k // n

    def levels_local(eds_local: jax.Array):
        # (2k/n, 2k, 512): this chip's rows of the square
        if col:
            mine = lax.all_to_all(eds_local, axis, split_axis=1,
                                  concat_axis=0, tiled=True)
            eds_local = jnp.swapaxes(mine, 0, 1)  # (2k/n cols, 2k, 512)
        first = lax.axis_index(axis) * per_chip
        leaf_ns = nmt.eds_axis_leaf_ns(
            eds_local, first + jnp.arange(per_chip), k)
        return nmt.nmt_levels(leaf_ns, eds_local)

    by_tree = P(axis, None, None)
    # check_vma=False as in parallel/sharded_eds: the SHA-256 loop mixes
    # a replicated initial state with device-varying data
    sharded = jax.shard_map(levels_local, mesh=mesh, in_specs=by_tree,
                            out_specs=by_tree, check_vma=False)

    # named for the trace: jit_mesh_prover_levels(...), apart from the
    # one-chip jit_prover_levels
    def mesh_prover_levels(eds: jax.Array):
        return sharded(eds)

    return jax.jit(mesh_prover_levels,
                   in_shardings=NamedSharding(mesh, by_tree))


# a gather's index vectors are padded to a power of two, and to no fewer
# than a light node's round (celestia-node samples 16 cells a header):
# one shape a height for every batch a sampler sends
MIN_GATHER_BUCKET = 16


def gather_bucket(n_cells: int) -> int:
    """Cells a gather of `n_cells` is padded to."""
    return max(MIN_GATHER_BUCKET, 1 << (n_cells - 1).bit_length())


def _cells_held(eds: jax.Array, levels, cells: jax.Array, col: bool, first):
    """Shares (n, 512) and sibling nodes (n, L, 90: min | max | hash) of
    `cells` ((2, n) int32: rows, cols) out of `eds.shape[0]` rows of the
    square and as many trees of the asked orientation's level stacks,
    both starting at global index `first`; zeros for a cell or a tree
    held elsewhere. Cell (r, c) is `eds[r, c]` whichever the axis; on the
    row axis its tree is r and its leaf c, on the column axis c and r;
    at level l its sibling on the path is node (leaf >> l) ^ 1."""
    rows, cols = cells[0], cells[1]
    tree, leaf = (cols, rows) if col else (rows, cols)
    held = eds.shape[0]

    def take(arr: jax.Array, major: jax.Array, minor: jax.Array):
        local = major - first
        mine = (local >= 0) & (local < held)
        picked = arr[jnp.where(mine, local, 0), minor]
        return jnp.where(mine[:, None], picked, 0)

    shares = take(eds, rows, cols)
    nodes = jnp.stack([
        jnp.concatenate([take(part, tree, (leaf >> level) ^ 1)
                         for part in triple], axis=-1)
        for level, triple in enumerate(levels)], axis=1)
    return shares, nodes


@functools.lru_cache(maxsize=None)
def _jitted_sample_gather(col: bool):
    """Compiled: a resident (2k, 2k, 512) EDS one chip holds, one
    orientation's level stack below the roots and (2, n) cells -> each
    cell's share and the log2(2k) sibling nodes of its proof."""

    # named for the trace: jit_sample_gather(...)
    def sample_gather(eds: jax.Array, levels, cells: jax.Array):
        return _cells_held(eds, levels, cells, col, 0)

    return jax.jit(sample_gather)


@functools.lru_cache(maxsize=None)
def _jitted_sharded_sample_gather(mesh, axis: str, k: int, col: bool):
    """Compiled: `_jitted_sample_gather` over a square whose rows, and
    level stacks whose trees, are split over `axis` of `mesh` (what
    `_jitted_sharded_levels` leaves resident), inside a shard_map on that
    mesh: each chip answers the cells and the trees it holds and
    contributes zeros for the rest, ONE all-reduce of the packed
    (n, 512 + L * 90) answer hands every chip the result. Neither the
    square nor a level stack is gathered anywhere."""
    per_chip = 2 * k // mesh.shape[axis]

    def gather_local(eds_local, levels_local, cells):
        first = lax.axis_index(axis) * per_chip
        shares, nodes = _cells_held(eds_local, levels_local, cells, col,
                                    first)
        n = shares.shape[0]
        packed = jnp.concatenate([shares, nodes.reshape(n, -1)], axis=1)
        # exactly one chip holds each byte: the sum is that byte
        packed = lax.psum(packed.astype(jnp.uint32), axis).astype(jnp.uint8)
        return (packed[:, :shares.shape[1]],
                packed[:, shares.shape[1]:].reshape(nodes.shape))

    by_tree = P(axis, None, None)
    sharded = jax.shard_map(gather_local, mesh=mesh,
                            in_specs=(by_tree, by_tree, P()),
                            out_specs=P(), check_vma=False)

    # named for the trace: jit_mesh_sample_gather(...)
    def mesh_sample_gather(eds: jax.Array, levels, cells: jax.Array):
        return sharded(eds, levels, cells)

    split = NamedSharding(mesh, by_tree)
    return jax.jit(mesh_sample_gather,
                   in_shardings=(split, split, NamedSharding(mesh, P())))


def sample_gather_program(eds, k: int, col: bool):
    """The gather over whatever holds `eds`, and where its (2, n) cells
    go: (program, placement of the index array — None for the one chip
    a plain jit reads)."""
    placed = rows_sharded_over(eds)
    if placed is None:
        return _jitted_sample_gather(col), None
    mesh, axis = placed
    return (_jitted_sharded_sample_gather(mesh, axis, k, col),
            NamedSharding(mesh, P()))


def single_leaf_path(total: int, leaf: int) -> list[int]:
    """Levels of a one-leaf range proof's nodes in proof order — what
    `BlockProver._range_proof(row, leaf, leaf + 1)` walks: the siblings
    left of the leaf top-down, then those right of it bottom-up. The node
    at level l is (l, (leaf >> l) ^ 1)."""
    depth = total.bit_length() - 1
    left = [lv for lv in reversed(range(depth)) if (leaf >> lv) & 1]
    right = [lv for lv in range(depth) if not (leaf >> lv) & 1]
    return left + right


def gathered_proofs(cells, col: bool, total: int, shares: np.ndarray,
                    nodes: np.ndarray):
    """[(share bytes, NmtRangeProof)] from a gather's host arrays, one a
    cell (the arrays' padding rows beyond `cells` are dropped): the
    bytes `BlockProver.prove_cell` gives for the same cell."""
    out = []
    for i, (row, col_) in enumerate(cells):
        leaf = row if col else col_
        out.append((
            shares[i].tobytes(),
            nmt_host.NmtRangeProof(
                start=leaf, end=leaf + 1, total=total,
                nodes=[nodes[i, lv].tobytes()
                       for lv in single_leaf_path(total, leaf)]),
        ))
    return out


class BlockProver:
    """Per-block proof factory: one device pass, then index-only proofs."""

    def __init__(self, eds: ExtendedDataSquare, dah: DataAvailabilityHeader,
                 levels=None):
        self.eds = eds
        self.dah = dah
        self.k = eds.width // 2
        from celestia_app_tpu import obs
        from celestia_app_tpu.obs import xfer

        if levels is None:
            # the EDS goes up again, the level pass runs, the levels
            # come down: three adjacent phases, each priced
            eds_dev = xfer.to_device(eds.squares, "proof.row_levels")
            with obs.span("proof.levels.run", k=self.k):
                levels_dev = jax.block_until_ready(
                    _jitted_row_levels(self.k)(eds_dev))
            levels = xfer.to_host(levels_dev, "proof.row_levels")
        # [(mins, maxs, vs)] with node counts 2k, k, ..., 1 per row tree;
        # `levels` may be precomputed on the host (utils/fast_host
        # nmt_levels_fast) by engines that must not touch jax — only a
        # device-resident level crosses the boundary, and it crosses
        # counted (obs.xfer.ensure_host)
        self.levels = [
            (xfer.ensure_host(m, "proof.levels"),
             xfer.ensure_host(x, "proof.levels"),
             xfer.ensure_host(v, "proof.levels"))
            for m, x, v in levels
        ]
        all_roots = list(dah.row_roots) + list(dah.col_roots)
        _, self._root_proofs = merkle_host.proofs_from_leaves(all_roots)

    def _node(self, row: int, level: int, idx: int) -> bytes:
        m, x, v = self.levels[level]
        return m[row, idx].tobytes() + x[row, idx].tobytes() + v[row, idx].tobytes()

    def _range_proof(self, row: int, p_start: int, p_end: int) -> nmt_host.NmtRangeProof:
        """Maximal out-of-range subtree roots of the perfect 2k-leaf tree."""
        total = 2 * self.k
        nodes: list[bytes] = []

        def walk(lo: int, hi: int) -> None:
            if hi <= p_start or lo >= p_end:
                width = hi - lo
                level = width.bit_length() - 1
                nodes.append(self._node(row, level, lo >> level))
                return
            if hi - lo == 1:
                return  # in-range leaf: verifier recomputes
            mid = lo + (hi - lo) // 2  # split_point of a power of two
            walk(lo, mid)
            walk(mid, hi)

        walk(0, total)
        return nmt_host.NmtRangeProof(
            start=p_start, end=p_end, total=total, nodes=nodes
        )

    def prove_cell(self, row: int, col: int) -> tuple[bytes, "nmt_host.NmtRangeProof"]:
        """One EXTENDED-square cell (any quadrant) with its NMT proof under
        the row root — the unit a DAS sampler requests (da/sampling.py).
        Pure index arithmetic over the cached row trees."""
        width = 2 * self.k
        if not (0 <= row < width and 0 <= col < width):
            raise ValueError(f"cell ({row}, {col}) outside the {width}x{width} square")
        return (
            self.eds.squares[row, col].tobytes(),
            self._range_proof(row, col, col + 1),
        )

    def prove_shares(
        self, start_share: int, end_share: int, namespace: bytes
    ) -> ShareProof:
        """ShareProof for ODS shares [start_share, end_share), row-major."""
        k = self.k
        if not (0 <= start_share < end_share <= k * k):
            raise ValueError(f"invalid share range [{start_share}, {end_share})")
        start_row, end_row = start_share // k, (end_share - 1) // k
        data: list[bytes] = []
        nmt_proofs: list[nmt_host.NmtRangeProof] = []
        for row in range(start_row, end_row + 1):
            col_start = start_share - row * k if row == start_row else 0
            col_end = end_share - row * k if row == end_row else k
            nmt_proofs.append(self._range_proof(row, col_start, col_end))
            data += [
                self.eds.squares[row, c].tobytes()
                for c in range(col_start, col_end)
            ]
        row_proof = RowProof(
            row_roots=[self.dah.row_roots[r] for r in range(start_row, end_row + 1)],
            proofs=[self._root_proofs[r] for r in range(start_row, end_row + 1)],
            start_row=start_row,
            end_row=end_row,
        )
        return ShareProof(
            data=data,
            share_proofs=nmt_proofs,
            namespace=namespace,
            row_proof=row_proof,
            start_share=start_share,
            end_share=end_share,
        )

    def commitment_from_eds(
        self, square: Square, pfb_index: int, blob_index: int,
        subtree_root_threshold: int,
    ) -> bytes:
        """Blob share commitment recomputed from the committed EDS's cached
        row trees — zero hashing beyond the final MMR fold.

        Reference: pkg/inclusion/get_commit.go:12-30 with the
        EDSSubTreeRootCacher — the non-interactive defaults guarantee each
        MMR chunk of the blob aligns to a subtree of its row NMT, so every
        subtree root is a node the device pass already computed."""
        from celestia_app_tpu.da import commitment as commitment_mod

        start, end = proof_mod.blob_share_range(square, pfb_index, blob_index)
        n_shares = end - start
        width = commitment_mod.subtree_width(n_shares, subtree_root_threshold)
        sizes = commitment_mod.merkle_mountain_range_sizes(n_shares, width)
        k = self.k
        subtree_roots: list[bytes] = []
        cursor = start
        for size in sizes:
            row, col = cursor // k, cursor % k
            if col % size != 0 or col + size > k:
                raise ValueError(
                    "blob chunk not aligned to a row subtree (layout violation)"
                )
            level = size.bit_length() - 1
            subtree_roots.append(self._node(row, level, col >> level))
            cursor += size
        return merkle_host.hash_from_leaves(subtree_roots)

    def prove_tx(self, square: Square, tx_index: int) -> ShareProof:
        """Tx inclusion proof (pkg/proof/proof.go:NewTxInclusionProof)."""
        from celestia_app_tpu.da import namespace as ns_mod

        start, end = proof_mod.tx_share_range(square, tx_index)
        ns = (
            ns_mod.TX_NAMESPACE.raw
            if tx_index < len(square.txs)
            else ns_mod.PAY_FOR_BLOB_NAMESPACE.raw
        )
        return self.prove_shares(start, end, ns)
