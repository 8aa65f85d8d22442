"""Batched blob share commitments on device (BASELINE.md config 3).

Computes the same commitments as da/commitment.py (go-square
`inclusion.CreateCommitment`, x/blob/types/payforblob.go:53) for every blob
of a batch at once: ONE buffer up, ONE program, the subtree roots down, and
a host-side Merkle fold over each blob's ordered 90-byte roots.

**The buffer.** Every blob's shares are written by `shares.write_blob`
straight into one zeroed `(rows, 512)` uint8 array: no `Share` object, no
join. A leaf's namespace is the first 29 bytes of its own share, so nothing
else goes up.

**The alignment rule.** A blob of n shares starts at a row that is a
multiple of its OWN subtree width w = `commitment.subtree_width(n,
threshold)` (a power of two, at most 128 for any blob that fits a
k <= 128 square). Its MMR sizes are powers of two, none
larger than w and none larger than the one before, so every subtree of size
s then begins at a row that is a multiple of s: its root is node
`start // s` of level `log2(s)` of a plain pairwise reduction over the whole
buffer. `subtree_plan` names those nodes by integer arithmetic; no chunk is
sliced or copied. Rows between blobs and after the last stay zero; nodes
that mix two blobs or padding are computed and never read. Aligning to the
blob's own width (not the batch's largest) keeps the padding below the
batch's own row count whatever the mix.

**The bucket rule and the program's shape key.** `rows` is padded to the
next power of two (at least W, the largest width in the batch), and the
index vector that gathers the named roots ON THE CHIP is padded to the next
power of two of the root count (pad picks read node 0 and are dropped), so
one compiled program serves every batch of one (padded rows, W, padded
picks): a cell's blocks are one shape each. The roots are gathered on the
chip rather than the level arrays brought down whole because what comes down
is then n_roots x 90 B (180 KB for a full k=128 block, not 2.7 MB) and the
key grows by one small integer that repeats block after block.

The reduction is `ops/nmt.nmt_levels` over the buffer viewed as
`(rows / W, W, 512)`: levels 0 .. log2(W) from one launch, each level one
vectorized SHA-256 call (the Pallas kernel on TPU from 1,024 messages).
Levels above a narrow blob's own width cost at most a third of its leaf
hashing (3 SHA blocks an inner node against 9 a leaf).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from celestia_app_tpu import appconsts, obs
from celestia_app_tpu.da import commitment as commitment_mod
from celestia_app_tpu.da import shares as shares_mod
from celestia_app_tpu.da.blob import Blob
from celestia_app_tpu.ops import nmt, pow2_bucket
from celestia_app_tpu.utils import hostbuf, merkle_host, telemetry

NS = appconsts.NAMESPACE_SIZE
SHARE = appconsts.SHARE_SIZE
ROOT = appconsts.NMT_ROOT_SIZE  # a serialized node: min || max || digest

telemetry.set_help(
    "commitment.batch_programs",
    "device programs launched by blob commitment batches (one a batch)")

_POW2 = 1 << np.arange(32, dtype=np.int64)


def aligned_start(cursor: int, width: int) -> int:
    """The first row at or after `cursor` that is a multiple of `width`."""
    return -(-cursor // width) * width


def subtree_plan(start: int, n_shares: int,
                 width: int) -> tuple[np.ndarray, np.ndarray]:
    """(levels, indices) of a blob's subtree roots, in MMR order, for a blob
    of `n_shares` shares whose first share is global row `start` (a multiple
    of `width`): the subtree of size s that begins at row r is node r // s
    of level log2(s) of the pairwise reduction over the whole buffer."""
    sizes = np.asarray(
        commitment_mod.merkle_mountain_range_sizes(n_shares, width),
        dtype=np.int64)
    starts = start + np.cumsum(sizes) - sizes
    levels = np.searchsorted(_POW2, sizes)  # log2 of a power of two
    return levels, starts >> levels


def level_offsets(rows: int, n_levels: int) -> np.ndarray:
    """Where each level begins in the program's node stack (level 0's
    `rows` nodes first, then level 1's rows / 2, ...)."""
    counts = rows >> np.arange(n_levels, dtype=np.int64)
    return np.cumsum(counts) - counts


@functools.partial(jax.jit, static_argnames=("width",))
def commitment_subtree_roots(data: jax.Array, picks: jax.Array, *,
                             width: int) -> jax.Array:
    """(rows, 512) u8 shares + (p,) i32 positions in the node stack ->
    (p, 90) u8 serialized nodes. Named for the trace: the extend rooflines
    select programs by the prefix `jit_run(`."""
    trees = data.reshape(data.shape[0] // width, width, SHARE)
    stack = jnp.concatenate([
        jnp.concatenate([mins, maxs, digests], axis=-1).reshape(-1, ROOT)
        for mins, maxs, digests in nmt.nmt_levels(trees[..., :NS], trees)
    ])
    return stack[picks]


def _pack(blobs: list[Blob], subtree_root_threshold: int):
    """The batch as device input: (buffer, width, picks, roots per blob,
    rows used)."""
    counts = [shares_mod.sparse_shares_needed(len(b.data)) for b in blobs]
    widths = [commitment_mod.subtree_width(n, subtree_root_threshold)
              for n in counts]
    starts = []
    cursor = 0
    for n, w in zip(counts, widths):
        starts.append(aligned_start(cursor, w))
        cursor = starts[-1] + n
    width = max(widths)
    buf = hostbuf.lease_zeroed(max(pow2_bucket(cursor), width), SHARE)
    offsets = level_offsets(buf.shape[0], width.bit_length())
    picks, per_blob = [], []
    for blob, start, n, w in zip(blobs, starts, counts, widths):
        shares_mod.write_blob(buf, start, blob.namespace, blob.data,
                              blob.share_version)
        levels, indices = subtree_plan(start, n, w)
        picks.append(offsets[levels] + indices)
        per_blob.append(len(levels))
    flat = np.concatenate(picks)
    padded = np.zeros(pow2_bucket(len(flat)), dtype=np.int32)
    padded[: len(flat)] = flat
    return buf, width, padded, per_blob, cursor


def commitments_device(
    blobs: list[Blob], subtree_root_threshold: int
) -> list[bytes]:
    """Share commitments for all blobs: one buffer, one program, one fold."""
    if not blobs:
        return []
    with obs.span("admission.commit_pack") as sp:
        buf, width, picks, per_blob, used = _pack(
            blobs, subtree_root_threshold)
        sp.set(rows=used, padded_rows=buf.shape[0],
               levels=width.bit_length())
    with obs.span("admission.commit_dispatch"):
        out = commitment_subtree_roots(
            jnp.asarray(buf), jnp.asarray(picks), width=width)
        telemetry.incr("commitment.batch_programs")
        roots = np.asarray(out).tobytes()
    with obs.span("admission.commit_fold"):
        commitments = []
        at = 0
        for n_roots in per_blob:
            end = at + n_roots * ROOT
            commitments.append(merkle_host.hash_from_leaves(
                [roots[i : i + ROOT] for i in range(at, end, ROOT)]))
            at = end
    return commitments
