"""Blob share commitments: the Merkle-mountain-range over NMT subtree roots.

Reference parity: go-square `inclusion.CreateCommitment` (called from
x/blob/types/payforblob.go:53 and blob_tx.go:98) per the spec's "Blob Share
Commitment Rules" (specs/src/specs/data_square_layout.md:38-58):

  SubtreeWidth  = min(roundUpPow2(ceil(shares / SubtreeRootThreshold)),
                      minSquareSize(shares))
  tree sizes    = MMR decomposition of the share count with max width
                  SubtreeWidth (full-width trees, then descending powers of 2)
  subtree roots = NMT roots over each chunk's ns-prefixed shares
  commitment    = RFC-6962 Merkle root over the serialized (90 B) subtree roots

Because blobs start at multiples of SubtreeWidth (non-interactive default,
square.py), these subtree roots appear verbatim as inner nodes of the row NMTs
for any square size — commitments are square-size independent (ADR-008/013).

Host path here (hashlib, used per-tx in CheckTx); da/commitment_device.py
writes every blob of a batch into one buffer and takes all their subtree
roots from one device program (BASELINE config 3); admission and
ProcessProposal reach it via blob_validation.batch_commitments.
"""

from __future__ import annotations

import hashlib

from celestia_app_tpu import appconsts as c
from celestia_app_tpu.da import shares as shares_mod
from celestia_app_tpu.da.blob import Blob
from celestia_app_tpu.utils import merkle_host


def round_up_pow2(n: int) -> int:
    k = 1
    while k < n:
        k *= 2
    return k


def min_square_size(share_count: int) -> int:
    """Smallest power-of-two square edge that fits `share_count` shares."""
    import math

    return round_up_pow2(math.isqrt(share_count - 1) + 1 if share_count > 1 else 1)


def subtree_width(share_count: int, subtree_root_threshold: int) -> int:
    s = -(-share_count // subtree_root_threshold)  # ceil
    return min(round_up_pow2(s), min_square_size(share_count))


def merkle_mountain_range_sizes(total: int, max_tree_size: int) -> list[int]:
    """Decompose `total` leaves into MMR tree sizes with cap `max_tree_size`."""
    sizes = []
    while total >= max_tree_size:
        sizes.append(max_tree_size)
        total -= max_tree_size
    if total:
        p = max_tree_size
        while total:
            while p > total:
                p //= 2
            sizes.append(p)
            total -= p
    return sizes


def _leaf_digests(blob: Blob) -> list[bytes]:
    """The NMT leaf hash, sha256(0x00 | ns | share), of every share
    `shares.split_blob` gives for the blob, straight from the blob's bytes:
    a share is a fixed header, a slice of the data and, in the last, zero
    fill, so no share is built to be hashed."""
    ns, data = blob.namespace.raw, memoryview(blob.data)
    first_header, later_header = shares_mod.sparse_share_headers(
        blob.namespace, len(data), blob.share_version)
    take = c.FIRST_SPARSE_SHARE_CONTENT_SIZE
    hasher = hashlib.sha256(b"\x00" + ns + first_header)
    later = hashlib.sha256(b"\x00" + ns + later_header)
    digests = []
    pos = 0
    while True:
        chunk = data[pos:pos + take]
        hasher.update(chunk)
        if len(chunk) < take:
            hasher.update(bytes(take - len(chunk)))
        digests.append(hasher.digest())
        pos += take
        if pos >= len(data):
            return digests
        take = c.CONTINUATION_SPARSE_SHARE_CONTENT_SIZE
        hasher = later.copy()


def create_commitment(blob: Blob, subtree_root_threshold: int) -> bytes:
    """32-byte share commitment of a blob.

    Every leaf of a blob's subtrees carries the blob's namespace, so every
    node's (min, max) is (ns, ns) and only the digests differ; every MMR
    size is a power of two, so each subtree folds level by level. Equal,
    digest for digest, to NmtTree over split_blob (tests/test_commitment.py
    keeps that as the reference): a wallet signs thousands of these."""
    digests = _leaf_digests(blob)
    width = subtree_width(len(digests), subtree_root_threshold)
    span = blob.namespace.raw * 2
    inner = hashlib.sha256(b"\x01" + span)
    subtree_roots: list[bytes] = []
    cursor = 0
    for size in merkle_mountain_range_sizes(len(digests), width):
        level = digests[cursor:cursor + size]
        cursor += size
        while len(level) > 1:
            folded = []
            for i in range(0, len(level), 2):
                node = inner.copy()
                node.update(level[i] + span + level[i + 1])
                folded.append(node.digest())
            level = folded
        subtree_roots.append(span + level[0])
    return merkle_host.hash_from_leaves(subtree_roots)


def create_commitments(blobs: list[Blob], subtree_root_threshold: int) -> list[bytes]:
    return [create_commitment(b, subtree_root_threshold) for b in blobs]
