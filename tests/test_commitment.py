"""Share commitments: MMR decomposition, spec pins, size-independence."""

import numpy as np
import pytest

from celestia_app_tpu.da import namespace as ns_mod
from celestia_app_tpu.da import square as square_mod
from celestia_app_tpu.da.blob import Blob
from celestia_app_tpu.da.commitment import (
    create_commitment,
    merkle_mountain_range_sizes,
    min_square_size,
    round_up_pow2,
    subtree_width,
)
from celestia_app_tpu.da.square import PfbEntry
from celestia_app_tpu.utils import merkle_host, nmt_host


def test_round_up_pow2():
    assert [round_up_pow2(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]


def test_min_square_size():
    assert min_square_size(1) == 1
    assert min_square_size(2) == 2
    assert min_square_size(4) == 2
    assert min_square_size(5) == 4
    assert min_square_size(15) == 4
    assert min_square_size(17) == 8


def test_subtree_width_spec_example():
    """Spec: a 172-share blob with SRT=64 gives width 4 -> 43 trees of 4."""
    assert subtree_width(172, 64) == 4
    assert merkle_mountain_range_sizes(172, 4) == [4] * 43


def test_subtree_width_small_blob():
    assert subtree_width(15, 64) == 1
    assert subtree_width(1, 64) == 1


def test_mmr_sizes():
    assert merkle_mountain_range_sizes(11, 4) == [4, 4, 2, 1]
    assert merkle_mountain_range_sizes(2, 64) == [2]
    assert merkle_mountain_range_sizes(64, 8) == [8] * 8


def test_commitment_deterministic():
    rng = np.random.default_rng(0)
    blob = Blob(ns_mod.Namespace.v0(b"c"), rng.integers(0, 256, 999, dtype=np.uint8).tobytes())
    assert create_commitment(blob, 64) == create_commitment(blob, 64)
    assert create_commitment(blob, 64) != create_commitment(
        Blob(blob.namespace, blob.data + b"x"), 64
    )


def test_commitment_subtree_roots_are_row_tree_nodes():
    """ADR-008/013: with the NI-default alignment, the commitment's subtree
    roots are literally nodes of the row NMTs. For a width-1 blob the subtree
    roots are row-tree leaf nodes; check them against a built square."""
    rng = np.random.default_rng(1)
    blob = Blob(ns_mod.Namespace.v0(b"w"), rng.integers(0, 256, 3 * 478, dtype=np.uint8).tobytes())
    assert subtree_width(blob.share_count(), 64) == 1

    sq = square_mod.build([], [PfbEntry(b"p", (blob,))], 64, 64)
    start = sq.blob_start_indexes[(0, 0)]
    count = blob.share_count()

    # subtree roots from the square's own shares (width-1 => leaf nodes)
    roots = []
    for i in range(count):
        share = sq.shares[start + i]
        roots.append(
            nmt_host.serialize(nmt_host.leaf_node(blob.namespace.raw, share.raw))
        )
    assert create_commitment(blob, 64) == merkle_host.hash_from_leaves(roots)


def _reference_commitment(blob: Blob, subtree_root_threshold: int) -> bytes:
    """The definition, slowly: split the blob into shares, push each chunk
    of them into a namespaced Merkle tree, hash the serialized roots."""
    from celestia_app_tpu.da import shares as shares_mod

    shares = shares_mod.split_blob(
        blob.namespace, blob.data, blob.share_version)
    width = subtree_width(len(shares), subtree_root_threshold)
    roots, cursor = [], 0
    for size in merkle_mountain_range_sizes(len(shares), width):
        tree = nmt_host.NmtTree()
        for share in shares[cursor:cursor + size]:
            tree.push(blob.namespace.raw, share.raw)
        roots.append(nmt_host.serialize(tree.root()))
        cursor += size
    return merkle_host.hash_from_leaves(roots)


# around the first share's 478 bytes and a later share's 482, MMR tails of
# every shape (11 = 8+2+1 ...), and the benchmark's 50 / 200 KB blobs
_BLOB_SIZES = [1, 477, 478, 479, 478 + 482, 478 + 483, 478 + 10 * 482 - 1,
               999, 8_000, 50_000, 200_000, 1_200_000]


@pytest.mark.parametrize("size", _BLOB_SIZES)
def test_commitment_equals_the_tree_definition(size):
    rng = np.random.default_rng(size)
    data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    for ns in (ns_mod.Namespace.v0(b"fast"),
               ns_mod.Namespace(ns_mod.PARITY_NS_RAW)):
        blob = Blob(ns, data)
        for threshold in (64, 8, 1):
            assert create_commitment(blob, threshold) \
                == _reference_commitment(blob, threshold), (size, threshold)


@pytest.mark.parametrize("threshold", [64, 8])
def test_device_commitments_of_two_batches_over_one_held_buffer(
        monkeypatch, threshold):
    """With the pool's size constant patched down to the batches' pack
    buffer: the second batch packs into the array the first one wrote, and
    stale rows of the first never reach the second's roots."""
    from celestia_app_tpu.da import commitment_device
    from celestia_app_tpu.da.commitment import create_commitments
    from celestia_app_tpu.utils import hostbuf, telemetry

    rng = np.random.default_rng(threshold)

    def blobs(sizes):
        return [Blob(ns_mod.Namespace.v0(bytes([1 + i]) * 5),
                     rng.integers(0, 256, s, dtype=np.uint8).tobytes())
                for i, s in enumerate(sizes)]

    # both batches pad to 64 rows; the second leaves rows the first wrote
    first, second = blobs([9_000, 14_000, 700]), blobs([300, 20_000])
    monkeypatch.setattr(hostbuf, "HELD_FROM_BYTES", 64 * 512)
    monkeypatch.setattr(hostbuf, "_held", [])
    c0 = telemetry.snapshot()["counters"]
    for batch in (first, second, first):
        assert commitment_device._pack(batch, threshold)[0].shape == (64, 512)
        assert commitment_device.commitments_device(batch, threshold) \
            == create_commitments(batch, threshold)
    c1 = telemetry.snapshot()["counters"]
    assert c1.get("hostbuf.leases", 0) - c0.get("hostbuf.leases", 0) == 6
    assert c1.get("hostbuf.reuses", 0) - c0.get("hostbuf.reuses", 0) >= 4
    assert len(hostbuf._held) <= 2
