"""Device-batched commitments and proofs vs the host reference paths."""

import numpy as np
import pytest

from celestia_app_tpu.da import commitment, commitment_device, dah, proof, proof_device, square
from celestia_app_tpu.da import shares as shares_mod
from celestia_app_tpu.da.blob import Blob
from celestia_app_tpu.da.dah import ExtendedDataSquare
from celestia_app_tpu.da.namespace import Namespace
from celestia_app_tpu import appconsts


def _blobs(rng, spec):
    out = []
    for i, size in enumerate(spec):
        ns = Namespace.v0(bytes([i + 1]) * 8)
        out.append(Blob(ns, rng.integers(0, 256, size, dtype=np.uint8).tobytes()))
    return out


def _blob_bytes(n_shares: int) -> int:
    """The largest blob that fills exactly `n_shares` shares."""
    return (appconsts.FIRST_SPARSE_SHARE_CONTENT_SIZE
            + (n_shares - 1) * appconsts.CONTINUATION_SPARSE_SHARE_CONTENT_SIZE)


# blob sizes in bytes, one batch a case; counts reduced from the cells' 36 / 16
COMMITMENT_BATCHES = {
    # sizes chosen to hit 1-share, multi-share, multi-subtree, and
    # non-power-of-two MMR decompositions
    "assorted": [10, 500, 2000, 480 * 9, 480 * 30, 7],
    "k128-pfb-full": [200_000] * 4,
    "k64-pfb-full": [50_000] * 4,
    "k64-pfb-light-1000": [1_000] * 4,
    "k64-pfb-light-2000": [2_000] * 4,
    "k64-pfb-light-8000": [8_000] * 4,
    # more than 4,096 shares: width 128 at threshold 64
    "wider-than-4096-shares": [_blob_bytes(4201), 300],
    # widths 128, 1, 8, 2, 1 at threshold 64, in one buffer
    "mixed-widths": [_blob_bytes(4201), _blob_bytes(3), 200_000,
                     _blob_bytes(100), 9],
    "one-share": [1, appconsts.FIRST_SPARSE_SHARE_CONTENT_SIZE, 40, 300],
    # n an exact multiple of its width: 128 = 64 x 2, 416 = 52 x 8
    "exact-width-multiples": [_blob_bytes(128), _blob_bytes(416),
                              _blob_bytes(64), _blob_bytes(128)],
}
THRESHOLDS = [appconsts.subtree_root_threshold(appconsts.LATEST_VERSION), 8]


@pytest.mark.backend
@pytest.mark.parametrize("share_version", appconsts.SUPPORTED_SHARE_VERSIONS)
@pytest.mark.parametrize("thr", THRESHOLDS)
@pytest.mark.parametrize("case", COMMITMENT_BATCHES)
def test_commitments_device_match_host(case, thr, share_version):
    rng = np.random.default_rng(0)
    blobs = [Blob(b.namespace, b.data, share_version)
             for b in _blobs(rng, COMMITMENT_BATCHES[case])]
    host = commitment.create_commitments(blobs, thr)
    dev = commitment_device.commitments_device(blobs, thr)
    assert dev == host


def test_mixed_widths_share_one_buffer_with_bounded_padding():
    """The batch of the `mixed-widths` case is what it says: widths 1, 2,
    8 and 128 in one buffer, each blob at a multiple of its own width, and
    less padding between blobs than the batch has rows of its own."""
    thr = THRESHOLDS[0]
    blobs = _blobs(np.random.default_rng(0), COMMITMENT_BATCHES["mixed-widths"])
    counts = [shares_mod.sparse_shares_needed(len(b.data)) for b in blobs]
    widths = [commitment.subtree_width(n, thr) for n in counts]
    assert sorted(set(widths)) == [1, 2, 8, 128]
    buf, width, picks, per_blob, used = commitment_device._pack(blobs, thr)
    assert width == 128 and buf.shape == (8192, 512)
    assert used < 2 * sum(counts)
    assert per_blob == [
        len(commitment.merkle_mountain_range_sizes(n, w))
        for n, w in zip(counts, widths)]
    n_roots = sum(per_blob)
    assert n_roots == 144 and len(picks) == 256
    assert not picks[n_roots:].any()
    # the buffer holds exactly split_blob's shares, each blob at its start
    cursor = 0
    for blob, n, w in zip(blobs, counts, widths):
        cursor = commitment_device.aligned_start(cursor, w)
        want = b"".join(s.raw for s in shares_mod.split_blob(
            blob.namespace, blob.data, blob.share_version))
        assert buf[cursor:cursor + n].tobytes() == want
        cursor += n
    assert cursor == used


@pytest.mark.parametrize("thr", THRESHOLDS)
def test_subtree_plan_names_every_share_once(thr):
    """Host only. For every share count 1 .. 5,000: a blob that starts at
    a multiple of its width has every MMR chunk start at a multiple of the
    chunk's size, and the (level, index) plan walks the blob's rows in
    order, each exactly once."""
    for n in range(1, 5001):
        w = commitment.subtree_width(n, thr)
        assert w & (w - 1) == 0 and w <= n
        start = 3 * w
        sizes = np.asarray(commitment.merkle_mountain_range_sizes(n, w))
        chunk_starts = start + np.cumsum(sizes) - sizes
        assert not (chunk_starts % sizes).any(), n
        levels, indices = commitment_device.subtree_plan(start, n, w)
        assert ((1 << levels) == sizes).all(), n
        assert ((indices << levels) == chunk_starts).all(), n
        assert chunk_starts[-1] + sizes[-1] == start + n


@pytest.mark.backend
def test_block_prover_matches_host_proofs():
    rng = np.random.default_rng(1)
    blobs = _blobs(rng, [700, 1500, 300])
    sq = square.build(
        [b"\x09sometx"],
        [square.PfbEntry(tx=bytes([i]) * 8, blobs=[b]) for i, b in enumerate(blobs)],
        64,
        64,
    )
    ods = dah.shares_to_ods(sq.share_bytes())
    d, eds_obj, root = dah.new_dah_from_ods(ods)
    prover = proof_device.BlockProver(eds_obj, d)
    k = sq.size

    # every blob's range + a few arbitrary ranges: byte-identical proofs
    ranges = [proof.blob_share_range(sq, i, 0) for i in range(len(blobs))]
    ranges += [(0, 1), (0, k * k), (k - 1, k + 1 if k > 1 else k)]
    for lo, hi in ranges:
        ns = b"\x00" * 29
        dev_p = prover.prove_shares(lo, hi, ns)
        host_p = proof.new_share_inclusion_proof(eds_obj, d, lo, hi, ns)
        assert dev_p == host_p, (lo, hi)
        assert dev_p.verify(root)

    # tx proof parity
    dev_t = prover.prove_tx(sq, 0)
    host_t = proof.new_tx_inclusion_proof(sq, eds_obj, d, 0)
    assert dev_t == host_t
    assert dev_t.verify(root)


@pytest.mark.backend
def test_block_prover_rejects_bad_range():
    rng = np.random.default_rng(2)
    sq = square.build([], [square.PfbEntry(tx=b"x", blobs=_blobs(rng, [100]))], 64, 64)
    ods = dah.shares_to_ods(sq.share_bytes())
    d, eds_obj, _ = dah.new_dah_from_ods(ods)
    prover = proof_device.BlockProver(eds_obj, d)
    with pytest.raises(ValueError):
        prover.prove_shares(0, sq.size * sq.size + 1, b"\x00" * 29)


@pytest.mark.backend
def test_commitment_from_eds_matches_direct():
    """pkg/inclusion GetCommitment analog: the commitment recomputed from
    the committed EDS's cached row-tree nodes equals the one computed
    directly from the blob bytes, for every blob in the block."""
    rng = np.random.default_rng(7)
    thr = appconsts.subtree_root_threshold(appconsts.LATEST_VERSION)
    blobs = _blobs(rng, [100, 700, 480 * 3, 2500, 30])
    sq = square.build(
        [b"\x05tx"],
        [square.PfbEntry(tx=bytes([i]) * 6, blobs=[b]) for i, b in enumerate(blobs)],
        64, thr,
    )
    ods = dah.shares_to_ods(sq.share_bytes())
    d, eds_obj, _ = dah.new_dah_from_ods(ods)
    prover = proof_device.BlockProver(eds_obj, d)
    for i, b in enumerate(blobs):
        want = commitment.create_commitment(b, thr)
        got = prover.commitment_from_eds(sq, i, 0, thr)
        assert got == want, i
