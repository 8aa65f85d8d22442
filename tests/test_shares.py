"""Share codec: byte formats, splitting, parsing (specs/src/specs/shares.md)."""

import numpy as np
import pytest

from celestia_app_tpu import appconsts as c
from celestia_app_tpu.da import namespace as ns_mod
from celestia_app_tpu.da import shares


def test_content_sizes():
    assert c.FIRST_SPARSE_SHARE_CONTENT_SIZE == 478
    assert c.CONTINUATION_SPARSE_SHARE_CONTENT_SIZE == 482
    assert c.FIRST_COMPACT_SHARE_CONTENT_SIZE == 474
    assert c.CONTINUATION_COMPACT_SHARE_CONTENT_SIZE == 478


def test_tail_padding_share_bytes():
    s = shares.tail_padding_share()
    assert len(s) == 512
    assert s[:29] == ns_mod.TAIL_PADDING_NAMESPACE.raw
    assert s[29] == 0x01  # version 0, sequence_start=1
    assert s[30:] == b"\x00" * 482


@pytest.mark.parametrize("size", [0, 1, 478, 479, 960, 961, 5000])
def test_blob_split_parse_roundtrip(size):
    rng = np.random.default_rng(size)
    ns = ns_mod.Namespace.v0(b"roundtrip")
    data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    shs = shares.split_blob(ns, data)
    assert len(shs) == shares.sparse_shares_needed(size)
    assert shs[0].is_sequence_start and shs[0].sequence_len() == size
    for s in shs[1:]:
        assert not s.is_sequence_start
    for s in shs:
        assert s.namespace == ns
    assert shares.parse_sparse_shares(shs) == data


def test_sparse_shares_needed():
    assert shares.sparse_shares_needed(0) == 1
    assert shares.sparse_shares_needed(478) == 1
    assert shares.sparse_shares_needed(479) == 2
    assert shares.sparse_shares_needed(478 + 482) == 2
    assert shares.sparse_shares_needed(478 + 482 + 1) == 3


@pytest.mark.parametrize(
    "tx_sizes",
    [[10], [100, 200, 300], [474], [5000], [1, 473], [600, 600, 600], []],
)
def test_tx_split_parse_roundtrip(tx_sizes):
    rng = np.random.default_rng(sum(tx_sizes) + len(tx_sizes))
    txs = [rng.integers(0, 256, s, dtype=np.uint8).tobytes() for s in tx_sizes]
    shs = shares.split_txs(ns_mod.TX_NAMESPACE, txs)
    if not txs:
        assert shs == [] or shares.parse_compact_shares(shs) == []
        return
    assert shares.parse_compact_shares(shs) == txs


def test_first_compact_share_reserved_bytes():
    """First unit starts right after the header: offset 38 (shares.md figure)."""
    shs = shares.split_txs(ns_mod.TX_NAMESPACE, [b"\xaa" * 10])
    raw = shs[0].raw
    reserved = int.from_bytes(raw[34:38], "big")
    assert reserved == 38


def test_continuation_share_reserved_bytes():
    """A tx spanning into share 2 leaves its tail there; the next unit start
    is recorded in share 2's reserved bytes."""
    tx1 = b"\xbb" * 500  # spills into the second share
    tx2 = b"\xcc" * 10
    shs = shares.split_txs(ns_mod.TX_NAMESPACE, [tx1, tx2])
    assert len(shs) == 2
    raw2 = shs[1].raw
    reserved = int.from_bytes(raw2[30:34], "big")
    # unit2 starts at sequence offset len(uvarint(500)) + 500 = 502;
    # share 2 content starts at sequence offset 474, in-share content offset 34.
    assert reserved == 34 + (502 - 474)
    assert shares.parse_compact_shares(shs) == [tx1, tx2]


def test_namespace_validation():
    with pytest.raises(ValueError):
        ns_mod.TX_NAMESPACE.validate_for_blob()  # reserved
    with pytest.raises(ValueError):
        ns_mod.PARITY_SHARE_NAMESPACE.validate_for_blob()
    ns_mod.Namespace.v0(b"okay").validate_for_blob()


def test_namespace_ordering():
    assert ns_mod.TX_NAMESPACE < ns_mod.PAY_FOR_BLOB_NAMESPACE
    assert ns_mod.PAY_FOR_BLOB_NAMESPACE < ns_mod.PRIMARY_RESERVED_PADDING_NAMESPACE
    user = ns_mod.Namespace.v0(b"zzz")
    assert ns_mod.PRIMARY_RESERVED_PADDING_NAMESPACE < user
    assert user < ns_mod.TAIL_PADDING_NAMESPACE < ns_mod.PARITY_SHARE_NAMESPACE


def test_padding_share_parse():
    s = shares.namespace_padding_share(ns_mod.Namespace.v0(b"pad"))
    assert s.is_padding() and s.sequence_len() == 0


# -- the array writers against the share-by-share definition ----------------


@pytest.mark.parametrize(
    "size", [0, 1, 477, 478, 479, 478 + 482, 478 + 482 + 1, 5000, 200_000])
def test_write_blob_is_split_blob(size):
    rng = np.random.default_rng(size)
    ns = ns_mod.Namespace.v0(b"writer")
    data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    want = shares.split_blob(ns, data)
    out = np.zeros((len(want) + 3, 512), dtype=np.uint8)
    assert shares.write_blob(out, 2, ns, data) == len(want)
    assert out[2:-1].tobytes() == b"".join(s.raw for s in want)
    assert not out[:2].any() and not out[-1].any()  # nothing beside its rows


@pytest.mark.parametrize(
    "tx_sizes",
    [[], [10], [100, 200, 300], [472], [472, 3 * 478 - 2, 100], [474],
     [5000], [1, 473], [600, 600, 600], [1] * 700, [127, 128, 16383, 16384]],
)
def test_write_txs_is_split_txs(tx_sizes):
    rng = np.random.default_rng(sum(tx_sizes) + len(tx_sizes))
    txs = [rng.integers(0, 256, s, dtype=np.uint8).tobytes() for s in tx_sizes]
    want = shares.split_txs(ns_mod.PAY_FOR_BLOB_NAMESPACE, txs)
    out = np.zeros((len(want) + 2, 512), dtype=np.uint8)
    assert shares.write_txs(
        out, 1, ns_mod.PAY_FOR_BLOB_NAMESPACE, txs) == len(want)
    assert out[1:-1].tobytes() == b"".join(s.raw for s in want)
    assert not out[0].any() and not out[-1].any()


def test_write_blob_refuses_what_split_blob_refuses():
    ns = ns_mod.Namespace.v0(b"writer")
    out = np.zeros((2, 512), dtype=np.uint8)
    with pytest.raises(ValueError):
        shares.split_blob(ns, b"x", share_version=9)
    with pytest.raises(ValueError):
        shares.write_blob(out, 0, ns, b"x", share_version=9)


def test_padding_row_is_the_padding_share():
    ns = ns_mod.Namespace.v0(b"pad")
    assert shares.padding_row(ns).tobytes() \
        == shares.namespace_padding_share(ns).raw
    assert shares.padding_row(ns_mod.TAIL_PADDING_NAMESPACE).tobytes() \
        == shares.tail_padding_share()
    assert shares.padding_row(
        ns_mod.PRIMARY_RESERVED_PADDING_NAMESPACE).tobytes() \
        == shares.reserved_padding_share().raw
