"""Square layout: determinism, alignment, ordering, parsing back."""

import numpy as np
import pytest

from celestia_app_tpu.da import namespace as ns_mod
from celestia_app_tpu.da import shares as shares_mod
from celestia_app_tpu.da import square as square_mod
from celestia_app_tpu.da.blob import (
    Blob,
    marshal_index_wrapper,
    unmarshal_index_wrapper,
)
from celestia_app_tpu.da.commitment import subtree_width
from celestia_app_tpu.da.square import PfbEntry
from celestia_app_tpu.utils import telemetry

THRESHOLD = 64


def _blob(rng, ns_byte: int, size: int) -> Blob:
    ns = ns_mod.Namespace.v0(bytes([ns_byte]) * 5)
    return Blob(ns, rng.integers(0, 256, size, dtype=np.uint8).tobytes())


def test_empty_square():
    sq = square_mod.build([], [], 64, THRESHOLD)
    assert sq.size == 1
    assert len(sq.shares) == 1
    assert sq.shares[0].raw == shares_mod.tail_padding_share()


def test_txs_only_roundtrip():
    rng = np.random.default_rng(0)
    txs = [rng.integers(0, 256, s, dtype=np.uint8).tobytes() for s in (50, 700, 30)]
    sq = square_mod.build(txs, [], 64, THRESHOLD)
    tx_shares = sq.shares[: sq.tx_shares_len]
    assert shares_mod.parse_compact_shares(tx_shares) == txs
    # everything after is tail padding
    for s in sq.shares[sq.tx_shares_len :]:
        assert s.namespace == ns_mod.TAIL_PADDING_NAMESPACE


def test_blob_alignment_and_order():
    rng = np.random.default_rng(1)
    pfbs = [
        PfbEntry(b"pfb-b", (_blob(rng, 9, 3000),)),
        PfbEntry(b"pfb-a", (_blob(rng, 3, 1000), _blob(rng, 7, 600))),
    ]
    sq = square_mod.build([b"tx1"], pfbs, 64, THRESHOLD)
    # every blob starts at a multiple of its subtree width
    for (i, j), start in sq.blob_start_indexes.items():
        blob = sq.pfbs[i].blobs[j]
        width = subtree_width(blob.share_count(), THRESHOLD)
        assert start % width == 0, (i, j, start, width)
    # square is namespace-sorted
    ns_order = [s.namespace.raw for s in sq.shares]
    assert ns_order == sorted(ns_order)
    # blob namespaces appear in ascending order: 3, 7, 9
    starts = sorted(sq.blob_start_indexes.items(), key=lambda kv: kv[1])
    ns_bytes = [sq.pfbs[i].blobs[j].namespace.raw[-5] for (i, j), _ in starts]
    assert ns_bytes == [3, 7, 9]


def test_blob_data_recoverable():
    rng = np.random.default_rng(2)
    blob = _blob(rng, 5, 2500)
    sq = square_mod.build([], [PfbEntry(b"pfb", (blob,))], 64, THRESHOLD)
    start = sq.blob_start_indexes[(0, 0)]
    count = blob.share_count()
    got = shares_mod.parse_sparse_shares(sq.shares[start : start + count])
    assert got == blob.data


def test_wrapped_pfb_roundtrip():
    rng = np.random.default_rng(3)
    blob = _blob(rng, 4, 100)
    sq = square_mod.build([], [PfbEntry(b"mypfb", (blob,))], 64, THRESHOLD)
    pfb_shares = sq.shares[sq.tx_shares_len : sq.tx_shares_len + sq.pfb_shares_len]
    wrapped = shares_mod.parse_compact_shares(pfb_shares)
    assert len(wrapped) == 1
    iw = unmarshal_index_wrapper(wrapped[0])
    assert iw.tx == b"mypfb"
    assert iw.share_indexes == (sq.blob_start_indexes[(0, 0)],)


def test_in_square_wrapper_is_reference_protobuf():
    """VERDICT r3 #2 done-criterion: the PAY_FOR_BLOB_NAMESPACE shares carry
    protobuf IndexWrappers (type_id "INDX") decodable with the byte-compat
    codec (wire/txpb.py, cross-checked against the google.protobuf runtime
    in tests/test_wire.py) — the bytes go-square writes in-square
    (app/encoding/index_wrapper_decoder.go:10)."""
    from celestia_app_tpu.wire import txpb

    rng = np.random.default_rng(7)
    pfbs = [
        PfbEntry(b"pfb-x" * 20, (_blob(rng, 3, 900), _blob(rng, 6, 150))),
        PfbEntry(b"pfb-y", (_blob(rng, 5, 5000),)),
    ]
    sq = square_mod.build([b"normal-tx"], pfbs, 64, THRESHOLD)
    pfb_shares = sq.shares[sq.tx_shares_len : sq.tx_shares_len + sq.pfb_shares_len]
    wrapped = shares_mod.parse_compact_shares(pfb_shares)
    assert len(wrapped) == 2
    for w, entry, i in zip(wrapped, sq.pfbs, range(2)):
        tx, idxs = txpb.parse_index_wrapper(w)  # raises unless protobuf INDX
        assert tx == entry.tx
        assert idxs == [
            sq.blob_start_indexes[(i, j)] for j in range(len(entry.blobs))
        ]


def test_reserved_padding_fills_pessimistic_gap():
    """The compact PFB sequence is reserved at worst-case index sizing; the
    actually-written wrappers are shorter, and the gap up to the first blob
    is primary-reserved padding (ADR-020 pessimistic append, shares.md
    'Primary Reserved Padding Share')."""
    rng = np.random.default_rng(8)
    # 28 single-blob PFBs at max square 128: reserved indexes are 3-byte
    # varints (16384), actual ones 1-2 bytes, so the reserve crosses a
    # share boundary the actual bytes don't
    pfbs = [PfbEntry(b"p%02d" % i, (_blob(rng, 10 + i, 600),)) for i in range(28)]
    sq = square_mod.build([], pfbs, 128, THRESHOLD)
    assert sq.pfb_shares_len < sq.pfb_shares_reserved
    first_blob = min(sq.blob_start_indexes.values())
    gap = sq.shares[sq.tx_shares_len + sq.pfb_shares_len : first_blob]
    assert gap, "expected a nonzero reserved-padding gap"
    for s in gap:
        assert s.namespace == ns_mod.PRIMARY_RESERVED_PADDING_NAMESPACE


def test_construct_equals_build():
    """The proposer's square and every validator's reconstruction must agree
    byte for byte (the PrepareProposal/ProcessProposal consistency core)."""
    rng = np.random.default_rng(4)
    txs = [rng.integers(0, 256, 80, dtype=np.uint8).tobytes() for _ in range(3)]
    pfbs = [
        PfbEntry(b"p1", (_blob(rng, 8, 1200),)),
        PfbEntry(b"p2", (_blob(rng, 2, 400), _blob(rng, 2, 90))),
    ]
    built = square_mod.build(txs, pfbs, 32, THRESHOLD)
    constructed = square_mod.construct(built.txs, built.pfbs, 32, THRESHOLD)
    assert built.size == constructed.size
    assert [s.raw for s in built.shares] == [s.raw for s in constructed.shares]


def test_construct_rejects_overflow():
    rng = np.random.default_rng(5)
    big = _blob(rng, 6, 1000 * 478)  # ~1000 shares
    with pytest.raises(ValueError):
        square_mod.construct([], [PfbEntry(b"p", (big,))], 16, THRESHOLD)


def test_build_drops_overflowing_tx():
    rng = np.random.default_rng(6)
    big = PfbEntry(b"big", (_blob(rng, 6, 200 * 478),))
    small = PfbEntry(b"small", (_blob(rng, 7, 100),))
    sq = square_mod.build([], [big, small], 4, THRESHOLD)  # max 16 shares
    assert [e.tx for e in sq.pfbs] == [b"small"]
    assert sq.size <= 4


def test_compact_shares_needed():
    assert square_mod.compact_shares_needed(0) == 0
    assert square_mod.compact_shares_needed(474) == 1
    assert square_mod.compact_shares_needed(475) == 2
    assert square_mod.compact_shares_needed(474 + 478) == 2
    assert square_mod.compact_shares_needed(474 + 478 + 1) == 3


def test_square_is_perfect_and_pow2():
    rng = np.random.default_rng(7)
    for n_blobs in (1, 3, 6):
        pfbs = [PfbEntry(b"p%d" % i, (_blob(rng, 3 + i, 700),)) for i in range(n_blobs)]
        sq = square_mod.build([], pfbs, 64, THRESHOLD)
        assert len(sq.shares) == sq.size**2
        assert sq.size & (sq.size - 1) == 0


def test_build_admitted_set_always_fits_exactly():
    """Pessimistic admission (worst-case padding) must over-approximate: an
    admitted set can never fail the exact layout (no eviction loop)."""
    rng = np.random.default_rng(21)
    for seed in range(6):
        r = np.random.default_rng(seed)
        pfbs = [
            PfbEntry(
                b"t%d" % i,
                tuple(
                    _blob(r, int(r.integers(1, 60)), int(r.integers(1, 5000)))
                    for _ in range(int(r.integers(1, 4)))
                ),
            )
            for i in range(30)
        ]
        for max_k in (8, 16, 32):
            sq = square_mod.build([], pfbs, max_k, THRESHOLD)
            assert sq.size <= max_k
            # re-running construct on the kept set must succeed (exact fit)
            sq2 = square_mod.construct(sq.txs, sq.pfbs, max_k, THRESHOLD)
            assert sq2.size == sq.size


def test_build_layout_speed_large_mempool():
    """VERDICT r2 #6 'done' criterion: a reference-MaxTxBytes-sized (7.9 MB)
    mempool lays out host-side in < 1 s."""
    import time

    rng = np.random.default_rng(0)
    pfbs = []
    total = 0
    while total < 7_900_000:
        size = int(rng.integers(800, 120_000))
        pfbs.append(
            PfbEntry(
                tx=bytes(350),
                blobs=(_blob(rng, int(rng.integers(1, 200)), size),),
            )
        )
        total += size + 350
    t0 = time.perf_counter()
    sq = square_mod.build([], pfbs, 128, THRESHOLD)
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"7.9MB layout took {dt:.2f}s"
    assert sq.size == 128
    assert len(sq.pfbs) >= len(pfbs) - 5  # nearly everything admitted


def test_builder_reserve_invariants_fuzz():
    """Property fuzz over the pessimistic-reserve builder (round-4 layout):
    for random tx/blob workloads across square caps,
      - build() == construct() share-for-share (Prepare/Process core),
      - actual PFB shares never exceed the reserve,
      - blobs start at/after the reserved region with NI-default alignment,
      - the square never exceeds the cap build() admitted against."""
    rng = np.random.default_rng(2024)
    for trial in range(40):
        max_sq = int(rng.choice([8, 16, 32, 64, 128]))
        txs = [
            rng.integers(0, 256, int(rng.integers(10, 400)),
                         dtype=np.uint8).tobytes()
            for _ in range(int(rng.integers(0, 6)))
        ]
        pfbs = []
        for i in range(int(rng.integers(0, 10))):
            n_blobs = int(rng.integers(1, 4))
            blobs = tuple(
                _blob(rng, int(rng.integers(1, 200)),
                      int(rng.integers(1, 40_000)))
                for _ in range(n_blobs)
            )
            tx_len = int(rng.integers(5, 600))
            pfbs.append(PfbEntry(bytes(tx_len), blobs))
        built = square_mod.build(txs, pfbs, max_sq, THRESHOLD)
        assert built.size <= max_sq, (trial, built.size, max_sq)
        assert built.pfb_shares_len <= built.pfb_shares_reserved
        if built.blob_start_indexes:
            first = min(built.blob_start_indexes.values())
            assert first >= built.tx_shares_len + built.pfb_shares_len
            for (i, j), start in built.blob_start_indexes.items():
                width = subtree_width(
                    built.pfbs[i].blobs[j].share_count(), THRESHOLD
                )
                assert start % width == 0
        constructed = square_mod.construct(
            built.txs, built.pfbs, max_sq, THRESHOLD
        )
        assert built.size == constructed.size, trial
        assert [s.raw for s in built.shares] == [
            s.raw for s in constructed.shares
        ], trial


# ---------------------------------------------------------------------------
# The square as one array: byte identity with the share-by-share definition
# ---------------------------------------------------------------------------


def _reference_share_bytes(txs, pfbs, max_sq, threshold=THRESHOLD) -> bytes:
    """The square as da/shares defines it share by share: `split_txs`,
    `split_blob` and the padding constructors placed by `_Layout`'s
    indexes, joined."""
    layout = square_mod._Layout(txs, pfbs, threshold, max_sq)
    k = max(layout.square_size(), 1)
    shares = []
    if layout.tx_shares:
        shares += shares_mod.split_txs(ns_mod.TX_NAMESPACE, txs)
    if layout.pfb_shares_reserved:
        shares += shares_mod.split_txs(ns_mod.PAY_FOR_BLOB_NAMESPACE, [
            marshal_index_wrapper(
                e.tx, [layout.starts[(i, j)] for j in range(len(e.blobs))])
            for i, e in enumerate(pfbs)
        ])
    prev_ns = None
    for _, i, j in layout.ordered:
        b = pfbs[i].blobs[j]
        gap = layout.starts[(i, j)] - len(shares)
        shares += [shares_mod.reserved_padding_share() if prev_ns is None
                   else shares_mod.namespace_padding_share(prev_ns)] * gap
        shares += shares_mod.split_blob(b.namespace, b.data, b.share_version)
        prev_ns = b.namespace
    shares += shares_mod.tail_padding_shares(k * k - len(shares))
    assert len(shares) == k * k
    return b"".join(s.raw for s in shares)


def _tx(rng, size: int) -> bytes:
    return rng.integers(0, 256, size, dtype=np.uint8).tobytes()


def _identity_case(name: str):
    """(txs, pfbs, max square) of one branch of the writer."""
    rng = np.random.default_rng(len(name))
    if name == "empty":
        return [], [], 64
    if name == "txs_only":
        return [_tx(rng, s) for s in (50, 700, 30)], [], 64
    if name == "tx_units_on_share_boundaries":
        # unit 1 = 2 + 472 = the first share's 474 content bytes, so unit 2
        # starts on share 1's first content byte; unit 2 = 3 x 478 spans
        # three shares none of which starts a unit; unit 3 starts on share
        # 4's first content byte and ends mid-share
        return [_tx(rng, 472), _tx(rng, 3 * 478 - 2), _tx(rng, 100)], [], 64
    if name == "pfb_reservation_longer_than_written":
        return [], [PfbEntry(b"p%02d" % i, (_blob(rng, 10 + i, 600),))
                    for i in range(28)], 128
    if name == "blob_size_edges":
        sizes = (1, 478, 479, 478 + 482, 478 + 482 + 1, 478 + 482 * 7,
                 478 + 482 * 7 + 1)
        return [b"tx"], [PfbEntry(b"pfb-%d" % i, (_blob(rng, 20 + i, s),))
                          for i, s in enumerate(sizes)], 64
    if name == "equal_namespaces_and_namespace_padding":
        # 70 shares a blob -> subtree width 2: odd starts leave namespace
        # padding; two blobs share namespace 5, across two PFBs
        big = 478 + 482 * 69
        return [_tx(rng, 80)], [
            PfbEntry(b"pfb-a", (_blob(rng, 5, big), _blob(rng, 9, big + 1))),
            PfbEntry(b"pfb-b", (_blob(rng, 5, big - 3), _blob(rng, 7, 90))),
        ], 64
    if name == "build_drops_overflow":
        return [_tx(rng, 40)], [
            PfbEntry(b"big", (_blob(rng, 6, 200 * 478),)),
            PfbEntry(b"small", (_blob(rng, 7, 100), _blob(rng, 3, 900))),
        ], 4
    raise AssertionError(name)


@pytest.mark.parametrize("via", ["build", "construct"])
@pytest.mark.parametrize("name", [
    "empty", "txs_only", "tx_units_on_share_boundaries",
    "pfb_reservation_longer_than_written", "blob_size_edges",
    "equal_namespaces_and_namespace_padding", "build_drops_overflow",
])
def test_square_array_is_the_share_by_share_square(name, via):
    txs, pfbs, max_sq = _identity_case(name)
    if via == "build":
        sq = square_mod.build(txs, pfbs, max_sq, THRESHOLD)
        if name == "build_drops_overflow":
            assert [e.tx for e in sq.pfbs] == [b"small"]
    else:
        if name == "build_drops_overflow":
            with pytest.raises(ValueError):
                square_mod.construct(txs, pfbs, max_sq, THRESHOLD)
            pfbs = pfbs[1:]
        sq = square_mod.construct(txs, pfbs, max_sq, THRESHOLD)
    assert sq.ods.shape == (sq.size, sq.size, 512)
    assert sq.ods.dtype == np.uint8 and sq.ods.flags.c_contiguous
    assert not sq.ods.flags.writeable
    assert sq.ods.tobytes() == _reference_share_bytes(sq.txs, sq.pfbs, max_sq)
    # the branch the case is named for is really taken
    raw = sq.share_bytes()
    namespaces = [r[:29] for r in raw]
    if name == "empty":
        assert sq.size == 1
    if name == "tx_units_on_share_boundaries":
        reserved = [int.from_bytes(r[34:38] if i == 0 else r[30:34], "big")
                    for i, r in enumerate(raw[: sq.tx_shares_len])]
        assert reserved == [38, 34, 0, 0, 34]
    if name == "pfb_reservation_longer_than_written":
        assert sq.pfb_shares_len < sq.pfb_shares_reserved
        assert ns_mod.PRIMARY_RESERVED_PADDING_NAMESPACE.raw in namespaces
    if name == "equal_namespaces_and_namespace_padding":
        padding = [r for r in raw if r[29] == 1 and r[30:34] == bytes(4)
                   and r[:29] != ns_mod.TAIL_PADDING_NAMESPACE.raw]
        assert padding and all(r[28] in (5, 7, 9) for r in padding)


def _share_objects() -> int:
    return telemetry.snapshot()["counters"].get("square.share_objects", 0)


def test_shares_view_is_derived_and_counted():
    """`.shares` / `share_bytes()` are built from the array on every call
    and `square.share_objects` counts what they build."""
    rng = np.random.default_rng(11)
    before = _share_objects()
    sq = square_mod.build([], [PfbEntry(b"p", (_blob(rng, 4, 3000),))],
                          64, THRESHOLD)
    assert _share_objects() == before
    shares = sq.shares
    assert _share_objects() == before + sq.size ** 2
    assert shares is not sq.shares
    assert [s.raw for s in shares] == sq.share_bytes()
    assert _share_objects() == before + 3 * sq.size ** 2
    assert b"".join(sq.share_bytes()) == sq.ods.tobytes()


def test_block_path_builds_no_share_objects(tmp_path):
    """Prepare -> Process -> commit -> a rebuild of the height (light round
    and namespace read on a core the warmer does not seed), 8x8 squares:
    the derived share view is never materialised."""
    from obs_drive import drive

    before = _share_objects()
    out = drive("host", str(tmp_path / "home"), blocks=2)
    assert out["height"] == 2
    names = {r["name"] for r in out["rows"]}
    assert {"square.build", "square.construct",
            "query.rebuild_square"} <= names
    assert _share_objects() == before


def test_construct_leaves_few_tracked_objects():
    """A full k=32 square leaves fewer GC-tracked objects behind than a
    tenth of its shares (one object a share and more, before the square
    was one array): what a layout promotes towards a full collection."""
    import gc

    rng = np.random.default_rng(32)
    pfbs = [PfbEntry(b"pfb-%d" % i,
                     tuple(_blob(rng, 1 + (i + j) % 8, 12_000)
                           for j in range(6)))
            for i in range(6)]
    square_mod.construct([], pfbs, 32, THRESHOLD)  # anything lazy, once
    gc.collect()
    gc.disable()
    try:
        before = len(gc.get_objects())
        sq = square_mod.construct([], pfbs, 32, THRESHOLD)
        left = len(gc.get_objects()) - before
    finally:
        gc.enable()
    assert sq.size == 32
    used = max(sq.blob_start_indexes.values())
    assert used > 0.8 * 32 * 32
    assert left < 32 * 32 // 10, left


def _two_blocks():
    """Two different blocks of one square size (8x8), the second with
    fewer shares than the first so stale rows would show as wrong padding."""
    rng = np.random.default_rng(39)
    first = ([b"tx-a" * 40], [PfbEntry(b"pfb-%d" % i,
                                       (_blob(rng, 2 + i, 6_000),))
                              for i in range(4)])
    second = ([b"tx-b" * 9], [PfbEntry(b"pfb-x", (_blob(rng, 7, 16_000),))])
    return first, second


@pytest.mark.parametrize("first_square", ["kept", "dropped"])
@pytest.mark.parametrize("lay_out", ["build", "construct"])
def test_consecutive_squares_over_the_held_pool(monkeypatch, lay_out,
                                                first_square):
    """With the pool's size constant patched down to an 8x8 square: a
    `Square` somebody holds is never rewritten by the next layout; a dropped
    one hands its array on, and the next square equals the one an unpatched
    run lays out."""
    from celestia_app_tpu.utils import hostbuf

    fn = getattr(square_mod, lay_out)
    (txs1, pfbs1), (txs2, pfbs2) = _two_blocks()
    want1 = fn(txs1, pfbs1, 64, THRESHOLD)
    want2 = fn(txs2, pfbs2, 64, THRESHOLD)
    assert want1.size == want2.size == 8

    monkeypatch.setattr(hostbuf, "HELD_FROM_BYTES", 8 * 8 * 512)
    monkeypatch.setattr(hostbuf, "_held", [])
    c0 = telemetry.snapshot()["counters"]

    def moved(name):
        return telemetry.snapshot()["counters"].get(name, 0) - c0.get(name, 0)

    sq1 = fn(txs1, pfbs1, 64, THRESHOLD)
    assert np.array_equal(sq1.ods, want1.ods)
    at = sq1.ods.ctypes.data
    if first_square == "dropped":
        del sq1
    sq2 = fn(txs2, pfbs2, 64, THRESHOLD)
    assert np.array_equal(sq2.ods, want2.ods)
    assert not sq2.ods.flags.writeable
    if first_square == "kept":
        assert sq2.ods.ctypes.data != at
        assert np.array_equal(sq1.ods, want1.ods)
        assert not sq1.ods.flags.writeable
        with pytest.raises(ValueError):
            sq1.ods[0, 0, 0] = 1
        assert moved("hostbuf.fresh") == 2 and moved("hostbuf.reuses") == 0
    else:
        assert sq2.ods.ctypes.data == at
        assert moved("hostbuf.fresh") == 1 and moved("hostbuf.reuses") == 1
    assert moved("hostbuf.leases") == 2
