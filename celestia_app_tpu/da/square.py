"""Deterministic data-square layout: the go-square `square.Build`/`Construct`
equivalent (reference call sites: app/prepare_proposal.go:50,
app/process_proposal.go:122, app/extend_block.go:16).

Layout rules implemented (specs/src/specs/data_square_layout.md):
- normal txs -> one compact-share sequence in TRANSACTION_NAMESPACE,
  IndexWrapper-wrapped PFB txs -> one in PAY_FOR_BLOB_NAMESPACE;
- blobs sorted by namespace (stable: ties keep PFB priority order), each
  starting at a multiple of its SubtreeWidth (non-interactive default,
  `next_share_index`), with primary-reserved / namespace / tail padding;
- the square edge k is the smallest power of two fitting all shares
  (alignment is k-independent, so the share count is computed once).

`build` mirrors go-square Build: greedily include txs in priority order,
skipping any that would overflow the max square. `construct` mirrors
Construct: all txs must fit or the whole layout fails (ProcessProposal path).

The square is ONE array: `_export` writes every sequence straight into a
zeroed C-order (k², 512) uint8 buffer (da/shares' array writers) and
`Square.ods` is that buffer as (k, k, 512) — what the cache hashes and the
pipeline extends. No `Share` object exists per share unless a caller asks
for the derived `Square.shares` view.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from celestia_app_tpu import appconsts
from celestia_app_tpu.da import blob as blob_mod
from celestia_app_tpu.da import namespace as ns_mod
from celestia_app_tpu.da import shares as shares_mod
from celestia_app_tpu.da.blob import Blob
from celestia_app_tpu.da.commitment import round_up_pow2, subtree_width
from celestia_app_tpu.da.shares import Share, uvarint
from celestia_app_tpu.utils import hostbuf, telemetry


def next_share_index(cursor: int, blob_share_count: int, subtree_root_threshold: int) -> int:
    """Non-interactive default: first aligned index >= cursor for this blob."""
    width = subtree_width(blob_share_count, subtree_root_threshold)
    return -(-cursor // width) * width


def compact_shares_needed(total_bytes: int) -> int:
    """Shares for a compact sequence of `total_bytes` (incl. varint prefixes)."""
    if total_bytes == 0:
        return 0
    if total_bytes <= appconsts.FIRST_COMPACT_SHARE_CONTENT_SIZE:
        return 1
    rest = total_bytes - appconsts.FIRST_COMPACT_SHARE_CONTENT_SIZE
    return 1 + -(-rest // appconsts.CONTINUATION_COMPACT_SHARE_CONTENT_SIZE)


def _sequence_len(txs: list[bytes]) -> int:
    return sum(len(uvarint(len(t))) + len(t) for t in txs)


@dataclasses.dataclass(frozen=True)
class PfbEntry:
    """A blob tx admitted to layout: the unwrapped signed tx + its blobs."""

    tx: bytes
    blobs: tuple[Blob, ...]


@dataclasses.dataclass
class Square:
    """A built original data square plus the layout metadata proofs need.

    The square IS `ods`: the k*k shares row-major as one read-only C-order
    (k, k, 512) uint8 array, handed as it is to the cache and the pipeline.
    `shares` / `share_bytes()` are a derived view for tests, proofs and
    tools that want one object per share: built anew on every call, never
    kept, and counted in `square.share_objects` (no block path builds it)."""

    size: int  # k
    ods: np.ndarray  # (k, k, 512) uint8: the square
    txs: list[bytes]  # normal txs included
    pfbs: list[PfbEntry]  # blob txs included (priority order)
    # start share index of each blob, parallel to the namespace-sorted order
    blob_start_indexes: dict[tuple[int, int], int]  # (pfb_idx, blob_idx) -> start
    tx_shares_len: int  # shares in TRANSACTION_NAMESPACE
    pfb_shares_len: int  # shares ACTUALLY written in PAY_FOR_BLOB_NAMESPACE
    # shares the layout reserved for the PFB sequence (worst-case index
    # sizing); blobs start after this, the gap is primary-reserved padding
    pfb_shares_reserved: int = 0

    def share_bytes(self) -> list[bytes]:
        flat = self.ods.tobytes()
        size = appconsts.SHARE_SIZE
        telemetry.incr("square.share_objects", self.size * self.size)
        return [flat[i : i + size] for i in range(0, len(flat), size)]

    @property
    def shares(self) -> list[Share]:
        return [Share(raw) for raw in self.share_bytes()]

    def wrapped_pfb_txs(self) -> list[bytes]:
        """IndexWrapper-encoded PFB txs as placed in the square."""
        out = []
        for i, e in enumerate(self.pfbs):
            idxs = [self.blob_start_indexes[(i, j)] for j in range(len(e.blobs))]
            out.append(blob_mod.marshal_index_wrapper(e.tx, idxs))
        return out


class _Layout:
    """One deterministic layout pass over a candidate tx set.

    The PFB compact sequence is RESERVED at its worst-case size (every
    share index priced at the max square's max index,
    `index_wrapper_size_worst_case`) because blob start indexes — hence the
    actual packed-varint index bytes — are only known once the sequence
    length is fixed. go-square breaks the same cycle the same way
    (ADR-020 CompactShareCounter fed with worst-case-marshalled wrappers);
    the export pass writes the real (≤ reserved) wrapper bytes and fills
    the difference with primary-reserved padding shares."""

    def __init__(self, txs: list[bytes], pfbs: list[PfbEntry], threshold: int,
                 max_square_size: int):
        self.txs = txs
        self.pfbs = pfbs
        self.threshold = threshold
        self.max_square_size = max_square_size
        self.wrapped_sizes = [
            blob_mod.index_wrapper_size_worst_case(
                len(e.tx), len(e.blobs), max_square_size
            )
            for e in pfbs
        ]
        self.tx_shares = compact_shares_needed(_sequence_len(txs))
        self.pfb_shares_reserved = compact_shares_needed(
            sum(len(uvarint(s)) + s for s in self.wrapped_sizes)
        )
        # Stable namespace sort preserves PFB priority order within a namespace
        # and blob order within a PFB (data_square_layout.md "Ordering").
        self.ordered = sorted(
            [
                (e.blobs[j].namespace.raw, i, j)
                for i, e in enumerate(pfbs)
                for j in range(len(e.blobs))
            ],
            key=lambda t: (t[0],),
        )
        self.starts: dict[tuple[int, int], int] = {}
        cursor = self.tx_shares + self.pfb_shares_reserved
        self.first_blob_index = None
        worst_blob_shares = 0
        for ns_raw, i, j in self.ordered:
            count = pfbs[i].blobs[j].share_count()
            start = next_share_index(cursor, count, threshold)
            if self.first_blob_index is None:
                self.first_blob_index = start
            self.starts[(i, j)] = start
            cursor = start + count
            width = subtree_width(count, threshold)
            worst_blob_shares += count + width - 1
        self.total = cursor
        # the square size comes from the ESTIMATE (worst-case alignment
        # padding per blob, order-independent), not the exact layout —
        # ADR-020: "from the estimation can formulate the minimum square
        # size". Deterministic on both Prepare and Process sides.
        self.worst_total = (
            self.tx_shares + self.pfb_shares_reserved + worst_blob_shares
        )

    def square_size(self) -> int:
        k = 1
        while k * k < self.worst_total:
            k *= 2
        return k


def _export(layout: _Layout, k: int) -> Square:
    """Write the shares of a computed layout into one (k*k, 512) array."""
    assert layout.total <= k * k, "layout exceeds its square"
    out = hostbuf.lease_zeroed(k * k, appconsts.SHARE_SIZE)
    cursor = 0
    if layout.tx_shares:
        cursor += shares_mod.write_txs(out, 0, ns_mod.TX_NAMESPACE, layout.txs)
    pfb_shares_actual = 0
    if layout.pfb_shares_reserved:
        wrapped = [
            blob_mod.marshal_index_wrapper(
                e.tx,
                [layout.starts[(i, j)] for j in range(len(e.blobs))],
            )
            for i, e in enumerate(layout.pfbs)
        ]
        pfb_shares_actual = shares_mod.write_txs(
            out, cursor, ns_mod.PAY_FOR_BLOB_NAMESPACE, wrapped)
        # real index varints ≤ the reserved worst case; the gap up to the
        # first blob becomes primary-reserved padding below
        assert pfb_shares_actual <= layout.pfb_shares_reserved
        cursor += pfb_shares_actual

    prev_ns: ns_mod.Namespace | None = None
    for ns_raw, i, j in layout.ordered:
        b = layout.pfbs[i].blobs[j]
        start = layout.starts[(i, j)]
        if start > cursor:
            out[cursor:start] = shares_mod.padding_row(
                ns_mod.PRIMARY_RESERVED_PADDING_NAMESPACE
                if prev_ns is None else prev_ns)
        cursor = start + shares_mod.write_blob(
            out, start, b.namespace, b.data, b.share_version)
        prev_ns = b.namespace
    out[cursor:] = shares_mod.padding_row(ns_mod.TAIL_PADDING_NAMESPACE)
    out.flags.writeable = False
    return Square(
        size=k,
        ods=out.reshape(k, k, appconsts.SHARE_SIZE),
        txs=layout.txs,
        pfbs=layout.pfbs,
        blob_start_indexes=layout.starts,
        tx_shares_len=layout.tx_shares,
        pfb_shares_len=pfb_shares_actual,
        pfb_shares_reserved=layout.pfb_shares_reserved,
    )


def construct(
    txs: list[bytes],
    pfbs: list[PfbEntry],
    max_square_size: int,
    subtree_root_threshold: int,
) -> Square:
    """All txs must fit in max_square_size or ValueError (ProcessProposal)."""
    layout = _Layout(txs, pfbs, subtree_root_threshold, max_square_size)
    k = max(layout.square_size(), 1)
    if k > max_square_size:
        raise ValueError(
            f"block does not fit: needs square {k} > max {max_square_size}"
        )
    return _export(layout, k)


def build(
    txs: list[bytes],
    pfbs: list[PfbEntry],
    max_square_size: int,
    subtree_root_threshold: int,
) -> Square:
    """Greedy fill in priority order, dropping txs that overflow (proposer).

    Admission is O(1) per candidate via running counters with WORST-CASE
    padding accounting (each blob costs share_count + width−1: the maximum
    non-interactive-default alignment gap, `next_share_index` math), the
    same pessimistic-append design as go-square's Builder
    (go-square square/builder.go, ref app/prepare_proposal.go:50). Since
    worst-case ≥ exact, every admitted set is guaranteed to fit and the
    single exact layout pass at the end never needs an eviction loop —
    O(n log n) overall (the final sort) instead of the old per-admission
    full relayout (O(n² log n))."""
    cap = max_square_size * max_square_size
    kept_txs: list[bytes] = []
    seq_len = 0
    for t in txs:
        cand_len = seq_len + len(uvarint(len(t))) + len(t)
        if compact_shares_needed(cand_len) <= cap:
            kept_txs.append(t)
            seq_len = cand_len
    tx_shares = compact_shares_needed(seq_len)

    kept_pfbs: list[PfbEntry] = []
    pfb_seq_len = 0
    blob_shares_worst = 0
    for e in pfbs:
        wrapped = blob_mod.index_wrapper_size_worst_case(
            len(e.tx), len(e.blobs), max_square_size
        )
        cand_pfb_len = pfb_seq_len + len(uvarint(wrapped)) + wrapped
        cand_blob_worst = blob_shares_worst
        for b in e.blobs:
            count = b.share_count()
            width = subtree_width(count, subtree_root_threshold)
            cand_blob_worst += count + width - 1
        total_worst = (
            tx_shares + compact_shares_needed(cand_pfb_len) + cand_blob_worst
        )
        if total_worst <= cap:
            kept_pfbs.append(e)
            pfb_seq_len = cand_pfb_len
            blob_shares_worst = cand_blob_worst
    layout = _Layout(kept_txs, kept_pfbs, subtree_root_threshold, max_square_size)
    k = max(layout.square_size(), 1)
    assert k <= max_square_size, "worst-case accounting must over-approximate"
    return _export(layout, k)


def empty_square() -> Square:
    """The k=1 square holding a single tail-padding share."""
    return _export(_Layout([], [], 1, 1), 1)
