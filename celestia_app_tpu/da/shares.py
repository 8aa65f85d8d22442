"""Share codec: the 512-byte atomic units of the data square.

Byte-exact implementation of specs/src/specs/shares.md (reference
implementation: go-square/shares):

  share := namespace(29) || info(1) || [sequence_len(4, BE, first share only)]
           || [reserved(4, BE, compact shares only)] || data || zero-fill
  info  := share_version(7 bits) << 1 | sequence_start(1 bit)

Sparse shares carry blob data (one blob = one sequence). Compact shares carry
the length-delimited (uvarint-prefixed) transactions of a reserved namespace
as a single sequence, with 4 reserved bytes holding the in-share offset of the
first unit that starts in the share (0 if none). Padding shares
(namespace/primary-reserved/tail) have sequence_start=1, sequence_len=0 and a
zero body.

Two definitions of the same bytes live here. The share-by-share functions
(`split_blob`, `split_txs`, the padding constructors) return `Share`
objects: clients, proofs and tests use them, and they are what the array
writers are tested against. The array writers (`write_blob`, `write_txs`,
`padding_row`) put the same bytes straight into rows of a zeroed
``(n, 512)`` uint8 array: da/square lays a whole square out with them, one
handful of numpy calls a sequence and no Python object per share.
"""

from __future__ import annotations

import bisect
import dataclasses

import numpy as np

from celestia_app_tpu import appconsts as c
from celestia_app_tpu.da import namespace as ns_mod
from celestia_app_tpu.da.namespace import Namespace


def uvarint(n: int) -> bytes:
    """Protobuf unsigned varint encoding."""
    if n < 0:
        raise ValueError("uvarint of negative value")
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def read_uvarint(data: bytes, offset: int) -> tuple[int, int]:
    """Decode a uvarint at `offset`; returns (value, next_offset)."""
    result = 0
    shift = 0
    while True:
        if offset >= len(data):
            raise ValueError("truncated uvarint")
        b = data[offset]
        offset += 1
        result |= (b & 0x7F) << shift
        if not (b & 0x80):
            return result, offset
        shift += 7
        if shift > 63:
            raise ValueError("uvarint overflow")


@dataclasses.dataclass(frozen=True)
class Share:
    raw: bytes

    def __post_init__(self):
        if len(self.raw) != c.SHARE_SIZE:
            raise ValueError(f"share must be {c.SHARE_SIZE} bytes, got {len(self.raw)}")

    @property
    def namespace(self) -> Namespace:
        return Namespace(self.raw[: c.NAMESPACE_SIZE])

    @property
    def info_byte(self) -> int:
        return self.raw[c.NAMESPACE_SIZE]

    @property
    def version(self) -> int:
        return self.info_byte >> 1

    @property
    def is_sequence_start(self) -> bool:
        return bool(self.info_byte & 1)

    def sequence_len(self) -> int:
        if not self.is_sequence_start:
            raise ValueError("sequence_len only present on the first share")
        off = c.NAMESPACE_SIZE + c.SHARE_INFO_BYTES
        return int.from_bytes(self.raw[off : off + c.SEQUENCE_LEN_BYTES], "big")

    def is_compact(self) -> bool:
        return self.namespace in (ns_mod.TX_NAMESPACE, ns_mod.PAY_FOR_BLOB_NAMESPACE)

    def is_padding(self) -> bool:
        return self.is_sequence_start and not self.is_compact() and self.sequence_len() == 0

    def content(self) -> bytes:
        """Data region (after header fields; includes any zero fill)."""
        off = c.NAMESPACE_SIZE + c.SHARE_INFO_BYTES
        if self.is_sequence_start:
            off += c.SEQUENCE_LEN_BYTES
        if self.is_compact():
            off += c.SHARE_RESERVED_BYTES
        return self.raw[off:]


def _info_byte(version: int, sequence_start: bool) -> int:
    if version not in c.SUPPORTED_SHARE_VERSIONS:
        raise ValueError(f"unsupported share version {version}")
    return (version << 1) | int(sequence_start)


# ---------------------------------------------------------------------------
# Sparse (blob) shares
# ---------------------------------------------------------------------------


def sparse_shares_needed(blob_len: int) -> int:
    """Number of shares a blob of `blob_len` bytes occupies."""
    if blob_len <= c.FIRST_SPARSE_SHARE_CONTENT_SIZE:
        return 1
    rest = blob_len - c.FIRST_SPARSE_SHARE_CONTENT_SIZE
    return 1 + -(-rest // c.CONTINUATION_SPARSE_SHARE_CONTENT_SIZE)


def sparse_share_headers(ns: Namespace, blob_len: int,
                         share_version: int = 0) -> tuple[bytes, bytes]:
    """What precedes the data in a blob's first share (namespace, info
    byte, sequence length) and in each later one (namespace, info byte)."""
    return (
        ns.raw + bytes([_info_byte(share_version, True)])
        + blob_len.to_bytes(c.SEQUENCE_LEN_BYTES, "big"),
        ns.raw + bytes([_info_byte(share_version, False)]),
    )


def split_blob(ns: Namespace, data: bytes, share_version: int = 0) -> list[Share]:
    """Share-split a blob (shares.md "Share Splitting")."""
    first_header, later_header = sparse_share_headers(
        ns, len(data), share_version)
    shares: list[Share] = []
    first = True
    pos = 0
    while first or pos < len(data):
        if first:
            header = first_header
            take = c.FIRST_SPARSE_SHARE_CONTENT_SIZE
        else:
            header = later_header
            take = c.CONTINUATION_SPARSE_SHARE_CONTENT_SIZE
        chunk = data[pos : pos + take]
        pos += take
        shares.append(Share(header + chunk + b"\x00" * (take - len(chunk))))
        first = False
    return shares


def parse_sparse_shares(shares: list[Share]) -> bytes:
    """Reassemble one blob from its share sequence."""
    if not shares or not shares[0].is_sequence_start:
        raise ValueError("sequence must begin with a start share")
    total = shares[0].sequence_len()
    data = b"".join(s.content() for s in shares)
    if len(data) < total:
        raise ValueError("share sequence shorter than sequence_len")
    return data[:total]


# ---------------------------------------------------------------------------
# Compact (transaction) shares
# ---------------------------------------------------------------------------


def split_txs(ns: Namespace, txs: list[bytes]) -> list[Share]:
    """Encode txs as one compact-share sequence in `ns` (shares.md
    "Transaction Shares"). Each tx is uvarint-length-prefixed; reserved bytes
    point at the in-share offset of the first unit starting in each share."""
    blob = b"".join(uvarint(len(tx)) + tx for tx in txs)
    # Unit start offsets within the concatenated sequence data.
    unit_starts = []
    off = 0
    for tx in txs:
        unit_starts.append(off)
        off += len(uvarint(len(tx))) + len(tx)

    shares: list[Share] = []
    pos = 0
    first = True
    while first or pos < len(blob):
        if first:
            fixed = ns.raw + bytes([_info_byte(0, True)]) + len(blob).to_bytes(4, "big")
            take = c.FIRST_COMPACT_SHARE_CONTENT_SIZE
        else:
            fixed = ns.raw + bytes([_info_byte(0, False)])
            take = c.CONTINUATION_COMPACT_SHARE_CONTENT_SIZE
        content_abs_off = len(fixed) + c.SHARE_RESERVED_BYTES
        # first unit starting in [pos, pos + take), if any
        i = bisect.bisect_left(unit_starts, pos)
        starts_here = i < len(unit_starts) and unit_starts[i] < pos + take
        reserved = (content_abs_off + unit_starts[i] - pos) if starts_here else 0
        chunk = blob[pos : pos + take]
        pos += take
        share = fixed + reserved.to_bytes(4, "big") + chunk + b"\x00" * (take - len(chunk))
        shares.append(Share(share))
        first = False
    return shares


def parse_compact_shares(shares: list[Share]) -> list[bytes]:
    """Decode the uvarint-delimited txs of a compact-share sequence."""
    if not shares:
        return []
    if not shares[0].is_sequence_start:
        raise ValueError("compact sequence must begin with a start share")
    total = shares[0].sequence_len()
    if total == 0:
        return []
    data = b"".join(s.content() for s in shares)[:total]
    txs = []
    off = 0
    while off < len(data):
        length, off = read_uvarint(data, off)
        if off + length > len(data):
            raise ValueError("truncated tx in compact shares")
        txs.append(data[off : off + length])
        off += length
    return txs


# ---------------------------------------------------------------------------
# Padding shares
# ---------------------------------------------------------------------------


def _padding_share(ns: Namespace) -> bytes:
    body = ns.raw + bytes([_info_byte(0, True)]) + (0).to_bytes(4, "big")
    return body + b"\x00" * (c.SHARE_SIZE - len(body))


def namespace_padding_share(ns: Namespace) -> Share:
    return Share(_padding_share(ns))


def reserved_padding_share() -> Share:
    return Share(_padding_share(ns_mod.PRIMARY_RESERVED_PADDING_NAMESPACE))


def tail_padding_share() -> bytes:
    return _padding_share(ns_mod.TAIL_PADDING_NAMESPACE)


def tail_padding_shares(n: int) -> list[Share]:
    return [Share(tail_padding_share()) for _ in range(n)]


# ---------------------------------------------------------------------------
# Array writers: the same bytes, into rows of a zeroed (n, 512) uint8 array
# ---------------------------------------------------------------------------

_INFO_OFF = c.NAMESPACE_SIZE
_SEQ_LEN_OFF = _INFO_OFF + c.SHARE_INFO_BYTES
_FIRST_OFF = _SEQ_LEN_OFF + c.SEQUENCE_LEN_BYTES  # data / reserved, first share
_LATER_OFF = _SEQ_LEN_OFF  # data / reserved, continuation shares


def _fill_rows(rows: np.ndarray, col: int, src: np.ndarray) -> None:
    """Copy `src` into `rows[:, col:]` row after row; the zero fill of the
    last row is the allocation's."""
    width = rows.shape[1] - col
    full = len(src) // width
    if full:
        rows[:full, col:] = src[: full * width].reshape(full, width)
    if len(src) > full * width:
        rows[full, col : col + len(src) - full * width] = src[full * width :]


def write_blob(out: np.ndarray, start: int, ns: Namespace, data: bytes,
               share_version: int = 0) -> int:
    """`split_blob`'s shares written into `out[start:]` (zeroed rows);
    returns how many."""
    first_header, later_header = sparse_share_headers(
        ns, len(data), share_version)
    n = sparse_shares_needed(len(data))
    rows = out[start : start + n]
    rows[:, :_LATER_OFF] = np.frombuffer(later_header, dtype=np.uint8)
    rows[0, :_FIRST_OFF] = np.frombuffer(first_header, dtype=np.uint8)
    src = np.frombuffer(data, dtype=np.uint8)
    head = src[: c.FIRST_SPARSE_SHARE_CONTENT_SIZE]
    rows[0, _FIRST_OFF : _FIRST_OFF + len(head)] = head
    _fill_rows(rows[1:], _LATER_OFF, src[c.FIRST_SPARSE_SHARE_CONTENT_SIZE :])
    return n


def write_txs(out: np.ndarray, start: int, ns: Namespace,
              txs: list[bytes]) -> int:
    """`split_txs`'s shares written into `out[start:]` (zeroed rows);
    returns how many."""
    units = [uvarint(len(tx)) + tx for tx in txs]
    src = np.frombuffer(b"".join(units), dtype=np.uint8)
    first = c.FIRST_COMPACT_SHARE_CONTENT_SIZE
    later = c.CONTINUATION_COMPACT_SHARE_CONTENT_SIZE
    n = 1 + -(-max(len(src) - first, 0) // later)
    rows = out[start : start + n]
    rows[:, :_INFO_OFF] = np.frombuffer(ns.raw, dtype=np.uint8)
    rows[:, _INFO_OFF] = _info_byte(0, False)
    rows[0, _INFO_OFF] = _info_byte(0, True)
    rows[0, _SEQ_LEN_OFF:_FIRST_OFF] = np.frombuffer(
        len(src).to_bytes(c.SEQUENCE_LEN_BYTES, "big"), dtype=np.uint8)
    first_data = _FIRST_OFF + c.SHARE_RESERVED_BYTES
    later_data = _LATER_OFF + c.SHARE_RESERVED_BYTES
    head = src[:first]
    rows[0, first_data : first_data + len(head)] = head
    _fill_rows(rows[1:], later_data, src[first:])
    if units:
        # reserved bytes: in-share offset of the first unit that starts in
        # each share, 0 where none does; unit 0 starts the first share
        unit_starts = np.cumsum([0] + [len(u) for u in units[:-1]])
        ends = first + np.arange(n) * later
        pos = np.concatenate(([0], ends[:-1]))
        i = np.searchsorted(unit_starts, pos)
        u = unit_starts[np.minimum(i, len(unit_starts) - 1)]
        starts_here = (i < len(unit_starts)) & (u < ends)
        reserved = np.where(starts_here, later_data + u - pos, 0)
        reserved[0] = first_data
        as_bytes = reserved.astype(">u4").view(np.uint8).reshape(n, 4)
        rows[0, _FIRST_OFF:first_data] = as_bytes[0]
        rows[1:, _LATER_OFF:later_data] = as_bytes[1:]
    return n


def padding_row(ns: Namespace) -> np.ndarray:
    """One padding share of `ns` as a (512,) row, to assign over a slice."""
    return np.frombuffer(_padding_share(ns), dtype=np.uint8)
