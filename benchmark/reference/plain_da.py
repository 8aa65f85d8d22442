"""The plain reference for the DA block path: numpy + hashlib, nothing of
the program imported.

From the raw BlobTxs of a block, in the block's order, to the data root a
validator must have committed — what ProcessProposal recomputes:

  parse_blob_tx        protobuf BlobTx envelope -> (signed tx, blobs)
  layout / build_ods   celestia-app v3 square layout (go-square builder:
                       worst-case PFB reservation, namespace-sorted blobs at
                       non-interactive default alignment), share splitting
  extend               2D Reed-Solomon, Leopard (Lin-Chung-Han additive FFT
                       over the Cantor basis), run as the FFT itself: over
                       GF(2^8) up to 256 shards an axis (k <= 128), over
                       GF(2^16) beyond, the shard count alone deciding
  axis_roots/data_root namespaced Merkle trees over rows and columns, RFC 6962
                       root over the 4k axis roots
  verify_range         NMT range-proof check (light node's side of a sample)

Sources: celestia-app specs/src/specs/{shares,data_square_layout,namespace}.md,
go-square square/builder.go, celestiaorg/nmt hasher.go + proof.go,
catid/leopard LeopardFF8; beyond 256 shards rsmt2d NewLeoRSCodec ->
klauspost/reedsolomon WithLeopardGF (leopard.go, the 16-bit code) and
catid/leopard LeopardFF16 (kPolynomial 0x1002D, kCantorBasis). FROM MEMORY,
with no vector to check it against offline: which bytes of a shard make a
16-bit symbol (`symbols_of_bytes` / `bytes_of_symbols`, the one place that
says it).
"""

from __future__ import annotations

import functools
import hashlib
import math

import numpy as np

SHARE = 512
NS = 29
FIRST_SPARSE = SHARE - NS - 1 - 4          # 478
CONT_SPARSE = SHARE - NS - 1               # 482
FIRST_COMPACT = SHARE - NS - 1 - 4 - 4     # 474
CONT_COMPACT = SHARE - NS - 1 - 4          # 478
SUBTREE_ROOT_THRESHOLD = 64
SQUARE_SIZE_UPPER_BOUND = 128   # celestia-app v3 pkg/appconsts/v3/app_consts.go

TX_NS = b"\x00" * 28 + b"\x01"
PFB_NS = b"\x00" * 28 + b"\x04"
RESERVED_PADDING_NS = b"\x00" * 28 + b"\xff"
TAIL_PADDING_NS = b"\xff" * 28 + b"\xfe"
PARITY_NS = b"\xff" * 29


def sha256(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


# -- protobuf, as little as the envelopes need ------------------------------


def uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _read_uvarint(buf: bytes, pos: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes) -> list[tuple[int, object]]:
    """[(field number, varint value or bytes)] of one protobuf message."""
    out, pos = [], 0
    while pos < len(buf):
        key, pos = _read_uvarint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _read_uvarint(buf, pos)
        elif wire == 2:
            length, pos = _read_uvarint(buf, pos)
            value = buf[pos:pos + length]
            pos += length
        else:
            raise ValueError(f"unexpected wire type {wire}")
        out.append((number, value))
    return out


def parse_blob_tx(raw: bytes) -> tuple[bytes, list[tuple[bytes, bytes]]]:
    """BlobTx{1: tx, 2: repeated Blob{1: ns id, 2: data, 3: share version,
    4: ns version}, 3: "BLOB"} -> (tx, [(namespace29, data)])."""
    tx, blobs, type_id = b"", [], b""
    for number, value in _fields(raw):
        if number == 1:
            tx = value
        elif number == 2:
            ns_id, data, ns_version = b"", b"", 0
            for n2, v2 in _fields(value):
                if n2 == 1:
                    ns_id = v2
                elif n2 == 2:
                    data = v2
                elif n2 == 3 and v2 != 0:
                    raise ValueError("share version 0 only")
                elif n2 == 4:
                    ns_version = v2
            blobs.append((bytes([ns_version]) + ns_id, data))
        elif number == 3:
            type_id = value
    if type_id != b"BLOB" or not blobs:
        raise ValueError("not a BlobTx")
    return tx, blobs


def _field_bytes(number: int, value: bytes) -> bytes:
    return uvarint(number << 3 | 2) + uvarint(len(value)) + value


def index_wrapper(tx: bytes, share_indexes: list[int]) -> bytes:
    """IndexWrapper{1: tx, 2: packed uint32 share_indexes, 3: "INDX"}."""
    packed = b"".join(uvarint(i) for i in share_indexes)
    return (_field_bytes(1, tx) + _field_bytes(2, packed)
            + _field_bytes(3, b"INDX"))


def index_wrapper_worst_size(tx_len: int, n_blobs: int, max_k: int) -> int:
    idx = n_blobs * len(uvarint(max_k * max_k))
    return (1 + len(uvarint(tx_len)) + tx_len
            + 1 + len(uvarint(idx)) + idx + 1 + 1 + 4)


# -- shares -----------------------------------------------------------------


def sparse_share_count(n_bytes: int) -> int:
    if n_bytes <= FIRST_SPARSE:
        return 1
    return 1 + -(-(n_bytes - FIRST_SPARSE) // CONT_SPARSE)


def blob_bytes_for_shares(n_shares: int) -> int:
    """The most bytes a blob of exactly n_shares shares holds."""
    return FIRST_SPARSE + (n_shares - 1) * CONT_SPARSE


def split_blob(ns: bytes, data: bytes) -> list[bytes]:
    out = [ns + b"\x01" + len(data).to_bytes(4, "big")
           + data[:FIRST_SPARSE].ljust(FIRST_SPARSE, b"\x00")]
    for pos in range(FIRST_SPARSE, len(data), CONT_SPARSE):
        out.append(ns + b"\x00"
                   + data[pos:pos + CONT_SPARSE].ljust(CONT_SPARSE, b"\x00"))
    return out


def compact_share_count(n_bytes: int) -> int:
    if n_bytes == 0:
        return 0
    if n_bytes <= FIRST_COMPACT:
        return 1
    return 1 + -(-(n_bytes - FIRST_COMPACT) // CONT_COMPACT)


def split_compact(ns: bytes, units: list[bytes]) -> list[bytes]:
    """One compact sequence: units length-prefixed, each share's reserved
    bytes pointing at the first unit that starts in it."""
    seq = b"".join(uvarint(len(u)) + u for u in units)
    starts, off = [], 0
    for u in units:
        starts.append(off)
        off += len(uvarint(len(u))) + len(u)
    out, pos, nxt = [], 0, 0
    while pos < len(seq) or not out:
        first = not out
        fixed = ns + (b"\x01" + len(seq).to_bytes(4, "big") if first
                      else b"\x00")
        take = FIRST_COMPACT if first else CONT_COMPACT
        while nxt < len(starts) and starts[nxt] < pos:
            nxt += 1
        reserved = 0
        if nxt < len(starts) and starts[nxt] < pos + take:
            reserved = len(fixed) + 4 + starts[nxt] - pos
        out.append(fixed + reserved.to_bytes(4, "big")
                   + seq[pos:pos + take].ljust(take, b"\x00"))
        pos += take
    return out


def padding_share(ns: bytes) -> bytes:
    return (ns + b"\x01" + b"\x00" * 4).ljust(SHARE, b"\x00")


# -- layout -----------------------------------------------------------------


def _pow2_at_least(n: int) -> int:
    k = 1
    while k < n:
        k *= 2
    return k


def subtree_width(n_shares: int) -> int:
    by_threshold = _pow2_at_least(-(-n_shares // SUBTREE_ROOT_THRESHOLD))
    min_square = _pow2_at_least(math.isqrt(n_shares - 1) + 1
                                if n_shares > 1 else 1)
    return min(by_threshold, min_square)


def build_ods(raw_blob_txs: list[bytes], max_k: int) -> np.ndarray:
    """The (k, k, 512) original data square of a block of BlobTxs. Raises
    ValueError if the txs do not fit max_k (a proposer would have dropped
    one; the traffic is sized so that none is)."""
    parsed = [parse_blob_tx(r) for r in raw_blob_txs]
    reserved = compact_share_count(sum(
        len(uvarint(s)) + s for s in (
            index_wrapper_worst_size(len(tx), len(blobs), max_k)
            for tx, blobs in parsed)))
    order = sorted(((ns, i, j) for i, (_tx, blobs) in enumerate(parsed)
                    for j, (ns, _d) in enumerate(blobs)),
                   key=lambda t: t[0])
    cursor, worst, starts = reserved, reserved, {}
    for ns, i, j in order:
        count = sparse_share_count(len(parsed[i][1][j][1]))
        width = subtree_width(count)
        start = -(-cursor // width) * width
        starts[(i, j)] = start
        cursor = start + count
        worst += count + width - 1
    k = 1
    while k * k < worst:
        k *= 2
    if k > max_k:
        raise ValueError(f"block needs a square of {k} > {max_k}")
    shares = split_compact(PFB_NS, [
        index_wrapper(tx, [starts[(i, j)] for j in range(len(blobs))])
        for i, (tx, blobs) in enumerate(parsed)]) if parsed else []
    prev_ns = RESERVED_PADDING_NS
    for ns, i, j in order:
        shares += [padding_share(prev_ns)] * (starts[(i, j)] - len(shares))
        shares += split_blob(ns, parsed[i][1][j][1])
        prev_ns = ns
    shares += [padding_share(TAIL_PADDING_NS)] * (k * k - len(shares))
    return np.frombuffer(b"".join(shares), dtype=np.uint8).reshape(
        k, k, SHARE)


def namespace_shares(ods: np.ndarray, ns: bytes) -> list[bytes]:
    """Every share of the square under `ns`, row-major: what a complete
    namespace read returns (namespace padding shares included)."""
    flat = ods.reshape(-1, SHARE)
    hit = np.all(flat[:, :NS] == np.frombuffer(ns, dtype=np.uint8), axis=1)
    return [flat[i].tobytes() for i in np.flatnonzero(hit)]


# -- Leopard: GF(2^8) up to 256 shards an axis, GF(2^16) beyond --------------

# bits of a symbol -> (field polynomial, Cantor basis: beta_0 = 1,
# beta_{i+1}^2 + beta_{i+1} = beta_i)
_FIELDS = {
    8: (0x11D, (1, 214, 152, 146, 86, 200, 88, 230)),
    16: (0x1002D, (0x0001, 0xACCA, 0x3C0E, 0x163E, 0xC582, 0xED2E, 0x914C,
                   0x4012, 0x6C98, 0x10D8, 0x6A72, 0xB900, 0xFDB8, 0xFB34,
                   0xFF38, 0x991E)),
}
_SYMBOL = {8: np.uint8, 16: np.uint16}


def field_bits(k: int) -> int:
    """The field of an axis of k data + k recovery shards, chosen as the
    chain's codec chooses it (rsmt2d NewLeoRSCodec, klauspost/reedsolomon
    WithLeopardGF): 8-bit symbols up to 256 shards, 16-bit beyond. The shard
    count alone decides."""
    return 8 if 2 * k <= 256 else 16


@functools.lru_cache(maxsize=None)
def _log_exp(bits: int) -> tuple[np.ndarray, np.ndarray]:
    """(log, exp) of the field on labels: the polynomial-basis field
    conjugated by the Cantor change of basis (label bit b <-> beta_b), so
    that the product of labels a, b != 0 is exp[(log[a] + log[b]) % (2^bits
    - 1)]. log[0] is meaningless."""
    poly, basis = _FIELDS[bits]
    order = 1 << bits
    log = np.zeros(order, dtype=np.int64)
    state = 1
    for i in range(order - 1):
        log[state] = i
        state <<= 1
        if state & order:
            state ^= poly
    cantor = np.zeros(order, dtype=np.int64)
    for b in range(bits):
        cantor[1 << b:2 << b] = cantor[:1 << b] ^ basis[b]
    label_log = log[cantor]
    exp = np.zeros(order - 1, dtype=np.int64)
    exp[label_log[1:]] = np.arange(1, order)
    return label_log, exp


def gf_mul(bits: int, a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    log, exp = _log_exp(bits)
    return int(exp[(log[a] + log[b]) % (len(log) - 1)])


def gf_inv(bits: int, a: int) -> int:
    log, exp = _log_exp(bits)
    return int(exp[-log[a] % (len(log) - 1)])


def _times(bits: int, w: int) -> np.ndarray:
    """The products of w != 0 with every label, as a lookup table."""
    log, exp = _log_exp(bits)
    table = exp[(log[w] + log) % (len(log) - 1)]
    table[0] = 0
    return table.astype(_SYMBOL[bits])


@functools.lru_cache(maxsize=None)
def _skews(bits: int) -> np.ndarray:
    """S[d, b] = s_d(2^b) / s_d(2^d) for b >= d, s_d the polynomial that
    vanishes on labels 0..2^d-1: s_0(x) = x, s_{d+1}(x) = s_d(x) * s_d(x ^
    2^d). Linearized, so s_d at any label is the XOR over the label's set
    bits, and the recursion needs its values at the labels 2^b alone."""
    out = np.zeros((bits, bits), dtype=np.int64)
    at = [1 << b for b in range(bits)]              # s_0(2^b)
    for d in range(bits):
        inv = gf_inv(bits, at[d])
        for b in range(d, bits):
            out[d, b] = gf_mul(bits, at[b], inv)
        at = [gf_mul(bits, v, v ^ at[d]) for v in at]
    return out


def _skew(bits: int, d: int, gamma: int) -> int:
    acc, b, g = 0, d, gamma >> d
    while g:
        if g & 1:
            acc ^= int(_skews(bits)[d, b])
        g >>= 1
        b += 1
    return acc


def rs_encode(data: np.ndarray) -> np.ndarray:
    """(k, ...) data shards -> (k, ...) recovery shards, symbol for symbol:
    the data are a polynomial's values at labels [k, 2k); recovery its values
    at [0, k). Symbols are uint8 over GF(2^8) or uint16 over GF(2^16), as
    `field_bits(k)` says; shares' bytes become 16-bit symbols through
    `symbols_of_bytes`."""
    k = data.shape[0]
    bits = field_bits(k)
    if data.dtype != _SYMBOL[bits]:
        raise TypeError(f"{2 * k} shards an axis take {bits}-bit symbols, "
                        f"not {data.dtype}")
    if k == 1:
        return data.copy()
    buf = np.array(data, order="C")     # a copy, and rows of it contiguous
    for d in range(k.bit_length() - 1):             # IFFT at offset k
        half = 1 << d
        for j in range(0, k, 2 * half):
            x, y = buf[j:j + half], buf[j + half:j + 2 * half]
            y ^= x
            w = _skew(bits, d, k + j)
            if w:
                x ^= _times(bits, w)[y]
    for d in range(k.bit_length() - 2, -1, -1):     # FFT at offset 0
        half = 1 << d
        for j in range(0, k, 2 * half):
            x, y = buf[j:j + half], buf[j + half:j + 2 * half]
            w = _skew(bits, d, j)
            if w:
                x ^= _times(bits, w)[y]
            y ^= x
    return buf


# Which bytes of a shard make a 16-bit symbol: klauspost/reedsolomon
# leopard.go (refMulAdd) and catid/leopard LeopardFF16.cpp work on 64-byte
# blocks in which byte i is the low and byte i + 32 the high half of symbol i.
# FROM MEMORY: no copy of either source and no test vector is on this machine.
_BLOCK = 64


def symbols_of_bytes(shards: np.ndarray) -> np.ndarray:
    """(..., n) uint8, n a multiple of 64 -> (..., n / 2) uint16."""
    blocks = shards.reshape(*shards.shape[:-1], -1, 2, _BLOCK // 2)
    low, high = blocks[..., 0, :], blocks[..., 1, :]
    return (low | high.astype(np.uint16) << 8).reshape(
        *shards.shape[:-1], -1)


def bytes_of_symbols(symbols: np.ndarray) -> np.ndarray:
    """The inverse of `symbols_of_bytes`."""
    blocks = symbols.reshape(*symbols.shape[:-1], -1, 1, _BLOCK // 2)
    halves = np.concatenate([blocks & 0xFF, blocks >> 8], axis=-2)
    return halves.astype(np.uint8).reshape(*symbols.shape[:-1], -1)


def extend(ods: np.ndarray) -> np.ndarray:
    """(k, k, 512) -> (2k, 2k, 512): Q1 extends rows, Q2 columns, Q3 the
    rows of Q2."""
    k = ods.shape[0]
    to_symbols, to_bytes = ((symbols_of_bytes, bytes_of_symbols)
                            if field_bits(k) == 16 else (np.asarray,) * 2)

    def by_rows(q: np.ndarray) -> np.ndarray:
        return to_bytes(rs_encode(q.transpose(1, 0, 2)).transpose(1, 0, 2))

    q0 = to_symbols(ods)
    q2 = rs_encode(q0)
    eds = np.zeros((2 * k, 2 * k, SHARE), dtype=np.uint8)
    eds[:k, :k] = ods
    eds[:k, k:] = by_rows(q0)
    eds[k:, :k] = to_bytes(q2)
    eds[k:, k:] = by_rows(q2)
    return eds


# -- namespaced Merkle tree -------------------------------------------------

Node = tuple[bytes, bytes, bytes]  # (min namespace, max namespace, digest)


def nmt_leaf(ns: bytes, share: bytes) -> Node:
    return ns, ns, sha256(b"\x00" + ns + share)


def nmt_inner(left: Node, right: Node) -> Node:
    if left[0] == PARITY_NS:
        hi = PARITY_NS
    elif right[0] == PARITY_NS:
        hi = left[1]                      # IgnoreMaxNamespace
    else:
        hi = max(left[1], right[1])
    return (min(left[0], right[0]), hi,
            sha256(b"\x01" + b"".join(left) + b"".join(right)))


def _split(n: int) -> int:
    k = 1
    while k * 2 < n:
        k *= 2
    return k


def nmt_root(leaves: list[Node]) -> Node:
    if len(leaves) == 1:
        return leaves[0]
    k = _split(len(leaves))
    return nmt_inner(nmt_root(leaves[:k]), nmt_root(leaves[k:]))


def axis_roots(eds: np.ndarray) -> tuple[list[bytes], list[bytes]]:
    """The 2k row roots and 2k column roots, 90 bytes each. A leaf's
    namespace is the share's own in Q0 and the parity namespace elsewhere."""
    width = eds.shape[0]
    k = width // 2
    raw = eds.tobytes()

    def leaf(r: int, c: int) -> Node:
        share = raw[(r * width + c) * SHARE:(r * width + c + 1) * SHARE]
        return nmt_leaf(share[:NS] if r < k and c < k else PARITY_NS, share)

    grid = [[leaf(r, c) for c in range(width)] for r in range(width)]
    rows = [b"".join(nmt_root(grid[r])) for r in range(width)]
    cols = [b"".join(nmt_root([grid[r][c] for r in range(width)]))
            for c in range(width)]
    return rows, cols


def merkle_root(leaves: list[bytes]) -> bytes:
    """RFC 6962."""
    if len(leaves) == 1:
        return sha256(b"\x00" + leaves[0])
    k = _split(len(leaves))
    return sha256(b"\x01" + merkle_root(leaves[:k]) + merkle_root(leaves[k:]))


def data_root(rows: list[bytes], cols: list[bytes]) -> bytes:
    return merkle_root(rows + cols)


def verify_range(root: bytes, start: int, end: int, total: int,
                 leaves: list[Node], nodes: list[bytes]) -> bool:
    """Whether `leaves` at [start, end) of a tree of `total` leaves, with the
    out-of-range subtree roots `nodes` left to right, hash to `root`."""
    if not (0 <= start < end <= total) or len(leaves) != end - start:
        return False
    rest = list(nodes)

    def walk(lo: int, hi: int) -> Node:
        if hi <= start or lo >= end:
            raw = rest.pop(0)
            return raw[:NS], raw[NS:2 * NS], raw[2 * NS:]
        if hi - lo == 1:
            return leaves[lo - start]
        mid = lo + _split(hi - lo)
        left = walk(lo, mid)
        return nmt_inner(left, walk(mid, hi))

    try:
        got = walk(0, total)
    except IndexError:
        return False
    return not rest and b"".join(got) == root


def commit_block(raw_blob_txs: list[bytes], max_k: int) -> dict:
    """What a validator must have committed for this block's txs."""
    ods = build_ods(raw_blob_txs, max_k)
    eds = extend(ods)
    rows, cols = axis_roots(eds)
    return {"square_size": ods.shape[0], "eds": eds, "row_roots": rows,
            "col_roots": cols, "data_root": data_root(rows, cols)}
