"""The signature check of a tx, in plain integer arithmetic.

What a validator must answer for a tx's signature, computed with Python
integers and hashlib alone: the SIGN_MODE_DIRECT sign-doc rebuilt from the
raw tx bytes (`plain_da`'s protobuf reader), secp256k1 ECDSA verification by
the textbook (Jacobian double-and-add from the top bit, one multiplication
for each of u1*G and u2*Q, no windows, no endomorphism, no tables), and the
two policy checks cosmos-sdk applies before any curve arithmetic: a signature
is exactly 64 bytes (r || s, big-endian) and s is in the lower half of the
group order. Nothing of the program is imported here.

Sources: SEC 2 v2 section 2.4.1 (the curve's parameters), SEC 1 v2 section
4.1.4 (verification), cosmos-sdk crypto/keys/secp256k1 (64-byte r || s,
low-S), cosmos.tx.v1beta1 TxRaw / SignDoc / AuthInfo / SignerInfo.
"""

from __future__ import annotations

import hashlib

from reference import plain_da as da

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

INFINITY = (0, 1, 0)          # Jacobian (X, Y, Z) with Z = 0


def _double(p):
    x, y, z = p
    if z == 0 or y == 0:
        return INFINITY
    yy = y * y % P
    s = 4 * x * yy % P
    m = 3 * x * x % P                     # a = 0
    x3 = (m * m - 2 * s) % P
    return x3, (m * (s - x3) - 8 * yy * yy) % P, 2 * y * z % P


def _add(p, q):
    x1, y1, z1 = p
    x2, y2, z2 = q
    if z1 == 0:
        return q
    if z2 == 0:
        return p
    z1z1, z2z2 = z1 * z1 % P, z2 * z2 % P
    u1, u2 = x1 * z2z2 % P, x2 * z1z1 % P
    s1, s2 = y1 * z2 * z2z2 % P, y2 * z1 * z1z1 % P
    if u1 == u2:
        return _double(p) if s1 == s2 else INFINITY
    h, r = (u2 - u1) % P, (s2 - s1) % P
    hh = h * h % P
    hhh, v = h * hh % P, u1 * hh % P
    x3 = (r * r - hhh - 2 * v) % P
    return x3, (r * (v - x3) - s1 * hhh) % P, h * z1 * z2 % P


def _multiply(k: int, point):
    acc = INFINITY
    for bit in bin(k)[2:] if k else "":
        acc = _double(acc)
        if bit == "1":
            acc = _add(acc, point)
    return acc


def decompress(pubkey33: bytes):
    """The affine point of a 33-byte compressed key, or None."""
    if len(pubkey33) != 33 or pubkey33[0] not in (2, 3):
        return None
    x = int.from_bytes(pubkey33[1:], "big")
    if x >= P:
        return None
    yy = (x * x * x + 7) % P
    y = pow(yy, (P + 1) // 4, P)          # P = 3 (mod 4)
    if y * y % P != yy:
        return None
    if y & 1 != pubkey33[0] & 1:
        y = P - y
    return x, y


def ecdsa_verify(pubkey33: bytes, signature: bytes, message: bytes) -> bool:
    """SEC 1 section 4.1.4 over sha256(message): r and s are the two
    32-byte halves of what the first 64 bytes of `signature` give (a
    shorter one reads short halves), both in [1, n-1]. No policy."""
    q = decompress(pubkey33)
    if q is None:
        return False
    r = int.from_bytes(signature[:32], "big")
    s = int.from_bytes(signature[32:], "big")
    if not (1 <= r < N and 1 <= s < N):
        return False
    z = int.from_bytes(hashlib.sha256(message).digest(), "big") % N
    w = pow(s, -1, N)
    x, _y, zz = _add(_multiply(z * w % N, (GX, GY, 1)),
                     _multiply(r * w % N, (q[0], q[1], 1)))
    if zz == 0:
        return False
    inv = pow(zz, -1, P)
    return (x * inv * inv % P) % N == r


def verify(pubkey33: bytes, signature: bytes, message: bytes) -> bool:
    """What the ante answers: the policy first (64 bytes, low-S), then
    the curve."""
    if len(signature) != 64:
        return False
    if int.from_bytes(signature[32:], "big") > N // 2:
        return False
    return ecdsa_verify(pubkey33, signature, message)


# -- the sign-doc, from the raw tx ------------------------------------------


def _first(buf: bytes, number: int, default=b""):
    for n, value in da._fields(buf):
        if n == number:
            return value
    return default


def parse_tx(raw: bytes) -> tuple[bytes, bytes, bytes, bytes]:
    """(body bytes, auth-info bytes, signature, signer's 33-byte key) of a
    raw tx as broadcast: a BlobTx envelope's inner tx, or a bare TxRaw
    {1: body, 2: auth info, 3: repeated signature}. The key is
    AuthInfo{1: SignerInfo{1: Any{2: PubKey{1: key}}}} of the first signer."""
    try:
        tx = da.parse_blob_tx(raw)[0]
    except ValueError:
        tx = raw
    body, auth = _first(tx, 1), _first(tx, 2)
    signature = _first(tx, 3)
    key = _first(_first(_first(_first(auth, 1), 1), 2), 1)
    return bytes(body), bytes(auth), bytes(signature), bytes(key)


def _field(number: int, value: bytes) -> bytes:
    return da._field_bytes(number, value) if value else b""


def sign_doc(body: bytes, auth_info: bytes, chain_id: str,
             account_number: int) -> bytes:
    """SignDoc{1: body bytes, 2: auth-info bytes, 3: chain id, 4: account
    number}, proto3: a field at its default is left out."""
    number = (da.uvarint(4 << 3) + da.uvarint(account_number)
              if account_number else b"")
    return (_field(1, body) + _field(2, auth_info)
            + _field(3, chain_id.encode()) + number)


def verify_tx(raw: bytes, chain_id: str, account_number_of) -> bool:
    """Does the raw tx carry its signer's valid signature over its own
    sign-doc on this chain? `account_number_of(key33)` gives the signer's
    account number, or None for a signer the chain does not know."""
    try:
        body, auth, signature, key = parse_tx(raw)
    except (ValueError, IndexError):
        return False
    number = account_number_of(key)
    if number is None:
        return False
    return verify(key, signature, sign_doc(body, auth, chain_id, number))
