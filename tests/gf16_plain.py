"""The 16-bit code's bytes, written out plainly for the tests.

Which bytes of a share make a 16-bit symbol is the chain's codec's choice
(rsmt2d NewLeoRSCodec -> klauspost/reedsolomon WithLeopardGF, above 256
shards an axis): 64-byte blocks, byte i the low and byte i + 32 the high
half of symbol i of its block (`leopard.go` refMulAdd, catid/leopard
`LeopardFF16.cpp`; both from memory, docs/DESIGN.md "Reed-Solomon"). Here
the mapping is index arithmetic a reader can check against that sentence,
one byte at a time; `ops/rs.py`'s two pairs (device bits, host symbols) are
held to it. The symbol-domain encode is `ops/leopard.encode16`, which
`benchmark/reference/plain_da.rs_encode` pins symbol for symbol.
"""

import numpy as np

from celestia_app_tpu.ops import leopard


def symbols(shard: np.ndarray) -> np.ndarray:
    """(D,) uint8 -> (D/2,) uint16 under the published 64-byte block."""
    byte = shard.tolist()
    assert len(byte) % 64 == 0
    return np.array([byte[b + i] | byte[b + 32 + i] << 8
                     for b in range(0, len(byte), 64) for i in range(32)],
                    dtype=np.uint16)


def shard_bytes(symbol_row: np.ndarray) -> np.ndarray:
    """The inverse of `symbols`."""
    sym = symbol_row.tolist()
    out = []
    for b in range(0, len(sym), 32):
        out += [s & 0xFF for s in sym[b:b + 32]]
        out += [s >> 8 for s in sym[b:b + 32]]
    return np.array(out, dtype=np.uint8)


def parity(axis: np.ndarray) -> np.ndarray:
    """(k, D) uint8 data shards -> (k, D) parity, published mapping."""
    coded = leopard.encode16(np.stack([symbols(s) for s in axis]))
    return np.stack([shard_bytes(s) for s in coded])


def parity_adjacent_pairs(axis: np.ndarray) -> np.ndarray:
    """What the program computed before PR 36: symbol p = bytes (2p, 2p+1),
    little-endian. Never the chain's bytes; kept to show the difference."""
    pairs = np.ascontiguousarray(axis).view("<u2")
    return leopard.encode16(pairs).view(np.uint8)


def extend(ods: np.ndarray) -> np.ndarray:
    """(k, k, D) -> (2k, 2k, D): Q1 rows, Q2 columns, Q3 the rows of Q2,
    every axis through `parity`."""
    k = ods.shape[0]
    q1 = np.stack([parity(ods[r]) for r in range(k)])
    q2 = np.stack([parity(ods[:, c]) for c in range(k)], axis=1)
    q3 = np.stack([parity(q2[r]) for r in range(k)])
    return np.concatenate([np.concatenate([ods, q1], axis=1),
                           np.concatenate([q2, q3], axis=1)], axis=0)
