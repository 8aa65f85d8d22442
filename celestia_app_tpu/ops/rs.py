"""Device-side 2D Reed-Solomon extension of the data square.

TPU-native formulation of what the reference does with
`rsmt2d.ComputeExtendedDataSquare` (pkg/da/data_availability_header.go:65-75):

    Q1 = RS-extend each row of Q0
    Q2 = RS-extend each column of Q0
    Q3 = RS-extend each row of Q2
    (specs/src/specs/data_structures.md "2D Reed-Solomon Encoding Scheme")

Instead of per-row scalar GF loops, each pass is ONE bit-matrix matmul on the
MXU: bytes are unpacked to bits (LSB-first), parity_bits = (B @ data_bits) & 1
with B = leopard.bit_matrix(k) of shape (8k, 8k) — the Leopard-RS
construction the reference uses (rsmt2d.NewLeoRSCodec) collapsed to a GF(2)
matrix — batched over all k rows / columns at once. Codeword bit-compat for
varied data is argued structurally (see ops/leopard.py "residual risk": the
FFT-output-to-parity ordering and no-bit-reversal conventions are pinned by
construction and by the independent C++ reimplementation + round-trip
decoder, not yet by an external rsmt2d-generated vector). For k=128 that is 3 matmuls of (1024,1024)x(1024,512) per
batch of 128 — ~0.4 TFLOP total, well inside a v5e chip's budget.

All functions are shape-static per power-of-two k bucket and cached per k.
"""

from __future__ import annotations

import collections
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from celestia_app_tpu import appconsts
from celestia_app_tpu.ops import leopard, pow2_bucket

SHARE = appconsts.SHARE_SIZE


def bytes_to_bits(x: jax.Array) -> jax.Array:
    """(..., n, S) uint8 -> (..., 8n, S) int8 bits, LSB-first within each byte."""
    n = x.shape[-2]
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., :, None, :] >> shifts[None, :, None]) & 1
    return bits.reshape(*x.shape[:-2], 8 * n, x.shape[-1]).astype(jnp.int8)


def bits_to_bytes(b: jax.Array) -> jax.Array:
    """(..., 8n, S) int bits -> (..., n, S) uint8, LSB-first within each byte."""
    n = b.shape[-2] // 8
    b = b.reshape(*b.shape[:-2], n, 8, b.shape[-1]).astype(jnp.int32)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))[None, :, None]
    return jnp.sum(b * weights, axis=-2).astype(jnp.uint8)


def _gf_mix(bit_mat: jax.Array, x_bits: jax.Array) -> jax.Array:
    """(8k,8k) x (..., 8k, S) -> (..., 8k, S), all arithmetic mod 2 via int matmul."""
    out = jnp.einsum(
        "pq,...qs->...ps", bit_mat, x_bits, preferred_element_type=jnp.int32
    )
    return (out & 1).astype(jnp.int8)


# Which bytes of a share make a 16-bit symbol. The chain's codec above 256
# shards an axis is rsmt2d NewLeoRSCodec -> klauspost/reedsolomon
# WithLeopardGF, whose GF(2^16) code works on 64-byte blocks: byte i is the
# low and byte i + 32 the high half of symbol i of its block
# (klauspost/reedsolomon leopard.go refMulAdd: `loA := y[:32]; hiA :=
# y[32:64]`; catid/leopard LeopardFF16.cpp: `lo = x[i]`, `hi = x[i + 32]`).
# Both sources are quoted from memory: no copy of either and no vector made
# by them is on this machine (docs/DESIGN.md "Reed-Solomon"). The two pairs
# below — device bits and host symbols — are the only places that know it.
SYMBOL_BLOCK = 64
_HALF = SYMBOL_BLOCK // 2


def _blocks(d: int) -> int:
    if d % SYMBOL_BLOCK:
        raise ValueError(
            f"a shard of {d} bytes is not whole {SYMBOL_BLOCK}-byte blocks")
    return d // SYMBOL_BLOCK


def bytes_to_bits16(x: jax.Array) -> jax.Array:
    """(..., n, D) uint8 -> (..., 16n, D//2) int8 bits of uint16 symbols.

    Symbol p = 32b + i of a shard is bytes 64b + i (low) and 64b + 32 + i
    (high); symbol-bit j is bit j%8 of the low (j < 8) or high byte. Row
    16l + j = bit j of shard l's symbols."""
    lead, n, d = x.shape[:-2], x.shape[-2], x.shape[-1]
    blk = x.reshape(*lead, n, _blocks(d), 2, _HALF)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (blk[..., None] >> shifts) & 1  # (..., n, block, half, i, bit)
    at = len(lead)
    bits = bits.transpose(*range(at), at, at + 2, at + 4, at + 1, at + 3)
    # (..., n, half, bit, block, i): (half, bit) is the symbol-bit, low
    # byte first, and (block, i) the symbol's place in the shard
    return bits.reshape(*lead, 16 * n, d // 2).astype(jnp.int8)


def bits_to_bytes16(b: jax.Array) -> jax.Array:
    """Inverse of bytes_to_bits16: (..., 16n, D//2) -> (..., n, D) uint8."""
    lead, n, half_d = b.shape[:-2], b.shape[-2] // 16, b.shape[-1]
    n_blocks = _blocks(2 * half_d)
    bits = b.reshape(*lead, n, 2, 8, n_blocks, _HALF).astype(jnp.int32)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))[:, None, None]
    by = jnp.sum(bits * weights, axis=-3).astype(jnp.uint8)
    # (..., n, half, block, i) -> (..., n, block, half, i)
    return jnp.swapaxes(by, -3, -2).reshape(*lead, n, 2 * half_d)


def symbols_of_bytes(shards: np.ndarray) -> np.ndarray:
    """Host twin of bytes_to_bits16: (..., D) uint8 -> (..., D//2) uint16."""
    shards = np.asarray(shards)
    lead, d = shards.shape[:-1], shards.shape[-1]
    blk = shards.reshape(*lead, _blocks(d), 2, _HALF)
    sym = blk[..., 0, :] | (blk[..., 1, :].astype(np.uint16) << 8)
    return sym.reshape(*lead, d // 2)


def bytes_of_symbols(symbols: np.ndarray) -> np.ndarray:
    """Inverse of symbols_of_bytes: (..., D//2) uint16 -> (..., D) uint8."""
    symbols = np.asarray(symbols)
    lead, half_d = symbols.shape[:-1], symbols.shape[-1]
    blk = symbols.reshape(*lead, _blocks(2 * half_d), 1, _HALF)
    halves = np.concatenate([blk & 0xFF, blk >> 8], axis=-2)
    return halves.astype(np.uint8).reshape(*lead, 2 * half_d)


def _codec(k: int):
    """(bit_matrix, to_bits, from_bits) for the field."""
    if leopard.uses_gf16(k):
        return leopard.bit_matrix16(k), bytes_to_bits16, bits_to_bytes16
    return leopard.bit_matrix(k), bytes_to_bits, bits_to_bytes


def extend_square_fn(k: int):
    """Return a jittable fn: (k, k, 512) uint8 ODS -> (2k, 2k, 512) uint8 EDS.

    k <= 128 uses the GF(2^8) code; k >= 256 the GF(2^16) code (leopard16),
    both as one bit-matrix MXU matmul per pass: an int8 einsum batched
    over the k rows (or columns), accumulated in int32."""
    mat, to_bits, from_bits = _codec(k)
    bit_mat = jnp.asarray(mat)  # constant folded into the jaxpr

    def extend(ods: jax.Array) -> jax.Array:
        assert ods.shape == (k, k, SHARE), ods.shape
        # Row pass: mix across the share index within each row.
        q1 = from_bits(_gf_mix(bit_mat, to_bits(ods)))  # (k, k, S)
        # Column pass: transpose so columns become the mixing axis.
        col_bits = _gf_mix(bit_mat, to_bits(jnp.swapaxes(ods, 0, 1)))
        q2 = jnp.swapaxes(from_bits(col_bits), 0, 1)  # (k parity rows, k cols, S)
        # Q3 = row-extend Q2 (== column-extend Q1,
        # data_structures.md:304-310)
        q3 = from_bits(_gf_mix(bit_mat, to_bits(q2)))
        top = jnp.concatenate([ods, q1], axis=1)
        bottom = jnp.concatenate([q2, q3], axis=1)
        return jnp.concatenate([top, bottom], axis=0)

    return extend


@functools.lru_cache(maxsize=None)
def jitted_extend(k: int):
    return jax.jit(extend_square_fn(k))


# ---------------------------------------------------------------------------
# Host-side reference + repair (numpy byte-domain; used by tests and the
# light-node reconstruction path — the "any 50% recovers all" MDS property).
# ---------------------------------------------------------------------------


def _encode_axis_np(block: np.ndarray) -> np.ndarray:
    """(k, D) data shards -> (k, D) parity, byte domain, codec by k."""
    k = block.shape[0]
    if leopard.uses_gf16(k):
        return bytes_of_symbols(leopard.encode16(symbols_of_bytes(block)))
    return leopard.encode(block)


def extend_square_np(ods: np.ndarray) -> np.ndarray:
    """Byte-domain numpy reference of the same extension (FFT-based encode,
    quasilinear: fast enough for k=256 host tests)."""
    k = ods.shape[0]
    assert ods.shape == (k, k, SHARE)
    q1 = np.stack([_encode_axis_np(ods[r]) for r in range(k)])  # rows
    q2 = np.stack(
        [_encode_axis_np(ods[:, c, :]) for c in range(k)], axis=1
    )  # columns
    q3 = np.stack([_encode_axis_np(q2[r]) for r in range(k)])
    top = np.concatenate([ods, q1], axis=1)
    bottom = np.concatenate([q2, q3], axis=1)
    return np.concatenate([top, bottom], axis=0)


# (k, present) -> jitted closure; each entry pins a device bit matrix, so
# the cache is an explicit LRU (not functools.lru_cache) with hit/miss
# telemetry. Build-free consumers (the sweep engine's cached-singleton
# policy, one-shot BEFP verification) use the ATOMIC `repair_axes_get`;
# `repair_axes_cached` is a test-only probe and racy as a policy hook.
_AXES_FN_LOCK = threading.Lock()
_AXES_FN_CACHE: "collections.OrderedDict[tuple, object]" = \
    collections.OrderedDict()
_AXES_FN_MAXSIZE = 64


def repair_axes_cached(k: int, present: tuple[int, ...]) -> bool:
    """True iff `repair_axes_fn(k, present)` would be a cache hit (no
    matrix build, no jit compile). Does not touch LRU order or counters."""
    with _AXES_FN_LOCK:
        return (k, tuple(present)) in _AXES_FN_CACHE


class _RepairAxesRunner:
    """Host wrapper around one pattern's jitted matmul: pads every batch
    to a power-of-two bucket before dispatch (bounding per-pattern
    compiles to log2(2k) shapes instead of one per batch size — jax.jit
    retraces per SHAPE, so a bare closure would recompile for every new
    group width) and records which buckets have actually executed.
    Build-free consumers gate on `compiled_for(n)`: a cached closure that
    has never run this batch bucket would still pay a full XLA compile."""

    __slots__ = ("_run", "_buckets", "_lock", "_k")

    def __init__(self, run, k: int = 0):
        self._run = run
        self._buckets: set[int] = set()
        self._lock = threading.Lock()
        self._k = k  # square size, for the mesh plane's sharding gate

    def compiled_for(self, n: int) -> bool:
        with self._lock:
            return pow2_bucket(n) in self._buckets

    def __call__(self, symbols_batch) -> np.ndarray:
        from celestia_app_tpu.obs import xfer

        batch = np.asarray(symbols_batch)
        n = batch.shape[0]
        bucket = pow2_bucket(n)
        if bucket != n:
            batch = np.concatenate([
                batch,
                np.zeros((bucket - n, *batch.shape[1:]), dtype=batch.dtype),
            ])
        # mesh plane: when active for this square size, split the padded
        # batch over the flat device list BEFORE dispatch — the jitted
        # fused-decode matmul partitions by input sharding, so the
        # repair sweep runs mesh-sharded with identical bytes (the pow2
        # bucket discipline already makes shard extents shape-static)
        dev_batch = batch
        if self._k:
            from celestia_app_tpu.parallel import mesh_engine

            dev_batch = mesh_engine.maybe_shard_batch(batch, self._k)
        if dev_batch is batch:
            dev_batch = xfer.to_device(batch, "ops.repair_dispatch")
        out = xfer.to_host(self._run(dev_batch), "ops.repair_fetch")[:n]
        with self._lock:
            self._buckets.add(bucket)
        return out


def repair_axes_get(k: int, present: tuple[int, ...],
                    batch_size: int | None = None):
    """The cached runner for (k, present), or None — ONE atomic lookup,
    so a caller that must never pay a build/compile (one-shot BEFP
    verification, the sweep engine's cached-singleton policy) cannot race
    an eviction between a peek and a `repair_axes_fn` call. With
    `batch_size`, the runner is returned only if its power-of-two bucket
    has already EXECUTED (compiled): presence in the LRU alone does not
    mean this shape is compiled. A returned runner counts into
    `repair.matrix_cache_hits`; a None is not a miss (nothing is
    built)."""
    from celestia_app_tpu.utils import telemetry

    key = (k, tuple(present))
    with _AXES_FN_LOCK:
        run = _AXES_FN_CACHE.get(key)
        if run is not None:
            _AXES_FN_CACHE.move_to_end(key)
    if run is not None and batch_size is not None \
            and not run.compiled_for(batch_size):
        return None
    if run is not None:
        telemetry.incr("repair.matrix_cache_hits")
    return run


def repair_axes_cache_clear() -> None:
    with _AXES_FN_LOCK:
        _AXES_FN_CACHE.clear()


def repair_axes_fn(k: int, present: tuple[int, ...]):
    """Jitted BATCHED erasure repair for one shared pattern: the
    TPU-native path for the common DA-repair shape, where whole COLUMNS of
    the square are missing and every row therefore has the same erasure
    pattern. Repairing n axes collapses into one MXU bit-matmul over the
    batch — (bits·2k, bits·k) @ (n, bits·k, S) — instead of rsmt2d's
    per-axis heap decodes.

    Returns run((n, 2k, SHARE) uint8, garbage at missing) -> (n, 2k, SHARE)
    full codewords (a `_RepairAxesRunner`: the batch is padded to a
    power-of-two bucket before the jitted dispatch and the result comes
    back as numpy, so per-pattern compiles are bounded at log2(2k)
    shapes). NOTE the output is the full RE-ENCODE from the first k
    sorted present positions: for a consistent codeword it equals
    repair_axis's output bit-for-bit (tests/test_repair.py), but any EXTRA
    present shares are overwritten rather than passed through — a caller
    doing byzantine DETECTION must compare output vs input at present
    positions (da/repair.py's sweep engine does exactly that, falling
    back to the FWHT decoder on mismatch so both engines agree
    bit-for-bit; root-gating alone cannot catch a corrupt present share
    outside the first-k use-set).

    Closures are LRU-cached per (k, pattern); hits and misses count into
    `repair.matrix_cache_hits` / `repair.matrix_cache_misses`."""
    from celestia_app_tpu.utils import telemetry

    key = (k, tuple(present))
    with _AXES_FN_LOCK:
        run = _AXES_FN_CACHE.get(key)
        if run is not None:
            _AXES_FN_CACHE.move_to_end(key)
            telemetry.incr("repair.matrix_cache_hits")
            return run
    telemetry.incr("repair.matrix_cache_misses")
    from celestia_app_tpu.obs import jax_profile

    jax_profile.note_compile("rs.repair_axes", k)
    from celestia_app_tpu.ops import leopard_decode

    two_k = 2 * k
    if len(present) < k:
        raise ValueError(f"need at least {k} of {two_k} symbols")
    use = tuple(sorted(present)[:k])
    labels = leopard_decode.fused_decode_matrix(k, use)
    # one branch assigns the matched (matrix, packers) triple — the bit
    # matrix and the bit packers must always come from the same field
    if leopard.uses_gf16(k):
        bitmat = jnp.asarray(leopard.to_bit_matrix16(labels))
        to_bits, from_bits = bytes_to_bits16, bits_to_bytes16
    else:
        bitmat = jnp.asarray(leopard.to_bit_matrix(labels))
        to_bits, from_bits = bytes_to_bits, bits_to_bytes

    @jax.jit
    def run(symbols_batch: jax.Array) -> jax.Array:
        x = symbols_batch[:, list(use), :]
        return from_bits(_gf_mix(bitmat, to_bits(x))).astype(jnp.uint8)

    runner = _RepairAxesRunner(run, k=k)
    with _AXES_FN_LOCK:
        _AXES_FN_CACHE[key] = runner
        while len(_AXES_FN_CACHE) > _AXES_FN_MAXSIZE:
            _AXES_FN_CACHE.popitem(last=False)
    return runner


def repair_axis(symbols: np.ndarray, present: list[int]) -> np.ndarray:
    """Recover all 2k symbols of one row/column from any k known ones.

    `symbols` is (2k, S) with arbitrary content at missing positions;
    `present` lists the >=k known positions. Uses Leopard's own O(n log n)
    FWHT/error-locator decoder (ops/leopard_decode.py); the O(k^3) matrix-
    inversion path remains as `repair_axis_matrix` for cross-checking.
    """
    from celestia_app_tpu.ops import leopard_decode

    two_k = symbols.shape[0]
    k = two_k // 2
    if len(present) < k:
        raise ValueError(f"need at least {k} of {two_k} symbols, got {len(present)}")
    if leopard.uses_gf16(k):
        return bytes_of_symbols(leopard_decode.decode16(
            symbols_of_bytes(symbols), list(present)))
    return leopard_decode.decode8(
        np.ascontiguousarray(symbols), list(present)
    )


def repair_axis_matrix(symbols: np.ndarray, present: list[int]) -> np.ndarray:
    """Matrix-inversion repair (independent of the FFT decode path)."""
    two_k = symbols.shape[0]
    k = two_k // 2
    if len(present) < k:
        raise ValueError(f"need at least {k} of {two_k} symbols, got {len(present)}")
    use = tuple(sorted(present)[:k])
    if leopard.uses_gf16(k):
        m = leopard.decode_matrix16(k, use)
        data16 = leopard.matmul16(m, symbols_of_bytes(symbols)[list(use)])
        parity16 = leopard.encode16(data16)
        return bytes_of_symbols(np.concatenate([data16, parity16], axis=0))
    m = leopard.decode_matrix(k, use)
    data = leopard.matmul(m, symbols[list(use)])
    parity = leopard.matmul(leopard.encode_matrix(k), data)
    return np.concatenate([data, parity], axis=0)
