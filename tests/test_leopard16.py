"""GF(2^16) leopard16: the k>=256 codec (BASELINE config 5 scale-out)."""

import os
import sys

import numpy as np
import pytest

from celestia_app_tpu.ops import gf256, leopard, rs

sys.path.insert(0, os.path.dirname(__file__))
import gf16_plain  # noqa: E402


def _gmul16(a, b):
    r = 0
    while b:
        if b & 1:
            r ^= a
        a <<= 1
        if a & (1 << 16):
            a ^= leopard.POLY16
        b >>= 1
    return r


def test_cantor_basis16_recurrence():
    basis = leopard.CANTOR_BASIS16
    assert basis[0] == 1
    for i in range(15):
        b = basis[i + 1]
        assert _gmul16(b, b) ^ b == basis[i], i
        assert b % 2 == 0  # the documented even-root selection rule


def test_field16_laws():
    rng = np.random.default_rng(0)
    for _ in range(60):
        a, b, c = (int(x) for x in rng.integers(1, 65536, 3))
        assert leopard.mul16(a, b) == leopard.mul16(b, a)
        assert leopard.mul16(a, leopard.mul16(b, c)) == leopard.mul16(
            leopard.mul16(a, b), c
        )
        assert leopard.mul16(a, b ^ c) == leopard.mul16(a, b) ^ leopard.mul16(a, c)
        assert leopard.mul16(a, leopard.inv16(a)) == 1


def test_fft16_roundtrip_and_constant():
    rng = np.random.default_rng(1)
    for n in [2, 32, 256]:
        v = rng.integers(0, 65536, (n, 3), dtype=np.uint16)
        assert np.array_equal(leopard.fft16(leopard.ifft16(v, n), n), v)
    c = np.full((256, 2), 0xBEEF, np.uint16)
    assert np.all(leopard.encode16(c) == 0xBEEF)


def test_mds16_random_k256():
    rng = np.random.default_rng(2)
    data = rng.integers(0, 65536, (256, 4), dtype=np.uint16)
    cw = np.concatenate([data, leopard.encode16(data)], axis=0)
    for _ in range(2):
        present = tuple(sorted(rng.choice(512, 256, replace=False).tolist()))
        m = leopard.decode_matrix16(256, present)
        assert np.array_equal(leopard.matmul16(m, cw[list(present)]), data)


def test_bit_matrix16_equals_symbol_domain():
    rng = np.random.default_rng(3)
    k = 4  # small k: the formulation is k-independent
    data16 = rng.integers(0, 65536, (k, 6), dtype=np.uint16)
    parity16 = leopard.matmul16(leopard.encode_matrix16(k), data16)
    bits = ((data16[:, None, :] >> np.arange(16)[None, :, None]) & 1).reshape(
        16 * k, -1
    )
    out_bits = (leopard.bit_matrix16(k).astype(np.int64) @ bits) & 1
    out = (
        (out_bits.reshape(k, 16, -1) * (1 << np.arange(16))[None, :, None])
        .sum(axis=1)
        .astype(np.uint16)
    )
    assert np.array_equal(out, parity16)


@pytest.mark.backend
def test_device_bits16_pack_roundtrip_and_extend():
    """The device bit pack/unpack and one row-extension pass with the
    16-bit matrix agree with the plain encode under the published 64-byte
    block (small payload, forced 16-bit formulation via direct kernel
    plumbing at test scale)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.integers(0, 256, (3, 4, 128), dtype=np.uint8))
    back = rs.bits_to_bytes16(rs.bytes_to_bits16(x))
    assert np.array_equal(np.asarray(back), np.asarray(x))

    k, d = 8, 64
    block = rng.integers(0, 256, (k, d), dtype=np.uint8)
    bits = rs.bytes_to_bits16(jnp.asarray(block)[None])  # (1, 16k, d/2)
    mixed = rs._gf_mix(jnp.asarray(leopard.bit_matrix16(k)), bits)
    got = np.asarray(rs.bits_to_bytes16(mixed))[0]
    assert np.array_equal(got, gf16_plain.parity(block))
    assert not np.array_equal(got, gf16_plain.parity_adjacent_pairs(block))


# -- which bytes of a share make a 16-bit symbol ------------------------------


def test_host_pair_is_the_published_64_byte_block():
    """Symbol p = 32b + i of a shard: low byte 64b + i, high byte
    64b + 32 + i — stated here on the bytes 1, 3, 5, ... so that every
    position is told apart."""
    shard = (np.arange(128, dtype=np.uint8) * 2 + 1)
    byte = shard.tolist()
    want = ([byte[i] | byte[i + 32] << 8 for i in range(32)]
            + [byte[64 + i] | byte[96 + i] << 8 for i in range(32)])
    sym = rs.symbols_of_bytes(shard[None])
    assert sym.dtype == np.uint16 and sym.shape == (1, 64)
    assert sym[0].tolist() == want == gf16_plain.symbols(shard).tolist()
    assert rs.bytes_of_symbols(sym).dtype == np.uint8
    assert np.array_equal(rs.bytes_of_symbols(sym)[0], shard)
    assert np.array_equal(gf16_plain.shard_bytes(sym[0]), shard)
    # any leading shape, a share's 512 bytes
    x = np.random.default_rng(36).integers(0, 256, (3, 5, 512),
                                           dtype=np.uint8)
    assert np.array_equal(rs.bytes_of_symbols(rs.symbols_of_bytes(x)), x)
    assert rs.symbols_of_bytes(x).shape == (3, 5, 256)
    # and never adjacent little-endian pairs
    assert not np.array_equal(rs.symbols_of_bytes(x),
                              np.ascontiguousarray(x).view("<u2"))


@pytest.mark.backend
def test_device_packers_equal_the_host_pair():
    """Row 16l + j of the bit array = bit j of shard l's symbols, the
    symbols being the host pair's: one mapping, two forms."""
    import jax.numpy as jnp

    x = np.random.default_rng(37).integers(0, 256, (2, 3, 512),
                                           dtype=np.uint8)
    bits = np.asarray(rs.bytes_to_bits16(jnp.asarray(x)))
    assert bits.shape == (2, 48, 256) and bits.dtype == np.int8
    sym = rs.symbols_of_bytes(x)                               # (2, 3, 256)
    want = (sym[:, :, None, :] >> np.arange(16)[None, None, :, None]) & 1
    assert np.array_equal(bits, want.reshape(2, 48, 256))
    back = np.asarray(rs.bits_to_bytes16(jnp.asarray(bits)))
    assert back.dtype == np.uint8 and np.array_equal(back, x)


@pytest.mark.parametrize("fn,arg", [
    ("symbols_of_bytes", np.zeros((2, 96), np.uint8)),
    ("bytes_of_symbols", np.zeros((2, 48), np.uint16)),
    ("bytes_to_bits16", np.zeros((1, 2, 96), np.uint8)),
    ("bits_to_bytes16", np.zeros((1, 32, 48), np.int8)),
])
def test_a_shard_that_is_not_whole_blocks_is_refused(fn, arg):
    with pytest.raises(ValueError, match="64-byte blocks"):
        getattr(rs, fn)(arg)


@pytest.fixture(scope="module")
def plain_da():
    """The benchmark's plain reference (numpy + hashlib; its own field,
    skews and FFT, nothing of the program imported)."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        from reference import plain_da as da
    finally:
        sys.path.remove(bench)
    return da


@pytest.mark.backend
@pytest.mark.parametrize("k", [256, 512])
def test_program_parity_is_the_published_mapping_byte_for_byte(k, plain_da):
    """512 and 1,024 shards an axis — real k = 256 and 512, what
    `extend_square_fn` runs on every axis (`rs._codec`): the device route
    bytes -> bits -> bit matrix -> bytes, the host route and the plain
    in-test encode give one answer, the plain reference's, and not the
    adjacent-pairs bytes the program gave before PR 36."""
    import jax.numpy as jnp

    axes = np.random.default_rng([36, k]).integers(
        0, 256, (2, k, 128), dtype=np.uint8)
    matrix, to_bits, from_bits = rs._codec(k)
    device = np.asarray(from_bits(rs._gf_mix(
        jnp.asarray(matrix), to_bits(jnp.asarray(axes)))))
    for axis, got in zip(axes, device):
        assert np.array_equal(got, gf16_plain.parity(axis))
        assert np.array_equal(got, rs._encode_axis_np(axis))
        assert np.array_equal(got, plain_da.bytes_of_symbols(
            plain_da.rs_encode(plain_da.symbols_of_bytes(axis))))
        assert not np.array_equal(got,
                                  gf16_plain.parity_adjacent_pairs(axis))


def test_repair_axis_gf16():
    rng = np.random.default_rng(5)
    k = 256
    data = rng.integers(0, 256, (k, 64), dtype=np.uint8)
    parity = rs._encode_axis_np(data)
    assert np.array_equal(parity, gf16_plain.parity(data))
    row = np.concatenate([data, parity], axis=0)
    present = sorted(rng.choice(2 * k, k, replace=False).tolist())
    corrupted = row.copy()
    for i in range(2 * k):
        if i not in present:
            corrupted[i] = 0
    rec = rs.repair_axis(corrupted, present)
    assert np.array_equal(rec, row)
    assert np.array_equal(rs.repair_axis_matrix(corrupted, present), row)


@pytest.mark.slow
@pytest.mark.backend
def test_extend_square_256_device_vs_host():
    """Full k=256 square: device bit-matrix extension == host FFT encode.

    Payload kept at full 512 B but run once (slow: ~4096-wide bit matmuls
    on CPU)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(6)
    ods = rng.integers(0, 256, (256, 256, 512), dtype=np.uint8)
    eds_host = rs.extend_square_np(ods)
    eds_dev = np.asarray(rs.jitted_extend(256)(jnp.asarray(ods)))
    assert np.array_equal(eds_dev, eds_host)
