"""Device RS extension vs numpy byte-domain reference; repair path."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from celestia_app_tpu.ops import rs

sys.path.insert(0, os.path.dirname(__file__))
import gf16_plain  # noqa: E402

PARENT_EXTEND_SHA256 = {   # rs.extend_square_np at e94e245, seed [36, k]
    1: "fd51211d61a97fd7d8eadf85253506bb63ac503bf81ccaefcec3ab036c7369f0",
    2: "753fdf0ccce9556d16be153bc284f7de440093f7f1ec6d60bd64483777af1343",
    8: "0f6f2e200d2f1bbaecf89e1d02b64fe9b825243bdd400353b55d60a05ede389e",
    128: "8a3c501ed8d09e6070711e89be3fc7a355fff44504d0f91f6ebe853e14a10438",
}


@pytest.mark.backend
@pytest.mark.parametrize("k", [1, 2, 4, 8, 16])
def test_device_matches_numpy(k):
    rng = np.random.default_rng(k)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    eds_np = rs.extend_square_np(ods)
    eds_dev = np.asarray(rs.jitted_extend(k)(jnp.asarray(ods)))
    assert (eds_np == eds_dev).all()


@pytest.mark.parametrize("k", [2, 4, 8])
def test_quadrant_consistency(k):
    """Q3 via rows of Q2 must equal Q3 via columns of Q1 (data_structures.md:310)."""
    rng = np.random.default_rng(7)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    eds = rs.extend_square_np(ods)
    q1 = eds[:k, k:, :]
    q3 = eds[k:, k:, :]
    from celestia_app_tpu.ops import leopard

    e = leopard.encode_matrix(k)
    q3_from_q1 = np.stack([leopard.matmul(e, q1[:, c, :]) for c in range(k)], axis=1)
    assert (q3_from_q1 == q3).all()


@pytest.mark.parametrize("k", [2, 4, 8])
def test_repair_from_any_half(k):
    rng = np.random.default_rng(k + 100)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    eds = rs.extend_square_np(ods)
    row = eds[1].copy()
    lost = rng.choice(2 * k, size=k, replace=False)
    present = [i for i in range(2 * k) if i not in lost]
    corrupted = row.copy()
    corrupted[lost] = 0
    rec = rs.repair_axis(corrupted, present)
    assert (rec == row).all()


def test_repair_needs_half():
    k = 4
    row = np.zeros((2 * k, 512), dtype=np.uint8)
    with pytest.raises(ValueError):
        rs.repair_axis(row, list(range(k - 1)))


@pytest.mark.backend
def test_bits_roundtrip():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 256, size=(3, 4, 16), dtype=np.uint8))
    back = rs.bits_to_bytes(rs.bytes_to_bits(x))
    assert (np.asarray(back) == np.asarray(x)).all()


def test_extend_square_fn_takes_k_alone():
    """One RS schedule, chosen by the code: no layout, dtype or other
    selector reaches the square's extension."""
    import inspect

    assert list(inspect.signature(rs.extend_square_fn).parameters) == ["k"]


@pytest.mark.backend
def test_device_matches_numpy_in_the_16_bit_field(monkeypatch):
    """The same schedule under GF(2^16) — production's k >= 256 — at k=8,
    with the field's cutover lowered for this test: the full square, all
    three passes, against the numpy FFT encode. A fresh jit, so no cache
    keeps a 16-bit program under an 8-bit k."""
    import jax

    from celestia_app_tpu.ops import leopard

    k = 8
    rng = np.random.default_rng(16)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    eds_8bit = rs.extend_square_np(ods)
    monkeypatch.setattr(leopard, "_gf16_threshold", lambda: 4)
    assert leopard.uses_gf16(k)
    eds_np = rs.extend_square_np(ods)
    assert not (eds_np == eds_8bit).all()      # another code, not a relabel
    eds_dev = np.asarray(jax.jit(rs.extend_square_fn(k))(jnp.asarray(ods)))
    assert (eds_np == eds_dev).all()
    # and both are the plain encode under the published 64-byte block:
    # every quadrant, never the adjacent-pairs bytes
    assert (eds_dev == gf16_plain.extend(ods)).all()
    assert not (eds_dev[0, k:] == gf16_plain.parity_adjacent_pairs(ods[0])
                ).all()


@pytest.mark.parametrize("k", [1, 2, 8, 128])
def test_squares_up_to_128_keep_their_bytes(k):
    """The 16-bit symbol mapping is no business of the 8-bit code: the
    extension's digest at k <= 128 as pinned at the parent of PR 36
    (`rs.extend_square_np`, ods from seed [36, k])."""
    import hashlib

    ods = np.random.default_rng([36, k]).integers(
        0, 256, (k, k, 512), dtype=np.uint8)
    assert hashlib.sha256(rs.extend_square_np(ods).tobytes()).hexdigest() \
        == PARENT_EXTEND_SHA256[k]


@pytest.mark.backend
@pytest.mark.parametrize("lead", [(3,), (2, 3)])
def test_gf_mix_is_the_matrix_product_mod_2(lead):
    """`_gf_mix` is the one contraction under every device RS path (the
    square's extension, the sharded pipeline's 4-D slabs, batched repair):
    for any leading batch shape it is (B @ x) mod 2, bit for bit."""
    rng = np.random.default_rng(len(lead))
    q, s = 16, 24
    mat = rng.integers(0, 2, size=(q, q), dtype=np.int8)
    x = rng.integers(0, 2, size=(*lead, q, s), dtype=np.int8)
    got = np.asarray(rs._gf_mix(jnp.asarray(mat), jnp.asarray(x)))
    want = (np.einsum("pq,...qs->...ps", mat.astype(np.int64),
                      x.astype(np.int64)) % 2).astype(np.int8)
    assert got.dtype == np.int8 and (got == want).all()
