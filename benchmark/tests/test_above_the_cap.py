"""A deployment above the versioned cap of 128: the reference's 16-bit code
against the field's own laws and the program's matrix form, the bytes of
every square up to 128 unchanged, which byte mapping the program follows at
2k = 512, and the configuration key that carries the cap to `App` and to
the plain validator."""

import hashlib
import json
import os

import numpy as np
import pytest

from conftest import REPO_DIR, _write
from lib import cells
from reference import plain_da as da


# -- the field, by a route of its own: carry-less products in the polynomial
# basis, all elements at once ------------------------------------------------


def _poly_mul(bits: int, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    poly = da._FIELDS[bits][0]
    a, b = np.asarray(a, dtype=np.int64), np.asarray(b, dtype=np.int64)
    acc = np.zeros(np.broadcast(a, b).shape, dtype=np.int64)
    for i in range(bits):
        acc ^= np.where(b >> i & 1, a, 0)
        a = a << 1
        a = np.where(a >> bits & 1, a ^ poly, a)
    return acc


def _cantor(bits: int, labels: np.ndarray) -> np.ndarray:
    """label -> field element: the XOR of beta_b over the label's set bits"""
    out = np.zeros_like(labels)
    for b, beta in enumerate(da._FIELDS[bits][1]):
        out ^= np.where(labels >> b & 1, beta, 0)
    return out


@pytest.mark.parametrize("bits", [8, 16])
def test_the_cantor_basis_follows_its_recurrence(bits):
    """beta_0 = 1, beta_{i+1}^2 + beta_{i+1} = beta_i, the even root of the
    two: re-derived here, not copied."""
    every = np.arange(1 << bits)
    artin_schreier = _poly_mul(bits, every, every) ^ every
    basis = [1]
    while len(basis) < bits:
        roots = np.flatnonzero(artin_schreier == basis[-1])
        assert len(roots) == 2 and roots[0] ^ roots[1] == 1
        basis.append(int(roots[0] & ~1))
    assert tuple(basis) == da._FIELDS[bits][1]


@pytest.mark.parametrize("bits", [8, 16])
def test_the_references_tables_obey_the_field_laws(bits):
    rng = np.random.default_rng([35, bits])
    abc = rng.integers(1, 1 << bits, (200, 3))
    for a, b, c in abc.tolist():
        mul = lambda x, y: da.gf_mul(bits, x, y)   # noqa: E731
        assert mul(a, b) == mul(b, a)
        assert mul(mul(a, b), c) == mul(a, mul(b, c))
        assert mul(a, b ^ c) == mul(a, b) ^ mul(a, c)
        assert mul(a, 1) == a and mul(a, 0) == 0
        assert mul(a, da.gf_inv(bits, a)) == 1
        assert da._times(bits, a)[b] == mul(a, b)
    # the label product is the field's product seen through the Cantor basis
    a, b = abc[:, 0], abc[:, 1]
    products = np.array([da.gf_mul(bits, x, y) for x, y in zip(a, b)])
    assert np.array_equal(_cantor(bits, products),
                          _poly_mul(bits, _cantor(bits, a), _cantor(bits, b)))


@pytest.mark.parametrize("k,symbol", [(4, np.uint8), (128, np.uint8),
                                      (256, np.uint16)])
def test_constant_data_give_constant_parity(k, symbol):
    data = np.full((k, 3), 0xA7 if symbol is np.uint8 else 0xA7C3, symbol)
    assert np.array_equal(da.rs_encode(data), data)


def test_the_field_goes_by_the_shard_count_alone():
    assert [da.field_bits(k) for k in (1, 64, 128, 256, 512)] == \
        [8, 8, 8, 16, 16]
    with pytest.raises(TypeError):      # bytes are not 16-bit symbols
        da.rs_encode(np.zeros((256, 4), dtype=np.uint8))
    with pytest.raises(TypeError):
        da.rs_encode(np.zeros((128, 4), dtype=np.uint16))


@pytest.mark.parametrize("k", [256, 512])
def test_the_fft_and_the_programs_matrix_form_give_one_answer(k):
    """Two algorithms — the butterflies here, `ifft16`/`fft16` and the
    product-over-the-subspace skews there — symbol for symbol."""
    from celestia_app_tpu.ops import leopard

    data = np.random.default_rng([35, k]).integers(
        0, 1 << 16, (k, 24), dtype=np.uint16)
    assert np.array_equal(da.rs_encode(data), leopard.encode16(data))


def test_the_symbol_mapping_is_the_published_64_byte_block():
    shard = np.arange(128, dtype=np.uint8)[None, :] * 2 + 1   # (1, 128)
    symbols = da.symbols_of_bytes(shard)
    assert symbols.shape == (1, 64) and symbols.dtype == np.uint16
    # symbol i of block 0: bytes i (low) and i + 32 (high); block 1 follows
    byte = shard[0].tolist()
    assert symbols[0].tolist() == (
        [byte[i] | byte[i + 32] << 8 for i in range(32)]
        + [byte[64 + i] | byte[96 + i] << 8 for i in range(32)])
    ods = np.random.default_rng(35).integers(0, 256, (3, 2, 512),
                                             dtype=np.uint8)
    assert np.array_equal(da.bytes_of_symbols(da.symbols_of_bytes(ods)), ods)


# -- squares up to 128: not one byte moved ------------------------------------

PARENT_EXTEND_SHA256 = {     # plain_da.extend at bc831b8, ods from seed [35, k]
    1: "9853f31488837bb10d884d9b8af7e4566cf6bdaf8966b1d15f93fe24985e5724",
    2: "dbdde8d7022a97d5d80ea5dbc92a47d632c7d92d98b6c15e7c2090f21418a671",
    8: "eebafbcbafdd112b504ff318f25e9923b783dcef023f337cd2b542592ae03b5e",
    128: "64beb0d38f13ba70c678e31c78f84f87b6f8497fb3d58ae13c000a37ef9d464d",
}


@pytest.mark.parametrize("k", sorted(PARENT_EXTEND_SHA256))
def test_extend_up_to_128_returns_the_parents_bytes(k):
    ods = np.random.default_rng([35, k]).integers(
        0, 256, (k, k, 512), dtype=np.uint8)
    assert hashlib.sha256(da.extend(ods).tobytes()).hexdigest() == \
        PARENT_EXTEND_SHA256[k]


# -- 2k = 512: which bytes make a symbol, the program's answer ----------------
# Each of the two states what is true of the program today; a PR that changes
# the program's mapping flips both.


@pytest.fixture(scope="module")
def axes_at_512():
    """A few seeded axes of 256 shares and the parity the program's own
    bytes -> bits -> bit-matrix -> bytes route gives them (`ops/rs._codec`:
    what `extend_square_fn(256)` runs on every axis)."""
    import jax.numpy as jnp
    from celestia_app_tpu.ops import rs

    axes = np.random.default_rng([35, 512]).integers(
        0, 256, (2, 256, 512), dtype=np.uint8)
    matrix, to_bits, from_bits = rs._codec(256)
    parity = from_bits(rs._gf_mix(jnp.asarray(matrix),
                                  to_bits(jnp.asarray(axes))))
    return axes, np.asarray(parity)


def test_program_at_512_shards_differs_from_the_reference_under_the_published_mapping(
        axes_at_512):
    axes, program = axes_at_512
    for axis, parity in zip(axes, program):
        reference = da.bytes_of_symbols(da.rs_encode(
            da.symbols_of_bytes(axis)))
        assert not np.array_equal(reference, parity)


def test_program_at_512_shards_equals_the_reference_under_adjacent_pairs(
        axes_at_512):
    axes, program = axes_at_512
    for axis, parity in zip(axes, program):
        pairs = da.rs_encode(np.ascontiguousarray(axis).view("<u2"))
        assert np.array_equal(pairs.view(np.uint8), parity)


def test_extend_at_256_is_the_axis_code_under_the_mapping():
    """`extend` above the cap: every quadrant the 16-bit code of its axis
    under `symbols_of_bytes`, Q3 the same by rows and by columns."""
    k = 256
    ods = np.random.default_rng([35, k]).integers(
        0, 256, (k, k, 512), dtype=np.uint8)
    eds = da.extend(ods)
    assert eds.shape == (2 * k, 2 * k, 512)
    assert np.array_equal(eds[:k, :k], ods)

    def parity(axis):
        return da.bytes_of_symbols(da.rs_encode(da.symbols_of_bytes(axis)))

    for i in (0, 77, 255):
        assert np.array_equal(eds[i, k:], parity(ods[i]))           # Q1 row
        assert np.array_equal(eds[k:, i], parity(ods[:, i]))        # Q2 column
        assert np.array_equal(eds[k + i, k:], parity(eds[k + i, :k]))
        assert np.array_equal(eds[k:, k + i], parity(eds[:k, k + i]))


# -- the cap reaches App and the plain validator from a configuration file ---


@pytest.fixture(scope="module")
def above_cap_cell(tiny_tree):
    """`hard-cap-k128.json` with the governed bound and the home-config cap at
    256, and a four-chip cell of it, ADDED to the tiny tree as files and
    manifest entries."""
    root = os.path.dirname(tiny_tree)
    config = cells.read_json(os.path.join(tiny_tree, "configs",
                                          "hard-cap-k128.json"))
    config.update(gov_max_square_size=256, max_square_size=256,
                  source="a test's toy: no deployment")
    _write(os.path.join(tiny_tree, "configs", "above-cap-k256.json"), config)
    manifest = cells.load_manifest(tiny_tree)
    manifest["configs"].append({
        "name": "above-cap-k256", "source": config["source"],
        "file": "benchmark/configs/above-cap-k256.json",
        "reduced": ["max_square_size"], "why": "CPU rehearsal"})
    manifest["workloads"].append({
        "name": "above-cap", "config": "above-cap-k256",
        "traffic": "pfb-full", "chips": 4, "why": "CPU rehearsal"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "k128-pfb-full" in m.get("workloads", []):
            m["workloads"].append("above-cap")
    _write(os.path.join(root, "BENCHMARK.json"), manifest)
    return cells.load_cell("above-cap", bench_dir=tiny_tree)


def test_a_four_chip_cell_above_the_cap_loads_as_files(above_cap_cell):
    like = cells.load_cell("k128-pfb-full")
    assert above_cap_cell.chips == 4
    assert above_cap_cell.config["max_square_size"] == 256
    assert [m.name for m in above_cap_cell.per_layer] == \
        [m.name for m in like.per_layer]
    assert [m.name for m in above_cap_cell.end_to_end] == \
        [m.name for m in like.end_to_end]


def test_the_configurations_cap_reaches_app(above_cap_cell):
    import run

    traffic = above_cap_cell.generator().Traffic(above_cap_cell, 35)
    sut = run.real_validator(above_cap_cell, traffic)
    try:
        assert sut.app.max_square_size == 256
        assert sut.app.max_effective_square_size(sut._query._ctx()) == 256
    finally:
        sut.close()


def test_a_configuration_without_the_key_keeps_the_versioned_cap():
    import run

    cell = cells.load_cell("k128-pfb-full")
    assert "max_square_size" not in cell.config
    cell.config["gov_max_square_size"] = 256     # governed above the cap
    traffic = cell.generator().Traffic(cell, 35)
    sut = run.real_validator(cell, traffic)
    try:
        assert sut.app.max_square_size is None
        assert sut.app.max_effective_square_size(sut._query._ctx()) == 128
    finally:
        sut.close()


@pytest.mark.parametrize("governed,cap,expected", [
    (256, 256, 256), (512, 256, 256), (256, None, 128), (64, None, 64),
    (128, None, 128), (64, 256, 64)])
def test_the_plain_validator_lays_out_under_the_same_bound(
        governed, cap, expected):
    from reference.plain_node import PlainValidator

    config = {"gov_max_square_size": governed, "served_heights": 4}
    if cap is not None:
        config["max_square_size"] = cap
    assert PlainValidator(config, [(b"a" * 20, 1)], {}).max_k == expected


def test_every_committed_configuration_keeps_its_bound():
    """The four configurations that exist: the plain validator's bound is the
    governed one, as before this key."""
    from reference.plain_node import PlainValidator

    with open(os.path.join(REPO_DIR, "BENCHMARK.json"),
              encoding="utf-8") as f:
        manifest = json.load(f)
    for entry in manifest["configs"]:
        config = cells.read_json(os.path.join(REPO_DIR, entry["file"]))
        assert "max_square_size" not in config
        assert PlainValidator(config, [(b"a" * 20, 1)], {}).max_k == \
            config["gov_max_square_size"]
