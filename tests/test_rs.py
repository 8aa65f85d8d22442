"""Device RS extension vs numpy byte-domain reference; repair path."""

import jax.numpy as jnp
import numpy as np
import pytest

from celestia_app_tpu.ops import rs


@pytest.mark.backend
@pytest.mark.parametrize("k", [1, 2, 4])
def test_device_matches_numpy(k):
    rng = np.random.default_rng(k)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    eds_np = rs.extend_square_np(ods)
    eds_dev = np.asarray(rs.jitted_extend(k)(jnp.asarray(ods)))
    assert (eds_np == eds_dev).all()


def test_quadrant_consistency():
    """Q3 via rows of Q2 must equal Q3 via columns of Q1 (data_structures.md:310)."""
    k = 4
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


def test_flat_gemm_layout_bit_identical():
    """CELESTIA_RS_LAYOUT=flat is a schedule change only: outputs must be
    bit-identical to the batched einsum for both fields."""
    import jax

    from celestia_app_tpu.ops import rs as rs_mod

    rng = np.random.default_rng(11)
    for k in (4, 8):
        ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
        ref = np.asarray(jax.jit(rs_mod.extend_square_fn(k, layout="batched", dtype="int8"))(ods))
        for layout in ("batched", "flat", "fused"):
            for dtype in ("int8", "bf16"):
                out = np.asarray(
                    jax.jit(rs_mod.extend_square_fn(k, layout=layout, dtype=dtype))(ods)
                )
                np.testing.assert_array_equal(ref, out, err_msg=f"{layout}/{dtype}")


def test_pallas_fused_rs_pass_interpret_mode():
    """The Pallas fused extend (unpack+GF2-matmul+pack in one kernel) is
    bit-identical to the XLA path — verified in interpret mode since no
    TPU is guaranteed in CI; the bench cross-checks again on hardware."""
    import jax

    from celestia_app_tpu.ops import rs as rs_mod
    from celestia_app_tpu.ops import rs_pallas

    rng = np.random.default_rng(3)
    for k in (4, 8):
        ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
        ref = np.asarray(
            jax.jit(rs_mod.extend_square_fn(k, layout="batched", dtype="int8"))(ods)
        )
        got = np.asarray(rs_pallas.extend_square_fn(k, interpret=True)(ods))
        np.testing.assert_array_equal(ref, got)


def test_pallas_rs_composes_with_full_pipeline():
    """The whole jitted ODS->DAH pipeline with the Pallas RS pass inside
    (interpret mode): same data root as the default schedule — de-risks
    the TPU composition before hardware ever sees it."""
    import subprocess
    import sys as _sys

    code = r"""
import numpy as np
import jax
from celestia_app_tpu.da import eds as eds_mod

k = 8
rng = np.random.default_rng(4)
ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
ods[..., :29] = 0
ods[..., 28] = 5
ref_root = bytes(np.asarray(eds_mod.jitted_pipeline(k)(ods)[3]))
import os
os.environ["CELESTIA_RS_LAYOUT"] = "pallas"
os.environ["CELESTIA_PALLAS_INTERPRET"] = "1"
eds_mod.jitted_pipeline.cache_clear()
pallas_root = bytes(np.asarray(eds_mod.jitted_pipeline(k)(ods)[3]))
assert pallas_root == ref_root, (pallas_root.hex(), ref_root.hex())
print("PIPELINE-PALLAS-OK")
"""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([_sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PIPELINE-PALLAS-OK" in r.stdout
