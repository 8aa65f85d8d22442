"""Tunable-rate Reed-Solomon: the arXiv:2201.08261 protocol trade study.

The production 2D-RS scheme (ops/rs.py, ops/leopard.py) is pinned at
rate 1/2 per axis — k data shards always extend to n = 2k. The paper's
point is that the extension factor is a PROTOCOL KNOB, not a law of
nature: stretching an axis to n > 2k raises the fraction an adversary
must withhold (fewer samples to a confidence target, at more encoded
bytes), while n < 2k trades the other way. This module is the
instrument for that sweep — a systematic RS code with a
*parametrized* (k, n) per axis, n_r x n_c rectangles included — NOT a
registered wire codec, and on no benchmark cell's path: not measured.

Construction: classic GF(2^8) evaluation RS. Data shard j sits at
evaluation point j; the codeword is the degree-(k-1) interpolating
polynomial evaluated at points 0..n-1 (so the code is systematic and
any k of n shards recover all n — MDS). The field caps n at 256
points; sweeps past the cap are skipped and logged, never silently
truncated. Encode/decode matrices are Lagrange-basis evaluations,
host-side table arithmetic; the device engine lifts the fixed (n-k, k)
GF matrix to an (8(n-k), 8k) GF(2) bit-matrix and runs ONE jitted
bit-matmul per axis pass — the exact ops/rs.py playbook, bit-identical
to the host loops (pinned in tests/test_rs_tunable.py).

Engine gating follows ops/ldpc.py: "device" demands jax and raises,
"host" never touches it, "auto" degrades loudly via the
app.device_path_fallback counter.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from celestia_app_tpu import appconsts

# GF(2^8) modulus x^8+x^4+x^3+x^2+1 — the classic RS polynomial (0x11D),
# NOT tied to ops/leopard.py's field: this code is a measurement
# instrument, deliberately independent of the production codec's tables.
GF_POLY = 0x11D
FIELD = 256


def _build_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:510] = exp[0:255]  # wraparound: exp[(la+lb) % 255] sans mod
    return exp, log


_EXP, _LOG = _build_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[int(_LOG[a]) + int(_LOG[b])])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(256) inverse of 0")
    return int(_EXP[255 - int(_LOG[a])])


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(m, k) u8 GF matrix x (k, D) u8 shards -> (m, D) u8: the host
    engine's axis pass. Vectorized per data shard (k <= 256 iterations
    of one table-lookup outer product), exact GF(256) arithmetic."""
    m = a.shape[0]
    out = np.zeros((m, b.shape[1]), dtype=np.uint8)
    for j in range(a.shape[1]):
        col = a[:, j]
        row = b[j]
        nz = col != 0
        if not nz.any():
            continue
        prod = _EXP[_LOG[col[nz]][:, None] + _LOG[row][None, :]]
        prod = np.where(row[None, :] == 0, 0, prod)
        out[nz] ^= prod
    return out


def _lagrange_row(xs: list[int], x_eval: int) -> list[int]:
    """Coefficients c_i with p(x_eval) = XOR_i c_i * p(xs[i]) for any
    polynomial of degree < len(xs) — one Lagrange basis evaluation."""
    coeffs = []
    for i, xi in enumerate(xs):
        num, den = 1, 1
        for m, xm in enumerate(xs):
            if m == i:
                continue
            num = gf_mul(num, x_eval ^ xm)
            den = gf_mul(den, xi ^ xm)
        coeffs.append(gf_mul(num, gf_inv(den)))
    return coeffs


def _check_kn(k: int, n: int) -> None:
    if not 1 <= k < n:
        raise ValueError(f"need 1 <= k < n, got k={k} n={n}")
    if n > FIELD:
        raise ValueError(
            f"n={n} exceeds the GF(256) point budget ({FIELD}); "
            f"sweeps must skip (and log) this combination")


@functools.lru_cache(maxsize=256)
def encode_matrix(k: int, n: int) -> np.ndarray:
    """(n-k, k) u8: parity shard r (point k+r) from the k data shards
    (points 0..k-1). Pure function of (k, n) — nothing rides the wire."""
    _check_kn(k, n)
    xs = list(range(k))
    mat = np.array(
        [_lagrange_row(xs, x) for x in range(k, n)], dtype=np.uint8)
    mat.setflags(write=False)
    return mat


@functools.lru_cache(maxsize=256)
def decode_matrix(k: int, n: int, use: tuple[int, ...]) -> np.ndarray:
    """(k, k) u8: the data shards from any k distinct present points
    ``use`` — the MDS any-k-of-n interpolation."""
    _check_kn(k, n)
    if len(use) != k or len(set(use)) != k \
            or not all(0 <= u < n for u in use):
        raise ValueError(f"use must be k={k} distinct points < {n}")
    xs = list(use)
    mat = np.array(
        [_lagrange_row(xs, x) for x in range(k)], dtype=np.uint8)
    mat.setflags(write=False)
    return mat


def _to_bit_matrix(gf_mat: np.ndarray) -> np.ndarray:
    """Lift an (m, k) GF(256) matrix to the (8m, 8k) GF(2) bit-matrix of
    the same linear map under ops/rs.py's LSB-first bit packing:
    bit (8r+a) of the output depends on bit (8j+b) of the input iff bit
    a of gf_mul(M[r, j], 1 << b) is set."""
    m, k = gf_mat.shape
    out = np.zeros((8 * m, 8 * k), dtype=np.int8)
    for r in range(m):
        for j in range(k):
            c = int(gf_mat[r, j])
            if c == 0:
                continue
            for b in range(8):
                prod = gf_mul(c, 1 << b)
                for a in range(8):
                    if (prod >> a) & 1:
                        out[8 * r + a, 8 * j + b] = 1
    return out


def encode_axis_host(data: np.ndarray, n: int) -> np.ndarray:
    """(k, D) u8 data shards -> (n-k, D) parity shards."""
    return gf_matmul(encode_matrix(data.shape[0], n), data)


@functools.lru_cache(maxsize=64)
def jitted_encode_axis(k: int, n: int, shard_bytes: int):
    import jax
    import jax.numpy as jnp

    from celestia_app_tpu.obs import jax_profile
    from celestia_app_tpu.ops import rs

    jax_profile.note_compile("rs_tunable.encode", (k, n, shard_bytes))
    bit_mat = jnp.asarray(_to_bit_matrix(np.asarray(encode_matrix(k, n))))

    @jax.jit
    def run(data: jax.Array) -> jax.Array:
        bits = rs.bytes_to_bits(data)
        out = jnp.einsum("pq,qs->ps", bit_mat, bits,
                         preferred_element_type=jnp.int32)
        return rs.bits_to_bytes((out & 1).astype(jnp.int8))

    return run


def encode_axis(data: np.ndarray, n: int,
                engine: str = "auto") -> np.ndarray:
    """Engine-gated parity encode for one axis; both paths
    bit-identical."""
    from celestia_app_tpu.ops import ldpc

    data = np.ascontiguousarray(data, dtype=np.uint8)
    _check_kn(data.shape[0], n)
    if engine == "auto" and not ldpc.auto_wants_device():
        return encode_axis_host(data, n)
    if engine in ("device", "auto"):
        try:
            import jax.numpy as jnp

            run = jitted_encode_axis(data.shape[0], n, data.shape[1])
            return np.asarray(run(jnp.asarray(data)))
        except Exception:
            if engine == "device":
                raise
            from celestia_app_tpu.utils import telemetry

            telemetry.incr("app.device_path_fallback")
    return encode_axis_host(data, n)


def extend_axis(data: np.ndarray, n: int,
                engine: str = "auto") -> np.ndarray:
    """(k, D) -> (n, D): systematic codeword (data verbatim, then
    parity)."""
    return np.concatenate([np.ascontiguousarray(data, dtype=np.uint8),
                           encode_axis(data, n, engine)], axis=0)


def recover_axis(symbols: np.ndarray, present: list[int],
                 k: int) -> np.ndarray:
    """Recover the full n-shard codeword from any >= k known shards
    ((n, D) with garbage at missing positions)."""
    n = symbols.shape[0]
    if len(present) < k:
        raise ValueError(
            f"need at least {k} of {n} shards, got {len(present)}")
    use = tuple(sorted(present)[:k])
    data = gf_matmul(decode_matrix(k, n, use), symbols[list(use)])
    return np.concatenate([data, encode_axis_host(data, n)], axis=0)


def extend_2d(ods: np.ndarray, n_r: int, n_c: int,
              engine: str = "auto") -> np.ndarray:
    """(k, k, S) ODS -> (n_r, n_c, S) rectangle: rows stretched to n_c,
    then every (now n_c-wide) column stretched to n_r — the generalized
    Q1/Q2/Q3 of ops/rs.py, rates decoupled per axis."""
    k = ods.shape[0]
    s = ods.shape[2]
    flat = np.ascontiguousarray(ods, dtype=np.uint8)
    # row pass: mix across the column index within each row
    rows = np.stack([extend_axis(flat[r], n_c, engine)
                     for r in range(k)])  # (k, n_c, S)
    # column pass over the full-width intermediate
    cols = np.stack(
        [extend_axis(rows[:, c, :], n_r, engine)
         for c in range(n_c)], axis=1)  # (n_r, n_c, S)
    assert cols.shape == (n_r, n_c, s)
    return cols


def analytics(k: int, n_r: int, n_c: int) -> dict:
    """The paper's protocol economics for one (k, n_r, n_c) point —
    closed-form, so sweeps are free:

    - rate: useful fraction of encoded bytes, k^2 / (n_r * n_c).
    - min_unrecoverable: the smallest withholding that defeats repair —
      an (n_r-k+1) x (n_c-k+1) sub-rectangle (every surviving row and
      column then has < k shards), the MDS generalization of the rate-
      1/2 (k+1)^2 bound.
    - catch_probability: min_unrecoverable / (n_r * n_c) — one uniform
      sample hits a minimal withholding at this rate.
    - samples_99: draws to 99% confidence at that per-sample catch.
    - commitment_bytes: one 32-byte root per row + column (the NMT
      commitment layout generalized to the rectangle).
    - proof_bytes_model: share + one axis Merkle path, ceil(log2 n_c)
      nodes of (32 + 2*NAMESPACE_SIZE) bytes — a MODEL of the NMT proof
      (the committed schemes' bench numbers are measured; this knob is
      analytic by design and labeled so in the bench output).
    """
    _check_kn(k, n_r)
    _check_kn(k, n_c)
    min_unrec = (n_r - k + 1) * (n_c - k + 1)
    catch = min_unrec / (n_r * n_c)
    node = 32 + 2 * appconsts.NAMESPACE_SIZE
    return {
        "k": k,
        "n_rows": n_r,
        "n_cols": n_c,
        "rate": (k * k) / (n_r * n_c),
        "min_unrecoverable": min_unrec,
        "catch_probability": catch,
        "samples_99": max(
            1, math.ceil(math.log(0.01) / math.log(1.0 - catch))),
        "commitment_bytes": (n_r + n_c) * 32,
        "proof_bytes_model":
            appconsts.SHARE_SIZE + math.ceil(math.log2(n_c)) * node,
    }
