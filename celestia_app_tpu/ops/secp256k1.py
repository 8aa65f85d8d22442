"""Batched secp256k1 ECDSA verification as vmapped JAX int-limb arithmetic.

The per-user hot path (tx admission) verifies signatures one at a time in
pure Python (chain/crypto.py `_py_verify`) — the GF(256) playbook from
ops/gf256.py and the RS pipeline applies here too: fixed-width limb
arithmetic with no data-dependent control flow, batched into one device
dispatch (the program-optimization framing of arXiv:2108.02692, carried
from GF(256) matmuls to mod-p field math).

Design:

- Field elements are 10 uint64 limbs of 26 bits (libsecp256k1's 10x26
  field layout): products of 30-bit-bounded limbs fit uint64 with room to
  accumulate a full 10-term convolution column, and secp256k1's
  pseudo-Mersenne prime p = 2^256 - 0x1000003D1 reduces by a few shifted
  adds (2^260 ≡ 0x1000003D10 (mod p), so the high convolution columns
  fold straight back into the low ones).
- Point arithmetic uses the COMPLETE addition formulas of Renes-Costello-
  Batina (EUROCRYPT 2016, algorithms 7/9 for a=0) in homogeneous
  projective coordinates: one formula covers generic addition, doubling,
  the identity, and P + (-P) with NO case analysis — branch-free by
  construction, which is what makes the batched path agree bit-for-bit
  with the scalar `_py_verify` reference on adversarial inputs instead of
  only on the happy path. The identity is (0 : 1 : 0).
- u1·G + u2·Q runs as a fixed-window (w=4) Strauss-Shamir double-scalar
  multiplication: 64 shared window steps of 4 doublings, one add from a
  per-lane Q table ([0..15]Q, identity included — the complete formula
  absorbs digit 0), and one add from a precomputed affine G table
  ([0..15]G module constants; digit 0 selected out, as the affine table
  cannot encode the identity).
- The final check avoids any modular inversion: x_affine(R) mod n == r
  iff X == r·Z or X == (r+n)·Z (mod p, when r+n < p), since n < p < 2n.
  R at infinity (Z ≡ 0) verifies False, exactly as `_py_verify`.

One `vmap`/`jit` dispatch verifies a whole batch and returns a bool lane
mask. uint64 requires x64 — enabled through the THREAD-LOCAL
`jax.enable_x64(True)` scope around trace and dispatch, so the
rest of the process keeps the default 32-bit world. Scalar host work per
signature (pubkey decompression, r/s range checks, s^-1 mod n, window
digits) stays in Python: it is microseconds against the milliseconds of
EC arithmetic the kernel amortizes.

`verify_batch` has exactly `_py_verify`'s semantics per lane (same
parsing, same range checks, no low-S or length policy — those are
`PublicKey.verify` wrapper policy, applied by chain/admission.py). Where
JAX is unavailable the scalar reference runs per lane, so callers always
get `_py_verify`-identical answers.
"""

from __future__ import annotations

import functools
import hashlib

import numpy as np

from celestia_app_tpu.chain import crypto as _crypto

_P = _crypto._P
_N = _crypto._N

# -- limb layout -------------------------------------------------------------

N_LIMBS = 10
LIMB_BITS = 26
_M26 = (1 << 26) - 1
_M22 = (1 << 22) - 1
# 2^256 ≡ C (mod p); 2^260 ≡ 16·C = R1·2^26 + R0
_C0, _C1 = 977, 64          # C = 0x1000003D1 = C1·2^26 + C0
_R0, _R1 = 15632, 1024      # 16·C = R1·2^26 + R0


_LIMB_POWS = (np.uint64(1) << np.arange(LIMB_BITS, dtype=np.uint64))


def _to_limbs(x: int) -> np.ndarray:
    bits = np.unpackbits(
        np.frombuffer(x.to_bytes(33, "little"), np.uint8),
        bitorder="little",
    )[: N_LIMBS * LIMB_BITS]
    return bits.reshape(N_LIMBS, LIMB_BITS).astype(np.uint64) @ _LIMB_POWS


def _from_limbs(l) -> int:
    return sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(l))


# p in the redundant "all limbs maximal" form (libsecp fe_negate's P∞):
# subtracting a magnitude-m element from 2(m+1)·P∞ can never borrow.
_P_INF = np.array(
    [0x3FFFC2F, 0x3FFFFBF] + [0x3FFFFFF] * 7 + [0x3FFFFF], dtype=np.uint64
)
_NEG = {m: (2 * (m + 1)) * _P_INF for m in (1, 2, 3)}
# 2^260 - p, for the conditional-subtract in full normalization
_K_COMP = _to_limbs((1 << 260) - _P)

WINDOW = 4
N_WINDOWS = 33            # w=4 windows covering the |k| < 2^132 GLV halves
G_WINDOW = 8
N_G_WINDOWS = 17          # w=8 windows covering the same range


def _digits(u: int, count: int, width: int) -> np.ndarray:
    """`count` `width`-bit windows of a scalar, most significant first."""
    nbytes = (count * width + 7) // 8
    bits = np.unpackbits(
        np.frombuffer(u.to_bytes(nbytes, "little"), np.uint8),
        bitorder="little",
    )[: count * width]
    pows = np.int32(1) << np.arange(width, dtype=np.int32)
    return (bits.reshape(count, width).astype(np.int32) @ pows)[::-1]


# ---------------------------------------------------------------------------
# GLV endomorphism: derived from first principles at import, then verified
# ---------------------------------------------------------------------------
# secp256k1 has j-invariant 0, so x -> beta·x (beta a primitive cube root
# of unity mod p) is an endomorphism acting as scalar multiplication by
# lambda (a cube root of unity mod n): (beta·x, y) = lambda·(x, y). A
# scalar u then splits as u = k1 + k2·lambda (mod n) with |k1|, |k2| on
# the order of sqrt(n), which HALVES the doubling chain of the Strauss
# ladder. Nothing here is a memorized constant: beta/lambda come from
# Fermat exponentiation, the matching (lambda vs lambda^2) is pinned by
# checking the action on G, and the lattice basis comes from the
# classic extended-Euclid construction (Guide to ECC, alg 3.74).


def _derive_glv() -> tuple[int, int]:
    def cube_root_of_unity(m: int) -> int:
        g = 2
        while True:
            w = pow(g, (m - 1) // 3, m)
            if w != 1:
                return w
            g += 1

    beta = cube_root_of_unity(_P)
    lam = cube_root_of_unity(_N)
    gx, gy = _crypto._GX, _crypto._GY
    for lam_c in (lam, pow(lam, 2, _N)):
        pt = _crypto._to_affine(_crypto._jac_mult(_crypto._G, lam_c))
        for beta_c in (beta, pow(beta, 2, _P)):
            if pt == (beta_c * gx % _P, gy):
                return lam_c, beta_c
    raise AssertionError("GLV cube-root pairing failed to verify on G")


_LAMBDA, _BETA = _derive_glv()


def _glv_basis() -> tuple[int, int, int, int]:
    """Two short lattice vectors (a, b) with a + b·lambda ≡ 0 (mod n)."""
    import math

    sq = math.isqrt(_N)
    rows = [(_N, 0), (_LAMBDA, 1)]
    while rows[-1][0] >= sq:
        q = rows[-2][0] // rows[-1][0]
        rows.append((rows[-2][0] - q * rows[-1][0],
                     rows[-2][1] - q * rows[-1][1]))
    a1, b1 = rows[-1][0], -rows[-1][1]
    q = rows[-2][0] // rows[-1][0]
    nxt = (rows[-2][0] - q * rows[-1][0], rows[-2][1] - q * rows[-1][1])
    cand = [(rows[-2][0], -rows[-2][1]), (nxt[0], -nxt[1])]
    a2, b2 = min(cand, key=lambda v: v[0] * v[0] + v[1] * v[1])
    for a, b in ((a1, b1), (a2, b2)):
        if (a + b * _LAMBDA) % _N:
            raise AssertionError("GLV basis vector not in the lattice")
    return a1, b1, a2, b2


_A1, _B1, _A2, _B2 = _glv_basis()


def _glv_split(u: int) -> tuple[int, int]:
    """u ≡ k1 + k2·lambda (mod n) with |k1|, |k2| ~ sqrt(n). The caller
    re-checks the congruence and the 2^132 bound per lane (falling back
    to the scalar path on any violation, which never fires in practice)."""
    c1 = (2 * _B2 * u + _N) // (2 * _N)     # round(b2·u / n)
    c2 = (-2 * _B1 * u + _N) // (2 * _N)    # round(-b1·u / n)
    k1 = u - c1 * _A1 - c2 * _A2
    k2 = -c1 * _B1 - c2 * _B2
    return k1, k2


# ---------------------------------------------------------------------------
# precomputed G tables (lazy: ~0.5 s of host point arithmetic, built on
# first use and kept for the process lifetime)
# ---------------------------------------------------------------------------
# For the G side both GLV halves use PER-POSITION w=8 tables, so G adds
# never need the shared doubling chain: entry (j, s, d) is ±d·2^(8j)·B
# for base B in {G, lambda·G}, with s selecting the negated-y mirror
# (negative GLV halves flip the point, not the digit).


@functools.lru_cache(maxsize=None)
def _g_pos_tables() -> np.ndarray:
    """(2, 17, 512, 2, 10): [base][position][sign·256 + digit][x, y]."""
    out = np.zeros((2, N_G_WINDOWS, 2 * 256, 2, N_LIMBS), dtype=np.uint64)
    for bi, base_scalar in enumerate((1, _LAMBDA)):
        base = _crypto._jac_mult(_crypto._G, base_scalar)
        for j in range(N_G_WINDOWS):
            acc = (0, 0, 0)
            for d in range(1, 256):
                acc = _crypto._jac_add(acc, base)
                x, y = _crypto._to_affine(acc)
                out[bi, j, d, 0] = _to_limbs(x)
                out[bi, j, d, 1] = _to_limbs(y)
                out[bi, j, 256 + d, 0] = out[bi, j, d, 0]
                out[bi, j, 256 + d, 1] = _to_limbs(_P - y)
            for _ in range(G_WINDOW):
                base = _crypto._jac_double(base)
    return out


def available() -> bool:
    try:
        import jax  # noqa: F401

        return True
    except ImportError:
        return False


# ---------------------------------------------------------------------------
# the kernel (everything below `_build` traces under enable_x64)
# ---------------------------------------------------------------------------
# Magnitude discipline (all bounds static, checked in comments):
#   fe_mul / fe_sub / fe_mul21 outputs are WEAK: limbs < 2^26 (+1 ulp on
#   the ripple tail), top limb < 2^22 + 1, value < 2p.  fe_add outputs
#   carry the summed magnitude.  Every multiplication input stays below
#   2^30 per limb, so convolution columns stay below 10·2^60 < 2^64.


def _kernel_fns():
    import jax
    import jax.numpy as jnp

    u64 = jnp.uint64

    def _shift1(c):
        """One limb up along the limb axis: [0, c0, ..., c_{n-2}]."""
        z = jnp.zeros_like(c[..., :1])
        return jnp.concatenate([z, c[..., :-1]], axis=-1)

    def _pass(x):
        """One parallel carry pass that first folds the top limb's
        >= 2^256 bits through C (so no overflow bit is ever dropped),
        then masks and shifts every limb's carry up one slot."""
        hi = x[..., 9] >> 22                 # all bits of weight >= 2^256
        x = x.at[..., 9].set(x[..., 9] & u64(_M22))
        x = x.at[..., 0].add(hi * u64(_C0))
        x = x.at[..., 1].add(hi * u64(_C1))
        return (x & u64(_M26)) + _shift1(x >> 26)

    # Bound discipline (all static, comments carry the proofs):
    #   M1   = _pass(_pass(·)) output: limbs < 2^26 + 2^9, top < 2^22 + 1
    #   sums of ≤ 3 M1 values stay subtractable through _NEG[3]
    #   LAZY = fe_sub output: limbs < 2^29.4 (no normalization at all)
    #   every fe_mul operand is ≤ LAZY + M1 sums < 2^29.6, so 10-term
    #   convolution columns stay < 10 · 2^59.2 < 2^62.6 < 2^64.
    neg3 = jnp.asarray(_NEG[3], dtype=jnp.uint64)

    def fe_mul(a, b):
        """Schoolbook convolution + pseudo-Mersenne fold; M1 output.

        Operands may be lazy (limbs < 2^30): column sums < 2^63. Shapes
        are (..., 10); independent multiplications are STACKED along the
        leading axis so one call amortizes the whole carry machinery."""
        cols = jnp.zeros(a.shape[:-1] + (2 * N_LIMBS,), jnp.uint64)
        for i in range(N_LIMBS):
            cols = cols.at[..., i : i + N_LIMBS].add(a[..., i : i + 1] * b)
        # one carry pass caps columns at 2^26 + 2^37, small enough for
        # the R0/R1 fold multipliers to stay under 2^64
        cols = (cols & u64(_M26)) + _shift1(cols >> 26)
        h = cols[..., N_LIMBS:]              # weights 2^260 · 2^26j
        l = (cols[..., :N_LIMBS] + h * u64(_R0) + _shift1(h) * u64(_R1))
        spill = h[..., 9] * u64(_R1)         # weight 2^260 again
        l = l.at[..., 0].add(spill * u64(_R0))  # < 2^61
        l = l.at[..., 1].add(spill * u64(_R1))
        return _pass(_pass(l))

    def fe_sub(a, b):
        """a - b (mod p), b any sum of ≤ 3 M1 values; LAZY output
        (limbs < 2^29.4) — safe directly as a fe_mul operand."""
        return a + (neg3 - b)

    def fe_mul21(a):
        """3b = 21 scaling (b = 7 for secp256k1); M1 output."""
        return _pass(a * u64(21))

    def fe_norm(x):
        """Full canonical (UNIQUE-limb) form: sequential carry
        propagation to strict 26-bit limbs (folding BOTH the top limb's
        >= 2^256 bits and the chain's 2^260 carry-out each pass), then
        one conditional subtract of p. Equality tests compare only
        these. Accepts any lazy element; shape (B, 10)."""
        for _ in range(3):                    # value < 2^256 after pass 3
            carry = jnp.zeros_like(x[..., 0])
            limbs = []
            for k in range(N_LIMBS):
                v = x[..., k] + carry
                limbs.append(v & u64(_M26))
                carry = v >> 26               # final: weight 2^260
            hi = limbs[9] >> 22               # weight 2^256
            limbs[9] = limbs[9] & u64(_M22)
            limbs[0] = limbs[0] + carry * u64(_R0) + hi * u64(_C0)
            limbs[1] = limbs[1] + carry * u64(_R1) + hi * u64(_C1)
            x = jnp.stack(limbs, axis=-1)
        carry = jnp.zeros_like(x[..., 0])
        d = []
        for k in range(N_LIMBS):
            v = x[..., k] + k_comp[k] + carry
            d.append(v & u64(_M26))
            carry = v >> 26
        ge = (carry > 0)[..., None]           # 1 iff x >= p
        return jnp.where(ge, jnp.stack(d, axis=-1), x)

    k_comp = jnp.asarray(_K_COMP, dtype=jnp.uint64)

    # -- complete point arithmetic (Renes-Costello-Batina, a=0, b3=21) ----
    # Points are (X, Y, Z) triples of (B, 10) limb arrays. The 12M of the
    # complete add and the 8M of the doubling run as TWO / THREE stacked
    # fe_mul calls: the formulas' independent products concatenate along
    # the lane axis, so the carry/fold machinery amortizes 6x.

    def _mul_stack(parts_a, parts_b):
        a = jnp.concatenate(parts_a, axis=0)
        b = jnp.concatenate(parts_b, axis=0)
        m = fe_mul(a, b)
        n = parts_a[0].shape[0]
        return [m[i * n : (i + 1) * n] for i in range(len(parts_a))]

    def pt_add(p, q):
        """Algorithm 7: complete addition, any P/Q including identity."""
        X1, Y1, Z1 = p
        X2, Y2, Z2 = q
        t0, t1, t2, ta, tb, tc = _mul_stack(
            [X1, Y1, Z1, X1 + Y1, Y1 + Z1, X1 + Z1],
            [X2, Y2, Z2, X2 + Y2, Y2 + Z2, X2 + Z2],
        )
        t3 = fe_sub(ta, t0 + t1)              # X1Y2 + X2Y1
        t4 = fe_sub(tb, t1 + t2)              # Y1Z2 + Y2Z1
        ty = fe_sub(tc, t0 + t2)              # X1Z2 + X2Z1
        t0_3 = (t0 + t0) + t0                 # 3·X1X2
        t2b = fe_mul21(t2)                    # 3b·Z1Z2
        z3p = t1 + t2b                        # Y1Y2 + 3bZ1Z2
        t1m = fe_sub(t1, t2b)                 # Y1Y2 - 3bZ1Z2
        y3b = fe_mul21(ty)                    # 3b·(X1Z2 + X2Z1)
        m0, m1, m2, m3, m4, m5 = _mul_stack(
            [t4, t3, y3b, t1m, t0_3, z3p],
            [y3b, t1m, t0_3, z3p, t3, t4],
        )
        X3 = fe_sub(m1, m0)                   # t3·t1m - t4·y3b
        Y3 = m3 + m2                          # t1m·z3p + y3b·t0_3
        Z3 = m5 + m4                          # z3p·t4 + t0_3·t3
        return (X3, Y3, Z3)

    def pt_dbl(p):
        """Algorithm 9: complete doubling (identity doubles to identity)."""
        X, Y, Z = p
        t0, t1, t2 = _mul_stack([Y, Y, Z], [Y, Z, Z])
        z3a = (t0 + t0) + (t0 + t0)
        z3a = z3a + z3a                       # 8·Y²
        t2b = fe_mul21(t2)                    # 3b·Z²
        x3, z3, txy = _mul_stack([t2b, t1, X], [z3a, z3a, Y])
        y3p = t0 + t2b
        t0s = fe_sub(t0, (t2b + t2b) + t2b)   # Y² - 9bZ²
        ma, mb = _mul_stack([t0s, t0s], [y3p, txy])
        Y3 = x3 + ma                          # t2b·z3a + t0s·y3p
        X3 = mb + mb                          # 2·t0s·txy
        return (X3, Y3, z3)

    beta_c = jnp.asarray(_to_limbs(_BETA), dtype=jnp.uint64)

    def verify_kernel(qx, qy, ydiff, kq1d, kq2d, kg1d, kg2d,
                      sg1, sg2, r_l, r2_l, has_r2):
        """The batched verifier: (B,...) arrays in, (B,) bool mask out.

        Computes u2·Q = |k1|·(±Q) + |k2|·(±λQ) over the shared 33-window
        doubling chain (the GLV halves), then folds in the G side from
        the per-position tables (no doubles needed there), and checks
        the x-coordinate equation projectively."""
        n = qx.shape[0]
        zero = jnp.zeros((n, N_LIMBS), jnp.uint64)
        one = zero.at[:, 0].set(u64(1))
        ident = (zero, one, zero)
        q = (qx, qy, one)
        # per-lane Q table: [0..15]·(±Q); entry 0 is the identity, which
        # the complete formula handles natively (no digit mask needed)
        tab = [ident, q]
        for d in range(2, 16):
            tab.append(pt_dbl(tab[d // 2]) if d % 2 == 0
                       else pt_add(tab[d - 1], q))
        qtab = tuple(
            jnp.stack([t[i] for t in tab], axis=1) for i in range(3)
        )  # 3 × (B, 16, 10)
        # λQ table via the endomorphism applied ENTRY-WISE: φ(d·Q) =
        # d·λQ = (β·X : ±Y : Z) — one stacked β·X multiply, a sign
        # select on Y when the two GLV halves disagree in sign, Z shared.
        lx = fe_mul(qtab[0].reshape(n * 16, N_LIMBS), beta_c)
        ly = jnp.where(ydiff[:, None, None], _pass(neg3 - qtab[1]), qtab[1])
        ltab = (lx.reshape(n, 16, N_LIMBS), ly, qtab[2])

        def gather(tab3, d):
            idx = d[:, None, None]
            return tuple(
                jnp.take_along_axis(c, idx, axis=1)[:, 0] for c in tab3
            )

        def body(i, acc):
            acc = jax.lax.fori_loop(0, WINDOW, lambda _j, a: pt_dbl(a), acc)
            d1 = jax.lax.dynamic_slice_in_dim(kq1d, i, 1, axis=1)[:, 0]
            acc = pt_add(acc, gather(qtab, d1))
            d2 = jax.lax.dynamic_slice_in_dim(kq2d, i, 1, axis=1)[:, 0]
            acc = pt_add(acc, gather(ltab, d2))
            return acc

        acc = jax.lax.fori_loop(0, N_WINDOWS, body, ident)

        # G side: affine entries from the (2, 17, 512, ...) const tables,
        # flattened so one take() resolves [base][position][sign·256+d]
        gtab = jnp.asarray(
            _g_pos_tables().reshape(2 * N_G_WINDOWS * 512, 2, N_LIMBS),
            dtype=jnp.uint64,
        )
        sbase1 = sg1.astype(jnp.int32) * 256
        sbase2 = sg2.astype(jnp.int32) * 256

        def g_body(j, acc):
            def one_add(acc, base_off, sbase, dig):
                d = jax.lax.dynamic_slice_in_dim(dig, j, 1, axis=1)[:, 0]
                idx = base_off + j * 512 + sbase + d
                tg = jnp.take(gtab, idx, axis=0)   # (B, 2, 10)
                added = pt_add(acc, (tg[:, 0], tg[:, 1], one))
                # affine tables cannot encode the identity: digit 0 keeps acc
                keep = (d == 0)[:, None]
                return tuple(
                    jnp.where(keep, a, b) for a, b in zip(acc, added)
                )

            acc = one_add(acc, 0, sbase1, kg1d)
            acc = one_add(acc, N_G_WINDOWS * 512, sbase2, kg2d)
            return acc

        X, Y, Z = jax.lax.fori_loop(0, N_G_WINDOWS, g_body, acc)

        # x_affine mod n == r  ⇔  X == r·Z or X == (r+n)·Z (mod p); the
        # identity (Z ≡ 0) verifies False, as in _py_verify
        rz, r2z = _mul_stack([r_l, r2_l], [Z, Z])
        xn = fe_norm(X)
        eq1 = jnp.all(xn == fe_norm(rz), axis=-1)
        eq2 = jnp.all(xn == fe_norm(r2z), axis=-1) & has_r2
        z_zero = jnp.all(fe_norm(Z) == u64(0), axis=-1)
        return (~z_zero) & (eq1 | eq2)

    return verify_kernel


@functools.lru_cache(maxsize=None)
def jitted_verify(n: int):
    """Compiled batch verifier for one padded lane count (bucketed so the
    jit cache stays bounded). Instrumented like every jitted factory
    (obs/jax_profile): the cache miss counts one ``jax.compilations``."""
    import jax

    from celestia_app_tpu.obs import jax_profile

    jax_profile.note_compile("secp256k1.verify", n)
    return jax_profile.instrument(f"secp256k1.verify[{n}]",
                                  jax.jit(_kernel_fns()))


from celestia_app_tpu.obs import jax_profile as _jax_profile  # noqa: E402

_jax_profile.register_cache(jitted_verify)
del _jax_profile


# ---------------------------------------------------------------------------
# host orchestration
# ---------------------------------------------------------------------------

_MIN_BUCKET = 32
# 512 lanes keeps the stacked (3072, 20) uint64 intermediates inside L2
# on the CPU backend (measured fastest: larger dispatches regress)
MAX_DISPATCH = 512


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return b


def padded_lanes(n: int) -> int:
    """The lanes the device runs for n signatures: full MAX_DISPATCH
    chunks plus the last chunk's bucket."""
    full, rest = divmod(n, MAX_DISPATCH)
    return full * MAX_DISPATCH + (_bucket(rest) if rest else 0)


_SLOW = object()  # sentinel: decomposition irregularity -> scalar fallback


def _prep(pubkey: bytes, signature: bytes, message: bytes):
    """The scalar prefix of _py_verify: parse, range-check, compute
    (u1, u2) = (z/s, r/s) mod n, and GLV-split both scalars. None =
    verifies False with no EC work; _SLOW = verify on the scalar path."""
    q = _crypto._decompress(pubkey)
    if q is None:
        return None
    r = int.from_bytes(signature[:32], "big")
    s = int.from_bytes(signature[32:], "big")
    if not (1 <= r < _N and 1 <= s < _N):
        return None
    z = int.from_bytes(hashlib.sha256(message).digest(), "big") % _N
    w = pow(s, -1, _N)
    u1, u2 = z * w % _N, r * w % _N
    k1a, k2a = _glv_split(u1)
    k1b, k2b = _glv_split(u2)
    for u, k1, k2 in ((u1, k1a, k2a), (u2, k1b, k2b)):
        if (k1 + k2 * _LAMBDA - u) % _N or max(
            abs(k1), abs(k2)
        ).bit_length() > WINDOW * N_WINDOWS:
            return _SLOW  # never expected; the scalar path stays correct
    # Q side rides the doubling chain: base point sign-adjusted for k1b,
    # the λQ table's Y sign-flipped on device when k2b's sign differs
    qy = q[1] if k1b >= 0 else _P - q[1]
    return (
        q[0], qy, (k2b < 0) != (k1b < 0),
        abs(k1b), abs(k2b), abs(k1a), abs(k2a),
        int(k1a < 0), int(k2a < 0), r,
    )


def verify_batch(items, backend: str = "auto") -> np.ndarray:
    """Verify a batch of (pubkey33, signature, message) triples in one
    device dispatch per MAX_DISPATCH chunk; returns a bool lane mask with
    exactly `_py_verify`'s per-item semantics. backend: "auto" (device
    when JAX imports, else scalar) | "device" | "scalar".

    Two spans price the device path's halves: `admission.sig_prep` (the
    host's per-lane Python: decompression, s^-1, the GLV split, limb and
    digit packing) and `admission.sig_dispatch` (device call -> mask on
    the host; attr `lanes` = the padded bucket). The counter
    `admission.batch_padded_lanes` adds each dispatch's bucket."""
    from celestia_app_tpu import obs
    from celestia_app_tpu.utils import telemetry

    out = np.zeros(len(items), dtype=bool)
    if not items:
        return out
    use_device = backend == "device" or (backend == "auto" and available())
    if not use_device:
        for i, (pk, sig, msg) in enumerate(items):
            out[i] = _crypto._py_verify(pk, sig, msg)
        return out

    with obs.span("admission.sig_prep", n_sigs=len(items)):
        preps = [_prep(pk, sig, msg) for pk, sig, msg in items]
        lanes = []
        for i, p in enumerate(preps):
            if p is _SLOW:
                out[i] = _crypto._py_verify(*items[i])
            elif p is not None:
                lanes.append(i)
        chunks = [lanes[start : start + MAX_DISPATCH]
                  for start in range(0, len(lanes), MAX_DISPATCH)]
        packed = [_pack([preps[i] for i in chunk]) for chunk in chunks]
    for chunk, arrays in zip(chunks, packed):
        bucket = arrays[0].shape[0]
        with obs.span("admission.sig_dispatch", lanes=bucket):
            out[chunk] = _dispatch(arrays)[: len(chunk)]
        telemetry.incr("admission.batch_padded_lanes", by=bucket)
    return out


def _pack(preps) -> tuple[np.ndarray, ...]:
    """The kernel's twelve arguments for one chunk, padded to its bucket."""
    n = len(preps)
    b = _bucket(n)
    qx = np.zeros((b, N_LIMBS), np.uint64)
    qy = np.zeros((b, N_LIMBS), np.uint64)
    ydiff = np.zeros((b,), bool)
    kq1d = np.zeros((b, N_WINDOWS), np.int32)
    kq2d = np.zeros((b, N_WINDOWS), np.int32)
    kg1d = np.zeros((b, N_G_WINDOWS), np.int32)
    kg2d = np.zeros((b, N_G_WINDOWS), np.int32)
    sg1 = np.zeros((b,), np.int32)
    sg2 = np.zeros((b,), np.int32)
    r_l = np.zeros((b, N_LIMBS), np.uint64)
    r2_l = np.zeros((b, N_LIMBS), np.uint64)
    has_r2 = np.zeros((b,), bool)
    for i, (x, y, yd, k1b, k2b, k1a, k2a, s1, s2, r) in enumerate(preps):
        qx[i] = _to_limbs(x)
        qy[i] = _to_limbs(y)
        ydiff[i] = yd
        kq1d[i] = _digits(k1b, N_WINDOWS, WINDOW)
        kq2d[i] = _digits(k2b, N_WINDOWS, WINDOW)
        # G digits run LSB-first: position table j carries d·2^(8j)·base
        kg1d[i] = _digits(k1a, N_G_WINDOWS, G_WINDOW)[::-1]
        kg2d[i] = _digits(k2a, N_G_WINDOWS, G_WINDOW)[::-1]
        sg1[i] = s1
        sg2[i] = s2
        r_l[i] = _to_limbs(r)
        if r + _N < _P:
            r2_l[i] = _to_limbs(r + _N)
            has_r2[i] = True
    return (qx, qy, ydiff, kq1d, kq2d, kg1d, kg2d, sg1, sg2, r_l, r2_l,
            has_r2)


def _dispatch(arrays) -> np.ndarray:
    """One device call over a packed chunk; the whole bucket's mask."""
    import jax

    with jax.enable_x64(True):
        return np.asarray(jitted_verify(arrays[0].shape[0])(*arrays))
