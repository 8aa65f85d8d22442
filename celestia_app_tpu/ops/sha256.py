"""Vectorized SHA-256 for JAX: hash N same-length messages in one launch.

The DA pipeline's hashing workload (reference: `crypto/sha256` inside the nmt
hasher, pkg/wrapper/nmt_wrapper.go) is millions of *independent* fixed-length
messages per block — NMT leaves are 542-byte preimages, inner nodes 181 bytes,
binary-Merkle nodes 65 bytes. That maps to the TPU VPU as pure u32 lane
arithmetic: one traced program hashing a whole tree level at a time, with the
64-round compression unrolled so XLA fuses it into a single elementwise chain.

Semantics match FIPS 180-4 exactly (golden-tested against hashlib).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from celestia_app_tpu.ops.sha256_consts import H0_WORDS, K_WORDS

_K = np.array(K_WORDS, dtype=np.uint32)
_H0 = np.array(H0_WORDS, dtype=np.uint32)


def _rotr(x: jax.Array, n) -> jax.Array:
    n = jnp.asarray(n, dtype=jnp.uint32)
    return (x >> n) | (x << (np.uint32(32) - n))


def _compress(state: jax.Array, block_words: jax.Array) -> jax.Array:
    """One SHA-256 block over N lanes: state (8, N) u32, block (16, N) u32.

    Rolled with fori_loop so the traced graph stays small — hashing is called
    at every tree level of every pipeline, and an unrolled 64-round body
    multiplies XLA compile time by ~100x for zero VPU runtime benefit.
    """
    n = state.shape[1]
    w = jnp.zeros((64, n), dtype=jnp.uint32).at[:16].set(block_words)

    def schedule(i, w):
        s0 = _rotr(w[i - 15], 7) ^ _rotr(w[i - 15], 18) ^ (w[i - 15] >> np.uint32(3))
        s1 = _rotr(w[i - 2], 17) ^ _rotr(w[i - 2], 19) ^ (w[i - 2] >> np.uint32(10))
        return w.at[i].set(w[i - 16] + s0 + w[i - 7] + s1)

    w = jax.lax.fori_loop(16, 64, schedule, w)
    k_const = jnp.asarray(_K)

    def round_fn(i, s):
        a, b, c, d, e, f, g, h = s
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k_const[i] + w[i]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        return (t1 + t2, a, b, c, d + t1, e, f, g)

    out = jax.lax.fori_loop(0, 64, round_fn, tuple(state))
    return state + jnp.stack(out)


def _pad_len(msg_len: int) -> int:
    return ((msg_len + 8) // 64 + 1) * 64


def use_pallas() -> bool:
    """Pallas kernel on the TPU backend; jnp scan path anywhere else.

    CELESTIA_SHA256_IMPL=pallas|jnp forces one (tests pin both kernels
    against each other, and the compile tests force the Pallas kernel
    for a described TPU while the process's backend is the CPU).
    """
    impl = os.environ.get("CELESTIA_SHA256_IMPL", "")
    if impl == "pallas":
        return True
    if impl == "jnp":
        return False
    return jax.default_backend() == "tpu"


def sha256(msgs: jax.Array) -> jax.Array:
    """SHA-256 of N equal-length messages: (N, L) uint8 -> (N, 32) uint8.

    L is static; padding and block count are resolved at trace time. Blocks
    are consumed by the Pallas register kernel on TPU (sha256_pallas.py) or
    a lax.scan of compressions on CPU.
    """
    n, msg_len = msgs.shape
    total = _pad_len(msg_len)
    tail = np.zeros(total - msg_len, dtype=np.uint8)
    tail[0] = 0x80
    bit_len = msg_len * 8
    # trace-time constant: L is static, so the padding tail is host
    # numpy over Python ints, baked into the traced program
    tail[-8:] = np.frombuffer(bit_len.to_bytes(8, "big"), dtype=np.uint8)  # lint: disable=jit-purity
    padded = jnp.concatenate(
        [msgs, jnp.broadcast_to(jnp.asarray(tail), (n, tail.shape[0]))], axis=1
    )
    # Big-endian u32 words, grouped per block: (nblocks, 16, N)
    quads = padded.reshape(n, total // 4, 4).astype(jnp.uint32)
    be = jnp.array([1 << 24, 1 << 16, 1 << 8, 1], dtype=jnp.uint32)
    words = jnp.sum(quads * be, axis=-1, dtype=jnp.uint32)  # (N, total/4)
    blocks = jnp.transpose(words.reshape(n, total // 64, 16), (1, 2, 0))

    if use_pallas() and n >= 1024:
        # Pallas register kernel for the big batched levels; tiny upper tree
        # levels (N < one 1024-lane tile) stay on the jnp path rather than
        # paying a nearly-all-padding kernel dispatch per level.
        from celestia_app_tpu.ops import sha256_pallas

        state = sha256_pallas.compress_words(blocks)
    else:
        state0 = jnp.broadcast_to(jnp.asarray(_H0)[:, None], (8, n))

        def step(state, block_words):
            return _compress(state, block_words), None

        state, _ = jax.lax.scan(step, state0, blocks)
    digest_words = jnp.transpose(state)  # (N, 8) u32
    shifts = jnp.array([24, 16, 8, 0], dtype=jnp.uint32)
    out = (digest_words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(0xFF)
    return out.reshape(n, 32).astype(jnp.uint8)


EMPTY_SHA256 = bytes.fromhex(
    "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855"
)
