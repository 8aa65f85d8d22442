"""Pallas SHA-256 kernel: the kernel body's math vs hashlib.

Interpret-mode pallas_call is unusable on this CPU (the inlined 64-round
kernel makes XLA's CPU backend compile for minutes), so the kernel *body* is
driven directly with mock Refs under jax.disable_jit() — that executes the
exact arithmetic the TPU kernel runs (rolling 16-word schedule window,
unrolled rounds, multi-block fori_loop) eagerly against numpy buffers. The
pallas_call plumbing itself (BlockSpec layout) is compiled for a v5e by
tests/test_tpu_compile.py and runs on the chip in every benchmark cell.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from celestia_app_tpu.ops import sha256_pallas as sp


class _MockRef:
    def __init__(self, a):
        self.a = a

    def __getitem__(self, idx):
        return self.a[idx]

    def __setitem__(self, idx, v):
        self.a[idx] = np.asarray(v)


def _pack_blocks(msgs: np.ndarray) -> tuple[np.ndarray, int]:
    """FIPS padding + big-endian word packing, like ops/sha256.sha256."""
    n, msg_len = msgs.shape
    total = ((msg_len + 8) // 64 + 1) * 64
    tail = np.zeros(total - msg_len, dtype=np.uint8)
    tail[0] = 0x80
    tail[-8:] = np.frombuffer((msg_len * 8).to_bytes(8, "big"), dtype=np.uint8)
    padded = np.concatenate([msgs, np.broadcast_to(tail, (n, len(tail)))], axis=1)
    quads = padded.reshape(n, total // 4, 4).astype(np.uint32)
    be = np.array([1 << 24, 1 << 16, 1 << 8, 1], dtype=np.uint32)
    words = (quads * be).sum(axis=-1).astype(np.uint32)
    return words.reshape(n, total // 64, 16).transpose(1, 2, 0), total // 64


def test_kernel_body_matches_hashlib():
    rng = np.random.default_rng(0)
    with jax.disable_jit():
        # NMT leaf (9 blocks), NMT inner (3), binary-Merkle node (2)
        for msg_len, n in [(542, 3), (181, 5), (65, 2)]:
            msgs = rng.integers(0, 256, (n, msg_len), dtype=np.uint8)
            blocks, nb = _pack_blocks(msgs)
            x = np.zeros((16 * nb, 1, sp.SUBLANES, sp.LANES), np.uint32)
            x.reshape(16 * nb, sp.TILE)[:, :n] = blocks.reshape(nb * 16, n)
            o = np.zeros((8, 1, sp.SUBLANES, sp.LANES), np.uint32)
            sp._kernel(nb, _MockRef(jnp.asarray(x)), _MockRef(o))
            state = o.reshape(8, sp.TILE)[:, :n]
            got = state.T.astype(">u4").tobytes()
            want = b"".join(
                hashlib.sha256(msgs[i].tobytes()).digest() for i in range(n)
            )
            assert got == want, msg_len


def test_compress_words_pad_slice_layout():
    """compress_words' lane padding/reshape agrees with the kernel layout:
    a second message in lane 1 must produce its own digest, and padding
    lanes must not disturb real lanes."""
    rng = np.random.default_rng(1)
    msgs = rng.integers(0, 256, (2, 65), dtype=np.uint8)
    blocks, nb = _pack_blocks(msgs)

    # emulate compress_words' internal layout transform, then run the body
    n = 2
    n_pad = sp.TILE
    x = np.zeros((nb * 16, n_pad), dtype=np.uint32)
    x[:, :n] = blocks.reshape(nb * 16, n)
    x = x.reshape(nb * 16, 1, sp.SUBLANES, sp.LANES)
    o = np.zeros((8, 1, sp.SUBLANES, sp.LANES), np.uint32)
    with jax.disable_jit():
        sp._kernel(nb, _MockRef(jnp.asarray(x)), _MockRef(o))
    state = o.reshape(8, n_pad)[:, :n]
    for i in range(2):
        assert state[:, i].astype(">u4").tobytes() == hashlib.sha256(
            msgs[i].tobytes()
        ).digest()
