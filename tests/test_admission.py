"""The admission plane (PR 6): batched secp256k1 verification + the
verified-sig cache.

Tier-1 because any disagreement between the batched verifier and the
scalar `_py_verify` reference is a CONSENSUS FORK: a block one validator
accepts and another rejects. The differential test therefore runs the
full adversarial vector set, and the telemetry tests pin the acceptance
criterion that a CheckTx-admitted tx is never re-verified in
ProcessProposal, delivery, or WAL replay.
"""

from __future__ import annotations

import hashlib
import os
import random
import shutil
import tempfile

import pytest

from celestia_app_tpu.chain import admission, crypto
from celestia_app_tpu.chain.app import App
from celestia_app_tpu.chain.crypto import PrivateKey
from celestia_app_tpu.chain.node import Node
from celestia_app_tpu.chain.tx import MsgSend
from celestia_app_tpu.client.tx_client import Signer
from celestia_app_tpu.ops import secp256k1 as fast
from celestia_app_tpu.utils import telemetry


def _counter(name: str) -> int:
    return telemetry.snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------------
# differential property test: batched verifier vs _py_verify
# ---------------------------------------------------------------------------


def _adversarial_vectors() -> list[tuple[bytes, bytes, bytes]]:
    """Valid, corrupted, malformed, and edge-case-scalar vectors. Kept
    under 32 so every dispatch in this module shares ONE jit bucket."""
    rng = random.Random(1234)
    vecs: list[tuple[bytes, bytes, bytes]] = []
    keys = [PrivateKey.from_seed(b"adv-%d" % i) for i in range(4)]
    # valid signatures across keys and messages
    for i, priv in enumerate(keys):
        msg = b"adversarial-%d" % i
        vecs.append((priv.public_key().compressed, priv.sign(msg), msg))
    pub = keys[0].public_key().compressed
    sig = crypto.PrivateKey.from_seed(b"adv-0").sign(b"adversarial-0")
    # single bit flips through r and s
    for pos in (0, 15, 31, 32, 47, 63):
        bad = bytearray(sig)
        bad[pos] ^= 1 << rng.randrange(8)
        vecs.append((pub, bytes(bad), b"adversarial-0"))
    # wrong message / truncated message
    vecs.append((pub, sig, b"adversarial-1"))
    vecs.append((pub, sig, b""))
    # high-S (valid at the _py_verify layer; the wrapper policy rejects)
    s = int.from_bytes(sig[32:], "big")
    vecs.append((pub, sig[:32] + (crypto._N - s).to_bytes(32, "big"),
                 b"adversarial-0"))
    # r/s edge scalars: 0, n, n+1, huge
    r32, s32 = sig[:32], sig[32:]
    nb = crypto._N.to_bytes(32, "big")
    vecs.append((pub, b"\x00" * 32 + s32, b"adversarial-0"))
    vecs.append((pub, r32 + b"\x00" * 32, b"adversarial-0"))
    vecs.append((pub, nb + s32, b"adversarial-0"))
    vecs.append((pub, r32 + nb, b"adversarial-0"))
    vecs.append((pub, b"\xff" * 64, b"adversarial-0"))
    # malformed signature lengths (sliced exactly as _py_verify slices)
    vecs.append((pub, sig[:63], b"adversarial-0"))
    vecs.append((pub, sig + b"\x00", b"adversarial-0"))
    vecs.append((pub, b"", b"adversarial-0"))
    # the point-at-infinity construction: Q = G, r = -z mod n makes
    # u1·G + u2·Q the identity, which must verify False
    g_pub = crypto._compress(crypto._GX, crypto._GY)
    z = int.from_bytes(hashlib.sha256(b"inf").digest(), "big") % crypto._N
    vecs.append((
        g_pub,
        ((-z) % crypto._N).to_bytes(32, "big") + (5).to_bytes(32, "big"),
        b"inf",
    ))
    # non-canonical / invalid pubkey encodings
    vecs.append((b"\x04" + pub[1:], sig, b"adversarial-0"))   # bad prefix
    vecs.append((b"\x00" + pub[1:], sig, b"adversarial-0"))
    vecs.append((b"\x02" + crypto._P.to_bytes(32, "big"), sig,
                 b"adversarial-0"))                           # x >= p
    x = 1
    while crypto._decompress(b"\x02" + x.to_bytes(32, "big")) is not None:
        x += 1                                                # x off-curve
    vecs.append((b"\x02" + x.to_bytes(32, "big"), sig, b"adversarial-0"))
    vecs.append((pub[:32], sig, b"adversarial-0"))            # 32 bytes
    vecs.append((pub + b"\x00", sig, b"adversarial-0"))       # 34 bytes
    vecs.append((b"", sig, b"adversarial-0"))
    assert len(vecs) <= 32
    return vecs


def test_batched_agrees_with_py_verify_on_adversarial_vectors():
    vecs = _adversarial_vectors()
    ref = [crypto._py_verify(pk, sg, msg) for pk, sg, msg in vecs]
    # the suite must contain both verdicts or it proves nothing
    assert True in ref and False in ref
    got = fast.verify_batch(vecs)
    assert list(got) == ref
    # the scalar fallback path is the reference by construction
    got_scalar = fast.verify_batch(vecs, backend="scalar")
    assert list(got_scalar) == ref


def test_batched_agrees_on_random_valid_and_flipped():
    rng = random.Random(7)
    vecs, ref = [], []
    for i in range(24):
        priv = PrivateKey.from_seed(b"rnd-%d" % i)
        pk = priv.public_key().compressed
        msg = b"rand-msg-%d" % i
        sg = priv.sign(msg)
        if i % 3 == 1:
            bad = bytearray(sg)
            bad[rng.randrange(64)] ^= 1 << rng.randrange(8)
            sg = bytes(bad)
        if i % 5 == 2:
            msg += b"?"
        vecs.append((pk, sg, msg))
        ref.append(crypto._py_verify(pk, sg, msg))
    assert list(fast.verify_batch(vecs)) == ref


def test_glv_split_roundtrip():
    rng = random.Random(99)
    for _ in range(200):
        u = rng.randrange(crypto._N)
        k1, k2 = fast._glv_split(u)
        assert (k1 + k2 * fast._LAMBDA - u) % crypto._N == 0
        assert max(abs(k1), abs(k2)).bit_length() <= 132


# ---------------------------------------------------------------------------
# the two-phase admission plane
# ---------------------------------------------------------------------------


def _fresh_node(n_accounts: int = 8, chain: str = "admission-test"):
    privs = [PrivateKey.from_seed(b"adm-acct-%d" % i)
             for i in range(n_accounts)]
    addrs = [p.public_key().address() for p in privs]
    # a device-class engine: host-engine apps keep to the scalar path
    app = App(chain_id=chain, engine="auto")
    app.init_chain({
        "time_unix": 1_700_000_000.0,
        "accounts": [{"address": a.hex(), "balance": 10**12}
                     for a in addrs],
        "validators": [{"operator": addrs[0].hex(), "power": 10}],
    })
    signer = Signer(chain)
    for i, p in enumerate(privs):
        signer.add_account(p, number=i)
    return Node(app), signer, privs, addrs


def _send_raws(signer, addrs, rounds: int = 1) -> list[bytes]:
    raws = []
    for _ in range(rounds):
        for i, a in enumerate(addrs):
            tx = signer.create_tx(
                a, [MsgSend(a, addrs[(i + 1) % len(addrs)], 1)],
                fee=2000, gas_limit=100_000,
            )
            signer.accounts[a].sequence += 1
            raws.append(tx.encode())
    return raws


def test_checktx_admitted_txs_never_reverified(monkeypatch):
    """THE acceptance criterion: after batched CheckTx admission, neither
    PrepareProposal's ante filter, ProcessProposal, nor FinalizeBlock
    runs a single scalar signature verification — every phase hits the
    verified-sig cache (asserted via the admission.* telemetry counters)."""
    monkeypatch.setattr(admission, "MIN_DEVICE_BATCH", 4)
    node, signer, _privs, addrs = _fresh_node()
    raws = _send_raws(signer, addrs)

    scalar0 = _counter("admission.sig_scalar_verified")
    batch0 = _counter("admission.batch_verified")
    res = node.broadcast_txs(raws)
    assert all(r.code == 0 for r in res)
    if fast.available():
        # phase 1 verified every signature in one dispatch; the ante saw
        # only cache hits — zero scalar verifications at admission
        assert _counter("admission.batch_verified") - batch0 == len(raws)
        assert _counter("admission.sig_scalar_verified") == scalar0

    scalar1 = _counter("admission.sig_scalar_verified")
    hits1 = _counter("admission.sig_cache_hits")
    block, results = node.produce_block(t=1_700_000_001.0)
    assert len(block.txs) == len(raws)
    assert all(r.code == 0 for r in results)
    # prepare filter + process_proposal + finalize delivery: all cached
    assert _counter("admission.sig_scalar_verified") == scalar1
    assert _counter("admission.sig_cache_hits") - hits1 >= 3 * len(raws)


def test_wal_replay_prevalidates_in_batch(monkeypatch):
    """Crash recovery re-verifies block signatures BATCHED (one dispatch
    per replayed block), never through the scalar ante path."""
    monkeypatch.setattr(admission, "MIN_DEVICE_BATCH", 4)
    from celestia_app_tpu.chain import consensus as cons

    tmp = tempfile.mkdtemp(prefix="admission-wal-")
    try:
        priv = PrivateKey.from_seed(b"adm-wal")
        genesis = {
            "time_unix": 1_700_000_000.0,
            "accounts": [],
            "validators": [
                {"operator": priv.public_key().address().hex(), "power": 10,
                 "pubkey": priv.public_key().compressed.hex()}
            ],
        }
        chain = "admission-wal"
        senders = [PrivateKey.from_seed(b"adm-wal-%d" % i) for i in range(4)]
        addrs = [p.public_key().address() for p in senders]
        genesis["accounts"] = [
            {"address": a.hex(), "balance": 10**12} for a in addrs
        ]
        data_dir = os.path.join(tmp, "val0")
        node = cons.ValidatorNode("val0", priv, genesis, chain,
                                  data_dir=data_dir)
        net = cons.LocalNetwork([node])
        signer = Signer(chain)
        for i, p in enumerate(senders):
            signer.add_account(p, number=i)
        t = 1_700_000_000.0
        for _h in range(3):
            for res in node.add_txs(_send_raws(signer, addrs)):
                assert res.code == 0
            t += 1.0
            net.produce_height(t=t)
        committed = node.app.height
        node.app.close()

        # crash: lose the last 2 durable commits, keep the WAL
        from celestia_app_tpu.chain.storage import ChainDB

        db = ChainDB(data_dir)
        db.delete_above(committed - 2)
        db.backend.set_latest(committed - 2)
        db.close()

        node2 = cons.ValidatorNode("val0", priv, genesis, chain,
                                   data_dir=data_dir, engine="auto")
        node2.app.load()
        assert node2.app.height == committed - 2
        scalar0 = _counter("admission.sig_scalar_verified")
        batch0 = _counter("admission.batch_dispatches")
        assert node2.replay_wal() == 2
        assert node2.app.height == committed
        if fast.available():
            # replayed blocks' sigs went through batched prevalidation;
            # the delivery ante saw only cache hits
            assert _counter("admission.sig_scalar_verified") == scalar0
            assert _counter("admission.batch_dispatches") > batch0
        node2.app.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def test_two_phase_admission_equivalent_to_per_tx(monkeypatch):
    """The batched path must be a pure optimization: identical TxResults,
    identical pool contents, identical reap order vs per-tx admission."""
    monkeypatch.setattr(admission, "MIN_DEVICE_BATCH", 4)
    node_a, signer_a, _p, addrs = _fresh_node(chain="adm-eq")
    node_b, signer_b, _p2, _addrs2 = _fresh_node(chain="adm-eq")
    raws = _send_raws(signer_a, addrs, rounds=2)
    res_a = [node_a.broadcast_tx(raw) for raw in raws]       # scalar path
    res_b = node_b.broadcast_txs(raws)                        # two-phase
    assert [r.code for r in res_a] == [r.code for r in res_b]
    assert [r.log for r in res_a] == [r.log for r in res_b]
    assert node_a.pool.raws() == node_b.pool.raws()
    assert node_a._reap() == node_b._reap()


def test_prevalidation_never_admits_a_bad_signature(monkeypatch):
    """A corrupted signature in a batch must fail CheckTx exactly as on
    the scalar path — batch verification fills the cache with successes
    only, and the ante remains the authority."""
    monkeypatch.setattr(admission, "MIN_DEVICE_BATCH", 4)
    node, signer, _p, addrs = _fresh_node(chain="adm-bad")
    raws = _send_raws(signer, addrs)
    bad = bytearray(raws[3])
    bad[-7] ^= 0x40  # flip a signature bit (sig is the tx tail)
    raws[3] = bytes(bad)
    res = node.broadcast_txs(raws)
    codes = [r.code for r in res]
    assert codes[3] == 1
    assert "signature" in res[3].log or "decode" in res[3].log.lower() \
        or "truncated" in res[3].log.lower()
    # every other tx is unaffected by the bad lane
    assert [c for i, c in enumerate(codes) if i != 3] == [0] * 7


# ---------------------------------------------------------------------------
# cache mechanics + the decompression LRU satellite
# ---------------------------------------------------------------------------


def test_verified_sig_cache_is_bounded_lru():
    cache = admission.VerifiedSigCache(maxsize=4)
    keys = [admission.sig_key(b"%d" % i, b"s", b"m") for i in range(6)]
    for k in keys[:4]:
        cache.put(k)
    assert cache.hit(keys[0])            # refresh 0 -> evict 1 next
    cache.put(keys[4])
    assert not cache.hit(keys[1])
    assert cache.hit(keys[0]) and cache.hit(keys[4])
    assert len(cache) == 4


def test_sig_key_is_framing_safe():
    assert admission.sig_key(b"ab", b"c", b"") != \
        admission.sig_key(b"a", b"bc", b"")
    assert admission.sig_key(b"", b"", b"x") != \
        admission.sig_key(b"x", b"", b"")


def test_pubkey_decompression_is_cached():
    priv = PrivateKey.from_seed(b"lru-probe")
    pub = priv.public_key().compressed
    crypto._decompress.cache_clear()
    before = crypto._decompress.cache_info()
    assert crypto._decompress(pub) is not None
    assert crypto._decompress(pub) is not None
    after = crypto._decompress.cache_info()
    assert after.hits - before.hits >= 1
    assert after.misses - before.misses == 1
    # invalid encodings cache too (a malformed-key flood costs one
    # attempt per distinct key), and stay None
    assert crypto._decompress(b"\x02" + crypto._P.to_bytes(32, "big")) is None
    assert crypto._decompress(b"\x02" + crypto._P.to_bytes(32, "big")) is None


def test_extract_sig_item_policies():
    node, signer, _p, addrs = _fresh_node(chain="adm-extract")
    raw = _send_raws(signer, addrs)[0]
    item = admission.extract_sig_item(node.app, raw)
    assert item is not None
    pk, sig, doc = item
    assert len(pk) == 33 and len(sig) == 64
    assert crypto.PublicKey(pk).verify(sig, doc)
    # junk raw bytes extract as None, not an exception
    assert admission.extract_sig_item(node.app, b"\x01\x02\x03") is None
