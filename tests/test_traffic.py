"""The traffic plane (PR 15): the verified-commitment cache + the
sustained-load txsim.

Tier-1 because the commitment cache sits on the consensus path: a wrong
cached commitment (or a framing collision between two blobs) would let a
CheckTx-admitted tx and a ProcessProposal revalidation disagree — a
consensus fork. The telemetry tests pin the acceptance criterion that a
commitment checked at admission is NEVER recomputed at
PrepareProposal/ProcessProposal/commit/WAL replay, the differential
tests pin cached ≡ cold byte identity on both engines, and the
Byzantine test pins that a warm cache can only skip recomputes that
would AGREE (a mismatching claim still rejects).
"""

from __future__ import annotations

import os
import shutil
import tempfile

import numpy as np
import pytest

from celestia_app_tpu import appconsts
from celestia_app_tpu.chain import admission, blob_validation
from celestia_app_tpu.chain.app import App
from celestia_app_tpu.chain.blob_validation import BlobTxError
from celestia_app_tpu.chain.crypto import PrivateKey
from celestia_app_tpu.chain.node import Node
from celestia_app_tpu.client.tx_client import Signer
from celestia_app_tpu.da import blob as blob_mod
from celestia_app_tpu.da import commitment as commitment_mod
from celestia_app_tpu.da.blob import Blob
from celestia_app_tpu.da.namespace import Namespace
from celestia_app_tpu.utils import telemetry

THRESHOLD = appconsts.subtree_root_threshold(1)


def _counter(name: str) -> int:
    return telemetry.snapshot()["counters"].get(name, 0)


def _fresh_node(n_accounts: int = 8, chain: str = "traffic-test",
                engine: str = "host", data_dir: str | None = None):
    privs = [PrivateKey.from_seed(b"traffic-%d" % i)
             for i in range(n_accounts)]
    addrs = [p.public_key().address() for p in privs]
    app = App(chain_id=chain, engine=engine, data_dir=data_dir)
    app.init_chain({
        "time_unix": 1_700_000_000.0,
        "accounts": [{"address": a.hex(), "balance": 10**14}
                     for a in addrs],
        "validators": [{"operator": addrs[0].hex(), "power": 10}],
    })
    signer = Signer(chain)
    for i, p in enumerate(privs):
        signer.add_account(p, number=i)
    return Node(app), signer, privs, addrs


def _blobs_for(seed: int, n: int, size_range=(100, 1500)) -> list[Blob]:
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        size = int(rng.integers(size_range[0], size_range[1] + 1))
        ns = Namespace.v0(bytes([(seed % 200) + 1, (i % 250) + 1]) * 5)
        out.append(Blob(ns, rng.integers(0, 256, size,
                                         dtype=np.uint8).tobytes()))
    return out


def _pfb_raws(signer, addrs, blobs_per_addr: list[list[Blob]]) -> list[bytes]:
    raws = []
    for a, blobs in zip(addrs, blobs_per_addr):
        raws.append(signer.create_pay_for_blobs(
            a, blobs, fee=300_000, gas_limit=5_000_000))
        signer.accounts[a].sequence += 1
    return raws


# ---------------------------------------------------------------------------
# THE acceptance pin: no recompute from admission through commit + replay
# ---------------------------------------------------------------------------


def test_no_commitment_recompute_through_lifecycle(monkeypatch):
    """Batched admission computes every pending blob's commitment in ONE
    dispatch; CheckTx, PrepareProposal, and ProcessProposal then consume
    pure cache lookups — `commitment.recomputes` delta stays 0 from the
    moment admission ran through commit."""
    monkeypatch.setattr(admission, "MIN_DEVICE_BATCH", 4)
    node, signer, _p, addrs = _fresh_node()
    raws = _pfb_raws(signer, addrs,
                     [_blobs_for(10 + i, 1) for i in range(len(addrs))])

    d0 = _counter("commitment.batch_dispatches")
    r0 = _counter("commitment.recomputes")
    h0 = _counter("commitment.cache_hits")
    res = node.broadcast_txs(raws)
    assert all(r.code == 0 for r in res)
    # ONE batched dispatch covered all 8 blobs; CheckTx validated every
    # claim from the cache, paying zero per-blob host recomputes
    assert _counter("commitment.batch_dispatches") - d0 == 1
    assert _counter("commitment.batch_lanes") >= len(raws)
    assert _counter("commitment.recomputes") == r0
    assert _counter("commitment.cache_hits") - h0 >= len(raws)

    h1 = _counter("commitment.cache_hits")
    block, results = node.produce_block(t=1_700_000_001.0)
    assert len(block.txs) == len(raws)
    assert all(r.code == 0 for r in results)
    # prepare filter + process_proposal resolve: all lookups, 0 recomputes
    assert _counter("commitment.recomputes") == r0
    assert _counter("commitment.cache_hits") - h1 >= 2 * len(raws)


@pytest.mark.backend
def test_admitted_window_is_one_buffer_and_one_program(monkeypatch):
    """A window of 16 uncached blobs on a device-class engine: ONE batch,
    ONE device program, the batch's three phases once each under
    `admission.commitments` — and no `Share` object anywhere on the
    admission path (the blobs are written into one array)."""
    from celestia_app_tpu.da import shares as shares_mod

    node, signer, _p, addrs = _fresh_node(chain="traffic-one-program",
                                          engine="auto")
    raws = _pfb_raws(signer, addrs,
                     [_blobs_for(60 + i, 2) for i in range(len(addrs))])
    built = []
    post_init = shares_mod.Share.__post_init__
    monkeypatch.setattr(
        shares_mod.Share, "__post_init__",
        lambda self: (built.append(1), post_init(self))[1])
    monkeypatch.setattr(
        shares_mod, "split_blob",
        lambda *a, **k: pytest.fail("split_blob on the admission path"))
    names = ("commitment.batch_dispatches", "commitment.batch_programs",
             "commitment.batch_lanes", "square.share_objects")
    before = {n: _counter(n) for n in names}
    mark = len(node.app.traces.read("spans", 0, 100_000))
    assert all(r.code == 0 for r in node.broadcast_txs(raws))
    moved = {n: _counter(n) - before[n] for n in names}
    assert moved == {"commitment.batch_dispatches": 1,
                     "commitment.batch_programs": 1,
                     "commitment.batch_lanes": 16,
                     "square.share_objects": 0}
    assert built == []
    rows = node.app.traces.read("spans", 0, 100_000)[mark:]
    batch = [r for r in rows if r["name"] == "admission.commitments"]
    assert len(batch) == 1
    phases = {}
    for phase in ("admission.commit_pack", "admission.commit_dispatch",
                  "admission.commit_fold"):
        [phases[phase]] = [r for r in rows if r["name"] == phase]
        assert phases[phase]["parent_id"] == batch[0]["span_id"]
    pack = phases["admission.commit_pack"]
    assert 16 <= pack["rows"] <= pack["padded_rows"]
    assert pack["padded_rows"] & (pack["padded_rows"] - 1) == 0
    assert pack["levels"] == 1  # blobs of at most 4 shares: width 1


def test_scalar_admission_fills_cache_for_later_phases():
    """A single /broadcast_tx (below any batch window) pays exactly ONE
    host recompute at CheckTx — and the proposal phases still resolve
    that blob from the cache it filled."""
    node, signer, _p, addrs = _fresh_node(chain="traffic-scalar")
    raw = _pfb_raws(signer, addrs[:1], [_blobs_for(77, 1)])[0]
    r0 = _counter("commitment.recomputes")
    assert node.broadcast_tx(raw).code == 0
    assert _counter("commitment.recomputes") - r0 == 1
    node.produce_block(t=1_700_000_001.0)
    assert _counter("commitment.recomputes") - r0 == 1  # still just the one


def test_wal_replay_no_commitment_recompute(monkeypatch):
    """Crash recovery pays ZERO commitment work: delivery under a commit
    certificate validates no blob commitments, so replay neither
    recomputes per blob NOR dispatches a commitment batch (the
    commitments=False gate on the replay prevalidate)."""
    monkeypatch.setattr(admission, "MIN_DEVICE_BATCH", 4)
    from celestia_app_tpu.chain import consensus as cons
    from celestia_app_tpu.chain.storage import ChainDB

    tmp = tempfile.mkdtemp(prefix="traffic-wal-")
    try:
        priv = PrivateKey.from_seed(b"traffic-wal")
        senders = [PrivateKey.from_seed(b"traffic-wal-%d" % i)
                   for i in range(4)]
        addrs = [p.public_key().address() for p in senders]
        genesis = {
            "time_unix": 1_700_000_000.0,
            "accounts": [{"address": a.hex(), "balance": 10**14}
                         for a in addrs],
            "validators": [
                {"operator": priv.public_key().address().hex(), "power": 10,
                 "pubkey": priv.public_key().compressed.hex()}
            ],
        }
        chain = "traffic-wal"
        data_dir = os.path.join(tmp, "val0")
        node = cons.ValidatorNode("val0", priv, genesis, chain,
                                  data_dir=data_dir)
        net = cons.LocalNetwork([node])
        signer = Signer(chain)
        for i, p in enumerate(senders):
            signer.add_account(p, number=i)
        t = 1_700_000_000.0
        for h in range(2):
            raws = _pfb_raws(signer, addrs,
                             [_blobs_for(100 + 10 * h + i, 1)
                              for i in range(len(addrs))])
            for res in node.add_txs(raws):
                assert res.code == 0
            t += 1.0
            net.produce_height(t=t)
        committed = node.app.height
        node.app.close()

        db = ChainDB(data_dir)
        db.delete_above(committed - 1)
        db.backend.set_latest(committed - 1)
        db.close()

        node2 = cons.ValidatorNode("val0", priv, genesis, chain,
                                   data_dir=data_dir)
        node2.app.load()
        r0 = _counter("commitment.recomputes")
        d0 = _counter("commitment.batch_dispatches")
        h0 = _counter("commitment.cache_hits")
        assert node2.replay_wal() == 1
        assert node2.app.height == committed
        # replay touched the commitment plane not at all: no per-blob
        # recompute, no batch dispatch, no lookups
        assert _counter("commitment.recomputes") == r0
        assert _counter("commitment.batch_dispatches") == d0
        assert _counter("commitment.cache_hits") == h0
        node2.app.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# byte identity: cached ≡ cold, device ≡ host
# ---------------------------------------------------------------------------


def test_cached_equals_cold_byte_identical_both_engines():
    """Every path to a commitment — per-blob host, device batch, and a
    cache round-trip through either — produces identical bytes."""
    blobs = _blobs_for(3, 8, size_range=(100, 4000))
    cold = [commitment_mod.create_commitment(b, THRESHOLD) for b in blobs]
    host_batch = blob_validation.batch_commitments(blobs, THRESHOLD,
                                                   engine="host")
    assert host_batch == cold
    device_batch = blob_validation.batch_commitments(blobs, THRESHOLD,
                                                     engine="device")
    assert device_batch == cold
    for engine in ("host", "auto"):
        cache = admission.VerifiedCommitmentCache()
        resolved = blob_validation.resolve_commitments(
            blobs, THRESHOLD, engine=engine, cache=cache)
        assert resolved == cold
        # and the cached replay resolves identically from pure lookups
        r0 = _counter("commitment.recomputes")
        again = blob_validation.resolve_commitments(
            blobs, THRESHOLD, engine=engine, cache=cache)
        assert again == cold
        assert _counter("commitment.recomputes") == r0


def test_prevalidate_commitments_matches_host_reference(monkeypatch):
    """The admission batch fills the cache with exactly the host
    reference's bytes (keyed per blob), on a device-class engine."""
    monkeypatch.setattr(admission, "MIN_DEVICE_BATCH", 4)
    node, signer, _p, addrs = _fresh_node(chain="traffic-pre",
                                          engine="auto")
    blob_sets = [_blobs_for(40 + i, 1) for i in range(len(addrs))]
    raws = _pfb_raws(signer, addrs, blob_sets)
    computed = admission.prevalidate_commitments(node.app, raws)
    assert computed == len(addrs)
    cache = node.app.commitment_cache
    for blobs in blob_sets:
        for blob in blobs:
            key = cache.key(blob.namespace.raw, blob.share_version,
                            blob.data, THRESHOLD)
            assert cache.contains(key)
            assert cache.hit(key) == commitment_mod.create_commitment(
                blob, THRESHOLD)
    # idempotent: everything cached now, no second dispatch
    d0 = _counter("commitment.batch_dispatches")
    assert admission.prevalidate_commitments(node.app, raws) == 0
    assert _counter("commitment.batch_dispatches") == d0


# ---------------------------------------------------------------------------
# the Byzantine case: a warm cache can only skip recomputes that agree
# ---------------------------------------------------------------------------


def _forged_pfb(signer, addr: bytes, blob: Blob,
                forged_commitment: bytes) -> bytes:
    """A signed BlobTx whose PFB CLAIMS `forged_commitment` for `blob`."""
    from celestia_app_tpu.chain.tx import MsgPayForBlobs

    msg = MsgPayForBlobs(
        signer=addr,
        namespaces=(blob.namespace.raw,),
        blob_sizes=(len(blob.data),),
        share_commitments=(forged_commitment,),
        share_versions=(blob.share_version,),
    )
    tx = signer.create_tx(addr, [msg], fee=300_000, gas_limit=5_000_000)
    return blob_mod.marshal_blob_tx(tx.encode(), [blob])


def test_byzantine_mismatch_rejected_despite_warm_cache(monkeypatch):
    """A tx claiming a WRONG commitment for a blob whose TRUE commitment
    is already cached must be rejected — the cache stores computed-true
    values, so the byte-compare against the claim still fails."""
    monkeypatch.setattr(admission, "MIN_DEVICE_BATCH", 4)
    node, signer, _p, addrs = _fresh_node(chain="traffic-byz")
    blob = _blobs_for(55, 1)[0]
    honest = _pfb_raws(signer, addrs[:1], [[blob]])[0]
    # warm: the admission batch caches the blob's TRUE commitment
    admission.prevalidate_commitments(
        node.app, [honest] + _pfb_raws(
            signer, addrs[1:4], [_blobs_for(60 + i, 1) for i in range(3)]))
    true_c = commitment_mod.create_commitment(blob, THRESHOLD)
    forged = _forged_pfb(signer, addrs[4], blob, b"\xee" * 32)
    r0 = _counter("commitment.recomputes")
    res = node.broadcast_tx(forged)
    assert res.code == 1
    assert "commitment mismatch" in res.log
    # the rejection came FROM the warm cache: no recompute was paid
    assert _counter("commitment.recomputes") == r0
    # and validate_blob_tx agrees directly, warm or cold
    btx = blob_mod.try_unmarshal_blob_tx(forged)
    with pytest.raises(BlobTxError, match="commitment mismatch"):
        blob_validation.validate_blob_tx(btx, THRESHOLD,
                                         cache=node.app.commitment_cache)
    with pytest.raises(BlobTxError, match="commitment mismatch"):
        blob_validation.validate_blob_tx(btx, THRESHOLD)
    # the honest tx with the SAME blob still admits off the same cache
    assert node.broadcast_tx(honest).code == 0
    assert true_c == commitment_mod.create_commitment(blob, THRESHOLD)


def test_process_proposal_rejects_forged_commitment_block(monkeypatch):
    """A proposed block carrying a forged-commitment blob tx is rejected
    by ProcessProposal even when every commitment involved is cached."""
    monkeypatch.setattr(admission, "MIN_DEVICE_BATCH", 4)
    node, signer, _p, addrs = _fresh_node(chain="traffic-byz-block")
    # an honest block first (warms height/hash plumbing)
    raws = _pfb_raws(signer, addrs[:4],
                     [_blobs_for(70 + i, 1) for i in range(4)])
    for raw in raws:
        assert node.broadcast_tx(raw).code == 0
    block, _ = node.produce_block(t=1_700_000_001.0)
    assert len(block.txs) == 4
    # forge: take a fresh honest proposal and swap in a forged tx
    blob = _blobs_for(80, 1)[0]
    honest = _pfb_raws(signer, addrs[4:5], [[blob]])[0]
    assert node.broadcast_tx(honest).code == 0
    prop = node.app.prepare_proposal([honest], t=1_700_000_002.0)
    assert node.app.process_proposal(prop.block)
    forged_raw = _forged_pfb(signer, addrs[5], blob, b"\xbb" * 32)
    import dataclasses as dc

    forged_block = dc.replace(prop.block,
                              txs=tuple(list(prop.block.txs)
                                        + [forged_raw]))
    assert not node.app.process_proposal(forged_block)


# ---------------------------------------------------------------------------
# cache mechanics: LRU bound + framing safety
# ---------------------------------------------------------------------------


def test_commitment_cache_is_bounded_lru():
    cache = admission.VerifiedCommitmentCache(maxsize=4)
    keys = [admission.commitment_key(b"ns%d" % i, 0, b"data", 64)
            for i in range(6)]
    for k in keys[:4]:
        cache.put(k, b"c" * 32)
    assert cache.hit(keys[0]) is not None  # refresh 0 -> evict 1 next
    cache.put(keys[4], b"d" * 32)
    assert cache.hit(keys[1]) is None
    assert cache.hit(keys[0]) == b"c" * 32
    assert cache.hit(keys[4]) == b"d" * 32
    assert len(cache) == 4


def test_commitment_key_is_framing_safe():
    """Two blobs whose fields CONCATENATE identically must not collide:
    the key length-frames every part."""
    assert admission.commitment_key(b"ab", 0, b"c", 64) != \
        admission.commitment_key(b"a", 0, b"bc", 64)
    # a data prefix of another blob's data, same namespace
    assert admission.commitment_key(b"ns", 0, b"abc", 64) != \
        admission.commitment_key(b"ns", 0, b"ab", 64)
    # share version and threshold are part of the identity
    assert admission.commitment_key(b"ns", 0, b"abc", 64) != \
        admission.commitment_key(b"ns", 1, b"abc", 64)
    assert admission.commitment_key(b"ns", 0, b"abc", 64) != \
        admission.commitment_key(b"ns", 0, b"abc", 32)


# ---------------------------------------------------------------------------
# the sustained-load txsim against an in-process devnet
# ---------------------------------------------------------------------------


def test_txsim_load_against_inprocess_devnet(tmp_path):
    """Honest load: every submitted tx is accepted AND confirmed, the
    report carries real latencies, and the admission/traffic status
    block is served over HTTP."""
    from celestia_app_tpu.client.tx_client import HttpNodeClient
    from celestia_app_tpu.service.server import NodeService
    from celestia_app_tpu.tools import txsim

    node, signer, _p, addrs = _fresh_node(
        chain="traffic-devnet", data_dir=str(tmp_path / "data"))
    svc = NodeService(node, port=0)
    svc.serve_background()
    url = f"http://127.0.0.1:{svc.port}"

    def produce():
        with svc.lock:
            node.produce_block()

    driver = txsim.BlockDriver(produce, block_time=0.05)
    driver.start()
    try:
        rep = txsim.run_load(
            [url], signer, addrs,
            txsim.LoadConfig(blob_sequences=2, send_sequences=1,
                             txs_per_sequence=2,
                             blob_sizes=(100, 600), blobs_per_pfb=(1, 2),
                             confirm_timeout_s=60.0,
                             poll_interval_s=0.02, seed=1),
        )
    finally:
        driver.stop()
    assert rep.errors == 0
    assert rep.pfbs_submitted == 4 and rep.sends_submitted == 2
    assert rep.pfbs_accepted == rep.pfbs_submitted
    assert rep.sends_accepted == rep.sends_submitted
    assert rep.pfbs_confirmed == rep.pfbs_submitted
    assert rep.sends_confirmed == rep.sends_submitted
    assert rep.blobs_confirmed == rep.blobs_submitted > 0
    assert rep.blobs_per_sec > 0
    assert rep.admission_commit_p99_ms >= rep.admission_commit_p50_ms > 0
    # the status surface carries the admission + traffic block
    client = HttpNodeClient(url)
    status = client.status()
    adm = status["admission"]
    assert adm["txsim"]["submitted"] >= 6
    assert adm["txsim"]["confirmed"] >= 6
    assert adm["commitment"]["cache_hits"] > 0
    assert "recomputes" in adm["commitment"]
    # the keep-alive client held ONE persistent connection across calls
    conn0 = client._conn
    assert conn0 is not None
    client.status()
    assert client._conn is conn0
    client.close()
    svc.shutdown()
    node.app.close()
