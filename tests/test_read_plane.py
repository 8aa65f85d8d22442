"""The read plane (ISSUE 16): batched device-side namespace proofs,
static blob packs, and the verifying rollup follower.

Tier-1 because the plane's contracts are all byte-identity and
refusal-safety pins: the batched search must serve EXACTLY the host
reference's proofs (a divergence would hand rollups unverifiable — or
worse, wrongly-verifiable — data), pack bytes must equal live bytes (a
CDN cache must never be able to serve something the node would not),
and the follower must refuse every tampered doc and every Byzantine
root no matter how warm the serving side's caches are.

Covers the six ISSUE 16 areas: (a) device ≡ host proof byte identity
(both engines, presence + both absence orientations), (b) batched ≡
single byte identity over HTTP, (c) pack ≡ live byte identity + a torn
pack is never served, (d) follower catch-up + checkpointed restart,
(e) absence proofs end to end + tamper rejection, (f) Byzantine root
rejection despite a warm serving cache.
"""

import hashlib
import json
import os

import numpy as np
import pytest

from celestia_app_tpu import faults
from celestia_app_tpu.chain import consensus as cons
from celestia_app_tpu.chain import light as light_mod
from celestia_app_tpu.chain.app import App
from celestia_app_tpu.chain.crypto import PrivateKey
from celestia_app_tpu.chain.node import Node
from celestia_app_tpu.chain.query import _share_proof_json
from celestia_app_tpu.client.follower import (
    BlobFollower,
    FollowerConfig,
    FollowerError,
)
from celestia_app_tpu.client.tx_client import Signer
from celestia_app_tpu.da import dah as dah_mod
from celestia_app_tpu.da import edscache
from celestia_app_tpu.da import namespace_data as nsd
from celestia_app_tpu.da import namespace_device as nsdev
from celestia_app_tpu.da import proof_device
from celestia_app_tpu.da import square as square_mod
from celestia_app_tpu.da.blob import Blob
from celestia_app_tpu.da.namespace import Namespace
from celestia_app_tpu.da.square import PfbEntry
from celestia_app_tpu.das import blob_packs as blob_packs_mod
from celestia_app_tpu.das.blob_server import BlobCore
from celestia_app_tpu.das.checkpoint import CheckpointStore
from celestia_app_tpu.das.daser import PeerSet
from celestia_app_tpu.das.server import SampleCore, SampleError
from celestia_app_tpu.service.server import NodeService
from celestia_app_tpu.utils import telemetry

TARGET = Namespace.v0(b"roll1")  # the followed rollup namespace
OTHER = Namespace.v0(b"zzay1")
ABSENT = Namespace.v0(b"nope0")  # never written anywhere


def _counters():
    return telemetry.snapshot().get("counters", {})


def _delta(c0, c1, key):
    return c1.get(key, 0) - c0.get(key, 0)


def _canon(doc) -> str:
    return json.dumps(doc, sort_keys=True)


def _nd_canon(nd) -> str:
    """A NamespaceData's full wire identity: shares AND the proof JSON
    exactly as served (chain/query._share_proof_json)."""
    import base64

    return _canon({
        "shares": [base64.b64encode(s).decode() for s in nd.shares],
        "proof": _share_proof_json(nd.proof) if nd.proof else None,
    })


# ---------------------------------------------------------------------------
# (a) batched search ≡ host reference — both engines, both orientations
# ---------------------------------------------------------------------------


def _block(rng, blobs):
    sq = square_mod.build([b"some-tx"], [PfbEntry(b"pfb", tuple(blobs))],
                          64, 64)
    ods = dah_mod.shares_to_ods(sq.share_bytes())
    d, eds_obj, root = dah_mod.new_dah_from_ods(ods)
    return sq, d, proof_device.BlockProver(eds_obj, d), root


def _mk_blobs(rng):
    return [
        Blob(Namespace.v0(b"aaaaa"),
             rng.integers(0, 256, 3000, dtype=np.uint8).tobytes()),
        Blob(Namespace.v0(b"mmmmm"),
             rng.integers(0, 256, 900, dtype=np.uint8).tobytes()),
        Blob(Namespace.v0(b"zzzzz"),
             rng.integers(0, 256, 500, dtype=np.uint8).tobytes()),
    ]


@pytest.mark.parametrize("device", (False, True))
def test_batched_matches_host_reference(device):
    """THE tentpole pin: one batched dispatch resolves presence, the
    straddling-row absence (successor proof) and the no-covering-row
    absence (no proof) byte-identically to per-query
    get_namespace_data — on both paths (the device search degrades to
    the host pass when no accelerator runtime is available, counted,
    never raised — identity holds either way)."""
    rng = np.random.default_rng(7)
    blobs = _mk_blobs(rng)
    _sq, d, prover, _root = _block(rng, blobs)
    queries = (
        [b.namespace.raw for b in blobs]
        + [Namespace.v0(b"qqqqq").raw]  # straddling-row absence
        + [bytes(29)]                   # below every row: proofless
        + [blobs[0].namespace.raw]      # duplicate query, order pinned
    )
    c0 = _counters()
    got = nsdev.get_namespace_data_batched(prover, queries, device=device)
    c1 = _counters()
    assert len(got) == len(queries)
    for q, nd in zip(queries, got):
        ref = nsd.get_namespace_data(prover, q)
        assert _nd_canon(nd) == _nd_canon(ref)
        assert nsd.verify_namespace_data(d, q, nd)
    # orientations really exercised: 4 presences, one absence WITH a
    # successor proof, one absence with none
    assert [bool(nd.shares) for nd in got] == [
        True, True, True, False, False, True]
    assert got[3].proof is not None and got[4].proof is None
    if device:
        # the dispatch either ran on-device or fell back, counted
        assert (_delta(c0, c1, "blob.device_batches")
                + _delta(c0, c1, "blob.device_fallbacks")) >= 1


@pytest.mark.parametrize("holds,device_batches", [
    ("host-entry", 0), ("started-copy", 1)])
def test_the_entry_says_where_its_search_runs(holds, device_batches):
    """A namespace read of two queries searches where the entry's engine
    put it, whatever the batch size: on the host for the host engine's
    entry (no device batch, no fallback), on the device for a
    `DeviceEntry` that holds host bytes (one device batch) — the same
    bytes either way."""
    rng = np.random.default_rng(8)
    blobs = _mk_blobs(rng)
    sq, _d, prover, _root = _block(rng, blobs)
    ods = dah_mod.shares_to_ods(sq.share_bytes())
    engine = "host" if holds == "host-entry" else "device"
    entry = edscache.compute_entry(ods, engine)
    assert entry.proves_on_host()
    queries = [blobs[0].namespace.raw, ABSENT.raw]
    reader = entry.namespace_reader()
    c0 = _counters()
    got = reader.assemble(queries, reader.search(queries))
    c1 = _counters()
    assert _delta(c0, c1, "blob.device_batches") == device_batches
    assert _delta(c0, c1, "blob.device_fallbacks") == 0
    for q, nd in zip(queries, got):
        assert _nd_canon(nd) == _nd_canon(nsd.get_namespace_data(prover, q))


# ---------------------------------------------------------------------------
# blob-bearing chain fixtures
# ---------------------------------------------------------------------------


def _payload(height: int, i: int) -> bytes:
    return bytes([height % 251, i + 1]) * 150  # 300 bytes, per-height


def _blob_batch(height: int):
    return [Blob(TARGET, _payload(height, 0)),
            Blob(TARGET, _payload(height, 1)),
            Blob(OTHER, _payload(height, 2))]


def _packed_node(tmp_path, blocks=2):
    """(app, node, core, blob_core): a disk-backed single-proposer chain
    with `blocks` blob-bearing heights and every height's blob pack
    built (builds are idempotent; the warmer coalesces under rapid
    commits, so stragglers are built explicitly)."""
    priv = PrivateKey.from_seed(b"read-plane")
    addr = priv.public_key().address()
    app = App(chain_id="read-plane", engine="host",
              data_dir=str(tmp_path / "data"), da_scheme="rs2d-nmt",
              pack_keep=4)
    app.init_chain({
        "time_unix": 1_700_000_000.0,
        "accounts": [{"address": addr.hex(), "balance": 10**14}],
        "validators": [{"operator": addr.hex(), "power": 10}],
    })
    node = Node(app)
    core = node.attach_das_core(SampleCore(app))
    signer = Signer(app.chain_id)
    signer.add_account(priv, number=0)
    for i in range(blocks):
        raw = signer.create_pay_for_blobs(
            addr, _blob_batch(i + 1), fee=300_000, gas_limit=20_000_000)
        signer.accounts[addr].sequence += 1
        node.broadcast_tx(raw)
        node.produce_block(t=1_700_000_000.0 + i + 1)
    app.da_warmer.wait_idle(30)
    for h in range(1, blocks + 1):
        app.blob_pack_store.build(h, core._entry(h).cache_entry)
    return app, node, core, BlobCore(core), signer, addr


def _vchain(tmp_path, blocks=3):
    """(vnode, svc, url, priv): a one-validator certified blob chain
    served by a NodeService — commit certificates back the follower's
    light client, blob packs back the static read path."""
    priv = PrivateKey.from_seed(b"read-val")
    addr = priv.public_key().address()
    genesis = {
        "time_unix": 1_700_000_000.0,
        "accounts": [{"address": addr.hex(), "balance": 10**14}],
        "validators": [{
            "operator": addr.hex(),
            "power": 10,
            "pubkey": priv.public_key().compressed.hex(),
        }],
    }
    vnode = cons.ValidatorNode(
        "read", priv, genesis, "read-chain",
        data_dir=str(tmp_path / "read" / "data"), da_scheme="rs2d-nmt",
        pack_keep=4)
    signer = Signer(vnode.app.chain_id)
    signer.add_account(priv, number=0)
    _grow(vnode, signer, addr, blocks)
    svc = NodeService(vnode, port=0)
    svc.serve_background()
    return vnode, svc, f"http://127.0.0.1:{svc.port}", priv, signer, addr


def _grow(vnode, signer, addr, blocks):
    for _ in range(blocks):
        height = vnode.app.height + 1
        raw = signer.create_pay_for_blobs(
            addr, _blob_batch(height), fee=300_000, gas_limit=20_000_000)
        signer.accounts[addr].sequence += 1
        vnode.add_tx(raw)
        last_cert = vnode.certificates.get(height - 1)
        block = vnode.propose(t=1_700_000_000.0 + height)
        bh = block.header.hash()
        vote = vnode._signed(height, bh, "precommit", 0)
        cert = cons.CommitCertificate(height, bh, (vote,), 0)
        vnode.apply(block, cert, absent_cert=last_cert)
        vnode.clear_lock()
    vnode.app.da_warmer.wait_idle(30)
    for h in range(1, vnode.app.height + 1):
        entry = vnode.app.eds_cache.lookup_root(
            vnode.app.db.load_block(h).header.data_hash)
        if entry is not None:  # evicted ⇒ already packed earlier
            vnode.app.blob_pack_store.build(h, entry)


def _follower(url, namespace, store_path, vnode, priv, **cfg):
    trust = light_mod.TrustedState(
        height=0, header_hash=b"",
        validators={vnode.address: priv.public_key().compressed},
        powers={vnode.address: 10},
    )
    return BlobFollower(
        [url], namespace,
        light_mod.LightClient(vnode.app.chain_id, trust),
        CheckpointStore(store_path),
        cfg=FollowerConfig(request_timeout=5.0, retries=2, backoff=0.01,
                           **cfg),
    )


# ---------------------------------------------------------------------------
# (b) batched ≡ single over HTTP
# ---------------------------------------------------------------------------


def test_http_batched_members_byte_identical_to_single(tmp_path):
    """Every POST /blob/namespaces member equals the GET /blob/get
    response for the same (height, namespace) byte for byte — including
    absences — while an unresolvable height degrades to an error member
    without failing the batch."""
    vnode, svc, url, _priv, _signer, _addr = _vchain(tmp_path, blocks=2)
    try:
        peers = PeerSet([url], timeout=5.0, retries=2, backoff=0.01)
        queries = [
            {"height": h, "namespace": ns.raw.hex()}
            for h in (1, 2)
            for ns in (TARGET, OTHER, ABSENT, TARGET)  # dup pins order
        ] + [{"height": 99, "namespace": TARGET.raw.hex()}]
        c0 = _counters()
        out = peers.request("/blob/namespaces", {"queries": queries})
        c1 = _counters()
        assert len(out["queries"]) == len(queries)
        for q, member in zip(queries[:-1], out["queries"][:-1]):
            single = peers.request(
                f"/blob/get?height={q['height']}"
                f"&namespace={q['namespace']}")
            assert _canon(member) == _canon(single)
            assert member["height"] == q["height"]
            assert member["namespace"] == q["namespace"]
        bad = out["queries"][-1]
        assert bad["height"] == 99 and "error" in bad
        # telemetry satellite: the batch is counted once, per-query
        assert _delta(c0, c1, "blob.namespace_batches") == 1
        assert _delta(c0, c1, "blob.namespace_queries") >= len(queries)
        # the status surface mounts the counters
        status = peers.request("/status")
        assert status["blob"]["namespace_queries"] > 0
    finally:
        svc.shutdown()
        vnode.app.close()


# ---------------------------------------------------------------------------
# (c) pack ≡ live + torn packs never served
# ---------------------------------------------------------------------------


def test_pack_bytes_identical_to_live(tmp_path):
    """Every doc in every blob-pack chunk equals the live /blob/get doc
    (minus the route's height envelope), the chunk bytes hash to the
    manifest entry, and the namespace→chunk position mapping holds."""
    app, _node, _core, blob_core, _s, _a = _packed_node(tmp_path, blocks=2)
    try:
        for h in (1, 2):
            m = blob_core.pack_manifest(h)
            assert m["scheme"] == "rs2d-nmt"
            assert set(m["namespaces"]) >= {TARGET.raw.hex(),
                                            OTHER.raw.hex()}
            seen = []
            for ci in range(m["n_chunks"]):
                data = blob_core.pack_chunk(h, ci)
                assert hashlib.sha256(data).hexdigest() == \
                    m["chunk_hashes"][ci]
                for doc in blob_packs_mod.decode_chunk(data):
                    live = blob_core.get(h, doc["namespace"])
                    assert _canon(doc) == _canon(
                        {k: v for k, v in live.items() if k != "height"})
                    seen.append(doc["namespace"])
            # chunk order IS manifest order: position // chunk_namespaces
            assert seen == m["namespaces"]
    finally:
        app.close()


def test_torn_pack_never_served_and_recovers(tmp_path):
    """A build killed at blobpacks.mid_write leaves a manifest-less dir:
    /blob/pack refuses ("not served", 404-mapped), live reads keep
    answering, and a rebuild serves bytes identical to live."""
    app, node, core, blob_core, signer, addr = _packed_node(
        tmp_path, blocks=1)
    try:
        faults.arm("blobpacks.mid_write", "error")
        raw = signer.create_pay_for_blobs(
            addr, _blob_batch(2), fee=300_000, gas_limit=20_000_000)
        signer.accounts[addr].sequence += 1
        node.broadcast_tx(raw)
        node.produce_block(t=1_700_000_002.0)
        app.da_warmer.wait_idle(30)  # warmer's own build fails, counted
        h = app.height
        entry = core._entry(h).cache_entry
        store = app.blob_pack_store
        with pytest.raises(OSError):
            store.build(h, entry)
        root_hex = entry.data_root.hex()
        torn = store.path_for(root_hex)
        assert os.path.isdir(torn)
        assert not os.path.exists(os.path.join(torn, "manifest.json"))
        with pytest.raises(SampleError, match="not served"):
            blob_core.pack_manifest(h)
        live = blob_core.get(h, TARGET.raw.hex())
        assert live["present"] is True
        # recovery: disarm, rebuild, serve — byte-identical to live
        faults.reset()
        m = store.build(h, entry)
        assert blob_core.pack_manifest(h) == m
        docs = blob_packs_mod.decode_chunk(blob_core.pack_chunk(h, 0))
        for doc in docs:
            got = blob_core.get(h, doc["namespace"])
            assert _canon(doc) == _canon(
                {k: v for k, v in got.items() if k != "height"})
    finally:
        faults.reset()
        app.close()


# ---------------------------------------------------------------------------
# (d) follower catch-up + checkpointed restart
# ---------------------------------------------------------------------------


def test_follower_catch_up_and_checkpointed_restart(tmp_path):
    """A fresh follower verifies the whole chain and delivers exactly
    the namespace's blob payloads; a restarted follower resumes from the
    fsync'd checkpoint and re-reads nothing."""
    vnode, svc, url, priv, signer, addr = _vchain(tmp_path, blocks=3)
    cp = str(tmp_path / "cp" / "follower.json")
    try:
        f = _follower(url, TARGET.raw, cp, vnode, priv)
        c0 = _counters()
        out = f.sync()
        c1 = _counters()
        assert out == {"head": 3, "next_height": 4, "verified": 3}
        blobs = f.pop_blobs()
        assert sorted(blobs) == [1, 2, 3]
        for h in (1, 2, 3):
            assert sorted(blobs[h]) == sorted(
                [_payload(h, 0), _payload(h, 1)])
        assert _delta(c0, c1, "follower.heights") == 3
        assert _delta(c0, c1, "follower.blobs") == 6
        assert _delta(c0, c1, "follower.pack_reads") == 3  # CDN path
        assert _delta(c0, c1, "follower.verify_failures") == 0
        # the checkpoint doc landed durably (§21.4 shape)
        with open(cp) as fh:
            doc = json.load(fh)
        assert doc["version"] == 1
        assert doc["namespace"] == TARGET.raw.hex()
        assert doc["next_height"] == 4

        # grow the chain, restart from the checkpoint: only the new
        # heights are read
        _grow(vnode, signer, addr, 2)
        f2 = _follower(url, TARGET.raw, cp, vnode, priv)
        assert f2.next_height == 4  # resumed, not re-reading
        c2 = _counters()
        out2 = f2.sync()
        c3 = _counters()
        assert out2 == {"head": 5, "next_height": 6, "verified": 2}
        assert sorted(f2.pop_blobs()) == [4, 5]
        assert _delta(c2, c3, "follower.heights") == 2

        # another namespace's checkpoint is not ours to resume
        f3 = _follower(url, OTHER.raw, cp, vnode, priv)
        assert f3.next_height == 1
    finally:
        svc.shutdown()
        vnode.app.close()


# ---------------------------------------------------------------------------
# (e) absence proofs end to end + tamper rejection
# ---------------------------------------------------------------------------


def test_follower_verifies_absence_end_to_end(tmp_path):
    """Following a namespace the chain never wrote: every height yields
    a VERIFIED absence (counted follower.absences), zero blobs, zero
    verification failures — absence is a proof, not a 404."""
    vnode, svc, url, priv, _signer, _addr = _vchain(tmp_path, blocks=2)
    try:
        peers = PeerSet([url], timeout=5.0, retries=2, backoff=0.01)
        doc = peers.request(
            f"/blob/get?height=1&namespace={ABSENT.raw.hex()}")
        assert doc["present"] is False and doc["shares"] == []
        f = _follower(url, ABSENT.raw,
                      str(tmp_path / "cp-absent.json"), vnode, priv)
        c0 = _counters()
        out = f.sync()
        c1 = _counters()
        assert out["verified"] == 2 and out["next_height"] == 3
        assert f.pop_blobs() == {}
        assert _delta(c0, c1, "follower.absences") == 2
        assert _delta(c0, c1, "follower.verify_failures") == 0
    finally:
        svc.shutdown()
        vnode.app.close()


def test_follower_rejects_tampered_docs(tmp_path):
    """Every tamper orientation is refused and counted: a wrong data
    root, a flipped share byte under a valid proof, and a fake absence
    claim for a present namespace — and a tampered response aborts the
    sweep WITHOUT advancing the checkpoint."""
    import base64

    vnode, svc, url, priv, _signer, _addr = _vchain(tmp_path, blocks=1)
    try:
        f = _follower(url, TARGET.raw, str(tmp_path / "cp-t.json"),
                      vnode, priv, prefer_packs=False)
        f._follow_head()
        root_hex, square_size = f._roots[1]
        dah = f._certified_dah(1, root_hex, square_size)
        doc = f._fetch_live_doc(1)
        c0 = _counters()
        with pytest.raises(FollowerError, match="certified root"):
            f._verified_nd(1, dah, root_hex,
                           {**doc, "data_root": "00" * 32})
        flipped = bytearray(base64.b64decode(doc["shares"][0]))
        flipped[40] ^= 0xFF
        bad_share = {**doc, "shares": [base64.b64encode(
            bytes(flipped)).decode()] + doc["shares"][1:]}
        with pytest.raises(FollowerError, match="failed verification"):
            f._verified_nd(1, dah, root_hex, bad_share)
        with pytest.raises(FollowerError, match="failed verification"):
            f._verified_nd(1, dah, root_hex,
                           {**doc, "present": False, "shares": [],
                            "proof": None})
        c1 = _counters()
        assert _delta(c0, c1, "follower.verify_failures") == 3
        # end to end: a tampering peer aborts the sweep, no progress
        f._fetch_live_doc = lambda _h: bad_share
        with pytest.raises(FollowerError):
            f.sync()
        assert f.next_height == 1
    finally:
        svc.shutdown()
        vnode.app.close()


def test_follower_rejects_tampered_pack_chunk_and_falls_back(tmp_path):
    """A tampered pack chunk (bytes no longer hash to the manifest) is
    rejected client-side — serving peer penalized, the height resolved
    via the live route instead — and the delivered blobs are unchanged;
    static-path integrity never gates reads."""
    vnode, svc, url, priv, _signer, _addr = _vchain(tmp_path, blocks=1)
    try:
        store = vnode.app.blob_pack_store
        m = BlobCore(svc.das_core).pack_manifest(1)
        pos = m["namespaces"].index(TARGET.raw.hex())
        ci = pos // m["chunk_namespaces"]
        chunk_path = os.path.join(store.path_for(m["data_root"]),
                                  m["chunk_hashes"][ci] + ".chunk")
        with open(chunk_path, "r+b") as fh:
            raw = bytearray(fh.read())
            raw[len(raw) // 2] ^= 0xFF
            fh.seek(0)
            fh.write(raw)
        f = _follower(url, TARGET.raw, str(tmp_path / "cp-p.json"),
                      vnode, priv)
        c0 = _counters()
        out = f.sync()
        c1 = _counters()
        assert out["verified"] == 1
        assert sorted(f.pop_blobs()[1]) == sorted(
            [_payload(1, 0), _payload(1, 1)])
        assert _delta(c0, c1, "follower.verify_failures") >= 1
        assert _delta(c0, c1, "net.penalized") >= 1
        assert _delta(c0, c1, "follower.live_reads") == 1  # the fallback
        assert _delta(c0, c1, "follower.pack_reads") == 0
    finally:
        svc.shutdown()
        vnode.app.close()


# ---------------------------------------------------------------------------
# (f) Byzantine root rejection despite a warm serving cache
# ---------------------------------------------------------------------------


def test_follower_rejects_byzantine_commitments(tmp_path):
    """A peer serving height 2 the (internally consistent) commitments
    doc of height 1 is refused at the bind step — the served row roots
    do not commit to the CERTIFIED data root — even though the peer's
    entries and packs are fully warm. Verified progress (height 1)
    survives; the poisoned height does not advance."""
    vnode, svc, url, priv, _signer, _addr = _vchain(tmp_path, blocks=2)
    try:
        f = _follower(url, TARGET.raw, str(tmp_path / "cp-b.json"),
                      vnode, priv)
        f._follow_head()
        assert f._roots[1][0] != f._roots[2][0]  # distinct data roots
        doc1 = f.peers.request("/das/header?height=1")
        orig = f.peers.request

        def poisoned(path, payload=None, raw=False):
            if path == "/das/header?height=2":
                return doc1
            return orig(path, payload=payload, raw=raw)

        f.peers.request = poisoned
        c0 = _counters()
        with pytest.raises(FollowerError, match="certified data root"):
            f.sync()
        c1 = _counters()
        assert _delta(c0, c1, "follower.verify_failures") >= 1
        assert f.next_height == 2  # height 1 verified, height 2 refused
        # an honest peer un-sticks the same follower
        f.peers.request = orig
        out = f.sync()
        assert out["next_height"] == 3 and out["verified"] == 1
    finally:
        svc.shutdown()
        vnode.app.close()
