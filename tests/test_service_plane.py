"""Durable storage, query routes, HTTP service, CLI, txsim, tools.

VERDICT #9 'done' criteria: a node restarts and resumes at its committed
height; proofs are queryable out-of-process."""

import base64
import json
import os
import urllib.request

import numpy as np
import pytest

from celestia_app_tpu.chain.app import App
from celestia_app_tpu.chain.node import Node
from celestia_app_tpu.chain.query import QueryRouter, share_proof_from_json
from celestia_app_tpu.chain.crypto import PrivateKey
from celestia_app_tpu.client.tx_client import Signer, TxClient
from celestia_app_tpu.da.blob import Blob
from celestia_app_tpu.da.namespace import Namespace

from test_app import CHAIN, make_app


def _persistent_app(tmp_path, **kw):
    app = App(chain_id=CHAIN, engine="host", data_dir=str(tmp_path / "data"), **kw)
    privs = [PrivateKey.from_seed(bytes([i])) for i in range(3)]
    genesis = {
        "time_unix": 1_700_000_000.0,
        "accounts": [
            {"address": p.public_key().address().hex(), "balance": 10**12}
            for p in privs
        ],
        "validators": [
            {"operator": p.public_key().address().hex(), "power": 10}
            for p in privs
        ],
    }
    app.init_chain(genesis)
    signer = Signer(CHAIN)
    for i, p in enumerate(privs):
        signer.add_account(p, i)
    return app, signer, privs


def _run_blocks(app, signer, privs, n_blobs=2):
    node = Node(app)
    client = TxClient(node, signer)
    addr = privs[0].public_key().address()
    rng = np.random.default_rng(0)
    blobs = [
        Blob(Namespace.v0(bytes([i + 1]) * 4),
             rng.integers(0, 256, 900, dtype=np.uint8).tobytes())
        for i in range(n_blobs)
    ]
    client.submit_pay_for_blob(addr, blobs)
    client.submit_send(addr, privs[1].public_key().address(), 777)
    return node


def test_restart_resumes_at_committed_height(tmp_path):
    app, signer, privs = _persistent_app(tmp_path)
    _run_blocks(app, signer, privs)
    h, ah, bh = app.height, app.last_app_hash, app.last_block_hash
    assert h == 2
    app.close()  # "process exit": releases the storage engine's flock

    # a brand-new process: fresh App over the same data dir
    app2 = App(chain_id="x", engine="host", data_dir=str(tmp_path / "data"))
    app2.load()
    assert app2.height == h
    assert app2.last_app_hash == ah
    assert app2.last_block_hash == bh
    assert app2.chain_id == CHAIN  # identity restored from disk

    # and it keeps producing blocks on top
    blk, _ = app2.produce_block([], t=1_700_001_000.0)
    assert blk.header.height == h + 1


def test_rollback_from_disk(tmp_path):
    app, signer, privs = _persistent_app(tmp_path)
    _run_blocks(app, signer, privs)
    hash_h1 = None
    app.load_height(1)
    assert app.height == 1
    blk, _ = app.produce_block([], t=1_700_002_000.0)
    assert blk.header.height == 2


def test_proof_queries_verify(tmp_path):
    app, signer, privs = _persistent_app(tmp_path)
    _run_blocks(app, signer, privs)
    router = QueryRouter(app)

    blk = app.db.load_block(1)
    out = router.query("custom/txInclusionProof", {"height": 1, "tx_index": 0})
    pf = share_proof_from_json(out["proof"])
    assert pf.verify(bytes.fromhex(out["data_root"]))
    assert out["data_root"] == blk.header.data_hash.hex()

    out2 = router.query(
        "custom/shareInclusionProof",
        {"height": 1, "start": 0, "end": 2, "namespace": "00" * 29},
    )
    pf2 = share_proof_from_json(out2["proof"])
    assert pf2.verify(bytes.fromhex(out2["data_root"]))

    # tampered proof fails
    out2["proof"]["data"][0] = base64.b64encode(b"\x00" * 512).decode()
    assert not share_proof_from_json(out2["proof"]).verify(
        bytes.fromhex(out2["data_root"])
    )


def test_keeper_query_routes(tmp_path):
    app, signer, privs = _persistent_app(tmp_path)
    _run_blocks(app, signer, privs)
    router = QueryRouter(app)
    addr = privs[1].public_key().address().hex()
    assert router.query("bank/balance", {"address": addr})["balance"] > 0
    assert router.query("blob/params", {})["params"]["gov_max_square_size"] > 0
    assert len(router.query("staking/validators", {})["validators"]) == 3
    st = router.query("status", {})
    assert st["height"] == app.height
    assert "prepare_proposal" in st["telemetry"]["timers"]


def test_http_service_roundtrip(tmp_path):
    app, signer, privs = _persistent_app(tmp_path)
    node = _run_blocks(app, signer, privs)
    from celestia_app_tpu.service.server import NodeService

    svc = NodeService(node, port=0)  # ephemeral port
    svc.serve_background()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        st = json.loads(urllib.request.urlopen(f"{base}/status").read())
        assert st["height"] == app.height

        blk = json.loads(urllib.request.urlopen(f"{base}/block/1").read())
        assert blk["height"] == 1 and blk["txs"]

        # out-of-process proof query + verify
        req = urllib.request.Request(
            f"{base}/abci_query",
            data=json.dumps(
                {"path": "custom/txInclusionProof",
                 "data": {"height": 1, "tx_index": 0}}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        out = json.loads(urllib.request.urlopen(req).read())
        assert share_proof_from_json(out["proof"]).verify(
            bytes.fromhex(out["data_root"])
        )

        # broadcast a tx over HTTP and produce a block
        addr = privs[2].public_key().address()
        tx = signer.create_tx(
            addr,
            [__import__("celestia_app_tpu.chain.tx", fromlist=["MsgSend"]).MsgSend(
                addr, privs[0].public_key().address(), 5
            )],
            fee=2000, gas_limit=100_000,
        )
        req = urllib.request.Request(
            f"{base}/broadcast_tx",
            data=json.dumps(
                {"tx": base64.b64encode(tx.encode()).decode()}
            ).encode(),
        )
        res = json.loads(urllib.request.urlopen(req).read())
        assert res["code"] == 0, res
        req = urllib.request.Request(
            f"{base}/produce_block", data=json.dumps({"time": 1_700_005_000.0}).encode()
        )
        out = json.loads(urllib.request.urlopen(req).read())
        assert out["n_txs"] == 1 and out["results"][0]["code"] == 0
    finally:
        svc.shutdown()


def test_cli_init_txsim_tools(tmp_path):
    from celestia_app_tpu import cli

    home = str(tmp_path / "home")
    addrs = []
    for i in range(3):
        pk = PrivateKey.from_seed(str(i).encode())
        addrs.append(pk.public_key().address().hex())
    argv = ["init", "--home", home, "--chain-id", "cli-test-1"]
    for a in addrs:
        argv += ["--account", f"{a}=1000000000000", "--validator", f"{a}=10"]
    assert cli.main(argv) == 0
    assert cli.main(["txsim", "--home", home, "--rounds", "2"]) == 0
    assert cli.main(["blocktime", "--home", home]) == 0
    assert cli.main(["blockscan", "--home", home]) == 0
    assert cli.main(["query", "--home", home, "status"]) == 0
    # restart resume through the CLI app factory
    app, _ = cli._make_app(home)
    assert app.height == 2


def test_txsim_full_acceptance(tmp_path):
    app, signer, privs = _persistent_app(tmp_path)
    node = Node(app)
    from celestia_app_tpu.tools import txsim

    accounts = [p.public_key().address() for p in privs]
    rep = txsim.run(node, signer, accounts, rounds=3, blob_sequences=2,
                    send_sequences=1)
    assert rep.pfbs_accepted == rep.pfbs_submitted == 6
    assert rep.sends_accepted == rep.sends_submitted == 3
    assert rep.blocks == 3


def test_txsim_stake_sequences(tmp_path):
    """Stake sequences (test/txsim/stake.go): alternating delegate /
    undelegate against the validator set, every tx accepted and the
    delegation visible in state."""
    from celestia_app_tpu.chain.state import Context, InfiniteGasMeter
    from celestia_app_tpu.tools import txsim

    app, signer, privs = _persistent_app(tmp_path)
    node = Node(app)
    accounts = [p.public_key().address() for p in privs]
    ctx = Context(app.store, InfiniteGasMeter(), app.height, 0,
                  CHAIN, app.app_version)
    validators = [op for op, _p in app.staking.validators(ctx)]
    rep = txsim.run(node, signer, accounts, rounds=4, blob_sequences=1,
                    send_sequences=1, stake_sequences=1,
                    validators=validators)
    assert rep.stakes_accepted == rep.stakes_submitted == 4
    assert rep.pfbs_accepted == 4 and rep.sends_accepted == 4
    # the staker holds live delegations after the run
    staker = accounts[2]
    ctx2 = Context(app.store, InfiniteGasMeter(), app.height, 0,
                   CHAIN, app.app_version)
    total = sum(
        app.staking.delegation(ctx2, v, staker) for v in validators
    )
    assert total > 0


def test_export_genesis_reproduces_state(tmp_path):
    from celestia_app_tpu.chain.state import Context, InfiniteGasMeter
    from celestia_app_tpu.chain.staking import POWER_REDUCTION

    app, signer, privs = _persistent_app(tmp_path)
    _run_blocks(app, signer, privs)
    ctx1 = Context(app.store, InfiniteGasMeter(), 0, 0, CHAIN, 1)
    # non-operator delegation + a governed param change + a never-signing
    # recipient balance must all survive the export round trip
    d = privs[2].public_key().address()
    v0 = privs[0].public_key().address()
    app.staking.delegate(ctx1, v0, d, 2 * POWER_REDUCTION)
    params = app.blob.params(ctx1)
    params["gov_max_square_size"] = 32
    app.blob.set_params(ctx1, params)
    stranger = b"\x42" * 20  # bank balance, no auth account
    app.bank.mint(ctx1, stranger, 777)

    doc = app.export_genesis()
    assert doc["exported_height"] == app.height
    assert len(doc["validators"]) == 3

    app2 = App(chain_id=doc["chain_id"], engine="host")
    app2.init_chain(doc)
    ctx2 = Context(app2.store, InfiniteGasMeter(), 0, 0, doc["chain_id"], 1)
    for acc in doc["accounts"]:
        addr = bytes.fromhex(acc["address"])
        assert app2.bank.balance(ctx2, addr) == app.bank.balance(ctx1, addr)
    assert app2.bank.balance(ctx2, stranger) == 777
    assert app2.staking.delegation(ctx2, v0, d) == app.staking.delegation(ctx1, v0, d)
    assert app2.blob.params(ctx2)["gov_max_square_size"] == 32
    # auth records restored verbatim: numbers AND sequences (anti-replay)
    a0 = privs[0].public_key().address()
    assert app2.auth.account(ctx2, a0) == app.auth.account(ctx1, a0)
    assert app2.auth.account(ctx2, a0)["sequence"] > 0
    # height-anchored state stays consistent: the new chain resumes there
    assert app2.height == doc["exported_height"]
    blk, _ = app2.produce_block([], t=1_700_009_000.0)
    assert blk.header.height == doc["exported_height"] + 1
    ctx2 = Context(app2.store, InfiniteGasMeter(), app2.height, 0, doc["chain_id"], 1)
    app2.crisis.assert_invariants(ctx2)


def test_simulate_based_gas_estimation(tmp_path):
    """VERDICT r2 missing #5: gas estimation via true simulation — the
    measured PFB gas must match actual DeliverTx consumption better than
    being a pure formula, and simulation must not mutate state."""
    import numpy as np

    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.client.tx_client import TxClient
    from celestia_app_tpu.da.blob import Blob
    from celestia_app_tpu.da.namespace import Namespace

    app, signer, privs = _persistent_app(tmp_path)
    node = Node(app)
    addr = privs[0].public_key().address()
    rng = np.random.default_rng(0)
    blobs = [Blob(Namespace.v0(b"gasns"), rng.integers(0, 256, 5_000, dtype=np.uint8).tobytes())]

    # direct simulation: no state change, positive gas
    raw = signer.create_pay_for_blobs(addr, blobs, fee=1, gas_limit=1 << 40)
    h_before = app.store.app_hash()
    res = app.simulate_tx(raw)
    assert res.code == 0 and res.gas_used > 0
    assert app.store.app_hash() == h_before  # discarded branch

    # TxClient end-to-end with simulate-backed estimation
    client = TxClient(node, signer)
    result = client.submit_pay_for_blob(addr, blobs)
    assert result is not None

    # the estimate tracked real usage (within the 1.1 multiplier + margin)
    est = client.estimate_gas(addr, [], blobs)
    assert res.gas_used <= est <= int(res.gas_used * 1.3)


def test_remote_tx_client_over_http(tmp_path):
    """The remote TxClient mode: broadcast + simulate over the HTTP service
    (the reference's gRPC TxClient analog, pkg/user/tx_client.go)."""
    import numpy as np

    from celestia_app_tpu.client.tx_client import HttpNodeClient, TxClient
    from celestia_app_tpu.da.blob import Blob
    from celestia_app_tpu.da.namespace import Namespace
    from celestia_app_tpu.service.server import NodeService

    app, signer, privs = _persistent_app(tmp_path)
    node = _run_blocks(app, signer, privs)
    svc = NodeService(node, port=0)
    svc.serve_background()
    try:
        remote = HttpNodeClient(f"http://127.0.0.1:{svc.port}")
        addr = privs[2].public_key().address()
        rng = np.random.default_rng(1)
        blobs = [Blob(Namespace.v0(b"rmtns"),
                      rng.integers(0, 256, 900, dtype=np.uint8).tobytes())]
        # remote simulation returns measured gas
        probe = signer.create_pay_for_blobs(addr, blobs, fee=1, gas_limit=1 << 40)
        gas = remote.simulate_tx(probe)
        assert gas > 0
        # remote broadcast admits the real tx
        gas_limit = int(gas * 1.2)
        fee = max(1, int(gas_limit * 0.002) + 1)
        raw = signer.create_pay_for_blobs(
            addr, blobs, fee=fee, gas_limit=gas_limit
        )
        res = remote.broadcast_tx(raw)
        assert res.code == 0, res.log
        assert remote.status()["height"] == app.height
        # not yet in a block
        assert remote.confirm_tx(raw)["found"] is False
        # drive a block remotely, then confirmation succeeds
        remote._post("/produce_block", {"time": 1_700_001_000.0})
        conf = remote.confirm_tx(raw)
        assert conf["found"] is True and conf["height"] == app.height
    finally:
        svc.shutdown()


def test_trace_tables_block_summary(tmp_path):
    """§5.1 pkg/trace analog: per-block columnar rows, pullable over HTTP
    with resume-from-index."""
    import urllib.request as _url

    from celestia_app_tpu.service.server import NodeService
    from celestia_app_tpu.utils import telemetry

    telemetry.reset_traces()
    app, signer, privs = _persistent_app(tmp_path)
    node = _run_blocks(app, signer, privs)
    svc = NodeService(node, port=0)
    svc.serve_background()
    try:
        out = json.loads(_url.urlopen(
            f"http://127.0.0.1:{svc.port}/trace/block_summary").read())
        assert "block_summary" in out["tables"]
        rows = out["rows"]
        assert len(rows) == app.height
        assert rows[0]["height"] == 1 and rows[-1]["height"] == app.height
        assert all("data_hash" in r and "block_bytes" in r for r in rows)
        # resume from an index
        out2 = json.loads(_url.urlopen(
            f"http://127.0.0.1:{svc.port}/trace/block_summary?since={rows[-1]['_index']}"
        ).read())
        assert [r["height"] for r in out2["rows"]] == [app.height]
    finally:
        svc.shutdown()


def test_cli_tx_send_and_pfb(tmp_path):
    """`tx send` / `tx pay-for-blob`: the x/blob CLI analog, end to end
    against a durable home (resumes, signs protobuf, commits a block)."""
    from celestia_app_tpu import cli

    home = str(tmp_path / "txhome")
    assert cli.main(["init", "--home", home]) == 0
    import io
    from contextlib import redirect_stdout

    from celestia_app_tpu.chain.crypto import PrivateKey

    to_addr = PrivateKey.from_seed(b"1").public_key().address().hex()
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main([
            "tx", "send", "--home", home, "--from-seed", "0",
            "--to", to_addr, "--amount", "555",
        ])
    assert rc == 0
    out = json.loads(buf.getvalue())
    assert out["code"] == 0 and out["height"] == 1

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main([
            "tx", "pay-for-blob", "--home", home, "--from-seed", "0",
            "--namespace", "0a0b0c0d0e", "--data", "00112233445566",
        ])
    assert rc == 0
    out = json.loads(buf.getvalue())
    assert out["code"] == 0 and out["height"] == 2 and out["gas_used"] > 0


def test_native_cpp_verify_client(tmp_path):
    """§7.1.7 cross-language boundary: the C++ client drives the HTTP
    service and INDEPENDENTLY verifies a share-inclusion proof chain
    (NMT semantics + RFC-6962 + SHA-256 all reimplemented in C++). Also
    self-checks that a tampered share fails its verifier."""
    import subprocess

    from celestia_app_tpu.utils import native_build

    # make is the up-to-date check: edits to verify_client.cc must rebuild
    try:
        binary = native_build.make("verify_client")
    except (subprocess.SubprocessError, OSError) as e:
        pytest.skip(f"no C++ toolchain: {e}")

    from celestia_app_tpu.service.server import NodeService

    app, signer, privs = _persistent_app(tmp_path)
    node = _run_blocks(app, signer, privs)  # height >= 1 with a PFB block
    svc = NodeService(node, port=0)
    svc.serve_background()
    try:
        # share range [1,3) of block 1 (the namespace argument is echoed
        # into the proof envelope; verification binds the SHARES' own
        # namespace prefixes)
        r = subprocess.run(
            [binary, "127.0.0.1", str(svc.port), "1", "1", "3",
             "00" * 29],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, f"stdout={r.stdout!r} stderr={r.stderr!r}"
        assert "VERIFIED" in r.stdout
    finally:
        svc.shutdown()


def test_http_service_concurrent_stress(tmp_path):
    """§5.2 race-detection analog: hammer the threaded HTTP service from
    several client threads (broadcasts, status, traces, blocks, proofs)
    while the server produces blocks — no 500s, no torn reads, and the
    node finishes at a consistent height."""
    import threading
    import urllib.request as _url

    from celestia_app_tpu.service.server import NodeService

    app, signer, privs = _persistent_app(tmp_path)
    node = _run_blocks(app, signer, privs)
    svc = NodeService(node, port=0)
    svc.serve_background()
    base = f"http://127.0.0.1:{svc.port}"
    errors: list[str] = []
    stop = threading.Event()

    def hit(path):
        try:
            with _url.urlopen(base + path, timeout=30) as r:
                json.loads(r.read())
        except Exception as e:  # noqa: BLE001 — collect everything
            errors.append(f"{path}: {type(e).__name__}: {e}")

    def reader(path):
        while not stop.is_set():
            hit(path)

    def producer():
        for i in range(5):
            req = _url.Request(
                base + "/produce_block",
                data=json.dumps({"time": 1_700_000_500.0 + i}).encode(),
                headers={"Content-Type": "application/json"},
            )
            try:
                with _url.urlopen(req, timeout=60) as r:
                    json.loads(r.read())
            except Exception as e:  # noqa: BLE001
                errors.append(f"produce: {type(e).__name__}: {e}")

    threads = [
        threading.Thread(target=reader, args=("/status",)),
        threading.Thread(target=reader, args=("/trace/block_summary",)),
        threading.Thread(target=reader, args=("/block/1",)),
        threading.Thread(target=producer),
    ]
    try:
        for t in threads:
            t.start()
        threads[-1].join(timeout=120)  # producer finishes its 5 blocks
        stop.set()
        for t in threads[:-1]:
            t.join(timeout=30)
        assert not errors, errors[:5]
        # trace table is consistent: strictly increasing heights, no tears
        with _url.urlopen(base + "/trace/block_summary", timeout=30) as r:
            rows = json.loads(r.read())["rows"]
        heights = [row["height"] for row in rows]
        assert heights == sorted(heights)
        assert heights[-1] == app.height
    finally:
        stop.set()
        svc.shutdown()


def test_cli_devnet(tmp_path):
    """The local_devnet analog: N validators, real consensus, identical
    app hashes, HTTP service per node — through the CLI entry point."""
    import io
    from contextlib import redirect_stdout

    from celestia_app_tpu import cli

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main([
            "devnet", "--home", str(tmp_path / "dv"), "--validators", "3",
            "--blocks", "2", "--block-time", "0.01", "--load",
        ])
    assert rc == 0
    out = json.loads(buf.getvalue())
    assert out["validators"] == 3 and out["final_height"] == 2


def test_cli_snapshot_create_restore(tmp_path):
    """State-sync via the CLI: create chunks from one home, bootstrap a
    fresh home, identical app hash; tampered chunk rejected."""
    import io
    from contextlib import redirect_stdout

    from celestia_app_tpu import cli

    src = str(tmp_path / "src")
    dst = str(tmp_path / "dst")
    snap = str(tmp_path / "snap")
    assert cli.main(["init", "--home", src]) == 0
    assert cli.main(["txsim", "--home", src, "--rounds", "2"]) == 0
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["snapshot", "create", "--home", src, "--out", snap]) == 0
    created = json.loads(buf.getvalue())
    assert cli.main(["init", "--home", dst]) == 0
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["snapshot", "restore", "--home", dst, "--out", snap]) == 0
    restored = json.loads(buf.getvalue())
    assert restored["app_hash"] == created["app_hash"]
    assert restored["restored_height"] == created["height"]

    # tamper a chunk: restore refuses
    chunk0 = os.path.join(snap, "chunk_000000.json")
    raw = open(chunk0, "rb").read()
    open(chunk0, "wb").write(raw[:-2] + b'"]')  # corrupt
    dst2 = str(tmp_path / "dst2")
    assert cli.main(["init", "--home", dst2]) == 0
    with pytest.raises(ValueError):
        cli.main(["snapshot", "restore", "--home", dst2, "--out", snap])


def test_grpc_cosmos_tx_service(tmp_path):
    """VERDICT r2 row 42: the real gRPC:9090 surface — cosmos.tx.v1beta1
    Service/BroadcastTx + Simulate + GetTx with the real wire messages,
    driven by a plain grpcio client the way pkg/user/tx_client.go is."""
    import grpc as grpc_mod

    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.chain.tx import MsgSend
    from celestia_app_tpu.service.grpc_server import GrpcTxServer
    from celestia_app_tpu.wire import txpb

    app, signer, privs = _persistent_app(tmp_path)
    node = Node(app)
    server = GrpcTxServer(node, port=0)
    try:
        chan = grpc_mod.insecure_channel(f"127.0.0.1:{server.port}")
        ident = lambda x: x  # noqa: E731
        bcast = chan.unary_unary(
            "/cosmos.tx.v1beta1.Service/BroadcastTx",
            request_serializer=ident, response_deserializer=ident)
        sim = chan.unary_unary(
            "/cosmos.tx.v1beta1.Service/Simulate",
            request_serializer=ident, response_deserializer=ident)
        get_tx = chan.unary_unary(
            "/cosmos.tx.v1beta1.Service/GetTx",
            request_serializer=ident, response_deserializer=ident)

        a0 = privs[0].public_key().address()
        a1 = privs[1].public_key().address()
        tx = signer.create_tx(a0, [MsgSend(a0, a1, 321)], fee=2000,
                              gas_limit=100_000)
        raw = tx.encode()

        # Simulate measures gas
        out = txpb.parse_simulate_response(
            sim(txpb.simulate_request_pb(raw)))
        assert out["gas_used"] > 0

        # BroadcastTx admits it
        resp = txpb.parse_broadcast_tx_response(
            bcast(txpb.broadcast_tx_request_pb(raw)))
        assert resp["code"] == 0, resp
        import hashlib as _h

        txhash = _h.sha256(raw).hexdigest()
        # not yet committed: NOT_FOUND
        with pytest.raises(grpc_mod.RpcError) as exc:
            get_tx(txpb.get_tx_request_pb(txhash))
        assert exc.value.code() == grpc_mod.StatusCode.NOT_FOUND
        # commit a block, then GetTx succeeds with the height
        node.produce_block(t=1_700_000_900.0)
        got = txpb.parse_get_tx_response(get_tx(txpb.get_tx_request_pb(txhash)))
        assert got["code"] == 0 and got["height"] == app.height
        assert got["txhash"].lower() == txhash
        # a failing simulate maps to INVALID_ARGUMENT
        bad = signer.create_tx(a0, [MsgSend(a0, a1, 10**18)], fee=2000,
                               gas_limit=100_000)
        with pytest.raises(grpc_mod.RpcError) as exc:
            sim(txpb.simulate_request_pb(bad.encode()))
        assert exc.value.code() == grpc_mod.StatusCode.INVALID_ARGUMENT
    finally:
        server.stop()


def test_grpc_service_rejects_bad_inputs(tmp_path):
    import grpc as grpc_mod

    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.service.grpc_server import GrpcTxServer
    from celestia_app_tpu.wire import txpb
    from celestia_app_tpu.wire.proto import field_string, field_varint

    app, signer, privs = _persistent_app(tmp_path)
    server = GrpcTxServer(Node(app), port=0)
    try:
        chan = grpc_mod.insecure_channel(f"127.0.0.1:{server.port}")
        ident = lambda x: x  # noqa: E731
        bcast = chan.unary_unary(
            "/cosmos.tx.v1beta1.Service/BroadcastTx",
            request_serializer=ident, response_deserializer=ident)
        get_tx = chan.unary_unary(
            "/cosmos.tx.v1beta1.Service/GetTx",
            request_serializer=ident, response_deserializer=ident)
        # unsupported broadcast mode -> INVALID_ARGUMENT, not silent SYNC
        with pytest.raises(grpc_mod.RpcError) as exc:
            bcast(txpb.broadcast_tx_request_pb(b"tx", mode=1))  # BLOCK
        assert exc.value.code() == grpc_mod.StatusCode.INVALID_ARGUMENT
        # malformed hash -> INVALID_ARGUMENT, not UNKNOWN
        with pytest.raises(grpc_mod.RpcError) as exc:
            get_tx(field_string(1, "not-hex"))
        assert exc.value.code() == grpc_mod.StatusCode.INVALID_ARGUMENT
    finally:
        server.stop()


def test_grpc_bootstrap_and_pfb_submit(tmp_path):
    """VERDICT r3 #3 done-criterion: a TxClient bootstraps chain-id,
    account number/sequence, and min gas price over gRPC ALONE
    (SetupTxClient, pkg/user/tx_client.go:147-198) and submits a PFB
    end-to-end on the same channel."""
    import threading

    import numpy as np

    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.client.tx_client import setup_tx_client_grpc
    from celestia_app_tpu.da.blob import Blob
    from celestia_app_tpu.da.namespace import Namespace
    from celestia_app_tpu.service.grpc_server import GrpcTxServer
    from celestia_app_tpu.wire import bech32

    app, signer, privs = _persistent_app(tmp_path)
    node = Node(app)
    node.produce_block(t=1_700_000_500.0)  # height 1 for GetLatestBlock
    server = GrpcTxServer(node, port=0)
    try:
        # an extra key with no account in state must be skipped, as the
        # reference skips keyring records absent from state
        ghost = PrivateKey.from_seed(b"\xAA" * 4)
        client = setup_tx_client_grpc(
            f"127.0.0.1:{server.port}", [privs[0], privs[1], ghost]
        )
        # chain-id and accounts came from the wire, not local config
        assert client.signer.chain_id == CHAIN
        assert len(client.signer.accounts) == 2
        a0 = privs[0].public_key().address()
        acc = client.signer.accounts[a0]
        assert (acc.number, acc.sequence) == (0, 0)
        assert ghost.public_key().address() not in client.signer.accounts
        # min gas price came from node Config / minfee params
        assert client.default_gas_price and client.default_gas_price > 0
        # bank balance is queryable over the same channel
        assert client.node.query_balance(bech32.encode(a0)) == 10**12
        assert client.node.blob_params()["gov_max_square_size"] > 0

        # submit a PFB: broadcast over gRPC, commit mid-confirm, confirm
        rng = np.random.default_rng(5)
        blobs = [Blob(Namespace.v0(b"grpcb"),
                      rng.integers(0, 256, 700, dtype=np.uint8).tobytes())]
        timer = threading.Timer(
            0.4, lambda: node.produce_block(t=1_700_000_600.0)
        )
        timer.start()
        try:
            conf = client.submit_pay_for_blob(a0, blobs)
        finally:
            timer.cancel()
        assert conf["found"] is True and conf["height"] == app.height
        assert client.signer.accounts[a0].sequence == 1
    finally:
        server.stop()


def test_prometheus_metrics_endpoint(tmp_path):
    """§5.1: /metrics serves the Prometheus text exposition of the node's
    counters and prepare/process/commit timing summaries."""
    from celestia_app_tpu.service.server import NodeService
    from celestia_app_tpu.utils import telemetry

    app, signer, privs = _persistent_app(tmp_path)
    node = _run_blocks(app, signer, privs)
    svc = NodeService(node, port=0)
    svc.serve_background()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{svc.port}/metrics"
        ) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "# TYPE" in body
        assert "celestia_prepare_proposal_seconds_count" in body
        assert "celestia_prepare_proposal_seconds_sum" in body
        # counters render as prometheus counters
        snap = telemetry.snapshot()
        if snap["counters"]:
            assert "_total " in body
    finally:
        svc.shutdown()


def test_start_interval_snapshots_with_pruning(tmp_path):
    """The node loop writes state-sync snapshots every N blocks and prunes
    to keep-recent (default_overrides.go:294-297: interval 1500, keep 2 —
    shrunk via config for the test), and a fresh home restores from the
    newest one."""
    from celestia_app_tpu import cli

    home = str(tmp_path / "snapnode")
    assert cli.main(["init", "--home", home]) == 0
    cfg_path = os.path.join(home, "config.json")
    cfg = json.load(open(cfg_path))
    cfg["snapshot_interval_blocks"] = 2
    cfg["snapshot_keep_recent"] = 1
    json.dump(cfg, open(cfg_path, "w"))

    assert cli.main(["start", "--home", home, "--blocks", "5",
                     "--block-time", "0.01", "--listen", "0"]) == 0
    snaps = sorted(os.listdir(os.path.join(home, "snapshots")))
    assert snaps == ["4"], snaps  # heights 2 and 4 written, 2 pruned

    # a fresh home bootstraps from the interval snapshot
    dst = str(tmp_path / "joiner")
    assert cli.main(["init", "--home", dst]) == 0
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main(["snapshot", "restore", "--home", dst, "--out",
                       os.path.join(home, "snapshots", "4")])
    assert rc == 0
    out = json.loads(buf.getvalue())
    assert out["restored_height"] == 4


def test_grpc_staking_and_gov_queries(tmp_path):
    """cosmos.staking.v1beta1.Query Validator/Validators and
    cosmos.gov.v1beta1.Query Proposal over gRPC — the module query
    surface beyond the SetupTxClient bootstrap four (app/app.go:393-425
    serves every module's querier)."""
    import grpc as grpc_mod

    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.chain.tx import MsgSubmitProposal
    from celestia_app_tpu.service.grpc_server import GrpcTxServer
    from celestia_app_tpu.wire import bech32 as b32
    from celestia_app_tpu.wire import txpb
    from celestia_app_tpu.wire.proto import field_string, field_varint

    app, signer, privs = _persistent_app(tmp_path)
    node = Node(app)
    # one live proposal so gov has state to serve
    a0 = privs[0].public_key().address()
    import json as json_mod

    tx = signer.create_tx(
        a0,
        [MsgSubmitProposal(
            proposer=a0,
            changes_json=json_mod.dumps(
                [{"param": "blob/gas_per_blob_byte", "value": 9}]
            ).encode(),
            initial_deposit=10_000_000,
            title="t")],
        fee=2000, gas_limit=400_000,
    )
    assert node.broadcast_tx(tx.encode()).code == 0
    node.produce_block(t=1_700_000_100.0)

    server = GrpcTxServer(node, port=0)
    try:
        chan = grpc_mod.insecure_channel(f"127.0.0.1:{server.port}")
        ident = lambda x: x  # noqa: E731

        val = chan.unary_unary(
            "/cosmos.staking.v1beta1.Query/Validator",
            request_serializer=ident, response_deserializer=ident)
        vals = chan.unary_unary(
            "/cosmos.staking.v1beta1.Query/Validators",
            request_serializer=ident, response_deserializer=ident)
        prop = chan.unary_unary(
            "/cosmos.gov.v1beta1.Query/Proposal",
            request_serializer=ident, response_deserializer=ident)

        op_str = b32.encode(a0, b32.HRP_VALOPER)
        got = txpb.parse_query_validator_response(
            val(field_string(1, op_str)))
        assert got["operator_address"] == op_str
        assert got["bonded"] is True and got["jailed"] is False
        assert got["tokens"] == 10 * 1_000_000

        all_vals = txpb.parse_query_validators_response(vals(b""))
        assert len(all_vals) == 3
        assert {v["operator_address"] for v in all_vals} == {
            b32.encode(p.public_key().address(), b32.HRP_VALOPER)
            for p in privs
        }

        pid, status = txpb.parse_query_proposal_response(
            prop(field_varint(1, 1, emit_default=True)))
        assert pid == 1 and status in ("deposit_period", "voting_period")

        # unknown ids/addresses are NOT_FOUND, not crashes
        with pytest.raises(grpc_mod.RpcError) as exc:
            prop(field_varint(1, 99, emit_default=True))
        assert exc.value.code() == grpc_mod.StatusCode.NOT_FOUND
        with pytest.raises(grpc_mod.RpcError) as exc:
            val(field_string(
                1, b32.encode(b"\x01" * 20, b32.HRP_VALOPER)))
        assert exc.value.code() == grpc_mod.StatusCode.NOT_FOUND
    finally:
        server.stop()
