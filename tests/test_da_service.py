"""The DA-core shim surface (SURVEY §7.1.7, VERDICT r4 missing #1): a
FOREIGN process submits an ODS and gets back the byte-identical DAH the
framework's own pipeline computes, plus share proofs — over HTTP
(/da/extend_commit, /da/prove_shares on the node service AND the
standalone da-serve sidecar) and over gRPC
(celestia_tpu.da.v1.DAService). The C++ end of the story lives in
native/da_client.cc (driven by test_native_da_client below)."""

from __future__ import annotations

import base64
import json
import urllib.request

import numpy as np
import pytest

from celestia_app_tpu import appconsts
from celestia_app_tpu.service.da_service import DACore, DAError, DAService

T0 = 1_700_000_000.0


def _ods_shares(k: int, seed: int = 7) -> list[bytes]:
    """k*k deterministic 512-byte shares with valid namespace prefixes."""
    rng = np.random.default_rng(seed)
    shares = []
    for i in range(k * k):
        ns = bytes([0] * 18) + bytes([1 + (i % 3)]) + bytes([0] * 10)
        body = rng.integers(0, 256, appconsts.SHARE_SIZE - 29,
                            dtype=np.uint8).tobytes()
        shares.append(ns + body)
    return sorted(shares)  # namespace-ordered, as a square builder emits


def _b64_ods(shares: list[bytes]) -> str:
    return base64.b64encode(b"".join(shares)).decode()


def test_extend_and_commit_matches_internal_pipeline():
    """The RPC result IS the framework's DAH — byte-identical roots."""
    from celestia_app_tpu.da import dah as dah_mod
    from celestia_app_tpu.utils import refimpl

    shares = _ods_shares(4)
    core = DACore(engine="host")
    out = core.extend_and_commit({"ods": _b64_ods(shares),
                                  "square_size": 4})

    ods = dah_mod.shares_to_ods(shares)
    _eds, rows, cols, root = refimpl.pipeline_host(ods)
    assert out["square_size"] == 4
    assert [bytes.fromhex(r) for r in out["row_roots"]] == rows
    assert [bytes.fromhex(r) for r in out["col_roots"]] == cols
    assert out["data_root"] == root.hex()
    assert len(out["row_roots"]) == 8  # 2k roots each axis


def test_prove_shares_from_cache_and_fresh_ods():
    from celestia_app_tpu.chain.query import share_proof_from_json

    shares = _ods_shares(4, seed=11)
    core = DACore(engine="host")
    out = core.extend_and_commit({"ods": _b64_ods(shares)})
    root = bytes.fromhex(out["data_root"])

    # cached path (data_root reference — no recompute)
    ns = shares[5][:29]
    pf_doc = core.prove_shares({
        "data_root": out["data_root"], "start": 5, "end": 9,
        "namespace": ns.hex(),
    })
    pf = share_proof_from_json(pf_doc["proof"])
    assert pf.verify(root)
    assert pf.data[0] == shares[5]

    # stateless path (fresh ODS, namespace defaulted from share prefix)
    pf_doc2 = core.prove_shares({
        "ods": _b64_ods(shares), "start": 0, "end": 2,
    })
    assert share_proof_from_json(pf_doc2["proof"]).verify(root)

    # tampered share data must not verify
    bad_data = list(pf_doc["proof"]["data"])
    flipped = bytearray(base64.b64decode(bad_data[0]))
    flipped[100] ^= 0xFF
    bad_data[0] = base64.b64encode(bytes(flipped)).decode()
    bad = dict(pf_doc["proof"], data=bad_data)
    assert not share_proof_from_json(bad).verify(root)


def test_da_core_rejects_malformed_input():
    core = DACore(engine="host")
    with pytest.raises(DAError, match="power-of-two"):
        core.extend_and_commit(
            {"ods": base64.b64encode(b"\x00" * (3 * 512)).decode()})
    with pytest.raises(DAError, match="share size"):
        core.extend_and_commit(
            {"ods": base64.b64encode(b"\x00" * 100).decode()})
    with pytest.raises(DAError, match="does not match"):
        core.extend_and_commit({"ods": _b64_ods(_ods_shares(2)),
                                "square_size": 4})
    with pytest.raises(DAError, match="no cached square"):
        core.prove_shares({"data_root": "ab" * 32, "start": 0, "end": 1})
    # cache is bounded: oldest square evicted
    small = DACore(engine="host", cache_squares=1)
    a = small.extend_and_commit({"ods": _b64_ods(_ods_shares(2, seed=1))})
    small.extend_and_commit({"ods": _b64_ods(_ods_shares(2, seed=2))})
    with pytest.raises(DAError, match="no cached square"):
        small.prove_shares({"data_root": a["data_root"],
                            "start": 0, "end": 1})


def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def test_standalone_da_serve_http():
    """The sidecar shape: no chain anywhere in the process."""
    svc = DAService(DACore(engine="host"), port=0).serve_background()
    try:
        base = f"http://127.0.0.1:{svc.port}"
        shares = _ods_shares(2, seed=3)
        out = _post(base + "/da/extend_commit",
                    {"ods": _b64_ods(shares)})
        assert len(out["row_roots"]) == 4 and len(out["data_root"]) == 64

        from celestia_app_tpu.chain.query import share_proof_from_json

        pf_doc = _post(base + "/da/prove_shares", {
            "data_root": out["data_root"], "start": 0, "end": 4,
            "namespace": shares[0][:29].hex(),
        })
        assert share_proof_from_json(pf_doc["proof"]).verify(
            bytes.fromhex(out["data_root"]))

        # client errors are 400s with a reason, not 500s
        req = urllib.request.Request(
            base + "/da/extend_commit",
            data=json.dumps({"ods": "AAAA"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            raise AssertionError("malformed ods accepted")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "share size" in json.loads(e.read())["error"]
    finally:
        svc.shutdown()


def test_node_service_mounts_da_routes(tmp_path):
    """The integrated shape: the same routes on a chain-backed node."""
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.service.server import NodeService

    from test_app import make_app

    app, _signer, _privs = make_app()
    svc = NodeService(Node(app), port=0)
    svc.serve_background()
    try:
        out = _post(
            f"http://127.0.0.1:{svc.port}/da/extend_commit",
            {"ods": _b64_ods(_ods_shares(2, seed=5))},
        )
        assert len(out["col_roots"]) == 4
    finally:
        svc.shutdown()


def test_grpc_da_service_round_trip(tmp_path):
    """A gRPC caller (any language with the .proto) gets the identical
    DAH bytes — proto/celestia_tpu/da/v1/da.proto is the contract."""
    grpc = pytest.importorskip("grpc")

    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.service.grpc_server import GrpcTxServer
    from celestia_app_tpu.wire import proto as p

    from test_app import make_app

    app, _signer, _privs = make_app()
    server = GrpcTxServer(Node(app), port=0)
    try:
        chan = grpc.insecure_channel(f"127.0.0.1:{server.port}")
        shares = _ods_shares(2, seed=9)
        req = (p.field_bytes(1, b"".join(shares))
               + p.field_varint(2, 2))
        call = chan.unary_unary(
            "/celestia_tpu.da.v1.DAService/ExtendAndCommit",
            request_serializer=lambda x: x,
            response_deserializer=lambda x: x,
        )
        raw = call(req, timeout=30)
        resp = p.Fields(raw)
        rows = resp.repeated_bytes(2)
        cols = resp.repeated_bytes(3)
        root = resp.get_bytes(4)
        core = DACore(engine="host")
        want = core.extend_and_commit({"ods": _b64_ods(shares)})
        assert [r.hex() for r in rows] == want["row_roots"]
        assert [c.hex() for c in cols] == want["col_roots"]
        assert root.hex() == want["data_root"]
        assert resp.get_int(1) == 2

        # ProveShares over gRPC, verified against the data root
        from celestia_app_tpu.chain.query import share_proof_from_json

        preq = (p.field_bytes(1, root) + p.field_varint(3, 0)
                + p.field_varint(4, 2)
                + p.field_bytes(5, shares[0][:29]))
        pcall = chan.unary_unary(
            "/celestia_tpu.da.v1.DAService/ProveShares",
            request_serializer=lambda x: x,
            response_deserializer=lambda x: x,
        )
        praw = pcall(preq, timeout=30)
        presp = p.Fields(praw)
        assert presp.get_bytes(2) == root
        pf = p.Fields(presp.get_bytes(1))
        # decode back to the JSON form and reuse the verifier
        import base64 as _b64

        rp = p.Fields(pf.get_bytes(4))
        doc = {
            "data": [_b64.b64encode(d).decode()
                     for d in pf.repeated_bytes(1)],
            "namespace": pf.get_bytes(3).hex(),
            "start_share": pf.get_int(5),
            "end_share": pf.get_int(6),
            "share_proofs": [
                {
                    "start": (sp := p.Fields(raw_sp)).get_int(1),
                    "end": sp.get_int(2),
                    "total": sp.get_int(3),
                    "nodes": [_b64.b64encode(n).decode()
                              for n in sp.repeated_bytes(4)],
                }
                for raw_sp in pf.repeated_bytes(2)
            ],
            "row_proof": {
                "row_roots": [r.hex() for r in rp.repeated_bytes(1)],
                "proofs": [
                    {
                        "index": (mp := p.Fields(raw_mp)).get_int(1),
                        "total": mp.get_int(2),
                        "leaf_hash": _b64.b64encode(
                            mp.get_bytes(3)).decode(),
                        "aunts": [_b64.b64encode(a).decode()
                                  for a in mp.repeated_bytes(4)],
                    }
                    for raw_mp in rp.repeated_bytes(2)
                ],
                "start_row": rp.get_int(3),
                "end_row": rp.get_int(4),
            },
        }
        assert share_proof_from_json(doc).verify(root)
    finally:
        server.stop()


def test_native_da_client_end_to_end():
    """THE foreign-caller story (VERDICT r4 missing #1 done-criterion): a
    C++ process builds an ODS, recomputes the expected DAH with its own
    GF(2^8)/NMT/Merkle implementation, submits the ODS over the wire, and
    requires the returned DAH BYTE-IDENTICAL — then fetches and verifies
    a share proof, all without Python in the loop."""
    import subprocess

    from celestia_app_tpu.utils import native_build

    # make is the up-to-date check: the binary is NOT in version control
    # (ADVICE r5 #2), so build it from source here; skip only when the
    # environment has no C++ toolchain
    try:
        binary = native_build.make("da_client")
    except (subprocess.SubprocessError, OSError) as e:
        pytest.skip(f"cannot build native/da_client: {e}")
    svc = DAService(DACore(engine="host"), port=0).serve_background()
    try:
        out = subprocess.run(
            [binary, "127.0.0.1", str(svc.port), "8"],
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert "DA OK" in out.stdout
    finally:
        svc.shutdown()


def test_prove_shares_client_errors_are_daerrors():
    """Code-review regression: malformed prove_shares inputs must raise
    DAError (transports map to 400/INVALID_ARGUMENT), never IndexError/
    KeyError/bare ValueError (500s)."""
    core = DACore(engine="host")
    out = core.extend_and_commit({"ods": _b64_ods(_ods_shares(2, seed=4))})
    root = out["data_root"]
    with pytest.raises(DAError, match="invalid share range"):
        core.prove_shares({"data_root": root, "start": 3, "end": 3})
    with pytest.raises(DAError, match="invalid share range"):
        core.prove_shares({"data_root": root, "start": 8, "end": 9})
    with pytest.raises(DAError, match="integer start"):
        core.prove_shares({"data_root": root})
    with pytest.raises(DAError, match="hex"):
        core.prove_shares({"data_root": root, "start": 0, "end": 1,
                           "namespace": "zz"})
    with pytest.raises(DAError, match="missing field"):
        core.handle("/da/extend_commit", {})


def test_grpc_and_http_share_one_square_cache(tmp_path):
    """Code-review regression: one process serving both transports must
    serve a /da/prove_shares referencing a square extended over gRPC —
    one DACore, one cache."""
    grpc = pytest.importorskip("grpc")

    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.service.grpc_server import GrpcTxServer
    from celestia_app_tpu.service.server import NodeService
    from celestia_app_tpu.wire import proto as p

    from test_app import make_app

    app, _signer, _privs = make_app()
    node = Node(app)
    svc = NodeService(node, port=0)
    svc.serve_background()
    server = GrpcTxServer(node, port=0, lock=svc.lock,
                          da_core=svc.da_core)
    try:
        chan = grpc.insecure_channel(f"127.0.0.1:{server.port}")
        shares = _ods_shares(2, seed=21)
        raw = chan.unary_unary(
            "/celestia_tpu.da.v1.DAService/ExtendAndCommit",
            request_serializer=lambda x: x,
            response_deserializer=lambda x: x,
        )(p.field_bytes(1, b"".join(shares)), timeout=30)
        root = p.Fields(raw).get_bytes(4)
        # the HTTP transport must find the gRPC-extended square
        pf_doc = _post(f"http://127.0.0.1:{svc.port}/da/prove_shares", {
            "data_root": root.hex(), "start": 0, "end": 2,
        })
        from celestia_app_tpu.chain.query import share_proof_from_json

        assert share_proof_from_json(pf_doc["proof"]).verify(root)

        # malformed gRPC input surfaces INVALID_ARGUMENT with the reason
        bad = chan.unary_unary(
            "/celestia_tpu.da.v1.DAService/ExtendAndCommit",
            request_serializer=lambda x: x,
            response_deserializer=lambda x: x,
        )
        with pytest.raises(grpc.RpcError) as exc:
            bad(p.field_bytes(1, b"\x00" * (3 * 512)), timeout=30)
        assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        assert "power-of-two" in exc.value.details()
    finally:
        server.stop()
        svc.shutdown()
