"""A namespace read is cut where the square lives.

A height whose square lives only on the devices — a mesh entry with its
rows split over 4 of the 8 virtual CPU devices, or a one-device entry built
without a host copy — answers a rollup's namespace read by a search over
its resident row level stack and one gather of the rows the namespace
touches, with their proof nodes. The contract under test: every
`NamespaceData` is the host reference's (`get_namespace_data` over a host
prover of the same square) byte for byte and verifies against the header;
nothing of the square or of a level stack comes down, no host prover is
built, the entry stays "device" and later samples still gather; entries
with host bytes read as before. Through `NodeService` and the `BlobService`
sidecar the /blob/ routes answer through the node's HTTP front
(`das/server.serve_http`): spanned, counted, 4xx for a malformed request.
A read's reply is encoded in one base64 pass a namespace
(`das/blob_packs.EncodedShares`): the rendered body and the in-process
dict are the per-share encoder's FORMATS §21.1 doc to the byte.
"""

import base64
import http.client
import json

import numpy as np
import pytest

from celestia_app_tpu.da import dah as dah_mod
from celestia_app_tpu.da import edscache
from celestia_app_tpu.da import namespace as ns_mod
from celestia_app_tpu.da import namespace_data as nsd
from celestia_app_tpu.da import namespace_device as nsdev
from celestia_app_tpu.da import square as square_mod
from celestia_app_tpu.da.blob import Blob
from celestia_app_tpu.da.namespace import Namespace
from celestia_app_tpu.chain.query import _share_proof_json
from celestia_app_tpu.da.square import PfbEntry
from celestia_app_tpu.das import blob_packs
from celestia_app_tpu.das.server import SampleError
from celestia_app_tpu.utils import telemetry

CHIPS = 4
HEIGHTS = (7, 8)
# blob sizes (bytes) a square of each size is laid out from: 4 namespaces,
# ranges that cross a device's rows, and tail padding over whole rows
SIZES = {8: [478 * 9, 478 * 8, 900, 478 * 3],
         16: [478 * 40, 478 * 16, 478 * 30, 2000],
         64: [478 * 700, 478 * 400, 900, 478 * 300]}


def _counter(name: str) -> int:
    return telemetry.snapshot().get("counters", {}).get(name, 0)


def _ods(k: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    blobs = [Blob(Namespace.v0(bytes([0x10 + i]) * 5),
                  rng.integers(0, 256, n, dtype=np.uint8).tobytes())
             for i, n in enumerate(SIZES[k])]
    sq = square_mod.build([b"a-tx"], [PfbEntry(b"a-pfb", tuple(blobs))],
                          k, 64)
    ods = dah_mod.shares_to_ods(sq.share_bytes())
    assert ods.shape[0] == k
    return ods


def _namespaces(ods: np.ndarray) -> list[bytes]:
    """Every namespace of the original square, then absent ones: one just
    below each blob namespace (its row straddles it), one below every
    share (no row window covers it)."""
    present = sorted({row.tobytes() for row in ods[:, :, :29].reshape(-1, 29)})
    absent = [Namespace.v0(bytes([0x10 + i]) * 4 + b"\x01").raw
              for i in range(4)]
    return present + absent + [Namespace.v0(b"\x05" * 5).raw, bytes(29)]


def _mesh_entry(ods: np.ndarray, shard_small):
    """A device entry with its rows split over CHIPS devices, and both of
    its level passes run there."""
    with shard_small(chips=CHIPS):
        entry = edscache.compute_entry(ods, "device")
    assert entry.chips == CHIPS
    passes = _counter("mesh.sharded_level_passes")
    entry.warm()
    assert _counter("mesh.sharded_level_passes") - passes == 2
    return entry


def _entry(kind: str, ods: np.ndarray, shard_small):
    if kind == "mesh":
        return _mesh_entry(ods, shard_small)
    started = edscache.compute_entry(ods, "device")
    entry = edscache.DeviceEntry(started._eds_dev, started.dah,
                                 started.data_root)
    assert entry.chips == 1
    return entry


def _doc(entry, namespace: bytes, nd) -> str:
    return json.dumps(blob_packs.live_namespace_doc(entry, namespace, nd=nd))


@pytest.mark.parametrize("k", [8, 16])
@pytest.mark.parametrize("kind", ["mesh", "one-chip"])
def test_resident_reads_equal_the_host_reference(kind, k, shard_small):
    """Every namespace of a seeded square — the reserved tx and PFB
    namespaces, ranges that cross a device's rows and that fill whole
    rows, straddling absences and one no row covers — read off an entry
    with no host copy: the host reference's bytes, verified against the
    header; the entry still "device", nothing came down, and a later
    sample still gathers."""
    ods = _ods(k, 4200 + k)
    entry = _entry(kind, ods, shard_small)
    host = edscache.compute_entry(ods, "host")
    prover = host.get_prover()
    entry.warm()
    c0 = _counter("edscache.host_crossings")
    g0 = _counter("blob.ns_gathers")
    r0 = _counter("blob.rows_gathered")
    reader = entry.namespace_reader()
    assert isinstance(reader, nsdev.ResidentReader)
    spaces = _namespaces(ods)
    batch = reader.assemble(spaces, reader.search(spaces))
    per_chip = 2 * k // entry.chips
    seen, rows = set(), 0
    for namespace, nd in zip(spaces, batch):
        want = nsd.get_namespace_data(prover, namespace)
        assert _doc(entry, namespace, nd) == _doc(host, namespace, want)
        assert _doc(entry, namespace, reader.read_one(namespace)) == \
            _doc(host, namespace, want)
        assert nsd.verify_namespace_data(host.dah, namespace, nd)
        if nd.proof is None:
            seen.add("absent, no row covers it")
            continue
        if not nd.shares:
            seen.add("absent, a straddling row")
            continue
        first, last = nd.proof.row_proof.start_row, nd.proof.row_proof.end_row
        rows += last - first + 1
        if first // per_chip != last // per_chip:
            seen.add("across devices")
        if any((p.start, p.end) == (0, k) for p in nd.proof.share_proofs):
            seen.add("whole rows")
        if namespace in (ns_mod.TX_NAMESPACE.raw,
                         ns_mod.PAY_FOR_BLOB_NAMESPACE.raw):
            seen.add(namespace)
    want_seen = {"absent, no row covers it", "absent, a straddling row",
                 "whole rows", ns_mod.TX_NAMESPACE.raw,
                 ns_mod.PAY_FOR_BLOB_NAMESPACE.raw}
    if kind == "mesh":
        want_seen.add("across devices")
    assert want_seen <= seen
    present = sum(1 for nd in batch if nd.shares)
    # one gather a present namespace in the batch and one a read_one
    assert _counter("blob.ns_gathers") - g0 == 2 * present
    assert _counter("blob.rows_gathered") - r0 == 2 * rows
    assert _counter("edscache.host_crossings") == c0
    assert entry.residency() == "device"
    assert not entry.proves_on_host()
    s0 = _counter("das.samples_gathered")
    [(share, proof)] = entry.prove_cells([(1, 2)])
    assert _counter("das.samples_gathered") - s0 == 1
    assert (share, proof.nodes) == \
        (prover.prove_cell(1, 2)[0], prover.prove_cell(1, 2)[1].nodes)
    assert _counter("edscache.host_crossings") == c0


@pytest.mark.parametrize("holds", ["host-entry", "started-copy",
                                   "host-prover"])
def test_entries_with_host_bytes_read_from_them(holds, shard_small):
    """An entry that holds host bytes — the host engine's, one whose
    engine started a copy (every one-chip device engine), one whose host
    prover was built — reads from its host prover as before: the same
    bytes, and no row gather."""
    k = 8
    ods = _ods(k, 4300)
    if holds == "host-entry":
        entry = edscache.compute_entry(ods, "host")
    elif holds == "started-copy":
        entry = edscache.compute_entry(ods, "device")
    else:
        entry = _mesh_entry(ods, shard_small)
        entry.get_prover()
    host = edscache.compute_entry(ods, "host")
    prover = host.get_prover()
    g0 = _counter("blob.ns_gathers")
    reader = entry.namespace_reader()
    assert isinstance(reader, nsdev.ProverReader)
    spaces = _namespaces(ods)
    for namespace, nd in zip(spaces, reader.assemble(
            spaces, reader.search(spaces))):
        want = nsd.get_namespace_data(prover, namespace)
        assert _doc(entry, namespace, nd) == _doc(host, namespace, want)
        assert _doc(entry, namespace, reader.read_one(namespace)) == \
            _doc(host, namespace, want)
    assert _counter("blob.ns_gathers") == g0


# ---------------------------------------------------------------------------
# the /blob/ routes through the node's HTTP front
# ---------------------------------------------------------------------------


class _Front:
    """Two mesh heights behind the node service and the blob-serve
    sidecar, and the host engine's in-process read core over the same
    squares."""

    def __init__(self, k: int, shard_small):
        from celestia_app_tpu.chain.app import App
        from celestia_app_tpu.chain.node import Node
        from celestia_app_tpu.das.blob_server import BlobCore, BlobService
        from celestia_app_tpu.das.server import SampleCore
        from celestia_app_tpu.service.server import NodeService

        self.k = k
        self.app = App(chain_id=f"ns-http-{k}")
        self.app.init_chain({"time_unix": 0})
        self.node_svc = NodeService(Node(self.app), port=0)
        side_core = SampleCore(self.app)
        self.host = BlobCore(SampleCore(self.app))
        self.entries, self.spaces = {}, {}
        for h in HEIGHTS:
            ods = _ods(k, 4400 + 10 * k + h)
            entry = _mesh_entry(ods, shard_small)
            assert entry.residency() == "device"
            self.entries[h] = entry
            self.spaces[h] = _namespaces(ods)
            self.node_svc.das_core.seed_cache_entry(h, entry)
            side_core.seed_cache_entry(h, entry)
            self.host.core.seed_cache_entry(
                h, edscache.compute_entry(ods, "host"))
            # the host core's first touch of a height builds its prover
            # (das.build_provers): paid here, before anything is counted
            self.host.get(h, bytes(29).hex())
        self.node_svc.serve_background()
        self.side_svc = BlobService(BlobCore(side_core), port=0)
        self.side_svc.serve_background()

    def close(self):
        self.node_svc.shutdown()
        self.side_svc.shutdown()
        self.app.close()


@pytest.fixture(scope="module", params=[8, 16], ids=["k8", "k16"])
def front(request, shard_small):
    f = _Front(request.param, shard_small)
    yield f
    f.close()


def _ask(conn, method: str, path: str, body=None):
    data = None if body is None else (
        body if isinstance(body, bytes) else json.dumps(body).encode())
    conn.request(method, path, body=data)
    resp = conn.getresponse()
    return resp.status, resp.read()


def test_blob_routes_answer_the_host_references_doc(front):
    """`POST /blob/namespaces` (one query a request, as a rollup's client
    sends it, and one of every namespace at both heights) and `GET
    /blob/get` on a mesh node: 200 and the FORMATS §21 doc of the host
    engine's in-process core byte for byte, on both transports; every
    read cut on the devices, no host prover built, nothing came down."""
    b0 = _counter('obs.span_n{name="das.build_provers"}')
    c0 = _counter("edscache.host_crossings")
    g0 = _counter("blob.ns_gathers")
    r0 = _counter("das.http_requests")
    e0 = _counter("das.http_errors")
    asked = 0
    for svc in (front.node_svc, front.side_svc):
        conn = http.client.HTTPConnection("127.0.0.1", svc.port, timeout=60)
        everything = [{"height": h, "namespace": ns.hex()}
                      for h in HEIGHTS for ns in front.spaces[h]]
        status, raw = _ask(conn, "POST", "/blob/namespaces",
                           {"queries": everything})
        assert status == 200
        assert raw == json.dumps(front.host.namespaces_many(everything)
                                 ).encode()
        asked += 1
        for h in HEIGHTS:
            for ns in front.spaces[h][::3]:
                one = [{"height": h, "namespace": ns.hex()}]
                status, raw = _ask(conn, "POST", "/blob/namespaces",
                                   {"queries": one})
                assert (status, raw) == \
                    (200, json.dumps(front.host.namespaces_many(one)).encode())
                status, raw = _ask(conn, "GET",
                                   f"/blob/get?height={h}&namespace={ns.hex()}")
                assert (status, raw) == \
                    (200, json.dumps(front.host.get(h, ns.hex())).encode())
                asked += 2
        conn.close()
    assert _counter("das.http_requests") - r0 == asked
    assert _counter("das.http_errors") == e0
    assert _counter("blob.ns_gathers") > g0
    assert _counter('obs.span_n{name="das.build_provers"}') == b0
    assert _counter("edscache.host_crossings") == c0
    assert all(e.residency() == "device" for e in front.entries.values())


def test_the_fronts_spans_close_a_namespace_read(front):
    """das.http.request = das.http.decode + blob.namespaces_many +
    das.http.encode + das.http.write to within 0.5 ms a request, route
    /blob/namespaces; under the read, the search and one row gather with
    its run."""
    conn = http.client.HTTPConnection("127.0.0.1", front.node_svc.port,
                                      timeout=60)
    since = front.app.traces.read("spans", 0, 10**9)
    since = since[-1]["_index"] + 1 if since else 0
    spaces = [ns for ns in front.spaces[HEIGHTS[0]] if ns[0] == 0][:4]
    for i in range(12):
        one = [{"height": HEIGHTS[i % 2],
                "namespace": spaces[i % len(spaces)].hex()}]
        status, _ = _ask(conn, "POST", "/blob/namespaces", {"queries": one})
        assert status == 200
    conn.close()
    rows = front.app.traces.read("spans", since, 10**9)
    by_parent: dict[str, list] = {}
    for r in rows:
        by_parent.setdefault(r["parent_id"], []).append(r)
    gaps = []
    for r in rows:
        if r["name"] != "das.http.request":
            continue
        assert r["route"] == "/blob/namespaces" and r["status"] == 200
        children = by_parent.get(r["span_id"], [])
        assert {c["name"] for c in children} == {
            "das.http.decode", "blob.namespaces_many", "das.http.encode",
            "das.http.write"}
        gaps.append(r["dur_ms"] - sum(c["dur_ms"] for c in children))
        [read] = [c for c in children if c["name"] == "blob.namespaces_many"]
        phases = {c["name"] for c in by_parent.get(read["span_id"], [])}
        assert phases == {"blob.ns.search", "blob.ns.proofs",
                          "blob.ns.encode"}
    assert len(gaps) == 12
    assert min(gaps) >= -0.01
    assert sorted(gaps)[len(gaps) // 2] <= 0.5, gaps
    assert sum(g <= 0.5 for g in gaps) >= 10, gaps
    gathers = [r for r in rows if r["name"] == "blob.ns.gather"]
    assert gathers and all(r["chips"] == CHIPS and r["rows"] >= 1
                           for r in gathers)
    runs = {r["parent_id"] for r in rows if r["name"] == "proof.ns_gather.run"}
    assert runs == {r["span_id"] for r in gathers}


@pytest.mark.parametrize("service", ["node", "sidecar"])
@pytest.mark.parametrize("method,path,body", [
    ("POST", "/blob/namespaces", b"{not json"),
    ("POST", "/blob/namespaces", b"[1, 2]"),
    ("POST", "/blob/namespaces", {"queries": []}),
    ("POST", "/blob/namespaces", {"queries": [{"namespace": "00" * 29}]}),
    ("POST", "/blob/namespaces", {"queries": [{"height": 7,
                                               "namespace": "zz"}]}),
    ("POST", "/blob/namespaces", {"queries": ["abc"]}),
    ("GET", "/blob/get?namespace=" + "00" * 29, None),
    ("GET", "/blob/get?height=7&namespace=0011", None),
    ("GET", "/blob/get?height=99&namespace=" + "00" * 29, None),
    ("GET", "/blob/nowhere", None),
])
def test_a_malformed_read_is_a_4xx_never_a_5xx(front, service, method, path,
                                               body):
    svc = front.node_svc if service == "node" else front.side_svc
    conn = http.client.HTTPConnection("127.0.0.1", svc.port, timeout=60)
    e0, s0 = _counter("das.http_errors"), _counter("das.server_errors")
    got, raw = _ask(conn, method, path, body)
    # the connection stays usable after a refusal
    ok, _ = _ask(conn, "GET", f"/blob/get?height=7&namespace={'00' * 29}")
    conn.close()
    assert 400 <= got < 500 and ok == 200
    assert "error" in json.loads(raw)
    assert _counter("das.http_errors") - e0 == 1
    assert _counter("das.server_errors") == s0


# ---------------------------------------------------------------------------
# a read's reply, encoded once: the per-share encoder's bytes and dict
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [0, 1, 2, 3, 1000])
def test_one_pass_encodes_what_base64_does_share_by_share(n):
    shares = [np.random.default_rng([n, i]).integers(
        0, 256, 512, dtype=np.uint8).tobytes() for i in range(n)]
    want = [base64.b64encode(s).decode() for s in shares]
    encoded = blob_packs.EncodedShares(shares)
    assert encoded.json == json.dumps(want).encode()
    first, second = encoded.strings(), encoded.strings()
    assert first == second == want
    assert first is not second
    assert all(a is b for a, b in zip(first, second))


def _parents_member(height: int, root: bytes, namespace: bytes, nd) -> dict:
    """A §21.1 member as the per-share encoder wrote it: one
    ``b64encode`` a share for ``shares`` and again for ``proof.data``."""
    return {
        "height": height,
        "namespace": namespace.hex(),
        "present": bool(nd.shares),
        "shares": [base64.b64encode(s).decode() for s in nd.shares],
        "proof": _share_proof_json(nd.proof) if nd.proof else None,
        "data_root": root.hex(),
    }


class _Reads:
    """Two heights of one entry kind behind an in-process `BlobCore`, and
    the host reference's `NamespaceData` of every namespace of each."""

    def __init__(self, kind: str, shard_small):
        from celestia_app_tpu.chain.app import App
        from celestia_app_tpu.das.blob_server import BlobCore
        from celestia_app_tpu.das.server import SampleCore

        engine, k = kind.split("-k")
        k = int(k)
        self.app = App(chain_id=f"ns-render-{kind}")
        self.app.init_chain({"time_unix": 0})
        self.blob = BlobCore(SampleCore(self.app))
        self.roots, self.refs = {}, {}
        for h in HEIGHTS:
            ods = _ods(k, 4600 + 10 * k + h)
            if engine == "mesh":
                entry = _mesh_entry(ods, shard_small)
            else:
                entry = edscache.compute_entry(ods, engine)
            assert entry.residency() == engine.replace("mesh", "device")
            self.blob.core.seed_cache_entry(h, entry)
            prover = edscache.compute_entry(ods, "host").get_prover()
            self.roots[h] = entry.data_root
            self.refs[h] = {ns: nsd.get_namespace_data(prover, ns)
                            for ns in _namespaces(ods)}

    def pick(self, height: int, form: str) -> bytes:
        """The first namespace of the height whose read has the form."""
        def rows(nd):
            return nd.proof.row_proof.end_row - nd.proof.row_proof.start_row

        test = {"many rows": lambda nd: nd.shares and rows(nd) > 0,
                "one row": lambda nd: nd.shares and rows(nd) == 0,
                "successor": lambda nd: not nd.shares and nd.proof,
                "uncovered": lambda nd: nd.proof is None}[form]
        return next(ns for ns, nd in self.refs[height].items() if test(nd))

    def parents_member(self, height: int, namespace: bytes) -> dict:
        return _parents_member(height, self.roots[height], namespace,
                               self.refs[height][namespace])


@pytest.fixture(scope="module")
def reads(shard_small):
    made: dict[str, _Reads] = {}

    def get(kind: str) -> _Reads:
        if kind not in made:
            made[kind] = _Reads(kind, shard_small)
        return made[kind]

    yield get
    for r in made.values():
        r.app.close()


MISSING = 99  # a height no core serves: an error member
READS = {
    "present over many rows": [(HEIGHTS[0], "many rows")],
    "present in one row": [(HEIGHTS[1], "one row")],
    "absent, a successor leaf": [(HEIGHTS[0], "successor")],
    "absent, no covering row": [(HEIGHTS[1], "uncovered")],
    "an error member": [(HEIGHTS[0], "many rows"), (MISSING, "many rows"),
                        (HEIGHTS[0], "successor")],
    "mixed heights": [(HEIGHTS[0], "one row"), (HEIGHTS[1], "many rows"),
                      (HEIGHTS[1], "successor"), (HEIGHTS[0], "many rows"),
                      (HEIGHTS[0], "one row")],
}


@pytest.mark.parametrize("case", list(READS))
@pytest.mark.parametrize("kind", ["host-k8", "host-k64", "mesh-k8"])
def test_a_reply_is_the_per_share_encoders_doc(reads, kind, case):
    """`POST /blob/namespaces` and `GET /blob/get` as the route renders
    them equal ``json.dumps`` of the per-share encoder's doc byte for byte,
    and the in-process dict equals that doc, its two share lists separate
    list objects of the same strings — on host-prover entries and on a
    copy-less mesh entry."""
    r = reads(kind)
    picked = [(h, r.pick(HEIGHTS[0] if h == MISSING else h, form))
              for h, form in READS[case]]
    queries = [{"height": h, "namespace": ns.hex()} for h, ns in picked]
    with pytest.raises(SampleError) as missing:
        r.blob.get(MISSING, bytes(29).hex())
    want = {"queries": [
        {"height": h, "namespace": ns.hex(), "error": str(missing.value)}
        if h == MISSING else r.parents_member(h, ns) for h, ns in picked]}
    c0 = _counter("blob.rendered_replies")
    assert r.blob.namespaces_reply(queries).render() == \
        json.dumps(want).encode()
    assert _counter("blob.rendered_replies") - c0 == 1
    got = r.blob.namespaces_many(queries)
    assert got == want
    for member in got["queries"]:
        if member.get("proof") and member["shares"]:
            assert member["shares"] is not member["proof"]["data"]
            assert all(a is b for a, b in zip(member["shares"],
                                              member["proof"]["data"]))
    if MISSING in dict(picked):
        return
    for (h, ns), member in zip(picked, want["queries"]):
        assert r.blob.get_reply(h, ns.hex()).render() == \
            json.dumps(member).encode()
        assert r.blob.get(h, ns.hex()) == member


@pytest.mark.parametrize("k", [8, 64])
def test_pack_chunks_are_the_per_share_encoders_bytes(k):
    """The pack builder's chunks, from the same encoding, are the chunks
    of the per-share encoder's docs."""
    ods = _ods(k, 4700 + k)
    entry = edscache.compute_entry(ods, "host")
    prover = entry.get_prover()
    _manifest, chunks = blob_packs.build_blob_pack(entry, 5,
                                                   chunk_namespaces=2)
    docs = []
    for ns in blob_packs.blob_namespaces(entry):
        member = _parents_member(0, entry.data_root, ns,
                                 nsd.get_namespace_data(prover, ns))
        del member["height"]
        docs.append(member)
    assert chunks == [blob_packs.encode_chunk(docs[i:i + 2])
                      for i in range(0, len(docs), 2)]
