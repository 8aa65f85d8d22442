"""The node's HTTP DAS front over a copy-less mesh entry (ISSUE 40).

A mesh-engine height (rows split over the 8 virtual CPU devices, no host
copy) behind `service/server.NodeService` and the `das/server.
SampleService` sidecar, sampled by keep-alive clients on their own
threads, as a light-node fleet samples a full node. The contract under
test: every reply is the host engine's in-process reply byte for byte and
verifies against the header; every cell is cut on the devices
(`das.samples_gathered`), no host prover is built and the square never
comes down; both transports give the same bodies; `gather_cells` from
many threads gives the serial bytes; the front's spans (`das.http.*`)
close their request; a malformed request is one `das.http_errors` and a
4xx, never a 5xx. A namespace read's reply (`/blob/`) is rendered inside
the one `das.http.encode` and counted `blob.rendered_replies`; the
`/das/` replies and every error body stay `json.dumps` of their dict.
"""

import base64
import http.client
import json
import threading

import numpy as np
import pytest

from celestia_app_tpu import obs
from celestia_app_tpu.da import edscache, sampling
from celestia_app_tpu.da.dah import DataAvailabilityHeader
from celestia_app_tpu.das.server import SampleError
from celestia_app_tpu.utils import nmt_host, telemetry

HEIGHTS = (7, 8)
CLIENTS, REQUESTS, CELLS = 32, 20, 16


def _counter(name: str) -> int:
    return telemetry.snapshot().get("counters", {}).get(name, 0)


def _random_ods(k: int, seed: int) -> np.ndarray:
    ods = np.random.default_rng(seed).integers(
        0, 256, size=(k, k, 512), dtype=np.uint8)
    ods[:, :, 0] = 0
    ods[:, :, 1:19] = 0
    return ods


class _Front:
    """Two served mesh heights behind the node service and the sidecar,
    and the host engine's in-process core over the same squares."""

    def __init__(self, k: int, shard_small):
        from celestia_app_tpu.chain.app import App
        from celestia_app_tpu.chain.node import Node
        from celestia_app_tpu.das.server import SampleCore, SampleService
        from celestia_app_tpu.service.server import NodeService

        self.k = k
        self.app = App(chain_id=f"das-http-{k}")
        self.app.init_chain({"time_unix": 0})
        self.node_svc = NodeService(Node(self.app), port=0)
        self.side_core = SampleCore(self.app)
        self.host_core = SampleCore(self.app)
        self.entries = {}
        for h in HEIGHTS:
            ods = _random_ods(k, 4000 + 10 * k + h)
            with shard_small():
                entry = edscache.compute_entry(ods, "device")
            assert entry.chips == 8 and entry.residency() == "device"
            p0 = _counter("mesh.sharded_level_passes")
            entry.warm()
            assert _counter("mesh.sharded_level_passes") - p0 == 2
            self.entries[h] = entry
            self.node_svc.das_core.seed_cache_entry(h, entry)
            self.side_core.seed_cache_entry(h, entry)
            self.host_core.seed_cache_entry(
                h, edscache.compute_entry(ods, "host"))
            # the host core's first touch of a height builds its prover
            # (das.build_provers): paid here, before anything is counted
            self.host_core.sample(h, 0, 0)
        self.node_svc.serve_background()
        self.side_svc = SampleService(self.side_core, port=0)
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


def _ask(conn, method: str, path: str, body=None, headers=None):
    """(status, raw body) over a kept-alive connection."""
    data = None if body is None else (
        body if isinstance(body, bytes) else json.dumps(body).encode())
    conn.request(method, path, body=data, headers=headers or {})
    resp = conn.getresponse()
    return resp.status, resp.read()


def _cells(rng, k: int, n: int = CELLS):
    return [[int(r), int(c)] for r, c in rng.integers(0, 2 * k, size=(n, 2))]


def _verified(header: dict, reply: dict) -> int:
    """Samples of a reply whose NMT proof verifies against the header."""
    dah = DataAvailabilityHeader(
        tuple(bytes.fromhex(r) for r in header["row_roots"]),
        tuple(bytes.fromhex(c) for c in header["col_roots"]))
    ok = 0
    for doc in reply["samples"]:
        proof = nmt_host.NmtRangeProof(
            start=doc["proof"]["start"], end=doc["proof"]["end"],
            total=doc["proof"]["total"],
            nodes=[base64.b64decode(n) for n in doc["proof"]["nodes"]])
        ok += sampling.verify_sample(dah, doc["row"], doc["col"],
                                     base64.b64decode(doc["share"]), proof)
    return ok


def test_a_fleet_over_http_is_answered_on_the_devices(front):
    """32 keep-alive clients x 20 requests of 16 cells, at both heights:
    every body is the host engine's in-process reply byte for byte and
    every proof verifies against the header the client fetched; every
    cell was cut on the devices, no host prover was built and nothing
    of the square came down."""
    port = front.node_svc.port
    headers = {}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    for h in HEIGHTS:
        status, raw = _ask(conn, "GET", f"/das/header?height={h}")
        assert status == 200
        headers[h] = json.loads(raw)
        assert raw == json.dumps(front.host_core.header(h)).encode()
    conn.close()
    g0 = _counter("das.samples_gathered")
    d0 = _counter("das.gather_dispatches")
    j0 = _counter("das.gather_joined")
    b0 = _counter('obs.span_n{name="das.build_provers"}')
    x0 = _counter('xfer.d2h_bytes{site="edscache.eds"}')
    c0 = _counter("edscache.host_crossings")
    r0 = _counter("das.http_requests")
    e0 = _counter("das.http_errors")
    results = [None] * CLIENTS

    def client(ci: int):
        rng = np.random.default_rng([ci, front.k])
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        got = []
        for i in range(REQUESTS):
            h = HEIGHTS[i % len(HEIGHTS)]
            cells = _cells(rng, front.k)
            status, raw = _ask(conn, "POST", "/das/samples",
                               {"height": h, "cells": cells})
            got.append((h, cells, status, raw))
        conn.close()
        results[ci] = got

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    asked = 0
    for got in results:
        assert len(got) == REQUESTS
        for h, cells, status, raw in got:
            assert status == 200
            want = front.host_core.sample_many(h, [tuple(c) for c in cells])
            assert raw == json.dumps(want).encode()
            assert _verified(headers[h], json.loads(raw)) == CELLS
            asked += len(cells)
    assert _counter("das.samples_gathered") - g0 == asked
    # requests that waited together went out in one dispatch: each
    # dispatch carries its dispatcher's own request, and every other
    # request in it joined
    dispatches = _counter("das.gather_dispatches") - d0
    assert 1 <= dispatches <= CLIENTS * REQUESTS
    assert _counter("das.gather_joined") - j0 + dispatches == \
        CLIENTS * REQUESTS
    assert _counter('obs.span_n{name="das.build_provers"}') == b0
    assert _counter('xfer.d2h_bytes{site="edscache.eds"}') == x0
    assert _counter("edscache.host_crossings") == c0
    assert _counter("das.http_requests") - r0 == CLIENTS * REQUESTS
    assert _counter("das.http_errors") == e0
    assert all(e.residency() == "device" for e in front.entries.values())


def test_node_service_and_sidecar_give_the_same_bytes(front):
    """One helper answers both transports: headers, batches (with error
    docs in their places), the one-cell GET, and refusals."""
    k = front.k
    rng = np.random.default_rng(k)
    w = 2 * k
    asks = [
        ("GET", "/das/header?height=7", None),
        ("GET", "/das/head", None),
        ("GET", f"/das/sample?height=8&row={w - 1}&col=0", None),
        ("POST", "/das/samples", {"height": 7, "cells": _cells(rng, k)}),
        ("POST", "/das/samples",
         {"height": 8, "cells": [[0, w - 1], [w, 0], [-1, 2], [1, 1]]}),
        ("POST", "/das/samples", {"height": 8, "cells": _cells(rng, k, 17),
                                  "axis": "col"}),
        ("GET", "/das/header?height=99", None),
        ("POST", "/das/samples", b"{not json"),
        ("GET", "/das/nowhere", None),
    ]
    conns = [http.client.HTTPConnection("127.0.0.1", svc.port, timeout=60)
             for svc in (front.node_svc, front.side_svc)]
    statuses = []
    for method, path, body in asks:
        node, side = (_ask(c, method, path, body) for c in conns)
        assert node == side, (method, path)
        statuses.append(node[0])
    for c in conns:
        c.close()
    assert statuses == [200, 200, 200, 200, 200, 200, 400, 400, 400]


def test_gather_cells_from_many_threads_equals_serial(front):
    """32 threads cutting batches out of ONE entry at once get exactly
    the bytes the same batches give one at a time."""
    entry = front.entries[HEIGHTS[0]]
    rng = np.random.default_rng([front.k, 5])
    batches = [[tuple(c) for c in _cells(rng, front.k)]
               for _ in range(CLIENTS)]
    together = [None] * CLIENTS
    start = threading.Barrier(CLIENTS)

    def cut(i: int):
        start.wait()
        together[i] = entry.gather_cells(batches[i])

    threads = [threading.Thread(target=cut, args=(i,))
               for i in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for batch, got in zip(batches, together):
        serial = entry.gather_cells(batch)
        assert [(s, p.start, p.end, p.total, p.nodes) for s, p in got] == \
            [(s, p.start, p.end, p.total, p.nodes) for s, p in serial]


@pytest.mark.parametrize("route", ["header", "samples"])
def test_the_fronts_spans_close_the_request(front, route):
    """das.http.request = das.http.decode + the route's span +
    das.http.encode + das.http.write to within 0.5 ms a request (the
    rows of requests that carry their height's trace, one at a time)."""
    conn = http.client.HTTPConnection("127.0.0.1", front.node_svc.port,
                                      timeout=60)
    rng = np.random.default_rng(17)
    since = front.app.traces.read("spans", 0, 10**9)
    since = since[-1]["_index"] + 1 if since else 0
    for i in range(12):
        h = HEIGHTS[i % 2]
        trace = {obs.TRACE_HEADER:
                 f"{obs.trace_id_for(front.app.chain_id, h)}:{i:016x}"}
        if route == "header":
            status, _ = _ask(conn, "GET", f"/das/header?height={h}",
                             headers=trace)
        else:
            status, _ = _ask(conn, "POST", "/das/samples",
                             {"height": h, "cells": _cells(rng, front.k)},
                             headers=trace)
        assert status == 200
    conn.close()
    rows = front.app.traces.read("spans", since, 10**9)
    by_parent: dict[str, list] = {}
    for r in rows:
        by_parent.setdefault(r["parent_id"], []).append(r)
    route_span = {"header": "das.header", "samples": "das.serve_sample"}
    want = {"das.http.encode", "das.http.write", route_span[route]}
    if route == "samples":
        want.add("das.http.decode")
    gaps = []
    for r in rows:
        if r["name"] != "das.http.request":
            continue
        children = by_parent.get(r["span_id"], [])
        assert {c["name"] for c in children} == want
        assert r["status"] == 200 and r["bytes_out"] > 0
        gaps.append(r["dur_ms"] - sum(c["dur_ms"] for c in children))
    assert len(gaps) == 12
    assert min(gaps) >= -0.01
    assert sorted(gaps)[len(gaps) // 2] <= 0.5, gaps
    assert sum(g <= 0.5 for g in gaps) >= 10, gaps


@pytest.mark.parametrize("service", ["node", "sidecar"])
@pytest.mark.parametrize("method,path,body,status", [
    ("POST", "/das/samples", b"{not json", 400),
    ("POST", "/das/samples", b"[1, 2]", 400),
    ("POST", "/das/samples", {"height": 7}, 400),
    ("POST", "/das/samples", {"height": 7, "cells": [[1, 2, 3]]}, 400),
    ("GET", "/das/sample?height=7&row=x&col=1", None, 400),
    ("GET", "/das/header?height=99", None, 400),
])
def test_a_malformed_request_is_one_error_and_never_a_5xx(
        front, service, method, path, body, status):
    svc = front.node_svc if service == "node" else front.side_svc
    conn = http.client.HTTPConnection("127.0.0.1", svc.port, timeout=60)
    e0, r0 = _counter("das.http_errors"), _counter("das.http_requests")
    s0 = _counter("das.server_errors")
    got, raw = _ask(conn, method, path, body)
    # the connection stays usable after a refusal
    ok, _ = _ask(conn, "GET", "/das/head")
    conn.close()
    assert (got, ok) == (status, 200)
    assert "error" in json.loads(raw)
    assert _counter("das.http_errors") - e0 == 1
    assert _counter("das.http_requests") - r0 == 2
    assert _counter("das.server_errors") == s0


class _BlobFront:
    """One height of a laid-out square (namespaces in order, as the
    read plane needs) as a copy-less mesh entry behind the node service."""

    HEIGHT = 5

    def __init__(self, shard_small):
        from celestia_app_tpu.chain.app import App
        from celestia_app_tpu.chain.node import Node
        from celestia_app_tpu.da import dah as dah_mod
        from celestia_app_tpu.da import square as square_mod
        from celestia_app_tpu.da.blob import Blob
        from celestia_app_tpu.da.namespace import Namespace
        from celestia_app_tpu.da.square import PfbEntry
        from celestia_app_tpu.service.server import NodeService

        rng = np.random.default_rng(4500)
        blobs = [Blob(Namespace.v0(bytes([0x20 + i]) * 5),
                      rng.integers(0, 256, n, dtype=np.uint8).tobytes())
                 for i, n in enumerate([478 * 9, 478 * 8, 900])]
        sq = square_mod.build([b"a-tx"], [PfbEntry(b"a-pfb", tuple(blobs))],
                              8, 64)
        self.app = App(chain_id="blob-http")
        self.app.init_chain({"time_unix": 0})
        self.svc = NodeService(Node(self.app), port=0)
        with shard_small():
            entry = edscache.compute_entry(
                dah_mod.shares_to_ods(sq.share_bytes()), "device")
        assert entry.residency() == "device" and entry.chips == 8
        p0 = _counter("mesh.sharded_level_passes")
        entry.warm()
        assert _counter("mesh.sharded_level_passes") - p0 == 2
        self.svc.das_core.seed_cache_entry(self.HEIGHT, entry)
        self.namespaces = [b.namespace.raw.hex() for b in blobs]
        self.svc.serve_background()

    def close(self):
        self.svc.shutdown()
        self.app.close()


@pytest.fixture(scope="module")
def blob_front(shard_small):
    f = _BlobFront(shard_small)
    yield f
    f.close()


def test_a_namespace_reply_is_rendered_inside_the_one_encode(blob_front):
    """Every `/blob/` reply: one `das.http.encode` under its request, one
    `blob.rendered_replies`, and the in-process core's doc to the byte;
    a `POST /blob/namespaces`'s children close it to within 0.5 ms (`GET
    /blob/get` opens no span of its route); `/das/` replies and error
    bodies move no such counter and are ``json.dumps`` of their dict."""
    h = blob_front.HEIGHT
    app, blob = blob_front.app, blob_front.svc.blob_core
    conn = http.client.HTTPConnection("127.0.0.1", blob_front.svc.port,
                                      timeout=60)
    since = app.traces.read("spans", 0, 10**9)
    since = since[-1]["_index"] + 1 if since else 0
    c0 = _counter("blob.rendered_replies")
    for i in range(12):
        ns = blob_front.namespaces[i % len(blob_front.namespaces)]
        if i % 2:
            status, raw = _ask(conn, "GET",
                               f"/blob/get?height={h}&namespace={ns}")
            want = blob.get(h, ns)
        else:
            one = [{"height": h, "namespace": ns}]
            status, raw = _ask(conn, "POST", "/blob/namespaces",
                               {"queries": one})
            want = blob.namespaces_many(one)
        assert (status, raw) == (200, json.dumps(want).encode())
    assert _counter("blob.rendered_replies") - c0 == 12
    rows = app.traces.read("spans", since, 10**9)
    by_parent: dict[str, list] = {}
    for r in rows:
        by_parent.setdefault(r["parent_id"], []).append(r)
    gaps, encodes = [], 0
    for r in rows:
        if r["name"] != "das.http.request":
            continue
        children = by_parent.get(r["span_id"], [])
        assert [c["name"] for c in children].count("das.http.encode") == 1
        encodes += 1
        if r["route"] == "/blob/namespaces":
            gaps.append(r["dur_ms"] - sum(c["dur_ms"] for c in children))
    assert encodes == 12 and len(gaps) == 6
    assert min(gaps) >= -0.01
    assert sorted(gaps)[len(gaps) // 2] <= 0.5, gaps
    assert sum(g <= 0.5 for g in gaps) >= 5, gaps
    with pytest.raises(SampleError) as missing:
        blob.get(99, blob_front.namespaces[0])
    status, raw = _ask(conn, "GET", f"/blob/get?height=99&namespace="
                                    f"{blob_front.namespaces[0]}")
    assert (status, raw) == (400, json.dumps(
        {"error": str(missing.value)}).encode())
    status, raw = _ask(conn, "GET", f"/das/header?height={h}")
    conn.close()
    assert (status, raw) == (200, json.dumps(
        blob_front.svc.das_core.header(h)).encode())
    assert _counter("blob.rendered_replies") - c0 == 12
