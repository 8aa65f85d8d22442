"""Blob read plane: batched namespace-query serving for rollup readers.

The serving half of the reference's ``pkg/proof`` + x/blob query surface
at the north star's scale: most users are rollup nodes reading their
namespace's blobs with inclusion (or absence) proofs, so the read path
must resolve MANY (namespace, height) queries per round-trip off the
resident NMT level stacks (da/namespace_device.py), never by a per-query
square scan.

Routes (mounted on the node HTTP service, the validator server, and the
standalone blob-serve sidecar; wire format in docs/FORMATS.md §21):

  GET  /blob/get?height=H&namespace=HEX    one namespace's shares +
                                           presence/absence proof
  POST /blob/namespaces {queries: [{height, namespace}...]}
                                           batched multi-query variant:
                                           entries resolved in ONE pass,
                                           search dispatched per height
                                           batch, response keeps request
                                           order, each member
                                           byte-identical to /blob/get
  GET  /blob/pack?height=H                 blob-pack manifest (§21.2)
  GET  /blob/pack/chunk?height=H&index=I   raw pack chunk bytes — static
                                           serving, no lock, no assembly

Absence is a first-class answer, not a 404: an empty-namespace query
returns {"present": false} with the absence witness
(da/namespace_data.verify_namespace_data semantics — a successor-leaf
proof for a straddling row, or no proof when every row window excludes
the target), so a follower can prove its namespace had NO blobs at a
height. Telemetry: ``blob.namespace_queries`` / ``blob.namespace_batches``
/ ``blob.absence_proofs`` / ``blob.pack_hits`` / ``blob.pack_misses``
plus the ``blob.batch_size`` histogram — surfaced on /metrics and both
status surfaces via ``status_block``.

Entries come from the DAS serving plane's SampleCore (single-flight
builds, commit-warmer seeding), so the read plane shares the sample
plane's cache discipline instead of duplicating it.
"""

from __future__ import annotations

import threading

from celestia_app_tpu.da import codec as codec_mod
from celestia_app_tpu.das import blob_packs as blob_packs_mod
from celestia_app_tpu.das.server import SampleCore, SampleError
from celestia_app_tpu.utils import telemetry


class BlobError(SampleError):
    """Client-side problem on the /blob/* surface; messages containing
    "not served" map to 404 in the HTTP services (the SampleError
    convention, so every mounting transport reuses one handler)."""


class BlobCore:
    """Namespace-read serving over the DAS plane's entry cache.

    Thread-safe: handler threads call `get`/`namespaces_many`
    concurrently; entry resolution single-flights through the shared
    SampleCore and the batched search runs on immutable level arrays."""

    def __init__(self, core: SampleCore,
                 pack_store: "blob_packs_mod.BlobPackStore | None" = None):
        self.core = core
        self.app = core.app
        # the static blob-pack store (das/blob_packs.py): built at warm
        # time by the app's ProverWarmer, served here as raw bytes.
        self.pack_store = (pack_store if pack_store is not None
                           else getattr(core.app, "blob_pack_store", None))

    # -- entries ---------------------------------------------------------

    def _entry(self, height: int):
        entry = self.core._entry(height)
        if entry.scheme != codec_mod.RS2D_NAME:
            raise BlobError(
                f"namespace reads need the {codec_mod.RS2D_NAME} scheme; "
                f"height {height} is {entry.scheme}"
            )
        return entry

    @staticmethod
    def _parse_namespace(value) -> bytes:
        from celestia_app_tpu.da import namespace_device as nsdev

        if not isinstance(value, str):
            raise BlobError("namespace must be a hex string")
        try:
            return nsdev.parse_namespace(value)
        except ValueError as e:
            raise BlobError(str(e)) from None

    @staticmethod
    def _doc(height: int, entry, namespace: bytes, nd=None) -> dict:
        """One query's response member — the shared builder
        (das/blob_packs.live_namespace_doc), so the single-query
        response, every batch member, and the pack bytes all agree by
        construction."""
        doc = blob_packs_mod.live_namespace_doc(
            entry.cache_entry, namespace, prover=entry.prover, nd=nd)
        if not doc["present"]:
            telemetry.incr("blob.absence_proofs")
        return {"height": height, **doc}

    # -- serving ---------------------------------------------------------

    def get(self, height: int, namespace_hex: str) -> dict:
        """GET /blob/get: one namespace at one height, resolved with the
        host reference's per-query scan
        (da/namespace_data.get_namespace_data) — the per-request loop
        the batched route replaces (the two are not measured against
        each other on the chip's host)."""
        namespace = self._parse_namespace(namespace_hex)
        entry = self._entry(height)
        telemetry.incr("blob.namespace_queries")
        telemetry.observe("blob.batch_size", 1.0)
        return self._doc(height, entry, namespace)

    def namespaces_many(self, queries) -> dict:
        """POST /blob/namespaces: resolve every query's height against
        the serving cache in ONE pass, then dispatch each height's
        namespaces as one batched search (da/namespace_device.py) —
        response keeps REQUEST order, each member byte-identical to the
        single-query response. A height that cannot be resolved yields
        {"height", "namespace", "error"} so the rest still serves."""
        from celestia_app_tpu import obs

        if not isinstance(queries, list) or not queries:
            raise BlobError("namespaces needs a non-empty 'queries' list")
        with obs.span("blob.namespaces_many",
                      traces=getattr(self.app, "traces", None),
                      n_queries=len(queries)):
            return self._namespaces_many(queries)

    def _namespaces_many(self, queries: list) -> dict:
        from celestia_app_tpu import obs
        from celestia_app_tpu.da import namespace_device as nsdev

        parsed: list[tuple[int, bytes]] = []
        for q in queries:
            try:
                height = int(q["height"])
            except (KeyError, TypeError, ValueError):
                raise BlobError(
                    "each query needs an integer 'height'") from None
            parsed.append((height, self._parse_namespace(
                q.get("namespace"))))
        telemetry.incr("blob.namespace_queries", len(parsed))
        telemetry.incr("blob.namespace_batches")
        telemetry.observe("blob.batch_size", float(len(parsed)))
        # resolve every entry first (single-flight per height) ...
        resolved: dict[int, object] = {}
        for height, _ns in parsed:
            if height in resolved:
                continue
            try:
                resolved[height] = self._entry(height)
            except SampleError as e:
                resolved[height] = e
        # ... then ONE batched search per resolved height. Three phases,
        # one span each whatever the number of heights: the search (pad,
        # dispatch, wait, read back), the proofs (walked out of the
        # level arrays), the encoding (base64 / hex / dicts)
        engine = self.core._engine()
        batches: dict[int, tuple] = {}  # height -> (prover, namespaces)
        for height, entry in resolved.items():
            if isinstance(entry, SampleError):
                continue
            batches[height] = (entry.prover, list(dict.fromkeys(
                ns for h, ns in parsed if h == height)))
        with obs.span("blob.ns.search", heights=len(batches)):
            found = {height: nsdev.search_batched(prover, batch, engine)
                     for height, (prover, batch) in batches.items()}
        with obs.span("blob.ns.proofs"):
            nds = {
                height: dict(zip(batch, nsdev.assemble_batched(
                    prover, batch, found[height])))
                for height, (prover, batch) in batches.items()}
        out = []
        with obs.span("blob.ns.encode"):
            for height, ns in parsed:
                entry = resolved[height]
                if isinstance(entry, SampleError):
                    out.append({"height": height, "namespace": ns.hex(),
                                "error": str(entry)})
                    continue
                out.append(self._doc(height, entry, ns,
                                     nd=nds[height][ns]))
        return {"queries": out}

    # -- blob packs (static serving; das/blob_packs.py) ------------------

    def _pack_root(self, height: int) -> bytes:
        """The height's data root WITHOUT building a square: cached
        serving entries first, then the durable block store — pack
        routes must never trigger an extend (the SampleCore._pack_root
        rule, counted on the blob plane's own miss counter)."""
        with self.core._lock:
            hit = self.core._cache.get(height)
        if hit is not None:
            return hit.root
        db = getattr(self.app, "db", None)
        if db is not None:
            try:
                return db.load_block(height).header.data_hash
            except (OSError, KeyError, ValueError):
                pass
        telemetry.incr("blob.pack_misses")
        raise BlobError(f"blob pack for height {height} not served")

    def pack_manifest(self, height: int) -> dict:
        """GET /blob/pack: the height's blob-pack manifest, or a
        404-mapped refusal (counted blob.pack_misses — the reader falls
        back to the live query)."""
        if self.pack_store is None:
            telemetry.incr("blob.pack_misses")
            raise BlobError(f"blob pack for height {height} not served")
        m = self.pack_store.manifest(self._pack_root(height))
        if m is None:
            telemetry.incr("blob.pack_misses")
            raise BlobError(f"blob pack for height {height} not served")
        return m

    def pack_chunk(self, height: int, index: int) -> bytes:
        """GET /blob/pack/chunk: raw chunk bytes straight from disk —
        no lock, no assembly, no JSON; the CDN-shaped hot path. Counted
        blob.pack_hits (misses blob.pack_misses)."""
        if self.pack_store is None:
            telemetry.incr("blob.pack_misses")
            raise BlobError(f"blob pack for height {height} not served")
        try:
            data = self.pack_store.chunk(self._pack_root(height), index)
        except blob_packs_mod.PackError as e:
            telemetry.incr("blob.pack_misses")
            raise BlobError(str(e)) from None
        telemetry.incr("blob.pack_hits")
        return data


def status_block() -> dict:
    """The read plane's status-surface block (mounted under "blob" on
    /status and /consensus/status — the admission.status_block
    pattern)."""
    counters = telemetry.snapshot()["counters"]

    def g(name: str) -> int:
        return int(counters.get(name, 0))

    return {
        "namespace_queries": g("blob.namespace_queries"),
        "namespace_batches": g("blob.namespace_batches"),
        "absence_proofs": g("blob.absence_proofs"),
        "pack_hits": g("blob.pack_hits"),
        "pack_misses": g("blob.pack_misses"),
        "device_batches": g("blob.device_batches"),
        "device_fallbacks": g("blob.device_fallbacks"),
        "packs_built": g("blobpacks.built"),
        "pack_build_errors": g("blobpacks.build_errors"),
    }


# -- one router shared by every transport -----------------------------------


def route_blob(core: BlobCore, method: str, path: str,
               query: dict, payload: dict | None = None):
    """Dispatch a /blob/* request. `query` holds the GET params
    (strings); POST bodies arrive in `payload`. Raises BlobError (a
    SampleError) for every malformed input, so transports reuse their
    /das/* handler: "not served" maps to 404, the rest to 400. Returns
    a JSON-able dict — or raw ``bytes`` for /blob/pack/chunk."""

    def _int(src: dict, key: str) -> int:
        try:
            v = src[key]
            return int(v[0] if isinstance(v, list) else v)
        except (KeyError, IndexError, TypeError, ValueError):
            raise BlobError(f"missing/invalid integer field {key!r}") \
                from None

    def _str(src: dict, key: str) -> str:
        v = src.get(key, "")
        return v[0] if isinstance(v, list) else v

    if method == "GET":
        if path == "/blob/get":
            return core.get(_int(query, "height"),
                            _str(query, "namespace"))
        if path == "/blob/pack":
            return core.pack_manifest(_int(query, "height"))
        if path == "/blob/pack/chunk":
            return core.pack_chunk(_int(query, "height"),
                                   _int(query, "index"))
    elif method == "POST" and path == "/blob/namespaces":
        payload = payload or {}
        return core.namespaces_many(payload.get("queries"))
    raise BlobError(f"no blob route {method} {path}")


class BlobService:
    """Standalone HTTP server for the read plane — the blob-serve
    sidecar: point it at a full node's home and it answers rollup
    readers (blob routes AND the /das/* routes a follower needs for
    headers) with no chain process attached."""

    def __init__(self, core: BlobCore, host: str = "127.0.0.1",
                 port: int = 26661):
        import json
        from http.server import (
            BaseHTTPRequestHandler,
            ThreadingHTTPServer,
        )
        from urllib.parse import parse_qs, urlparse

        from celestia_app_tpu.das.server import route_das

        service = self
        self.core = core

        class Handler(BaseHTTPRequestHandler):
            # keep-alive (HTTP/1.1): readers hold persistent
            # connections; every response sets Content-Length
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _send(self, code: int, obj) -> None:
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _send_raw(self, code: int, body: bytes) -> None:
                # pack chunks serve raw bytes (octet-stream, NOT base64)
                self.send_response(code)
                self.send_header("Content-Type",
                                 "application/octet-stream")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _route(self, method: str, payload: dict | None) -> None:
                parsed = urlparse(self.path)
                try:
                    if parsed.path.startswith("/blob/"):
                        out = route_blob(service.core, method,
                                         parsed.path,
                                         parse_qs(parsed.query), payload)
                    else:
                        out = route_das(service.core.core, method,
                                        parsed.path,
                                        parse_qs(parsed.query), payload)
                    if isinstance(out, bytes):
                        self._send_raw(200, out)
                    else:
                        self._send(200, out)
                except SampleError as e:
                    self._send(404 if "not served" in str(e) else 400,
                               {"error": str(e)})
                except Exception as e:  # never kill the serving thread
                    telemetry.incr("blob.server_errors")
                    self._send(500, {"error": f"{type(e).__name__}: {e}"})

            def do_GET(self):
                self._route("GET", None)

            def do_POST(self):
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                except ValueError:
                    self._send(400, {"error": "body must be JSON"})
                    return
                self._route("POST", payload)

        class Server(ThreadingHTTPServer):
            # reader fleets connect in bursts; the stdlib default
            # listen backlog of 5 resets most of a burst on arrival
            request_queue_size = 1024

        self._httpd = Server((host, port), Handler)
        self.port = self._httpd.server_address[1]

    def serve_background(self):
        t = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        t.start()
        return self

    def serve_forever(self):
        self._httpd.serve_forever()

    def shutdown(self):
        self._httpd.shutdown()
