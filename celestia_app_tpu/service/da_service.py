"""Stateless DA-core service: the boundary a FOREIGN node calls.

This is the SURVEY §7.1.7 shim surface — the framework's stated reason
to exist as a drop-in accelerator. A Go node (or any language) keeps its
own square builder and consensus, swaps the body of `da.ExtendShares` +
`NewDataAvailabilityHeader` (reference
``pkg/da/data_availability_header.go:44-75``, called from
``app/extend_block.go:14-26``) for one RPC here, and uses the returned
DAH verbatim:

  ExtendAndCommit  ODS shares in -> row roots + column roots + data root
                   (the erasure extension and every NMT/Merkle hash run
                   on this side — on TPU when a device engine backs the
                   service, host SIMD otherwise).
  ProveShares      share range in -> ShareProof against the data root
                   (``pkg/proof`` ProveShares analog), served from the
                   bounded cache of recently extended squares (keyed by
                   data root) or from a caller-supplied ODS.

Callers: the node HTTP service mounts these under ``/da/*``
(service/server.py), the standalone ``da-serve`` CLI serves them with no
chain attached (the sidecar deployment shape), the gRPC plane exposes
them as ``celestia_tpu.da.v1.DAService`` (proto/celestia_tpu/da/v1/
da.proto), ``shim/go`` holds the Go-side drop-in source, and
``native/da_client.cc`` drives the HTTP route end-to-end from C++ with
an independent local recompute (byte-identity check).
"""

from __future__ import annotations

import base64
import collections
import threading

import numpy as np

from celestia_app_tpu import appconsts
from celestia_app_tpu.utils import telemetry


class DAError(ValueError):
    pass


class DACore:
    """Engine-gated extend/commit/prove with a bounded square cache.

    engine="host": pure NumPy/SIMD path — safe in any process (never
    imports-and-dispatches jax, so a host-engine validator never
    initialises an accelerator backend it does not own). engine="device": one jitted dispatch per square
    (da/dah.new_dah_from_ods). Proof construction is host-side either
    way (tree traversal, not FLOPs)."""

    def __init__(self, engine: str = "host", cache_squares: int = 4):
        if engine not in ("host", "device"):
            raise DAError(f"unknown engine {engine!r}")
        self.engine = engine
        self._cache: collections.OrderedDict[str, tuple] = \
            collections.OrderedDict()
        self._cache_squares = cache_squares
        self._lock = threading.Lock()

    # -- core ------------------------------------------------------------

    def _pipeline(self, ods: np.ndarray):
        """(eds_obj, dah, data_root) for an ODS array."""
        from celestia_app_tpu.da import dah as dah_mod

        if self.engine == "device":
            dah, eds, root = dah_mod.new_dah_from_ods(ods)
            return eds, dah, root
        from celestia_app_tpu.utils import refimpl

        eds_np, rows, cols, root = refimpl.pipeline_host(ods)
        dah = dah_mod.DataAvailabilityHeader(
            row_roots=tuple(rows), col_roots=tuple(cols)
        )
        return dah_mod.ExtendedDataSquare(eds_np), dah, root

    def _decode_ods(self, payload: dict) -> np.ndarray:
        from celestia_app_tpu.da import dah as dah_mod

        raw = payload["ods"]
        if isinstance(raw, str):  # JSON transport; gRPC hands raw bytes
            raw = base64.b64decode(raw)
        if len(raw) % appconsts.SHARE_SIZE:
            raise DAError(
                f"ods byte length {len(raw)} is not a multiple of the "
                f"{appconsts.SHARE_SIZE}-byte share size"
            )
        n = len(raw) // appconsts.SHARE_SIZE
        k = int(n ** 0.5)
        if k * k != n or k & (k - 1) or not n:
            raise DAError(
                f"share count {n} is not a power-of-two perfect square"
            )
        # protocol cap is 128 (appconsts.square_size_upper_bound); allow
        # 2x headroom for benchmark-scale squares on device engines
        cap = 2 * appconsts.square_size_upper_bound(
            appconsts.LATEST_VERSION)
        if k > cap:
            raise DAError(f"square size {k} exceeds the service cap {cap}")
        if self.engine == "host" and k > 128:
            raise DAError(
                "host engine covers the GF(2^8) range (k <= 128); run the "
                "service with a device engine for larger squares"
            )
        want = payload.get("square_size")
        if want is not None and int(want) != k:
            raise DAError(
                f"square_size {want} does not match the {k}x{k} ods"
            )
        return dah_mod.shares_to_ods(
            [raw[i * appconsts.SHARE_SIZE:(i + 1) * appconsts.SHARE_SIZE]
             for i in range(n)]
        )

    def extend_and_commit(self, payload: dict) -> dict:
        """ODS in -> DAH out; the extended square is cached by data root
        so a follow-up ProveShares costs tree traversal only."""
        ods = self._decode_ods(payload)
        eds, dah, root = self._pipeline(ods)
        key = root.hex()
        with self._lock:
            self._cache[key] = (eds, dah)
            self._cache.move_to_end(key)
            while len(self._cache) > self._cache_squares:
                self._cache.popitem(last=False)
        return {
            "square_size": int(ods.shape[0]),
            "row_roots": [r.hex() for r in dah.row_roots],
            "col_roots": [r.hex() for r in dah.col_roots],
            "data_root": key,
        }

    def prove_shares(self, payload: dict) -> dict:
        """Share-range proof. Source square: ``data_root`` (hex, from the
        cache of recent ExtendAndCommit results) or a fresh ``ods``.
        Every malformed input raises DAError (transports map it to a
        client error, never a 500)."""
        from celestia_app_tpu.chain.query import _share_proof_json
        from celestia_app_tpu.da import proof as proof_mod

        want_root = payload.get("data_root")
        if want_root is not None:
            with self._lock:
                hit = self._cache.get(want_root)
                if hit is not None:
                    self._cache.move_to_end(want_root)
            if hit is None:
                raise DAError(
                    f"no cached square for data root {want_root}; resend "
                    "the ods or re-run extend_commit"
                )
            eds, dah = hit
            root = bytes.fromhex(want_root)
        elif "ods" in payload:
            eds, dah, root = self._pipeline(self._decode_ods(payload))
        else:
            raise DAError("prove_shares needs data_root or ods")

        try:
            start, end = int(payload["start"]), int(payload["end"])
        except (KeyError, TypeError, ValueError):
            raise DAError("prove_shares needs integer start and end") \
                from None
        k = eds.width // 2
        if not (0 <= start < end <= k * k):
            raise DAError(
                f"invalid share range [{start}, {end}) for a {k}x{k} square"
            )
        # namespace parsing + extraction live on the read plane's shared
        # helpers (da/namespace_device.py) — one codec for every caller
        from celestia_app_tpu.da import namespace_device as nsdev

        try:
            namespace = nsdev.decode_namespace(payload.get("namespace", ""))
        except ValueError:
            raise DAError("namespace must be hex") from None
        if not namespace:
            namespace = nsdev.share_namespace(eds.squares[start // k,
                                                          start % k])
        pf = proof_mod.new_share_inclusion_proof(eds, dah, start, end,
                                                 namespace)
        return {
            "proof": _share_proof_json(pf),
            "data_root": root.hex(),
        }

    # -- one dispatcher shared by every transport ------------------------

    def handle(self, path: str, payload: dict) -> dict:
        try:
            if path == "/da/extend_commit":
                return self.extend_and_commit(payload)
            if path == "/da/prove_shares":
                return self.prove_shares(payload)
        except KeyError as e:  # missing request field = client error
            raise DAError(f"missing field {e}") from None
        raise DAError(f"no DA route {path}")


class DAService:
    """Standalone HTTP server for the two DA routes — the sidecar shape:
    run it next to a foreign node, point the shim at it, no chain state
    anywhere in the process."""

    def __init__(self, core: DACore, host: str = "127.0.0.1",
                 port: int = 26659):
        import json
        from http.server import (
            BaseHTTPRequestHandler,
            ThreadingHTTPServer,
        )

        service = self
        self.core = core

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_POST(self):
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    out = service.core.handle(self.path, payload)
                    code = 200
                except DAError as e:
                    out, code = {"error": str(e)}, 400
                except Exception as e:  # never kill the serving thread
                    telemetry.incr("http.500")
                    out, code = {"error": f"{type(e).__name__}: {e}"}, 500
                body = json.dumps(out).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/status":
                    body = json.dumps({
                        "service": "da", "engine": service.core.engine,
                    }).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self.send_response(404)
                    self.end_headers()

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.port = self._httpd.server_address[1]

    def serve_background(self):
        t = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        t.start()
        return self

    def serve_forever(self):
        self._httpd.serve_forever()

    def shutdown(self):
        self._httpd.shutdown()
