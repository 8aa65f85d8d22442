"""Per-validator HTTP consensus service: the socket-crossing vote plane.

Reference parity: celestia-core's p2p reactors gossip proposals, votes, and
txs between validator PROCESSES over TCP (SURVEY §5.8). This server gives
one ValidatorNode (chain/consensus.py) that same out-of-process surface:
every proposal, prevote, precommit, commit, and state-sync chunk crosses a
real socket as JSON — nothing consensus-critical stays in-process. The
devnet's `--processes` mode runs one OS process per validator around this
server (cli.py cmd_validator_serve), with `chain/remote_consensus.py`
driving the round schedule from outside.

Trust model: the node signs votes LOCALLY and verifies every inbound
certificate against its own genesis pubkeys + staking powers
(`ValidatorNode.verify_certificate`) before applying — the orchestrator is
a scheduler, not a trusted party (a forged /consensus/commit is refused).

Routes (all JSON unless noted):
  GET  /consensus/status            {name, height, app_hash, chain_id, mempool}
  GET  /consensus/height            {height} — the lightweight probe
  POST /broadcast_tx {tx: b64}      CheckTx + mempool admission
  POST /consensus/propose {time}    -> {block}    (PrepareProposal or lock)
  POST /consensus/prevote {block}   -> {vote}     (ProcessProposal inside)
  POST /consensus/precommit {block?, polka, round} -> {vote}  (lock if polka)
  POST /consensus/commit {block, cert, evidence} -> {app_hash}

Sync plane (chain/sync.py; docs/FORMATS.md §15):
  GET  /sync/snapshots              {snapshots: [manifest,...]} newest first
  GET  /sync/chunk?height=&index=   raw chunk bytes (octet-stream)
  GET  /gossip/commits?from=&to=    {commits: [...]} batched blocksync
  GET  /consensus/snapshot          DEPRECATED one-shot adapter (§15.4)
  POST /consensus/sync {peer}       DEPRECATED orchestrated pull adapter

Autonomous (gossip) mode adds the peer-to-peer plane consumed by
chain/reactor.py — these routes deliberately BYPASS the big writer lock
(they only record into the reactor's inbox; a slow propose must not
starve vote intake):
  POST /gossip/proposal {proposal}  signed Proposal from a peer
  POST /gossip/vote {round, vote}   prevote/precommit from a peer
  POST /gossip/commit {proposal, cert}   a peer's committed height
  GET  /gossip/commit_at?height=H   recent commit record (laggard catch-up)
  POST /gossip/seen_tx {hash, from} CAT SeenTx announce (want/have gossip)
  GET  /gossip/want_tx?hash=H       CAT WantTx pull -> {tx: b64} delivery
  POST /gossip/tx {tx: b64}         direct Tx push (legacy flood delivery)

DAS serving plane (das/server.py; docs/FORMATS.md §7, §14):
  GET  /das/head | /das/header | /das/sample | /das/availability
  POST /das/samples                 batched sample serving — every commit
                                    seeds its EDS/DAH cache entry here, so
                                    post-commit samples never rebuild under
                                    the consensus lock

Fault-plane admin (celestia_app_tpu/faults; docs/FORMATS.md §9):
  GET  /faults                      armed fault specs + per-point fire counts
  POST /faults/arm {point, action, ...}   arm a fault; -> {id}
  POST /faults/disarm {id|point}    disarm one / by point / all
  POST /faults/reset {seed?}        disarm everything and reseed the rng

Observability plane (celestia_app_tpu/obs; docs/FORMATS.md §10):
  GET  /metrics                     Prometheus text exposition — validator
                                    processes are scrapable, not just nodes
  GET  /trace/<table>?since=&limit= columnar trace pull (spans included)
  POST /debug/profile {seconds}     on-demand jax.profiler capture
Every request's X-Celestia-Trace header is installed as the incoming
span context, so serve-side spans join the calling node's trace.
"""

from __future__ import annotations

import base64
import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from celestia_app_tpu import obs
from celestia_app_tpu.chain import consensus as c
from celestia_app_tpu.das.blob_packs import NamespaceReply
from celestia_app_tpu.utils import telemetry


class ValidatorService:
    def __init__(self, vnode: "c.ValidatorNode", host: str = "127.0.0.1",
                 port: int = 0):
        self.vnode = vnode
        self.lock = threading.Lock()
        self.reactor = None  # set by attach_reactor (autonomous mode)
        # block plane: validator processes serve DAS samples too — the
        # commit path seeds every committed height's EDS/DAH cache entry
        # into this core from the warmer's background thread, so a light
        # client sampling straight off a validator right after commit
        # never triggers a rebuild under the consensus lock
        from celestia_app_tpu.das.server import SampleCore

        self.das_core = SampleCore(vnode.app, app_lock=self.lock)
        vnode.app.add_da_seed_listener(self.das_core.seed_cache_entry)
        # read plane: validators answer namespace reads off the SAME
        # commit-seeded entry cache — no second build path
        from celestia_app_tpu.das.blob_server import BlobCore

        self.blob_core = BlobCore(self.das_core)
        # sync plane: the snapshot set this process serves for chunked
        # state sync (<home>/snapshots, written by the reactor's interval
        # hook / the CLI start loop); None for in-memory nodes — /sync/*
        # then serves an empty manifest list and 404s chunks
        from celestia_app_tpu.chain import sync as sync_mod

        self.sync_store = sync_mod.store_for(vnode)
        service = self

        class Handler(BaseHTTPRequestHandler):
            # HTTP/1.1 keep-alive (serving plane): gossip peers and
            # sampler fleets reuse connections; every response carries
            # Content-Length
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet
                pass

            def _send(self, code: int, obj) -> None:
                # a namespace read's reply comes rendered (FORMATS §21.1)
                body = (obj.render() if isinstance(obj, NamespaceReply)
                        else json.dumps(obj).encode())
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _send_raw(self, code: int, body: bytes) -> None:
                # /sync/chunk serves raw bytes (octet-stream, NOT base64):
                # chunk transfers must not pay the 4/3 b64 inflation
                self.send_response(code)
                self.send_header("Content-Type",
                                 "application/octet-stream")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                # incoming trace context (X-Celestia-Trace): serve-side
                # spans join the calling node's trace (obs/spans.py)
                obs.begin_request(self.headers)
                try:
                    self._get()
                finally:
                    obs.end_request()

            def do_POST(self):
                obs.begin_request(self.headers)
                try:
                    self._post()
                finally:
                    obs.end_request()

            def _get(self):
                try:
                    if self.path == "/consensus/status":
                        with service.lock:
                            self._send(200, service._status())
                    elif self.path == "/consensus/height":
                        # the lightweight height probe (sync plane): one
                        # integer, no lock, no telemetry/mempool/net
                        # blocks — what reactor._probe_peer_heights polls
                        self._send(200,
                                   {"height": service.vnode.app.height})
                    elif self.path.startswith("/sync/"):
                        # chunked state-sync serving (chain/sync.py):
                        # manifests + raw chunks straight from disk —
                        # never a capture, never under the service lock
                        from urllib.parse import parse_qs, urlparse

                        from celestia_app_tpu.chain import sync as sync_mod

                        parsed = urlparse(self.path)
                        try:
                            out = sync_mod.route_sync(
                                service.sync_store, parsed.path,
                                parse_qs(parsed.query),
                            )
                        except sync_mod.SyncError as e:
                            self._send(404 if "not served" in str(e)
                                       else 400, {"error": str(e)})
                            return
                        if isinstance(out, bytes):
                            self._send_raw(200, out)
                        else:
                            self._send(200, out)
                    elif self.path == "/metrics":
                        # Prometheus text exposition — validator
                        # processes were invisible to scrapers before
                        # this route (only the node service had it);
                        # ONE implementation shared with the node
                        # service (obs.serve_metrics)
                        obs.serve_metrics(self)
                    elif self.path.startswith("/trace/"):
                        # columnar trace pull (spans included) from THIS
                        # validator's per-app tables — the route e2e
                        # tooling and tools/timeline.py scrape
                        try:
                            self._send(200, obs.route_trace(
                                service.vnode.app.traces, self.path))
                        except ValueError as e:
                            self._send(400, {"error": str(e)})
                    elif self.path == "/faults":
                        # fault-plane admin surface (celestia_app_tpu/
                        # faults): chaos harnesses inspect and arm fault
                        # points on a LIVE validator through it
                        from celestia_app_tpu.faults import route_faults

                        self._send(200, route_faults("GET", self.path))
                    elif self.path.startswith("/gossip/commit_at"):
                        from urllib.parse import parse_qs, urlparse

                        if service.reactor is None:
                            self._send(404, {"error": "not autonomous"})
                            return
                        q = parse_qs(urlparse(self.path).query)
                        h = int(q.get("height", ["0"])[0])
                        self._send(200, service.reactor.commit_at(h) or {})
                    elif self.path.startswith("/gossip/commits"):
                        # batched blocksync serving (sync plane): one
                        # response carries a whole verification window of
                        # commit records, bytes-capped by the reactor
                        from urllib.parse import parse_qs, urlparse

                        if service.reactor is None:
                            self._send(404, {"error": "not autonomous"})
                            return
                        q = parse_qs(urlparse(self.path).query)
                        lo = int(q.get("from", ["0"])[0])
                        hi = int(q.get("to", ["0"])[0])
                        self._send(200, {
                            "commits":
                                service.reactor.commits_range(lo, hi),
                        })
                    elif self.path.startswith("/gossip/want_tx"):
                        # WantTx pull: serve tx content for an announced
                        # hash (the Tx delivery of the want/have protocol)
                        from urllib.parse import parse_qs, urlparse

                        if service.reactor is None:
                            self._send(404, {"error": "not autonomous"})
                            return
                        q = parse_qs(urlparse(self.path).query)
                        try:
                            h = bytes.fromhex(q.get("hash", [""])[0])
                        except ValueError:
                            self._send(400, {"error": "hash must be hex"})
                            return
                        raw = service.reactor.serve_want_tx(h)
                        self._send(200, {} if raw is None else {
                            "tx": base64.b64encode(raw).decode()
                        })
                    elif self.path.startswith("/das/"):
                        # DAS sample serving (das/server.py): commit-
                        # seeded entries answer from pre-built provers;
                        # misses take the service lock inside route_das
                        # (SampleCore.app_lock), never here
                        from urllib.parse import parse_qs, urlparse

                        from celestia_app_tpu.das.server import (
                            SampleError,
                            route_das,
                        )

                        parsed = urlparse(self.path)
                        try:
                            out = route_das(
                                service.das_core, "GET", parsed.path,
                                parse_qs(parsed.query),
                            )
                            if isinstance(out, bytes):
                                # /das/pack/chunk: raw static bytes
                                self._send_raw(200, out)
                            else:
                                self._send(200, out)
                        except SampleError as e:
                            self._send(404 if "not served" in str(e)
                                       else 400, {"error": str(e)})
                    elif self.path.startswith("/blob/"):
                        # read plane (das/blob_server.py): namespace
                        # reads + blob-pack static serving; BlobError
                        # is a SampleError, so one handler covers both
                        from urllib.parse import parse_qs, urlparse

                        from celestia_app_tpu.das.server import (
                            SampleError,
                        )
                        from celestia_app_tpu.das.blob_server import (
                            route_blob,
                        )

                        parsed = urlparse(self.path)
                        try:
                            out = route_blob(
                                service.blob_core, "GET", parsed.path,
                                parse_qs(parsed.query),
                            )
                            if isinstance(out, bytes):
                                # /blob/pack/chunk: raw static bytes
                                self._send_raw(200, out)
                            else:
                                self._send(200, out)
                        except SampleError as e:
                            self._send(404 if "not served" in str(e)
                                       else 400, {"error": str(e)})
                    elif self.path.split("?", 1)[0] \
                            == "/consensus/snapshot":
                        # DEPRECATED one-shot pull (FORMATS §15.4), now a
                        # thin adapter over the chunked plane: the newest
                        # restorable disk snapshot ahead of the puller's
                        # ?min_height= (no capture, no lock), else the
                        # legacy capture-on-request so fresh chains and
                        # already-ahead pullers keep bootstrapping
                        from urllib.parse import parse_qs, urlparse

                        from celestia_app_tpu.chain import sync as sync_mod

                        q = parse_qs(urlparse(self.path).query)
                        self._send(200, sync_mod.legacy_snapshot_doc(
                            service.vnode, service.sync_store,
                            service_lock=service.lock,
                            min_height=int(
                                q.get("min_height", ["0"])[0]),
                        ))
                    else:
                        self._send(404, {"error": f"no route {self.path}"})
                except Exception as e:
                    telemetry.incr("http.500")
                    self._send(500, {"error": f"{type(e).__name__}: {e}"})

            def _post(self):
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    # gossip intake records into the reactor inbox WITHOUT
                    # the writer lock — vote delivery must not wait behind
                    # a propose/apply in progress
                    gossip = {
                        "/gossip/proposal": "on_proposal",
                        "/gossip/vote": "on_vote",
                        "/gossip/commit": "on_commit",
                        "/gossip/tx": "on_tx",
                        "/gossip/seen_tx": "on_seen_tx",
                    }.get(self.path)
                    if gossip is not None:
                        if service.reactor is None:
                            self._send(404, {"error": "not autonomous"})
                            return
                        try:
                            # gossip receives are spans too: adopted into
                            # the sender's trace via the incoming header
                            with obs.span(
                                "gossip.recv."
                                + self.path.rsplit("/", 1)[1],
                                traces=service.vnode.app.traces,
                                node=service.vnode.name,
                            ):
                                getattr(service.reactor, gossip)(payload)
                        except (KeyError, TypeError, ValueError) as e:
                            # malformed peer input is the peer's problem,
                            # not a server error
                            self._send(400, {
                                "error": f"malformed gossip: "
                                         f"{type(e).__name__}"
                            })
                            return
                        self._send(200, {"ok": True})
                        return
                    if self.path.startswith("/faults/"):
                        from celestia_app_tpu.faults import route_faults

                        try:
                            self._send(200, route_faults(
                                "POST", self.path, payload))
                        except (ValueError, KeyError) as e:
                            # malformed spec: 400, matching the node
                            # service (FORMATS.md §9.1)
                            self._send(400, {"error": str(e)})
                        return
                    if self.path == "/debug/profile":
                        # on-demand jax.profiler capture (FORMATS §10.3);
                        # refuses on host-engine processes (jax unloaded)
                        self._send(*obs.route_profile(payload))
                        return
                    if self.path in ("/das/samples", "/das/headers"):
                        from celestia_app_tpu.das.server import (
                            SampleError,
                            route_das,
                        )

                        try:
                            self._send(200, route_das(
                                service.das_core, "POST", self.path,
                                {}, payload,
                            ))
                        except SampleError as e:
                            self._send(404 if "not served" in str(e)
                                       else 400, {"error": str(e)})
                        return
                    if self.path == "/blob/namespaces":
                        from celestia_app_tpu.das.server import (
                            SampleError,
                        )
                        from celestia_app_tpu.das.blob_server import (
                            route_blob,
                        )

                        try:
                            self._send(200, route_blob(
                                service.blob_core, "POST", self.path,
                                {}, payload,
                            ))
                        except SampleError as e:
                            self._send(404 if "not served" in str(e)
                                       else 400, {"error": str(e)})
                        return
                    route = {
                        "/broadcast_tx": service._broadcast_tx,
                        "/consensus/propose": service._propose,
                        "/consensus/prevote": service._prevote,
                        "/consensus/precommit": service._precommit,
                        "/consensus/commit": service._commit,
                        "/consensus/sync": service._sync,
                    }.get(self.path)
                    if route is None:
                        self._send(404, {"error": f"no route {self.path}"})
                        return
                    with service.lock:
                        self._send(200, route(payload))
                except ValueError as e:
                    self._send(400, {"error": str(e)})
                except Exception as e:
                    telemetry.incr("http.500")
                    self._send(500, {"error": f"{type(e).__name__}: {e}"})

        class Server(ThreadingHTTPServer):
            # burst connects (gossip storms, sampler fleets): the stdlib
            # default listen backlog of 5 resets most of a burst
            request_queue_size = 1024

        self.httpd = Server((host, port), Handler)
        self.port = self.httpd.server_address[1]
        # GIL-pressure sampler for this serving plane (no-op unless
        # CELESTIA_OBS is on): gil.pressure{service="validator"}
        from celestia_app_tpu.obs import gil
        gil.start("validator")

    # -- handlers (under self.lock) --------------------------------------

    @staticmethod
    def _admission_status(app) -> dict:
        from celestia_app_tpu.chain import admission as admission_mod

        return admission_mod.status_block(app)

    @staticmethod
    def _blob_status() -> dict:
        from celestia_app_tpu.das import blob_server as blob_server_mod

        return blob_server_mod.status_block()

    def _status(self) -> dict:
        v = self.vnode
        out = {
            "name": v.name,
            "address": v.address.hex(),
            "chain_id": v.app.chain_id,
            "height": v.app.height,
            "app_version": v.app.app_version,
            "app_hash": v.app.last_app_hash.hex(),
            "mempool": len(v.pool),
            "mempool_bytes": v.pool.pool_bytes,
            # CAT pool counters (admitted/rejected/duplicate/evicted/
            # expired_*/recheck_dropped/committed) — per NODE, unlike the
            # process-wide prometheus endpoint
            "mempool_stats": v.pool.stats(),
            "locked": v.locked_block.header.hash().hex()
            if v.locked_block is not None else None,
            # admission plane + traffic plane: the verified-sig and
            # verified-commitment cache behavior (FORMATS §12.3/§20.3)
            # plus any co-resident txsim load's counters — process-wide
            # (the same numbers /metrics exposes), surfaced here so an
            # operator sees admission economics next to the mempool
            "admission": self._admission_status(v.app),
            # read plane counters (blob.* / blobpacks.*) — process-wide
            "blob": self._blob_status(),
        }
        if self.reactor is not None:
            out["reactor"] = {
                "round": self.reactor.round,
                "step": self.reactor.step,
                "height_view": self.reactor.height_view,
                "loop_errors": self.reactor.loop_errors,
                # sync-plane failure visibility: a dead snapshot peer or
                # failing record fetches show up HERE, not as silence
                "statesync_errors": self.reactor.statesync_errors,
                "blocksync_fetch_errors":
                    self.reactor.blocksync_fetch_errors,
                # boundary observatory: ledger bytes the LAST committed
                # block moved across the host<->device boundary —
                # ROADMAP item 2's per-block gauge, beside the round
                # state an operator already watches
                "host_bytes_crossed_per_block":
                    v.app.last_host_bytes_crossed,
            }
            out["mempool_gossip"] = dict(self.reactor.mempool_gossip.stats)
            # per-peer transport health: breaker state, success/failure
            # tallies, EWMA latency (net/transport.py; FORMATS.md §9) —
            # how an operator (and the chaos tests) see a tripped breaker
            out["net"] = self.reactor.net.snapshot()
        return out

    def attach_reactor(self, peer_urls: list[str], config=None,
                       self_url: str | None = None):
        """Switch this validator to autonomous mode: start the consensus
        reactor thread gossiping with `peer_urls` (chain/reactor.py).
        `self_url` is the URL peers reach THIS service at (rides SeenTx
        announces so peers know whom to pull tx content from); defaults
        to localhost:port, which matches how the devnet spawner and the
        in-process test nets address each other."""
        from celestia_app_tpu.chain.reactor import ConsensusReactor

        self.reactor = ConsensusReactor(
            self.vnode, peer_urls, self.lock, config,
            self_url=self_url or f"http://127.0.0.1:{self.port}",
        )
        self.reactor.start()
        return self.reactor

    def _broadcast_tx(self, p: dict) -> dict:
        raw = base64.b64decode(p["tx"])
        res = self.vnode.add_tx(raw)  # the ONE admission path
        if res.code == 0 and self.reactor is not None:
            # autonomous mode: flood to peers (the mempool reactor) so any
            # upcoming proposer can include the tx
            self.reactor.gossip_tx(raw)
        return {"code": res.code, "log": res.log,
                "gas_wanted": res.gas_wanted, "gas_used": res.gas_used}

    def _propose(self, p: dict) -> dict:
        block = self.vnode.propose(t=float(p["time"]))
        return {"block": c.block_to_json(block)}

    def _prevote(self, p: dict) -> dict:
        block = c.block_from_json(p["block"])
        round_ = int(p.get("round", 0))
        return {"vote": c.vote_to_json(self.vnode.prevote_on(block, round_))}

    def _precommit(self, p: dict) -> dict:
        """polka=true: the orchestrator relays the >2/3 prevote set as the
        polka justification; the node re-counts it AGAINST ITS OWN trust
        roots before locking — a lying coordinator cannot force a lock.
        The polka must be FROM the precommit's round (stale-round prevote
        pooling is refused in _polka_checks_out), must not regress an
        existing lock to an older round, and the sign guard's monotonic
        watermark independently refuses old-round signatures — three
        layers against coordinator-harvested conflicting precommits."""
        round_ = int(p.get("round", 0))
        if not p.get("polka"):
            return {"vote": c.vote_to_json(
                self.vnode.precommit_on(None, round_))}
        block = c.block_from_json(p["block"])
        prevotes = [c.vote_from_json(v) for v in p.get("prevotes", [])]
        lock_ok = self.vnode.lock_permits(block.header.hash(), round_)
        if not lock_ok or not self._polka_checks_out(block, prevotes,
                                                     round_):
            return {"vote": c.vote_to_json(
                self.vnode.precommit_on(None, round_))}
        self.vnode.on_polka(block, round_)
        return {"vote": c.vote_to_json(
            self.vnode.precommit_on(block, round_))}

    def _polka_checks_out(self, block, prevotes, round_: int) -> bool:
        from celestia_app_tpu.chain.crypto import PublicKey
        from celestia_app_tpu.chain.state import Context, InfiniteGasMeter

        v = self.vnode
        bh = block.header.hash()
        ctx = Context(v.app.store, InfiniteGasMeter(), v.app.height, 0,
                      v.app.chain_id, v.app.app_version)
        powers = dict(v.app.staking.validators(ctx))
        known = v.known_pubkeys()
        signed = 0
        seen: set[bytes] = set()
        # a polka is >2/3 prevote power in ONE round — the round we are
        # being asked to precommit. Counting each prevote against its own
        # claimed round would let a lying coordinator pool stale prevotes
        # from failed rounds into a quorum no single round ever had.
        doc = c.Vote.sign_bytes(v.app.chain_id, block.header.height,
                                bh, "prevote", round_)
        for pv in prevotes:
            if (pv.block_hash != bh or pv.phase != "prevote"
                    or pv.round != round_ or pv.validator in seen):
                continue
            pub = known.get(pv.validator)
            if pub is None or not PublicKey(pub).verify(pv.signature, doc):
                continue
            seen.add(pv.validator)
            signed += powers.get(pv.validator, 0)
        return signed * 3 > sum(powers.values()) * 2

    def _commit(self, p: dict) -> dict:
        block = c.block_from_json(p["block"])
        cert = c.cert_from_json(p["cert"])
        evidence = tuple(
            c.evidence_from_json(e) for e in p.get("evidence", [])
        )
        if cert.block_hash != block.header.hash():
            raise ValueError("certificate does not cover this block")
        if not self.vnode.verify_certificate(cert):
            raise ValueError("commit certificate failed local verification")
        app_hash = self.vnode.apply(block, cert, evidence)
        self.vnode.clear_lock()
        return {"app_hash": app_hash.hex(), "height": self.vnode.app.height}

    def _sync(self, p: dict) -> dict:
        """State-sync catch-up over the wire (DEPRECATED orchestrated
        route, FORMATS §15.4) — now a thin adapter over the chunked
        plane: a peer serving /sync/* gets the parallel, verified,
        resumable chunk fetch; one that predates it falls back to the
        legacy one-shot /consensus/snapshot pull. Adoption goes through
        the unchanged app-hash-anchored state_sync_bootstrap either way."""
        import tempfile

        from celestia_app_tpu.chain import sync as sync_mod
        from celestia_app_tpu.net import transport

        before = self.vnode.app.height
        home = sync_mod.home_for(self.vnode)
        ephemeral = home is None
        workdir = (tempfile.mkdtemp(prefix="statesync-") if ephemeral
                   else os.path.join(home, sync_mod.RESTORE_DIRNAME))
        client = sync_mod.StateSyncClient(
            [p["peer"]], workdir, min_height=before,
            name=self.vnode.name,
            da_scheme=sync_mod.scheme_of(self.vnode),
        )
        try:
            try:
                manifest, chunks = client.fetch()
            except sync_mod.StateSyncUnavailable:
                import urllib.error

                try:
                    doc = transport.request_json(
                        p["peer"],
                        f"/consensus/snapshot?min_height={before}",
                        timeout=30,
                    )
                except urllib.error.HTTPError:
                    # pre-query peer: exact-path route only
                    doc = transport.request_json(
                        p["peer"], "/consensus/snapshot", timeout=30
                    )
                manifest = doc["manifest"]
                chunks = [base64.b64decode(ch) for ch in doc["chunks"]]
            # the legacy endpoint can serve a DISK snapshot OLDER than
            # this node (the capture-on-request original was always the
            # peer's tip): adopting it would rewind the chain
            if int(manifest["height"]) <= before:
                raise ValueError(
                    f"peer snapshot at {manifest['height']} is not "
                    f"ahead of height {before}"
                )
            c.state_sync_bootstrap(self.vnode, manifest, chunks)
            client.cleanup()
        except Exception:
            # failed adoption: drop the restore material, or the resume
            # preference would latch onto the same manifest next call
            client.cleanup()
            raise
        finally:
            if ephemeral:
                import shutil

                shutil.rmtree(workdir, ignore_errors=True)
        return {"height": self.vnode.app.height, "from_height": before,
                "app_hash": self.vnode.app.last_app_hash.hex()}

    # -- lifecycle -------------------------------------------------------

    def serve_background(self) -> threading.Thread:
        th = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        th.start()
        return th

    def serve_forever(self) -> None:
        self.httpd.serve_forever()

    def shutdown(self) -> None:
        if self.reactor is not None:
            self.reactor.stop()
        # deregister the commit-seed hook: a service rebuilt over a
        # long-lived vnode must not leave its dead SampleCore receiving
        # (and pinning) every future height's entries
        self.vnode.app.remove_da_seed_listener(
            self.das_core.seed_cache_entry)
        self.httpd.shutdown()
        self.httpd.server_close()
