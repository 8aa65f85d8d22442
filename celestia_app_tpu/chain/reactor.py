"""Autonomous consensus reactor: each validator drives its OWN rounds.

Reference parity: celestia-core's consensus reactor (SURVEY §5.8) — every
validator process runs the Tendermint round state machine itself and
exchanges proposals/votes with peers over the network; there is no
coordinator. This module is that state machine for this framework's
validator processes: a background thread per process stepping

    propose -> prevote -> (polka? lock) -> precommit -> commit

with wall-clock phase timeouts escalating per failed round, deterministic
proposer rotation, peer-to-peer flooding of proposals and votes over the
validator HTTP services (service/validator_server.py /gossip/* routes),
and commit-certificate assembly from each node's own received votes.

Determinism of commit info (the one subtlety vs the orchestrated
SocketNetwork): every node assembles a DIFFERENT >2/3 certificate from
gossip, but liveness accounting and evidence must be identical across the
network or app hashes diverge. Tendermint solves this by putting
LastCommitInfo and evidence IN the block; here the signed Proposal
envelope (chain/consensus.py Proposal) carries the height-1 certificate
and the evidence list, and apply() consumes THOSE for absence accounting
(absent_cert=) while storing the locally-assembled cert for the height.

Trust model: all inbound gossip is verified locally — proposal signatures
against the expected proposer for (height, round), vote signatures
against genesis pubkeys, certificates against the node's own staking
powers — a byzantine peer can at most waste inbox space. Vote signatures
commit to (chain_id, height, ROUND, hash, phase) — Tendermint's
CanonicalVote fields (celestia-core types/vote.go) — so a relayed
old-round vote cannot be replayed into a newer round, certificates are
round-scoped (Commit.round), and per-round attribution is exact; the
unlock-on-higher-polka rule this enables keeps a locked validator live
when the network polkas a different block in a later round.

Catch-up (the sync plane, chain/sync.py + docs/DESIGN.md): a node that
misses the commit gossip for its next height pulls peers' commit
records in batched windows (GET /gossip/commits, per-height
/gossip/commit_at as the fallback) and replays them through the same
verification live gossip gets — verified blocksync, any gap depth, with
the next window prefetched while the current one verifies. Chunked,
parallel, resumable state sync (GET /sync/snapshots + /sync/chunk,
app-hash-anchored adoption) covers gaps beyond cfg.statesync_gap or
records no peer can serve; the node also WRITES interval snapshots for
peers to join from (cfg.snapshot_interval).
"""

from __future__ import annotations

import dataclasses
import json
import threading

from celestia_app_tpu import obs
from celestia_app_tpu.chain import consensus as c
from celestia_app_tpu.chain.state import Context, InfiniteGasMeter
from celestia_app_tpu.net.transport import PeerClient, TransportConfig
from celestia_app_tpu.utils import telemetry

log = obs.get_logger("chain.reactor")


@dataclasses.dataclass
class ReactorConfig:
    """Phase timeouts (seconds). The COLD defaults cover a first proposal
    paying a jit compile on device engines; once this node commits its
    first height (the compile cache is hot), timeouts auto-scale down to
    the warm values — the reference's mainnet shape is TimeoutPropose
    10 s / TimeoutCommit 11 s (pkg/appconsts/consensus_consts.go:6-13),
    and the warm defaults match it. Steady-state block interval is
    therefore bounded by warm_propose + warm_prevote + warm_precommit +
    block_interval in the worst (full-timeout) round, and by gossip
    latency (~tens of ms on a devnet) when all validators are live."""

    timeout_propose: float = 30.0
    timeout_prevote: float = 20.0
    timeout_precommit: float = 20.0
    # post-first-commit shape (reference parity)
    warm_propose: float = 10.0
    warm_prevote: float = 5.0
    warm_precommit: float = 5.0
    timeout_delta: float = 5.0  # added per failed round
    block_interval: float = 0.05  # pause between committed heights
    poll: float = 0.02  # inbox poll granularity
    gossip_timeout: float = 5.0  # per-peer HTTP send timeout
    recent_commits: int = 8  # commit records served to laggards
    sync_grace: float = 5.0  # how long "peer ahead" persists before sync
    # artificial per-message send latency (seconds): software-level network
    # condition injection, the role BitTwister plays in the reference's e2e
    # benchmarks (test/e2e/benchmark/benchmark.go:110-117 injects 70 ms)
    gossip_delay: float = 0.0
    # verified blocksync (celestia-core blocksync analog): commit records
    # are persisted per height and served to laggards from disk, so a
    # node down ANY number of heights replays block-by-block with cert
    # verification against its own then-current valset. Per reactor step
    # at most `blocksync_batch` heights replay (keeps the loop
    # responsive); for a gap wider than `statesync_gap` a snapshot is
    # attempted ONCE per catch-up episode first (replay continues either
    # way). The record store keeps `commit_records_keep` heights — a
    # laggard farther back than any peer's window state-syncs instead.
    blocksync_batch: int = 64
    statesync_gap: int = 512
    commit_records_keep: int = 10_000
    # the sync plane (chain/sync.py): pipelined blocksync pulls commit
    # records in `blocksync_batch`-height windows via GET /gossip/commits
    # and prefetches window N+1 on a background thread while window N
    # runs the unchanged per-height verification — the replay loop is
    # verification-bound, not RTT-bound. `blocksync_serve_bytes` caps one
    # served range response; `blocksync_pipeline=False` keeps the
    # per-height round-trip loop (the differential baseline of
    # tests/test_sync.py; neither is measured on the chip's host).
    blocksync_pipeline: bool = True
    blocksync_serve_bytes: int = 2 << 20
    # chunked state sync: parallel chunk fetchers per restore, and the
    # interval snapshots this node WRITES for peers to join from
    # (default_overrides.go:294-297 interval 1500 keep 2; 0 disables).
    # Snapshots land under <home>/snapshots via chain/sync.SnapshotStore;
    # in-memory nodes (no data_dir) never write them.
    statesync_workers: int = 4
    snapshot_interval: int = 1500
    snapshot_keep: int = 2
    # shared-transport hardening (net/transport.py): gossip is fire-and-
    # forget so sends make ONE attempt (the pull paths recover anything
    # that matters); `breaker_failures` consecutive failures open the
    # peer's circuit and sends are SKIPPED (not retried every tick) until
    # a half-open probe after `breaker_reset` seconds succeeds
    net_retries: int = 1
    breaker_failures: int = 3
    breaker_reset: float = 2.5


class ConsensusReactor:
    """The per-validator round state machine (one thread per process)."""

    def __init__(self, vnode, peer_urls: list[str], service_lock,
                 config: ReactorConfig | None = None,
                 self_url: str = "", clock=None):
        from celestia_app_tpu.utils import clock as clock_mod

        self.vnode = vnode
        self.peers = [u.rstrip("/") for u in peer_urls]
        self.service_lock = service_lock
        self.cfg = config or ReactorConfig()
        # THE reactor time source (utils/clock.py): every poll/backoff/
        # deadline/timestamp below reads it — SystemClock by default
        # (production behavior pinned unchanged), a VirtualClock when a
        # scheduler drives this reactor on simulated time. Handed down to
        # the transport so breaker timers and retry backoffs ride the
        # same timeline.
        self.clock = clock if clock is not None else clock_mod.SYSTEM
        # peer-visible URL of THIS node: rides SeenTx announces so the
        # receiver knows whom to WantTx-pull the content from
        self.self_url = self_url.rstrip("/")
        # rotation order: operator addresses of the CURRENT staked set
        # (sorted), refreshed from state at every commit — a runtime
        # MsgCreateValidator(pubkey=...) joins the schedule the height
        # after it commits, Tendermint's valset-update flow. Genesis
        # pubkeys seed the set; every process derives the identical
        # schedule from its own state, no exchange needed.
        self.rotation = sorted(self.vnode.validator_pubkeys.keys())
        self._pubkey_cache = dict(self.vnode.validator_pubkeys)
        if not self.rotation:
            raise ValueError(
                "autonomous consensus needs genesis validator pubkeys"
            )
        # THE peer transport for everything this reactor sends or pulls:
        # gossip floods, WantTx pulls, status probes, blocksync record
        # fetches, state sync. One instance so breaker/health state is
        # per-PEER across all of them — a peer that hard-fails gossip is
        # also skipped by the pull paths until its half-open probe clears.
        self.net = PeerClient(
            TransportConfig(
                timeout=self.cfg.gossip_timeout,
                retries=self.cfg.net_retries,
                failure_threshold=self.cfg.breaker_failures,
                reset_timeout=self.cfg.breaker_reset,
            ),
            name=vnode.name,
            clock=self.clock,
        )
        self.round = 0
        self.step = "idle"
        self.loop_errors = 0  # counted, surfaced in /consensus/status
        # sync-plane failure counters (surfaced in /consensus/status's
        # reactor block + telemetry): a dead snapshot peer or a failing
        # record fetch must be VISIBLE, not silently swallowed into the
        # catch-up loop's return False
        self.statesync_errors = 0
        self.blocksync_fetch_errors = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # inbox (guarded by _msg_lock; handlers must never block on the
        # service lock, or a slow propose would starve vote intake)
        self._msg_lock = threading.Lock()
        self._proposals: dict[tuple[int, int], c.Proposal] = {}
        self._votes: dict[tuple[int, int, str], dict[bytes, c.Vote]] = {}
        self._pending_commits: list[dict] = []
        self._vote_pool: list[c.Vote] = []  # precommits, for evidence
        self._recent: dict[int, dict] = {}  # height -> gossiped commit doc
        self._ahead: tuple[int, str, float] | None = None  # (h, peer, t)
        self.height_view = self.vnode.app.height + 1  # for status only
        self.app_hashes: dict[int, str] = {}  # height -> hex (divergence checks)
        self._senders: dict[str, object] = {}  # peer url -> send queue
        # the mempool reactor's want/have protocol state (SeenTx/WantTx/Tx
        # — mempool/gossip.py); replaces the blind tx flood
        from celestia_app_tpu.mempool.gossip import MempoolGossip

        self.mempool_gossip = MempoolGossip(
            self.vnode.pool, self.peers, self.self_url
        )
        self._pending_txs: list[tuple[bytes, str]] = []  # direct deliveries
        self._pending_wants: list[tuple[bytes, str]] = []  # (hash, provider)
        # powers snapshot from just BEFORE our latest commit: the set that
        # signed that height's certificate (validators for height H come
        # from state after H-1). Verifying a height-1 cert against POST-
        # apply powers would mis-count when that block slashed a signer.
        self._last_powers: tuple[int, dict[bytes, int]] | None = None
        # proposers we have seen a valid proposal (or applied commit)
        # from: the warm propose-timeout applies only to them — a
        # never-seen proposer may be paying its cold jit compile, the
        # exact case the cold default exists for
        self._seen_proposers: set[bytes] = set()
        # snapshot-first attempted this catch-up episode? (reset when
        # caught up; replay continues regardless of the attempt)
        self._statesync_tried: bool = False
        # proposers whose last expected proposal timed out while they
        # were in _seen_proposers: they get ONE cold-window retry (a
        # restarted validator repays its jit compile); a second timeout
        # means dead, back to warm windows so rotation stays fast
        self._cold_retry: set[bytes] = set()
        # pipelined blocksync: the one-slot prefetch window — while the
        # reactor verifies/applies batch N, a background thread fills
        # this slot with batch N+1 (chain/sync plane)
        self._prefetch_lock = threading.Lock()
        self._prefetched: tuple[int, list[dict]] | None = None  # guarded-by: _prefetch_lock
        self._prefetch_thread: threading.Thread | None = None  # guarded-by: _prefetch_lock
        # the snapshot set THIS node serves for chunked state sync
        # (<home>/snapshots; None for in-memory nodes) — written at
        # cfg.snapshot_interval commits, outside the writer lock
        from celestia_app_tpu.chain import sync as sync_mod

        self.snapshot_store = sync_mod.store_for(self.vnode)

    # -- lifecycle -------------------------------------------------------

    def start(self) -> None:
        with self.service_lock:
            self._refresh_valset()  # a resumed node's set may differ from genesis
            self._drop_records_above(self.vnode.app.height)
        self._start_senders()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)

    # -- outbound gossip -------------------------------------------------

    def _gossip(self, path: str, payload: dict) -> None:
        """Fire-and-forget flood to every peer (fully-connected devnet
        topology). One daemon sender per peer drains a queue, so a dead
        peer costs ONE blocked thread regardless of message rate, and
        messages to a live peer stay ordered. The enqueuer's span context
        rides along (obs.capture), so the cross-thread send is recorded
        as part of the originating round's trace."""
        ctx = obs.capture()
        for u in self.peers:
            try:
                self._senders[u].put_nowait((path, payload, ctx))
            except Exception:
                # queue full (peer long dead): drop — gossip is best-
                # effort; the pull-probe recovers anything that matters
                telemetry.incr("reactor.gossip_dropped")

    def _start_senders(self) -> None:
        """One sender queue+thread per peer, created once at start (the
        peer list is static for a reactor's lifetime)."""
        import queue

        for url in self.peers:
            q = queue.Queue(maxsize=256)
            self._senders[url] = q

            def drain(u: str = url, qq=q) -> None:
                while not self._stop.is_set():
                    try:
                        item = qq.get(timeout=1.0)
                    except queue.Empty:
                        continue
                    path, payload, ctx = item
                    if self.cfg.gossip_delay > 0:  # injected latency
                        self.clock.sleep(self.cfg.gossip_delay)
                    if not self.net.available(u):
                        # circuit open: SKIP the peer instead of paying a
                        # connect timeout per queued message — gossip is
                        # best-effort and the pull probes recover anything
                        # that matters once the breaker half-opens
                        telemetry.incr("net.send_skipped")
                        continue
                    try:
                        # resume the enqueuer's span context so this send
                        # (and the peer's receive, via the trace header
                        # the transport injects) joins the height's trace
                        with obs.resume(ctx, "gossip.send", peer=u,
                                        path=path):
                            self.net.post(u, path, payload)
                    except (OSError, ValueError):
                        # counted, never silent: the transport's per-peer
                        # failure tally (net snapshot) carries the detail
                        telemetry.incr("net.send_failures")

            threading.Thread(target=drain, daemon=True).start()

    # -- inbound gossip (HTTP handler threads; _msg_lock only) -----------

    def on_proposal(self, doc: dict) -> None:
        prop = c.proposal_from_json(doc)
        expected = self.rotation[
            (prop.height + prop.round) % len(self.rotation)
        ]
        if prop.proposer != expected:
            return
        pub = self._pubkey_cache.get(prop.proposer)
        if pub is None or not prop.verify(self.vnode.app.chain_id, pub):
            return
        with self._msg_lock:
            self._proposals.setdefault((prop.height, prop.round), prop)
            self._seen_proposers.add(prop.proposer)  # warm-timeout gate
        telemetry.incr("reactor.gossip.proposals")
        self._note_height(prop.height)

    def on_vote(self, doc: dict) -> None:
        vote = c.vote_from_json(doc["vote"])
        pub = self._pubkey_cache.get(vote.validator)
        if pub is None:
            return
        # the vote's OWN signed round is authoritative — the envelope
        # round is transport metadata a relayer could rewrite
        signed = c.Vote.sign_bytes(
            self.vnode.app.chain_id, vote.height, vote.block_hash,
            vote.phase, vote.round,
        )
        from celestia_app_tpu.chain.crypto import PublicKey

        if not PublicKey(pub).verify(vote.signature, signed):
            return
        with self._msg_lock:
            pool = self._votes.setdefault(
                (vote.height, vote.round, vote.phase), {}
            )
            fresh = vote.validator not in pool
            pool.setdefault(vote.validator, vote)
            if fresh and vote.block_hash is not None:
                # both phases feed the evidence pool: same-round
                # duplicates in either phase are slashable
                self._vote_pool.append(vote)
        telemetry.incr("reactor.gossip.votes")
        self._note_height(vote.height)

    def on_commit(self, doc: dict, peer: str = "") -> None:
        """A peer announces a committed height: queue for the loop (the
        handler must not grab the service lock — apply can take seconds)."""
        with self._msg_lock:
            self._pending_commits.append(doc)
        try:
            self._note_height(int(doc["cert"]["height"]), peer)
        except (KeyError, TypeError, ValueError):
            pass

    def commit_at(self, height: int) -> dict | None:
        with self._msg_lock:
            doc = self._recent.get(height)
        if doc is None:
            # blocksync: any persisted height serves a laggard, not just
            # the in-memory recent window
            doc = self._load_commit_record(height)
        return doc

    def commits_range(self, lo: int, hi: int) -> list[dict]:
        """Batched blocksync serving (GET /gossip/commits?from=&to=):
        consecutive commit records from `lo` up to `hi` inclusive,
        clamped to one cfg.blocksync_batch window and to
        cfg.blocksync_serve_bytes of encoded payload (always at least
        one record when one exists). A gap ends the response — the
        requester falls back to per-height pulls / other peers there."""
        if lo < 1 or hi < lo:
            return []
        hi = min(hi, lo + self.cfg.blocksync_batch - 1)
        out: list[dict] = []
        size = 0
        for h in range(lo, hi + 1):
            doc = self.commit_at(h)
            if doc is None:
                break
            size += len(json.dumps(doc))
            if out and size > self.cfg.blocksync_serve_bytes:
                break
            out.append(doc)
        return out

    # -- mempool gossip: the CAT want/have reactor (mempool/gossip.py) ---
    # SeenTx (32-byte hash announce) replaces the old full-tx flood; a
    # peer that wants the content pulls it (WantTx -> Tx) from an
    # announcer. Per-peer have-sets and redundant-want suppression keep
    # tx payload bytes to ~one transfer per edge that needs it.

    def _announce_tx(self, h: bytes) -> None:
        """SeenTx to every peer not known to have the tx (hash + our URL,
        never the payload); marks the hash processed."""
        with self._msg_lock:
            self.mempool_gossip.first_seen(h)  # idempotent mark
            targets = self.mempool_gossip.announce_targets(h)
        payload = {"hash": h.hex(), "from": self.self_url}
        ctx = obs.capture()  # announces join the round's trace too
        for u in targets:
            try:
                self._senders[u].put_nowait(
                    ("/gossip/seen_tx", payload, ctx)
                )
            except Exception:
                telemetry.incr("reactor.gossip_dropped")  # best-effort

    def gossip_tx(self, raw: bytes) -> None:
        """Announce a locally-admitted tx to peers (mempool reactor out);
        dedup-gated so a duplicate /broadcast_tx does not re-announce."""
        from celestia_app_tpu.mempool.pool import tx_hash

        h = tx_hash(raw)
        with self._msg_lock:
            fresh = not self.mempool_gossip.seen(h)
        if fresh:
            self._announce_tx(h)

    def on_seen_tx(self, doc: dict) -> None:
        """A peer announces it HAS a tx: queue a pull if we want it (the
        handler must not do network I/O or take the writer lock)."""
        h = bytes.fromhex(doc["hash"])
        if len(h) != 32:
            raise ValueError("seen_tx hash must be 32 bytes")
        provider = str(doc.get("from", "")).rstrip("/")
        with self._msg_lock:
            if self.mempool_gossip.on_seen(h, provider) and provider:
                self._pending_wants.append((h, provider))
        telemetry.incr("reactor.gossip.seen_tx")

    def serve_want_tx(self, h: bytes, to_peer: str = "") -> bytes | None:
        """Inbound WantTx pull: deliver the tx bytes from the pool."""
        with self._msg_lock:
            return self.mempool_gossip.serve_want(h, to_peer)

    def on_tx(self, doc: dict) -> None:
        """Direct Tx push (legacy flood delivery, still accepted): queue
        for the reactor loop — like every gossip intake, this handler must
        not touch the writer lock (a delivery during a slow apply() would
        pile up blocked handler threads)."""
        import base64

        raw = base64.b64decode(doc["tx"])
        from celestia_app_tpu.mempool.pool import tx_hash

        h = tx_hash(raw)
        with self._msg_lock:
            if not self.mempool_gossip.first_seen(h):
                return
            self.mempool_gossip.on_delivered(h, raw, "")
            self._pending_txs.append((raw, ""))

    def _pull_tx(self, h: bytes, provider: str) -> bytes | None:
        """WantTx: pull tx content from an announcer; on failure fall
        through that hash's remaining candidate providers."""
        import base64

        url = provider
        while url:
            try:
                doc = self.net.get(url, f"/gossip/want_tx?hash={h.hex()}")
                tx_b64 = doc.get("tx")
                if tx_b64:
                    raw = base64.b64decode(tx_b64)
                    with self._msg_lock:
                        self.mempool_gossip.on_delivered(h, raw, url)
                    return raw
            except (OSError, ValueError):
                pass
            with self._msg_lock:
                url = self.mempool_gossip.pull_failed(h)
        return None

    def _admit_pending_txs(self) -> None:
        """The mempool-reactor loop half: drain queued WantTx pulls and
        direct deliveries, admit through the ONE CAT admission path —
        two-phase: the whole drained queue pays a single stateless
        signature-prevalidation dispatch (admission plane phase 1,
        OUTSIDE the service lock so a first-batch jit compile cannot
        stall the consensus loop) before the per-tx stateful CheckTx —
        and re-announce admitted txs to peers not known to have them."""
        with self._msg_lock:
            wants, self._pending_wants = self._pending_wants, []
            pending, self._pending_txs = self._pending_txs, []
        for h, provider in wants:
            raw = self._pull_tx(h, provider)
            if raw is not None:
                pending.append((raw, provider))
        if not pending:
            return
        from celestia_app_tpu.mempool.pool import tx_hash

        raws = [raw for raw, _src in pending]
        self.vnode.prevalidate_txs(raws)
        with self.service_lock:
            results = [self.vnode.add_tx(raw) for raw in raws]
        for (raw, _src), res in zip(pending, results):
            if res.code == 0:
                # announce UNCONDITIONALLY (not via gossip_tx's dedup
                # gate): a direct-push delivery already consumed
                # first_seen in on_tx, but its admission still has to be
                # announced to peers the pusher may not reach
                self._announce_tx(tx_hash(raw))
            else:
                # mark processed so peers re-announcing a tx we refuse
                # cannot make us re-pull it forever
                with self._msg_lock:
                    self.mempool_gossip.first_seen(tx_hash(raw))

    def _note_height(self, height: int, peer: str = "") -> None:
        """Track evidence that the network is ahead of us. The first-seen
        timestamp is PRESERVED while we stay behind — resetting it on
        every height advance would starve the sync_grace gate exactly
        when peers commit faster than the grace window (the case where
        catch-up matters most)."""
        if height > self.vnode.app.height + 1:
            with self._msg_lock:
                if self._ahead is None:
                    self._ahead = (height, peer, self.clock.monotonic())
                elif self._ahead[0] < height:
                    self._ahead = (height, peer or self._ahead[1],
                                   self._ahead[2])

    # -- helpers ---------------------------------------------------------

    def _powers(self) -> dict[bytes, int]:
        app = self.vnode.app
        ctx = Context(app.store, InfiniteGasMeter(), app.height, 0,
                      app.chain_id, app.app_version)
        return dict(app.staking.validators(ctx))

    def _refresh_valset(self) -> None:
        """Recompute rotation + vote-verification keys from state (call
        under service_lock). The set changes only at commits, so gossip
        handlers read the cached copies lock-free; they are an intake
        filter at worst one height stale — the authoritative checks
        (_proposal_acceptable, certificate verification) always read
        live state under the lock."""
        pubkeys = self.vnode.known_pubkeys()
        powers = self._powers()
        rotation = sorted(
            op for op in powers if op in pubkeys
        )
        if rotation:
            self.rotation = rotation
        self._pubkey_cache = pubkeys

    def proposer_for(self, height: int, round_: int) -> bytes:
        return self.rotation[(height + round_) % len(self.rotation)]

    def _timeout(self, phase: str, force_cold: bool = False) -> float:
        """Phase timeout with per-failed-round escalation. Cold values
        apply until this node's first committed height (jit compile paid
        once); warm values — capped BY the cold ones, so a fast test
        config is never slowed down — apply after (VERDICT r4 weak #6:
        tighten toward the reference's 10/11 s mainnet shape).
        `force_cold` keeps the cold value for one specific wait: the
        propose phase passes it for a never-seen proposer, whose FIRST
        proposal may be paying ITS cold compile however warm we are."""
        base = getattr(self.cfg, f"timeout_{phase}")
        if not force_cold and self.vnode.app.height >= 1:
            base = min(base, getattr(self.cfg, f"warm_{phase}"))
        return base + self.round * self.cfg.timeout_delta

    # NOTE: the per-block BlockSummary row is written by App.commit itself
    # (chain/app.py — one schema for every consensus mode); the reactor
    # only adds the RoundState rows below.

    def _trace_round(self, height: int, round_: int, step: str,
                     t0: float) -> None:
        """RoundState trace row (the celestia-core pkg/trace columnar
        table, SURVEY §5.1) into this validator's own trace plane —
        served at /trace/round_state by the node HTTP service."""
        try:
            self.vnode.app.traces.write(
                "round_state", height=height, round=round_, step=step,
                elapsed_ms=round((self.clock.monotonic() - t0) * 1e3, 3),
            )
        except Exception:
            # observability must never kill consensus — but not silently
            telemetry.incr("obs.trace_write_errors")

    def _wait(self, deadline: float, check):
        """Poll `check` (under _msg_lock) until non-None or deadline.
        The poll pause is the clock's INTERRUPTIBLE wait-with-wakeup —
        stop() wakes it immediately instead of losing up to a full poll
        interval per fixed sleep, and a VirtualClock resolves it against
        simulated time so a scheduler can preempt an idle node."""
        while not self._stop.is_set():
            with self._msg_lock:
                got = check()
            if got is not None:
                return got
            if self.clock.monotonic() >= deadline:
                return None
            self.clock.wait(self._stop, self.cfg.poll)
        return None

    def _prune(self, floor_height: int) -> None:
        with self._msg_lock:
            self._proposals = {
                k: v for k, v in self._proposals.items()
                if k[0] >= floor_height
            }
            self._votes = {
                k: v for k, v in self._votes.items() if k[0] >= floor_height
            }
            self._vote_pool = [
                v for v in self._vote_pool
                if v.height > floor_height - 10
            ]
            for h in [h for h in self._recent
                      if h < floor_height - self.cfg.recent_commits]:
                del self._recent[h]

    # -- proposal validity ----------------------------------------------

    def _proposal_acceptable(self, prop: c.Proposal, height: int,
                             known: dict[bytes, bytes] | None = None) -> bool:
        """Stateful checks beyond the signature (which on_proposal did):
        the block chains from OUR committed tip, the embedded last-commit
        certificate is real for height-1 (the absences every node will
        apply are derived from it, so a proposer cannot smuggle a
        thin/padded cert past the network), and every evidence item
        actually proves a double-sign — apply() slashes whoever the
        evidence names, so unverified evidence would let a byzantine
        proposer tombstone honest validators."""
        app = self.vnode.app
        if prop.height != height or prop.block.header.height != height:
            return False
        # the envelope must come from the rotation proposer for its
        # (height, round): without this, any registered validator could
        # re-wrap a legitimately certified block in its OWN envelope with
        # different last_cert/evidence via commit gossip, and nodes
        # applying different envelopes would diverge on absence/slash sets
        if prop.proposer != self.proposer_for(prop.height, prop.round):
            return False
        if prop.block.header.last_block_hash != app.last_block_hash:
            return False
        if len(prop.evidence) > len(self.rotation):
            return False  # at most one double-sign per validator
        if known is None:
            known = self.vnode.known_pubkeys()
        accused: set[bytes] = set()
        for ev in prop.evidence:
            pub = known.get(ev.vote_a.validator)
            if pub is None or not ev.verify(app.chain_id, pub):
                return False
            if not 0 < ev.height <= height:
                return False
            if ev.vote_a.validator in accused:
                return False  # duplicates would double-count nothing, but
            accused.add(ev.vote_a.validator)  # reject sloppy proposals
        if height == 1:
            return prop.last_cert is None
        lc = prop.last_cert
        if lc is None or lc.height != height - 1:
            return False
        if lc.block_hash != app.last_block_hash:
            return False
        # verify against the powers that were in force when height-1 was
        # certified (snapshotted just before we applied it): the current
        # set may already reflect a slash that block itself carried. A
        # node without the snapshot (WAL replay / state sync) falls back
        # to current powers — best effort, same as its cert verification.
        if (self._last_powers is not None
                and self._last_powers[0] == height - 1):
            powers = self._last_powers[1]
        else:
            powers = self._powers()
        return lc.verify(app.chain_id, known,
                         sum(powers.values()), powers)

    # -- the state machine ----------------------------------------------

    def _run(self) -> None:
        backoff = 0.2
        while not self._stop.is_set():
            try:
                committed = self._step_traced()
            except Exception as e:  # keep the reactor alive — but COUNTED
                # (reactor.loop_errors) and with escalating backoff, not
                # the old fixed-0.2s hot loop that could spin a wedged
                # node at 5 errors/second forever. Both this backoff and
                # the inter-height pause below are the clock's
                # interruptible wait: stop() no longer blocks behind a
                # sleeping loop (the old fixed time.sleep could hold
                # stop() for a full interval), and a VirtualClock lets
                # the sim scheduler preempt an idle node instead of
                # burning virtual-time steps.
                self.loop_errors += 1
                telemetry.incr("reactor.loop_errors")
                log.error("round error", node=self.vnode.name, err=e)
                committed = False
                self.clock.wait(self._stop, backoff)
                backoff = min(backoff * 2, 5.0)
            else:
                backoff = 0.2
            if committed:
                self.round = 0
                self.clock.wait(self._stop, self.cfg.block_interval)

    def _apply_pending_commit(self) -> bool:
        """Adopt a gossiped commit for our next height, if one is queued.
        Verification order: cert against our own trust roots, proposal
        signature + last-cert, then ProcessProposal — a certified block
        that fails local validity means >1/3 byzantine power or a bug;
        refuse and say so rather than follow the herd."""
        with self._msg_lock:
            pending, self._pending_commits = self._pending_commits, []
        applied = False
        for doc in pending:
            try:
                prop = c.proposal_from_json(doc["proposal"])
                cert = c.cert_from_json(doc["cert"])
            except (KeyError, ValueError, TypeError):
                continue
            with self.service_lock:
                app = self.vnode.app
                height = app.height + 1
                if cert.height != height:
                    continue
                if cert.block_hash != prop.block.header.hash():
                    continue
                known = self.vnode.known_pubkeys()  # ONE staking scan
                pub = known.get(prop.proposer)
                if pub is None or not prop.verify(app.chain_id, pub):
                    continue
                if not self._proposal_acceptable(prop, height, known=known):
                    continue
                if not self.vnode.verify_certificate(cert, pubkeys=known):
                    continue
                if not app.process_proposal(prop.block):
                    log.error(
                        "REFUSING certified block: local validation "
                        "failed (>1/3 byzantine or bug)",
                        node=self.vnode.name, height=height,
                    )
                    continue
                self._last_powers = (height, self._powers())
                h = self.vnode.apply(prop.block, cert,
                                     evidence=prop.evidence,
                                     absent_cert=prop.last_cert)
                self.vnode.clear_lock()
                self._refresh_valset()
                self.app_hashes[height] = h.hex()
                self._seen_proposers.add(prop.proposer)
                telemetry.incr("reactor.commits_adopted")
            # persist OUTSIDE the writer lock (as the self-commit path
            # does): a blocksync batch is one fsync per height, and the
            # lock must not serialize HTTP handlers against disk flushes
            self._remember_commit(doc, height)
            applied = True
        return applied

    def _remember_commit(self, doc: dict, height: int) -> None:
        import base64
        import hashlib

        punished = {
            bytes.fromhex(v["validator"])
            for e in doc.get("proposal", {}).get("evidence", [])
            for v in e.get("votes", [])
        }
        # committed txs left the pool in apply(); drop their want/have
        # tracking too, so gossip state follows pool membership
        committed_hashes = [
            hashlib.sha256(base64.b64decode(t)).digest()
            for t in doc.get("proposal", {}).get("block", {}).get("txs", [])
        ]
        with self._msg_lock:
            self.mempool_gossip.forget(committed_hashes)
            self._recent[height] = doc
            # clear the behind-marker only once this commit actually
            # reaches it — clearing unconditionally would abort a deep
            # blocksync after its first batch
            if self._ahead is not None and self._ahead[0] <= height + 1:
                self._ahead = None
            if punished:
                # x/evidence tombstones are idempotent, but re-proposing
                # settled evidence forever would bloat every proposal
                self._vote_pool = [
                    v for v in self._vote_pool if v.validator not in punished
                ]
        self._persist_commit_record(doc, height)
        self._maybe_snapshot(height)

    def _maybe_snapshot(self, height: int) -> None:
        """Interval state-sync snapshots (the serving half of the sync
        plane): called on BOTH commit paths after the commit record is
        durable, always OUTSIDE the writer lock — only the state capture
        inside sync.maybe_snapshot takes it, briefly."""
        from celestia_app_tpu.chain import sync as sync_mod

        sync_mod.maybe_snapshot(
            self.vnode.app, self.service_lock, self.snapshot_store,
            self.cfg.snapshot_interval, self.cfg.snapshot_keep, height,
        )

    # -- durable commit records (the block store blocksync reads) --------

    def _commits_dir(self) -> str | None:
        if self.vnode.wal_dir is None:
            return None
        import os

        d = os.path.join(os.path.dirname(self.vnode.wal_dir), "commits")
        os.makedirs(d, exist_ok=True)
        return d

    def _persist_commit_record(self, doc: dict, height: int) -> None:
        """The full gossiped commit doc (signed proposal envelope +
        certificate) hits disk per height — exactly what a laggard needs
        to replay the height through the SAME verification path live
        gossip uses (_apply_pending_commit). The reference keeps blocks +
        commits in the block store for blocksync the same way. Durable
        (fsync-before-replace, like every per-height artifact) and
        bounded: records older than cfg.commit_records_keep are pruned
        (amortized) — a laggard farther back than every peer's window
        state-syncs instead."""
        d = self._commits_dir()
        if d is None:
            return
        import os

        path = os.path.join(d, f"{height:020d}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        if height % 256 == 0:
            self._prune_commit_records(d, height)

    def _prune_commit_records(self, d: str, height: int) -> None:
        import os

        floor = height - self.cfg.commit_records_keep
        if floor <= 0:
            return
        for name in os.listdir(d):
            if not name.endswith(".json"):
                continue
            try:
                if int(name.split(".")[0]) < floor:
                    os.unlink(os.path.join(d, name))
            except (ValueError, OSError):
                continue

    def _drop_records_above(self, height: int) -> None:
        """Post-rollback hygiene at startup: never serve commit records
        for heights above our own durable state — after a rollback they
        describe a timeline this node can no longer vouch for."""
        d = self._commits_dir()
        if d is None:
            return
        import os

        for name in os.listdir(d):
            if not name.endswith(".json"):
                continue
            try:
                if int(name.split(".")[0]) > height:
                    os.unlink(os.path.join(d, name))
            except (ValueError, OSError):
                continue

    def _load_commit_record(self, height: int) -> dict | None:
        d = self._commits_dir()
        if d is None:
            return None
        import os

        path = os.path.join(d, f"{height:020d}.json")
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _maybe_catch_up(self) -> bool:
        """If peers are persistently ahead, replay their served commit
        records with full verification (blocksync), state-syncing only
        when the gap exceeds cfg.statesync_gap or no peer can serve the
        needed records. Each replayed height goes through
        _apply_pending_commit — proposal signature, certificate against
        THIS node's then-current valset (its own staking state at
        height-1), evidence, ProcessProposal — so a tampered served
        record cannot advance the chain. Replay is pipelined: records
        arrive in blocksync_batch windows and the next window is
        prefetched while this one verifies (_blocksync_step)."""
        with self._msg_lock:
            ahead = self._ahead
        if ahead is None:
            return False
        target, peer, since = ahead
        if self.clock.monotonic() - since < self.cfg.sync_grace:
            return False
        progressed = False
        with self.service_lock:
            gap = target - (self.vnode.app.height + 1)
        if gap > self.cfg.statesync_gap and not self._statesync_tried:
            # a huge gap snapshots first — but ONCE per catch-up episode
            # (the flag resets when we catch up; keying on the moving
            # target would re-fire per batch on a live chain): a dead
            # snapshot endpoint must not tax every replay batch with its
            # timeout
            self._statesync_tried = True
            if self._state_sync(peer):
                progressed = True
        # verified windowed replay (bounded per reactor step; the _ahead
        # marker persists until fully caught up, so the next step
        # continues the sync with the prefetched window)
        if self._blocksync_step(target, peer):
            progressed = True
        with self.service_lock:
            still_behind = self.vnode.app.height + 1 < target
        if not still_behind:
            with self._msg_lock:
                if self._ahead is not None and self._ahead[0] <= target:
                    self._ahead = None  # caught up; stop re-checking
            self._statesync_tried = False  # episode over
            return progressed
        if not progressed:
            # no peer could serve an applicable record (windows pruned
            # past the gap): verified state sync is the only path left
            if self._state_sync(peer):
                progressed = True
                with self._msg_lock:
                    self._ahead = None
                self._statesync_tried = False
        return progressed

    # -- pipelined blocksync (the sync plane's replay half) ---------------

    def _blocksync_step(self, target: int, peer: str) -> bool:
        """Replay up to one blocksync_batch window of heights. The window
        is taken from the prefetch slot when the previous step armed it
        (so its fetch overlapped that step's verification), else fetched
        synchronously via GET /gossip/commits; before applying, the NEXT
        window's fetch is kicked off in the background — verification,
        not the round-trip, is the loop's critical path. Heights the
        batch path could not cover (no range-serving peer, a bad record
        mid-window) fall back to the per-height _replay_height pull,
        which tries every peer."""
        with self.service_lock:
            need = self.vnode.app.height + 1
        if need > target:
            return False
        progressed = False
        applied = 0
        docs: list[dict] = []
        if self.cfg.blocksync_pipeline:
            got = self._take_prefetch(need)
            docs = got if got is not None \
                else self._fetch_commit_batch(need, target, peer)
        if docs:
            # overlap: the next window downloads while THIS one verifies
            self._start_prefetch(need + len(docs), target, peer)
            for doc in docs:
                self.on_commit(doc)
                if not self._apply_pending_commit():
                    break
                applied += 1
                progressed = True
        if docs and applied == len(docs):
            return progressed  # full window applied; next step continues
        # per-height verified pull for the rest of this step's window
        for _ in range(self.cfg.blocksync_batch - applied):
            with self.service_lock:
                need = self.vnode.app.height + 1
            if need > target:
                break
            if not self._replay_height(need, prefer=peer):
                break
            progressed = True
        return progressed

    def _fetch_commit_batch(self, lo: int, target: int,
                            prefer: str) -> list[dict]:
        """One range fetch: consecutive records from `lo`, at most one
        blocksync_batch window, from the first peer that serves a
        non-empty consistent prefix. Pre-sync-plane peers 404 the route
        — they are skipped silently (the per-height path covers them);
        transport failures are counted + logged."""
        import urllib.error

        hi = min(target, lo + self.cfg.blocksync_batch - 1)
        for u in self._peer_order(prefer):
            if not self.net.available(u):
                continue  # breaker open: already recorded, skip
            try:
                doc = self.net.get(
                    u, f"/gossip/commits?from={lo}&to={hi}"
                )
            except urllib.error.HTTPError:
                continue  # old peer without the range route
            except (OSError, ValueError) as e:
                self._count_fetch_error(u, e)
                continue
            got = doc.get("commits") if isinstance(doc, dict) else None
            out: list[dict] = []
            for i, d in enumerate(got or []):
                try:
                    if int(d["cert"]["height"]) != lo + i:
                        break  # non-consecutive: keep the good prefix
                except (KeyError, TypeError, ValueError):
                    break
                out.append(d)
            if out:
                return out
        return []

    def _take_prefetch(self, lo: int) -> list[dict] | None:
        """Claim the prefetched window if it starts exactly at `lo`
        (waiting briefly for an in-flight fetch); a stale window — the
        chain moved differently than predicted — is discarded."""
        with self._prefetch_lock:
            th = self._prefetch_thread
        if th is not None:
            th.join(timeout=self.cfg.gossip_timeout + 1.0)
            if th.is_alive():
                return None  # still downloading: don't stall the step
        with self._prefetch_lock:
            got, self._prefetched = self._prefetched, None
            self._prefetch_thread = None
        if got is None or got[0] != lo:
            return None
        return got[1]

    def _start_prefetch(self, lo: int, target: int, prefer: str) -> None:
        """Arm the one-slot prefetch window for [lo, lo+batch) on a
        background thread — the pipelining half: this download runs
        while the caller verifies the window it just took."""
        if lo > target or not self.cfg.blocksync_pipeline:
            return
        with self._prefetch_lock:
            if self._prefetch_thread is not None:
                return  # one in-flight prefetch at a time

        def work() -> None:
            docs = self._fetch_commit_batch(lo, target, prefer)
            with self._prefetch_lock:
                self._prefetched = (lo, docs) if docs else None

        th = threading.Thread(target=work, daemon=True)
        with self._prefetch_lock:
            self._prefetch_thread = th
        th.start()

    def _replay_height(self, need: int, prefer: str) -> bool:
        """Blocksync one height: try EVERY peer's served record until one
        passes the full verification in _apply_pending_commit — a single
        peer serving a corrupt/tampered record must not defeat the sync
        while honest peers hold a good one."""
        with obs.span(
            "blocksync.pull", traces=self.vnode.app.traces,
            trace_id=obs.trace_id_for(self.vnode.app.chain_id, need),
            height=need, node=self.vnode.name,
        ) as sp:
            for u in self._peer_order(prefer):
                if not self.net.available(u):
                    continue  # breaker open: already recorded, skip
                doc = self._fetch_record_from(u, need)
                if doc is None:
                    continue
                self.on_commit(doc)
                if self._apply_pending_commit():
                    sp.set(peer=u)
                    return True
            sp.set(error="no applicable record")
            return False

    def _peer_order(self, prefer: str) -> list[str]:
        return ([prefer] if prefer else []) + [
            u for u in self.peers if u != prefer
        ]

    def _probe_peer_heights(self) -> None:
        """Probe each peer's height via the lightweight GET
        /consensus/height (one integer — pulling the full status document
        with its telemetry/mempool/net blocks every step was pure waste);
        peers predating the route fall back to /consensus/status. Feeds
        the same catch-up path inbound gossip does."""
        import urllib.error

        for u in self.peers:
            try:
                try:
                    st = self.net.get(u, "/consensus/height")
                except urllib.error.HTTPError:
                    st = self.net.get(u, "/consensus/status")
                self._note_height(int(st["height"]) + 1, u)
            except (OSError, ValueError, KeyError, TypeError):
                continue

    def _count_fetch_error(self, url: str, err: Exception) -> None:
        """Blocksync fetch failures are counted + logged (never silently
        folded into a False return): a dead record peer must be visible
        in /metrics and /consensus/status. Cached breaker rejections are
        NOT re-counted — the transport recorded the underlying failure
        once, and a catch-up episode retries peers per height."""
        from celestia_app_tpu.net.transport import BreakerOpen

        if isinstance(err, BreakerOpen):
            return
        self.blocksync_fetch_errors += 1
        telemetry.incr("reactor.blocksync_fetch_errors")
        log.warning("blocksync fetch failed", node=self.vnode.name,
                    peer=url, err=err)

    def _fetch_record_from(self, url: str, height: int) -> dict | None:
        try:
            doc = self.net.get(url, f"/gossip/commit_at?height={height}")
            return doc or None
        except (OSError, ValueError) as e:
            self._count_fetch_error(url, e)
            return None

    # -- state sync (the joining half of the sync plane) -----------------

    def _statesync_workdir(self) -> str | None:
        import os

        from celestia_app_tpu.chain import sync as sync_mod

        home = sync_mod.home_for(self.vnode)
        if home is None:
            return None
        return os.path.join(home, sync_mod.RESTORE_DIRNAME)

    def _count_statesync_error(self, err: Exception) -> None:
        self.statesync_errors += 1
        telemetry.incr("reactor.statesync_errors")
        log.warning("state sync failed", node=self.vnode.name, err=err)

    def _state_sync(self, prefer: str) -> bool:
        """Chunked, parallel, resumable state sync across ALL healthy
        peers (chain/sync.StateSyncClient): discover the newest served
        manifest, pull chunks concurrently with per-chunk verification
        and durable resume, then adopt under the writer lock through the
        unchanged app-hash-anchored state_sync_bootstrap. Peers without
        the /sync/* routes fall back to the legacy one-shot pull."""
        import tempfile

        from celestia_app_tpu.chain import sync as sync_mod

        workdir = self._statesync_workdir()
        ephemeral = workdir is None
        if ephemeral:  # in-memory node: no resume across restarts anyway
            workdir = tempfile.mkdtemp(prefix="statesync-")
        with self.service_lock:
            floor = self.vnode.app.height
        client = sync_mod.StateSyncClient(
            self._peer_order(prefer), workdir, net=self.net,
            workers=self.cfg.statesync_workers, min_height=floor,
            name=self.vnode.name,
            da_scheme=sync_mod.scheme_of(self.vnode),
        )
        try:
            manifest, chunks = client.fetch()
        except sync_mod.StateSyncUnavailable as e:
            # nothing chunked to join from: try the legacy one-shot
            # endpoint peer-by-peer (pre-sync-plane peers serve it)
            log.info("chunked state sync unavailable",
                     node=self.vnode.name, err=e)
            for u in self._peer_order(prefer):
                if self._state_sync_from(u):
                    return True
            return False
        except (OSError, ValueError) as e:
            self._count_statesync_error(e)
            return False
        finally:
            if ephemeral:
                import shutil as shutil_mod

                shutil_mod.rmtree(workdir, ignore_errors=True)
        try:
            with self.service_lock:
                # re-check under the lock: commits may have advanced the
                # chain past the manifest while chunks were downloading —
                # adoption must never rewind the node
                if int(manifest["height"]) <= self.vnode.app.height:
                    raise ValueError(
                        f"snapshot at {manifest['height']} no longer "
                        f"ahead of height {self.vnode.app.height}"
                    )
                c.state_sync_bootstrap(self.vnode, manifest, chunks)
                self._refresh_valset()  # synced state may carry new validators
        except (ValueError, KeyError) as e:
            # adoption failed (e.g. the manifest's app_hash lied about
            # the reassembled store): the restore material is worthless —
            # REMOVE it, or discover()'s in-progress preference would
            # latch onto the same poisoned manifest on every retry
            client.cleanup()
            self._count_statesync_error(e)
            return False
        client.cleanup()
        telemetry.incr("reactor.statesync_joins")
        log.info("state sync adopted snapshot", node=self.vnode.name,
                 height=manifest["height"],
                 fetched=client.stats["fetched"],
                 reused=client.stats["reused"])
        return True

    def _state_sync_from(self, url: str) -> bool:
        """Legacy one-shot pull (GET /consensus/snapshot, the pre-sync-
        plane protocol) — kept as the fallback for peers that serve no
        chunked snapshots; failures are counted, not swallowed. Our
        height rides the ?min_height= query so a peer whose newest disk
        snapshot is behind us serves a capture instead (a pre-query
        server 404s the parameterized path; retry bare)."""
        import base64
        import urllib.error

        try:
            floor = self.vnode.app.height
            try:
                doc = self.net.get(
                    url, f"/consensus/snapshot?min_height={floor}",
                    timeout=30,
                )
            except urllib.error.HTTPError:
                doc = self.net.get(url, "/consensus/snapshot",
                                   timeout=30)
            chunks = [base64.b64decode(ch) for ch in doc["chunks"]]
            with self.service_lock:
                # the legacy endpoint now serves DISK snapshots, which
                # can be OLDER than this node's tip (the capture-on-
                # request original was always the peer's current height):
                # adopting one would REWIND the chain. Refuse stale.
                if int(doc["manifest"]["height"]) <= self.vnode.app.height:
                    raise ValueError(
                        f"peer snapshot at {doc['manifest']['height']} "
                        f"is not ahead of height {self.vnode.app.height}"
                    )
                c.state_sync_bootstrap(self.vnode, doc["manifest"], chunks)
                self._refresh_valset()  # the synced state may carry new validators
            return True
        except (OSError, ValueError, KeyError, TypeError) as e:
            self._count_statesync_error(e)
            return False

    def _step_traced(self) -> bool:
        """One reactor step under a per-round span: the root every
        gossip.send / wal.append / apply child of this round hangs off
        (trace id = the height's deterministic id)."""
        height = self.vnode.app.height + 1  # label-only read; no lock
        with obs.span(
            "reactor.round", traces=self.vnode.app.traces,
            trace_id=obs.trace_id_for(self.vnode.app.chain_id, height),
            height=height, round=self.round, node=self.vnode.name,
        ) as sp:
            committed = self._step_height()
            sp.set(committed=committed)
            return committed

    def _step_height(self) -> bool:
        """One (height, round) attempt; True iff a block was committed."""
        self._admit_pending_txs()
        if self._apply_pending_commit():
            return True
        if self._maybe_catch_up():
            return True
        with self.service_lock:
            height = self.vnode.app.height + 1
            my_last_cert = self.vnode.certificates.get(height - 1)
        self.height_view = height
        r = self.round
        _t_round = self.clock.monotonic()

        # ---- propose ----
        self.step = "propose"
        i_am_proposer = self.proposer_for(height, r) == self.vnode.address
        # a proposer that lacks the height-1 cert (it state-synced into
        # this height) cannot author valid commit info; it stays silent
        # and the round rotates past it
        if i_am_proposer and (height == 1 or my_last_cert is not None):
            with self._msg_lock:
                pool = [list(self._vote_pool)]
            with self.service_lock:
                evidence = tuple(c.detect_equivocation(
                    self.vnode.app.chain_id, pool,
                    self.vnode.known_pubkeys(),
                ))
                block = self.vnode.propose(t=self.clock.now())
            digest = c.Proposal.commit_info_digest(my_last_cert, evidence)
            sig = self.vnode.priv.sign(c.Proposal.sign_bytes(
                self.vnode.app.chain_id, height, r, block.header.hash(),
                digest,
            ))
            prop = c.Proposal(height, r, block, self.vnode.address, sig,
                              my_last_cert, evidence)
            with self._msg_lock:
                self._proposals.setdefault((height, r), prop)
            self._gossip("/gossip/proposal", c.proposal_to_json(prop))

        # cold propose window for (a) a proposer we have never seen a
        # proposal from, or (b) one whose last expected proposal timed
        # out (one retry: a RESTARTED validator repays its jit compile
        # with peers still remembering it as seen) — either may be
        # compiling; a second consecutive timeout reads as dead and
        # rotation returns to warm windows
        expected = self.proposer_for(height, r)
        force_cold = (expected not in self._seen_proposers
                      or expected in self._cold_retry)
        deadline = self.clock.monotonic() + self._timeout(
            "propose", force_cold=force_cold
        )
        prop = self._wait(
            deadline, lambda: self._proposals.get((height, r))
        )
        if prop is None and expected != self.vnode.address:
            if expected in self._cold_retry:
                self._cold_retry.discard(expected)  # dead: warm windows
            elif expected in self._seen_proposers:
                self._cold_retry.add(expected)  # maybe restarted: 1 retry
        elif prop is not None:
            self._cold_retry.discard(expected)
        self._trace_round(height, r, "propose", _t_round)

        # ---- prevote ----
        self.step = "prevote"
        accept = False
        if prop is not None:
            with self.service_lock:
                accept = self._proposal_acceptable(prop, height)
        if accept:
            with self.service_lock:
                pv = self.vnode.prevote_on(prop.block, r)  # ProcessProposal
        else:
            with self.service_lock:
                pv = self.vnode._signed(height, None, "prevote", r)
        self.on_vote({"round": r, "vote": c.vote_to_json(pv)})
        self._gossip("/gossip/vote",
                     {"round": r, "vote": c.vote_to_json(pv)})

        with self.service_lock:
            powers = self._powers()
        total = sum(powers.values())

        def polka_check():
            pool = self._votes.get((height, r, "prevote"), {})
            by_hash: dict[bytes, int] = {}
            nil_power = 0
            for v in pool.values():
                p = powers.get(v.validator, 0)
                if v.block_hash is None:
                    nil_power += p
                else:
                    by_hash[v.block_hash] = by_hash.get(v.block_hash, 0) + p
            for bh, power in by_hash.items():
                if power * 3 > total * 2:
                    return bh
            if nil_power * 3 > total * 2:
                return b"nil"  # sentinel: round is dead, move on
            return None

        deadline = self.clock.monotonic() + self._timeout("prevote")
        polka = self._wait(deadline, polka_check)
        polka_hash = polka if isinstance(polka, bytes) and polka != b"nil" \
            else None
        self._trace_round(height, r, "prevote", _t_round)

        # ---- precommit ----
        self.step = "precommit"
        with self.service_lock:
            # Tendermint lock discipline with round-scoped votes
            # (ValidatorNode.lock_permits — one definition shared with
            # the orchestrated server): precommit the polka block when it
            # matches our lock, we are unlocked, or the polka is at a
            # LATER round than our lock (unlock-on-higher-polka — the
            # cross-round precommit is legal and signed with this round,
            # so it can never read as a double-sign). `accept` gates
            # validity: the signed envelope carries the commit info
            # apply() will consume, so a polka on a block whose envelope
            # WE could not validate gets nil.
            lock_ok = (polka_hash is not None
                       and self.vnode.lock_permits(polka_hash, r))
            if (polka_hash is not None and prop is not None
                    and prop.block.header.hash() == polka_hash
                    and accept and lock_ok):
                self.vnode.on_polka(prop.block, r)
                pc = self.vnode.precommit_on(prop.block, r)
            else:
                pc = self.vnode.precommit_on(None, r)
        self.on_vote({"round": r, "vote": c.vote_to_json(pc)})
        self._gossip("/gossip/vote",
                     {"round": r, "vote": c.vote_to_json(pc)})

        def quorum_check():
            pool = self._votes.get((height, r, "precommit"), {})
            votes = [
                v for v in pool.values() if v.block_hash == polka_hash
            ]
            power = sum(powers.get(v.validator, 0) for v in votes)
            if power * 3 > total * 2:
                return tuple(votes)
            return None

        if polka_hash is None:
            # a nil polka (or none at all) already proved this round dead:
            # no certificate we could act on can form, so don't dead-wait
            # the precommit window — any commit others reached arrives by
            # gossip and is adopted at the top of the next attempt
            cert_votes = None
        else:
            deadline = self.clock.monotonic() + self._timeout("precommit")
            cert_votes = self._wait(deadline, quorum_check)

        # a certificate is only actionable if WE hold the matching
        # proposal: an equivocating proposer could have sent us block A
        # while the majority polka'd block B — applying A under a cert
        # for B would fork this node's state. Without the block, let the
        # commit arrive by gossip instead.
        if (cert_votes is not None
                and (prop is None
                     or prop.block.header.hash() != polka_hash)):
            cert_votes = None

        if cert_votes is None:
            # commit may still arrive by gossip (others saw the quorum)
            if self._apply_pending_commit():
                return True
            # pull-based peer probe: a failed round can mean the network
            # moved on without us (our inbound gossip is not arriving —
            # e.g. we rejoined on a new address). Ask peers where they are
            # so _maybe_catch_up can pull the gap.
            self._probe_peer_heights()
            self.round = r + 1
            self.step = "round-failed"
            self._trace_round(height, r, "round-failed", _t_round)
            telemetry.incr("reactor.round_failures")
            self._prune(self.vnode.app.height + 1)
            return False

        # ---- commit ----
        self.step = "commit"
        cert = c.CommitCertificate(height, polka_hash, cert_votes, r)
        doc = {"proposal": c.proposal_to_json(prop),
               "cert": c.cert_to_json(cert)}
        with self.service_lock:
            if self.vnode.app.height >= height:
                return True  # a gossiped commit beat us to it
            self._last_powers = (height, self._powers())
            ah = self.vnode.apply(prop.block, cert, evidence=prop.evidence,
                                  absent_cert=prop.last_cert)
            self.vnode.clear_lock()
            self._refresh_valset()
            self.app_hashes[height] = ah.hex()
        self._trace_round(height, r, "commit", _t_round)
        telemetry.incr("reactor.commits")
        self._remember_commit(doc, height)
        self._gossip("/gossip/commit", doc)
        self._prune(height + 1)
        return True
