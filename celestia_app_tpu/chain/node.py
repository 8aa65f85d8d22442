"""Single-process node: mempool + block production loop around the App.

Reference parity: test/util/testnode (an in-process chain producing blocks
against a real app via the local ABCI client, full_node.go:20-49) plus the
mempool behavior celestia tunes in app/default_overrides.go:258-284 (priority
mempool with per-tx TTL of 5 blocks, gas-price priority ordering).

The mempool itself is the content-addressable CAT pool
(celestia_app_tpu/mempool/pool.py) — hash-keyed dedup, byte+count caps with
lowest-priority eviction, TTL by height and wall-clock, and post-commit
recheck — shared with ValidatorNode and the autonomous reactor so all three
consumers have ONE admission path and ONE eviction policy. `self.mempool`
stays a list-shaped view for compatibility (tests, tools, status surfaces).
"""

from __future__ import annotations

import time as time_mod

from celestia_app_tpu import appconsts
from celestia_app_tpu.chain.app import App
from celestia_app_tpu.chain.block import Block, TxResult
from celestia_app_tpu.mempool.pool import (  # noqa: F401  (re-exports: the
    CATPool,      # historical import surface for these lived here)
    EntryView,
    PoolTx as MempoolTx,
    check_mempool_size,
    priority_order,
)

# heights of committed-tx lookups retained for GetTx/ConfirmTx; the
# reference's default lookback for confirmation polling is far shorter
COMMITTED_INDEX_WINDOW = 1000


def record_committed(index: dict, block: "Block", results) -> None:
    """THE committed-tx index recorder (tx-hash -> (height, result)), shared
    by Node and ValidatorNode so the gRPC GetTx/ConfirmTx contract stays
    single-sourced. Prunes entries older than COMMITTED_INDEX_WINDOW
    heights (amortized) so a long-lived validator process does not grow
    its index with the whole chain history."""
    import hashlib

    h = block.header.height
    for raw, res in zip(block.txs, results):
        index[hashlib.sha256(raw).digest()] = (h, res)
    if h % 50 == 0:
        floor = h - COMMITTED_INDEX_WINDOW
        if floor > 0:
            for key in [k for k, (hh, _r) in index.items() if hh <= floor]:
                del index[key]


class Node:
    def __init__(self, app: App,
                 mempool_ttl: int = appconsts.MEMPOOL_TX_TTL_BLOCKS,
                 mempool_max_txs: int = appconsts.MEMPOOL_MAX_TXS,
                 mempool_max_bytes: int = appconsts.MEMPOOL_MAX_POOL_BYTES,
                 mempool_ttl_seconds: float | None =
                 appconsts.MEMPOOL_TX_TTL_SECONDS):
        self.app = app
        self.pool = CATPool(
            max_pool_bytes=mempool_max_bytes,
            max_txs=mempool_max_txs,
            ttl_blocks=mempool_ttl,
            ttl_seconds=mempool_ttl_seconds,
        )
        self.mempool_ttl = mempool_ttl
        self.committed: dict[bytes, tuple[int, TxResult]] = {}  # tx hash -> (height, result)
        self.blocks: list[Block] = []
        # every process that runs a node samples its interpreter's
        # pressure (obs/gil.py; CELESTIA_OBS-gated): one sampler a
        # process, so none here beside a service's that started first
        from celestia_app_tpu.obs import gil

        if not gil.running():
            gil.start("node")

    # -- mempool -------------------------------------------------------

    @property
    def mempool(self) -> EntryView:
        """List-shaped view over the CAT pool (entries carry
        .raw/.gas_price/.height_added/.sender, the old MempoolTx shape)."""
        return EntryView(self.pool)

    @mempool.setter
    def mempool(self, items) -> None:
        """Compat for tests/tools that assign a replacement list; entries
        are re-admitted WITHOUT CheckTx (the caller already vouched)."""
        self.pool.clear()
        for it in items:
            raw = it.raw if hasattr(it, "raw") else it
            self.pool.add(raw, height=self.app.height)

    def broadcast_tx(self, raw: bytes) -> TxResult:
        """BroadcastMode_SYNC: CheckTx + CAT admission (size gate, hash
        dedup returning the original result, cap eviction) — the ONE
        admission path."""
        return self.pool.add(raw, height=self.app.height,
                             check_fn=self.app.check_tx)

    def broadcast_txs(self, raws) -> list[TxResult]:
        """Batched BroadcastMode_SYNC: one stateless prevalidation pass
        (admission plane phase 1 — a batched signature dispatch AND a
        batched blob-commitment dispatch), then the usual per-tx
        stateful CheckTx admission hitting the verified-sig and
        verified-commitment caches."""
        from celestia_app_tpu import obs
        from celestia_app_tpu.chain import admission

        with obs.span("node.broadcast_txs", traces=self.app.traces,
                      n_txs=len(raws)):
            return self.pool.add_batch(
                raws, height=self.app.height, check_fn=self.app.check_tx,
                prevalidate_fn=lambda rs: admission.prevalidate(
                    self.app, rs, check_state=True),
            )

    def _reap(self) -> list[bytes]:
        """Priority order: gas price desc, per-sender arrival order kept."""
        return self.pool.reap(self.app.height)

    # -- DAS serving (block plane) -------------------------------------

    def attach_das_core(self, core=None):
        """Create (or adopt) a DAS sample-serving core seeded by this
        node's commits: every committed height's EDS/DAH cache entry is
        handed over on the warmer's background thread with provers
        pre-built (da/edscache.py), so the first sample after a commit
        is pure index arithmetic. The canonical wiring for in-process
        embeddings (benches, tests, tools); the HTTP services register
        their own lock-guarded cores the same way."""
        if core is None:
            from celestia_app_tpu.das.server import SampleCore

            core = SampleCore(self.app)
        self.app.add_da_seed_listener(core.seed_cache_entry)
        return core

    # -- consensus loop ------------------------------------------------

    def produce_block(self, t: float | None = None) -> tuple[Block, list[TxResult]]:
        # the PROPOSER's clock is the protocol's source of header time
        # (same prerogative as App.prepare_proposal)
        t = t if t is not None else time_mod.time()  # lint: disable=det-wallclock
        # one root span for the whole round — prepare/process/finalize/
        # commit nest under it with the height's deterministic trace id
        from celestia_app_tpu import obs

        with obs.span(
            "block.produce", traces=self.app.traces,
            trace_id=obs.trace_id_for(self.app.chain_id,
                                      self.app.height + 1),
            height=self.app.height + 1,
        ):
            prop = self.app.prepare_proposal(self._reap(), t=t)
            if not self.app.process_proposal(prop.block):
                raise RuntimeError("node rejected its own proposal")
            results = self.app.finalize_block(prop.block)
            self.app.commit(prop.block)
            self.blocks.append(prop.block)

            with obs.span("pool.recheck"):
                self.pool.remove_committed(prop.block.txs)
                # post-commit recheck (RecheckTx): survivors re-run
                # CheckTx against the fresh check state; nonce-stale/
                # now-unfunded txs drop here instead of wasting the next
                # proposal's slot
                self.pool.recheck(self.app.check_tx)
        record_committed(self.committed, prop.block, results)
        return prop.block, results

    def confirm_tx(self, raw: bytes):
        """ConfirmTx: drive blocks until the tx commits (tx_client.go:412)."""
        import hashlib

        h = hashlib.sha256(raw).digest()
        for _ in range(self.mempool_ttl + 1):
            if h in self.committed:
                return self.committed[h]
            self.produce_block()
        raise TimeoutError("tx not committed within TTL")
