"""ABCI-style query routing, including the custom proof routes.

Reference parity: app/app.go:393-394 registers the custom query routes
``custom/txInclusionProof`` and ``custom/shareInclusionProof``
(pkg/proof/querier.go:20-67), which re-extend the square from the stored
block's txs and emit a ShareProof. This router does the same against the
ChainDB block store, using the batched device prover
(da/proof_device.BlockProver) with per-height caching, plus the standard
keeper queries (bank balance, auth account, gov proposal, staking
validator, blob params, signal tally).

All requests/responses are JSON dicts so the HTTP service layer
(service/server.py) and the CLI can route them verbatim.
"""

from __future__ import annotations

import base64

from celestia_app_tpu import appconsts, obs
from celestia_app_tpu.chain.state import Context, InfiniteGasMeter
from celestia_app_tpu.da import edscache as edscache_mod
from celestia_app_tpu.da import square as square_mod
from celestia_app_tpu.da.blob import is_blob_tx, unmarshal_blob_tx
from celestia_app_tpu.da.square import PfbEntry
from celestia_app_tpu.utils import telemetry


class QueryError(Exception):
    pass


def rebuild_square(app, height: int):
    """Reconstruct the square from the stored block (querier.go:88-116:
    proofs are derived from block data, not cached trees)."""
    if app.db is None:
        raise QueryError("no block store attached (need data_dir)")
    # load + parse + lay out again: what a read of a height nobody
    # handed over pays before it can look anything up
    with obs.span("query.rebuild_square", height=height):
        block, bound = app.db.load_block_and_bound(height)
        if bound is None:
            # a block stored before the bound was recorded with it: the
            # chain's bound as it stands now, which is the proposer's
            # unless governance moved gov_max_square_size since
            telemetry.incr("query.layout_bound_fallbacks")
            bound = app.max_effective_square_size(Context(
                app.store, InfiniteGasMeter(), app.height, 0,
                app.chain_id, app.app_version))
        normal, pfbs = [], []
        for raw in block.txs:
            if is_blob_tx(raw):
                btx = unmarshal_blob_tx(raw)
                pfbs.append(PfbEntry(btx.tx, btx.blobs))
            else:
                normal.append(raw)
        threshold = appconsts.subtree_root_threshold(
            block.header.app_version)
        # the bound the proposer laid the block out under, never the
        # versioned constant: a PFB's reserved share-index bytes depend on
        # it, and with them where every blob of the square starts
        square = square_mod.construct(normal, pfbs, bound, threshold)
    return block, square


def build_prover_entry(app, height: int):
    """(block, square, EdsCacheEntry) for a committed height — the
    extend-once read path shared by the query router and the DAS sample
    server (das/server.py). The square is reconstructed from the stored
    block's txs (cheap host work); the EDS/DAH/roots come from the app's
    content-addressed cache when any lifecycle phase already computed
    them, and from ONE engine-gated pipeline dispatch
    (da/edscache.compute_entry — device, or the bit-identical fast_host
    path for host-engine validators, which must not initialise an
    accelerator backend they do not own) otherwise."""
    block, square = rebuild_square(app, height)
    cache = getattr(app, "eds_cache", None)
    engine = app.engine
    codec = getattr(app, "codec", None)
    scheme = codec.name if codec is not None else "rs2d-nmt"
    if cache is not None:
        entry = cache.entry_for_square(square, engine, scheme)
    else:  # bare apps (fixtures) still get the one-shot pipeline
        entry = edscache_mod.compute_entry(square.ods, engine, scheme)
    if entry.data_root != block.header.data_hash:
        # a Byzantine (or corrupted-store) header can never be served
        # from the cache: the entry is a pure function of the ODS and the
        # header must match it — same check, cached or cold
        raise QueryError("recomputed data root mismatches stored header")
    return block, square, entry


def build_prover(app, height: int):
    """(block, square, BlockProver, data_root) for a committed height —
    the tuple-shaped wrapper over build_prover_entry the query routes
    consume; the prover builds at most once per entry (lazily, or ahead
    of time by the commit warmer)."""
    block, square, entry = build_prover_entry(app, height)
    if entry.scheme != "rs2d-nmt":
        # share/tx inclusion proofs are an NMT-range construction; other
        # codec-plane schemes serve their own sample proofs via /das/*
        raise QueryError(
            f"share proofs are not defined under DA scheme "
            f"{entry.scheme!r}")
    prover = entry.get_prover()
    return block, square, prover, entry.data_root


class QueryRouter:
    def __init__(self, app):
        self.app = app
        self._prover_cache: dict[int, tuple] = {}
        self._tx_hash_cache: dict[int, dict[str, int]] = {}
        self._cache_generation = getattr(app, "state_generation", 0)

    def _ctx(self) -> Context:
        return Context(
            self.app.store, InfiniteGasMeter(), self.app.height, 0.0,
            self.app.chain_id, self.app.app_version,
        )

    # -- proof plumbing --------------------------------------------------

    def _prover(self, height: int):
        # rollback guard: any load()/load_height() bumps the app's state
        # generation; cached provers from before then may describe a
        # replaced block
        if getattr(self.app, "state_generation", 0) != self._cache_generation:
            self._prover_cache.clear()
            self._cache_generation = self.app.state_generation
        if height in self._prover_cache:
            return self._prover_cache[height]
        entry = build_prover(self.app, height)
        self._prover_cache.clear()  # keep at most one height resident
        self._prover_cache[height] = entry
        return entry

    # -- routes ----------------------------------------------------------

    def query(self, path: str, data: dict) -> dict:
        out = self._route(path, data)
        # count only after routing succeeds: attacker-varied junk paths must
        # not grow the telemetry registry unboundedly
        telemetry.incr(f"query.{path.replace('/', '_')}")
        return out

    def _route(self, path: str, data: dict) -> dict:
        if path == "custom/txInclusionProof":
            return self._tx_inclusion(data)
        if path == "custom/shareInclusionProof":
            return self._share_inclusion(data)
        if path == "custom/namespaceData":
            return self._namespace_data(data)
        if path == "custom/dah":
            return self._dah(data)
        if path == "custom/sampleCell":
            return self._sample_cell(data)
        if path == "bank/balance":
            addr = bytes.fromhex(data["address"])
            return {"balance": self.app.bank.balance(self._ctx(), addr)}
        if path == "auth/account":
            acc = self.app.auth.account(self._ctx(), bytes.fromhex(data["address"]))
            return {"account": acc}
        if path == "gov/proposal":
            return {"proposal": self.app.gov.proposal(self._ctx(), int(data["id"]))}
        if path == "staking/validators":
            ctx = self._ctx()
            return {
                "validators": [
                    {"operator": op.hex(), "power": p}
                    for op, p in self.app.staking.validators(ctx)
                ]
            }
        if path == "blob/params":
            return {"params": self.app.blob.params(self._ctx())}
        if path == "minfee/params":
            return {
                "network_min_gas_price":
                    self.app.minfee.network_min_gas_price(self._ctx())
            }
        if path == "tx":
            return self._tx_by_hash(data)
        if path == "blobstream/attestation":
            from celestia_app_tpu.chain import blobstream as bs_mod

            att = self.app.blobstream.attestation_by_nonce(
                self._ctx(), int(data["nonce"])
            )
            if att is None:
                return {"attestation": None}
            return {"attestation": bs_mod._att_to_json(att)}
        if path == "signal/tally":
            # x/signal QueryVersionTally (x/signal/keeper.go): voting
            # power signalled for a version + the total, plus any
            # scheduled upgrade — what an operator watches pre-flip
            version = int(data["version"])
            ctx = self._ctx()
            power, total = self.app.signal.tally(ctx, version)
            return {
                "power": power,
                "total": total,
                "pending": self.app.signal.pending_upgrade(ctx),
            }
        if path == "blobstream/latest_nonce":
            return {
                "nonce": self.app.blobstream.latest_attestation_nonce(self._ctx())
            }
        if path == "status":
            return {
                "chain_id": self.app.chain_id,
                "height": self.app.height,
                "app_version": self.app.app_version,
                "last_app_hash": self.app.last_app_hash.hex(),
                "last_block_hash": self.app.last_block_hash.hex(),
                "telemetry": telemetry.snapshot(),
            }
        raise QueryError(f"unknown query path {path!r}")

    def _tx_by_hash(self, data: dict) -> dict:
        """Confirmation lookup: find a tx by the sha256 of its broadcast
        bytes (blocks store the same BlobTx-envelope bytes clients hash) —
        the reference's /tx RPC that TxClient.ConfirmTx polls,
        pkg/user/tx_client.go:412. Per-height hash sets are cached so a
        confirmation polling loop costs O(new heights), not a reload
        of the whole lookback window per poll."""
        import hashlib as _hashlib

        want = data["hash"].lower()
        if self.app.db is None:
            raise QueryError("no block store attached (need data_dir)")
        if getattr(self.app, "state_generation", 0) != self._cache_generation:
            self._prover_cache.clear()
            self._tx_hash_cache.clear()
            self._cache_generation = self.app.state_generation
        heights = self.app.db.block_heights()
        for h in reversed(heights[-int(data.get("lookback", 50)) :]):
            cached = self._tx_hash_cache.get(h)
            if cached is None:
                block = self.app.db.load_block(h)
                cached = {
                    _hashlib.sha256(raw).hexdigest(): i
                    for i, raw in enumerate(block.txs)
                }
                self._tx_hash_cache[h] = cached
            if want in cached:
                return {"found": True, "height": h, "index": cached[want]}
        return {"found": False}

    def _tx_inclusion(self, data: dict) -> dict:
        height = int(data["height"])
        tx_index = int(data["tx_index"])
        block, square, prover, root = self._prover(height)
        pf = prover.prove_tx(square, tx_index)
        return {"proof": _share_proof_json(pf), "data_root": root.hex()}

    def _share_inclusion(self, data: dict) -> dict:
        height = int(data["height"])
        start, end = int(data["start"]), int(data["end"])
        namespace = bytes.fromhex(data["namespace"])
        block, square, prover, root = self._prover(height)
        pf = prover.prove_shares(start, end, namespace)
        return {"proof": _share_proof_json(pf), "data_root": root.hex()}

    def prover_for(self, height: int):
        """Public accessor for the per-height device prover: (prover,
        data_root). The CLI's das command and tests use this instead of
        the private cache-entry tuple."""
        _block, _square, prover, root = self._prover(height)
        return prover, root

    def _dah(self, data: dict) -> dict:
        """A block's full DAH (row+col roots) — what a light node needs to
        verify samples; it binds to the header via dah.hash()==data_hash."""
        height = int(data["height"])
        block, square, prover, root = self._prover(height)
        return {
            "row_roots": [r.hex() for r in prover.dah.row_roots],
            "col_roots": [r.hex() for r in prover.dah.col_roots],
            "data_root": root.hex(),
        }

    def _sample_cell(self, data: dict) -> dict:
        """One extended-square cell + NMT proof (the DAS serving side)."""
        height = int(data["height"])
        row, col = int(data["row"]), int(data["col"])
        block, square, prover, root = self._prover(height)
        share, proof = prover.prove_cell(row, col)
        return {
            "share": base64.b64encode(share).decode(),
            "proof": {
                "start": proof.start,
                "end": proof.end,
                "total": proof.total,
                "nodes": [base64.b64encode(n).decode() for n in proof.nodes],
            },
        }

    def _namespace_data(self, data: dict) -> dict:
        """GetSharesByNamespace-style route: every share of a namespace in
        a block with a presence-and-completeness proof, or an absence
        witness (da/namespace_data.py)."""
        from celestia_app_tpu.da import namespace_data as nsd

        height = int(data["height"])
        namespace = bytes.fromhex(data["namespace"])
        block, square, prover, root = self._prover(height)
        nd = nsd.get_namespace_data(prover, namespace)
        return {
            "present": bool(nd.shares),
            "shares": [base64.b64encode(s).decode() for s in nd.shares],
            "proof": _share_proof_json(nd.proof) if nd.proof else None,
            "data_root": root.hex(),
        }


def _share_proof_json(pf, data=None) -> dict:
    """Serialize a ShareProof for transport; verifiable via
    proof.share_proof_from_json. ``data`` stands for the encoded share
    list where the caller has encoded ``pf.data`` already
    (das/blob_packs.namespace_member)."""
    return {
        "data": ([base64.b64encode(d).decode() for d in pf.data]
                 if data is None else data),
        "namespace": pf.namespace.hex(),
        "start_share": pf.start_share,
        "end_share": pf.end_share,
        "share_proofs": [
            {
                "start": sp.start,
                "end": sp.end,
                "total": sp.total,
                "nodes": [base64.b64encode(n).decode() for n in sp.nodes],
            }
            for sp in pf.share_proofs
        ],
        "row_proof": {
            "row_roots": [r.hex() for r in pf.row_proof.row_roots],
            "proofs": [
                {
                    "index": p.index,
                    "total": p.total,
                    "leaf_hash": base64.b64encode(p.leaf_hash).decode(),
                    "aunts": [base64.b64encode(a).decode() for a in p.aunts],
                }
                for p in pf.row_proof.proofs
            ],
            "start_row": pf.row_proof.start_row,
            "end_row": pf.row_proof.end_row,
        },
    }


def share_proof_from_json(doc: dict):
    """Rebuild a verifiable ShareProof from its JSON transport form."""
    from celestia_app_tpu.da.proof import RowProof, ShareProof
    from celestia_app_tpu.utils import merkle_host, nmt_host

    row_proof = RowProof(
        row_roots=[bytes.fromhex(r) for r in doc["row_proof"]["row_roots"]],
        proofs=[
            merkle_host.Proof(
                index=p["index"],
                total=p["total"],
                leaf_hash=base64.b64decode(p["leaf_hash"]),
                aunts=[base64.b64decode(a) for a in p["aunts"]],
            )
            for p in doc["row_proof"]["proofs"]
        ],
        start_row=doc["row_proof"]["start_row"],
        end_row=doc["row_proof"]["end_row"],
    )
    return ShareProof(
        data=[base64.b64decode(d) for d in doc["data"]],
        share_proofs=[
            nmt_host.NmtRangeProof(
                start=sp["start"],
                end=sp["end"],
                total=sp["total"],
                nodes=[base64.b64decode(n) for n in sp["nodes"]],
            )
            for sp in doc["share_proofs"]
        ],
        namespace=bytes.fromhex(doc["namespace"]),
        row_proof=row_proof,
        start_share=doc["start_share"],
        end_share=doc["end_share"],
    )
