"""The admission plane: two-phase tx admission with batched sig verification.

The per-user hot path used to pay one scalar secp256k1 verification per
transaction per phase — CheckTx at the mempool door, the PrepareProposal
ante filter, ProcessProposal on every validator, FinalizeBlock delivery,
and again on blocksync/WAL replay. This module restructures that into the
ROADMAP's two-phase admit:

  phase 1 (stateless): a whole batch of pending signatures is verified in
      ONE vmapped device dispatch (`ops/secp256k1.verify_batch`), and
      every success is recorded in the App's `VerifiedSigCache` keyed by
      the exact (pubkey, signature, sign-doc) triple;
  phase 2 (stateful): the ante chain runs per tx as before — nonce, fee,
      gas, blob gates — but its signature step consults the cache first,
      so a tx admitted at CheckTx is NEVER re-verified at proposal,
      delivery, or replay time.

The cache is sound by construction: a key is inserted only after the
signature verified TRUE over exactly the bytes the ante would verify, so
a hit can only skip a verification that would have returned True with
identical inputs. Consensus results are bit-identical with the cache on,
off, hot, or cold — prevalidation is an optimization plane, never an
authority, and any failure inside it degrades to the scalar path (counted
in telemetry, `admission.prevalidate_errors`).

The traffic plane (ISSUE 15) extends phase 1 with the SAME pattern for
blob share commitments: the reference recomputes each blob's commitment
in both CheckTx and ProcessProposal (`ValidateBlobTx`), and this repo
used to pay a per-blob host-Python subtree-root MMR at CheckTx and then
recompute every one of them again in ProcessProposal's batch.
`prevalidate` now also computes ALL uncached pending blobs' commitments
in one `da/commitment_device` dispatch and fills the App's
`VerifiedCommitmentCache`; `blob_validation.validate_blob_tx` consults
it before paying a host recompute, so a commitment checked at CheckTx
is NEVER recomputed at PrepareProposal, ProcessProposal, FinalizeBlock,
or WAL replay. The cache maps blob content to its COMPUTED-TRUE
commitment — a hit can only skip a recompute that would have produced
the identical bytes, so a Byzantine tx whose claimed commitment
mismatches is rejected by the same byte-compare, warm cache or cold.

Telemetry (the counters the tier-1 no-re-verification test pins):
  admission.sig_cache_hits       ante skipped a verify via the cache
  admission.sig_scalar_verified  ante ran a scalar verify (cache miss)
  admission.batch_dispatches     device batch dispatches
  admission.batch_lanes          signatures sent through the device path
  admission.batch_padded_lanes   lanes the device ran, padding included
                                 (each dispatch adds its bucket)
  admission.batch_verified       lanes that verified and were cached
  admission.batch_rejected       lanes that failed batch verification
  admission.prevalidate_below_batch  batches too small for the device
  admission.prevalidate_host_engine  batches a host-engine app left to
                                 the scalar path (it never touches JAX)

Commitment counters (the tier-1 no-recompute test pins; FORMATS §20):
  commitment.cache_hits          a validation consumed a cached commitment
  commitment.recomputes          a validation paid a commitment compute
                                 (per blob; host path or a cold batch)
  commitment.batch_dispatches    prevalidation commitment batches
  commitment.batch_lanes         blobs computed by prevalidation batches
  commitment.batch_programs      device programs launched by commitment
                                 batches (da/commitment_device: one a batch)
  commitment.prevalidate_below_batch  commitment batches below the gate
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict

from celestia_app_tpu import obs
from celestia_app_tpu.utils import telemetry

# Below this many uncached signatures the device dispatch is not worth
# its padding (and, on first use in a process, its jit compile): the
# ante's scalar path fills the cache instead. Tests and the bench pin it
# via env to force either path.
MIN_DEVICE_BATCH = int(os.environ.get("CELESTIA_ADMISSION_MIN_BATCH", "16"))
SIG_CACHE_MAX = 65536


def sig_key(pubkey: bytes, signature: bytes, message: bytes) -> bytes:
    """The cache key: length-framed so no two distinct (pubkey, sig,
    sign-doc) triples can collide by concatenation ambiguity."""
    h = hashlib.sha256()
    for part in (pubkey, signature, message):
        h.update(len(part).to_bytes(4, "big"))
        h.update(part)
    return h.digest()


class VerifiedSigCache:
    """Bounded LRU set of signature triples that verified TRUE.

    Lives on the App (one per state machine); CheckTx, the proposal
    paths, and replay all share it. Entries are state-independent facts
    (pure curve math over fixed bytes), so the cache survives rollbacks
    and reloads untouched."""

    def __init__(self, maxsize: int = SIG_CACHE_MAX):
        self.maxsize = maxsize
        self._lock = threading.Lock()
        self._keys: OrderedDict[bytes, None] = OrderedDict()  # guarded-by: _lock
        # LRU churn evidence: soak verdicts assert the cap actually
        # cycled (per-instance, unlike the process-global telemetry)
        self.evictions = 0  # guarded-by: _lock

    key = staticmethod(sig_key)

    def __len__(self) -> int:
        with self._lock:
            return len(self._keys)

    def hit(self, key: bytes) -> bool:
        with self._lock:
            if key in self._keys:
                self._keys.move_to_end(key)
                telemetry.incr("admission.sig_cache_hits")
                return True
            return False

    def contains(self, key: bytes) -> bool:
        """Membership probe WITHOUT the hit counter or LRU refresh —
        prevalidation's dedup uses this so `admission.sig_cache_hits`
        keeps meaning "the ante skipped a verify"."""
        with self._lock:
            return key in self._keys

    def put(self, key: bytes) -> None:
        with self._lock:
            self._keys[key] = None
            self._keys.move_to_end(key)
            while len(self._keys) > self.maxsize:
                self._keys.popitem(last=False)
                self.evictions += 1
                telemetry.incr("admission.sig_cache_evictions")


COMMITMENT_CACHE_MAX = 16384


def commitment_key(namespace: bytes, share_version: int, data: bytes,
                   subtree_root_threshold: int) -> bytes:
    """The commitment-cache key: sha256 over the length-framed
    (namespace, share-version, blob bytes, threshold) tuple — exactly
    the inputs `da/commitment.create_commitment` hashes from, framed so
    no two distinct blobs can collide by concatenation ambiguity (two
    blobs sharing a byte prefix must never share a key). The integer
    fields encode as decimal bytes: total over ANY int, so an
    adversarial tx carrying an out-of-range share version (the wire
    varint is unbounded; Blob() does not validate on construction) can
    never make prevalidation's key pass raise and knock the whole
    window's honest blobs off the batch — it just gets a key, and the
    ante's own validation rejects the blob later."""
    h = hashlib.sha256()
    for part in (namespace, b"%d" % share_version, data,
                 b"%d" % subtree_root_threshold):
        h.update(len(part).to_bytes(4, "big"))
        h.update(part)
    return h.digest()


class VerifiedCommitmentCache:
    """Bounded LRU of blob-content keys -> their COMPUTED share
    commitment (32 bytes).

    Lives on the App beside the VerifiedSigCache; CheckTx, both proposal
    phases, and replay share it. Values are pure functions of the key
    (the MMR-of-NMT-subtree-roots over the blob's shares), so the cache
    survives rollbacks and reloads untouched, and a hit can only skip a
    recompute that would have produced identical bytes — the Byzantine
    mismatch case still rejects on the same byte-compare."""

    def __init__(self, maxsize: int = COMMITMENT_CACHE_MAX):
        self.maxsize = maxsize
        self._lock = threading.Lock()
        self._map: OrderedDict[bytes, bytes] = OrderedDict()  # guarded-by: _lock
        # LRU churn evidence for soak verdicts (see VerifiedSigCache)
        self.evictions = 0  # guarded-by: _lock

    key = staticmethod(commitment_key)

    def __len__(self) -> int:
        with self._lock:
            return len(self._map)

    def hit(self, key: bytes) -> bytes | None:
        """The cached commitment, counting the hit (a validation skipped
        a recompute), or None on a miss."""
        with self._lock:
            value = self._map.get(key)
            if value is not None:
                self._map.move_to_end(key)
                telemetry.incr("commitment.cache_hits")
            return value

    def contains(self, key: bytes) -> bool:
        """Membership probe WITHOUT the hit counter or LRU refresh —
        prevalidation's dedup uses this so `commitment.cache_hits`
        keeps meaning "a validation skipped a recompute"."""
        with self._lock:
            return key in self._map

    def put(self, key: bytes, commitment: bytes) -> None:
        with self._lock:
            self._map[key] = commitment
            self._map.move_to_end(key)
            while len(self._map) > self.maxsize:
                self._map.popitem(last=False)
                self.evictions += 1
                telemetry.incr("commitment.cache_evictions")


def status_block(app) -> dict:
    """The admission/traffic block both HTTP status surfaces serve
    (/status and /consensus/status, FORMATS §12.3/§20.3): the verified-
    sig and verified-commitment cache economics plus any co-resident
    txsim load's counters. Counters are process-wide (exactly what
    /metrics exposes); the cache sizes are this App's."""
    counters = telemetry.snapshot().get("counters", {})

    def g(name: str) -> int:
        return counters.get(name, 0)

    sig_cache = getattr(app, "sig_cache", None)
    commitment_cache = getattr(app, "commitment_cache", None)
    return {
        "sig_cache_hits": g("admission.sig_cache_hits"),
        "sig_scalar_verified": g("admission.sig_scalar_verified"),
        "batch_verified": g("admission.batch_verified"),
        "batch_rejected": g("admission.batch_rejected"),
        "sig_cache_size": len(sig_cache) if sig_cache is not None else 0,
        "commitment": {
            "cache_hits": g("commitment.cache_hits"),
            "recomputes": g("commitment.recomputes"),
            "batch_dispatches": g("commitment.batch_dispatches"),
            "batch_lanes": g("commitment.batch_lanes"),
            "cache_size": (len(commitment_cache)
                           if commitment_cache is not None else 0),
        },
        "txsim": {
            "submitted": g("txsim.submitted"),
            "accepted": g("txsim.accepted"),
            "confirmed": g("txsim.confirmed"),
            "rejected": g("txsim.rejected"),
            "resyncs": g("txsim.resyncs"),
            "errors": g("txsim.errors"),
        },
    }


# sentinel for a raw whose BlobTx envelope failed to parse: both phase-1
# halves skip it (the ante rejects it with its own error later)
_UNDECODABLE = object()


def _unmarshal_batch(raws) -> list:
    """One envelope parse per raw, shared by both phase-1 halves (the
    sig half and the commitment half must not each pay a full BlobTx
    decode over devnet-scale blobs). Entries: a BlobTx, None (a plain
    tx), or _UNDECODABLE."""
    from celestia_app_tpu.da import blob as blob_mod

    out = []
    for raw in raws:
        try:
            out.append(blob_mod.try_unmarshal_blob_tx(raw))
        except ValueError:
            out.append(_UNDECODABLE)
    return out


def extract_blob_items(raws, btxs=None):
    """Every blob of every decodable BlobTx in `raws`, in block order.
    Undecodable entries are skipped — the ante/validate path remains the
    authority and rejects them with its own error. `btxs` optionally
    supplies the pre-parsed envelopes (_unmarshal_batch)."""
    if btxs is None:
        btxs = _unmarshal_batch(raws)
    blobs = []
    for btx in btxs:
        if btx is not None and btx is not _UNDECODABLE:
            blobs.extend(btx.blobs)
    return blobs


def prevalidate_commitments(app, raws, btxs=None) -> int:
    """Phase 1, commitment half: compute the share commitments of every
    pending blob not already in the App's verified-commitment cache in
    ONE batched dispatch (da/commitment_device via
    blob_validation.batch_commitments — device-class engines write the
    blobs into one buffer and take every subtree root from one level
    pass, host engines the host loop), and cache the results. Returns
    how many blobs were computed.
    Never raises and never rejects: a blob that skips the batch simply
    meets `validate_blob_tx`'s per-blob host compute later, with
    identical bytes (counted `commitment.recomputes`)."""
    from celestia_app_tpu import appconsts

    cache = getattr(app, "commitment_cache", None)
    if cache is None or not raws:
        return 0
    threshold = appconsts.subtree_root_threshold(app.app_version)
    pending = []
    keys = []
    seen: set[bytes] = set()
    for blob in extract_blob_items(raws, btxs=btxs):
        try:
            # stateless per-blob gate (share version, namespace, empty
            # data): an adversarial blob must not reach the batch
            # compute, where its malformed shape would throw the WHOLE
            # window's honest blobs back onto the per-tx host path —
            # the ante rejects the tx itself later with its own error
            blob.validate()
        except ValueError:
            continue
        key = commitment_key(blob.namespace.raw, blob.share_version,
                             blob.data, threshold)
        if key in seen or cache.contains(key):
            continue
        seen.add(key)
        pending.append(blob)
        keys.append(key)
    if not pending:
        return 0
    if len(pending) < MIN_DEVICE_BATCH:
        telemetry.incr("commitment.prevalidate_below_batch")
        return 0
    from celestia_app_tpu.chain import blob_validation

    try:
        # the one batch: pack one buffer, one program, fold (the device
        # engines open admission.commit_pack / _dispatch / _fold under it)
        with obs.span("admission.commitments", n_blobs=len(pending)):
            commitments = blob_validation.batch_commitments(
                pending, threshold, engine=app.engine)
    except Exception as e:
        # the per-blob host path in validate_blob_tx stays authoritative
        telemetry.incr("admission.prevalidate_errors")
        obs.get_logger("chain.admission").error(
            "batch commitment prevalidation failed; per-blob host path "
            "takes over", err=e,
        )
        return 0
    telemetry.incr("commitment.batch_dispatches")
    telemetry.incr("commitment.batch_lanes", by=len(pending))
    for key, commitment in zip(keys, commitments):
        cache.put(key, commitment)
    return len(pending)


def extract_sig_item(app, raw: bytes, store=None, btx=_UNDECODABLE):
    """(pubkey, signature, sign-doc bytes) for one raw tx, or None when
    the tx cannot be prevalidated — undecodable, policy-rejected sig
    shape (non-64-byte or high-S, which `PublicKey.verify` refuses before
    any curve math), or a proto tx whose signer account does not exist
    yet (its sign doc needs the account number ensure_account will only
    assign inside the ante). None is never an error: the ante remains
    the authority and simply verifies those txs on its scalar path.
    `btx` optionally supplies the pre-parsed envelope (prevalidate's
    shared parse); the _UNDECODABLE default means "parse here"."""
    from celestia_app_tpu.chain.crypto import _N, PublicKey
    from celestia_app_tpu.chain.state import Context, InfiniteGasMeter
    from celestia_app_tpu.chain.tx import decode_tx
    from celestia_app_tpu.da import blob as blob_mod

    try:
        if btx is _UNDECODABLE:  # standalone call: parse the envelope
            btx = blob_mod.try_unmarshal_blob_tx(raw)
        tx = decode_tx(btx.tx if btx is not None else raw)
    except ValueError:
        return None
    sig = tx.signature
    if len(sig) != 64 or int.from_bytes(sig[32:], "big") > _N // 2:
        return None
    if getattr(tx, "wire_format", "native") == "proto":
        addr = PublicKey(tx.pubkey).address()
        ctx = Context(
            store if store is not None else app.store,
            InfiniteGasMeter(), app.height, 0,
            app.chain_id, app.app_version,
        )
        acc = app.auth.account(ctx, addr)
        if acc is None:
            return None
        doc = tx.sign_doc(app.chain_id, acc["number"])
    else:
        doc = tx.sign_doc()
    return (tx.pubkey, sig, doc)


def prevalidate(app, raws, *, check_state: bool = False,
                commitments: bool = True) -> int:
    """Phase 1: batch-verify the signatures of `raws` that are not
    already in the App's verified-sig cache, in one device dispatch, and
    cache the successes — and batch-compute the share commitments of
    their uncached blobs the same way (prevalidate_commitments).
    Returns how many signature lanes verified. Never raises and never
    rejects anything — a tx that fails (or skips) batch verification
    simply meets the ante's scalar verify later and fails THERE, with
    identical semantics.

    ``commitments=False`` skips the commitment half: ProcessProposal
    passes it because its own `resolve_commitments` already does ONE
    keyed pass through the cache (running both would hash every blob's
    bytes twice), and WAL replay passes it because delivery under a
    commit certificate validates no commitments at all."""
    if not raws:
        return 0
    with obs.span("admission.prevalidate",
                  traces=getattr(app, "traces", None), n_txs=len(raws)):
        return _prevalidate(app, raws, check_state, commitments)


def _prevalidate(app, raws, check_state: bool, commitments: bool) -> int:
    btxs = _unmarshal_batch(raws)  # ONE envelope parse per raw, both halves
    # commitment half first (its own try: a commitment failure must not
    # cost the signature batch, and vice versa — both halves degrade
    # independently to their scalar/host paths)
    if commitments:
        try:
            prevalidate_commitments(app, raws, btxs=btxs)
        except Exception:
            telemetry.incr("admission.prevalidate_errors")
    cache = getattr(app, "sig_cache", None)
    if cache is None:
        return 0
    if app.engine == "host":
        # the signature batch is a JAX dispatch like the extend: a
        # host-engine process must not initialise an accelerator backend
        # it does not own (N validator processes cannot share one chip),
        # so its signatures keep to the ante's scalar path
        telemetry.incr("admission.prevalidate_host_engine")
        return 0
    from celestia_app_tpu.ops import secp256k1 as fast

    store = None
    if check_state:
        # single read: a concurrent commit nulls app._check_state, and a
        # torn two-read would hand Context a None store. A stale branch
        # is harmless — the cache only stores state-independent facts.
        store = app._check_state
        if store is None:
            store = app.store
    items: list[tuple[bytes, bytes, bytes]] = []
    keys: list[bytes] = []
    seen: set[bytes] = set()
    for raw, btx in zip(raws, btxs):
        if btx is _UNDECODABLE:
            continue  # malformed envelope: the ante rejects it itself
        try:
            item = extract_sig_item(app, raw, store=store, btx=btx)
        except Exception:
            # prevalidation NEVER raises (callers may run it outside the
            # service lock, racing commits): an unexpected extraction
            # failure just leaves the tx to the ante's scalar path
            telemetry.incr("admission.prevalidate_errors")
            item = None
        if item is None:
            continue
        key = sig_key(*item)
        if key in seen or cache.contains(key):
            continue
        seen.add(key)
        items.append(item)
        keys.append(key)
    if not items:
        return 0
    if len(items) < MIN_DEVICE_BATCH or not fast.available():
        telemetry.incr("admission.prevalidate_below_batch")
        return 0
    try:
        # children (ops/secp256k1.verify_batch): admission.sig_prep, the
        # host's per-lane Python, and admission.sig_dispatch, the device
        with obs.span("admission.signatures", n_sigs=len(items),
                      lanes=fast.padded_lanes(len(items))):
            mask = fast.verify_batch(items)
    except Exception as e:
        # the scalar path in the ante stays authoritative; count + log
        telemetry.incr("admission.prevalidate_errors")
        obs.get_logger("chain.admission").error(
            "batch sig prevalidation failed; scalar path takes over",
            err=e,
        )
        return 0
    telemetry.incr("admission.batch_dispatches")
    telemetry.incr("admission.batch_lanes", by=len(items))
    verified = 0
    for ok, key in zip(mask, keys):
        if bool(ok):
            cache.put(key)
            verified += 1
        else:
            telemetry.incr("admission.batch_rejected")
    telemetry.incr("admission.batch_verified", by=verified)
    return verified
