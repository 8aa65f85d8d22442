"""Stateless BlobTx validation: the mempool/proposal admission gate.

Reference parity: x/blob/types/blob_tx.go:37-108 `ValidateBlobTx` — the tx
must decode to exactly one MsgPayForBlobs whose per-blob namespace, size,
share version, and recomputed share commitment all match the attached blobs.
Called from CheckTx (app/check_tx.go:43) and ProcessProposal
(app/process_proposal.go:107), i.e. the reference recomputes commitments on
every admission. This repo instead routes every phase through the App's
`VerifiedCommitmentCache` (chain/admission.py, the traffic plane): the
admission batch (or the first per-blob host compute) fills it, and every
later phase consumes the cached bytes — the byte-compare against the tx's
CLAIMED commitment still runs on every path, so a mismatching (Byzantine)
tx is rejected identically warm or cold.
"""

from __future__ import annotations

from celestia_app_tpu.chain.tx import MsgPayForBlobs, Tx, decode_tx
from celestia_app_tpu.da import commitment as commitment_mod
from celestia_app_tpu.da.blob import BlobTx
from celestia_app_tpu.utils import telemetry


class BlobTxError(Exception):
    pass


def batch_commitments(blobs: list, subtree_root_threshold: int,
                      engine: str = "auto") -> list[bytes]:
    """Commitments for many blobs at once: device-batched when the workload
    is big enough to amortize a dispatch (BASELINE config 3), host
    otherwise. `engine` is the owning App's compute engine — a host-engine
    validator must NEVER touch the jax backend here: it would initialise
    an accelerator backend the process does not own (one process per
    chip) the first time a block carries >= 4 blobs. Under "auto" a
    failed dispatch degrades to the host, counted
    ``app.device_path_fallback`` as compute_entry's does; under "device"
    it raises."""
    if engine != "host" and len(blobs) >= 4:
        try:
            from celestia_app_tpu.da import commitment_device

            return commitment_device.commitments_device(
                blobs, subtree_root_threshold
            )
        except Exception:
            if engine == "device":
                raise
            telemetry.incr("app.device_path_fallback")
    return commitment_mod.create_commitments(blobs, subtree_root_threshold)


def resolve_commitments(blobs: list, subtree_root_threshold: int,
                        engine: str = "auto", cache=None) -> list[bytes]:
    """Commitments for `blobs` through the verified-commitment cache:
    cached blobs cost a lookup (`commitment.cache_hits`), the uncached
    remainder is computed in ONE batch (`commitment.recomputes`, one per
    computed blob) and cached for every later phase. With no cache this
    is exactly `batch_commitments`. The admitted-path telemetry pin
    (tests/test_traffic.py) rides on this split: a block whose txs were
    admitted at CheckTx resolves every commitment by lookup."""
    if cache is None:
        telemetry.incr("commitment.recomputes", by=len(blobs))
        return batch_commitments(blobs, subtree_root_threshold, engine)
    out: list[bytes | None] = []
    missing: list = []
    missing_at: list[int] = []
    keys: list[bytes] = []
    for i, blob in enumerate(blobs):
        key = cache.key(blob.namespace.raw, blob.share_version, blob.data,
                        subtree_root_threshold)
        got = cache.hit(key)
        out.append(got)
        if got is None:
            missing.append(blob)
            missing_at.append(i)
            keys.append(key)
    if missing:
        telemetry.incr("commitment.recomputes", by=len(missing))
        computed = batch_commitments(missing, subtree_root_threshold, engine)
        for i, key, commitment in zip(missing_at, keys, computed):
            out[i] = commitment
            cache.put(key, commitment)
    return out


def validate_blob_tx(
    btx: BlobTx,
    subtree_root_threshold: int,
    commitments: list[bytes] | None = None,
    cache=None,
) -> tuple[Tx, MsgPayForBlobs]:
    """Validate and return the decoded signed tx + its PFB message.

    ``commitments`` optionally supplies this tx's precomputed blob
    commitments (from resolve_commitments over the whole block) so
    ProcessProposal doesn't recompute per blob on the host; ``cache``
    is the owning App's VerifiedCommitmentCache — a hit replaces the
    per-blob host recompute, a miss computes and fills it.
    """
    if not btx.blobs:
        raise BlobTxError("blob tx contains no blobs")
    try:
        tx = decode_tx(btx.tx)
    except ValueError as e:
        raise BlobTxError(f"undecodable tx in blob tx: {e}") from None

    pfbs = [m for m in tx.body.msgs if isinstance(m, MsgPayForBlobs)]
    if len(pfbs) != 1 or len(tx.body.msgs) != 1:
        raise BlobTxError("blob tx must contain exactly one MsgPayForBlobs")
    msg = pfbs[0]
    msg.validate_basic()

    if len(btx.blobs) != len(msg.namespaces):
        raise BlobTxError(
            f"blob count mismatch: {len(btx.blobs)} attached, {len(msg.namespaces)} in msg"
        )
    for i, blob in enumerate(btx.blobs):
        blob.validate()
        if blob.namespace.raw != msg.namespaces[i]:
            raise BlobTxError(f"blob {i} namespace does not match msg")
        if len(blob.data) != msg.blob_sizes[i]:
            raise BlobTxError(
                f"blob {i} size mismatch: {len(blob.data)} != {msg.blob_sizes[i]}"
            )
        if blob.share_version != msg.share_versions[i]:
            raise BlobTxError(f"blob {i} share version mismatch")
        if commitments is not None:
            want = commitments[i]
        else:
            want = None
            key = None
            if cache is not None:
                key = cache.key(blob.namespace.raw, blob.share_version,
                                blob.data, subtree_root_threshold)
                want = cache.hit(key)
            if want is None:
                telemetry.incr("commitment.recomputes")
                want = commitment_mod.create_commitment(
                    blob, subtree_root_threshold)
                if cache is not None:
                    cache.put(key, want)
        if want != msg.share_commitments[i]:
            raise BlobTxError(f"blob {i} share commitment mismatch")
    return tx, msg
