"""Blob packs: content-addressed, pre-assembled namespace-read bundles.

The read plane's static half (das/packs.py's pattern applied to the
rollup-reader workload): at warm time — the moment the ProverWarmer
already owns, provers built, level stacks resident — a full node
precomputes EVERY present blob namespace's full query response (shares +
presence-and-completeness proof, da/namespace_data.py) for a committed
height and writes the bundle under

    <home>/blobpacks/<data_root_hex>/
        <sha256(chunk)>.chunk ...     fsync'd, content-named chunks
        manifest.json                 written LAST (tmp+fsync+rename)

so serving a rollup follower becomes `open(); read(); write()` — no
lock, no proof assembly, no JSON encoding per query — and any blob
store or CDN can front the read fleet by mirroring the directory. A
pack is a pure function of the data root, so mirrors dedupe and a
reader verifies every byte against the manifest it fetched.

Byte-identity contract: each chunk is the canonical JSON encoding of a
list of per-namespace docs, and each doc is built by the SAME
``live_namespace_doc`` the live `/blob/get` path serves — pack bytes ≡
live bytes by construction, pinned in tests/test_read_plane.py.

Crash safety is the das/packs.py discipline verbatim: chunks fsync as
they land, the manifest goes last via tmp+fsync+rename, so a crash
mid-build leaves a manifest-less dir — never advertised, never served,
pruned on the next build. The ``blobpacks.mid_write`` fault point
(catalog: faults/__init__.py) fires after each durable chunk. Disk is
bounded with the keep-newest-N prune.

Wire formats: docs/FORMATS.md §21. Design: docs/DESIGN.md "The read
plane".
"""

from __future__ import annotations

import binascii
import hashlib
import json
import os
import shutil
import threading

import numpy as np

from celestia_app_tpu.appconsts import SHARE_SIZE
from celestia_app_tpu.da import codec as codec_mod
from celestia_app_tpu.das.packs import PackError, decode_chunk, encode_chunk
from celestia_app_tpu.utils import telemetry

BLOB_PACK_DIRNAME = "blobpacks"

# bounded disk: keep the newest N blob packs (0 = keep everything)
DEFAULT_BLOB_PACK_KEEP = int(os.environ.get("CELESTIA_BLOB_PACK_KEEP",
                                            "4"))
# namespaces per chunk: a follower fetches THE chunk covering its one
# namespace, so small chunks keep reads cheap while still amortizing
# the HTTP round-trip over a namespace neighborhood
DEFAULT_CHUNK_NAMESPACES = int(os.environ.get(
    "CELESTIA_BLOB_PACK_CHUNK_NS", "8"))

MANIFEST_FIELDS = (
    "version", "height", "data_root", "scheme", "n_namespaces",
    "namespaces", "chunk_namespaces", "n_chunks", "chunk_hashes",
)

__all__ = [
    "BLOB_PACK_DIRNAME", "MANIFEST_FIELDS", "PackError", "encode_chunk",
    "decode_chunk", "EncodedShares", "NamespaceReply", "namespace_member",
    "live_namespace_doc", "blob_namespaces", "build_blob_pack",
    "advertised", "BlobPackStore",
]

# one share in base64: 171 groups of 3 bytes, the last of them short by one
SHARE_B64 = (SHARE_SIZE + 2) // 3 * 4
# one share as a member of a JSON list: '"' + its base64 + '", '
_FRAMED = SHARE_B64 + 4


class EncodedShares:
    """A list of shares in base64, made in one pass over all of them.

    ``json`` is the list as JSON text — byte for byte what ``json.dumps``
    gives for the list of each share's ``base64.b64encode`` — and
    ``strings()`` the list itself. The pass: the shares joined with a
    zero byte after each (``SHARE_SIZE + 1`` bytes a row, a multiple of
    3, so every row starts a base64 group), ONE ``binascii.b2a_base64``
    over the rows, and one numpy pass that turns each row's last
    character — the zero byte's — into the ``=`` a 512-byte share ends
    with, and frames the rows as ``["…", "…"]``."""

    __slots__ = ("n", "json", "_strings")

    def __init__(self, shares: list[bytes]):
        n = self.n = len(shares)
        self._strings = None
        if not n:
            self.json = b"[]"
            return
        rows = b"\0".join([*shares, b""])
        b64 = np.frombuffer(binascii.b2a_base64(rows, newline=False),
                            np.uint8).reshape(n, SHARE_B64)
        # '[' then n framed rows; the last row's '", ' becomes '"]' and
        # its spare byte is cut off with the final slice
        out = np.empty(1 + n * _FRAMED, np.uint8)
        out[0] = ord("[")
        framed = out[1:].reshape(n, _FRAMED)
        framed[:, 0] = ord('"')
        framed[:, 1:SHARE_B64] = b64[:, :-1]
        framed[:, SHARE_B64] = ord("=")
        framed[:, SHARE_B64 + 1:] = np.frombuffer(b'", ', np.uint8)
        framed[-1, SHARE_B64 + 2] = ord("]")
        self.json = out[:-1].tobytes()

    def strings(self) -> list[str]:
        """The list of base64 strings: a new list on every call, of the
        same ``str`` objects, sliced once out of one decoded string."""
        if self._strings is None:
            text = self.json.decode("ascii")
            self._strings = [text[i + 2:i + 2 + SHARE_B64]
                             for i in range(0, self.n * _FRAMED, _FRAMED)]
        return list(self._strings)


def namespace_member(entry, namespace: bytes, prover=None,
                     nd=None) -> dict:
    """THE per-namespace read doc (FORMATS §21.1) with its two share
    lists still encoded: ``shares`` and ``proof["data"]`` hold
    `EncodedShares` — ONE for both where the namespace is present (the
    proof's data is its share range), the successor leaf's alone where
    it is absent. One builder shared by the live serving path
    (das/blob_server.BlobCore) and the pack builder, so pack bytes ≡
    live bytes by construction; `member_doc` makes it the dict,
    `member_json` the bytes. ``prover`` lets callers pass a resolved
    prover; ``nd`` lets the batched route pass an already-resolved
    `NamespaceData` (batched resolution is pinned byte-identical to the
    host reference, so the doc bytes are unchanged)."""
    from celestia_app_tpu.chain.query import _share_proof_json
    from celestia_app_tpu.da import namespace_data as nsd_mod

    if nd is None:
        if prover is None:
            prover = entry.get_prover()
        nd = nsd_mod.get_namespace_data(prover, namespace)
    shares = EncodedShares(nd.shares)
    proof = None
    if nd.proof:
        data = shares if nd.shares else EncodedShares(nd.proof.data)
        proof = _share_proof_json(nd.proof, data=data)
    return {
        "namespace": namespace.hex(),
        "present": bool(nd.shares),
        "shares": shares,
        "proof": proof,
        "data_root": entry.data_root.hex(),
    }


def member_doc(member: dict) -> dict:
    """A `namespace_member` (or an error member, returned as it is) as
    the §21.1 dict: each share list a list object of its own."""
    shares = member.get("shares")
    if not isinstance(shares, EncodedShares):
        return member
    doc = {**member, "shares": shares.strings()}
    if member["proof"] is not None:
        doc["proof"] = {**member["proof"],
                        "data": member["proof"]["data"].strings()}
    return doc


def member_json(member: dict) -> list[bytes]:
    """A `namespace_member` as the pieces of ``json.dumps(member_doc(
    member)).encode()``: the doc with both share lists empty goes
    through ``json.dumps``, and the lists' JSON is spliced in where the
    empty ones stand. ``"shares": []`` is found as the first, and
    ``"data": []`` as the first after it: the keys before them hold a
    number, hex and a boolean, and a quote inside a JSON string is
    always escaped."""
    shares = member.get("shares")
    if not isinstance(shares, EncodedShares):
        return [json.dumps(member).encode()]
    proof = member["proof"]
    flat = {**member, "shares": []}
    if proof is not None:
        flat["proof"] = {**proof, "data": []}
    text = json.dumps(flat).encode()
    i = text.index(b'"shares": []') + len(b'"shares": ')
    if proof is None:
        return [text[:i], shares.json, text[i + 2:]]
    j = text.index(b'"data": []', i) + len(b'"data": ')
    return [text[:i], shares.json, text[i + 2:j], proof["data"].json,
            text[j + 2:]]


class NamespaceReply:
    """A namespace read's reply — one member (``GET /blob/get``) or
    ``{"queries": [members]}`` (``POST /blob/namespaces``) — held as its
    members with their share lists still encoded: a transport takes it
    as bytes (`render`), an in-process caller as the dict (`doc`); both
    are the FORMATS §21.1 reply to the byte."""

    __slots__ = ("members", "batched")

    def __init__(self, members: list[dict], batched: bool):
        self.members = members
        self.batched = batched

    def doc(self) -> dict:
        docs = [member_doc(m) for m in self.members]
        return {"queries": docs} if self.batched else docs[0]

    def render(self) -> bytes:
        """``json.dumps(self.doc()).encode()``, with each share list
        written from its one encoding; counted ``blob.rendered_replies``."""
        pieces: list[bytes] = []
        for i, member in enumerate(self.members):
            if self.batched:
                pieces.append(b", " if i else b'{"queries": [')
            pieces += member_json(member)
        if self.batched:
            pieces.append(b"]}")
        telemetry.incr("blob.rendered_replies")
        return b"".join(pieces)


def live_namespace_doc(entry, namespace: bytes, prover=None,
                       nd=None) -> dict:
    """The §21.1 dict of `namespace_member` — what the pack builder and
    in-process readers take."""
    return member_doc(namespace_member(entry, namespace, prover=prover,
                                       nd=nd))


def blob_namespaces(entry, prover=None) -> list[bytes]:
    """The height's packable namespaces: every DISTINCT unreserved
    namespace present in the Q0 square, in square (= lexicographic)
    order — read off the prover's resident level-0 mins, the same
    source the batched search uses."""
    from celestia_app_tpu.da import namespace as ns_mod
    from celestia_app_tpu.da import namespace_device as nsdev

    if prover is None:
        prover = entry.get_prover()
    leaf = nsdev.leaf_namespaces(prover)
    import numpy as np

    distinct = np.unique(leaf, axis=0)
    out = []
    for row in distinct:
        raw = row.tobytes()
        if not ns_mod.Namespace(raw).is_reserved():
            out.append(raw)
    return out


def build_blob_pack(entry, height: int,
                    chunk_namespaces: int | None = None
                    ) -> tuple[dict, list[bytes]]:
    """(manifest, chunks) for one height's full namespace-read bundle.

    Namespaces are chunked in square order, so a reader maps its
    namespace to a chunk by position in the manifest's ``namespaces``
    list — no per-namespace index table on the wire. Only the default
    scheme packs (namespace reads are an rs2d-nmt surface)."""
    if entry.scheme != codec_mod.RS2D_NAME:
        raise PackError(
            f"blob packs need the {codec_mod.RS2D_NAME} scheme, "
            f"not {entry.scheme}"
        )
    chunk_namespaces = chunk_namespaces or DEFAULT_CHUNK_NAMESPACES
    prover = entry.get_prover()
    spaces = blob_namespaces(entry, prover=prover)
    docs = [live_namespace_doc(entry, ns, prover=prover) for ns in spaces]
    chunks = [
        encode_chunk(docs[i:i + chunk_namespaces])
        for i in range(0, len(docs), chunk_namespaces)
    ]
    manifest = {
        "version": 1,
        "height": height,
        "data_root": entry.data_root.hex(),
        "scheme": entry.scheme,
        "n_namespaces": len(spaces),
        "namespaces": [ns.hex() for ns in spaces],
        "chunk_namespaces": chunk_namespaces,
        "n_chunks": len(chunks),
        "chunk_hashes": [hashlib.sha256(c).hexdigest() for c in chunks],
    }
    return manifest, chunks


def _manifest_ok(m) -> bool:
    if not isinstance(m, dict):
        return False
    if any(k not in m for k in MANIFEST_FIELDS):
        return False
    return (isinstance(m["chunk_hashes"], list)
            and len(m["chunk_hashes"]) == m["n_chunks"]
            and isinstance(m["namespaces"], list)
            and len(m["namespaces"]) == m["n_namespaces"])


def advertised(manifest: dict) -> dict:
    """The pack advertisement a reader needs to map its namespace to a
    chunk (FORMATS §21.2) — the manifest's normative fields."""
    return {k: manifest[k] for k in MANIFEST_FIELDS}


class BlobPackStore:
    """The on-disk blob-pack set one node serves (``<home>/blobpacks``).

    Read paths touch only the filesystem plus a small manifest memo —
    serving a manifest or chunk never takes any app/service lock. Packs
    are immutable once their manifest lands (content-addressed by data
    root), so the memo never needs invalidation; bounded LRU all the
    same."""

    _MEMO_MAX = 16

    def __init__(self, root: str, keep: int | None = None,
                 chunk_namespaces: int | None = None):
        self.root = root
        self.keep = DEFAULT_BLOB_PACK_KEEP if keep is None else int(keep)
        self.chunk_namespaces = (chunk_namespaces
                                 or DEFAULT_CHUNK_NAMESPACES)
        self._lock = threading.Lock()
        # data_root hex -> manifest (immutable docs; bounded)
        self._memo: dict[str, dict] = {}  # guarded-by: _lock

    # -- lookup ----------------------------------------------------------

    def path_for(self, root_hex: str) -> str:
        return os.path.join(self.root, root_hex)

    def manifest(self, data_root: bytes | str) -> dict | None:
        """The pack manifest for a data root, or None when no complete
        pack exists (half-written dirs have no manifest and never
        serve)."""
        root_hex = (data_root.hex() if isinstance(data_root, bytes)
                    else data_root)
        with self._lock:
            hit = self._memo.get(root_hex)
        if hit is not None:
            return hit
        path = os.path.join(self.path_for(root_hex), "manifest.json")
        try:
            with open(path) as f:
                m = json.load(f)
        except (OSError, ValueError):
            return None
        if not _manifest_ok(m):
            return None
        with self._lock:
            while len(self._memo) >= self._MEMO_MAX:
                self._memo.pop(next(iter(self._memo)))
            self._memo[root_hex] = m
        return m

    def chunk(self, data_root: bytes | str, index: int) -> bytes:
        """Raw chunk bytes from disk — the /blob/pack/chunk body.
        Raises PackError('... not served') when the pack/chunk is
        absent."""
        m = self.manifest(data_root)
        root_hex = (data_root.hex() if isinstance(data_root, bytes)
                    else data_root)
        if m is None:
            raise PackError(f"blob pack {root_hex[:16]} not served")
        if not 0 <= int(index) < m["n_chunks"]:
            raise PackError(
                f"blob pack chunk index {index} out of range "
                f"(n_chunks {m['n_chunks']})"
            )
        path = os.path.join(self.path_for(root_hex),
                            m["chunk_hashes"][int(index)] + ".chunk")
        try:
            with open(path, "rb") as f:
                return f.read()
        except OSError:
            raise PackError(
                f"blob pack chunk {root_hex[:16]}/{index} not served"
            ) from None

    # -- build / prune ---------------------------------------------------

    def build(self, height: int, entry) -> dict | None:
        """Build + durably persist the height's blob pack (idempotent:
        an existing complete pack for the same data root is left alone).
        Returns the manifest, the resident one on skip, or None for a
        scheme that does not pack. Fires ``blobpacks.mid_write`` after
        each durable chunk; a crash/error there leaves no manifest, so
        the half-pack is never served and the next build restarts it."""
        from celestia_app_tpu import faults

        if entry.scheme != codec_mod.RS2D_NAME:
            return None
        existing = self.manifest(entry.data_root)
        if existing is not None:
            telemetry.incr("blobpacks.build_skipped")
            return existing
        t0 = telemetry.start_timer()
        manifest, chunks = build_blob_pack(entry, height,
                                           self.chunk_namespaces)
        from celestia_app_tpu.chain.sync import (
            atomic_json_write,
            fsync_write,
        )

        out_dir = self.path_for(manifest["data_root"])
        os.makedirs(out_dir, exist_ok=True)
        for i, chunk in enumerate(chunks):
            fsync_write(
                os.path.join(out_dir, manifest["chunk_hashes"][i]
                             + ".chunk"),
                chunk,
            )
            telemetry.incr("blobpacks.chunks_written")
            # crash point: THIS chunk is durable, the manifest is not —
            # the pack must stay invisible to /blob/pack until it is
            action = faults.fire("blobpacks.mid_write", height=height,
                                 data_root=manifest["data_root"],
                                 index=i)
            if action in ("drop", "error"):
                raise OSError("injected fault: blobpacks.mid_write")
        atomic_json_write(os.path.join(out_dir, "manifest.json"),
                          manifest)
        telemetry.incr("blobpacks.built")
        telemetry.measure_since("blobpacks.build", t0)
        self.prune(self.keep)
        return manifest

    def prune(self, keep: int) -> None:
        """Keep only the newest ``keep`` complete packs (by manifest
        height; 0 = keep everything). A manifest-less dir — a crashed
        build — is deleted outright and never counts toward the kept
        set."""
        if not os.path.isdir(self.root):
            return
        complete: list[tuple[int, str]] = []
        for name in sorted(os.listdir(self.root)):
            path = os.path.join(self.root, name)
            if not os.path.isdir(path):
                continue
            m = self.manifest(name)
            if m is None:
                shutil.rmtree(path, ignore_errors=True)
                telemetry.incr("blobpacks.pruned_torn")
                continue
            complete.append((int(m["height"]), name))
        if keep <= 0:
            return
        for _h, name in sorted(complete, reverse=True)[keep:]:
            shutil.rmtree(os.path.join(self.root, name),
                          ignore_errors=True)
            with self._lock:
                self._memo.pop(name, None)
            telemetry.incr("blobpacks.pruned")
