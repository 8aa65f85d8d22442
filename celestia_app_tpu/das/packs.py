"""Proof packs: content-addressed, pre-assembled sample-proof bundles.

The serving half of arXiv:1910.01247's light-client model — many dumb
samplers hitting a *static* commitment — taken literally: at warm time
(the moment `da/edscache.ProverWarmer` already owns) a full node
precomputes EVERY cell's share + proof for a committed height in the
scheme's wire encoding and writes the bundle under

    <home>/packs/<data_root_hex>/
        <sha256(chunk)>.chunk ...     fsync'd, content-named chunks
        manifest.json                 written LAST (tmp+fsync+rename)

so serving a sample becomes `open(); read(); write()` — no lock, no
proof assembly, no JSON encoding per cell — and any blob store or CDN
can front the light-client fleet by mirroring the directory. The layout
is the sync plane's chunk pattern (chain/sync.py) with the chunk files
named by their OWN sha256 instead of an index: a pack is a pure function
of the data root, so mirrors can dedupe and a reader can verify every
byte against the manifest it fetched.

Byte-identity contract: each chunk is the canonical JSON encoding of a
list of per-cell sample docs, and each doc is built by the SAME
``live_cell_doc`` the live `/das/samples` path uses — pack-served proofs
are byte-identical to live-assembled ones by construction, and pinned
per scheme in tier-1 (tests/test_serving.py).

Crash safety: chunks are fsync'd as they land and the manifest goes last
via tmp+fsync+rename (``chain/sync.atomic_json_write`` — the
das/checkpoint.py discipline), so a crash mid-build leaves a dir with no
manifest: never advertised, never served, pruned on the next build. The
``packs.mid_write`` fault point (catalog: faults/__init__.py) fires
after each durable chunk so the chaos suite can kill a builder at the
torn moment and assert the node stays servable (live assembly).

Disk is bounded with the snapshot ``keep`` pattern: after every build
the store prunes to the newest ``CELESTIA_PACK_KEEP`` packs by the
height recorded in their manifests.

Wire formats: docs/FORMATS.md §17. Design: docs/DESIGN.md "The serving
plane".
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import shutil
import threading

from celestia_app_tpu.da import codec as codec_mod
from celestia_app_tpu.utils import telemetry

PACK_DIRNAME = "packs"

# bounded disk: keep the newest N packs (0 = keep everything)
DEFAULT_PACK_KEEP = int(os.environ.get("CELESTIA_PACK_KEEP", "4"))
# cells per chunk: small enough that a sampler's handful of draws maps
# to few chunks, big enough that a chunk amortizes its HTTP round-trip
DEFAULT_CHUNK_CELLS = int(os.environ.get("CELESTIA_PACK_CHUNK_CELLS",
                                         "256"))

MANIFEST_FIELDS = (
    "version", "height", "data_root", "scheme", "n_cells", "chunk_cells",
    "n_chunks", "chunk_hashes",
)


class PackError(ValueError):
    """Client-side problem on the /das/pack* surface (no pack for the
    height, bad chunk index); messages containing "not served" map to
    404 in the HTTP services."""


def cell_doc(row: int, col: int, share: bytes, proof) -> dict:
    """THE rs2d-nmt per-cell sample doc (FORMATS §7.1) from a proved
    cell — whoever proved it: a host prover's index arithmetic or the
    gather on the chip(s) (da/edscache `prove_cells`)."""
    return {
        "row": row,
        "col": col,
        "share": base64.b64encode(share).decode(),
        "proof": {
            "start": proof.start,
            "end": proof.end,
            "total": proof.total,
            "nodes": [base64.b64encode(n).decode()
                      for n in proof.nodes],
        },
    }


def live_cell_doc(entry, cell) -> dict:
    """One cell proved by the entry's host row prover and its doc built
    (FORMATS §7.1 / §16.3): the pack builder's unit of work — a pack is
    every cell of the square, so it asks for the prover outright. The
    live serving path (das/server.SampleCore) proves a request's cells
    in one `prove_cells` batch and builds each doc with the same
    `cell_doc`, so pack bytes ≡ live bytes by construction."""
    if entry.scheme == codec_mod.RS2D_NAME:
        row, col = cell
        return cell_doc(row, col, *entry.get_prover().prove_cell(row, col))
    # non-default schemes: the codec's own doc, with row/col aliases so
    # batched responses keep one shape across schemes (FORMATS §16.3)
    codec = codec_mod.get(entry.scheme)
    doc = codec.open_sample(entry, cell)
    return {"row": cell[0], "col": cell[1], **doc}


def encode_chunk(docs: list[dict]) -> bytes:
    """Canonical chunk bytes: sorted-key, separator-minimal JSON over the
    doc list — deterministic, so the chunk's sha256 is a pure function of
    the served proofs."""
    return json.dumps(docs, sort_keys=True,
                      separators=(",", ":")).encode()


def decode_chunk(data: bytes) -> list[dict]:
    """Parse chunk bytes back to the doc list; raises PackError on
    anything that is not a JSON list (UNTRUSTED input on the DASer
    side — hash verification happens before, doc verification after)."""
    try:
        docs = json.loads(data)
    except ValueError as e:
        raise PackError(f"undecodable pack chunk: {e}") from None
    if not isinstance(docs, list):
        raise PackError("pack chunk must be a JSON list of sample docs")
    return docs


def build_pack(entry, height: int,
               chunk_cells: int | None = None) -> tuple[dict, list[bytes]]:
    """(manifest, chunks) for one height's full sample-proof bundle.

    Cells are chunked in the codec's ``sample_space`` order (row-major
    for rs2d-nmt, layer-0 index order for cmt-ldpc), so a sampler maps a
    drawn cell to its chunk by position — no per-cell index table on the
    wire. The manifest carries the scheme's commitments doc, making a
    pack fully self-contained for a CDN-fronted sampler (it still
    verifies every proof against the CERTIFIED data root)."""
    chunk_cells = chunk_cells or DEFAULT_CHUNK_CELLS
    codec = codec_mod.get(entry.scheme)
    space = codec.sample_space(entry.dah)
    docs = [live_cell_doc(entry, cell) for cell in space]
    chunks = [
        encode_chunk(docs[i:i + chunk_cells])
        for i in range(0, len(docs), chunk_cells)
    ]
    manifest = {
        "version": 1,
        "height": height,
        "data_root": entry.data_root.hex(),
        "scheme": entry.scheme,
        "n_cells": len(space),
        "chunk_cells": chunk_cells,
        "n_chunks": len(chunks),
        "chunk_hashes": [hashlib.sha256(c).hexdigest() for c in chunks],
        "commitments": codec.commitments_doc(entry),
    }
    return manifest, chunks


def _manifest_ok(m) -> bool:
    if not isinstance(m, dict):
        return False
    if any(k not in m for k in MANIFEST_FIELDS):
        return False
    return (isinstance(m["chunk_hashes"], list)
            and len(m["chunk_hashes"]) == m["n_chunks"])


def advertised(manifest: dict) -> dict:
    """The compact pack advertisement riding the /das/header doc (the
    sampler's zero-extra-round-trip discovery): everything a chunk
    fetcher needs, without the commitments doc the header already
    carries."""
    return {k: manifest[k] for k in MANIFEST_FIELDS}


class PackStore:
    """The on-disk pack set one node serves (``<home>/packs``).

    Read paths touch only the filesystem plus a small manifest memo —
    serving a manifest or chunk never takes any app/service lock. Packs
    are immutable once their manifest lands (content-addressed by data
    root), so the memo never needs invalidation; it is bounded LRU all
    the same."""

    _MEMO_MAX = 16

    def __init__(self, root: str, keep: int | None = None,
                 chunk_cells: int | None = None):
        self.root = root
        self.keep = DEFAULT_PACK_KEEP if keep is None else int(keep)
        self.chunk_cells = chunk_cells or DEFAULT_CHUNK_CELLS
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
        """Raw chunk bytes from disk — the /das/pack/chunk body. Raises
        PackError('... not served') when the pack/chunk is absent."""
        m = self.manifest(data_root)
        root_hex = (data_root.hex() if isinstance(data_root, bytes)
                    else data_root)
        if m is None:
            raise PackError(f"pack {root_hex[:16]} not served")
        if not 0 <= int(index) < m["n_chunks"]:
            raise PackError(
                f"pack chunk index {index} out of range "
                f"(n_chunks {m['n_chunks']})"
            )
        path = os.path.join(self.path_for(root_hex),
                            m["chunk_hashes"][int(index)] + ".chunk")
        try:
            with open(path, "rb") as f:
                return f.read()
        except OSError:
            raise PackError(
                f"pack chunk {root_hex[:16]}/{index} not served"
            ) from None

    # -- build / prune ---------------------------------------------------

    def build(self, height: int, entry) -> dict | None:
        """Build + durably persist the height's pack (idempotent: an
        existing complete pack for the same data root is left alone —
        packs are pure functions of the root). Returns the manifest, or
        the resident one on skip. Fires ``packs.mid_write`` after each
        durable chunk; a crash/error there leaves no manifest, so the
        half-pack is never served and the next build restarts it."""
        from celestia_app_tpu import faults

        existing = self.manifest(entry.data_root)
        if existing is not None:
            telemetry.incr("packs.build_skipped")
            return existing
        t0 = telemetry.start_timer()
        manifest, chunks = build_pack(entry, height, self.chunk_cells)
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
            telemetry.incr("packs.chunks_written")
            # crash point: THIS chunk is durable, the manifest is not —
            # the pack must stay invisible to /das/pack until it is
            action = faults.fire("packs.mid_write", height=height,
                                 data_root=manifest["data_root"],
                                 index=i)
            if action in ("drop", "error"):
                raise OSError("injected fault: packs.mid_write")
        atomic_json_write(os.path.join(out_dir, "manifest.json"),
                          manifest)
        telemetry.incr("packs.built")
        telemetry.measure_since("packs.build", t0)
        self.prune(self.keep)
        return manifest

    def prune(self, keep: int) -> None:
        """Keep only the newest ``keep`` complete packs (by manifest
        height; 0 = keep everything). A manifest-less dir — a crashed
        build — is deleted outright and never counts toward the kept
        set (the snapshot-prune semantics, chain/sync.prune_snapshots)."""
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
                telemetry.incr("packs.pruned_torn")
                continue
            complete.append((int(m["height"]), name))
        if keep <= 0:
            return
        for _h, name in sorted(complete, reverse=True)[keep:]:
            shutil.rmtree(os.path.join(self.root, name),
                          ignore_errors=True)
            with self._lock:
                self._memo.pop(name, None)
            telemetry.incr("packs.pruned")
