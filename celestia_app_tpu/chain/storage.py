"""Disk-backed chain database: committed state + block store.

Reference parity: the durable side of the reference node — cosmos-sdk's
commit multistore persisted via IAVL/LevelDB plus celestia-core's block
store (app/app.go:427-435 LoadLatestVersion, default_overrides.go pruning
windows).

Commit persistence is DELTA-BASED (the IAVL versioned-tree analog): most
commits write only the keys touched since the previous commit (writes +
deletions); a full snapshot is written every FULL_INTERVAL commits (and at
the first durable commit), so loading height ``h`` = nearest full snapshot
≤ h plus the delta chain up to h. Commit IO therefore scales with touched
keys, not total state size. Every block (header + txs) is kept so proofs
for past heights can be re-derived (pkg/proof/querier.go re-extends the
square from block data).

Two storage engines sit under ONE ChainDB (the commit/load/prune logic is
engine-independent; engines only move bytes):

- **native** (default where the toolchain exists): native/chaindb.cc via
  ctypes — a segmented append-only record store with CRC framing, fsync
  batching, torn-tail recovery, rollback/prune tombstones, dead-segment GC
  and writer flocks. This is the tm-db analog and the engine a real
  validator runs on.
- **files**: one artifact per height under state/ delta/ blocks/ plus a
  LATEST pointer, each atomically renamed and fsynced. Zero native
  dependencies; also the round-3 on-disk layout, which it still reads.

What the artifacts hold is ChainDB's, the same under both engines: state
snapshots and deltas are gzip-compressed JSON documents (a few KB a
commit); a block is a **binary record** (FORMATS §23.2): magic, version,
the header as its one JSON codec gives it, every tx as a length prefix and
its raw bytes, a CRC-32 of all of it. Blob payloads are high-entropy and
megabytes long; base64 + JSON + gzip of them cost nine tenths of a commit
and saved nothing. Blocks an earlier version wrote as gzip-JSON are still
read (told apart by the record's first bytes), never written.

Selection: ``CELESTIA_CHAINDB`` env = ``native`` / ``files`` / ``auto``
(default). Auto keeps whatever engine a home already uses (seg-*.log ⇒
native; LATEST/state ⇒ files) and picks native for fresh homes when the
.so is buildable.

Crash-safety contract (both engines): the commit artifact is durable
BEFORE the latest-pointer that references it — a crash between the two
resumes from the previous height; a torn tail is dropped on reopen.
"""

from __future__ import annotations

import gzip
import json
import os
import zlib

from celestia_app_tpu import faults
from celestia_app_tpu.chain.block import Block

PRUNE_KEEP = 100  # same rollback window the in-memory history kept
FULL_INTERVAL = 64  # full snapshot cadence (state-sync interval analog)

# record streams (shared by both engines; the file engine maps them to dirs)
STATE, DELTA, BLOCK, LATEST = 0, 1, 2, 3


# -- the block record (FORMATS §23.2) -------------------------------------
#
#   magic "CBLK" | version u8 | hlen u32 | header JSON | ntx u32 |
#   ntx x (len u32 | tx bytes) | crc32 u32 of everything before it
#
# Integers little-endian. The header rides as THE header codec's JSON
# (chain/consensus.py: block store, WAL and socket wire agree on every
# field, or a stored block re-hashes differently than the chain committed);
# tx bytes ride raw. Beside the header's keys the document carries one of
# the store's own, `layout_bound`: the square-size bound the block's
# proposer laid it out under (App.max_effective_square_size at the state
# the proposal was built on), which a read needs to lay the stored txs out
# the same way (chain/query.rebuild_square). It is no header field and is
# in no hash; a record without it (an earlier writer's) reads as None.
# The CRC is the record's own corruption check: the file engine frames
# nothing, and a flipped or torn record must fail in load_block, not parse
# into another block.

BLOCK_MAGIC = b"CBLK"
BLOCK_VERSION = 1
GZIP_MAGIC = b"\x1f\x8b"  # what every pre-record block starts with
_U32 = 4  # bytes of every integer in the record


def _u32(n: int) -> bytes:
    return n.to_bytes(_U32, "little")  # OverflowError past 4 GiB: loud


def _compact_json(doc: dict) -> bytes:
    return json.dumps(doc, sort_keys=True, separators=(",", ":")).encode()


class BlockRecordError(ValueError):
    """A stored block record that is torn, corrupt or of an unknown kind."""


LAYOUT_BOUND_KEY = "layout_bound"


def _encode_block(block: Block, layout_bound: int | None = None) -> bytes:
    from celestia_app_tpu.chain.consensus import header_to_json

    doc = header_to_json(block.header)
    if layout_bound is not None:
        doc[LAYOUT_BOUND_KEY] = layout_bound
    header = _compact_json(doc)
    parts = [
        BLOCK_MAGIC + bytes([BLOCK_VERSION]) + _u32(len(header)),
        header,
        _u32(len(block.txs)),
    ]
    for tx in block.txs:
        parts.append(_u32(len(tx)))
        parts.append(tx)
    crc = 0
    for part in parts:
        crc = zlib.crc32(part, crc)
    parts.append(_u32(crc))
    return b"".join(parts)  # the one copy of the payload


def _decode_record(blob: bytes) -> tuple[Block, int | None]:
    """(the block, the bound it was laid out under or None)."""
    from celestia_app_tpu.chain.consensus import header_from_json

    view = memoryview(blob)
    end = len(view) - _U32  # where the CRC starts
    fixed = len(BLOCK_MAGIC) + 1
    if end < fixed + 2 * _U32:
        raise BlockRecordError(f"block record truncated at {len(view)} B")
    if view[fixed - 1] != BLOCK_VERSION:
        raise BlockRecordError(
            f"block record version {view[fixed - 1]}, "
            f"this code reads {BLOCK_VERSION}")
    if zlib.crc32(view[:end]) != int.from_bytes(view[end:], "little"):
        raise BlockRecordError("block record fails its CRC")
    pos = fixed

    def take(n: int) -> memoryview:
        nonlocal pos
        if n > end - pos:
            raise BlockRecordError(
                f"block record: length {n} at offset {pos} runs past "
                f"its end ({end})")
        pos += n
        return view[pos - n:pos]

    def u32() -> int:
        return int.from_bytes(take(_U32), "little")

    doc = json.loads(bytes(take(u32())))
    header = header_from_json(doc)
    txs = tuple(bytes(take(u32())) for _ in range(u32()))
    if pos != end:
        raise BlockRecordError(
            f"block record: {end - pos} stray bytes after the last tx")
    return Block(header=header, txs=txs), doc.get(LAYOUT_BOUND_KEY)


def _atomic_write(path: str, data: bytes) -> None:
    # disk fault point: armed "error" surfaces as the OSError any real
    # full-disk/EIO failure would; "crash" kills the process here, before
    # anything of this artifact is durable; "delay" models a slow disk
    if faults.fire("storage.atomic_write", path=path) == "error":
        raise OSError(f"injected fault: storage.atomic_write {path}")
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    # fsync the directory so the rename itself is durable: without this a
    # power loss can persist a later artifact (LATEST) while losing an
    # earlier rename, breaking the write-ordering guarantee
    dfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)


class FileBackend:
    """One file per height and stream; every op is individually durable.

    The `.json.gz` suffix names the ENGINE's artifact, not its content:
    state and delta files are gzip-JSON, a block file holds ChainDB's
    binary block record (or, from an earlier version, gzip-JSON). Readers
    sniff the first bytes; the names stay, so a home keeps opening."""

    DIRS = {STATE: "state", DELTA: "delta", BLOCK: "blocks"}

    def __init__(self, data_dir: str):
        self.dir = data_dir
        for sub in self.DIRS.values():
            os.makedirs(os.path.join(data_dir, sub), exist_ok=True)

    def _path(self, stream: int, height: int) -> str:
        return os.path.join(
            self.dir, self.DIRS[stream], f"{height:020d}.json.gz"
        )

    def put(self, stream: int, height: int, blob: bytes) -> None:
        _atomic_write(self._path(stream, height), blob)

    def get(self, stream: int, height: int) -> bytes | None:
        try:
            with open(self._path(stream, height), "rb") as f:
                return f.read()
        except FileNotFoundError:
            return None

    def heights(self, stream: int) -> list[int]:
        out = []
        for name in os.listdir(os.path.join(self.dir, self.DIRS[stream])):
            if name.endswith(".json.gz"):
                try:
                    out.append(int(name.split(".")[0]))
                except ValueError:
                    pass
        return sorted(out)

    def latest(self) -> int | None:
        try:
            with open(os.path.join(self.dir, "LATEST"), "rb") as f:
                return int(f.read().decode())
        except FileNotFoundError:
            return None

    def set_latest(self, height: int) -> None:
        _atomic_write(os.path.join(self.dir, "LATEST"), str(height).encode())

    def delete_at(self, stream: int, height: int) -> None:
        try:
            os.unlink(self._path(stream, height))
        except FileNotFoundError:
            pass

    def delete_above(self, height: int) -> None:
        for stream in self.DIRS:
            for h in self.heights(stream):
                if h > height:
                    self.delete_at(stream, h)
        # keep the pointer consistent with the surviving artifacts (the
        # native engine's tomb_above does this implicitly): a crash after
        # rollback but before the next commit must resume at `height`, not
        # point at deltas that no longer exist
        latest = self.latest()
        if latest is not None and latest > height:
            self.set_latest(height)

    def sync(self) -> None:  # every put/set_latest already fsynced
        pass

    def close(self) -> None:
        pass


class NativeBackend:
    """native/chaindb.cc via ctypes (utils/native_chaindb.py)."""

    def __init__(self, data_dir: str, *, read_only: bool = False):
        from celestia_app_tpu.utils import native_chaindb

        self.dir = data_dir
        os.makedirs(data_dir, exist_ok=True)
        self.log = native_chaindb.NativeLog(data_dir, read_only=read_only)

    def put(self, stream: int, height: int, blob: bytes) -> None:
        self.log.put(stream, height, blob)

    def get(self, stream: int, height: int) -> bytes | None:
        return self.log.get(stream, height)

    def heights(self, stream: int) -> list[int]:
        return self.log.heights(stream)

    def latest(self) -> int | None:
        # LATEST is a stream of empty records; rollback tombstones shrink it
        # in the same append-only log as everything else
        return self.log.latest(LATEST)

    def set_latest(self, height: int) -> None:
        # barrier FIRST: the artifact this pointer references must be
        # durable before the pointer (the file engine gets this ordering
        # from its per-op fsyncs). No trailing sync: losing the pointer
        # itself just resumes from the previous height, which the
        # crash-safety contract permits — save_commit's final sync() is
        # the one that makes the whole commit durable.
        prev = self.log.latest(LATEST)
        self.log.sync()
        self.log.put(LATEST, height, b"")
        # retire the superseded pointer record or the LATEST stream (and
        # open-time replay) grows one dead entry per commit forever; tomb
        # AFTER the new put so a crash between the two cannot regress the
        # pointer below `prev`
        if prev is not None and prev != height:
            self.log.tomb_at(LATEST, prev)

    def delete_at(self, stream: int, height: int) -> None:
        self.log.tomb_at(stream, height)

    def delete_above(self, height: int) -> None:
        self.log.tomb_above(height)
        self.log.sync()

    def sync(self) -> None:
        self.log.sync()

    def close(self) -> None:
        self.log.close()


def _detect_backend(data_dir: str, *, read_only: bool = False):
    """CELESTIA_CHAINDB = native / files / auto (default: keep what the home
    already uses; native for fresh homes when the toolchain exists)."""
    choice = os.environ.get("CELESTIA_CHAINDB", "auto")
    if choice == "files":
        return FileBackend(data_dir)
    if choice == "native":
        return NativeBackend(data_dir, read_only=read_only)
    has_native = bool(
        [n for n in _listdir(data_dir) if n.startswith("seg-")]
    )
    has_files = os.path.exists(os.path.join(data_dir, "LATEST")) or (
        os.path.isdir(os.path.join(data_dir, "state"))
    )
    if has_native and not has_files:
        return NativeBackend(data_dir, read_only=read_only)
    if has_files:
        return FileBackend(data_dir)
    from celestia_app_tpu.utils import native_chaindb

    if native_chaindb.available():
        return NativeBackend(data_dir, read_only=read_only)
    return FileBackend(data_dir)


def _listdir(path: str) -> list[str]:
    try:
        return os.listdir(path)
    except FileNotFoundError:
        return []


def wipe_commits(data_dir: str) -> None:
    """Destroy ALL committed state + blocks (both engines' artifacts),
    keeping everything else in the home (WAL, keys, config). This is the
    disk-level wipe the crash-recovery tests and ops runbooks mean by
    "lost its data dir but kept the WAL" — after it, a node rebuilds from
    genesis + WAL replay (or state-sync)."""
    import shutil

    for sub in FileBackend.DIRS.values():
        shutil.rmtree(os.path.join(data_dir, sub), ignore_errors=True)
    # the reactor's durable commit-record store describes the same wiped
    # timeline — stale records must not be served to laggards
    shutil.rmtree(os.path.join(data_dir, "commits"), ignore_errors=True)
    for name in _listdir(data_dir):
        if name == "LATEST" or name == "LOCK" or name.startswith("seg-"):
            try:
                os.unlink(os.path.join(data_dir, name))
            except FileNotFoundError:
                pass


class ChainDB:
    def __init__(self, data_dir: str, *, backend=None, read_only: bool = False):
        self.dir = data_dir
        os.makedirs(data_dir, exist_ok=True)
        self.backend = backend or _detect_backend(data_dir, read_only=read_only)

    def close(self) -> None:
        self.backend.close()

    # -- commits ---------------------------------------------------------

    @staticmethod
    def _encode(doc: dict) -> bytes:
        return gzip.compress(_compact_json(doc))

    @staticmethod
    def _decode(blob: bytes) -> dict:
        return json.loads(gzip.decompress(blob))

    def save_commit(
        self,
        height: int,
        store,
        meta: dict,
        *,
        force_full: bool = False,
    ) -> None:
        """Persist one commit. ``store`` is the live KVStore: its change log
        (drain_changes) becomes the delta; a full snapshot is written at the
        first durable commit, every FULL_INTERVAL commits, or on demand."""
        from celestia_app_tpu import obs
        from celestia_app_tpu.utils import telemetry

        t0 = telemetry.start_timer()
        with obs.span("storage.save_commit", height=height):
            self._save_commit_inner(height, store, meta,
                                    force_full=force_full)
        telemetry.measure_since("storage.save_commit", t0)

    def _save_commit_inner(
        self,
        height: int,
        store,
        meta: dict,
        *,
        force_full: bool = False,
    ) -> None:
        changes = store.drain_changes()
        prior = self.latest_height()
        if prior is not None and height <= prior:
            # timeline rewrite (rollback then re-commit): stale state/delta/
            # block files from the abandoned fork must not survive above this
            # height, or a later load would chain the new fork's deltas into
            # the old fork's (reconstructing a state that existed on neither)
            self.delete_above(height)
            force_full = True
        fulls = self.backend.heights(STATE)
        write_full = (
            force_full
            or not fulls
            or height % FULL_INTERVAL == 0
            or height < max(fulls)  # fork guard belt-and-suspenders
        )
        if write_full:
            doc = {
                "height": height,
                "meta": meta,
                "store": {k.hex(): v.hex() for k, v in store.snapshot().items()},
            }
            self.backend.put(STATE, height, self._encode(doc))
        else:
            doc = {
                "height": height,
                "meta": meta,
                "changes": {
                    k.hex(): (None if v is None else v.hex())
                    for k, v in changes.items()
                },
            }
            self.backend.put(DELTA, height, self._encode(doc))
        # crash point 3 of the commit matrix: the commit artifact (and the
        # block, saved just before) are durable but LATEST still points at
        # height-1 — the crash-safety contract's "between the two" case.
        # Recovery: load() resumes at height-1, WAL replay re-commits.
        if faults.fire("consensus.post_apply_pre_latest",
                       height=height) == "error":
            raise OSError("injected fault: consensus.post_apply_pre_latest")
        self.backend.set_latest(height)
        self._prune(height)
        self.backend.sync()

    def latest_height(self) -> int | None:
        return self.backend.latest()

    def load_commit(self, height: int | None = None):
        """-> (height, store_data, meta); latest when height is None.

        Reconstructs: nearest full snapshot ≤ height, then the delta chain
        (full, height]. Raises FileNotFoundError when the chain is broken
        (pruned past, missing delta)."""
        if height is None:
            height = self.latest_height()
            if height is None:
                raise FileNotFoundError("no committed state on disk")
        fulls = [h for h in self.backend.heights(STATE) if h <= height]
        if not fulls:
            raise FileNotFoundError(f"no snapshot at or below height {height}")
        base = max(fulls)
        doc = self._decode(self.backend.get(STATE, base))
        store = {
            bytes.fromhex(k): bytes.fromhex(v) for k, v in doc["store"].items()
        }
        meta = doc["meta"]
        deltas = [h for h in self.backend.heights(DELTA) if base < h <= height]
        expected = list(range(base + 1, height + 1))
        if deltas != expected:
            raise FileNotFoundError(
                f"broken delta chain for height {height}: have {deltas[:5]}..., "
                f"need {base + 1}..{height}"
            )
        for h in deltas:
            d = self._decode(self.backend.get(DELTA, h))
            for k_hex, v_hex in d["changes"].items():
                k = bytes.fromhex(k_hex)
                if v_hex is None:
                    store.pop(k, None)
                else:
                    store[k] = bytes.fromhex(v_hex)
            meta = d["meta"]
        return height, store, meta

    def delete_above(self, height: int) -> None:
        """Remove commits and blocks above `height` (rollback discards the
        abandoned fork, like the reference's rollback deleting versions)."""
        self.backend.delete_above(height)

    def _prune(self, latest: int) -> None:
        """Prune outside the rollback window, keeping every height in
        [latest-PRUNE_KEEP, latest] reconstructible: the newest full
        snapshot at or below the window floor anchors the delta chain."""
        floor = latest - PRUNE_KEEP
        fulls = self.backend.heights(STATE)
        anchors = [h for h in fulls if h <= floor]
        anchor = max(anchors) if anchors else None
        for h in fulls:
            if h != anchor and h <= floor:
                self.backend.delete_at(STATE, h)
        if anchor is not None:
            for h in self.backend.heights(DELTA):
                if h <= anchor:
                    self.backend.delete_at(DELTA, h)

    # -- blocks ----------------------------------------------------------

    def save_block(self, block: Block,
                   layout_bound: int | None = None) -> None:
        from celestia_app_tpu import obs

        height = block.header.height
        with obs.span("storage.save_block", height=height):
            with obs.span("storage.block.encode"):
                record = _encode_block(block, layout_bound)
            # written AND synced on the caller's thread, before save_commit
            # may move LATEST (the crash-safety contract above)
            with obs.span("storage.block.put", bytes=len(record)):
                self.backend.put(BLOCK, height, record)
                self.backend.sync()

    def load_block(self, height: int) -> Block:
        return self.load_block_and_bound(height)[0]

    def load_block_and_bound(self, height: int) -> tuple[Block, int | None]:
        """The block and the square-size bound its proposer laid it out
        under; None for a block stored before the bound was recorded."""
        from celestia_app_tpu import obs

        # read + CRC + split: the first thing a read of a stored height
        # pays (chain/query.rebuild_square)
        with obs.span("storage.load_block", height=height):
            return self._load_block(height)

    def _load_block(self, height: int) -> tuple[Block, int | None]:
        blob = self.backend.get(BLOCK, height)
        if blob is None:
            raise FileNotFoundError(f"no block at height {height}")
        # the bytes say which codec wrote them: no setting, no suffix
        if blob.startswith(BLOCK_MAGIC):
            return _decode_record(blob)
        if blob.startswith(GZIP_MAGIC):
            # a block an earlier version stored (gzip-JSON, txs base64):
            # read-only compatibility, counted so a home shows how often
            from celestia_app_tpu.chain.consensus import block_from_json
            from celestia_app_tpu.utils import telemetry

            telemetry.incr("storage.legacy_block_reads")
            old = block_from_json(self._decode(blob))
            return Block(header=old.header, txs=tuple(old.txs)), None
        raise BlockRecordError(
            f"block {height}: unknown record magic {blob[:4]!r}")

    def block_heights(self) -> list[int]:
        return self.backend.heights(BLOCK)
