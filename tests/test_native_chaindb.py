"""Native storage engine (native/chaindb.cc) — framing, recovery, parity.

The engine replaces tm-db/LevelDB + the file-per-height store as the
durable byte plane under chain/storage.ChainDB. These tests pin:

- record round-trips, overwrite, tombstones, heights/latest queries
- torn-tail recovery (crash mid-append loses only that append)
- sealed-segment corruption is a LOUD open error, not silent data loss
- segment rotation + dead-segment GC
- writer flock exclusion; read-only opens neither lock nor truncate
- ChainDB-level parity: the same commit/rollback/prune history through the
  native and file backends reconstructs identical state at every height
"""

from __future__ import annotations

import os
import struct
import zlib

import pytest

from celestia_app_tpu.chain import storage
from celestia_app_tpu.chain.state import KVStore
from celestia_app_tpu.utils import native_chaindb



@pytest.fixture(scope="module", autouse=True)
def _native_engine():
    # decided when the first test runs, never at import: every xdist
    # worker imports every test file, and collection must compile nothing
    if not native_chaindb.available():
        pytest.skip("no native toolchain")


def test_concurrent_cold_load_builds_once_and_never_tears(tmp_path):
    """Regression pin for the cold-checkout build race: six processes
    call load() at once on a native/ that holds sources but no .so (what
    `pytest -n 6` does on a fresh clone). All must load a whole library —
    none may dlopen a half-linked file, none may report 'no toolchain'."""
    import shutil
    import subprocess
    import sys

    native = tmp_path / "native"
    native.mkdir()
    for name in ("Makefile", "chaindb.cc"):
        shutil.copy(os.path.join(native_chaindb.NATIVE_DIR, name), native)
    script = (
        "import sys\n"
        "from celestia_app_tpu.utils import native_chaindb as n\n"
        "n.NATIVE_DIR = sys.argv[1]\n"
        "n.LIB = sys.argv[1] + '/libchaindb.so'\n"
        "lib = n.load()\n"
        "assert n.available() and lib.cdb_open is not None\n"
        "print('loaded')\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [
        subprocess.Popen([sys.executable, "-c", script, str(native)],
                         cwd=repo, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
        for _ in range(6)
    ]
    for p in procs:
        out, err = p.communicate(timeout=180)
        assert p.returncode == 0 and "loaded" in out, err[-2000:]
    left = sorted(os.listdir(native))
    assert left == [".build.lock", "Makefile", "chaindb.cc",
                    "libchaindb.so"], left


def _log(tmp_path, name="db", **kw):
    return native_chaindb.NativeLog(str(tmp_path / name), **kw)


def test_roundtrip_overwrite_and_queries(tmp_path):
    log = _log(tmp_path)
    log.put(0, 5, b"five")
    log.put(0, 7, b"seven")
    log.put(1, 5, b"other-stream")
    log.put(0, 5, b"five-v2")  # overwrite
    assert log.get(0, 5) == b"five-v2"
    assert log.get(0, 7) == b"seven"
    assert log.get(0, 6) is None
    assert log.get(1, 5) == b"other-stream"
    assert log.heights(0) == [5, 7]
    assert log.latest(0) == 7
    assert log.latest(2) is None
    log.put(0, 9, b"")  # empty payload is a valid record
    assert log.get(0, 9) == b""
    log.close()


def test_tombstones_and_reopen(tmp_path):
    log = _log(tmp_path)
    for h in range(1, 11):
        log.put(0, h, f"s{h}".encode())
        log.put(2, h, f"b{h}".encode())
    log.tomb_at(0, 3)
    log.tomb_above(7)  # kills h=8..10 in ALL streams
    log.close()

    log = _log(tmp_path)  # replay applies the same tombstones
    assert log.heights(0) == [1, 2, 4, 5, 6, 7]
    assert log.heights(2) == [1, 2, 3, 4, 5, 6, 7]
    assert log.latest(0) == 7
    log.close()


def test_torn_tail_recovery(tmp_path):
    log = _log(tmp_path)
    log.put(0, 1, b"a" * 1000)
    log.put(0, 2, b"b" * 1000)
    log.sync()
    log.close()
    seg = tmp_path / "db" / "seg-00000000.log"
    size = seg.stat().st_size
    with open(seg, "r+b") as f:  # chop mid-record: a crash mid-append
        f.truncate(size - 100)
    log = _log(tmp_path)
    assert log.get(0, 1) == b"a" * 1000
    assert log.get(0, 2) is None  # only the torn append was lost
    log.put(0, 2, b"b2")  # and the log accepts appends again
    log.close()
    log = _log(tmp_path)
    assert log.get(0, 2) == b"b2"
    log.close()


def test_sealed_segment_corruption_is_loud(tmp_path):
    os.environ["CELESTIA_CDB_SEGBYTES"] = "512"
    try:
        log = _log(tmp_path)
        for h in range(20):  # forces several rotations at 512 B/segment
            log.put(0, h, bytes(100))
        assert log.segments() > 1
        log.close()
        segs = sorted((tmp_path / "db").glob("seg-*.log"))
        with open(segs[0], "r+b") as f:  # flip a payload byte mid-segment
            f.seek(40)
            f.write(b"\xff")
        with pytest.raises(IOError, match="sealed segment"):
            _log(tmp_path)
    finally:
        del os.environ["CELESTIA_CDB_SEGBYTES"]


# The engine's frame (docs/FORMATS.md §23.1): magic, kind, stream, height,
# len, then a CRC-32 over header[4:24] + payload — zlib.crc32's value.
_MAGIC = 0xCE1E57DA
_PUT, _TOMB_AT, _TOMB_ABOVE = 0, 1, 2
_HDR = struct.Struct("<IIIQII")
# every residue of the eight-byte step, both sides of a word and of a
# cache line, and sizes that span many steps
_LENGTHS = [0, 1, 7, 8, 9, 63, 64, 65, 4_099, 100_003, (1 << 20) + 5]


def _payload(n: int, salt: int = 0) -> bytes:
    cycle = bytes((i * 131 + salt) & 0xFF for i in range(251))  # odd period
    return (cycle * (n // 251 + 1))[:n]


def _frame(kind, stream, height, payload=b""):
    head = _HDR.pack(_MAGIC, kind, stream, height, len(payload), 0)
    crc = zlib.crc32(head[4:24] + payload)
    return head[:24] + struct.pack("<I", crc) + payload


def _frames(data: bytes):
    """Parse a segment: (kind, stream, height, payload, crc field, frame
    header) per record, asserting the file holds whole records only."""
    out, off = [], 0
    while off < len(data):
        magic, kind, stream, height, n, crc = _HDR.unpack_from(data, off)
        assert magic == _MAGIC
        head = data[off:off + _HDR.size]
        payload = data[off + _HDR.size:off + _HDR.size + n]
        assert len(payload) == n
        out.append((kind, stream, height, payload, crc, head))
        off += _HDR.size + n
    return out


@pytest.mark.parametrize("n", _LENGTHS)
def test_frame_on_disk_is_zlib_crc32(tmp_path, n):
    """Every frame the engine writes — PUT, TOMB_AT, TOMB_ABOVE — carries
    zlib.crc32(header[4:24] + payload), and every payload reads back (before
    and after a replay, which checks the same CRC)."""
    a, b = _payload(n), _payload(n, salt=7)
    log = _log(tmp_path)
    log.put(0, 1, a)
    log.put(1, 2, b)
    log.tomb_at(1, 2)
    log.put(2, 9, b)
    log.tomb_above(5)
    log.put(0, 3, a)
    log.sync()
    for reopened in (False, True):
        if reopened:
            log.close()
            log = _log(tmp_path)
        assert log.get(0, 1) == a and log.get(0, 3) == a
        assert log.get(1, 2) is None and log.get(2, 9) is None
    log.close()
    frames = _frames((tmp_path / "db" / "seg-00000000.log").read_bytes())
    assert [(k, s, h) for k, s, h, *_ in frames] == [
        (_PUT, 0, 1), (_PUT, 1, 2), (_TOMB_AT, 1, 2), (_PUT, 2, 9),
        (_TOMB_ABOVE, 0, 5), (_PUT, 0, 3)]
    assert [p for *_, p, _, _ in frames] == [a, b, b"", b, b"", a]
    for kind, stream, height, payload, crc, head in frames:
        assert crc == zlib.crc32(head[4:24] + payload)


@pytest.mark.parametrize("n", _LENGTHS)
def test_home_framed_by_zlib_crc32_still_opens(tmp_path, n):
    """A home framed in Python with zlib.crc32 — as earlier builds of the
    engine wrote it — opens: every key reads back, a later put lands after
    the old records, and a last-segment record with one payload bit flipped
    is cut off as a torn tail (a CRC that disagrees with zlib at any length
    or alignment would lose the good records instead)."""
    home = tmp_path / "db"
    home.mkdir()
    a, b = _payload(n), _payload(n, salt=3)
    sealed = (_frame(_PUT, 2, 1, b"odd")  # shifts what follows off 8 B
              + _frame(_PUT, 0, 1, a) + _frame(_TOMB_AT, 2, 1))
    bad = bytearray(_frame(_PUT, 0, 4, b or b"x"))
    bad[-1] ^= 0x01  # one payload bit; the CRC field still says the old one
    good = (_frame(_PUT, 1, 2, b) + _frame(_PUT, 2, 7, b"odd")
            + _frame(_TOMB_ABOVE, 0, 6) + _frame(_PUT, 0, 3, a))
    (home / "seg-00000000.log").write_bytes(sealed)
    (home / "seg-00000001.log").write_bytes(good + bytes(bad))

    log = _log(tmp_path)
    assert log.get(0, 1) == a and log.get(1, 2) == b and log.get(0, 3) == a
    assert log.get(2, 1) is None and log.get(2, 7) is None  # tombstoned
    assert log.get(0, 4) is None  # the flipped record was dropped ...
    tail = home / "seg-00000001.log"
    assert tail.read_bytes() == good  # ... and truncated off the tail
    c = _payload(n, salt=11)
    log.put(0, 5, c)
    log.sync()
    log.close()
    assert tail.read_bytes()[:len(good)] == good  # appended after them
    assert _frames(tail.read_bytes())[-1][:4] == (_PUT, 0, 5, c)
    log = _log(tmp_path)
    assert log.get(0, 5) == c and log.get(0, 1) == a
    assert log.heights(0) == [1, 3, 5]
    log.close()


def test_rotation_and_dead_segment_gc(tmp_path):
    os.environ["CELESTIA_CDB_SEGBYTES"] = "512"
    try:
        log = _log(tmp_path)
        for h in range(16):
            log.put(0, h, bytes(200))
        n_before = log.segments()
        assert n_before > 2
        for h in range(12):  # tombstone early records -> early segs die
            log.tomb_at(0, h)
        assert log.segments() < n_before
        # survivors still readable after GC + reopen
        log.close()
        log = _log(tmp_path)
        assert log.heights(0) == [12, 13, 14, 15]
        assert log.get(0, 12) == bytes(200)
        log.close()
    finally:
        del os.environ["CELESTIA_CDB_SEGBYTES"]


def test_gc_forwards_tombstones_no_resurrection(tmp_path):
    """A dying segment's tombstones must keep masking physical records in
    OLDER surviving segments: rollback's TOMB_ABOVE lives in a segment that
    later gets GC'd, and the rolled-back block (physically present in an
    earlier, still-pinned segment) must not resurrect on replay."""
    os.environ["CELESTIA_CDB_SEGBYTES"] = "100"
    try:
        log = _log(tmp_path)
        log.put(2, 8, b"A" * 30)   # fork-A block, height 8   (seg 0, 58 B)
        log.put(2, 1, b"K" * 30)   # keeps seg 0 alive forever (seg 0 -> 116)
        log.tomb_above(5)          # rollback                  (seg 1, 28 B)
        log.put(0, 50, b"L" * 50)  # seg 1's only live record  (seg 1 -> 106)
        log.put(0, 60, b"M" * 30)  # rotation                  (seg 2)
        assert log.segments() == 3
        log.tomb_at(0, 50)         # seg 1 dies -> tomb_above must forward
        assert log.segments() == 2  # the GC actually fired
        assert log.get(2, 8) is None
        log.close()

        log = _log(tmp_path)
        assert log.get(2, 8) is None   # rolled-back block stayed dead
        assert log.get(2, 1) == b"K" * 30
        assert log.get(0, 60) == b"M" * 30
        log.close()
    finally:
        del os.environ["CELESTIA_CDB_SEGBYTES"]


def test_gc_forwarding_never_kills_post_rollback_commits(tmp_path):
    """The fatal variant (caught in review): heights 6,7 are RE-COMMITTED
    after the rollback, then the segment holding TOMB_ABOVE(5) dies.
    Naively re-appending the TOMB_ABOVE at the log tail would re-apply it
    to the live post-rollback commits; the precise per-key forwarding must
    leave them intact while the old fork's bytes stay dead."""
    os.environ["CELESTIA_CDB_SEGBYTES"] = "100"
    try:
        log = _log(tmp_path)
        log.put(0, 6, b"fork-A-6")   # seg 0 (36 B)
        log.put(0, 7, b"fork-A-7")   # seg 0 (72 B)
        log.put(2, 1, b"pin" * 12)   # pins seg 0 forever (-> 136 B)
        log.tomb_above(5)            # rollback             (seg 1, 28 B)
        log.put(0, 99, b"x" * 50)    # seg 1's live record  (-> 106 B)
        log.put(0, 6, b"fork-B-6")   # re-commit            (seg 2)
        log.put(0, 7, b"fork-B-7")   # re-commit            (seg 2)
        log.tomb_at(0, 99)           # seg 1 dies; forwarding runs
        assert log.get(0, 6) == b"fork-B-6"   # live commits survived
        assert log.get(0, 7) == b"fork-B-7"
        log.close()

        log = _log(tmp_path)  # and survive replay
        assert log.get(0, 6) == b"fork-B-6"
        assert log.get(0, 7) == b"fork-B-7"
        assert log.get(2, 1) == b"pin" * 12
        log.close()
    finally:
        del os.environ["CELESTIA_CDB_SEGBYTES"]


def test_writer_flock_and_read_only(tmp_path):
    log = _log(tmp_path)
    log.put(0, 1, b"x")
    log.sync()
    with pytest.raises(IOError, match="locked"):
        _log(tmp_path)  # second writer must be refused
    ro = _log(tmp_path, read_only=True)  # reader is fine alongside
    assert ro.get(0, 1) == b"x"
    with pytest.raises(IOError):
        ro.put(0, 2, b"y")
    ro.close()
    log.close()
    log2 = _log(tmp_path)  # close released the flock
    log2.close()


def test_reader_never_truncates_live_tail(tmp_path):
    log = _log(tmp_path)
    log.put(0, 1, b"committed")
    log.sync()
    seg = tmp_path / "db" / "seg-00000000.log"
    with open(seg, "ab") as f:  # writer mid-append: torn record on disk
        f.write(b"\xda\x57\x1e\xce partial")
    size = seg.stat().st_size
    ro = _log(tmp_path, read_only=True)
    assert ro.get(0, 1) == b"committed"
    ro.close()
    assert seg.stat().st_size == size  # tail untouched by the reader
    log.close()


def _drive(db: storage.ChainDB, blocks=False) -> list[tuple[int, dict]]:
    """One deterministic history: writes, deletes, rollback, re-commit."""
    store = KVStore()
    snaps = []
    for h in range(1, 9):
        store.set(b"h", str(h).encode())
        store.set(f"k{h}".encode(), bytes([h]) * 4)
        if h % 3 == 0:
            store.delete(f"k{h - 1}".encode())
        db.save_commit(h, store, {"height": h})
        snaps.append((h, dict(store.snapshot())))
    # rollback to 5 and take a different fork
    db.delete_above(5)
    _, data, _ = db.load_commit(5)
    store = KVStore(data)
    for h in range(6, 8):
        store.set(b"fork", b"B" + bytes([h]))
        db.save_commit(h, store, {"height": h, "fork": "B"})
        snaps.append((h, dict(store.snapshot())))
    return snaps


def test_chaindb_parity_native_vs_files(tmp_path):
    native = storage.ChainDB(
        str(tmp_path / "n"), backend=storage.NativeBackend(str(tmp_path / "n"))
    )
    files = storage.ChainDB(
        str(tmp_path / "f"), backend=storage.FileBackend(str(tmp_path / "f"))
    )
    _drive(native)
    _drive(files)
    assert native.latest_height() == files.latest_height() == 7
    for h in (5, 6, 7):
        hn, sn, mn = native.load_commit(h)
        hf, sf, mf = files.load_commit(h)
        assert (hn, sn, mn) == (hf, sf, mf)
    native.close()
    # reopen (auto-detect must find the native engine) and check again
    reopened = storage.ChainDB(str(tmp_path / "n"))
    assert isinstance(reopened.backend, storage.NativeBackend)
    assert reopened.load_commit(7)[1] == files.load_commit(7)[1]
    reopened.close()
    files.close()


def test_chaindb_crash_before_latest_pointer(tmp_path):
    """Torn tail between artifact and LATEST record: the node resumes from
    the previous height (the crash-safety contract in storage.py)."""
    db = storage.ChainDB(
        str(tmp_path / "n"), backend=storage.NativeBackend(str(tmp_path / "n"))
    )
    store = KVStore()
    for h in (1, 2):
        store.set(b"h", str(h).encode())
        db.save_commit(h, store, {"height": h})
    db.close()
    # chop the tail back past the height-2 LATEST record (28-byte header,
    # empty payload), leaving the height-2 delta artifact as a torn write
    seg = tmp_path / "n" / "seg-00000000.log"
    with open(seg, "r+b") as f:
        f.truncate(seg.stat().st_size - 24 - 40)
    db = storage.ChainDB(str(tmp_path / "n"))
    assert db.latest_height() == 1
    h, data, meta = db.load_commit()
    assert h == 1 and data[b"h"] == b"1"
    db.close()


def test_auto_detection_env_override(tmp_path, monkeypatch):
    monkeypatch.setenv("CELESTIA_CHAINDB", "files")
    db = storage.ChainDB(str(tmp_path / "x"))
    assert isinstance(db.backend, storage.FileBackend)
    db.close()
    monkeypatch.setenv("CELESTIA_CHAINDB", "native")
    db = storage.ChainDB(str(tmp_path / "y"))
    assert isinstance(db.backend, storage.NativeBackend)
    db.close()
    # legacy file-layout home keeps the file engine under auto
    monkeypatch.delenv("CELESTIA_CHAINDB")
    db = storage.ChainDB(str(tmp_path / "x"))
    assert isinstance(db.backend, storage.FileBackend)
    db.close()
