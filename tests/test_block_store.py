"""The block store's record (chain/storage.py, FORMATS §23.2).

A block is stored as a binary record — magic, version, the header's one
JSON codec, every tx as a length prefix and its raw bytes, a CRC-32 — under
both engines. Pinned here:

- round trips: what `load_block` returns equals what `save_block` took,
  and the header hashes as the chain committed it;
- blocks an earlier version wrote (gzip-JSON, txs base64) still load, are
  counted (`storage.legacy_block_reads`), and live beside new ones;
- a flipped, torn, mislabelled or mis-framed record raises — it never
  parses into another block;
- the record costs no more room than its payload;
- durability: put, then sync, on the caller's thread, before the commit
  may move LATEST;
- the two child spans, and the benchmark's metric files naming them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
import types
import zlib

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from celestia_app_tpu import obs  # noqa: E402
from celestia_app_tpu.chain import storage  # noqa: E402
from celestia_app_tpu.chain.block import Block, Header  # noqa: E402
from celestia_app_tpu.chain.consensus import block_to_json  # noqa: E402
from celestia_app_tpu.chain.state import KVStore  # noqa: E402
from celestia_app_tpu.da.blob import (  # noqa: E402
    Blob,
    is_blob_tx,
    marshal_blob_tx,
    unmarshal_blob_tx,
)
from celestia_app_tpu.da.namespace import Namespace  # noqa: E402
from celestia_app_tpu.utils import native_chaindb, telemetry  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKENDS = ["native", "files"]
LEGACY_COUNTER = "storage.legacy_block_reads"


def _db(kind: str, path) -> storage.ChainDB:
    if kind == "native":
        if not native_chaindb.available():
            pytest.skip("no native toolchain")
        backend = storage.NativeBackend(str(path))
    else:
        backend = storage.FileBackend(str(path))
    return storage.ChainDB(str(path), backend=backend)


@pytest.fixture(params=BACKENDS)
def db(request, tmp_path):
    handle = _db(request.param, tmp_path / "home")
    yield handle
    handle.close()


def _header(height: int = 1, **kw) -> Header:
    fields = dict(
        chain_id="store-27", height=height,
        time_unix=1_700_000_000.123456 + height,
        data_hash=bytes([height % 251]) * 32, square_size=64,
        app_hash=b"\x02" * 32, proposer=b"\x03" * 20, app_version=3,
        last_block_hash=b"\x04" * 32, validators_hash=b"\x05" * 32)
    fields.update(kw)
    return Header(**fields)


def _random(n: int, seed: int = 27) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _blob_tx(size: int) -> bytes:
    return marshal_blob_tx(
        b"signed-pfb-stand-in",
        [Blob(Namespace.v0(b"store27\x01"), _random(size))])


BLOCKS = {
    "no_txs": lambda: Block(_header(), ()),
    "tiny_tx": lambda: Block(_header(), (b"\x00",)),
    "blob_tx_1p2MB": lambda: Block(
        _header(), (b"a-send", _blob_tx(1_200_000), b"")),
    "da_scheme_set": lambda: Block(
        _header(da_scheme=2), (b"under-another-codec",)),
}


def _legacy_record(block: Block) -> bytes:
    """What the parent commit's save_block wrote."""
    return storage.ChainDB._encode(block_to_json(block))


def _legacy_reads() -> int:
    return telemetry.snapshot()["counters"].get(LEGACY_COUNTER, 0)


def _reseal(body: bytes) -> bytes:
    """A record body with a CRC that matches it: what only a writer with
    a fault of its own could store."""
    return body + zlib.crc32(body).to_bytes(4, "little")


# -- round trips -----------------------------------------------------------


@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_block_round_trips_and_hashes_as_committed(db, kind):
    block = BLOCKS[kind]()
    before = _legacy_reads()
    db.save_block(block)
    got = db.load_block(block.header.height)
    assert got == block
    assert got.header.hash() == block.header.hash()
    assert all(type(tx) is bytes for tx in got.txs)
    assert db.block_heights() == [block.header.height]
    assert _legacy_reads() == before   # the new path is not the counted one
    blob = db.backend.get(storage.BLOCK, block.header.height)
    assert blob[:4] == storage.BLOCK_MAGIC and blob[4] == 1
    if kind == "blob_tx_1p2MB":
        assert is_blob_tx(got.txs[1])
        assert unmarshal_blob_tx(got.txs[1]).blobs[0].data \
            == _random(1_200_000)


def test_a_reopened_home_reads_what_it_stored(tmp_path):
    for kind in BACKENDS:
        first = _db(kind, tmp_path / kind)
        block = BLOCKS["blob_tx_1p2MB"]()
        first.save_block(block)
        first.close()
        again = storage.ChainDB(str(tmp_path / kind))   # auto-detected
        assert type(again.backend) is type(first.backend)
        assert again.load_block(1) == block
        again.close()


def test_a_missing_height_is_not_found(db):
    with pytest.raises(FileNotFoundError):
        db.load_block(7)


# -- blocks an earlier version wrote ----------------------------------------


@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_legacy_gzip_json_block_loads_and_is_counted(db, kind):
    block = BLOCKS[kind]()
    db.backend.put(storage.BLOCK, 1, _legacy_record(block))
    db.backend.sync()
    before = _legacy_reads()
    got = db.load_block(1)
    assert got == block and got.header.hash() == block.header.hash()
    assert _legacy_reads() == before + 1
    db.load_block(1)
    assert _legacy_reads() == before + 2


def test_old_and_new_heights_live_side_by_side(db):
    blocks = {h: Block(_header(h), (b"tx-%d" % h, _random(4_000, h)))
              for h in range(1, 7)}
    for h, block in blocks.items():
        if h % 2:
            db.backend.put(storage.BLOCK, h, _legacy_record(block))
        else:
            db.save_block(block)
    before = _legacy_reads()
    for h, block in blocks.items():
        assert db.load_block(h) == block
    assert _legacy_reads() == before + 3
    # a legacy height written again is stored in the new format
    db.save_block(blocks[1])
    assert db.backend.get(storage.BLOCK, 1)[:4] == storage.BLOCK_MAGIC
    assert db.load_block(1) == blocks[1]
    assert _legacy_reads() == before + 3


def test_home_of_the_parent_commit_serves_every_height(
        tmp_path, monkeypatch):
    """Two PFB blocks stored as the parent stored them, the home opened by
    this code, one more block committed into it: every height rebuilds to
    its committed data root, the old ones through the counted path."""
    from obs_drive import drive

    from celestia_app_tpu.chain import query
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.node import Node

    def parents_save_block(self, block, layout_bound=None):
        # the parent's writer knew no bound: its records carry none
        self.backend.put(storage.BLOCK, block.header.height,
                         _legacy_record(block))
        self.backend.sync()

    home = str(tmp_path / "home")
    with monkeypatch.context() as patched:
        patched.setattr(storage.ChainDB, "save_block", parents_save_block)
        out = drive("host", home, blocks=2)
    assert out["height"] == 2

    app = App(chain_id=out["chain_id"], engine="host", data_dir=home)
    try:
        app.load()
        for h in (1, 2):
            assert app.db.backend.get(storage.BLOCK, h)[:2] \
                == storage.GZIP_MAGIC
        block, _ = Node(app).produce_block(t=1_700_000_010.0)
        assert app.db.backend.get(storage.BLOCK, 3)[:4] \
            == storage.BLOCK_MAGIC
        before = _legacy_reads()
        for h in (1, 2, 3):
            stored, square, entry = query.build_prover_entry(app, h)
            assert entry.data_root == stored.header.data_hash
            assert stored.header.height == h
        assert len(stored.txs) == 0 and stored == block
        assert _legacy_reads() == before + 2
    finally:
        app.close()


# -- corruption fails loudly ------------------------------------------------


def _flip(record: bytes, offset: int) -> bytes:
    out = bytearray(record)
    out[offset] ^= 0x40
    return bytes(out)


def _ntx_at(record: bytes) -> int:
    """Where the tx count stands; the first tx's length follows it."""
    return 9 + int.from_bytes(record[5:9], "little")


def _u32_at(record: bytes, at: int) -> int:
    return int.from_bytes(record[at:at + 4], "little")


def _with_u32(record: bytes, at: int, value: int) -> bytes:
    """One integer of the framing rewritten, under a CRC that matches."""
    body = bytearray(record[:-4])
    body[at:at + 4] = value.to_bytes(4, "little")
    return _reseal(bytes(body))


DAMAGE = {
    "flipped_header_byte": lambda r: _flip(r, 12),
    "flipped_payload_byte": lambda r: _flip(r, -2_000),
    "flipped_length_byte": lambda r: _flip(r, _ntx_at(r) + 4),
    "flipped_crc_byte": lambda r: _flip(r, -1),
    "truncated_tail": lambda r: r[:-1],
    "truncated_half": lambda r: r[:len(r) // 2],
    "truncated_to_magic": lambda r: r[:4],
    "empty": lambda r: b"",
    "wrong_magic": lambda r: b"CBLX" + r[4:],
    "json_not_gzipped": lambda r: json.dumps(
        block_to_json(BLOCKS["tiny_tx"]())).encode(),
    "unknown_version": lambda r: _reseal(r[:4] + b"\x02" + r[5:-4]),
    # the first tx claims more than the record holds
    "length_past_the_end": lambda r: _with_u32(r, _ntx_at(r) + 4, len(r)),
    # ... or 1 byte less: the framing after it is then read from payload
    # bytes, and must not come out as some other block
    "length_one_short": lambda r: _with_u32(
        r, _ntx_at(r) + 4, _u32_at(r, _ntx_at(r) + 4) - 1),
    "tx_count_one_more": lambda r: _with_u32(
        r, _ntx_at(r), _u32_at(r, _ntx_at(r)) + 1),
    "stray_bytes_before_crc": lambda r: _reseal(r[:-4] + b"\x00"),
    "appended_garbage": lambda r: r + b"\x00\x00",
    "legacy_gzip_torn": lambda r: _legacy_record(BLOCKS["tiny_tx"]())[:-6],
}


@pytest.mark.parametrize("damage", sorted(DAMAGE))
def test_a_damaged_record_raises_and_yields_no_block(db, damage):
    block = Block(_header(), (_random(3_000, 1), b"second", _random(500, 2)))
    record = storage._encode_block(block)
    assert storage._decode_record(record) == (block, None)
    bad = DAMAGE[damage](record)
    assert bad != record
    db.backend.put(storage.BLOCK, 1, bad)
    db.backend.sync()
    # ValueError (BlockRecordError, JSON), OSError/EOFError (gzip's own):
    # what the readers of load_block already catch for a bad store
    with pytest.raises((ValueError, OSError, EOFError, zlib.error)) as err:
        db.load_block(1)
    if not damage.startswith("legacy"):
        assert isinstance(err.value, storage.BlockRecordError), err.value


# -- size -----------------------------------------------------------------


@pytest.mark.parametrize("n_txs,tx_bytes", [(6, 300_000), (64, 2_000),
                                            (0, 0)])
def test_record_is_no_larger_than_payload_plus_1k(n_txs, tx_bytes):
    block = Block(_header(), tuple(_random(tx_bytes, i)
                                   for i in range(n_txs)))
    record = storage._encode_block(block)
    assert len(record) <= n_txs * tx_bytes + 1024
    if n_txs:   # and smaller than what it replaces, on high-entropy bytes
        assert len(record) < len(_legacy_record(block))


# -- durability -------------------------------------------------------------


class _Recording:
    """A backend that notes every call and the thread that made it."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = []

    def __getattr__(self, name):
        target = getattr(self.inner, name)
        if name not in ("put", "sync", "set_latest"):
            return target

        def noted(*args):
            self.calls.append((name, args[:2], threading.get_ident()))
            return target(*args)
        return noted


def test_block_is_put_and_synced_on_the_callers_thread_before_latest(
        tmp_path):
    for kind in BACKENDS:
        db = _db(kind, tmp_path / kind)
        db.backend = rec = _Recording(db.backend)
        threads_before = threading.active_count()
        block = BLOCKS["tiny_tx"]()
        store = KVStore()
        store.set(b"k", b"v")
        db.save_block(block)
        assert [c[:2] for c in rec.calls] == [
            ("put", (storage.BLOCK, 1)), ("sync", ())]
        assert db.latest_height() is None    # the block alone moves no pointer
        db.save_commit(1, store, {"app_version": 3})
        names = [c[0] for c in rec.calls]
        assert names[:2] == ["put", "sync"]
        assert names.index("set_latest") > 1
        assert rec.calls[2][:2] == ("put", (storage.STATE, 1))
        assert {c[2] for c in rec.calls} == {threading.get_ident()}
        assert threading.active_count() == threads_before
        assert db.latest_height() == 1
        db.close()


# -- spans and the benchmark's readers ---------------------------------------


def _span_rows(db, block):
    tables = telemetry.TraceTables()
    with obs.span("commit", traces=tables):
        db.save_block(block)
    return tables.read("spans", 0, 100)


def test_save_block_prices_encode_against_put(db):
    rows = _span_rows(db, BLOCKS["blob_tx_1p2MB"]())
    by_name = {r["name"]: r for r in rows}
    assert sorted(by_name) == ["commit", "storage.block.encode",
                               "storage.block.put", "storage.save_block"]
    whole = by_name["storage.save_block"]
    assert whole["parent_id"] == by_name["commit"]["span_id"]
    assert whole["height"] == 1
    parts = [by_name["storage.block.encode"], by_name["storage.block.put"]]
    assert all(p["parent_id"] == whole["span_id"] for p in parts)
    assert by_name["storage.block.put"]["bytes"] == len(
        db.backend.get(storage.BLOCK, 1))
    # the two children lie inside save_block; how nearly they fill it is
    # a wall-clock reading, and the chip's to show (PERF.md §5)
    assert sum(p["dur_ms"] for p in parts) <= whole["dur_ms"] + 0.01


@pytest.mark.parametrize("metric,span", [
    ("block_encode_ms", "storage.block.encode"),
    ("block_put_ms", "storage.block.put"),
])
def test_benchmark_metric_reads_the_span_of_that_name(db, metric, span):
    """benchmark/metrics/<metric>.json names the call site letter for
    letter, the manifest lists it for the two produce cells, and the
    benchmark's own reducer turns a window's totals into ms a block."""
    with open(os.path.join(REPO, "benchmark", "metrics",
                           f"{metric}.json"), encoding="utf-8") as f:
        spec = json.load(f)
    assert spec == {"reducer": "span_total", "spans": [span],
                    "per_unit": "blocks"}
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[metric]
    # a later cell may be appended to the list (k64-pfb-light was): the
    # entry is pinned without it, the two produce cells must be IN it
    cells = entry.pop("workloads")
    assert {"k64-pfb-full", "k128-pfb-full"} <= set(cells)
    assert entry == {
        "name": metric, "unit": "ms/block", "better": "lower",
        "source": "program_span", "layer": "block lifecycle",
        "moves": "block_p90"}

    module_spec = importlib.util.spec_from_file_location(
        "bench_span_total",
        os.path.join(REPO, "benchmark", "reducers", "span_total.py"))
    reducer = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(reducer)

    def totals():
        return {k: v for k, v in telemetry.snapshot()["counters"].items()
                if k.startswith("obs.span_")}

    before = totals()
    blocks = 3
    for h in range(1, blocks + 1):
        rows = _span_rows(db, Block(_header(h), (_random(200_000, h),)))
        assert span in [r["name"] for r in rows]
    after = totals()
    delta = {k: v - before.get(k, 0) for k, v in after.items()}
    assert delta[f'obs.span_n{{name="{span}"}}'] == blocks
    reading = types.SimpleNamespace(counters=delta,
                                    units={"blocks": blocks})
    value = reducer.read(spec, reading)
    whole = reducer.read({"spans": ["storage.save_block"],
                          "per_unit": "blocks"}, reading)
    assert value is not None and 0 < value <= whole
    # a program without the span (the parent) reads 0 or nothing, and
    # nothing raises
    older = types.SimpleNamespace(
        counters={'obs.span_n{name="commit"}': 3}, units={"blocks": 3})
    assert reducer.read(spec, older) == 0.0
    assert reducer.read(spec, types.SimpleNamespace(
        counters={}, units={"blocks": 3})) is None
