"""Mesh plane: one engine decision, sharded production, device-resident
entries.

Runs on the 8-virtual-device CPU mesh (conftest sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; its
``shard_small`` fixture lowers the sharding rule's threshold so squares
of k=2 and up shard as k >= 256 squares do on chips). The contract under
test: an App takes one of four engine names and keeps one of three; the
device engine splits a square over the chips exactly when the sharding
rule holds; the sharded program is bit-identical to the single-device
and host engines at every co-supported size (entries, DAH roots, data
roots, row+col cell proofs) and device-resident until a proof/serve path
actually needs host bytes (pinned by the ``edscache.host_crossings``
counter).
"""

import base64
import os

import numpy as np
import pytest

from celestia_app_tpu import appconsts
from celestia_app_tpu.da import edscache
from celestia_app_tpu.utils import telemetry


def _random_ods(k: int, seed: int) -> np.ndarray:
    ods = np.random.default_rng(seed).integers(
        0, 256, size=(k, k, 512), dtype=np.uint8)
    ods[:, :, 0] = 0
    ods[:, :, 1:19] = 0
    return ods


def _counter(name: str) -> int:
    return telemetry.snapshot().get("counters", {}).get(name, 0)


def _spans(name: str) -> int:
    return _counter(f'obs.span_n{{name="{name}"}}')


def _assert_proofs_equal(a, b):
    sa, pa = a
    sb, pb = b
    assert sa == sb
    assert (pa.start, pa.end, pa.total) == (pb.start, pb.end, pb.total)
    assert pa.nodes == pb.nodes


# ---------------------------------------------------------------------------
# the engine decision: named once at the App, sharded by what it can see
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,kept", [
    ("host", "host"), ("device", "device"), ("auto", "auto"),
    ("mesh", "device"),
])
def test_app_keeps_one_of_three_engines(name, kept):
    from celestia_app_tpu.chain.app import App

    assert App(chain_id="engine-names", engine=name).engine == kept


@pytest.mark.parametrize("name", ["Device", "gpu", ""])
def test_app_refuses_an_unknown_engine(name):
    """A mistyped engine name raises at construction instead of falling
    through to the host path."""
    from celestia_app_tpu.chain.app import App

    with pytest.raises(ValueError, match="unknown engine"):
        App(chain_id="engine-names", engine=name)
    with pytest.raises(ValueError, match="unknown engine"):
        edscache.compute_entry(_random_ods(2, 0), name)


@pytest.mark.parametrize("k,sharded", [(8, False), (16, True)])
@pytest.mark.parametrize("engine", ["device", "auto", "mesh"])
def test_the_sharding_rule_decides_on_k(engine, k, sharded, shard_small):
    """With the rule's threshold at 16, a square on either side of it
    takes the single-chip or the sharded program, whichever device
    engine is named: pinned by the extend's span, the entry's chips and
    its level passes."""
    ods = _random_ods(k, 7)
    m0, d0 = _spans("mesh.extend.run"), _spans("da.extend.run")
    p0 = _counter("mesh.sharded_level_passes")
    with shard_small(min_k=16):
        entry = edscache.compute_entry(ods, engine)
        entry.warm()
    assert isinstance(entry, edscache.DeviceEntry)
    assert _spans("mesh.extend.run") - m0 == int(sharded)
    assert _spans("da.extend.run") - d0 == int(not sharded)
    assert entry.chips == (8 if sharded else 1)
    assert _counter("mesh.sharded_level_passes") - p0 == 2 * sharded
    assert entry.data_root == edscache.compute_entry(ods, "host").data_root


# ---------------------------------------------------------------------------
# bit-identity: mesh entry == host/single-device entry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [8, 32])
def test_mesh_entry_bit_identical_to_host(k, shard_small):
    """Sharded compute_entry == host compute_entry byte for byte:
    EDS, row/col roots, data root, and row+col cell proofs."""
    ods = _random_ods(k, 1000 + k)
    host = edscache.compute_entry(ods, "host")
    with shard_small():
        mesh = edscache.compute_entry(ods, "device")
    assert isinstance(mesh, edscache.DeviceEntry) and mesh.chips == 8
    p0 = _counter("mesh.sharded_level_passes")

    assert mesh.data_root == host.data_root
    assert mesh.dah.row_roots == host.dah.row_roots
    assert mesh.dah.col_roots == host.dah.col_roots
    assert mesh.k == host.k == k
    np.testing.assert_array_equal(mesh.eds.squares, host.eds.squares)

    ph, pm = host.get_prover(), mesh.get_prover()
    ch, cm = host.get_col_prover(), mesh.get_col_prover()
    assert _counter("mesh.sharded_level_passes") - p0 == 2
    rng = np.random.default_rng(k)
    for _ in range(4):
        r, c = (int(x) for x in rng.integers(0, 2 * k, size=2))
        _assert_proofs_equal(ph.prove_cell(r, c), pm.prove_cell(r, c))
        # col-axis proof: cell (r, c) at (c, r) of the transpose
        _assert_proofs_equal(ch.prove_cell(c, r), cm.prove_cell(c, r))


def test_mesh_engine_unshardable_square_degrades(shard_small):
    """The k=1 empty block has nothing to shard, even with the rule's
    threshold below it: the device engine extends it on one chip, not
    raise — a validator committing an empty height stays alive."""
    from celestia_app_tpu.da import dah as dah_mod
    from celestia_app_tpu.da import square as square_mod

    ods = dah_mod.shares_to_ods(square_mod.empty_square().share_bytes())
    m0 = _spans("mesh.extend.run")
    p0 = _counter("mesh.sharded_level_passes")
    with shard_small(min_k=1):
        entry = edscache.compute_entry(ods, "mesh")
        entry.warm()
    host = edscache.compute_entry(ods, "host")
    assert entry.data_root == host.data_root
    assert entry.chips == 1
    assert _spans("mesh.extend.run") == m0
    assert _counter("mesh.sharded_level_passes") == p0


# ---------------------------------------------------------------------------
# device residency: host crossings only when a proof/serve path needs bytes
# ---------------------------------------------------------------------------


def test_device_residency_and_host_crossings(shard_small):
    """The extend->commit->warm chain never crosses the host boundary,
    and neither does a sample of an entry that has no host copy: its
    cells are cut on the chips and the entry stays "device". A host
    prover asked for outright still materializes (counted), and later
    proofs from it are free."""
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.das.server import SampleCore

    k = 8
    with shard_small():
        entry = edscache.compute_entry(_random_ods(k, 42), "device")
    assert entry.residency() == "device" and entry.chips == 8

    c0 = _counter("edscache.host_crossings")
    p0 = _counter("mesh.sharded_level_passes")
    # what the lifecycle reads at Prepare/Process/commit: commitments
    assert len(entry.dah.row_roots) == 2 * k
    assert len(entry.data_root) == 32
    # the warmer's per-scheme hook: device-side level passes only
    entry.warm()
    assert entry.warmed()
    assert _counter("mesh.sharded_level_passes") - p0 == 2
    assert _counter("edscache.host_crossings") == c0
    assert entry.residency() == "device"

    # a served sample, either axis: gathered, nothing materializes
    app = App(chain_id="mesh-residency")
    app.init_chain({"time_unix": 0})
    core = SampleCore(app)
    core.seed_cache_entry(3, entry)
    core.sample(3, 0, 0)
    core.sample(3, 5, 9, axis="col")
    assert _counter("edscache.host_crossings") == c0
    assert entry.residency() == "device"
    assert core.availability(3)["residency"] == "device"

    # a prover asked for outright: EDS + row levels materialize (2
    # counted crossings)
    entry.get_prover().prove_cell(0, 0)
    after_first = _counter("edscache.host_crossings")
    assert after_first > c0
    assert entry.residency() == "device+host"
    # steady state: pure index arithmetic, zero further crossings
    entry.get_prover().prove_cell(1, 3)
    entry.get_prover().prove_cell(2 * k - 1, 2 * k - 1)
    assert _counter("edscache.host_crossings") == after_first


def test_device_entry_serves_das_with_crossings_pinned(shard_small):
    """A seeded device-resident entry serves /das/* with a
    host_crossings delta of exactly 0 from the first sample on — its
    cells are gathered on the chips — and the availability record says
    "device"; once a host prover has been asked for (one counted
    materialization an orientation), samples come from it, still
    crossing-free."""
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.das.server import SampleCore

    k = 8
    app = App(chain_id="mesh-serve")
    app.init_chain({"time_unix": 0})
    core = SampleCore(app)
    with shard_small():
        entry = edscache.compute_entry(_random_ods(k, 99), "device")
    p0 = _counter("mesh.sharded_level_passes")
    entry.warm()
    assert _counter("mesh.sharded_level_passes") - p0 == 2
    core.seed_cache_entry(5, entry)

    host = edscache.compute_entry(_random_ods(k, 99), "host")
    c0 = _counter("edscache.host_crossings")
    g0 = _counter("das.samples_gathered")
    first = core.sample(5, 0, 0)
    first_col = core.sample(5, 7, 1, axis="col")
    assert _counter("edscache.host_crossings") == c0, \
        "a copy-less device entry must serve its samples crossing-free"
    assert _counter("das.samples_gathered") - g0 == 2
    assert core.availability(5)["residency"] == "device"
    # a prover asked for outright materializes, counted, per orientation
    entry.get_prover()
    entry.get_col_prover()
    c1 = _counter("edscache.host_crossings")
    assert c1 > c0
    again = core.sample(5, 3, 4)
    col = core.sample(5, 2, 6, axis="col")
    assert _counter("edscache.host_crossings") == c1, \
        "a materialized device entry must serve later samples crossing-free"
    assert _counter("das.samples_gathered") - g0 == 2
    # and the served docs equal the host engine's byte for byte
    core_h = SampleCore(app)
    core_h.seed_cache_entry(5, host)
    assert first == core_h.sample(5, 0, 0)
    assert first_col == core_h.sample(5, 7, 1, axis="col")
    assert again == core_h.sample(5, 3, 4)
    assert col == core_h.sample(5, 2, 6, axis="col")
    # the availability record surfaces the residency
    assert core.availability(5)["residency"] == "device+host"


# ---------------------------------------------------------------------------
# a sample is cut where the square lives (PR 37)
# ---------------------------------------------------------------------------


def _seeded_cores(k: int, seed: int, kind: str, shard_small):
    """(entry under test, its SampleCore, the host engine's SampleCore)
    over one random square at height 7. `kind`: "mesh" (rows split over
    the 8 virtual devices) or "one-chip" (the single-device program's
    resident square in an entry built without `eds_fetch`)."""
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.das.server import SampleCore

    ods = _random_ods(k, seed)
    if kind == "mesh":
        with shard_small():
            entry = edscache.compute_entry(ods, "device")
        assert entry.chips == 8
    else:
        started = edscache.compute_entry(ods, "device")
        entry = edscache.DeviceEntry(started._eds_dev, started.dah,
                                     started.data_root)
        assert entry.chips == 1
    app = App(chain_id=f"gather-{kind}-{k}")
    app.init_chain({"time_unix": 0})
    core, core_h = SampleCore(app), SampleCore(app)
    core.seed_cache_entry(7, entry)
    core_h.seed_cache_entry(7, edscache.compute_entry(ods, "host"))
    return entry, core, core_h


@pytest.mark.parametrize("axis", ["row", "col"])
@pytest.mark.parametrize("k", [8, 16, 32])
@pytest.mark.parametrize("kind", ["mesh", "one-chip"])
def test_gathered_samples_equal_the_host_engines(kind, k, axis,
                                                 shard_small):
    """Docs served from an entry with no host copy — its cells and proof
    nodes gathered by one program where the square lives — equal the
    host engine's byte for byte: the corners, a cell of each parity
    quadrant, a repeated cell, a batch of 1 and of 17 (bucket padding),
    and a batch whose out-of-range and withheld cells keep their place
    as error docs. Nothing materializes."""
    w = 2 * k
    entry, core, core_h = _seeded_cores(k, 3700 + k, kind, shard_small)
    quadrants = [(1, 2), (3, k + 1), (k + 2, 5), (k + 3, k + 4)]
    rng = np.random.default_rng(k)
    seventeen = [(int(r), int(c))
                 for r, c in rng.integers(0, w, size=(17, 2))]
    batches = [
        [(0, 0), (w - 1, w - 1)] + quadrants + [(3, k + 1)],
        [(w - 1, 0)],
        seventeen,
        [(0, w - 1), (w, 0), (2, 2), (-1, 3), (5, 6), (k, k)],
    ]
    for c in (core, core_h):
        c.withhold(7, [(5, 6)])
    core_h.sample(7, 0, 0, axis=axis)  # the host core's first touch
    c0 = _counter("edscache.host_crossings")
    g0 = _counter("das.samples_gathered")
    d0 = _counter("das.gather_dispatches")
    n0 = _counter('obs.span_n{name="das.gather"}')
    b0 = _counter('obs.span_n{name="das.build_provers"}')
    p0 = _counter("mesh.sharded_level_passes")
    want_gathered = 0
    for cells in batches:
        got = core.sample_many(7, cells, axis=axis)
        assert got == core_h.sample_many(7, cells, axis=axis)
        errors = [i for i, doc in enumerate(got["samples"])
                  if "error" in doc]
        want_gathered += len(cells) - len(errors)
    assert errors == [1, 3, 4]  # the last batch: places kept
    assert [(d["row"], d["col"]) for d in got["samples"]] == batches[-1]
    assert _counter("das.samples_gathered") - g0 == want_gathered
    assert _counter("das.gather_dispatches") - d0 == len(batches)
    assert _counter('obs.span_n{name="das.gather"}') - n0 == len(batches)
    assert _counter('obs.span_n{name="das.build_provers"}') == b0
    # the gathered orientation's level pass, split where the square is
    assert _counter("mesh.sharded_level_passes") - p0 == (kind == "mesh")
    assert _counter("edscache.host_crossings") == c0
    assert entry.residency() == "device"
    assert core.availability(7)["residency"] == "device"
    assert core.availability(7)["samples_served"] == want_gathered
    assert core.availability(7)["withheld_refusals"] == 1


@pytest.mark.parametrize("holds", ["started-copy", "landed-copy",
                                   "host-prover", "host-entry"])
def test_an_entry_with_host_bytes_serves_from_them(holds, shard_small):
    """The gather is for an entry whose square lives only on the
    chip(s): one whose engine started a host copy (every one-chip
    device engine), whose copy landed, or whose host prover was built,
    serves as before — `das.samples_gathered` does not move — and so
    does the host engine's entry."""
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.das.server import SampleCore

    k = 8
    ods = _random_ods(k, 3737)
    if holds == "host-entry":
        entry = edscache.compute_entry(ods, "host")
    elif holds == "host-prover":
        with shard_small():
            entry = edscache.compute_entry(ods, "device")
        p0 = _counter("mesh.sharded_level_passes")
        entry.get_prover()
        entry.get_col_prover()
        assert _counter("mesh.sharded_level_passes") - p0 == 2
    else:
        entry = edscache.compute_entry(ods, "device")
        assert entry._eds_fetch is not None
        if holds == "landed-copy":
            entry.eds
            assert entry._eds_fetch is None
    app = App(chain_id="gather-bypass")
    app.init_chain({"time_unix": 0})
    core, core_h = SampleCore(app), SampleCore(app)
    core.seed_cache_entry(2, entry)
    core_h.seed_cache_entry(2, edscache.compute_entry(ods, "host"))
    g0 = _counter("das.samples_gathered")
    d0 = _counter("das.gather_dispatches")
    b0 = _counter('obs.span_n{name="das.build_provers"}')
    cells = [(0, 0), (3, 12), (15, 15)]
    for axis in ("row", "col"):
        assert entry.proves_on_host(axis == "col")
        assert core.sample_many(2, cells, axis=axis) == \
            core_h.sample_many(2, cells, axis=axis)
    assert _counter("das.samples_gathered") == g0
    assert _counter("das.gather_dispatches") == d0
    # the row prover's first touch of each served height, as before
    assert _counter('obs.span_n{name="das.build_provers"}') - b0 == 2


# ---------------------------------------------------------------------------
# e2e: a mesh-engine chain through Prepare/Process/commit/serve
# ---------------------------------------------------------------------------


def test_mesh_engine_chain_matches_host_chain(shard_small):
    """Two chains over the same txs — engine="mesh" (kept as "device",
    its square split over the devices) vs engine="host" — commit
    identical headers, and their served samples are byte-identical. This
    is the end-to-end PrepareProposal / ProcessProposal / serve pin at a
    CI-affordable size."""
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.client.tx_client import Signer
    from celestia_app_tpu.chain.tx import MsgSend
    from celestia_app_tpu.das.server import SampleCore

    def chain(engine):
        priv = PrivateKey.from_seed(b"mesh-e2e")
        addr = priv.public_key().address()
        app = App(chain_id="mesh-e2e", engine=engine)
        app.init_chain({
            "time_unix": 1_700_000_000.0,
            "accounts": [{"address": addr.hex(), "balance": 10**12}],
            "validators": [{"operator": addr.hex(), "power": 1}],
        })
        node = Node(app)
        # attach BEFORE committing: in-memory nodes serve from the
        # commit warmer's seed (no block store to rebuild from)
        core = node.attach_das_core(SampleCore(app))
        signer = Signer("mesh-e2e")
        signer.add_account(priv, number=0)
        # enough txs that the square is 2x2, so there is a square to split
        for _ in range(3):
            tx = signer.create_tx(addr, [MsgSend(addr, addr, 1)],
                                  fee=2000, gas_limit=100_000)
            signer.accounts[addr].sequence += 1
            node.broadcast_tx(tx.encode())
        blk, _ = node.produce_block(t=1_700_000_001.0)
        assert blk.header.square_size == 2 and len(blk.txs) == 3
        app.da_warmer.wait_idle(30)
        return app, core, blk

    p0 = _counter("mesh.sharded_level_passes")
    with shard_small():
        app_m, core_m, blk_m = chain("mesh")
    assert app_m.engine == "device"
    assert core_m._entry(1).cache_entry.chips == 2
    # the warmer's two level passes, over the split square
    assert _counter("mesh.sharded_level_passes") - p0 == 2
    app_h, core_h, blk_h = chain("host")
    assert blk_m.header.hash() == blk_h.header.hash()
    assert app_m.last_app_hash == app_h.last_app_hash
    assert core_m.sample(1, 0, 0) == core_h.sample(1, 0, 0)
    assert core_m.sample(1, 1, 1, axis="col") == \
        core_h.sample(1, 1, 1, axis="col")


def test_mesh_height_serves_a_whole_light_round(tmp_path, shard_small):
    """The benchmark's loop at 8x8 on the forced 8-device mesh: one PFB
    block through broadcast_txs -> produce_block on engine="mesh", then a
    light node's round of 16 cells at the new height, racing the prover
    warmer. 16 of 16 samples are served, verify against the header's
    data hash and equal the host engine's byte for byte, row and column
    axis; both level passes ran sharded (the square never left the mesh
    for them), under spans and a counter of their own, and the warmer
    met no error. On real chips this is what PR 35's rehearsal saw fail
    24 heights of 24 (tests/test_tpu_compile.py asks the chip's compiler
    for the same pass)."""
    import sys

    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.da import sampling
    from celestia_app_tpu.da.dah import DataAvailabilityHeader
    from celestia_app_tpu.da.proof_device import rows_sharded_over
    from celestia_app_tpu.das.server import SampleCore
    from celestia_app_tpu.utils import merkle_host, nmt_host

    sys.path.insert(0, os.path.dirname(__file__))
    from obs_drive import _T0, pfb_rounds

    chain_id = "mesh-light-round"
    privs, (raws,), _namespaces = pfb_rounds(chain_id, 1)
    addrs = [p.public_key().address() for p in privs]
    cells = [(int(r), int(c)) for r, c in
             np.random.default_rng(36).integers(0, 16, size=(16, 2))]

    def height_one(engine):
        app = App(chain_id=chain_id, engine=engine,
                  data_dir=str(tmp_path / engine))
        app.init_chain({
            "time_unix": _T0,
            "accounts": [{"address": a.hex(), "balance": 10**15}
                         for a in addrs],
            "validators": [{"operator": addrs[0].hex(), "power": 10}],
            "gov_max_square_size": 8,
        })
        node = Node(app)
        core = node.attach_das_core(SampleCore(app))
        assert [r.code for r in node.broadcast_txs(raws)] == [0] * 4
        block, _ = node.produce_block(t=_T0 + 1)
        assert block.header.square_size == 8
        # the light round comes with the commit: no wait for the warmer
        header = core.header(1)
        rows = core.sample_many(1, cells)
        cols = core.sample_many(1, cells, axis="col")
        assert app.da_warmer.wait_idle(60)
        return app, core, block, header, rows, cols

    passes0 = _counter("mesh.sharded_level_passes")
    errors0 = _counter("edscache.warm_errors")
    runs0 = _counter('obs.span_n{name="mesh.levels.run"}')
    extends0 = _counter('obs.span_n{name="mesh.extend.run"}')
    with shard_small():
        app_m, core_m, block, header, rows, cols = height_one("mesh")
    try:
        entry = core_m._entry(1).cache_entry
        assert isinstance(entry, edscache.DeviceEntry)
        mesh, axis = rows_sharded_over(entry._eds_dev)
        assert mesh.shape[axis] == 8 and entry.warmed()
        assert all(len(level[0].sharding.device_set) == 8
                   for level in entry._levels_dev + entry._col_levels_dev)
        assert _counter("mesh.sharded_level_passes") - passes0 == 2
        assert _counter('obs.span_n{name="mesh.levels.run"}') - runs0 == 2
        assert _counter('obs.span_n{name="mesh.extend.run"}') - extends0 == 1
        assert _counter("edscache.warm_errors") == errors0
    finally:
        app_m.close()

    # 16 of 16, each against the header's data hash
    row_roots = [bytes.fromhex(r) for r in header["row_roots"]]
    col_roots = [bytes.fromhex(c) for c in header["col_roots"]]
    assert merkle_host.hash_from_leaves(row_roots + col_roots) == \
        block.header.data_hash
    dah = DataAvailabilityHeader(tuple(row_roots), tuple(col_roots))
    assert len(rows["samples"]) == 16
    for doc in rows["samples"]:
        assert "error" not in doc, doc
        proof = nmt_host.NmtRangeProof(
            start=doc["proof"]["start"], end=doc["proof"]["end"],
            total=doc["proof"]["total"],
            nodes=[base64.b64decode(n) for n in doc["proof"]["nodes"]])
        assert sampling.verify_sample(
            dah, doc["row"], doc["col"], base64.b64decode(doc["share"]),
            proof)
    assert not any("error" in doc for doc in cols["samples"])

    app_h, _core, block_h, header_h, rows_h, cols_h = height_one("host")
    app_h.close()
    assert block_h.header.hash() == block.header.hash()
    assert (header, rows, cols) == (header_h, rows_h, cols_h)


# ---------------------------------------------------------------------------
# mesh-sharded repair + prover ops stay bit-identical
# ---------------------------------------------------------------------------


def test_mesh_sharded_ops_bit_identical(shard_small):
    """With the mesh active (min_k lowered), the repair sweep's two
    device programs — the fused decode matmul and the batched NMT root
    reduction — run with their batch dimension sharded over the device
    list, and a full 2D repair equals the scalar engine byte for byte."""
    from celestia_app_tpu.da import repair as repair_mod
    from celestia_app_tpu.ops import nmt as nmt_ops

    k = 8
    entry = edscache.compute_entry(_random_ods(k, 321), "host")
    eds = entry.eds.squares

    # batched NMT roots, sharded vs not: identical bytes
    slabs = np.stack([eds[i] for i in range(2 * k)])
    idx = list(range(2 * k))
    plain = nmt_ops.eds_axis_roots(slabs, idx, k)
    # whole-columns erasure: one shared pattern, mesh-sharded decode
    present = np.ones((2 * k, 2 * k), dtype=bool)
    present[:, k + 2:2 * k] = False  # k-2 columns lost
    garbled = eds.copy()
    garbled[~present] = 0
    row_roots = [bytes(r) for r in entry.dah.row_roots]
    col_roots = [bytes(c) for c in entry.dah.col_roots]
    with shard_small(min_k=4):
        s0 = _counter("mesh.batch_shards")
        sharded = nmt_ops.eds_axis_roots(slabs, idx, k)
        assert _counter("mesh.batch_shards") > s0, "batch must have sharded"
        np.testing.assert_array_equal(plain, sharded)
        fixed = repair_mod.repair_eds(garbled, present, row_roots,
                                      col_roots, engine="batched")
    fixed_scalar = repair_mod.repair_eds(garbled, present, row_roots,
                                         col_roots, engine="scalar")
    np.testing.assert_array_equal(fixed, fixed_scalar)
    np.testing.assert_array_equal(fixed, eds)


# ---------------------------------------------------------------------------
# square-cap plumbing: k=256/512 admitted end to end
# ---------------------------------------------------------------------------


def test_max_square_size_plumbing():
    """The consensus cap override admits k=256/512 layouts (gov param
    still gates below it); invalid overrides are refused loudly."""
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.state import InfiniteGasMeter

    app = App(chain_id="mesh-cap", max_square_size=512)
    app.init_chain({"time_unix": 0, "gov_max_square_size": 512})
    ctx = app._ctx(app.store.branch(), InfiniteGasMeter(), check=False)
    assert app.max_effective_square_size(ctx) == 512

    # default chains keep the reference cap even with a big gov param
    ref = App(chain_id="mesh-cap-ref")
    ref.init_chain({"time_unix": 0, "gov_max_square_size": 512})
    ctx_r = ref._ctx(ref.store.branch(), InfiniteGasMeter(), check=False)
    assert ref.max_effective_square_size(ctx_r) == \
        appconsts.square_size_upper_bound(1)

    with pytest.raises(ValueError):
        App(chain_id="bad", max_square_size=300)  # not a power of two
    with pytest.raises(ValueError):
        App(chain_id="bad", max_square_size=1024)  # above the plumbing


def test_square_layout_at_k256():
    """Layout accounting (host-only, no extend) admits a k=256 square:
    a blob bigger than the k=128 capacity lays out at 256 under the
    raised cap and is refused under the reference cap."""
    from celestia_app_tpu.da import blob as blob_mod
    from celestia_app_tpu.da import namespace as ns_mod
    from celestia_app_tpu.da import square as square_mod
    from celestia_app_tpu.da.square import PfbEntry

    ns = ns_mod.Namespace.v0(b"\x07" * 10)
    # > 128^2 shares of content => needs k=256
    data = bytes(140 * 140 * appconsts.CONTINUATION_SPARSE_SHARE_CONTENT_SIZE)
    blob = blob_mod.Blob(namespace=ns, data=data, share_version=0)
    entry = PfbEntry(tx=b"\x01" * 64, blobs=(blob,))

    sq = square_mod.construct([], [entry], 256, 64)
    assert sq.size == 256
    with pytest.raises(ValueError):
        square_mod.construct([], [entry], 128, 64)


# ---------------------------------------------------------------------------
# bytes-aware LRU (satellite)
# ---------------------------------------------------------------------------


def test_edscache_bytes_aware_eviction():
    """The LRU bounds BYTES as well as entries: big squares evict down
    to the budget, the newest entry always survives, and the count cap
    still applies."""
    k = 8
    # one k=8 entry charges (16*16*512)*2 = 256 KiB
    one = edscache.entry_nbytes(edscache.compute_entry(
        _random_ods(k, 0), "host"))
    cache = edscache.EdsCache(max_entries=10, max_bytes=2 * one)
    entries = []
    for i in range(4):
        ods = _random_ods(k, 500 + i)
        e = edscache.compute_entry(ods, "host")
        entries.append((edscache.cache_key(ods), e))
        cache.put(*entries[-1])
    assert len(cache) == 2  # byte budget binds before the count cap
    assert cache.nbytes() <= 2 * one
    # newest two survive, oldest two evicted
    assert cache.get(entries[3][0]) is not None
    assert cache.get(entries[2][0]) is not None
    assert cache.get(entries[0][0]) is None

    # a single over-budget entry is still retained (newest-entry rule)
    tiny = edscache.EdsCache(max_entries=10, max_bytes=1)
    tiny.put(*entries[0])
    assert len(tiny) == 1


# ---------------------------------------------------------------------------
# the big squares themselves (slow tier: minutes of GF(2^16) on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_mesh_k256_extend_commit_end_to_end():
    """k=256 (the streaming target, GF(2^16) codec) through the mesh
    engine: entry bit-identical to the HOST engine (the quasilinear FFT
    + SIMD-hash reference — the single-device jit program at this size
    is minutes more of the same already-pinned program), commitments
    well-formed, device-resident."""
    k = 256
    ods = _random_ods(k, 256)
    entry = edscache.compute_entry(ods, "device")
    assert isinstance(entry, edscache.DeviceEntry) and entry.chips == 8
    host = edscache.compute_entry(ods, "host")
    assert entry.data_root == host.data_root
    assert entry.dah.row_roots == host.dah.row_roots
    assert entry.dah.col_roots == host.dah.col_roots
    np.testing.assert_array_equal(entry.eds.squares, host.eds.squares)


@pytest.mark.slow
def test_mesh_k512_extend_commit_repair():
    """k=512 through extend+commit on the mesh, then a mesh-sharded
    repair of a column-erased corner of the square's rows (a full 2D
    k=512 repair is hours on CPU; the sharded decode program and root
    verification are exercised at full width here)."""
    from celestia_app_tpu.ops import nmt as nmt_ops
    from celestia_app_tpu.ops import rs

    k = 512
    ods = _random_ods(k, 512)
    entry = edscache.compute_entry(ods, "device")
    assert isinstance(entry, edscache.DeviceEntry) and entry.chips == 8
    assert len(entry.dah.row_roots) == 2 * k
    eds = entry.eds.squares

    # repair a batch of rows with a shared whole-columns erasure at
    # full k=512 width through the fused decode matmul...
    present = tuple(range(k))  # first k of 2k present
    run = rs.repair_axes_fn(k, present)
    rows = eds[:8].copy()
    garbled = rows.copy()
    garbled[:, k:, :] = 0
    out = run(garbled)
    np.testing.assert_array_equal(out, rows)
    # ...and verify their roots through the batched NMT reduction
    got = nmt_ops.eds_axis_roots(rows, list(range(8)), k)
    want = [bytes(r) for r in entry.dah.row_roots[:8]]
    assert [g.tobytes() for g in got] == want
