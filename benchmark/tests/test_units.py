"""The yardstick's arithmetic: floors, generators, statistics, the manifest's
names, and the plain reference against the program's host engine."""

import json
import os
import re

import numpy as np
import pytest

from conftest import BENCH_DIR, REPO_DIR
from lib import cells, stats
from reference import plain_da as da

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO_DIR, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def test_manifest_names_units_and_keys_are_legal(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    cell_names = [w["name"] for w in manifest["workloads"]]
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(r)
                                             for r in c["reduced"])
        assert c["file"].startswith("benchmark/")
        assert os.path.isfile(os.path.join(REPO_DIR, c["file"]))
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = set()
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        layers.add(m["layer"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cell_names)) <= set(cell_names)
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    assert len(cell_names) == len(set(cell_names))
    assert len(json.dumps(manifest)) < 64 * 1024


def test_every_cell_loads_and_every_metric_has_its_reader(manifest):
    for w in manifest["workloads"]:
        cell = cells.load_cell(w["name"])
        assert hasattr(cell.generator(), "prepare")
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            reducer = cells.load_module("reducers", m.spec["reducer"])
            assert callable(reducer.read)
            if "floor" in m.spec:
                assert callable(cells.load_module(
                    "floors", m.spec["floor"]).floor_seconds)
        for key in ("guarantees", "reduced", "assumed", "source"):
            assert key in cell.config


def test_files_under_paths_have_legal_names():
    for folder, _dirs, files in os.walk(BENCH_DIR):
        if "__pycache__" in folder:
            continue
        for f in files:
            if not f.endswith(".pyc"):
                rel = os.path.relpath(os.path.join(folder, f), REPO_DIR)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


# -- floors, against values worked by hand ------------------------------------

PEAKS = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


@pytest.mark.parametrize("k,n_bytes,n_ops,binds", [
    # k*k*512 + (2k)^2*512 + 4k*90 + 32 ; 3*k^3*512*2
    (64, 2_097_152 + 8_388_608 + 23_040 + 32, 805_306_368, "bytes"),
    (128, 8_388_608 + 33_554_432 + 46_080 + 32, 6_442_450_944, "bytes"),
])
def test_extend_commit_floor(k, n_bytes, n_ops, binds):
    floor = cells.load_module("floors", "extend_commit")
    seconds, what = floor.per_extend(k, PEAKS)
    assert what == binds
    assert seconds == pytest.approx(n_bytes / 819e9)
    assert n_ops / 393e12 < seconds
    # k=128: 41,989,152 B / 819 GB/s = 51.27 us against 16.39 us of int8 ops
    if k == 128:
        assert seconds == pytest.approx(51.27e-6, rel=1e-3)
        assert n_ops / 393e12 == pytest.approx(16.39e-6, rel=1e-3)
    total, _ = floor.floor_seconds({"square_size": [k, k, k]}, PEAKS)
    assert total == pytest.approx(3 * seconds)


def test_namespace_search_floor():
    floor = cells.load_module("floors", "namespace_search")
    # 64*64*29 leaf bytes + 9 queries * (29 in + 12 out)
    seconds, _ = floor.floor_seconds({"namespace_reads": [(64, 9)]}, PEAKS)
    assert seconds == pytest.approx((118_784 + 9 * 41) / 819e9)


def test_unknown_device_kind_is_an_error():
    import run

    assert run.load_peaks(BENCH_DIR, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cells.CellError):
        run.load_peaks(BENCH_DIR, "TPU v9 imaginary")


# -- generators ----------------------------------------------------------------


@pytest.mark.parametrize("mix,k,blob_shares,aligned", [
    # 200,000 B = 478 + 414 * 482 - ...: 415 shares, laid at multiples of 8
    ("pfb-full", 128, 415, 416), ("pfb-full-quarter", 64, 104, 104)])
def test_a_block_of_the_mix_fills_nine_tenths_of_its_square(
        mix, k, blob_shares, aligned):
    m = cells.read_json(os.path.join(BENCH_DIR, "traffic", f"{mix}.json"))
    blobs = m["pfbs_per_block"] * m["blobs_per_pfb"]
    assert (m["sequences"], m["blobs_per_pfb"], blobs) == (60, 6, 36)
    assert da.sparse_share_count(m["blob_bytes"]) == blob_shares
    width = da.subtree_width(blob_shares)
    assert -(-blob_shares // width) * width == aligned
    assert 0.90 <= blobs * aligned / k ** 2 < 0.95          # 91.4 %
    assert blobs * m["blob_bytes"] >= 0.9 * k * k * 482     # upstream's test
    assert blobs * aligned > (k // 2) ** 2                  # needs the k


def test_the_k64_block_lays_out_alike_under_the_bound_of_128():
    """PERF.md, Open questions: the program rebuilds a stored height with
    the versioned bound 128 where the proposer used the governed 64. This
    mix's PFB shares come to the same count under both (4,242-4,290 B of
    worst-case index wrappers against the 4,298 B that 9 compact shares
    hold), so no block of it can meet that fault."""
    cell = cells.load_cell("k64-pfb-full")
    traffic = cell.generator().Traffic(cell, 2**31 + 3)
    traffic.generate(12)                # every sender's first and second PFB
    seen = set()
    for block in traffic.pool:
        inner = [len(da.parse_blob_tx(raw)[0]) for raw in block]
        for bound in (64, 128):
            total = sum(len(da.uvarint(n)) + n for n in (
                da.index_wrapper_worst_size(x, cell.mix["blobs_per_pfb"],
                                            bound) for x in inner))
            seen.add((bound, da.compact_share_count(total)))
    assert seen == {(64, 9), (128, 9)}


def test_zipf_counts():
    pfb = cells.load_module("generators", "pfb_blocks")
    counts = pfb.zipf_counts(8, 36, 1.0)
    assert counts == [11, 6, 4, 4, 3, 3, 3, 2]
    counts = pfb.zipf_counts(8, 64, 1.0)
    assert sum(counts) == 64 and counts == sorted(counts, reverse=True)
    assert min(counts) >= 1 and counts[0] >= 2 * counts[3]


def test_traffic_is_a_function_of_the_seed_alone(tiny_tree):
    cell = cells.load_cell("tiny-produce", bench_dir=tiny_tree)
    pfb = cell.generator()

    def blocks(seed):
        t = pfb.Traffic(cell, seed)
        t.generate(4)
        return t

    a, b, c = blocks(2**31 + 7), blocks(2**31 + 7), blocks(8)
    assert a.pool == b.pool and a.pool != c.pool
    mix = cell.mix
    for t in (a, c):            # the same block shape under every seed
        for i, block in enumerate(t.pool):
            assert len(block) == mix["pfbs_per_block"]
            senders = sorted(t.client.addresses.index(t.client.sent[raw][0])
                             for raw in block)
            assert senders == sorted(     # sequences in rotation, by index
                (i * mix["pfbs_per_block"] + j) % mix["sequences"]
                for j in range(mix["pfbs_per_block"]))
            namespaces = []
            for raw in block:
                blobs = da.parse_blob_tx(raw)[1]
                assert [len(d) for _ns, d in blobs] == \
                    [mix["blob_bytes"]] * mix["blobs_per_pfb"]
                namespaces += [ns for ns, _d in blobs]
            assert sorted(namespaces.count(ns) for ns in t.namespaces) == \
                sorted(pfb.zipf_counts(mix["namespaces"], len(namespaces),
                                       mix["namespace_zipf_s"]))
    serve = cells.load_cell("tiny-serve", bench_dir=tiny_tree)
    tips = serve.generator()
    s1, s2 = tips.Traffic(serve, 5), tips.Traffic(serve, 6)
    kinds = lambda sched: sorted(
        (r["kind"], r.get("offset", 0), len(r.get("ranks", [])))
        for r in sched)
    assert kinds(s1.schedules[0]) == kinds(s2.schedules[1])   # same set
    assert s1.schedules[0] != s2.schedules[0]                 # another order
    assert any(r.get("keep") for r in s1.schedules[0])


def test_percentile_is_nearest_rank():
    xs = list(range(1, 31))
    assert stats.percentile(xs, 90) == 27 and stats.percentile(xs, 100) == 30
    assert stats.percentile([5.0], 90) == 5.0


# -- the plain reference against a second witness -------------------------------


def test_plain_reference_agrees_with_the_programs_host_engine():
    from celestia_app_tpu.ops import leopard
    from celestia_app_tpu.utils import refimpl

    rng = np.random.default_rng(3)
    for k in (2, 8, 32):
        data = rng.integers(0, 256, (k, 16), dtype=np.uint8)
        assert np.array_equal(da.rs_encode(data), leopard.encode(data))
    ods = rng.integers(0, 256, (4, 4, 512), dtype=np.uint8)
    ods[..., :29] = 0
    ods[..., 28] = np.arange(16).reshape(4, 4) // 3 + 1
    eds, rows, cols, root = refimpl.pipeline_host(ods)
    assert np.array_equal(da.extend(ods), eds)
    assert da.axis_roots(eds) == (rows, cols)
    assert da.data_root(rows, cols) == root


def test_range_proofs_verify_and_reject():
    from reference.plain_node import prove_range

    rng = np.random.default_rng(4)
    leaves = [da.nmt_leaf(bytes(28) + bytes([i // 3 + 1]),
                          rng.integers(0, 256, 512, dtype=np.uint8).tobytes())
              for i in range(8)] + [da.nmt_leaf(da.PARITY_NS, b"p" * 512)] * 8
    root = b"".join(da.nmt_root(leaves))
    nodes = prove_range(leaves, 5, 7)
    assert da.verify_range(root, 5, 7, 16, leaves[5:7], nodes)
    assert not da.verify_range(root, 5, 7, 16, leaves[4:6], nodes)
    assert not da.verify_range(root, 5, 7, 16, leaves[5:7], nodes[:-1])
    assert not da.verify_range(root, 4, 6, 16, leaves[5:7], nodes)


# -- the trace reducer on a recorded trace --------------------------------------


@pytest.fixture(scope="module")
def recorded():
    import gzip

    path = os.path.join(BENCH_DIR, "tests", "data", "recorded_trace.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_busy_union_and_idle_attribution_on_the_recorded_trace(recorded):
    from reducers import xplane

    got = xplane.reduce_events(recorded)
    w0, w1 = 0, 200_000_000
    # the union, the slow way: paint a 1-ns-resolution-free sweep
    ops = sorted((max(s, w0), min(s + d, w1))
                 for s, d in recorded["device_ops"]["/device:TPU:0"])
    busy, reach = 0, w0
    for lo, hi in ops:
        if hi > max(lo, reach):
            busy += hi - max(lo, reach)
            reach = hi
    assert got["window_s"] == pytest.approx(0.2)
    assert got["busy_s"] == pytest.approx(busy / 1e9, abs=1e-12)
    assert 0.015 < got["busy_s"] < 0.025      # 19.3 ms of the 200 were busy
    gaps = dict(got["idle_gaps"])
    # one thread, spans that do not overlap: the names add up to all idle time
    assert sum(gaps.values()) == pytest.approx(0.2 - got["busy_s"], abs=1e-9)
    assert gaps["produce_block"] > gaps["broadcast_txs"] > gaps["between_spans"]
    programs = dict(got["device_ops"])
    verify = "jit_verify_kernel(1902738338647931305)"
    assert got["device_ops"][0][0] == verify
    assert programs[verify] == pytest.approx(0.011183012)
    # programs cover the operations: their time is the busy time to 1 %
    assert sum(programs.values()) == pytest.approx(got["busy_s"], rel=0.01)


def test_roofline_reads_only_the_programs_its_span_sets_off(recorded):
    import run
    from reducers import xplane

    got = xplane.reduce_events(recorded)
    inside = got["programs_in_spans"]
    # the recorded block: the commitment batches and the signature kernel
    # start under broadcast_txs, the pipeline alone under produce_block
    assert sorted(n.split("(")[0] for n in inside["broadcast_txs"]) == \
        ["jit_nmt_roots"] * 4 + ["jit_verify_kernel"]
    assert list(inside["produce_block"]) == ["jit_run(3986748590254106279)"]
    runs = {n: s for n, s in inside["produce_block"].items()
            if n.startswith("jit_run(")}
    assert runs and max(runs.values()) == pytest.approx(
        dict(got["device_ops"])[max(runs, key=runs.get)])
    reading = run.Reading(spans={}, counters={}, units={"square_size": [64]},
                          trace=got, peaks=PEAKS, bench_dir=BENCH_DIR)
    roofline = cells.load_module("reducers", "device_roofline")
    spec = cells.read_json(os.path.join(
        BENCH_DIR, "metrics", "produce_device_roofline.json"))
    floor_s = (2_097_152 + 8_388_608 + 23_040 + 32) / 819e9
    assert roofline.read(spec, reading) == pytest.approx(
        100 * floor_s / max(runs.values()))
    assert roofline.read({**spec, "span": "warm_wait"}, reading) is None
    # a program that starts under two threads' spans counts once for each name
    ev = {"device_ops": {"/device:TPU:0": [[10, 10]]},
          "device_programs": {"/device:TPU:0": [["jit_run(1)", 10, 10],
                                                ["jit_run(2)", 40, 5],
                                                ["jit_other(3)", 12, 2]]},
          "host_spans": [["bench.window", 0, 100], ["bench.a", 0, 30],
                         ["bench.b", 35, 30]]}
    inside = xplane.reduce_events(ev)["programs_in_spans"]
    assert inside == {"a": {"jit_run(1)": 10e-9, "jit_other(3)": 2e-9},
                      "b": {"jit_run(2)": 5e-9}}


def test_overlapping_spans_of_many_threads_and_empty_traces():
    from reducers import xplane

    ev = {"device_ops": {"/device:TPU:0": [[10, 10], [15, 10], [60, 5]]},
          "device_programs": {"/device:TPU:0": [["jit_f(1)", 10, 15]]},
          "host_spans": [["bench.window", 0, 100], ["bench.a", 0, 50],
                         ["bench.a", 20, 50], ["bench.b", 90, 30]]}
    got = xplane.reduce_events(ev)
    assert got["busy_s"] == pytest.approx(20e-9)      # [10,25) and [60,65)
    gaps = dict(got["idle_gaps"])
    assert gaps["a"] == pytest.approx((70 - 20) * 1e-9)   # union [0,70)
    assert gaps["b"] == pytest.approx(10e-9)              # clipped to window
    assert gaps["between_spans"] == pytest.approx(20e-9)  # [70,90)
    assert xplane.reduce_events({**ev, "host_spans": []}) is None
    assert xplane.reduce_events({**ev, "device_ops": {}}) is None


def test_four_planes_average_over_the_chips():
    """A four-chip host: one plane a chip, unequal busy time, one sharded
    program on all four. Seconds are the mean over the chips, so the idle
    share stays inside 0..100 whatever one chip does."""
    import run
    from reducers import xplane

    ops = {0: [[0, 400]], 1: [[100, 200]], 2: [[-50, 1100]],   # all of it
           3: [[0, 100], [50, 100], [900, 300]]}               # 150 + 100
    ev = {"device_ops": {f"/device:TPU:{n}": o for n, o in ops.items()},
          "device_programs": {
              f"/device:TPU:{n}": [["jit_sharded(7)", 100, 100 + 40 * n]]
              for n in range(4)},
          "host_spans": [["bench.window", 0, 1000], ["bench.a", 0, 600]]}
    ev["device_programs"]["/device:TPU:1"].append(["jit_alone(8)", 700, 80])
    got = xplane.reduce_events(ev)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx((400 + 200 + 1000 + 250) / 4 * 1e-9)
    sharded = (100 + 140 + 180 + 220) / 4 * 1e-9
    assert dict(got["device_ops"]) == pytest.approx(
        {"jit_sharded(7)": sharded, "jit_alone(8)": 20e-9})
    assert got["programs_in_spans"] == {"a": {"jit_sharded(7)":
                                              pytest.approx(sharded)}}
    gaps = dict(got["idle_gaps"])
    # idle inside [0, 600): 200 + 400 + 0 + 450; outside: 400 + 400 + 0 + 300
    assert gaps["a"] == pytest.approx(1050 / 4 * 1e-9)
    assert gaps["between_spans"] == pytest.approx(1100 / 4 * 1e-9)
    assert sum(gaps.values()) == pytest.approx(
        got["window_s"] - got["busy_s"])
    idle = cells.load_module("reducers", "device_idle")
    for one_chip_does in ([[0, 1000]], []):
        ev["device_ops"]["/device:TPU:3"] = one_chip_does
        reading = run.Reading(spans={}, counters={}, units={},
                              trace=xplane.reduce_events(ev), peaks=PEAKS,
                              bench_dir=BENCH_DIR)
        assert 0.0 <= idle.read({}, reading) <= 100.0
    assert idle.read({}, reading) == pytest.approx(100 * (1 - 1600 / 4000))


def test_a_reader_with_nothing_to_read_returns_nothing():
    import run

    empty = run.Reading(spans={}, counters={}, units={}, trace=None,
                        peaks={}, bench_dir=BENCH_DIR)
    for name, spec in [
            ("span_median", {"span": "x"}),
            ("counter_ratio", {"counter": "a", "denominator": "b"}),
            ("unit_mean", {"unit_list": "x"}), ("device_idle", {}),
            ("device_roofline", {"floor": "extend_commit", "span": "x",
                                 "program_prefix": "jit_run("})]:
        assert cells.load_module("reducers", name).read(spec, empty) is None
