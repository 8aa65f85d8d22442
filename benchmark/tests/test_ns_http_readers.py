"""`bigblock-k256-ns-http`'s generator at a tiny size, on the CPU: rollup
readers over the node's blob API (`generators/http_ns_readers.py`) against a
mesh-engine chain of 8x8 squares split over 8 virtual devices.

A tiny configuration, a tiny mix and a cell are ADDED to the copy of the
benchmark's data `conftest.tiny_tree` makes — the proof that the new cell
needs no edit to a file that is there — and the run goes through
`run.run_cell` as the chip's does: `correct` true for the program and for
the plain reference in its place, false with each control break, or with
the timed path broken underneath (a share altered, a host prover built for
a mesh height). Besides: the mix's plan, the units the floors read, and both
floors on units made by hand.
"""

import json
import os

# the mesh engine needs devices to shard over (set before any backend is up)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

import run  # noqa: E402
from conftest import CPU_DEVICE  # noqa: E402
from control import plain as _plain  # noqa: E402
from lib import cells  # noqa: E402

CELL = "tiny-ns-http"
MODEL = "bigblock-k256-ns-http"
SEED = 2**31 + 43


@pytest.fixture(scope="module")
def ns_tree(tiny_tree):
    root = os.path.dirname(tiny_tree)
    with open(os.path.join(tiny_tree, "configs", "tiny-k8.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    config.update(engine="mesh", source="a test's toy: no deployment")
    with open(os.path.join(tiny_tree, "configs", "tiny-k8-mesh-ns.json"),
              "w", encoding="utf-8") as f:
        json.dump(config, f)
    with open(os.path.join(tiny_tree, "traffic", "ns-http-readers.json"),
              encoding="utf-8") as f:
        mix = json.load(f)
    # the tiny blocks hold 3 namespaces: a reader a rank and the absent one
    mix.update(setup_mix="pfb-tiny", setup_blocks=5, processes=2,
               readers_per_process=4, keep_every=2)
    with open(os.path.join(tiny_tree, "traffic", "ns-http-tiny.json"), "w",
              encoding="utf-8") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        manifest = json.load(f)
    if not any(w["name"] == CELL for w in manifest["workloads"]):
        manifest["configs"].append({
            "name": "tiny-k8-mesh-ns", "source": "a test's toy",
            "file": "benchmark/configs/tiny-k8-mesh-ns.json", "reduced": [],
            "why": "CPU rehearsal"})
        manifest["workloads"].append({
            "name": CELL, "config": "tiny-k8-mesh-ns",
            "traffic": "ns-http-tiny", "chips": 4,
            "why": "CPU rehearsal"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if MODEL in m.get("workloads", []):
                m["workloads"].append(CELL)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(manifest, f, indent=1)
    return tiny_tree


def _run(tree, make_sut=run.real_validator, seed=SEED, trace=False):
    cell = cells.load_cell(CELL, bench_dir=tree)
    return cell, run.run_cell(cell, seed, 1.5, trace, CPU_DEVICE,
                              make_sut=make_sut)


def _failing(out):
    return {n for n, (v, lim) in out["compared"].items() if v > lim}


def test_the_cell_lists_its_metrics():
    cell = cells.load_cell(MODEL)
    assert cell.chips == 4
    assert [m.name for m in cell.end_to_end] == ["serve_rate", "setup_s"]
    names = {m.name for m in cell.per_layer}
    assert {"ns_gather_ms", "ns_rows_per_read", "ns_gather_roofline",
            "ns_search_mesh_roofline", "device_idle.serve",
            "window_compiles.serve", "http_request_ms", "http_encode_ms",
            "http_write_ms", "http_bytes_per_request", "ns_search_ms",
            "ns_proofs_cpu_ms", "ns_encode_cpu_ms"} == names
    assert cell.mix["device_dispatch_counter"] == "blob.ns_gathers"
    assert cell.config["rollup_readers"] == \
        cell.mix["processes"] * cell.mix["readers_per_process"]


def test_the_mix_plans_one_query_a_rank(ns_tree):
    """Reader j of every process follows rank j — the blocks' namespaces
    in Zipf order, then the absent one — one query a request at the last
    4 heights in 4:2:1:1."""
    from reference import plain_da as da

    cell = cells.load_cell(CELL, bench_dir=ns_tree)
    traffic = cell.generator().prepare(cell, SEED, 1.0)
    pfb = cells.load_module("generators", "pfb_blocks", ns_tree)
    ranks = traffic.chain.mix["namespaces"]
    assert traffic.namespaces == [pfb.namespace_id(SEED, r)
                                  for r in range(ranks)] + \
        [pfb.namespace_id(SEED, 250)]
    # the absent namespace sorts after every blob namespace, before the
    # tail padding
    assert max(traffic.namespaces[:-1]) < traffic.namespaces[-1] < \
        da.TAIL_PADDING_NS
    traffic.heights = [10, 9, 8, 7]
    params = traffic._params(1, 4321)
    assert params["readers"] == len(params["namespaces"]) == ranks + 1
    assert params["present_ranks"] == ranks
    assert params["height_weights"] == [4, 2, 1, 1]
    assert params["namespaces"] == [ns.hex() for ns in traffic.namespaces]


def test_the_units_the_floors_read(ns_tree):
    cell = cells.load_cell(CELL, bench_dir=ns_tree)
    traffic = cell.generator().prepare(cell, SEED, 1.0)
    counts = [{"done": 5, "rows_padded": 12, "non_200": 0, "wrong": 0,
               "transport_errors": 0},
              {"done": 3, "rows_padded": 0, "non_200": 0, "wrong": 0,
               "transport_errors": 0}]
    units = traffic.units({"counts": counts, "seconds": 1.0})
    # an 8x8 square: paths of log2(16) = 4 nodes, two a row
    assert units == {"requests": 8, "square_size": 8, "ns_rows_padded": 12,
                     "ns_nodes": 12 * 2 * 4}
    client = __import__("generators.http_ns_reader_client",
                        fromlist=["row_bucket"])
    assert [client.row_bucket(n) for n in (1, 2, 3, 83, 128)] == \
        [1, 2, 4, 128, 128]


def test_both_floors_on_units_made_by_hand():
    peaks = {"hbm_bytes_per_s": 819e9}
    gather = cells.load_module("floors", "namespace_gather_mesh")
    search = cells.load_module("floors", "namespace_search_mesh")
    # one read of the largest namespace at k = 256: 128 padded rows
    units = {"square_size": 256, "ns_rows_padded": 128,
             "ns_nodes": 128 * 2 * 9, "requests": 1}
    seconds, binds = gather.floor_seconds(units, peaks)
    assert binds == "bytes"
    assert seconds == pytest.approx(
        2 * (128 * 256 * 512 + 128 * 18 * 90) / (4 * 819e9))
    seconds, binds = search.floor_seconds(units, peaks)
    assert binds == "bytes"
    assert seconds == pytest.approx((256 * 256 * 29 + 29) / (4 * 819e9))
    for floor in (gather, search):
        assert floor.floor_seconds({"square_size": 256}, peaks)[0] == 0.0


def test_the_readers_over_the_front_are_correct(ns_tree):
    cell, out = _run(ns_tree)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_rate", "setup_s"}
    assert out["metrics"]["serve_rate"]["value"] > 0
    assert {"host_provers_built", "square_host_crossings",
            "namespace_shares_vs_reference", "namespace_proofs_failed",
            "namespace_presence_wrong", "replies_wrong_on_arrival"} <= \
        set(out["compared"])


def test_plain_validator_behind_a_plain_front_is_correct(ns_tree):
    _cell, out = _run(ns_tree, make_sut=_plain(None))
    assert out["correct"] is True, out["compared"]


@pytest.mark.parametrize("breaks,caught_by", [
    ("stale_read", "namespace_shares_vs_reference"),
    ("partial_read", "namespace_shares_vs_reference"),
])
def test_control_breaks_are_not_correct(ns_tree, breaks, caught_by):
    cell, out = _run(ns_tree, make_sut=_plain(breaks))
    assert breaks in cell.mix["control_breaks"]
    assert out["correct"] is False
    assert caught_by in _failing(out), out["compared"]


def _broken(fault):
    def make(cell, traffic):
        sut = run.real_validator(cell, traffic)
        from celestia_app_tpu.das import blob_server, server

        if fault == "share_altered":
            real = blob_server.BlobCore._namespaces_many

            def namespaces_many(self, queries):
                import base64

                out = real(self, queries)
                doc = out["queries"][0]
                if doc["shares"]:
                    raw = bytearray(base64.b64decode(doc["shares"][-1]))
                    raw[100] ^= 1
                    doc["shares"][-1] = base64.b64encode(bytes(raw)).decode()
                return out

            traffic.restore = (blob_server.BlobCore, "_namespaces_many",
                               real)
            blob_server.BlobCore._namespaces_many = namespaces_many
        elif fault == "host_prover":
            # even heights read from a host prover: their squares come
            # down and the copy-less guarantee is gone (odd heights still
            # gather, so the device path is alive)
            real = server._Entry.namespace_reader

            def namespace_reader(self):
                if self.height % 2 == 0:
                    _ = self.prover
                return real(self)

            traffic.restore = (server._Entry, "namespace_reader", real)
            server._Entry.namespace_reader = namespace_reader
        return sut
    return make


@pytest.mark.parametrize("fault,caught_by", [
    ("share_altered", "namespace_shares_vs_reference"),
    ("host_prover", "host_provers_built"),
])
def test_broken_reads_are_not_correct(ns_tree, fault, caught_by):
    holder = {}
    make = _broken(fault)

    def remember(cell, traffic):
        holder["traffic"] = traffic
        return make(cell, traffic)

    try:
        _cell, out = _run(ns_tree, make_sut=remember)
    finally:
        owner, name, real = holder["traffic"].restore
        setattr(owner, name, real)
    assert out["correct"] is False
    assert caught_by in _failing(out), out["compared"]
