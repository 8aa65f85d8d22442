"""`bigblock-k256-das-http`'s generator at a tiny size, on the CPU: the
light-node fleet over the node's HTTP front (`generators/http_samplers.py`)
against a mesh-engine chain of 8x8 squares split over 8 virtual devices.

A tiny configuration, a tiny mix and a cell are ADDED to the copy of the
benchmark's data `conftest.tiny_tree` makes — the proof that the new cell
needs no edit to a file that is there — and the run goes through
`run.run_cell` as the chip's does: `correct` true for the program and for
the plain reference in its place, false with a guarantee broken in the
control or with the timed path broken underneath (a share altered, a host
prover built for a mesh height).
"""

import json
import os

# the mesh engine needs devices to shard over (set before any backend is up)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import run  # noqa: E402
from conftest import CPU_DEVICE  # noqa: E402
from control import plain as _plain  # noqa: E402
from lib import cells  # noqa: E402

CELL = "tiny-das-http"
MODEL = "bigblock-k256-das-http"


@pytest.fixture(scope="module")
def http_tree(tiny_tree):
    root = os.path.dirname(tiny_tree)
    with open(os.path.join(tiny_tree, "configs", "tiny-k8.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    config.update(engine="mesh", source="a test's toy: no deployment")
    with open(os.path.join(tiny_tree, "configs", "tiny-k8-mesh.json"), "w",
              encoding="utf-8") as f:
        json.dump(config, f)
    with open(os.path.join(tiny_tree, "traffic", "das-http-samplers.json"),
              encoding="utf-8") as f:
        mix = json.load(f)
    mix.update(setup_mix="pfb-tiny", setup_blocks=5, processes=2,
               samplers_per_process=3, keep_every=2)
    with open(os.path.join(tiny_tree, "traffic", "das-http-tiny.json"), "w",
              encoding="utf-8") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        manifest = json.load(f)
    if not any(w["name"] == CELL for w in manifest["workloads"]):
        manifest["configs"].append({
            "name": "tiny-k8-mesh", "source": "a test's toy",
            "file": "benchmark/configs/tiny-k8-mesh.json", "reduced": [],
            "why": "CPU rehearsal"})
        manifest["workloads"].append({
            "name": CELL, "config": "tiny-k8-mesh",
            "traffic": "das-http-tiny", "chips": 4,
            "why": "CPU rehearsal"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if MODEL in m.get("workloads", []):
                m["workloads"].append(CELL)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(manifest, f, indent=1)
    return tiny_tree


def _run(tree, make_sut=run.real_validator, seed=2**31 + 41):
    cell = cells.load_cell(CELL, bench_dir=tree)
    return cell, run.run_cell(cell, seed, 1.5, False, CPU_DEVICE,
                              make_sut=make_sut)


def _failing(out):
    return {n for n, (v, lim) in out["compared"].items() if v > lim}


def test_the_fleet_over_the_front_is_correct(http_tree):
    cell, out = _run(http_tree)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_rate", "setup_s"}
    assert out["metrics"]["serve_rate"]["value"] > 0
    assert {"host_provers_built", "square_host_crossings",
            "proof_nodes_vs_reference", "headers_vs_reference"} <= \
        set(out["compared"])


def test_plain_validator_behind_a_plain_front_is_correct(http_tree):
    _cell, out = _run(http_tree, make_sut=_plain(None))
    assert out["correct"] is True, out["compared"]


def test_control_serving_a_stale_height_is_not_correct(http_tree):
    _cell, out = _run(http_tree, make_sut=_plain("stale_sample"))
    assert out["correct"] is False
    assert "sample_proofs_failed" in _failing(out), out["compared"]


def _broken(fault):
    def make(cell, traffic):
        sut = run.real_validator(cell, traffic)
        if fault == "share_altered":
            from celestia_app_tpu.das import server

            real = server.SampleCore._serve_group

            def serve_group(self, entry, height, cells_, axis):
                import base64

                out = real(self, entry, height, cells_, axis)
                doc = out["samples"][0]
                raw = bytearray(base64.b64decode(doc["share"]))
                raw[100] ^= 1
                doc["share"] = base64.b64encode(bytes(raw)).decode()
                return out

            traffic.restore = (server.SampleCore, "_serve_group", real)
            server.SampleCore._serve_group = serve_group
        elif fault == "host_prover":
            # even heights proved from a host prover: their squares come
            # down and the copy-less guarantee is gone (odd heights still
            # gather, so the device path is alive)
            from celestia_app_tpu.das import server

            real = server._Entry.prove_cells

            def prove_cells(self, cells_, col):
                if self.height % 2 == 0:
                    _ = self.prover
                return real(self, cells_, col)

            traffic.restore = (server._Entry, "prove_cells", real)
            server._Entry.prove_cells = prove_cells
        return sut
    return make


@pytest.mark.parametrize("fault,caught_by", [
    ("share_altered", "sample_proofs_failed"),
    ("host_prover", "host_provers_built"),
])
def test_broken_front_is_not_correct(http_tree, fault, caught_by):
    holder = {}
    make = _broken(fault)

    def remember(cell, traffic):
        holder["traffic"] = traffic
        return make(cell, traffic)

    try:
        _cell, out = _run(http_tree, make_sut=remember)
    finally:
        owner, name, real = holder["traffic"].restore
        setattr(owner, name, real)
    assert out["correct"] is False
    assert caught_by in _failing(out), out["compared"]


def test_proof_nodes_read_off_the_levels_equal_the_plain_prover():
    from reference import plain_da as da
    from reference import plain_light as gen
    from reference.plain_node import prove_range

    eds = np.random.default_rng(5).integers(0, 256, size=(16, 16, 512),
                                            dtype=np.uint8)
    for row in (0, 3, 8, 15):
        levels = gen.row_levels(eds, row)
        leaves = levels[0]
        assert b"".join(levels[-1][0]) == b"".join(da.nmt_root(leaves))
        for col in range(16):
            assert gen.proof_nodes(levels, col) == \
                prove_range(leaves, col, col + 1)
