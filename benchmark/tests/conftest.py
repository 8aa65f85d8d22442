"""The benchmark's own tests: `pytest benchmark/tests`, by hand, on the CPU.

Not part of the repo's tier-1 run (that collects `tests/` only). A tiny
deployment (8x8 squares) and two tiny mixes are ADDED, as files and manifest
entries, to a copy of the benchmark's data — which is also the proof that a
new cell needs no edit to a file that is there.
"""

import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, REPO_DIR)

import pytest  # noqa: E402

CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _write(path, doc):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)


@pytest.fixture(scope="session")
def tiny_tree(tmp_path_factory):
    """A copy of BENCHMARK.json + benchmark/ data with a tiny config, two
    tiny mixes and two cells added; returns its benchmark directory."""
    root = tmp_path_factory.mktemp("tree")
    bench = str(root / "benchmark")
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", "*.pyc"))
    with open(os.path.join(BENCH_DIR, "configs", "mainnet-default-k64.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    config.update(gov_max_square_size=8,
                  source="a test's toy: no deployment")
    _write(os.path.join(bench, "configs", "tiny-k8.json"), config)
    with open(os.path.join(BENCH_DIR, "traffic", "pfb-full.json"),
              encoding="utf-8") as f:
        mix = json.load(f)
    # 4 PFBs x 4 blobs x 2 shares: 16 blobs reach the program's least device
    # batch, 32 + 4 shares need the 8x8 square
    mix.update(sequences=12, pfbs_per_block=4, blobs_per_pfb=4,
               blob_bytes=478 + 482, namespaces=3, warm_blocks=2,
               samples_per_block=4, reference_blocks=2)
    _write(os.path.join(bench, "traffic", "pfb-tiny.json"), mix)
    with open(os.path.join(BENCH_DIR, "traffic", "serve-tip.json"),
              encoding="utf-8") as f:
        serve = json.load(f)
    serve.update(setup_mix="pfb-tiny", setup_blocks=5, clients=3, cycle=20,
                 reads_per_cycle=4, cells_per_round=4,
                 read_namespaces=[1, 3], absent_every=2,
                 verify_per_cycle={"light": 4, "read": 2})
    _write(os.path.join(bench, "traffic", "serve-tiny.json"), serve)
    with open(os.path.join(REPO_DIR, "BENCHMARK.json"),
              encoding="utf-8") as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-k8", "source": "a test's toy: no deployment",
        "file": "benchmark/configs/tiny-k8.json", "reduced": [],
        "why": "CPU rehearsal"})
    added = {"tiny-produce": "pfb-tiny", "tiny-serve": "serve-tiny"}
    for name, traffic in added.items():
        manifest["workloads"].append({
            "name": name, "config": "tiny-k8", "traffic": traffic,
            "chips": 1, "why": "CPU rehearsal"})
    like = {"tiny-produce": "k64-pfb-full", "tiny-serve": "k64-serve-tip"}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for name, model in like.items():
            if model in m.get("workloads", []):
                m["workloads"].append(name)
    _write(str(root / "BENCHMARK.json"), manifest)
    return bench
