"""The catch-up cell rehearsed on the CPU at 8x8: a tiny history (14 stored
heights, 10 swept in five ranges of 2, caches of 4) ADDED to the tiny tree
as files and manifest entries, the way conftest.py adds the other two tiny
cells; and the real cell's files held against each other."""

import json
import os

import numpy as np
import pytest

import run
from conftest import BENCH_DIR, _write
from lib import cells
from control import plain as _plain
from test_rehearsal import RESULT_KEYS, _broken, _failing, _run

CELL = "k128-serve-catchup"


@pytest.fixture(scope="module")
def catchup_tree(tiny_tree):
    """tiny_tree + a config with a history, two mixes of the catch-up
    generator (one swept range wider than the caches, one that fits them)
    and their cells, listed wherever the real cell is."""
    root = os.path.dirname(tiny_tree)
    config = cells.read_json(os.path.join(tiny_tree, "configs",
                                          "tiny-k8.json"))
    config["stored_heights"] = 14
    _write(os.path.join(tiny_tree, "configs", "tiny-k8-history.json"), config)
    mix = cells.read_json(os.path.join(BENCH_DIR, "traffic",
                                       "serve-catchup.json"))
    mix.update(setup_mix="pfb-tiny", setup_blocks=14, swept_heights=10,
               range_heights=2, clients=5, sweepers=3, followers=2,
               cells_per_round=4, sweeper_offsets=[0, 2, 6],
               follower_offsets=[4, 8], follower_namespace_ranks=[0, 2],
               absent_every=2, keep_every=2, warm_heights=4)
    _write(os.path.join(tiny_tree, "traffic", "catchup-tiny.json"), mix)
    # 4 swept heights, every client on all 4, all 4 warmed: both LRUs (4
    # entries) hold the whole range before the window, so no request misses
    _write(os.path.join(tiny_tree, "traffic", "catchup-resident.json"),
           {**mix, "swept_heights": 4, "range_heights": 4,
            "sweeper_offsets": [0, 1, 2], "follower_offsets": [1, 3]})
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    if not any(c["name"] == "tiny-k8-history" for c in manifest["configs"]):
        manifest["configs"].append({
            "name": "tiny-k8-history", "source": "a test's toy",
            "file": "benchmark/configs/tiny-k8-history.json",
            "reduced": [], "why": "CPU rehearsal"})
        for name, traffic in (("tiny-catchup", "catchup-tiny"),
                              ("tiny-resident", "catchup-resident")):
            manifest["workloads"].append({
                "name": name, "config": "tiny-k8-history",
                "traffic": traffic, "chips": 1, "why": "CPU rehearsal"})
            for m in manifest["end_to_end"] + manifest["per_layer"]:
                if CELL in m.get("workloads", []):
                    m["workloads"].append(name)
        _write(os.path.join(root, "BENCHMARK.json"), manifest)
    return tiny_tree


def test_catchup_cell_runs_with_no_edit_and_every_window_re_extends(
        catchup_tree, capsys):
    cell, out = _run(catchup_tree, "tiny-catchup")
    assert list(out) == RESULT_KEYS
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_rate", "setup_s"}
    window = [json.loads(line) for line in capsys.readouterr().out.split("\n")
              if line.startswith('{"phase": "window"')][-1]
    # the gate's counter moved, and the roofline's units count it
    assert window["counters"]["da.extend_runs"] == window["extends"] > 0
    assert window["counters"]["das.entry_evictions"] > 0
    assert window["counters"]["edscache.evictions"] > 0
    assert window["requests"] == out["attempted"]
    # ranges that do not overlap: no reader waited on another's build
    assert window["counters"].get("das.entry_coalesced", 0) == 0
    assert min(window["requests_by_client"]) > 0
    assert "bench.window_compiles" in window["counters"]


def test_plain_validator_in_the_programs_place_is_correct(catchup_tree):
    _cell, out = _run(catchup_tree, "tiny-catchup", make_sut=_plain(None))
    assert out["correct"] is True, out["compared"]


@pytest.mark.parametrize("breaks,caught_by", [
    ("stale_sample", "sample_proofs_failed"),
    ("partial_read", "namespace_shares_vs_reference"),
])
def test_control_with_a_guarantee_broken_is_not_correct(
        catchup_tree, breaks, caught_by):
    cell = cells.load_cell("tiny-catchup", bench_dir=catchup_tree)
    assert breaks in cell.mix["control_breaks"]
    _cell, out = _run(catchup_tree, "tiny-catchup", make_sut=_plain(breaks))
    assert out["correct"] is False
    assert caught_by in _failing(out), out["compared"]


def _stale_entry(cell, traffic):
    """The miss path broken underneath: a height that is not resident is
    built from its NEIGHBOUR's stored block and served under its own
    number — what a cache keyed carelessly would do after an eviction."""
    sut = run.real_validator(cell, traffic)
    from celestia_app_tpu.chain import query

    real = query.build_prover_entry

    def build_prover_entry(app, height):
        return real(app, height + 1 if height < app.height - 4 else height)

    query.build_prover_entry = build_prover_entry
    close = sut.close

    def restore():
        query.build_prover_entry = real
        close()

    sut.close = restore
    return sut


@pytest.mark.parametrize("make_sut,caught_by", [
    (_broken("share_altered"), "sample_proofs_failed"),
    (_broken("read_altered"), "namespace_shares_vs_reference"),
    (_stale_entry, "sample_proofs_failed"),
], ids=["share_altered", "read_altered", "stale_entry"])
def test_broken_miss_path_is_not_correct(catchup_tree, make_sut, caught_by):
    _cell, out = _run(catchup_tree, "tiny-catchup", make_sut=make_sut)
    assert out["correct"] is False
    assert caught_by in _failing(out), out["compared"]


def test_a_window_served_from_the_cache_posts_nothing(catchup_tree,
                                                      monkeypatch, capsys):
    """Every swept height resident: no miss, no extend, exit 4."""
    from conftest import CPU_DEVICE
    from lib import device

    monkeypatch.setattr(device, "require_tpu", lambda chips: CPU_DEVICE)
    real = cells.load_cell
    monkeypatch.setattr(cells, "load_cell",
                        lambda name: real(name, bench_dir=catchup_tree))
    rc = run.main(["--workload", "tiny-resident", "--seed", "91",
                   "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == run.EXIT_DEAD_DEVICE_PATH
    assert "da.extend_runs did not move" in captured.err
    assert '"correct"' not in captured.out


# -- the real cell's files against each other --------------------------------


def test_stored_heights_the_set_up_and_the_swept_range_agree():
    cell = cells.load_cell(CELL)
    config, mix = cell.config, cell.mix
    assert (config["stored_heights"], config["served_heights"]) == (28, 4)
    assert mix["setup_blocks"] == config["stored_heights"]
    assert mix["setup_mix"] == "pfb-full" and cell.chips == 1
    # the 4 newest stay resident after set-up and are never swept
    assert mix["swept_heights"] == \
        config["stored_heights"] - config["served_heights"] == 24
    assert (mix["clients"], mix["sweepers"], mix["followers"]) == (8, 6, 2)
    # eight ranges of 3 tile the 24 swept heights: no height has two readers
    assert mix["range_heights"] * mix["clients"] == mix["swept_heights"]
    assert mix["sweeper_offsets"] == [0, 3, 6, 12, 15, 18]
    assert mix["follower_offsets"] == [9, 21]
    # one client's kept replies are all at one of its heights
    assert mix["keep_every"] % mix["range_heights"] == 0
    assert (mix["cells_per_round"], mix["absent_every"],
            mix["keep_every"], mix["warm_heights"]) == (16, 10, 9, 5)
    assert mix["device_dispatch_counter"] == "da.extend_runs"
    assert mix["control_breaks"] == ["stale_sample", "partial_read"]
    hard_cap = cells.load_cell("k128-pfb-full").config
    same = ("gov_max_square_size", "chain_id", "app_version", "engine",
            "validators", "served_heights")
    assert [config[key] for key in same] == [hard_cap[key] for key in same]
    assert config["guarantees"][:4] == hard_cap["guarantees"]
    assert "eviction never changes an answer" in config["guarantees"][4]
    assert set(config["reduced"]) == {"validators", "block_interval_s",
                                      "stored_heights"}
    assert [m.name for m in cell.end_to_end] == ["serve_rate", "setup_s"]
    assert {m.name for m in cell.per_layer} >= {
        "miss_build_ms", "miss_lock_wait_ms", "miss_load_ms",
        "miss_layout_ms", "miss_extend_ms", "miss_xfer_ms",
        "miss_provers_ms", "coalesced_wait_ms", "extends_per_request",
        "evictions_per_request", "catchup_extend_roofline",
        "sample_batch_ms", "ns_read_ms", "device_idle.serve",
        "window_compiles.serve"}
    # no reader waits on another's build in this mix: the wait is read per
    # BUILD (a mean over no wait at all would be left out of the line)
    spec = {m.name: m for m in cell.per_layer}["coalesced_wait_ms"].spec
    assert spec["spans"] == ["das.entry_build", "das.entry_wait"]
    assert spec["minus"] == ["das.entry_build"] and "per_unit" not in spec


def _walks(gen, plans: list[dict], swept: int) -> list[set[int]]:
    """The heights each client asks, over a walk much longer than a range."""
    return [{gen.height_index(plan, done, swept)
             for done in range(3 * swept)} for plan in plans]


def test_the_real_mix_gives_no_height_two_readers_whatever_the_seed():
    cell = cells.load_cell(CELL)
    mix, gen = cell.mix, cell.generator()
    rotations = {int(np.random.default_rng([s, 21]).integers(
        0, mix["swept_heights"])) for s in range(40)}
    assert len(rotations) > 10
    for rotation in rotations:
        walks = _walks(gen, [
            {"start": start + rotation, "span": mix["range_heights"]}
            for start in mix["sweeper_offsets"] + mix["follower_offsets"]],
            mix["swept_heights"])
        assert sorted(h for w in walks for h in w) == list(range(24))


def test_the_walks_are_a_function_of_the_seed_and_never_share_a_height(
        catchup_tree):
    cell = cells.load_cell("tiny-catchup", bench_dir=catchup_tree)
    gen = cell.generator()
    a, b, c = (gen.Traffic(cell, s) for s in (2**31 + 11, 2**31 + 11, 12))
    assert a.schedules == b.schedules
    for t in (a, c):
        walks = _walks(gen, t.schedules, cell.mix["swept_heights"])
        assert all(len(w) == cell.mix["range_heights"] for w in walks)
        asked = [h for w in walks for h in w]
        assert len(set(asked)) == len(asked) == cell.mix["swept_heights"]
        assert [p["kind"] for p in t.schedules] == \
            ["light"] * 3 + ["read"] * 2
        assert [p["namespace"] for p in t.schedules[3:]] == \
            [t.chain.namespaces[0], t.chain.namespaces[2]]
    with pytest.raises(cells.CellError, match="stores 14"):
        cell.mix["setup_blocks"] = 11
        gen.Traffic(cell, 1)
