"""The program's own spans as the benchmark reads them: the `span_total`
reducer on a hand-made reading, every new metric readable from the window
counters of a tiny rehearsal run, and program-named spans on the trace
getting rows of their own beside the benchmark's."""

import json
import os

import pytest

import run
from conftest import BENCH_DIR, CPU_DEVICE, REPO_DIR
from lib import cells

PRODUCE_METRICS = [
    "prepare_ms", "process_ms", "finalize_ms", "commit_ms",
    "produce_unspanned_ms", "recheck_ms", "square_layout_ms",
    "extend_run_ms", "extend_xfer_ms", "admission_commit_batch_ms",
    "warm_build_ms", "warm_xfer_ms", "entry_rebuild_ms",
    "entry_rebuilds_per_block", "gc_full_ms"]
SERVE_METRICS = ["ns_read_cpu_ms", "ns_search_ms", "ns_proofs_cpu_ms",
                 "ns_encode_cpu_ms"]


def _counters(**spans):
    """name=(n, wall_us, cpu_us) -> the window delta of the three families"""
    out = {}
    for name, (n, wall, cpu) in spans.items():
        for family, v in (("obs.span_n", n), ("obs.span_wall_us", wall),
                          ("obs.span_cpu_us", cpu)):
            if v:
                out[f'{family}{{name="{name}"}}'] = v
    return out


def _reading(counters, units=None):
    return run.Reading(spans={}, counters=counters, units=units or {},
                       trace=None, peaks={}, bench_dir=BENCH_DIR)


@pytest.mark.parametrize("spec,units,expected", [
    # wall, per block: (30000 + 6000) us / 4 blocks
    ({"spans": ["a", "b"], "per_unit": "blocks"}, {"blocks": 4}, 9.0),
    # the CPU clock
    ({"spans": ["a"], "clock": "cpu", "per_unit": "blocks"}, {"blocks": 4},
     5.0),
    # self time: a minus its child b
    ({"spans": ["a"], "minus": ["b"], "per_unit": "blocks"}, {"blocks": 4},
     6.0),
    # no per_unit: per occurrence of the first span (a finished 3 times)
    ({"spans": ["a"]}, {}, 10.0),
    # a span the window never opened counts 0, it does not hide the metric
    ({"spans": ["a", "never"], "per_unit": "blocks"}, {"blocks": 4}, 7.5),
    ({"spans": ["never"], "per_unit": "blocks"}, {"blocks": 4}, 0.0),
    # a divisor of 0 gives nothing
    ({"spans": ["a"], "per_unit": "blocks"}, {"blocks": 0}, None),
    ({"spans": ["a"], "per_unit": "blocks"}, {}, None),
    ({"spans": ["never"]}, {}, None),
])
def test_span_total_on_a_hand_made_reading(spec, units, expected):
    reader = cells.load_module("reducers", "span_total")
    counters = _counters(a=(3, 30_000, 20_000), b=(8, 6_000, 6_000))
    got = reader.read({"reducer": "span_total", **spec},
                      _reading(counters, units))
    assert got == (None if expected is None else pytest.approx(expected))


def test_span_total_gives_nothing_for_a_program_without_span_totals():
    """The parent of the PR that brought the totals has none: the new
    metrics are then left out of its line, and nothing raises."""
    reader = cells.load_module("reducers", "span_total")
    older = _reading({"da.extend_runs": 4, "commitment.batch_lanes": 144},
                     {"blocks": 4})
    for name in PRODUCE_METRICS + SERVE_METRICS:
        spec = cells.read_json(os.path.join(BENCH_DIR, "metrics",
                                            f"{name}.json"))
        if spec["reducer"] == "span_total":
            assert reader.read(spec, older) is None


def test_the_manifest_lists_the_nineteen_with_their_cells():
    with open(os.path.join(REPO_DIR, "BENCHMARK.json"),
              encoding="utf-8") as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    # `in`: later PRs append their cells to these lists
    for name in PRODUCE_METRICS:
        assert "k64-pfb-full" in per_layer[name]["workloads"]
        assert "k128-pfb-full" in per_layer[name]["workloads"]
    for name in SERVE_METRICS:
        assert "k64-serve-tip" in per_layer[name]["workloads"]
    for name in PRODUCE_METRICS + SERVE_METRICS:
        entry = per_layer[name]
        assert entry["better"] == "lower"
        assert entry["source"] == ("program_counter"
                                   if name == "entry_rebuilds_per_block"
                                   else "program_span")


@pytest.mark.parametrize("workload,names", [
    ("tiny-produce", PRODUCE_METRICS), ("tiny-serve", SERVE_METRICS)])
def test_rehearsal_window_counters_carry_every_new_metric(
        tiny_tree, capsys, workload, names):
    cell = cells.load_cell(workload, bench_dir=tiny_tree)
    out = run.run_cell(cell, 2**31 + 11, 1.5, False, CPU_DEVICE)
    assert out["correct"] is True, out["compared"]
    window = [json.loads(line) for line in capsys.readouterr().out.split("\n")
              if line.startswith('{"phase": "window"')]
    assert len(window) == 1
    # the serve mix's count of reads is a list the window line leaves out;
    # its metrics divide by how often their span finished instead
    reading = _reading(window[0]["counters"],
                       {"blocks": window[0].get("blocks", 0)})
    by_name = {m.name: m for m in cell.per_layer}
    values = {}
    for name in names:
        spec = by_name[name].spec
        reader = cells.load_module("reducers", spec["reducer"], tiny_tree)
        values[name] = reader.read(spec, reading)
    assert all(v is not None and v >= 0 for v in values.values()), values
    if workload == "tiny-produce":
        # the four phases and the device branch all ran in the window
        for name in ("prepare_ms", "process_ms", "finalize_ms", "commit_ms",
                     "square_layout_ms", "extend_run_ms", "extend_xfer_ms",
                     "admission_commit_batch_ms", "warm_build_ms",
                     "warm_xfer_ms"):
            assert values[name] > 0, (name, values)
        whole = cells.load_module("reducers", "span_total", tiny_tree).read(
            {"spans": ["block.produce"], "per_unit": "blocks"}, reading)
        assert 0 <= values["produce_unspanned_ms"] < 0.25 * whole
    else:
        parts = (values["ns_proofs_cpu_ms"] + values["ns_encode_cpu_ms"])
        assert 0 < parts <= values["ns_read_cpu_ms"] * 1.05
        assert values["ns_search_ms"] > 0


def test_program_spans_on_another_thread_get_rows_of_their_own():
    """The program's spans reach the trace under the benchmark's prefix. On
    the warmer's thread they overlap the benchmark's spans of the client
    thread: each name gets its own idle seconds and its own table of the
    programs that started under it, and `produce_block`'s stay as they
    were."""
    from reducers import xplane

    device = {"/device:TPU:0": [[120, 30], [400, 50], [700, 20]]}
    programs = {"/device:TPU:0": [["jit_run(1)", 120, 30],
                                  ["jit_prover_levels(2)", 400, 50],
                                  ["jit_prover_levels(2)", 700, 20]]}
    bench = [["bench.window", 0, 1000], ["bench.produce_block", 100, 350],
             ["bench.first_sample", 450, 150]]
    program = [  # the client thread, nested in produce_block
        ["bench.block.produce", 101, 348], ["bench.prepare_proposal", 102, 98],
        ["bench.da.extend.run", 119, 32],
        # the warmer's thread: starts inside produce_block, outlasts it
        ["bench.da.prover_warm", 390, 400],
        ["bench.proof.levels.run", 399, 52],
        ["bench.proof.levels.run", 699, 22]]
    before = xplane.reduce_events({"device_ops": device,
                                   "device_programs": programs,
                                   "host_spans": bench})
    after = xplane.reduce_events({"device_ops": device,
                                  "device_programs": programs,
                                  "host_spans": bench + program})
    gaps_before, gaps = dict(before["idle_gaps"]), dict(after["idle_gaps"])
    assert gaps["produce_block"] == gaps_before["produce_block"] \
        == pytest.approx((350 - 30 - 50) * 1e-9)
    assert after["programs_in_spans"]["produce_block"] \
        == before["programs_in_spans"]["produce_block"] \
        == {"jit_run(1)": 30e-9, "jit_prover_levels(2)": 50e-9}
    assert after["busy_s"] == before["busy_s"] == pytest.approx(100e-9)
    assert gaps["da.prover_warm"] == pytest.approx((400 - 70) * 1e-9)
    assert gaps["proof.levels.run"] == pytest.approx((52 + 22 - 70) * 1e-9)
    assert gaps["da.extend.run"] == pytest.approx(2e-9)
    inside = after["programs_in_spans"]
    assert inside["da.prover_warm"] == {"jit_prover_levels(2)": 70e-9}
    assert inside["proof.levels.run"] == {"jit_prover_levels(2)": 70e-9}
    assert inside["da.extend.run"] == {"jit_run(1)": 30e-9}
    assert inside["prepare_proposal"] == {"jit_run(1)": 30e-9}  # nests it
    # the pipeline's roofline still finds its program under produce_block,
    # and the prover's runs no longer share its name
    spec = cells.read_json(os.path.join(BENCH_DIR, "metrics",
                                        "produce_device_roofline.json"))
    matching = [n for n in inside[spec["span"]]
                if n.startswith(spec["program_prefix"])]
    assert matching == ["jit_run(1)"]
