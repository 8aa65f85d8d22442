"""The whole run after the chip gate, on the CPU at a tiny size: the result
line's keys, and `correct` coming out false when the timed path is broken
underneath or a guarantee is broken in the control."""

import json

import pytest

import run
from conftest import CPU_DEVICE
from lib import cells
from control import plain as _plain
from reference.plain_node import BREAKS

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]


def _run(tiny_tree, workload, make_sut=run.real_validator, seed=2**31 + 5,
         seconds=1.5):
    cell = cells.load_cell(workload, bench_dir=tiny_tree)
    return cell, run.run_cell(cell, seed, seconds, False, CPU_DEVICE,
                              make_sut=make_sut)


def _failing(out):
    return {n for n, (v, lim) in out["compared"].items() if v > lim}


@pytest.mark.parametrize("workload", ["tiny-produce", "tiny-serve"])
def test_new_cell_runs_with_no_edit_and_prints_the_contract_keys(
        tiny_tree, workload):
    cell, out = _run(tiny_tree, workload)
    assert list(out) == RESULT_KEYS          # `compared` comes last
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m.name for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert "setup_s" in out["metrics"]
    json.dumps(out)


@pytest.mark.parametrize("workload", ["tiny-produce", "tiny-serve"])
def test_plain_validator_in_the_programs_place_is_correct(tiny_tree,
                                                          workload):
    _cell, out = _run(tiny_tree, workload, make_sut=_plain(None))
    assert out["correct"] is True, out["compared"]


@pytest.mark.parametrize("workload,breaks,caught_by", [
    ("tiny-produce", "drop_acked_tx", "txs_not_in_their_block"),
    ("tiny-produce", "skip_q3", "data_root_vs_reference"),
    ("tiny-produce", "stale_sample", "sample_proofs_failed"),
    ("tiny-serve", "stale_sample", "sample_proofs_failed"),
    ("tiny-serve", "partial_read", "namespace_shares_vs_reference"),
    ("tiny-produce", "fees_vanish", "supply_and_fees_vs_reference"),
])
def test_control_with_a_guarantee_broken_is_not_correct(
        tiny_tree, workload, breaks, caught_by):
    assert breaks in BREAKS
    _cell, out = _run(tiny_tree, workload, make_sut=_plain(breaks))
    assert out["correct"] is False
    assert caught_by in _failing(out), out["compared"]


# -- the program itself, with its timed path broken underneath ---------------


def _broken(fault):
    def make(cell, traffic):
        sut = run.real_validator(cell, traffic)
        if fault == "state_unchanged":
            # a step that returns its state unchanged: after the first block,
            # produce hands back the last block again and commits nothing
            real = sut.node.produce_block
            last = []

            def produce_block(t=None):
                if len(sut.node.blocks) >= 3:
                    return last[0]
                last[:] = [real(t=t)]
                return last[0]

            sut.node.produce_block = produce_block
        elif fault == "half_batch":
            # half of the batch left out of the block it was offered for
            real_reap = sut.node._reap

            def reap():
                txs = real_reap()
                return txs[:len(txs) // 2]

            sut.node._reap = reap
        elif fault == "share_altered":
            real_sample = sut.core.sample_many

            def sample_many(height, cells_, axis="row"):
                import base64

                out = real_sample(height, cells_, axis)
                doc = out["samples"][0]
                raw = bytearray(base64.b64decode(doc["share"]))
                raw[100] ^= 1
                doc["share"] = base64.b64encode(bytes(raw)).decode()
                return out

            sut.core.sample_many = sample_many
        elif fault == "read_altered":
            real_read = sut.blob.namespaces_many

            def namespaces_many(queries):
                out = real_read(queries)
                for doc in out["queries"]:
                    if doc["shares"]:
                        doc["shares"] = doc["shares"][:-1]
                return out

            sut.blob.namespaces_many = namespaces_many
        return sut
    return make


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("tiny-produce", "state_unchanged", "height_gaps"),
    ("tiny-produce", "half_batch", "txs_not_in_their_block"),
    ("tiny-produce", "share_altered", "sample_proofs_failed"),
    ("tiny-serve", "share_altered", "sample_proofs_failed"),
    ("tiny-serve", "read_altered", "namespace_shares_vs_reference"),
])
def test_broken_timed_path_is_not_correct(tiny_tree, workload, fault,
                                          caught_by):
    _cell, out = _run(tiny_tree, workload, make_sut=_broken(fault))
    assert out["correct"] is False
    assert caught_by in _failing(out), out["compared"]


# -- the command itself ---------------------------------------------------------


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    rc = run.main(["--workload", "k64-pfb-full", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == 3 and "no TPU" in captured.err
    assert '"correct"' not in captured.out
    assert run.main(["--workload", "no-such-cell", "--seed", "1",
                     "--seconds", "1"]) == 2


def test_a_pool_used_up_cuts_the_run_with_no_result(tiny_tree, monkeypatch,
                                                    capsys):
    from conftest import CPU_DEVICE
    from lib import device

    monkeypatch.setattr(device, "require_tpu", lambda chips: CPU_DEVICE)
    real = cells.load_cell

    def starved(name):
        cell = real(name, bench_dir=tiny_tree)
        cell.mix["pool_headroom"] = 0.25      # a quarter of what it completes
        return cell

    monkeypatch.setattr(cells, "load_cell", starved)
    rc = run.main(["--workload", "tiny-produce", "--seed", "77",
                   "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == run.EXIT_WINDOW_CUT_SHORT and "ran dry" in captured.err
    assert '"correct"' not in captured.out


def test_command_prints_the_result_as_its_last_line(tiny_tree, monkeypatch,
                                                    capsys):
    from conftest import CPU_DEVICE
    from lib import device

    monkeypatch.setattr(device, "require_tpu", lambda chips: CPU_DEVICE)
    monkeypatch.setattr(cells, "BENCH_DIR", tiny_tree)
    real = cells.load_cell
    monkeypatch.setattr(cells, "load_cell",
                        lambda name: real(name, bench_dir=tiny_tree))
    rc = run.main(["--workload", "tiny-produce", "--seed", str(2**31 + 9),
                   "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == 0
    last = json.loads(captured.out.strip().split("\n")[-1])
    assert list(last) == RESULT_KEYS
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"}
    tail = captured.err.strip().split("\n")
    assert tail[-1] == "correct: True"
    assert tail[-2].startswith("compared ") and "(limit 0)" in tail[-2]
