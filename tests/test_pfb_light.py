"""The small-block cell's pieces on the CPU (`k64-pfb-light`, ISSUE 33).

- `benchmark/reference/plain_sig.py` against the program's scalar and
  batched verifiers on seeded keys: valid, forged (made over another
  sign-doc), high-S, bad length, r or s out of range.
- the signature batch is visible: `admission.signatures` with its children
  `admission.sig_prep` and `admission.sig_dispatch` nest and close, and
  `admission.batch_padded_lanes` moves by the bucket.
- every metric file the cell adds reads a name that a 16-PFB block at 8x8
  (plus one forged tx) produced.
- the generator, rehearsed at a tiny size through `run_cell` against the
  plain validator: sound -> correct, `forged_sig_acked` -> its number > 0.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)

import run  # noqa: E402
from lib import cells  # noqa: E402
from lib.client import Client  # noqa: E402
from reference import plain_sig  # noqa: E402
from reference.plain_node import PlainValidator  # noqa: E402

from celestia_app_tpu.chain import crypto  # noqa: E402
from celestia_app_tpu.chain.app import App  # noqa: E402
from celestia_app_tpu.chain.node import Node  # noqa: E402
from celestia_app_tpu.das.server import SampleCore  # noqa: E402
from celestia_app_tpu.ops import secp256k1 as fast  # noqa: E402
from celestia_app_tpu.utils import telemetry  # noqa: E402

CHAIN = "light-test"
T0 = 1_700_000_000.0
CELL = "k64-pfb-light"


def _namespace(i: int) -> bytes:
    return bytes(19) + b"lighttest" + bytes([i + 1])


def _wallets(n: int, seed: int = 33):
    """(the wallet, the same keys signing for another chain, account
    number by public key)."""
    client, forger = Client(CHAIN, seed, n), Client(CHAIN + "-forged", seed, n)
    accounts = client._signer.accounts
    number_of = {accounts[a].priv.public_key().compressed: accounts[a].number
                 for a in client.addresses}
    return client, forger, number_of


def _pfb(wallet: Client, sender: int, n_bytes: int, rng) -> bytes:
    return wallet.pay_for_blobs(sender, [(
        _namespace(sender),
        rng.integers(0, 256, n_bytes, dtype=np.uint8).tobytes())])


# -- plain_sig against the program's verifiers -------------------------------


@pytest.fixture(scope="module")
def vectors():
    """{kind: (pubkey, signature, sign-doc)} and what the program's batched
    verifier says of all of them in ONE dispatch (one jit bucket)."""
    client, forger, number_of = _wallets(2)
    rng = np.random.default_rng(33)
    body, auth, sig, key = plain_sig.parse_tx(_pfb(client, 0, 300, rng))
    doc = plain_sig.sign_doc(body, auth, CHAIN, number_of[key])
    _b, _a, other_sig, other_key = plain_sig.parse_tx(
        _pfb(forger, 0, 300, np.random.default_rng(33)))
    assert other_key == key and other_sig != sig
    r32, s32 = sig[:32], sig[32:]
    s = int.from_bytes(s32, "big")
    n32 = plain_sig.N.to_bytes(32, "big")
    out = {
        "valid": (key, sig, doc),
        "forged_other_sign_doc": (key, other_sig, doc),
        "high_s": (key, r32 + (plain_sig.N - s).to_bytes(32, "big"), doc),
        "short_63_bytes": (key, sig[:63], doc),
        "long_65_bytes": (key, sig + b"\x00", doc),
        "r_zero": (key, bytes(32) + s32, doc),
        "r_is_n": (key, n32 + s32, doc),
        "s_zero": (key, r32 + bytes(32), doc),
        "s_is_n": (key, r32 + n32, doc),
        "bad_pubkey": (b"\x05" + key[1:], sig, doc),
    }
    mask = fast.verify_batch(list(out.values()), backend="device")
    return out, dict(zip(out, (bool(m) for m in mask)))


KINDS = ["valid", "forged_other_sign_doc", "high_s", "short_63_bytes",
         "long_65_bytes", "r_zero", "r_is_n", "s_zero", "s_is_n",
         "bad_pubkey"]


@pytest.mark.parametrize("kind", KINDS)
def test_plain_sig_agrees_with_the_scalar_and_the_batched_verifier(
        vectors, kind):
    triples, batched = vectors
    key, sig, doc = triples[kind]
    plain = plain_sig.ecdsa_verify(key, sig, doc)
    assert plain == crypto._py_verify(key, sig, doc)
    assert plain == batched[kind]
    # with the ante's policy (64 bytes, low-S) in front
    try:
        policy = crypto.PublicKey(key).verify(sig, doc)
    except Exception:       # a key the program refuses to load
        policy = False
    assert plain_sig.verify(key, sig, doc) == policy
    assert plain_sig.verify(key, sig, doc) == (kind == "valid")
    if kind == "high_s":
        assert plain      # the curve accepts it; the policy refuses it


def test_the_sign_doc_is_the_programs(vectors):
    from celestia_app_tpu.chain.tx import decode_tx
    from celestia_app_tpu.da.blob import try_unmarshal_blob_tx

    client, _forger, number_of = _wallets(3)
    raw = _pfb(client, 2, 700, np.random.default_rng(1))
    body, auth, sig, key = plain_sig.parse_tx(raw)
    tx = decode_tx(try_unmarshal_blob_tx(raw).tx)
    assert (tx.pubkey, tx.signature) == (key, sig)
    assert number_of[key] == 2
    assert plain_sig.sign_doc(body, auth, CHAIN, 2) == tx.sign_doc(CHAIN, 2)
    assert plain_sig.sign_doc(body, auth, CHAIN, 0) == tx.sign_doc(CHAIN, 0)
    assert plain_sig.verify_tx(raw, CHAIN, number_of.get)
    assert not plain_sig.verify_tx(raw, CHAIN + "x", number_of.get)
    assert not plain_sig.verify_tx(raw, CHAIN, lambda key: None)


# -- a 16-PFB block at 8x8, one forged tx, on the device engine ---------------

SIG_COUNTERS = ["admission.batch_dispatches", "admission.batch_lanes",
                "admission.batch_padded_lanes", "admission.batch_verified",
                "admission.batch_rejected", "admission.sig_scalar_verified",
                "admission.sig_cache_hits"]


@pytest.fixture(scope="module")
def light_block(tmp_path_factory):
    """What one such block moved: counters, span rows, its units."""
    client, forger, _numbers = _wallets(18)
    rng = np.random.default_rng(7)
    raws = [_pfb(client, i, 1000, rng) for i in range(16)]
    forged = _pfb(forger, 16, 1000, rng)
    alone = _pfb(client, 17, 1000, rng)
    app = App(chain_id=CHAIN, engine="device",
              data_dir=str(tmp_path_factory.mktemp("light") / "data"))
    try:
        app.init_chain({
            "time_unix": T0,
            "accounts": [{"address": a.hex(), "balance": b}
                         for a, b in client.genesis_accounts()],
            "validators": [{"operator": client.addresses[0].hex(),
                            "power": 10}],
            "gov_max_square_size": 64,
        })
        node = Node(app)
        core = node.attach_das_core(SampleCore(app, cache_heights=4))
        before = dict(telemetry.snapshot()["counters"])
        mark = len(app.traces.read("spans", 0, 100_000))
        codes = [r.code for r in node.broadcast_txs(raws + [forged])]
        block, results = node.produce_block(t=T0 + 1)
        core.header(1)
        reply = core.sample_many(1, [(0, 0), (15, 9)])
        assert app.da_warmer.wait_idle(60)
        # one tx offered alone is under the batch's gate: the ante's own
        # verify, the path `sig_scalar_per_block` counts
        assert node.broadcast_tx(alone).code == 0
        after = dict(telemetry.snapshot()["counters"])
        rows = app.traces.read("spans", 0, 100_000)[mark:]
    finally:
        app.close()
    assert codes[:16] == [0] * 16 and codes[16] != 0
    assert block.header.square_size == 8 and len(block.txs) == 16
    assert forged not in block.txs
    assert [r.code for r in results] == [0] * 16
    assert not [s for s in reply["samples"] if "error" in s]
    delta = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    return {"delta": delta, "rows": rows,
            "units": {"blocks": 1, "sig_lanes": [17], "square_size": [8]}}


def test_the_signature_batch_is_counted_by_lane_and_by_bucket(light_block):
    delta = light_block["delta"]
    assert [delta.get(name, 0) for name in SIG_COUNTERS] == [
        1,      # one dispatch a block
        17,     # 16 honest signatures and the forged one
        32,     # the bucket they pad to
        16, 1,  # the mask: verified, rejected
        # the ante's own verifies that passed: the tx offered alone, and no
        # tx of the batch (the forged one's fails there and is not counted)
        1,
        # CheckTx, Prepare's ante, Process and Finalize: 4 x 16 from the cache
        64]
    assert fast.padded_lanes(17) == 32 and fast.padded_lanes(16) == 32
    assert fast.padded_lanes(512 + 1) == 512 + 32
    assert fast.padded_lanes(0) == 0


def test_the_three_signature_spans_nest_and_close(light_block):
    rows = light_block["rows"]
    by_id = {r["span_id"]: r for r in rows}
    named = {name: [r for r in rows if r["name"] == name]
             for name in ("admission.signatures", "admission.sig_prep",
                          "admission.sig_dispatch")}
    assert [len(v) for v in named.values()] == [1, 1, 1]
    whole = named["admission.signatures"][0]
    prep = named["admission.sig_prep"][0]
    dispatch = named["admission.sig_dispatch"][0]
    assert by_id[whole["parent_id"]]["name"] == "admission.prevalidate"
    assert prep["parent_id"] == dispatch["parent_id"] == whole["span_id"]
    assert (whole["n_sigs"], whole["lanes"]) == (17, 32)
    assert prep["n_sigs"] == 17 and dispatch["lanes"] == 32
    assert whole["dur_ms"] + 0.01 >= prep["dur_ms"] + dispatch["dur_ms"]
    assert prep["dur_ms"] > 0 and dispatch["dur_ms"] > 0


NEW_METRICS = ["sig_batch_ms", "sig_prep_ms", "sig_lanes_per_dispatch",
               "sig_padded_lanes_per_dispatch", "sig_scalar_per_block",
               "sig_verify_roofline", "light_extend_roofline"]
EXPECTED = {"sig_lanes_per_dispatch": 17.0,
            "sig_padded_lanes_per_dispatch": 32.0,
            "sig_scalar_per_block": 1.0}


def _manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_benchmark_metric_reads_a_name_the_block_produced(light_block,
                                                          metric):
    spec = cells.read_json(os.path.join(BENCH, "metrics", f"{metric}.json"))
    entry = {m["name"]: m for m in _manifest()["per_layer"]}[metric]
    # the light cell, and for what the signature batch fills the one other
    # cell whose blocks reach it: 24 signatures a block on four chips
    assert entry["workloads"][0] == CELL
    assert set(entry["workloads"][1:]) <= (
        {"bigblock-k256-mesh"} if metric.startswith("sig_") else set())
    assert entry["moves"] == "blob_throughput"
    delta, units = light_block["delta"], light_block["units"]
    reading = types.SimpleNamespace(counters=delta, units=units)
    reducer = cells.load_module("reducers", spec["reducer"])
    if spec["reducer"] == "span_total":
        assert entry["source"] == "program_span"
        assert entry["layer"] == "admission"
        for span in spec["spans"]:
            assert delta[f'obs.span_n{{name="{span}"}}'] == 1, span
        assert reducer.read(spec, reading) > 0
    elif spec["reducer"] == "counter_ratio":
        assert entry["source"] == "program_counter"
        assert delta[spec["counter"]] > 0
        assert reducer.read(spec, reading) == EXPECTED[metric]
    else:
        # a share of a roofline comes from a chip's trace alone; here: the
        # floor counts this block's work, and the names are the program's
        assert spec["reducer"] == "device_roofline"
        assert entry["source"] == "device_trace"
        assert entry["layer"] == "kernels" and "which" not in spec
        floor = cells.load_module("floors", spec["floor"])
        peaks = run.load_peaks(BENCH, "TPU v5 lite")
        seconds, binds = floor.floor_seconds(units, peaks)
        assert seconds > 0 and binds in ("ops", "bytes")
        if metric == "sig_verify_roofline":
            assert spec["span"] == "broadcast_txs"
            import jax

            with jax.enable_x64(True):    # as jitted_verify traces it
                kernel = fast._kernel_fns()
            assert spec["program_prefix"] == "jit_" + kernel.__name__
            assert seconds == 17 * floor.OPS_PER_SIGNATURE \
                / peaks["int8_ops_per_s"]
        else:
            whole = cells.read_json(os.path.join(
                BENCH, "metrics", "produce_device_roofline.json"))
            assert {**spec, "which": "largest"} == whole


def test_sig_batch_ms_holds_sig_prep_ms(light_block):
    reducer = cells.load_module("reducers", "span_total")
    reading = types.SimpleNamespace(counters=light_block["delta"],
                                    units=light_block["units"])
    values = [reducer.read(cells.read_json(os.path.join(
        BENCH, "metrics", f"{m}.json")), reading)
        for m in ("sig_batch_ms", "sig_prep_ms")]
    assert values[0] >= values[1] > 0


def test_the_cell_reports_what_the_produce_cells_report():
    """Appended to every per-layer metric of the two produce cells but the
    one whose `"largest"` rule needs one pipeline program a window."""
    manifest = _manifest()
    full = {m["name"] for m in manifest["per_layer"]
            if "k64-pfb-full" in m.get("workloads", [])}
    light = {m["name"] for m in manifest["per_layer"]
             if CELL in m.get("workloads", [])}
    assert full - light == {"produce_device_roofline"}
    assert light - full == set(NEW_METRICS)
    for m in manifest["end_to_end"]:
        if m["name"] in ("blob_throughput", "block_p90"):
            assert CELL in m["workloads"]
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.mix["generator"] == "pfb_light_blocks"
    assert len(cell.per_layer) == len(light)
    assert [c["square"] for c in cell.mix["classes"]] == [8, 16, 32]
    assert sorted(cell.mix["cycle"]) == [8, 8, 16, 16, 16, 16, 32, 32]


# -- the generator, rehearsed at a tiny size ---------------------------------


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """A copy of the benchmark's data with a tiny deployment (governed 8,
    8 rollups) and a tiny mix added as files and manifest entries: 2 PFBs
    a block, classes that lay out squares of 2 / 4 / 8, a forged tx every
    other block."""
    root = tmp_path_factory.mktemp("tree")
    bench = str(root / "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", "*.pyc"))
    config = cells.read_json(os.path.join(
        BENCH, "configs", "mainnet-default-k64-rollups.json"))
    config.update(gov_max_square_size=8, rollup_accounts=8,
                  source="a test's toy: no deployment")
    mix = cells.read_json(os.path.join(BENCH, "traffic", "pfb-light.json"))
    # headroom: a 0.4 s window beside five other test workers runs at any
    # pace but the warm-up's
    mix.update(pfbs_per_block=2, samples_per_block=4, forged_every=2,
               pool_headroom=8.0,
               classes=[{"square": 2, "blob_bytes": 100},
                        {"square": 4, "blob_bytes": 600},
                        {"square": 8, "blob_bytes": 4500}],
               cycle=[4, 2, 4, 8])
    manifest = _manifest()
    manifest["configs"].append({
        "name": "tiny-rollups", "source": config["source"],
        "file": "benchmark/configs/tiny-rollups.json", "reduced": [],
        "why": "CPU rehearsal"})
    manifest["workloads"].append({
        "name": "tiny-light", "config": "tiny-rollups",
        "traffic": "pfb-light-tiny", "chips": 1, "why": "CPU rehearsal"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-light")
    for path, doc in (
            (os.path.join(bench, "configs", "tiny-rollups.json"), config),
            (os.path.join(bench, "traffic", "pfb-light-tiny.json"), mix),
            (str(root / "BENCHMARK.json"), manifest)):
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f)
    return cells.load_cell("tiny-light", bench)


def _control(cell, seed: int, breaks):
    def plain(cell, traffic):
        return PlainValidator(cell.config, traffic.accounts(),
                              traffic.client.sent, breaks=breaks)

    return run.run_cell(
        cell, seed, 0.4, False,
        {"platform": "none", "kind": "plain reference", "count": 0},
        make_sut=plain)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_rehearsal_sound_is_correct(tiny_cell, seed, capsys):
    out = _control(tiny_cell, seed, None)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert all(value == 0 for value, _limit in out["compared"].values())
    assert set(out["metrics"]) == {"blob_throughput", "block_p90", "setup_s"}
    window = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if '"phase": "window"' in line][-1]
    # every class in the window, every block of the promised class
    assert set(window["squares"]) == {"2", "4", "8"}
    assert window["forged_offered"] >= 1
    assert window["sig_lanes"].count(3) == window["forged_offered"]
    assert "blocks_where_bounds_differ" in window


@pytest.mark.parametrize("breaks,number", [
    ("forged_sig_acked", "forged_tx_acknowledged"),
    ("drop_acked_tx", "txs_not_in_their_block"),
    ("skip_q3", "data_root_vs_reference"),
    ("stale_sample", "sample_proofs_failed"),
    ("fees_vanish", "supply_and_fees_vs_reference"),
])
def test_rehearsal_each_break_shows_in_its_own_number(tiny_cell, breaks,
                                                      number):
    out = _control(tiny_cell, 3, breaks)
    assert not out["correct"]
    value, limit = out["compared"][number]
    assert value > limit == 0
    if breaks == "forged_sig_acked":
        assert out["compared"]["forged_tx_in_a_block"][0] > 0
        # and it is nobody's failed tx: it was never in `attempted`
        assert out["failed"] == 0
