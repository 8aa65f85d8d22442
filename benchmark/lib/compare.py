"""What decides `correct`: the timed path's own outputs against the plain
reference (`reference/plain_da.py`), every number exact, every limit 0.

Each function returns {name: [value, limit]}; a run is correct when every
value is within its limit. Names are short and plain because the driver's
record keeps only the end of what a failing run printed.
"""

from __future__ import annotations

import numpy as np

from lib.sut import VALIDATOR_POWER
from reference import plain_da as da
from reference.plain_state import Ledger


def sample_of(n: int, want: int, seed: int, always: list[int]) -> list[int]:
    """`want` indexes of range(n) drawn from the seed, `always` among them."""
    rng = np.random.default_rng([seed, 7])
    picked = set(i for i in always if 0 <= i < n)
    for i in rng.permutation(n):
        if len(picked) >= min(want, n):
            break
        picked.add(int(i))
    return sorted(picked)


def check_samples(samples: list[dict], cells, rows: list[bytes],
                  ref_eds) -> tuple[int, int]:
    """(proofs that fail against the row roots, shares that differ from the
    reference's cell) over one sample reply; a refused or missing sample
    counts as a failed proof."""
    k = len(rows) // 2
    bad_proof = bad_share = 0
    if len(samples) != len(cells):
        return len(cells), 0
    for (row, col), s in zip(cells, samples):
        if "error" in s or (s["row"], s["col"]) != (row, col):
            bad_proof += 1
            continue
        share = s["share"]
        ns = share[:da.NS] if row < k and col < k else da.PARITY_NS
        if not (s["start"] == col and s["end"] == col + 1
                and da.verify_range(rows[row], s["start"], s["end"],
                                    s["total"], [da.nmt_leaf(ns, share)],
                                    s["nodes"])):
            bad_proof += 1
        if ref_eds is not None and share != ref_eds[row, col].tobytes():
            bad_share += 1
    return bad_proof, bad_share


def produce_cell(traffic, collected: dict) -> dict:
    blocks = collected["blocks"]
    max_k = traffic.k
    refused = missing = deliver_failed = gaps = not_warm = 0
    header_bad = proof_bad = share_bad = 0
    root_bad = axis_bad = size_bad = 0
    ledger = Ledger(traffic.accounts(), VALIDATOR_POWER)
    hash_bad = 0
    prev_height = prev_hash = None
    picked = set(sample_of(
        len(blocks), traffic.mix["reference_blocks"], traffic.seed,
        always=[len(blocks) - 1, collected["n_warm"]]))
    for i, b in enumerate(blocks):
        p = b["produced"]
        landed, offered = set(p.txs), set(b["offered"])
        refused += sum(1 for c in b["codes"] if c != 0)
        missing += len(offered - landed) + len(landed - offered)
        deliver_failed += sum(1 for c in p.tx_codes if c != 0)
        not_warm += 0 if b["warm"] else 1
        if prev_height is not None and p.height != prev_height + 1:
            gaps += 1
        # the header carries the state before the block, and every block
        # (its mint, its fees) leaves another state behind
        if (prev_hash is not None and p.prev_app_hash != prev_hash) \
                or not p.app_hash or p.app_hash == p.prev_app_hash:
            hash_bad += 1
        prev_height, prev_hash = p.height, p.app_hash
        ledger.begin_block(p.time_unix)
        for raw, code in zip(p.txs, p.tx_codes):
            if code == 0 and raw in traffic.client.sent:
                ledger.deliver(*traffic.client.sent[raw][:2])
        rows, cols = b["header"]
        if len(rows) != 2 * p.square_size or \
                da.data_root(rows, cols) != p.data_hash:
            header_bad += 1
        ref = None
        if i in picked:
            ref = da.commit_block(p.txs, max_k) if p.txs else None
            if ref is None or ref["square_size"] != p.square_size:
                size_bad += 1
                ref = None
            else:
                root_bad += ref["data_root"] != p.data_hash
                axis_bad += sum(a != b_ for a, b_ in zip(
                    ref["row_roots"] + ref["col_roots"], rows + cols))
        bp, bs = check_samples(b["samples"], b["cells"], rows,
                               None if ref is None else ref["eds"])
        proof_bad += bp
        share_bad += bs
        if p.square_size != max_k:
            size_bad += 1
    account_bad = sum(tuple(ledger.accounts[addr]) != tuple(got)
                      for addr, got in collected["accounts"].items())
    totals = ledger.totals()
    totals_bad = sum(collected["ledger"].get(name) != want
                     for name, want in totals.items())
    return {
        "checktx_refused": [refused, 0],
        "txs_not_in_their_block": [missing, 0],
        "delivertx_failed": [deliver_failed, 0],
        "height_gaps": [gaps, 0],
        "warmer_not_idle": [not_warm, 0],
        "square_size_wrong": [size_bad, 0],
        "data_root_vs_reference": [int(root_bad), 0],
        "axis_roots_vs_reference": [int(axis_bad), 0],
        "served_roots_vs_data_hash": [header_bad, 0],
        "sample_proofs_failed": [proof_bad, 0],
        "sample_shares_vs_reference": [share_bad, 0],
        "accounts_vs_reference": [int(account_bad), 0],
        "supply_and_fees_vs_reference": [int(totals_bad), 0],
        "app_hash_chain_broken": [hash_bad, 0],
    }


def check_namespace_read(doc: dict, ref: dict, ods, expect_present: bool
                         ) -> tuple[int, int, int]:
    """(share sets that differ from the reference's, row proofs that fail,
    presence flags that are wrong) for one namespace of one read."""
    if "error" in doc:
        return 1, 1, 1
    k = ref["square_size"]
    want = da.namespace_shares(ods, doc["namespace"])
    shares_bad = int(doc["shares"] != want
                     or doc["data_root"] != ref["data_root"])
    presence_bad = int(doc["present"] != expect_present
                       or bool(want) != expect_present)
    proof_bad = 0
    pos = 0
    for i, pr in enumerate(doc["row_proofs"]):
        n = pr["end"] - pr["start"]
        leaves = [da.nmt_leaf(s[:da.NS], s)
                  for s in doc["proof_shares"][pos:pos + n]]
        pos += n
        row = doc["start_row"] + i
        if not (row < k and pr["total"] == 2 * k and da.verify_range(
                ref["row_roots"][row], pr["start"], pr["end"], pr["total"],
                leaves, pr["nodes"])):
            proof_bad += 1
    if doc["present"] and (not doc["row_proofs"]
                           or doc["proof_shares"] != doc["shares"]):
        proof_bad += 1
    return shares_bad, proof_bad, presence_bad


def serve_cell(traffic, collected: dict) -> dict:
    refs = {}
    root_bad = 0
    for height, produced in collected["blocks"].items():
        refs[height] = da.commit_block(produced.txs, traffic.k)
        refs[height]["ods"] = refs[height]["eds"][:traffic.k, :traffic.k]
        root_bad += refs[height]["data_root"] != produced.data_hash
    proof_bad = share_bad = ns_share_bad = ns_proof_bad = presence_bad = 0
    lights = reads = 0
    for kind, height, asked, decoded in collected["kept"]:
        ref = refs[height]
        if kind == "light":
            lights += 1
            bp, bs = check_samples(decoded, asked, ref["row_roots"],
                                   ref["eds"])
            proof_bad += bp
            share_bad += bs
            continue
        reads += 1
        if len(decoded) != len(asked):
            ns_share_bad += len(asked)
            continue
        for ns, doc in zip(asked, decoded):
            if doc.get("namespace") != ns:
                ns_share_bad += 1
                continue
            a, b, c = check_namespace_read(
                doc, ref, ref["ods"], expect_present=ns != traffic.absent)
            ns_share_bad += a
            ns_proof_bad += b
            presence_bad += c
    return {
        "replies_refused": [collected["refused"], 0],
        "data_root_vs_reference": [int(root_bad), 0],
        "sample_proofs_failed": [proof_bad, 0],
        "sample_shares_vs_reference": [share_bad, 0],
        "namespace_shares_vs_reference": [ns_share_bad, 0],
        "namespace_proofs_failed": [ns_proof_bad, 0],
        "namespace_presence_wrong": [presence_bad, 0],
        "kept_reply_kinds_missing": [int(lights == 0) + int(reads == 0), 0],
    }
