"""Closed-loop block production of SMALL blocks of more than one size: rollup
sequencers on the default deployment, each posting one small blob a block.

What `README.md`'s `pfb-light` sketch (a `pfb_blocks` mix of one blob size)
could not say: the blocks of one window come in CLASSES of different blob
sizes, so their squares differ (8 / 16 / 32 under a governed 64), and the
comparison takes each block's square size from the reference layout instead
of pinning it to the governed maximum (`lib/compare.produce_cell` counts any
other size as a fault). It keeps `pfb_blocks`' loop, pool and signing thread
(one block = offer -> produce -> one light round at the new height -> wait
for the warmer) and adds:

- `classes`: of every `len(cycle)` blocks, `cycle` names the square of each
  in one fixed order, rotated by an amount the seed draws, so every seed
  does the same work. All `pfbs_per_block` blobs of a block have their
  class's size. Each class is checked in set-up against
  `plain_da.build_ods`: a class that does not give its square is an error.
- one sender = one rollup = one namespace (`rollup_accounts` of the
  configuration); block i carries senders i*P .. i*P+P-1 (mod that many),
  whatever the seed, so each block offers P signatures CheckTx has not
  seen: the program's batched verifier runs once a block.
- a FORGED tx in every `forged_every`-th block, offered with the others: a
  PFB from a funded sender that is not in this block, at its right
  sequence, that parses, whose signature is 64 bytes and low-S, and was
  made over another sign-doc (the same wallet signing for another chain
  id). It has to reach the batched verifier, be refused by its mask and
  then by CheckTx. It is expected, so it counts in neither `attempted` nor
  `failed`; the comparison counts it acknowledged, in a block, or not
  rejected by the batch (each limit 0).
- the warm-up: one block of each class (every shape compiled and warmed),
  then one whole cycle with the signing thread ended, whose mean loop time
  sizes the pool.
- the comparison (`compare`): all of `produce_cell`'s numbers with each
  block's square from `plain_da.build_ods(txs, governed bound)`; for the
  kept blocks (the window's first and last and one of each class) the
  square rebuilt whole and every signature verified by
  `reference/plain_sig.py` against what CheckTx answered, as is every
  forged tx of the run.
- the control: `reference/plain_node.PlainValidator` admits whatever it is
  offered and knows no such break, so this generator is its ante: it checks
  every offered tx with `plain_sig` and hands on those that verify — or,
  under the break `forged_sig_acked`, all of them.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from lib import cells
from lib.compare import check_samples
from lib.sut import VALIDATOR_POWER
from reference import plain_da as da
from reference import plain_node, plain_sig
from reference.plain_state import Ledger

_pfb = cells.load_module(
    "generators", "pfb_blocks",
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FORGED_BREAK = "forged_sig_acked"
if FORGED_BREAK not in plain_node.BREAKS:
    # PlainValidator refuses a break it does not list; this one is played
    # here, in `_Offer`, and the validator only has to carry its name
    plain_node.BREAKS = (*plain_node.BREAKS, FORGED_BREAK)

BATCH_REJECTED = "admission.batch_rejected"


class _Offer:
    """One block's offer: the sut with the block's forged tx added to what
    `one_block` offers, its code kept apart from the honest ones'. In front
    of the plain validator it is also the ante that validator lacks."""

    def __init__(self, sut, traffic, index: int):
        self._sut, self._traffic, self._index = sut, traffic, index

    def __getattr__(self, name):
        return getattr(self._sut, name)

    def offer(self, raws: list[bytes]) -> list[int]:
        traffic = self._traffic
        forged = traffic.forged.get(self._index)
        offered = raws + ([forged] if forged is not None else [])
        if self._sut.is_reference:
            admit_all = self._sut.breaks == FORGED_BREAK
            valid = [admit_all or traffic.signature_is_valid(raw)
                     for raw in offered]
            self._sut.offer([r for r, ok in zip(offered, valid) if ok])
            codes = [0 if ok else 4 for ok in valid]
        else:
            codes = self._sut.offer(offered)
        if forged is not None:
            traffic.forged_codes[self._index] = codes.pop()
        return codes


class Traffic(_pfb.Traffic):
    def __init__(self, cell, seed: int):
        mix, config = cell.mix, cell.config
        self.classes = {c["square"]: c["blob_bytes"] for c in mix["classes"]}
        self.cycle = list(mix["cycle"])
        if set(self.cycle) != set(self.classes):
            raise cells.CellError("the mix's cycle and classes disagree")
        senders = config["rollup_accounts"]
        # the base class draws namespaces by rank and sizes its signing
        # ahead from one blob size; both are replaced below
        super().__init__(cell, seed, mix={
            **mix, "sequences": senders, "namespaces": mix["pfbs_per_block"],
            "namespace_zipf_s": 0.0,
            "blob_bytes": max(self.classes.values())})
        self.mix = mix
        self.namespaces = [_pfb.namespace_id(seed, r) for r in range(senders)]
        self.rotation = int(np.random.default_rng([seed, 31]).integers(
            0, len(self.cycle)))
        # the same wallet signing for another chain: same keys, same
        # account numbers, another sign-doc
        self.forger = _pfb.Client(config["chain_id"] + "-forged", seed,
                                  senders)
        self.forged: dict[int, bytes] = {}        # block index -> raw tx
        self.forged_codes: dict[int, int] = {}    # block index -> CheckTx
        accounts = self.client._signer.accounts
        self._number_of = {
            accounts[a].priv.public_key().compressed: accounts[a].number
            for a in self.client.addresses}
        self._rejected_before = 0
        self._layouts: dict[int, tuple[int, bool]] = {}

    # -- generation ---------------------------------------------------------

    def square_of(self, index: int) -> int:
        """The first blocks are one of each class, smallest first; then the
        cycle, rotated."""
        n = len(self.classes)
        if index < n:
            return sorted(self.classes)[index]
        return self.cycle[(index - n + self.rotation) % len(self.cycle)]

    def _make_block(self, index: int) -> list[bytes]:
        rng = np.random.default_rng([self.seed, index])
        n_seq = len(self.client.addresses)
        n_bytes = self.classes[self.square_of(index)]

        def blob(sender: int):
            return [(self.namespaces[sender], rng.integers(
                0, 256, n_bytes, dtype=np.uint8).tobytes())]

        raws = []
        for slot in rng.permutation(self.per_block):
            sender = (index * self.per_block + int(slot)) % n_seq
            raws.append(self.client.pay_for_blobs(sender, blob(sender)))
        if index % self.mix["forged_every"] == self.mix["forged_every"] - 1:
            # the next block's first sender: funded, known to the chain,
            # not in this block, and at the sequence the chain holds for
            # it when this block is offered (every earlier block is in)
            sender = ((index + 1) * self.per_block) % n_seq
            addr = self.client.addresses[sender]
            self.forger._signer.accounts[addr].sequence = \
                self.client._signer.accounts[addr].sequence
            raw = self.forger.pay_for_blobs(sender, blob(sender))
            self.client.sent[raw] = self.forger.sent[raw]
            self.forged[index] = raw
        return raws

    def generate_in_background(self) -> None:
        """As the base class's, but ahead by `sign_ahead_blocks` at most:
        a block of these is signed in milliseconds, and a thread left to
        sign a gigabyte of them holds the interpreter against the
        validator's start for the better part of a minute."""
        ahead = self.mix["sign_ahead_blocks"]

        def run():
            try:
                while not self._stop and len(self.pool) < ahead:
                    self.pool.append(self._make_block(len(self.pool)))
            except BaseException as e:  # re-raised by ready()
                self._error = e

        self._thread = threading.Thread(target=run, name="bench-txgen",
                                             daemon=True)
        self._thread.start()

    def signature_is_valid(self, raw: bytes) -> bool:
        return plain_sig.verify_tx(raw, self.config["chain_id"],
                                   self._number_of.get)

    def check_classes(self) -> None:
        """Each class gives its square, by the reference layout under the
        governed bound, for this seed's first block of the class."""
        for index in range(len(self.classes)):
            want = self.square_of(index)
            got = da.build_ods(self.pool[index], self.k).shape[0]
            if got != want:
                raise cells.CellError(
                    f"{self.per_block} blobs of {self.classes[want]} B lay "
                    f"out a square of {got}, the mix says {want}")

    def layout_of(self, block: dict) -> tuple[int, bool]:
        """(the square the reference lays the block's txs out in under the
        governed bound, whether the layout under the versioned bound is
        another): ROADMAP R-x1's case is the second."""
        index = block["index"]
        if index not in self._layouts:
            txs = block["produced"].txs
            if not txs:
                self._layouts[index] = (1, False)
            else:
                mine = da.build_ods(txs, self.k)
                other = da.build_ods(txs, self.config[
                    "versioned_square_size_upper_bound"])
                self._layouts[index] = (
                    mine.shape[0], not np.array_equal(mine, other))
        return self._layouts[index]

    # -- set-up -------------------------------------------------------------

    def one_block(self, sut, spans) -> dict:
        return super().one_block(_Offer(sut, self, self.next_block), spans)

    def warm(self, sut, spans, log) -> list[dict]:
        self._rejected_before = sut.counters().get(BATCH_REJECTED, 0)
        out = []
        for i in range(len(self.classes) + len(self.cycle)):
            if i == len(self.classes):
                self._join_signing_thread()
            self.generate(self.next_block + 1)
            rec = self.one_block(sut, spans)
            log(phase="warm_block", height=rec["produced"].height,
                square_size=rec["produced"].square_size,
                seconds=round(rec["loop_s"], 3))
            out.append(rec)
        return out

    def ready(self, warm_records: list[dict], seconds: float) -> dict:
        """The pool gets `pool_headroom` times the blocks the window would
        complete at the mean pace of the warm-up's whole cycle."""
        pace = float(np.mean([r["loop_s"]
                              for r in warm_records[-len(self.cycle):]]))
        target = len(warm_records) + int(np.ceil(
            self.mix["pool_headroom"] * seconds / pace))
        self.generate(target)
        del self.pool[target:]
        for index in [i for i in self.forged if i >= target]:
            del self.forged[index]
        return {"pool_blocks": len(self.pool), "pace_block_s": round(pace, 4)}

    # -- what the window showed ---------------------------------------------

    def units(self, records: dict) -> dict:
        blocks = records["blocks"]
        sizes = [b["produced"].square_size for b in blocks]
        return {
            **super().units(records),
            # per block: the signatures offered to the batched verifier
            "sig_lanes": [len(b["offered"]) + (b["index"] in self.forged)
                          for b in blocks],
            "squares": {str(k): sizes.count(k) for k in sorted(set(sizes))},
            "forged_offered": sum(b["index"] in self.forged for b in blocks),
            "pool_used": self.next_block,
            # for how many blocks the layout under the governed bound and
            # under the versioned one differ (ROADMAP R-x1's case)
            "blocks_where_bounds_differ": sum(
                self.layout_of(b)[1] for b in blocks),
        }

    # -- correctness --------------------------------------------------------

    def collect(self, sut, records: dict, warm_records: list[dict]) -> dict:
        out = super().collect(sut, records, warm_records)
        out["batch_rejected"] = None if sut.is_reference else (
            sut.counters().get(BATCH_REJECTED, 0) - self._rejected_before)
        return out

    def _kept(self, blocks: list[dict], n_warm: int) -> set[int]:
        """The window's first and last block, for each class they leave
        out one block of it that the seed draws, and seeded blocks up to
        `reference_blocks` (so: that many, or one more when the first and
        the last are of one class)."""
        picked = {n_warm, len(blocks) - 1} & set(range(len(blocks)))
        rng = np.random.default_rng([self.seed, 7])
        for square in sorted(self.classes):
            of_class = [i for i, b in enumerate(blocks)
                        if self.square_of(b["index"]) == square]
            if of_class and not picked & set(of_class):
                picked.add(int(rng.choice(of_class)))
        for i in rng.permutation(len(blocks)):
            if len(picked) >= min(self.mix["reference_blocks"], len(blocks)):
                break
            picked.add(int(i))
        return picked

    def compare(self, collected: dict) -> dict:
        blocks = collected["blocks"]
        refused = missing = deliver_failed = gaps = not_warm = 0
        header_bad = proof_bad = share_bad = 0
        root_bad = axis_bad = size_bad = hash_bad = 0
        forged_acked = forged_landed = forged_offered = sig_bad = 0
        ledger = Ledger(self.accounts(), VALIDATOR_POWER)
        prev_height = prev_hash = None
        picked = self._kept(blocks, collected["n_warm"])
        for i, b in enumerate(blocks):
            p = b["produced"]
            landed, offered = set(p.txs), set(b["offered"])
            refused += sum(1 for c in b["codes"] if c != 0)
            forged = self.forged.get(b["index"])
            if forged is not None:
                forged_offered += 1
                acked = self.forged_codes[b["index"]] == 0
                forged_acked += acked
                forged_landed += forged in landed
                sig_bad += self.signature_is_valid(forged) != acked
                landed.discard(forged)
            missing += len(offered - landed) + len(landed - offered)
            deliver_failed += sum(1 for c in p.tx_codes if c != 0)
            not_warm += 0 if b["warm"] else 1
            if prev_height is not None and p.height != prev_height + 1:
                gaps += 1
            if (prev_hash is not None and p.prev_app_hash != prev_hash) \
                    or not p.app_hash or p.app_hash == p.prev_app_hash:
                hash_bad += 1
            prev_height, prev_hash = p.height, p.app_hash
            ledger.begin_block(p.time_unix)
            for raw, code in zip(p.txs, p.tx_codes):
                if code == 0 and raw in self.client.sent:
                    ledger.deliver(*self.client.sent[raw][:2])
            rows, cols = b["header"]
            if len(rows) != 2 * p.square_size or \
                    da.data_root(rows, cols) != p.data_hash:
                header_bad += 1
            # the block's square by the reference layout, under the bound
            # the proposer used, and the class the mix promised
            want = self.layout_of(b)[0]
            size_bad += p.square_size != want
            size_bad += want != self.square_of(b["index"])
            ref = None
            if i in picked and p.txs:
                ref = da.commit_block(p.txs, self.k)
                root_bad += ref["data_root"] != p.data_hash
                axis_bad += sum(a != b_ for a, b_ in zip(
                    ref["row_roots"] + ref["col_roots"], rows + cols))
                sig_bad += sum(
                    self.signature_is_valid(raw) != (code == 0)
                    for raw, code in zip(b["offered"], b["codes"]))
            bp, bs = check_samples(b["samples"], b["cells"], rows,
                                   None if ref is None else ref["eds"])
            proof_bad += bp
            share_bad += bs
        account_bad = sum(tuple(ledger.accounts[addr]) != tuple(got)
                          for addr, got in collected["accounts"].items())
        totals = ledger.totals()
        totals_bad = sum(collected["ledger"].get(name) != want
                         for name, want in totals.items())
        rejected = collected["batch_rejected"]
        return {
            "checktx_refused": [refused, 0],
            "txs_not_in_their_block": [missing, 0],
            "delivertx_failed": [deliver_failed, 0],
            "height_gaps": [gaps, 0],
            "warmer_not_idle": [not_warm, 0],
            "square_size_wrong": [int(size_bad), 0],
            "data_root_vs_reference": [int(root_bad), 0],
            "axis_roots_vs_reference": [int(axis_bad), 0],
            "served_roots_vs_data_hash": [header_bad, 0],
            "sample_proofs_failed": [proof_bad, 0],
            "sample_shares_vs_reference": [share_bad, 0],
            "accounts_vs_reference": [int(account_bad), 0],
            "supply_and_fees_vs_reference": [int(totals_bad), 0],
            "app_hash_chain_broken": [hash_bad, 0],
            "forged_tx_acknowledged": [int(forged_acked), 0],
            "forged_tx_in_a_block": [int(forged_landed), 0],
            # the plain validator has no batch: nothing to hold it to
            "forged_tx_not_rejected_by_batch": [
                0 if rejected is None else abs(forged_offered - rejected), 0],
            "signatures_vs_reference": [int(sig_bad), 0],
        }


def prepare(cell, seed: int, seconds: float) -> Traffic:
    from celestia_app_tpu import appconsts

    stated = cell.config["versioned_square_size_upper_bound"]
    versioned = appconsts.square_size_upper_bound(cell.config["app_version"])
    if stated != versioned:
        raise cells.CellError(
            f"the configuration states a versioned bound of {stated}, the "
            f"program's is {versioned}")
    traffic = Traffic(cell, seed)
    traffic.generate(len(traffic.classes))
    traffic.check_classes()
    traffic.generate_in_background()
    return traffic
