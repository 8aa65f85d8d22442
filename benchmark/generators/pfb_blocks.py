"""Closed-loop block production: one full square of PayForBlobs after another.

The block is the cited benchmark's own (celestia-app test/e2e/benchmark/
throughput.go: `BlobSequences` concurrent txsim sequences, each posting one
PFB of `BlobsPerSeq` blobs of `BlobSizes` bytes at a time). txsim, given one
number for each, draws nothing: every PFB has the same shape. A block takes
`pfbs_per_block` of them, as many as fit its square, from the `sequences`
funded senders in rotation: block i carries sequences i*P .. i*P+P-1 (mod
`sequences`), whatever the seed. The mix file gives those numbers; what the
manifest leaves open (namespaces) is listed there under `assumed`.

The seed decides the senders' keys, the blob bytes, which blob goes under
which namespace (the multiset of namespaces, Zipf counts, is the same in
every block), the order of a block's PFBs and the sampled cells. So every
seed does the same work in another order.

One block = offer (`broadcast_txs`) -> produce (`produce_block`) -> one light
node's round at the new height (header + `samples_per_block` cells), which
races the prover warmer as a light node's does -> wait for the warmer to go
idle (at a 6-15 s block interval it always is before the next proposal).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from lib import cells, stats
from lib.client import Client
from reference import plain_da as da


# The warm-up's last blocks run with the signing thread ended and set the pace
# the pool is sized from; before that the thread signs ahead, up to so many
# bytes of blobs.
PACE_BLOCKS = 2
SIGN_AHEAD_BYTES = 1 << 30


def zipf_counts(n_items: int, total: int, s: float) -> list[int]:
    """`total` draws over n_items ranks in Zipf(s) proportion, as whole
    counts (largest remainder), every rank at least once."""
    weights = np.array([1.0 / (r + 1) ** s for r in range(n_items)])
    exact = weights / weights.sum() * (total - n_items)
    counts = [1 + int(x) for x in exact]
    order = np.argsort(-(exact - np.floor(exact)), kind="stable")
    for i in range(total - sum(counts)):
        counts[int(order[i % n_items])] += 1
    return counts


def namespace_id(seed: int, rank: int) -> bytes:
    """Version-0 namespace: 19 zero bytes + 10 user bytes."""
    user = b"bench" + bytes([seed % 251, rank + 1])
    return bytes(19) + user.rjust(10, b"\x00")


class Traffic:
    def __init__(self, cell, seed: int, mix: dict | None = None):
        mix = cell.mix if mix is None else mix
        self.mix = mix
        self.config = cell.config
        self.seed = seed
        self.k = cell.config["gov_max_square_size"]
        self.per_block = mix["pfbs_per_block"]
        self.per_pfb = mix["blobs_per_pfb"]
        self.blob_bytes = mix["blob_bytes"]
        counts = zipf_counts(mix["namespaces"],
                             self.per_block * self.per_pfb,
                             mix["namespace_zipf_s"])
        self.namespaces = [namespace_id(seed, r)
                           for r in range(mix["namespaces"])]
        self._ns_of_slot = [r for r, c in enumerate(counts) for _ in range(c)]
        self.client = Client(cell.config["chain_id"], seed, mix["sequences"])
        self.pool: list[list[bytes]] = []
        self.next_block = 0
        self._stop = False
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    # -- generation ---------------------------------------------------------

    def _make_block(self, index: int) -> list[bytes]:
        rng = np.random.default_rng([self.seed, index])
        ranks = rng.permutation(self._ns_of_slot)
        n_seq = len(self.client.addresses)
        raws = []
        for slot in rng.permutation(self.per_block):
            sender = (index * self.per_block + int(slot)) % n_seq
            blobs = [(self.namespaces[int(ranks[slot * self.per_pfb + j])],
                      rng.integers(0, 256, self.blob_bytes,
                                   dtype=np.uint8).tobytes())
                     for j in range(self.per_pfb)]
            raws.append(self.client.pay_for_blobs(sender, blobs))
        return raws

    def generate(self, upto: int) -> None:
        while len(self.pool) < upto:
            self.pool.append(self._make_block(len(self.pool)))

    def generate_in_background(self) -> None:
        """Blocks beyond the warm-up ones are signed on a thread while the
        validator starts and warms up (mostly compile-cache loads). It signs
        up to SIGN_AHEAD_BYTES of blobs and is ended before the warm-up's
        pace blocks; `ready` signs what is still missing, so nothing is
        signed inside the window."""
        block_bytes = self.per_block * self.per_pfb * self.blob_bytes
        sign_ahead = SIGN_AHEAD_BYTES // block_bytes

        def run():
            try:
                while not self._stop and len(self.pool) < sign_ahead:
                    self.pool.append(self._make_block(len(self.pool)))
            except BaseException as e:  # re-raised by ready()
                self._error = e

        self._thread = threading.Thread(target=run, name="bench-txgen",
                                        daemon=True)
        self._thread.start()

    def _join_signing_thread(self) -> None:
        if self._thread is not None:
            self._stop = True               # it ends after the block in hand
            self._thread.join()
            self._thread = None
        if self._error is not None:
            raise self._error

    def ready(self, warm_records: list[dict], seconds: float) -> dict:
        """Before the window: the pool gets `pool_headroom` times the blocks
        the window would complete at the pace of the warm-up's last
        PACE_BLOCKS blocks (the faster of them), which ran with the signing
        thread ended: no more, whatever was signed ahead, and no fewer."""
        pace = min(r["loop_s"] for r in warm_records[-PACE_BLOCKS:])
        target = len(warm_records) + int(np.ceil(
            self.mix["pool_headroom"] * seconds / pace))
        self.generate(target)
        del self.pool[target:]
        return {"pool_blocks": len(self.pool), "pace_block_s": round(pace, 4)}

    def accounts(self):
        return self.client.genesis_accounts()

    # -- one block through the timed path -----------------------------------

    def one_block(self, sut, spans) -> dict:
        index = self.next_block
        raws = self.pool[index]
        self.next_block += 1
        rng = np.random.default_rng([self.seed, index, 1])
        t0 = time.perf_counter()
        with spans("broadcast_txs"):
            codes = sut.offer(raws)
        with spans("produce_block"):
            produced = sut.produce()
        t1 = time.perf_counter()
        width = 2 * produced.square_size
        cells = [(int(r), int(c)) for r, c in rng.integers(
            0, width, size=(self.mix["samples_per_block"], 2))]
        # the light round comes with the commit and races the prover warmer,
        # as a light node's does; the loop then waits the warmer out, which
        # at a 6-15 s block interval is always done before the next proposal
        header = reply = None
        with spans("first_sample"):
            try:
                header = sut.light_header(produced.height)
                reply = sut.sample(produced.height, cells)
            except Exception as e:  # a refused round is a failed one, not a crash
                print(f"light round at height {produced.height} refused: "
                      f"{e!r}", flush=True)
        t2 = time.perf_counter()
        with spans("warm_wait"):
            warm = sut.wait_warm(600)
        spans.record("commit_to_warm", time.perf_counter() - t1)
        return {"index": index, "offered": raws, "codes": codes,
                "produced": produced, "cells": cells, "header": header,
                "samples": reply, "warm": warm,
                "block_s": t1 - t0, "first_sample_s": t2 - t1,
                "loop_s": time.perf_counter() - t0,
                "host_bytes": sut.host_bytes_last_block()}

    def warm(self, sut, spans, log) -> list[dict]:
        out = []
        for i in range(self.mix["warm_blocks"] + PACE_BLOCKS):
            if i == self.mix["warm_blocks"]:
                self._join_signing_thread()
            self.generate(self.next_block + 1)
            rec = self.one_block(sut, spans)
            log(phase="warm_block", height=rec["produced"].height,
                square_size=rec["produced"].square_size,
                seconds=round(rec["loop_s"], 3))
            out.append(rec)
        return out

    def window(self, sut, seconds: float, spans) -> dict:
        blocks = []
        t_start = time.perf_counter()
        while time.perf_counter() - t_start < seconds:
            if self.next_block >= len(self.pool):
                raise cells.WindowCutShort(
                    f"the pool of {len(self.pool)} signed blocks ran dry "
                    f"{time.perf_counter() - t_start:.1f} s into a window of "
                    f"{seconds} s: raise pool_headroom in a mix of its own")
            blocks.append(self.one_block(sut, spans))
        return {"blocks": blocks, "seconds": time.perf_counter() - t_start}

    # -- what the window showed ---------------------------------------------

    def units(self, records: dict) -> dict:
        blocks = records["blocks"]
        return {"blocks": len(blocks),
                "loop_ms": [round(1e3 * b["loop_s"], 1) for b in blocks],
                "block_ms": [round(1e3 * b["block_s"], 1) for b in blocks],
                "first_sample_ms": [round(1e3 * b["first_sample_s"], 1)
                                    for b in blocks],
                "square_size": [b["produced"].square_size for b in blocks],
                "host_bytes": [b["host_bytes"] for b in blocks]}

    def counts(self, records: dict) -> tuple[int, int]:
        """(txs offered in the window, txs refused or not in their block)."""
        attempted = failed = 0
        for b in records["blocks"]:
            attempted += len(b["offered"])
            landed = set(b["produced"].txs)
            failed += sum(1 for raw, code in zip(b["offered"], b["codes"])
                          if code != 0 or raw not in landed)
        return attempted, failed

    def end_to_end(self, records: dict) -> dict:
        blocks = records["blocks"]
        if not blocks:
            raise RuntimeError("the window completed no block")
        landed = sum(self.client.sent[raw][2]
                     for b in blocks for raw in b["produced"].txs)
        return {
            "blob_throughput": landed / records["seconds"] / 1e6,
            "block_p90": 1e3 * stats.percentile(
                [b["block_s"] for b in blocks], 90),
        }

    # -- correctness --------------------------------------------------------

    def collect(self, sut, records: dict, warm_records: list[dict]) -> dict:
        """What the comparison needs from the live system, read before it is
        closed: every sender's account, and every reply decoded."""
        blocks = warm_records + records["blocks"]
        return {
            "blocks": [{
                "index": b["index"], "offered": b["offered"],
                "codes": b["codes"], "produced": b["produced"],
                "warm": b["warm"], "cells": b["cells"],
                "header": (sut.decode_header(b["header"])
                           if b["header"] is not None else ([], [])),
                "samples": (sut.decode_samples(b["samples"])
                            if b["samples"] is not None else []),
            } for b in blocks],
            "accounts": {a: sut.account(a) for a in self.client.addresses},
            "ledger": sut.ledger(),
            "n_warm": len(warm_records),
        }

    def compare(self, collected: dict) -> dict:
        from lib import compare

        return compare.produce_cell(self, collected)


def prepare(cell, seed: int, seconds: float) -> Traffic:
    traffic = Traffic(cell, seed)
    traffic.generate(cell.mix["warm_blocks"])
    traffic.generate_in_background()
    return traffic
