"""A validator made of the plain reference alone: the control.

It takes the program's place behind the calls of `lib/sut.Validator`: admits
every tx (the traffic is made so that none may be refused), commits a block of
them in arrival order, serves samples and namespace reads with its own NMT
proofs, keeps each sender's sequence and balance. Sound, it agrees with the
reference exactly, being it. With `breaks` set it breaks ONE guarantee the
configurations state, the way a later PR might be tempted to:

  drop_acked_tx   an acknowledged tx is left out of its block
  skip_q3         the third parity quadrant is not computed (left zero)
  stale_sample    samples at the tip are served from the height before
  partial_read    a namespace read returns all but the last share
  fees_vanish     a tx's fee leaves its sender and reaches no one

Nothing of the program is imported here.
"""

from __future__ import annotations

import numpy as np

from lib.sut import T0, VALIDATOR_POWER, Produced  # plain values; lib/sut imports the program only inside Validator
from reference import plain_da as da
from reference.plain_state import Ledger

BREAKS = ("drop_acked_tx", "skip_q3", "stale_sample", "partial_read",
          "fees_vanish")


def prove_range(leaves: list[da.Node], start: int, end: int) -> list[bytes]:
    """Out-of-range subtree roots, left to right (celestiaorg/nmt
    ProveRange)."""
    nodes: list[bytes] = []

    def walk(lo: int, hi: int) -> None:
        if hi <= start or lo >= end:
            nodes.append(b"".join(da.nmt_root(leaves[lo:hi])))
        elif hi - lo > 1:
            mid = lo + da._split(hi - lo)
            walk(lo, mid)
            walk(mid, hi)

    walk(0, len(leaves))
    return nodes


class PlainValidator:
    is_reference = True

    def __init__(self, config: dict, accounts: list[tuple[bytes, int]],
                 sent: dict[bytes, tuple], breaks: str | None = None):
        if breaks is not None and breaks not in BREAKS:
            raise ValueError(f"unknown break {breaks!r}; one of {BREAKS}")
        # min(governed bound, hard cap), as the program lays blocks out: the
        # hard cap is the configuration's `max_square_size` where it states
        # one, else the versioned bound
        self.max_k = min(config["gov_max_square_size"],
                         config.get("max_square_size")
                         or da.SQUARE_SIZE_UPPER_BOUND)
        self.keep = config["served_heights"]
        self.breaks = breaks
        self._sent = sent
        self._ledger = Ledger(accounts, VALIDATOR_POWER)
        self._pool: list[bytes] = []
        self._blocks: dict[int, dict] = {}
        self.height = 0
        self._counts = {"da.extend_runs": 0}

    def offer(self, raws: list[bytes]) -> list[int]:
        self._pool += raws
        return [0] * len(raws)

    def produce(self) -> Produced:
        txs, self._pool = self._pool, []
        if self.breaks == "drop_acked_tx" and txs:
            txs = txs[:-1]
        block = da.commit_block(txs, self.max_k)
        if self.breaks == "skip_q3":
            k = block["square_size"]
            block["eds"][k:, k:] = 0
            rows, cols = da.axis_roots(block["eds"])
            block.update(row_roots=rows, col_roots=cols,
                         data_root=da.data_root(rows, cols))
        prev_app_hash = self._ledger.state_hash()
        self.height += 1
        time_unix = int(T0) + self.height
        self._ledger.begin_block(time_unix)
        for raw in txs:
            addr, fee = self._sent[raw][:2]
            self._ledger.deliver(addr, fee,
                                 collect=self.breaks != "fees_vanish")
        self._blocks[self.height] = block
        self._blocks.pop(self.height - max(self.keep, 8), None)
        self._counts["da.extend_runs"] += 1
        return Produced(self.height, txs, [0] * len(txs),
                        block["square_size"], block["data_root"],
                        prev_app_hash, self._ledger.state_hash(), time_unix)

    def light_header(self, height: int):
        b = self._blocks[height]
        return b["row_roots"], b["col_roots"]

    def _row_leaves(self, block: dict, row: int) -> list[da.Node]:
        k = block["square_size"]
        out = []
        for c in range(2 * k):
            share = block["eds"][row, c].tobytes()
            ns = share[:da.NS] if row < k and c < k else da.PARITY_NS
            out.append(da.nmt_leaf(ns, share))
        return out

    def sample(self, height: int, cells):
        if self.breaks == "stale_sample" and height == self.height \
                and height - 1 in self._blocks:
            height -= 1
        block = self._blocks[height]
        out = []
        for row, col in cells:
            leaves = self._row_leaves(block, row)
            out.append({"row": row, "col": col,
                        "share": block["eds"][row, col].tobytes(),
                        "start": col, "end": col + 1, "total": len(leaves),
                        "nodes": prove_range(leaves, col, col + 1)})
        return out

    def namespaces(self, height: int, namespaces: list[bytes]):
        block = self._blocks[height]
        k = block["square_size"]
        ods = block["eds"][:k, :k]
        flat_ns = ods.reshape(-1, da.SHARE)[:, :da.NS]
        out = []
        for ns in namespaces:
            hit = np.flatnonzero(np.all(
                flat_ns == np.frombuffer(ns, dtype=np.uint8), axis=1))
            doc = {"namespace": ns, "present": bool(len(hit)), "shares": [],
                   "data_root": block["data_root"], "start_row": 0,
                   "proof_shares": [], "row_proofs": []}
            if len(hit):
                lo, hi = int(hit[0]), int(hit[-1]) + 1
                if self.breaks == "partial_read" and hi - lo > 1:
                    hi -= 1
                doc["shares"] = [ods.reshape(-1, da.SHARE)[i].tobytes()
                                 for i in range(lo, hi)]
                doc["proof_shares"] = doc["shares"]
                doc["start_row"] = lo // k
                for row in range(lo // k, (hi - 1) // k + 1):
                    s = max(lo, row * k) - row * k
                    e = min(hi, (row + 1) * k) - row * k
                    doc["row_proofs"].append({
                        "start": s, "end": e, "total": 2 * k,
                        "nodes": prove_range(
                            self._row_leaves(block, row), s, e)})
            out.append(doc)
        return out

    def wait_warm(self, timeout: float) -> bool:
        return True

    def host_bytes_last_block(self) -> int:
        return 0

    def counters(self) -> dict[str, int]:
        return dict(self._counts)

    def account(self, address: bytes) -> tuple[int, int]:
        seq, bal = self._ledger.accounts[address]
        return seq, bal

    def ledger(self) -> dict[str, int]:
        return self._ledger.totals()

    @staticmethod
    def refused_in(reply) -> int:
        return 0

    @staticmethod
    def decode_header(reply):
        return reply

    @staticmethod
    def decode_samples(reply):
        return reply

    @staticmethod
    def decode_namespaces(reply):
        return reply

    def close(self) -> None:
        self._blocks.clear()
