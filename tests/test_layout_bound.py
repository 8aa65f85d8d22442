"""A read lays a height out under the bound its proposer used (ROADMAP R-x1).

The proposer lays a block out under min(gov_max_square_size, the versioned
bound); a PFB's worst-case reservation prices each share index by that
bound, so the same txs laid out under another bound can put every blob
somewhere else. These tests CONSTRUCT such a block — the PFB count is
searched with the plain reference layout until `build_ods(txs, governed)`
and `build_ods(txs, 128)` differ — on chains governed at 8 and at 64 under
the versioned 128, and hold every read of that height to the header's data
root: `SampleCore.sample_many`, `BlobCore.namespaces_many`,
`custom/shareInclusionProof`; the same after governance raised
`gov_max_square_size` at a later height, after a restart from `data_dir`,
and for a block an earlier version stored without its bound.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from reference import plain_da  # noqa: E402

from celestia_app_tpu import appconsts  # noqa: E402
from celestia_app_tpu.chain import gov as gov_mod  # noqa: E402
from celestia_app_tpu.chain import storage  # noqa: E402
from celestia_app_tpu.chain.app import App  # noqa: E402
from celestia_app_tpu.chain.crypto import PrivateKey  # noqa: E402
from celestia_app_tpu.chain.modules import estimate_pfb_gas  # noqa: E402
from celestia_app_tpu.chain.node import Node  # noqa: E402
from celestia_app_tpu.chain.query import (  # noqa: E402
    QueryRouter,
    share_proof_from_json,
)
from celestia_app_tpu.chain.tx import MsgSubmitProposal, MsgVote  # noqa: E402
from celestia_app_tpu.client.tx_client import Signer  # noqa: E402
from celestia_app_tpu.da.blob import Blob  # noqa: E402
from celestia_app_tpu.da.namespace import Namespace  # noqa: E402
from celestia_app_tpu.das.blob_server import BlobCore  # noqa: E402
from celestia_app_tpu.das.server import SampleCore  # noqa: E402
from celestia_app_tpu.utils import telemetry  # noqa: E402

T0 = 1_700_000_000.0
VERSIONED = appconsts.square_size_upper_bound(3)
SENDERS = 40
# blobs a PFB: at 64 an index costs 2 bytes against 3 under 128, at 8 it
# costs 1 against 3, so a few blobs a PFB widen the window a boundary of
# the compact shares has to fall into
BLOBS_PER_PFB = {8: 1, 64: 4}
FALLBACKS = "query.layout_bound_fallbacks"


class Chain:
    def __init__(self, gov: int, home: str):
        self.gov = gov
        self.chain_id = f"layout-{gov}"
        self.home = home
        self.privs = [PrivateKey.from_seed(b"layout-%d" % i)
                      for i in range(SENDERS)]
        self.signer = Signer(self.chain_id)
        self.addrs = [self.signer.add_account(p, number=i)
                      for i, p in enumerate(self.privs)]
        self.namespaces = [Namespace.v0(b"lay" + bytes([i + 1]))
                           for i in range(4)]
        self.app = App(chain_id=self.chain_id, engine="host", data_dir=home)
        self.app.init_chain({
            "time_unix": T0,
            "accounts": [{"address": a.hex(), "balance": 10**15}
                         for a in self.addrs],
            "validators": [{"operator": self.addrs[0].hex(), "power": 10}],
            "gov_max_square_size": gov,
        })
        self.node = Node(self.app)

    def pfbs(self, n: int, seed: int) -> list[bytes]:
        """One PFB from each of the first n senders at its next sequence
        (`produce` moves the signer's sequences, so a candidate block that
        is never offered costs none)."""
        rng = np.random.default_rng([self.gov, seed])
        per = BLOBS_PER_PFB[self.gov]
        gas = 2 * estimate_pfb_gas([400] * per)
        raws = []
        for i in range(n):
            blobs = [Blob(self.namespaces[(i + j) % 4],
                          rng.integers(0, 256, 400, dtype=np.uint8).tobytes())
                     for j in range(per)]
            raws.append(self.signer.create_pay_for_blobs(
                self.addrs[i], blobs, fee=gas, gas_limit=gas))
        return raws

    def crossing_block(self) -> list[bytes]:
        """The fewest PFBs whose layout under the governed bound differs
        from their layout under the versioned one."""
        for n in range(1, SENDERS + 1):
            raws = self.pfbs(n, seed=n)
            try:
                mine = plain_da.build_ods(raws, self.gov)
            except ValueError:      # no longer fits the governed square
                break
            other = plain_da.build_ods(raws, VERSIONED)
            if mine.shape != other.shape or not np.array_equal(mine, other):
                return raws
        raise AssertionError(
            f"no PFB count up to {SENDERS} crosses a compact-share "
            f"boundary at gov={self.gov}")

    def produce(self, raws: list[bytes], t: float | None = None):
        codes = [r.code for r in self.node.broadcast_txs(raws)]
        assert codes == [0] * len(raws), codes
        block, results = self.node.produce_block(
            t=t if t is not None else T0 + self.app.height + 1)
        assert [r.code for r in results] == [0] * len(raws)
        for i in range(len(raws)):
            self.signer.accounts[self.addrs[i]].sequence += 1
        assert self.app.da_warmer.wait_idle(60)
        return block

    def raise_gov_bound(self, value: int) -> None:
        """The real route: a param-change proposal, the validator's yes,
        a block past the voting period."""
        addr = self.addrs[0]
        msg = MsgSubmitProposal(
            proposer=addr,
            changes_json=json.dumps(
                [{"param": "blob/gov_max_square_size", "value": value}],
                sort_keys=True).encode(),
            initial_deposit=gov_mod.DEFAULT_MIN_DEPOSIT, title="raise")
        for m, t in ((msg, 3600.0), (MsgVote(addr, 1, "yes"), 7200.0)):
            tx = self.signer.create_tx(addr, [m], fee=5000,
                                       gas_limit=400_000)
            assert self.node.broadcast_tx(tx.encode()).code == 0
            _block, results = self.node.produce_block(
                t=T0 + self.app.height * 10 + t)
            assert results[0].code == 0, results[0].log
            self.signer.accounts[addr].sequence += 1
        self.node.produce_block(t=T0 + 9 * 24 * 3600.0)
        assert self.app.max_effective_square_size(
            self.node.app._ctx(self.app.store.branch(), None, check=False)
        ) == min(value, VERSIONED)


def reads_answer_against_the_header(app, block, namespaces,
                                    bound: int) -> None:
    """Every caller of build_prover_entry, each on a serving plane that
    nobody seeded: the sample server, the blob server, the query router."""
    height, want = block.header.height, block.header.data_hash
    ref = plain_da.commit_block(list(block.txs), bound)
    assert ref["data_root"] == want      # the reference agrees on the block
    core = SampleCore(app)
    width = 2 * block.header.square_size
    cells = [(0, 0), (1, width - 1), (width - 1, 0), (width // 2, 3)]
    reply = core.sample_many(height, cells)
    assert reply["data_root"] == want.hex()
    assert not [s for s in reply["samples"] if "error" in s]
    header = core.header(height)
    assert plain_da.data_root(
        [bytes.fromhex(r) for r in header["row_roots"]],
        [bytes.fromhex(c) for c in header["col_roots"]]) == want
    read = BlobCore(SampleCore(app)).namespaces_many(
        [{"height": height, "namespace": ns.raw.hex()} for ns in namespaces])
    for q in read["queries"]:
        assert "error" not in q, q
        assert q["present"] and q["data_root"] == want.hex()
    out = QueryRouter(app).query(
        "custom/shareInclusionProof",
        {"height": height, "start": 0, "end": 1,
         "namespace": plain_da.PFB_NS.hex()})
    assert out["data_root"] == want.hex()
    assert share_proof_from_json(out["proof"]).verify(want)


@pytest.fixture(params=[8, 64], ids=["gov8", "gov64"])
def chain(request, tmp_path):
    c = Chain(request.param, str(tmp_path / "home"))
    yield c
    c.app.close()


def test_the_constructed_block_is_laid_out_under_the_governed_bound(chain):
    raws = chain.crossing_block()
    block = chain.produce(raws)
    ref = plain_da.commit_block(raws, chain.gov)
    assert block.header.square_size == ref["square_size"]
    assert block.header.data_hash == ref["data_root"]
    # and NOT as the versioned bound would have it: the case is real
    other = plain_da.commit_block(raws, VERSIONED)
    assert other["data_root"] != block.header.data_hash
    _stored, bound = chain.app.db.load_block_and_bound(block.header.height)
    assert bound == chain.gov


def test_reads_of_that_height_answer_against_its_data_root(chain):
    before = telemetry.snapshot()["counters"].get(FALLBACKS, 0)
    block = chain.produce(chain.crossing_block())
    reads_answer_against_the_header(chain.app, block, chain.namespaces,
                                    chain.gov)
    assert telemetry.snapshot()["counters"].get(FALLBACKS, 0) == before


def test_the_same_after_governance_raised_the_bound(chain):
    block = chain.produce(chain.crossing_block())
    chain.raise_gov_bound(VERSIONED)
    # a block proposed now is laid out under the new bound, and recorded so
    later = chain.produce(chain.pfbs(4, seed=99))
    assert chain.app.db.load_block_and_bound(
        later.header.height)[1] == VERSIONED
    reads_answer_against_the_header(chain.app, block, chain.namespaces,
                                    chain.gov)
    reads_answer_against_the_header(chain.app, later, chain.namespaces,
                                    VERSIONED)


def test_the_same_after_a_restart_from_data_dir(chain):
    block = chain.produce(chain.crossing_block())
    chain.app.close()
    again = App(chain_id=chain.chain_id, engine="host", data_dir=chain.home)
    try:
        again.load()
        assert again.height == block.header.height
        reads_answer_against_the_header(again, block, chain.namespaces,
                                        chain.gov)
    finally:
        again.close()
    chain.app = again       # the fixture closes what is open


def test_a_block_stored_without_its_bound_falls_back_counted(chain):
    """A record an earlier version wrote carries no bound: the read uses
    the chain's bound as it stands now (right unless governance moved it
    since) and counts the fallback."""
    block = chain.produce(chain.crossing_block())
    height = block.header.height
    record = storage._encode_block(block)        # as the parent wrote it
    assert storage.LAYOUT_BOUND_KEY.encode() not in record
    chain.app.db.backend.put(storage.BLOCK, height, record)
    chain.app.db.backend.sync()
    assert chain.app.db.load_block_and_bound(height) == (block, None)
    before = telemetry.snapshot()["counters"].get(FALLBACKS, 0)
    reads_answer_against_the_header(chain.app, block, chain.namespaces,
                                    chain.gov)
    assert telemetry.snapshot()["counters"].get(FALLBACKS, 0) > before
