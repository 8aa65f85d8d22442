"""The system under test behind the few calls the traffic drives.

`Validator` is the program: `App(engine=...)` + `Node` + `SampleCore` +
`BlobCore` in this process, entered where an operator's front ends enter it
(`Node.broadcast_txs`, `Node.produce_block`, `SampleCore.header` /
`sample_many`, `BlobCore.namespaces_many`). Replies are handed back as the
program made them and decoded only after the window (a client's work, not
the server's). `reference/plain_node.PlainValidator` offers the same calls
from the plain reference — the control, and the CPU tests' second system.
"""

from __future__ import annotations

import base64
import dataclasses
import os
import shutil
import tempfile

T0 = 1_700_000_000.0
VALIDATOR_POWER = 10

FALLBACK_COUNTERS = (
    "app.device_path_fallback",
    "mesh.engine_fallbacks",
    "mesh.unavailable",
    "blob.device_fallbacks",
    "admission.prevalidate_errors",
)


@dataclasses.dataclass
class Produced:
    height: int
    txs: list[bytes]
    tx_codes: list[int]
    square_size: int
    data_hash: bytes
    prev_app_hash: bytes     # the header's: the state before this block
    app_hash: bytes          # the state after its commit
    time_unix: int


def decode_proof(doc: dict) -> dict:
    return {"start": doc["start"], "end": doc["end"], "total": doc["total"],
            "nodes": [base64.b64decode(n) for n in doc["nodes"]]}


class Validator:
    is_reference = False

    def __init__(self, config: dict, accounts: list[tuple[bytes, int]]):
        from celestia_app_tpu.chain.app import App
        from celestia_app_tpu.chain.node import Node
        from celestia_app_tpu.chain.query import QueryRouter
        from celestia_app_tpu.das.blob_server import BlobCore
        from celestia_app_tpu.das.server import SampleCore

        # under TMPDIR, which the driver gives each side of a comparison
        self._dir = tempfile.mkdtemp(prefix="bench-validator-")
        # `max_square_size`: the consensus-critical home-config cap, which a
        # configuration above the versioned bound states (else None: App's)
        self.app = App(chain_id=config["chain_id"], engine=config["engine"],
                       app_version=config["app_version"],
                       data_dir=os.path.join(self._dir, "data"),
                       max_square_size=config.get("max_square_size"))
        self.app.init_chain({
            "time_unix": T0,
            "accounts": [{"address": a.hex(), "balance": b}
                         for a, b in accounts],
            "validators": [{"operator": accounts[0][0].hex(), "power": VALIDATOR_POWER}],
            "gov_max_square_size": config["gov_max_square_size"],
        })
        self.node = Node(self.app)
        self.core = self.node.attach_das_core(SampleCore(
            self.app, cache_heights=config["served_heights"]))
        self.blob = BlobCore(self.core)
        self._query = QueryRouter(self.app)

    # -- the timed path ---------------------------------------------------

    def offer(self, raws: list[bytes]) -> list[int]:
        return [r.code for r in self.node.broadcast_txs(raws)]

    def produce(self) -> Produced:
        t = T0 + self.app.height + 1
        block, results = self.node.produce_block(t=t)
        return Produced(block.header.height, list(block.txs),
                        [r.code for r in results], block.header.square_size,
                        block.header.data_hash, block.header.app_hash,
                        self.app.last_app_hash, int(t))

    def light_header(self, height: int):
        return self.core.header(height)

    def sample(self, height: int, cells):
        return self.core.sample_many(height, cells)

    def namespaces(self, height: int, namespaces: list[bytes]):
        return self.blob.namespaces_many(
            [{"height": height, "namespace": ns.hex()} for ns in namespaces])

    def wait_warm(self, timeout: float) -> bool:
        return self.app.da_warmer.wait_idle(timeout)

    # -- read after the window ---------------------------------------------

    def host_bytes_last_block(self) -> int:
        return self.app.last_host_bytes_crossed

    def counters(self) -> dict[str, int]:
        from celestia_app_tpu.utils import telemetry

        return dict(telemetry.snapshot()["counters"])

    def account(self, address: bytes) -> tuple[int, int]:
        acc = self._query.query("auth/account",
                                {"address": address.hex()})["account"]
        bal = self._query.query("bank/balance",
                                {"address": address.hex()})["balance"]
        return (acc or {}).get("sequence", 0), bal

    def ledger(self) -> dict[str, int]:
        """The bank's total supply and what its fee and reward module
        accounts hold together, as a query against the committed state."""
        from celestia_app_tpu.chain.modules import FEE_COLLECTOR
        from celestia_app_tpu.chain.sdk_modules import DISTRIBUTION_POOL

        ctx = self._query._ctx()
        return {"supply": self.app.bank.supply(ctx),
                "fees_and_rewards": sum(self.app.bank.balance(ctx, a)
                                        for a in (FEE_COLLECTOR,
                                                  DISTRIBUTION_POOL))}

    @staticmethod
    def refused_in(reply) -> int:
        """Members of a sample or namespace reply that the server refused:
        the cheap look a client takes at every reply as it arrives."""
        members = reply.get("samples") or reply.get("queries") or []
        return sum(1 for m in members if "error" in m)

    @staticmethod
    def decode_header(reply) -> tuple[list[bytes], list[bytes]]:
        return ([bytes.fromhex(r) for r in reply["row_roots"]],
                [bytes.fromhex(c) for c in reply["col_roots"]])

    @staticmethod
    def decode_samples(reply) -> list[dict]:
        out = []
        for doc in reply["samples"]:
            if "error" in doc:
                out.append({"row": doc["row"], "col": doc["col"],
                            "error": doc["error"]})
                continue
            out.append({"row": doc["row"], "col": doc["col"],
                        "share": base64.b64decode(doc["share"]),
                        **decode_proof(doc["proof"])})
        return out

    @staticmethod
    def decode_namespaces(reply) -> list[dict]:
        out = []
        for doc in reply["queries"]:
            if "error" in doc:
                out.append({"namespace": bytes.fromhex(doc["namespace"]),
                            "error": doc["error"]})
                continue
            proof = doc["proof"]
            out.append({
                "namespace": bytes.fromhex(doc["namespace"]),
                "present": doc["present"],
                "shares": [base64.b64decode(s) for s in doc["shares"]],
                "data_root": bytes.fromhex(doc["data_root"]),
                "start_row": proof["row_proof"]["start_row"] if proof else 0,
                "proof_shares": ([base64.b64decode(s) for s in proof["data"]]
                                 if proof else []),
                "row_proofs": ([decode_proof(p)
                                for p in proof["share_proofs"]]
                               if proof else []),
            })
        return out

    def close(self) -> None:
        self.app.da_warmer.wait_idle(60)
        self.app.close()
        shutil.rmtree(self._dir, ignore_errors=True)
