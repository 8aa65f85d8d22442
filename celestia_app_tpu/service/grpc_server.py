"""gRPC services: the reference's :9090 surface — tx service + the query
services the client bootstrap depends on.

pkg/user/tx_client.go broadcasts over gRPC (BroadcastMode_SYNC,
tx_client.go:320-330) and estimates gas via Simulate; GetTx backs
ConfirmTx polling. SetupTxClient additionally bootstraps over five query
RPCs (tx_client.go:147-198): tendermint GetLatestBlock (chain-id +
app version), auth Account (number/sequence), node Config + params/minfee
(min gas price); bank Balance and celestia.blob.v1 Params round out the
module query surface clients use. This server exposes all of them with the
real service/method names and the real cosmos wire messages (hand-rolled
codecs in wire/txpb.py, cross-checked against the protobuf runtime), so a
generated cosmos client stub can point at it unchanged. Handlers run
under the same single-writer lock as the HTTP service.

No protoc codegen: grpcio's generic method handlers with identity
serializers carry the raw message bytes; the codecs do the rest.
"""

from __future__ import annotations

import hashlib
import threading
from concurrent import futures

import grpc

from celestia_app_tpu.wire import bech32, txpb

SERVICE = "cosmos.tx.v1beta1.Service"
TM_SERVICE = "cosmos.base.tendermint.v1beta1.Service"
NODE_SERVICE = "cosmos.base.node.v1beta1.Service"
AUTH_QUERY = "cosmos.auth.v1beta1.Query"
BANK_QUERY = "cosmos.bank.v1beta1.Query"
PARAMS_QUERY = "cosmos.params.v1beta1.Query"
BLOB_QUERY = "celestia.blob.v1.Query"
MINFEE_QUERY = "celestia.minfee.v1.Query"
STAKING_QUERY = "cosmos.staking.v1beta1.Query"
GOV_QUERY = "cosmos.gov.v1beta1.Query"
DA_SERVICE = "celestia_tpu.da.v1.DAService"


class DAGrpcService:
    """gRPC transport for the stateless DA core (§7.1.7 shim surface) —
    the same DACore the HTTP /da/* routes use, encoded per
    proto/celestia_tpu/da/v1/da.proto with the hand-rolled codec
    (wire/proto.py). No node state, no lock: callers are foreign
    processes swapping da.ExtendShares for ExtendAndCommit."""

    def __init__(self, da_core):
        self.core = da_core

    def extend_and_commit(self, request: bytes, context) -> bytes:
        from celestia_app_tpu.service.da_service import DAError
        from celestia_app_tpu.wire import proto as p

        req = p.Fields(request)
        # raw bytes straight through — no base64 detour on the hot path
        # (an 8 MB 128x128 ODS per block is exactly what this service
        # exists to accelerate)
        payload = {"ods": req.get_bytes(1)}
        k = req.get_int(2)
        if k:
            payload["square_size"] = k
        try:
            out = self.core.extend_and_commit(payload)
        except DAError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        return b"".join([
            p.field_varint(1, out["square_size"]),
            p.field_repeated_bytes(
                2, [bytes.fromhex(r) for r in out["row_roots"]]),
            p.field_repeated_bytes(
                3, [bytes.fromhex(r) for r in out["col_roots"]]),
            p.field_bytes(4, bytes.fromhex(out["data_root"])),
        ])

    def prove_shares(self, request: bytes, context) -> bytes:
        import base64

        from celestia_app_tpu.service.da_service import DAError
        from celestia_app_tpu.wire import proto as p

        req = p.Fields(request)
        payload = {"start": req.get_int(3), "end": req.get_int(4)}
        if req.has(1):
            payload["data_root"] = req.get_bytes(1).hex()
        if req.has(2):
            payload["ods"] = req.get_bytes(2)
        if req.has(5):
            payload["namespace"] = req.get_bytes(5).hex()
        try:
            out = self.core.prove_shares(payload)
        except DAError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        pf = out["proof"]

        def nmt_range(sp: dict) -> bytes:
            return b"".join([
                p.field_varint(1, sp["start"]),
                p.field_varint(2, sp["end"]),
                p.field_varint(3, sp["total"]),
                p.field_repeated_bytes(
                    4, [base64.b64decode(n) for n in sp["nodes"]]),
            ])

        def merkle(mp: dict) -> bytes:
            return b"".join([
                p.field_varint(1, mp["index"]),
                p.field_varint(2, mp["total"]),
                p.field_bytes(3, base64.b64decode(mp["leaf_hash"])),
                p.field_repeated_bytes(
                    4, [base64.b64decode(a) for a in mp["aunts"]]),
            ])

        rp = pf["row_proof"]
        row_proof = b"".join([
            p.field_repeated_bytes(
                1, [bytes.fromhex(r) for r in rp["row_roots"]]),
            b"".join(p.field_message(2, merkle(mp))
                     for mp in rp["proofs"]),
            p.field_varint(3, rp["start_row"]),
            p.field_varint(4, rp["end_row"]),
        ])
        share_proof = b"".join([
            p.field_repeated_bytes(
                1, [base64.b64decode(d) for d in pf["data"]]),
            b"".join(p.field_message(2, nmt_range(sp))
                     for sp in pf["share_proofs"]),
            p.field_bytes(3, bytes.fromhex(pf["namespace"])),
            p.field_message(4, row_proof),
            p.field_varint(5, pf["start_share"]),
            p.field_varint(6, pf["end_share"]),
        ])
        return b"".join([
            p.field_message(1, share_proof),
            p.field_bytes(2, bytes.fromhex(out["data_root"])),
        ])


class CosmosTxService:
    def __init__(self, node, lock: threading.Lock | None = None):
        self.node = node
        self.lock = lock or threading.Lock()

    # -- handlers (bytes in, bytes out) ---------------------------------

    def broadcast_tx(self, request: bytes, context) -> bytes:
        tx_bytes, mode = txpb.parse_broadcast_tx_request(request)
        if mode not in (0, txpb.BROADCAST_MODE_SYNC):
            # ASYNC/BLOCK semantics are NOT silently downgraded to SYNC —
            # a BLOCK-mode caller would misread height=0 as committed
            context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"only BROADCAST_MODE_SYNC is supported, got mode={mode}",
            )
        with self.lock:
            res = self.node.broadcast_tx(tx_bytes)
        resp = txpb.tx_response_pb(
            height=0,  # SYNC mode: not yet in a block
            txhash=hashlib.sha256(tx_bytes).hexdigest().upper(),
            code=res.code,
            raw_log=res.log,
            gas_wanted=res.gas_wanted,
            gas_used=res.gas_used,
        )
        return txpb.broadcast_tx_response_pb(resp)

    def simulate(self, request: bytes, context) -> bytes:
        tx_bytes = txpb.parse_simulate_request(request)
        with self.lock:
            res = self.node.app.simulate_tx(tx_bytes)
        if res.code != 0:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                          f"simulation failed: {res.log}")
        return txpb.simulate_response_pb(0, res.gas_used)

    def get_tx(self, request: bytes, context) -> bytes:
        want = txpb.parse_get_tx_request(request).lower()
        try:
            want_raw = bytes.fromhex(want)
        except ValueError:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                          f"tx hash must be hex, got {want!r}")
        with self.lock:
            entry = self.node.committed.get(want_raw)
            pending = (getattr(self.node, "pool", None) is not None
                       and entry is None and self.node.pool.has(want_raw))
        if entry is None:
            # distinguish "still in the mempool" from "unknown" — a
            # ConfirmTx poller needs to keep waiting for the former and
            # may resubmit on the latter (tx_client.go:430 PENDING state)
            context.abort(
                grpc.StatusCode.NOT_FOUND,
                f"tx {want} pending in mempool" if pending
                else f"tx {want} not found",
            )
        height, res = entry
        resp = txpb.tx_response_pb(
            height=height,
            txhash=want.upper(),
            code=res.code,
            raw_log=res.log,
            gas_wanted=res.gas_wanted,
            gas_used=res.gas_used,
        )
        return txpb.get_tx_response_pb(resp)


class QueryServices:
    """The bootstrap query surface (one instance serves all five services).
    Reads go through the app's keepers under the shared lock, mirroring the
    HTTP QueryRouter's accessors (chain/query.py)."""

    def __init__(self, node, lock: threading.Lock):
        self.node = node
        self.lock = lock

    def _ctx(self):
        from celestia_app_tpu.chain.state import Context, InfiniteGasMeter

        app = self.node.app
        return Context(app.store, InfiniteGasMeter(), app.height, 0.0,
                       app.chain_id, app.app_version)

    # -- cosmos.base.tendermint.v1beta1.Service -------------------------

    def get_latest_block(self, request: bytes, context) -> bytes:
        with self.lock:
            app = self.node.app
            return txpb.get_latest_block_response_pb(
                app.chain_id, app.height, app.app_version
            )

    # -- cosmos.base.node.v1beta1.Service -------------------------------

    def config(self, request: bytes, context) -> bytes:
        from celestia_app_tpu import appconsts

        price = getattr(self.node.app, "min_gas_price",
                        appconsts.DEFAULT_MIN_GAS_PRICE)
        return txpb.node_config_response_pb(
            f"{price:.18f}{appconsts.BOND_DENOM}"
        )

    # -- cosmos.auth.v1beta1.Query --------------------------------------

    def account(self, request: bytes, context) -> bytes:
        addr_str = txpb.parse_query_account_request(request)
        try:
            addr = bech32.decode(addr_str, bech32.HRP_ACCOUNT)
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        with self.lock:
            acc = self.node.app.auth.account(self._ctx(), addr)
        if acc is None:
            # the reference returns NotFound for unknown accounts and
            # SetupTxClient skips them (tx_client.go:176-180)
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"account {addr_str} not found")
        pub = bytes.fromhex(acc["pubkey"]) if acc.get("pubkey") else None
        base = txpb.base_account_pb(addr_str, pub, acc["number"], acc["sequence"])
        return txpb.query_account_response_pb(base)

    # -- cosmos.bank.v1beta1.Query --------------------------------------

    def balance(self, request: bytes, context) -> bytes:
        from celestia_app_tpu import appconsts

        addr_str, denom = txpb.parse_query_balance_request(request)
        try:
            addr = bech32.decode(addr_str, bech32.HRP_ACCOUNT)
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        with self.lock:
            amount = self.node.app.bank.balance(self._ctx(), addr)
        return txpb.query_balance_response_pb(
            denom or appconsts.BOND_DENOM, amount
        )

    # -- cosmos.params.v1beta1.Query ------------------------------------

    def subspace_params(self, request: bytes, context) -> bytes:
        import json

        subspace, key = txpb.parse_query_subspace_params_request(request)
        if subspace == "minfee" and key == "NetworkMinGasPrice":
            if self.node.app.app_version < 2:
                # v1 has no minfee subspace; the reference surfaces exactly
                # this error string, which QueryMinimumGasPrice matches on
                # to fall back to the local price (tx_client.go:580)
                context.abort(grpc.StatusCode.NOT_FOUND,
                              "unknown subspace: minfee")
            with self.lock:
                price = self.node.app.minfee.network_min_gas_price(self._ctx())
            return txpb.query_subspace_params_response_pb(
                subspace, key, json.dumps(f"{price:.18f}")
            )
        context.abort(grpc.StatusCode.NOT_FOUND,
                      f"unknown subspace: {subspace}")

    # -- celestia.blob.v1.Query -----------------------------------------

    def blob_params(self, request: bytes, context) -> bytes:
        with self.lock:
            p = self.node.app.blob.params(self._ctx())
        return txpb.blob_params_response_pb(
            p["gas_per_blob_byte"], p["gov_max_square_size"]
        )

    # -- cosmos.staking.v1beta1.Query -----------------------------------

    def staking_validator(self, request: bytes, context) -> bytes:
        addr_str = txpb.parse_query_validator_request(request)
        try:
            op = bech32.decode(addr_str, bech32.HRP_VALOPER)
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        with self.lock:
            v = self.node.app.staking.validator(self._ctx(), op)
            if v is None:
                context.abort(grpc.StatusCode.NOT_FOUND,
                              f"validator {addr_str} not found")
            return txpb.query_validator_response_pb(txpb.validator_pb(
                op, v["jailed"], v["bonded"], v["tokens"]
            ))

    def staking_validators(self, request: bytes, context) -> bytes:
        with self.lock:
            ctx = self._ctx()
            out = []
            for op, _power in self.node.app.staking.validators(ctx):
                v = self.node.app.staking.validator(ctx, op)
                out.append(txpb.validator_pb(
                    op, v["jailed"], v["bonded"], v["tokens"]
                ))
        return txpb.query_validators_response_pb(out)

    # -- cosmos.gov.v1beta1.Query ---------------------------------------

    def gov_proposal(self, request: bytes, context) -> bytes:
        pid = txpb.parse_query_proposal_request(request)
        with self.lock:
            p = self.node.app.gov.proposal(self._ctx(), pid)
        if p is None:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          f"proposal {pid} doesn't exist")
        return txpb.query_proposal_response_pb(p["id"], p["status"])

    # -- celestia.minfee.v1.Query ---------------------------------------

    def network_min_gas_price(self, request: bytes, context) -> bytes:
        if self.node.app.app_version < 2:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          "minfee is a v2+ module")
        with self.lock:
            price = self.node.app.minfee.network_min_gas_price(self._ctx())
        return txpb.minfee_response_pb(price)


def _identity(x: bytes) -> bytes:
    return x


def _handler(fn):
    return grpc.unary_unary_rpc_method_handler(
        fn, request_deserializer=_identity, response_serializer=_identity
    )


class GrpcTxServer:
    def __init__(self, node, host: str = "127.0.0.1", port: int = 9090,
                 lock: threading.Lock | None = None, da_core=None):
        self.service = CosmosTxService(node, lock)
        self.queries = QueryServices(node, self.service.lock)
        # share the caller's DACore when both transports live in one
        # process (cli start --grpc): an ExtendAndCommit over gRPC must
        # be provable over HTTP by data_root from ONE square cache
        if da_core is None:
            from celestia_app_tpu.service.da_service import DACore

            da_core = DACore(
                engine="device" if node.app.engine == "device" else "host"
            )
        self.da = DAGrpcService(da_core)
        q = self.queries
        services = {
            DA_SERVICE: {
                "ExtendAndCommit": _handler(self.da.extend_and_commit),
                "ProveShares": _handler(self.da.prove_shares),
            },
            SERVICE: {
                "BroadcastTx": _handler(self.service.broadcast_tx),
                "Simulate": _handler(self.service.simulate),
                "GetTx": _handler(self.service.get_tx),
            },
            TM_SERVICE: {"GetLatestBlock": _handler(q.get_latest_block)},
            NODE_SERVICE: {"Config": _handler(q.config)},
            AUTH_QUERY: {"Account": _handler(q.account)},
            BANK_QUERY: {"Balance": _handler(q.balance)},
            PARAMS_QUERY: {"Params": _handler(q.subspace_params)},
            BLOB_QUERY: {"Params": _handler(q.blob_params)},
            MINFEE_QUERY: {"NetworkMinGasPrice": _handler(q.network_min_gas_price)},
            STAKING_QUERY: {
                "Validator": _handler(q.staking_validator),
                "Validators": _handler(q.staking_validators),
            },
            GOV_QUERY: {"Proposal": _handler(q.gov_proposal)},
        }
        self.server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
        self.server.add_generic_rpc_handlers(tuple(
            grpc.method_handlers_generic_handler(name, handlers)
            for name, handlers in services.items()
        ))
        self.port = self.server.add_insecure_port(f"{host}:{port}")
        if self.port == 0:
            # add_insecure_port returns 0 on bind FAILURE (port taken);
            # a requested port of 0 legitimately returns an ephemeral one
            raise OSError(f"could not bind gRPC port {host}:{port}")
        self.server.start()

    def stop(self) -> None:
        self.server.stop(grace=0.5)
