"""Rollup full nodes following their own namespace through the node's blob
API: `processes` client OS processes x `readers_per_process` readers, each
a thread with one persistent HTTP/1.1 connection over loopback TCP, in a
closed loop with no think time.

The server is the front a full node runs: `service/server.NodeService` over
the validator's node (its `app_lock`, its own `SampleCore` and `BlobCore`,
the commit warmer's seed listener, a handler thread per connection), built
BEFORE the set-up blocks so that the warmer seeds its core as it seeds a
serving node's. Set-up commits `setup_blocks` blocks of the `setup_mix`
traffic; the window produces none. The clients are
`generators/http_ns_reader_client.py`, started with `spawn` (never `fork`:
this process holds the chips), importing neither jax nor the program.
Reader j of every process follows namespace rank j: ranks below the setup
mix's `namespaces` are the blocks' own (Zipf counts, rank 0 the largest),
the next is a namespace no block holds (`pfb_blocks.namespace_id(seed,
250)`: after every blob namespace, before the tail padding). A request is
`POST /blob/namespaces` with ONE query {height, namespace}, at a height
drawn with `height_weights` (tip first). Warm-up: every reader reads its
namespace at each served height once (every row bucket a read uses).

`correct` (after the window, against `reference/plain_da.py`): the served
heights rebuilt from their raw txs, each in a process of its own; every
`keep_every`-th reply of a reader kept whole and held to the reference by
`lib/compare.check_namespace_read` — the shares, each row's range proof
against the reference's row root, the presence flag, the data root; every
reply of the run looked at in its client as it arrived — a 200, one answered
member, `present` as its rank's namespace is; and no read built a host
prover or brought the square down (`das.build_provers`,
`edscache.host_crossings`: the copy-less guarantee of a mesh height). Every
number is a count of exact mismatches; every limit 0.

With the plain reference in the program's place (the control) the same
readers read through a plain HTTP front over
`reference/plain_node.PlainValidator`, which can break a read two ways:
`partial_read` (plain_node's own) and `stale_read` — a read of the tip
answered from the height before.
"""

from __future__ import annotations

import importlib
import json
import multiprocessing
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from lib import cells as cells_mod
from reference import plain_node

# importable by name in a spawned child (the benchmark directory is on
# its sys.path), which a generator loaded from its file is not
client_mod = importlib.import_module("generators.http_ns_reader_client")
# the sampling fleet's: what no request may set off on a copy-less height
samplers = importlib.import_module("generators.http_samplers")

STALE_READ = "stale_read"
if STALE_READ not in plain_node.BREAKS:
    # the read twin of `stale_sample`, played by `PlainReadFront`
    plain_node.BREAKS = (*plain_node.BREAKS, STALE_READ)
ABSENT_RANK = 250
READY_TIMEOUT_S = 600.0


class PlainReadFront:
    """The control's front: `POST /blob/namespaces` in the program's wire
    format (docs/FORMATS.md §21.1) over the plain validator, one query a
    request; with the `stale_read` break a read of the tip is answered from
    the height before."""

    def __init__(self, plain):
        import base64

        def b64(raw: bytes) -> str:
            return base64.b64encode(raw).decode()

        def read(height: int, namespace: str) -> dict:
            if plain.breaks == STALE_READ and height == plain.height:
                height -= 1
            [doc] = plain.namespaces(height, [bytes.fromhex(namespace)])
            proof = None
            if doc["row_proofs"]:
                rows = doc["row_proofs"]
                proof = {"data": [b64(s) for s in doc["proof_shares"]],
                         "share_proofs": [
                             {"start": r["start"], "end": r["end"],
                              "total": r["total"],
                              "nodes": [b64(n) for n in r["nodes"]]}
                             for r in rows],
                         "row_proof": {
                             "start_row": doc["start_row"],
                             "end_row": doc["start_row"] + len(rows) - 1}}
            return {"height": height, "namespace": namespace,
                    "present": doc["present"],
                    "shares": [b64(s) for s in doc["shares"]],
                    "proof": proof, "data_root": doc["data_root"].hex()}

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                queries = json.loads(self.rfile.read(n))["queries"]
                body = json.dumps({"queries": [
                    read(int(q["height"]), q["namespace"])
                    for q in queries]}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        class Server(ThreadingHTTPServer):
            daemon_threads = True
            request_queue_size = 1024

        self.httpd = Server(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def shutdown(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()


class Traffic(samplers.Traffic):
    """The sampling fleet's set-up, window and front, with readers in the
    samplers' place."""

    def __init__(self, cell, seed: int):
        super().__init__(cell, seed)
        pfb = cells_mod.load_module("generators", "pfb_blocks",
                                    cell.bench_dir)
        self.present_ranks = self.chain.mix["namespaces"]
        self.namespaces = [pfb.namespace_id(seed, r)
                           for r in range(self.present_ranks)]
        self.namespaces.append(pfb.namespace_id(seed, ABSENT_RANK))
        if self.mix["readers_per_process"] != len(self.namespaces):
            raise cells_mod.CellError(
                "ns-http readers: one reader a namespace rank, "
                f"{len(self.namespaces)} ranks")

    def ready(self, warm_records: list[dict], seconds: float) -> dict:
        return {"readers": len(self.conns) * self.mix["readers_per_process"],
                "processes": len(self.conns),
                "heights": self.heights}

    def _params(self, process: int, port: int) -> dict:
        mix = self.mix
        return {"seed": self.seed, "process": process, "port": port,
                "readers": mix["readers_per_process"],
                "namespaces": [ns.hex() for ns in self.namespaces],
                "present_ranks": self.present_ranks,
                "heights": self.heights,
                "height_weights": mix["height_weights"],
                "keep_every": mix["keep_every"],
                "timeout_s": mix["request_timeout_s"]}

    # -- set-up -------------------------------------------------------------

    def warm(self, sut, spans, log) -> list[dict]:
        """The front first, then the chain, then the fleet: its processes
        up, every reader's namespace read once at each served height."""
        if sut.is_reference:
            self.front = PlainReadFront(sut)
        else:
            from celestia_app_tpu.service.server import NodeService

            self.front = NodeService(sut.node, port=0)
            self.front.serve_background()
        records = []
        for _ in range(self.mix["setup_blocks"]):
            rec = self.chain.one_block(sut, spans)
            log(phase="setup_block", height=rec["produced"].height,
                square_size=rec["produced"].square_size,
                seconds=round(rec["loop_s"], 3))
            records.append(rec)
        tip = records[-1]["produced"].height
        self.heights = [tip - o
                        for o in range(len(self.mix["height_weights"]))]
        self.blocks = {r["produced"].height: r["produced"] for r in records}
        self.front_c0 = sut.counters()
        ctx = multiprocessing.get_context("spawn")
        for p in range(self.mix["processes"]):
            parent_end, child_end = ctx.Pipe()
            proc = ctx.Process(target=client_mod.main, name=f"reader-{p}",
                               args=(self._params(p, self.front.port),
                                     child_end), daemon=True)
            proc.start()
            child_end.close()
            self.procs.append(proc)
            self.conns.append(parent_end)
        absence = {}
        for p, conn in enumerate(self.conns):
            if not conn.poll(READY_TIMEOUT_S):
                raise RuntimeError(f"reader process {p} not ready in "
                                   f"{READY_TIMEOUT_S} s")
            msg, doc = conn.recv()
            assert msg == "ready", msg
            errors = [c["error"] for c in doc["counts"] if c["error"]]
            if errors:
                raise RuntimeError(f"a reader's warm-up died: {errors[0]}")
            self.warm_docs.append(doc)
            absence.update(doc["absence"])
        # where the last blob ends mid-row the absent namespace's row
        # straddles it (a successor leaf proves it absent); where it ends
        # on a row's last share no row covers it
        log(phase="fleet_ready", processes=len(self.conns),
            readers=sum(len(d["counts"]) for d in self.warm_docs),
            absence=absence)
        return records

    # -- the window ---------------------------------------------------------

    def units(self, records: dict) -> dict:
        counts = records["counts"]
        rows = sum(c["rows_padded"] for c in counts)
        depth = (2 * self.k).bit_length() - 1
        return {"requests": sum(c["done"] for c in counts),
                "square_size": self.k,
                "ns_rows_padded": rows,
                "ns_nodes": rows * 2 * depth}

    def counts(self, records: dict) -> tuple[int, int]:
        c = records["counts"]
        return (sum(x["done"] for x in c),
                sum(x["non_200"] + x["wrong"] + x["transport_errors"]
                    for x in c))

    # -- correctness --------------------------------------------------------

    def collect(self, sut, records: dict, warm_records: list[dict]) -> dict:
        c1 = sut.counters()
        kept = []
        try:
            for conn in self.conns:
                msg, doc = conn.recv()
                assert msg == "kept", msg
                kept += doc
        finally:
            for proc in self.procs:
                proc.join(30)
                if proc.is_alive():
                    proc.kill()
            self.front.shutdown()
        return {
            "kept": kept,
            "counts": records["counts"],
            "blocks": {h: self.blocks[h] for h in self.heights},
            "front": {name: c1.get(name, 0) - self.front_c0.get(name, 0)
                      for name in (samplers.HOST_PROVER_SPAN,
                                   samplers.HOST_CROSSINGS)},
        }

    def compare(self, collected: dict) -> dict:
        """The served heights rebuilt, each in a process of its own (the
        same `spawn` as the clients), with the kept replies at it."""
        from concurrent.futures import ProcessPoolExecutor

        kept: dict[int, list] = {h: [] for h in collected["blocks"]}
        for height, rank, present, doc in collected["kept"]:
            kept[height].append((rank, present, doc))
        with ProcessPoolExecutor(
                len(kept), mp_context=multiprocessing.get_context("spawn")
        ) as pool:
            futures = {h: pool.submit(client_mod.check_reads, p.txs,
                                      self.k, kept[h])
                       for h, p in collected["blocks"].items()}
            refs = {h: f.result() for h, f in futures.items()}
        root_bad = sum(refs[h]["data_root"] != p.data_hash
                       for h, p in collected["blocks"].items())
        # a reader's counts run on from its warm-up: the last are all
        every = collected["counts"]
        return {
            "replies_not_200": [sum(c["non_200"] for c in every), 0],
            "replies_wrong_on_arrival": [sum(c["wrong"] for c in every), 0],
            "transport_errors": [sum(c["transport_errors"]
                                     for c in every), 0],
            "data_root_vs_reference": [int(root_bad), 0],
            "namespace_shares_vs_reference": [
                sum(r["shares_bad"] for r in refs.values()), 0],
            "namespace_proofs_failed": [
                sum(r["proofs_bad"] for r in refs.values()), 0],
            "namespace_presence_wrong": [
                sum(r["presence_bad"] for r in refs.values()), 0],
            "kept_replies_missing": [int(not collected["kept"]), 0],
            "host_provers_built": [
                collected["front"][samplers.HOST_PROVER_SPAN], 0],
            "square_host_crossings": [
                collected["front"][samplers.HOST_CROSSINGS], 0],
        }


def prepare(cell, seed: int, seconds: float) -> Traffic:
    return Traffic(cell, seed)
