"""A light-node fleet sampling a chain at rest through the node's own HTTP
DAS front: `processes` client OS processes x `samplers_per_process`
samplers, each a thread with one persistent HTTP/1.1 connection over
loopback TCP, in a closed loop with no think time.

The server is the front a full node runs: `service/server.NodeService` over
the validator's node (its `app_lock`, its own `SampleCore` and seed
listener, a handler thread per connection), built BEFORE the set-up blocks
so that the commit warmer seeds its core as it seeds a serving node's.
Set-up commits `setup_blocks` blocks of the `setup_mix` traffic; the window
produces none. The clients are `generators/http_sampler_client.py`, started
with `spawn` (never `fork`: this process holds the chips), importing neither
jax nor the program. Warm-up: every sampler fetches the header of each of
the last `len(height_weights)` heights and sends one sample request per
height. Window: `POST /das/samples {height, cells}` of `cells_per_round`
uniform cells at a height drawn with `height_weights` (tip first).

`correct` (after the window, against `reference/plain_da.py`): the served
heights rebuilt from their raw txs; every warm-up header's roots equal the
reference's and hash to its data root; every `keep_every`-th request of a
sampler kept whole, each share and proof node equal to the reference's;
every reply of the run checked in its client as it arrived — its status,
its refusals, every sample's proof against its height's header; and no
request built a host prover or brought the square down (`das.build_provers`,
`edscache.host_crossings`: the copy-less guarantee of a mesh height). Every
number is a count of exact mismatches; every limit 0.

With the plain reference in the program's place (the control) the same
clients sample a plain HTTP front over `reference/plain_node.PlainValidator`.
"""

from __future__ import annotations

import importlib
import json
import multiprocessing
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from lib import cells as cells_mod
from reference import plain_da as da
from reference import plain_light

# importable by name in a spawned child (the benchmark directory is on
# its sys.path), which a generator loaded from its file is not
client_mod = importlib.import_module("generators.http_sampler_client")

# the copy-less guarantee: what no request of this traffic may set off on
# a height whose square lives only on the chips
HOST_PROVER_SPAN = 'obs.span_n{name="das.build_provers"}'
HOST_CROSSINGS = "edscache.host_crossings"
READY_TIMEOUT_S = 600.0


def gather_bucket(n_cells: int) -> int:
    """Cells one gather is padded to: a power of two, at least 16 (the
    program's `proof_device.gather_bucket`, restated for the floor)."""
    return max(16, 1 << (n_cells - 1).bit_length())


class PlainFront:
    """The control's front: `GET /das/header` and `POST /das/samples` in the
    program's wire format (docs/FORMATS.md §7) over the plain validator."""

    def __init__(self, plain):
        import base64

        def header(h: int) -> dict:
            rows, cols = plain.light_header(h)
            return {"height": h, "scheme": "rs2d-nmt",
                    "square_width": len(rows),
                    "row_roots": [r.hex() for r in rows],
                    "col_roots": [c.hex() for c in cols],
                    "data_root": da.data_root(rows, cols).hex()}

        def samples(h: int, cells) -> dict:
            docs = [{"row": s["row"], "col": s["col"],
                     "share": base64.b64encode(s["share"]).decode(),
                     "proof": {"start": s["start"], "end": s["end"],
                               "total": s["total"],
                               "nodes": [base64.b64encode(n).decode()
                                         for n in s["nodes"]]}}
                    for s in plain.sample(h, [tuple(c) for c in cells])]
            return {"height": h, "samples": docs}

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _reply(self, doc: dict) -> None:
                body = json.dumps(doc).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                q = parse_qs(urlparse(self.path).query)
                self._reply(header(int(q["height"][0])))

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                doc = json.loads(self.rfile.read(n))
                self._reply(samples(int(doc["height"]), doc["cells"]))

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


class Traffic:
    def __init__(self, cell, seed: int):
        self.mix = cell.mix
        self.seed = seed
        self.k = cell.config["gov_max_square_size"]
        pfb = cells_mod.load_module("generators", "pfb_blocks",
                                    cell.bench_dir)
        setup_mix = cells_mod.read_json(os.path.join(
            cell.bench_dir, "traffic", f"{self.mix['setup_mix']}.json"))
        self.chain = pfb.Traffic(cell, seed, mix=setup_mix)
        self.chain.generate(self.mix["setup_blocks"])
        self.client = self.chain.client
        self.heights: list[int] = []
        self.blocks: dict = {}
        self.front = None
        self.procs: list = []
        self.conns: list = []
        self.warm_docs: list[dict] = []
        self.front_c0: dict = {}

    def accounts(self):
        return self.chain.accounts()

    def ready(self, warm_records: list[dict], seconds: float) -> dict:
        return {"samplers": len(self.conns) * self.mix["samplers_per_process"],
                "processes": len(self.conns),
                "heights": self.heights}

    def _params(self, process: int, port: int) -> dict:
        mix = self.mix
        return {"seed": self.seed, "process": process, "port": port,
                "samplers": mix["samplers_per_process"], "k": self.k,
                "heights": self.heights,
                "height_weights": mix["height_weights"],
                "cells_per_round": mix["cells_per_round"],
                "keep_every": mix["keep_every"],
                "timeout_s": mix["request_timeout_s"]}

    # -- set-up -------------------------------------------------------------

    def warm(self, sut, spans, log) -> list[dict]:
        """The front first, then the chain, then the fleet: its processes
        up, every header fetched and checked, one sample a height each."""
        if sut.is_reference:
            self.front = PlainFront(sut)
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
            proc = ctx.Process(target=client_mod.main, name=f"sampler-{p}",
                               args=(self._params(p, self.front.port),
                                     child_end), daemon=True)
            proc.start()
            child_end.close()
            self.procs.append(proc)
            self.conns.append(parent_end)
        for p, conn in enumerate(self.conns):
            if not conn.poll(READY_TIMEOUT_S):
                raise RuntimeError(f"sampler process {p} not ready in "
                                   f"{READY_TIMEOUT_S} s")
            msg, doc = conn.recv()
            assert msg == "ready", msg
            self.warm_docs.append(doc)
        log(phase="fleet_ready", processes=len(self.conns),
            samplers=sum(len(d["counts"]) for d in self.warm_docs),
            warm_requests=sum(c["warm_requests"] for d in self.warm_docs
                              for c in d["counts"]))
        return records

    # -- the window ---------------------------------------------------------

    def window(self, sut, seconds: float, spans) -> dict:
        t_start = time.perf_counter()
        deadline = time.monotonic() + seconds
        for conn in self.conns:
            conn.send(("go", deadline))
        counts = []
        for conn in self.conns:
            msg, doc = conn.recv()
            assert msg == "done", msg
            counts += doc
        out = {"seconds": time.perf_counter() - t_start, "counts": counts}
        errors = [c["error"] for c in counts if c["error"]]
        if errors:
            raise RuntimeError(f"{len(errors)} sampler(s) died: {errors[0]}")
        return out

    def units(self, records: dict) -> dict:
        requests = sum(c["done"] for c in records["counts"])
        return {"requests": requests, "square_size": self.k,
                "gather_cells_padded":
                    requests * gather_bucket(self.mix["cells_per_round"])}

    def counts(self, records: dict) -> tuple[int, int]:
        c = records["counts"]
        return (sum(x["done"] for x in c),
                sum(x["non_200"] + x["refused"] + x["transport_errors"]
                    for x in c))

    def end_to_end(self, records: dict) -> dict:
        return {"serve_rate": sum(c["done"] for c in records["counts"])
                / records["seconds"]}

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
            "headers": [hd for d in self.warm_docs for hd in d["headers"]],
            "samplers": len(records["counts"]),
            "counts": records["counts"],
            "blocks": {h: self.blocks[h] for h in self.heights},
            "front": {name: c1.get(name, 0) - self.front_c0.get(name, 0)
                      for name in (HOST_PROVER_SPAN, HOST_CROSSINGS)},
        }

    def compare(self, collected: dict) -> dict:
        """The served heights rebuilt, each in a process of its own (the
        same `spawn` as the clients), with the kept replies at it."""
        from concurrent.futures import ProcessPoolExecutor

        kept: dict[int, list] = {h: [] for h in collected["blocks"]}
        for height, cells, got in collected["kept"]:
            kept[height].append((cells, got))
        with ProcessPoolExecutor(
                len(kept), mp_context=multiprocessing.get_context("spawn")
        ) as pool:
            futures = {h: pool.submit(plain_light.check_height, p.txs,
                                      self.k, kept[h])
                       for h, p in collected["blocks"].items()}
            refs = {h: f.result() for h, f in futures.items()}
        root_bad = sum(refs[h]["data_root"] != p.data_hash
                       for h, p in collected["blocks"].items())
        header_bad = 0
        for height, digest, data_root, root_ok in collected["headers"]:
            ref = refs.get(height)
            header_bad += (ref is None or digest != ref["digest"]
                           or data_root != ref["data_root"] or not root_ok)
        # every sampler fetched every height's header
        missing = collected["samplers"] * len(refs) - len(
            collected["headers"])
        # a sampler's counts run on from its warm-up: the last are all
        every = collected["counts"]
        return {
            "replies_not_200": [sum(c["non_200"] for c in every), 0],
            "cells_refused": [sum(c["refused"] for c in every), 0],
            "transport_errors": [sum(c["transport_errors"]
                                     for c in every), 0],
            "data_root_vs_reference": [int(root_bad), 0],
            "headers_vs_reference": [int(header_bad) + max(missing, 0), 0],
            "sample_proofs_failed": [sum(c["proofs_failed"]
                                         for c in every), 0],
            "sample_shares_vs_reference": [
                sum(r["shares_bad"] for r in refs.values()), 0],
            "proof_nodes_vs_reference": [
                sum(r["nodes_bad"] for r in refs.values()), 0],
            "kept_replies_missing": [int(not collected["kept"]), 0],
            "host_provers_built": [collected["front"][HOST_PROVER_SPAN], 0],
            "square_host_crossings": [collected["front"][HOST_CROSSINGS], 0],
        }

def prepare(cell, seed: int, seconds: float) -> Traffic:
    return Traffic(cell, seed)
