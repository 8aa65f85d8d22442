"""One client OS process of the `http_samplers` fleet: `samplers` light
nodes, each a thread with ONE persistent HTTP/1.1 connection to the node's
DAS front, started with `spawn` by the benchmark process that holds the
chips. It imports neither jax nor the program: `http.client`, `json`,
`base64`, `hashlib` (through the reference) and `reference/plain_da`'s NMT
alone, so that no change to the program can move the yardstick. It is the
benchmark's own client, independent of the program's `tools/dasload.py`.

    main(params, conn)   params: see `generators/http_samplers._params`;
                         conn: this process's end of a multiprocessing Pipe

The protocol over `conn`, in order: -> ("ready", warm) once every sampler
has fetched and checked the header of every served height and sent one
sample request per height; <- ("go", deadline on time.monotonic(), the
clock every process of the host shares); -> ("done", counts) once every
sampler has stopped; -> ("kept", replies). A sampler's request is
`POST /das/samples {height, cells}`: `cells_per_round` cells drawn
uniformly from the extended square by its own seeded generator, the row
axis, at a height drawn with `height_weights` over the served heights (tip
first). Every reply is looked at as it arrives: its HTTP status, the cells
it refused, and every sample's NMT proof against the row root of the header
the sampler fetched at warm-up, as a light node verifies. Every
`keep_every`-th request of a sampler is kept whole for the reference.
"""

from __future__ import annotations

import base64
import hashlib
import http.client
import json
import threading
import time

import numpy as np

from reference import plain_da as da


class Sampler:
    def __init__(self, params: dict, index: int):
        self.p = params
        self.index = index
        self.rng = np.random.default_rng(
            [params["seed"], 41, params["process"], index])
        self.conn = http.client.HTTPConnection(
            "127.0.0.1", params["port"], timeout=params["timeout_s"])
        self.rows: dict[int, list[bytes]] = {}
        self.done = self.warm_requests = 0
        self.non_200 = self.refused = self.proofs_failed = 0
        self.transport_errors = 0
        self.kept: list[tuple] = []
        self.headers: list[tuple] = []   # (height, roots digest, root ok)
        self.error: BaseException | None = None

    def _ask(self, method: str, path: str, body: bytes | None = None):
        headers = {"Content-Type": "application/json"} if body else {}
        self.conn.request(method, path, body=body, headers=headers)
        resp = self.conn.getresponse()
        return resp.status, resp.read()

    def fetch_headers(self) -> None:
        """The header of every served height, as a light node takes it
        before it samples: its row roots are what every later proof is
        verified against; its roots must hash to its data root."""
        for h in self.p["heights"]:
            status, raw = self._ask("GET", f"/das/header?height={h}")
            if status != 200:
                self.non_200 += 1
                continue
            doc = json.loads(raw)
            rows = [bytes.fromhex(r) for r in doc["row_roots"]]
            cols = [bytes.fromhex(c) for c in doc["col_roots"]]
            self.rows[h] = rows
            digest = hashlib.sha256(b"".join(rows + cols)).digest()
            root_ok = da.data_root(rows, cols).hex() == doc["data_root"]
            self.headers.append((h, digest, bytes.fromhex(doc["data_root"]),
                                 root_ok))

    def _request(self, height: int, keep: bool) -> None:
        k2 = 2 * self.p["k"]
        cells = [(int(r), int(c)) for r, c in self.rng.integers(
            0, k2, size=(self.p["cells_per_round"], 2))]
        body = json.dumps({"height": height,
                           "cells": [list(c) for c in cells]}).encode()
        status, raw = self._ask("POST", "/das/samples", body)
        if status != 200:
            self.non_200 += 1
            return
        docs = json.loads(raw).get("samples", [])
        got = self.check(height, cells, docs)
        if keep:
            self.kept.append((height, cells, got))

    def check(self, height: int, cells, docs) -> list:
        """Refusals and proofs of one reply; the decoded samples."""
        rows = self.rows.get(height)
        k = self.p["k"]
        out = []
        if len(docs) != len(cells):
            self.refused += len(cells)
            return out
        for (row, col), doc in zip(cells, docs):
            if "error" in doc:
                self.refused += 1
                out.append(None)
                continue
            share = base64.b64decode(doc["share"])
            proof = doc["proof"]
            nodes = [base64.b64decode(n) for n in proof["nodes"]]
            out.append((share, nodes))
            ns = share[:da.NS] if row < k and col < k else da.PARITY_NS
            if rows is None or (doc["row"], doc["col"]) != (row, col) or \
                    (proof["start"], proof["end"]) != (col, col + 1) or \
                    not da.verify_range(rows[row], col, col + 1,
                                        proof["total"],
                                        [da.nmt_leaf(ns, share)], nodes):
                self.proofs_failed += 1
        return out

    def warm(self) -> None:
        try:
            self.fetch_headers()
            # one request a height: the gather program's bucket, warmed
            for h in self.p["heights"]:
                self._request(h, keep=False)
                self.warm_requests += 1
        except BaseException as e:  # reported, never silent
            self.error = e

    def run(self, go: threading.Event, clock: dict) -> None:
        go.wait()
        heights, weights = self.p["heights"], self.p["height_weights"]
        p = np.asarray(weights, dtype=float) / sum(weights)
        keep_every = self.p["keep_every"]
        i = 0
        try:
            while time.monotonic() < clock["deadline"]:
                height = heights[int(self.rng.choice(len(heights), p=p))]
                try:
                    self._request(height, keep=i % keep_every == 0)
                except (OSError, http.client.HTTPException):
                    # a dropped connection is a failed request, and the
                    # sampler reconnects (keep-alive is the front's)
                    self.transport_errors += 1
                    self.conn.close()
                i += 1
                self.done += 1
        except BaseException as e:  # reported, never silent
            self.error = e
        finally:
            self.conn.close()

    def counts(self) -> dict:
        return {"done": self.done, "non_200": self.non_200,
                "refused": self.refused,
                "proofs_failed": self.proofs_failed,
                "transport_errors": self.transport_errors,
                "warm_requests": self.warm_requests,
                "error": None if self.error is None else repr(self.error)}


def _all(samplers, target, *args) -> None:
    threads = [threading.Thread(target=getattr(s, target), args=args,
                                name=f"sampler-{s.index}", daemon=True)
               for s in samplers]
    for t in threads:
        t.start()
    return threads


def main(params: dict, conn) -> None:
    samplers = [Sampler(params, i) for i in range(params["samplers"])]
    for t in _all(samplers, "warm"):
        t.join()
    conn.send(("ready", {
        "headers": [hd for s in samplers for hd in s.headers],
        "counts": [s.counts() for s in samplers]}))
    go, clock = threading.Event(), {}
    threads = _all(samplers, "run", go, clock)
    msg, deadline = conn.recv()
    if msg != "go":
        return
    clock["deadline"] = deadline
    go.set()
    for t in threads:
        t.join()
    conn.send(("done", [s.counts() for s in samplers]))
    conn.send(("kept", [kept for s in samplers for kept in s.kept]))
    conn.close()
