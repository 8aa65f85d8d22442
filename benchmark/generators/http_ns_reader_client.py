"""One client OS process of the `http_ns_readers` fleet: `readers` rollup
full nodes, each a thread with ONE persistent HTTP/1.1 connection to the
node's blob API, started with `spawn` by the benchmark process that holds
the chips. It imports neither jax nor the program: `http.client`, `json`,
`base64` alone while the fleet runs, and after the window, in processes of
their own, the plain reference (`check_reads`). It is the benchmark's own
client, independent of the program's `tools/blobload.py`.

    main(params, conn)   params: see `generators/http_ns_readers._params`;
                         conn: this process's end of a multiprocessing Pipe

The protocol over `conn`, in order: -> ("ready", warm) once every reader has
read its namespace at every served height; <- ("go", deadline on
time.monotonic(), the clock every process of the host shares); -> ("done",
counts) once every reader has stopped; -> ("kept", replies). Reader j
follows namespace rank j of `namespaces` (a rollup reads its own
namespace): a request is `POST /blob/namespaces` with ONE query
{height, namespace}, the height drawn with `height_weights` over the served
heights (tip first), as celestia-node's `blob.GetAll(height, [namespace])`
asks. Every reply is looked at as it arrives: its HTTP status, and whether
its one member is answered and says `present` as the rank's namespace is
(ranks below `present_ranks` are in every block; the rest are absent).
Every `keep_every`-th request of a reader is kept whole for the reference.
"""

from __future__ import annotations

import base64
import http.client
import json
import threading
import time

import numpy as np


def row_bucket(rows: int) -> int:
    """Rows a namespace gather of `rows` touched rows is padded to: a power
    of two (the program's `proof_device.namespace_row_bucket`, restated for
    the floor)."""
    return 1 << (rows - 1).bit_length()


def decode(doc: dict) -> dict:
    """One reply member in the form `lib/compare.check_namespace_read`
    reads: bytes where the wire has base64 or hex."""
    if "error" in doc:
        return {"namespace": bytes.fromhex(doc["namespace"]),
                "error": doc["error"]}
    proof = doc["proof"]
    shares = [base64.b64decode(s) for s in doc["shares"]]
    proof_shares = ([base64.b64decode(s) for s in proof["data"]]
                    if proof else [])
    return {
        "namespace": bytes.fromhex(doc["namespace"]),
        "present": doc["present"],
        "shares": shares,
        # one list where the proof carries the shares themselves
        "proof_shares": shares if proof_shares == shares else proof_shares,
        "data_root": bytes.fromhex(doc["data_root"]),
        "start_row": proof["row_proof"]["start_row"] if proof else 0,
        "row_proofs": ([{"start": p["start"], "end": p["end"],
                         "total": p["total"],
                         "nodes": [base64.b64decode(n) for n in p["nodes"]]}
                        for p in proof["share_proofs"]] if proof else []),
    }


def check_reads(txs: list[bytes], max_k: int, kept: list) -> dict:
    """The reference's block and, over `kept` — [(namespace rank, expected
    presence, decoded member)] of one height — the counts of
    `lib/compare.check_namespace_read`. Runs in a process of its own (a
    256 x 256 square is seconds of reference on one core)."""
    from lib import compare
    from reference import plain_da as da

    ref = da.commit_block(txs, max_k)
    k = ref["square_size"]
    ods = ref["eds"][:k, :k]
    shares_bad = proofs_bad = presence_bad = 0
    for _rank, present, doc in kept:
        a, b, c = compare.check_namespace_read(doc, ref, ods, present)
        shares_bad += a
        proofs_bad += b
        presence_bad += c
    return {"data_root": ref["data_root"], "shares_bad": shares_bad,
            "proofs_bad": proofs_bad, "presence_bad": presence_bad}


class Reader:
    def __init__(self, params: dict, index: int):
        self.p = params
        self.index = index
        self.namespace = params["namespaces"][index]
        self.present = index < params["present_ranks"]
        self.rng = np.random.default_rng(
            [params["seed"], 42, params["process"], index])
        self.conn = http.client.HTTPConnection(
            "127.0.0.1", params["port"], timeout=params["timeout_s"])
        self.done = self.non_200 = self.wrong = self.transport_errors = 0
        self.rows_padded = 0
        self.kept: list[tuple] = []
        self.absence: dict[int, str] = {}  # height -> the absence's form
        self.error: BaseException | None = None

    def _read(self, height: int) -> dict | None:
        """One read: the reply's one member, or None (counted) when the
        reply is not a 200."""
        body = json.dumps({"queries": [{"height": height,
                                        "namespace": self.namespace}]})
        self.conn.request("POST", "/blob/namespaces", body=body.encode(),
                          headers={"Content-Type": "application/json"})
        resp = self.conn.getresponse()
        raw = resp.read()
        if resp.status != 200:
            self.non_200 += 1
            return None
        members = json.loads(raw).get("queries") or [{}]
        doc = members[0]
        if len(members) != 1 or "error" in doc or \
                doc.get("present") is not self.present:
            self.wrong += 1
        return doc

    def warm(self) -> None:
        """Each served height once: every row bucket the reads use."""
        try:
            for h in self.p["heights"]:
                doc = self._read(h)
                if doc is not None and not self.present:
                    self.absence[h] = ("successor leaf" if doc.get("proof")
                                       else "no row covers it")
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
                    doc = self._read(height)
                except (OSError, http.client.HTTPException):
                    # a dropped connection is a failed request, and the
                    # reader reconnects (keep-alive is the front's)
                    self.transport_errors += 1
                    self.conn.close()
                    doc = None
                if doc is not None and doc.get("proof") and doc["present"]:
                    row = doc["proof"]["row_proof"]
                    self.rows_padded += row_bucket(
                        row["end_row"] - row["start_row"] + 1)
                if doc is not None and i % keep_every == 0:
                    self.kept.append((height, self.index, self.present,
                                      decode(doc)))
                i += 1
                self.done += 1
        except BaseException as e:  # reported, never silent
            self.error = e
        finally:
            self.conn.close()

    def counts(self) -> dict:
        return {"done": self.done, "non_200": self.non_200,
                "wrong": self.wrong,
                "transport_errors": self.transport_errors,
                "rows_padded": self.rows_padded,
                "error": None if self.error is None else repr(self.error)}


def _all(readers, target, *args) -> list:
    threads = [threading.Thread(target=getattr(r, target), args=args,
                                name=f"reader-{r.index}", daemon=True)
               for r in readers]
    for t in threads:
        t.start()
    return threads


def main(params: dict, conn) -> None:
    readers = [Reader(params, i) for i in range(params["readers"])]
    for t in _all(readers, "warm"):
        t.join()
    conn.send(("ready", {
        "absence": {h: form for r in readers
                    for h, form in r.absence.items()},
        "counts": [r.counts() for r in readers]}))
    go, clock = threading.Event(), {}
    threads = _all(readers, "run", go, clock)
    msg, deadline = conn.recv()
    if msg != "go":
        return
    clock["deadline"] = deadline
    go.set()
    for t in threads:
        t.join()
    conn.send(("done", [r.counts() for r in readers]))
    conn.send(("kept", [kept for r in readers for kept in r.kept]))
    conn.close()
