"""Closed-loop readers against a chain at rest: light rounds and rollup
namespace reads from `clients` in-process threads (callers that each wait
for their reply).

Set-up commits `setup_blocks` blocks of the `setup_mix` traffic; the window
produces none. Each client walks, over and over, one cycle of `cycle`
requests that is the same set for every client and every seed — light rounds
at the last `len(height_weights)` heights in those weights, `reads_per_cycle`
namespace reads of 1..8 namespaces in Zipf proportion, every
`absent_every`-th read asking for one absent namespace besides — in an order
the seed shuffles. A seeded share of the replies (`verify_per_cycle`), the
largest read among them, is kept whole and checked after the window against
the plain reference; every reply is checked for refusals as it arrives.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from lib import cells as cells_mod


def _zipf_pick(rng, n_items: int, n: int, s: float) -> list[int]:
    w = np.array([1.0 / (r + 1) ** s for r in range(n_items)])
    return [int(i) for i in rng.choice(n_items, size=n, replace=False,
                                       p=w / w.sum())]


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
        self.absent = pfb.namespace_id(seed, 250)
        self.heights: list[int] = []
        self.schedules = [self._schedule(c)
                          for c in range(self.mix["clients"])]

    def accounts(self):
        return self.chain.accounts()

    def ready(self, warm_records: list[dict], seconds: float) -> dict:
        return {}

    def _schedule(self, client: int) -> list[dict]:
        """One client's cycle. Heights are offsets below the tip."""
        mix = self.mix
        rng = np.random.default_rng([self.seed, 11, client])
        n_light = mix["cycle"] - mix["reads_per_cycle"]
        weights = mix["height_weights"]
        offsets = [o for o, w in enumerate(weights) for _ in range(w)]
        lights = [{"kind": "light", "offset": offsets[i % len(offsets)]}
                  for i in range(n_light)]
        lo, hi = mix["read_namespaces"]
        sizes = [lo + i % (hi - lo + 1)
                 for i in range(mix["reads_per_cycle"])]
        reads = [{"kind": "read", "ranks": _zipf_pick(
                      rng, self.chain.mix["namespaces"], n,
                      mix["namespace_zipf_s"])} for n in sizes]
        keep = mix["verify_per_cycle"]
        for i in rng.permutation(n_light)[:keep["light"]]:
            lights[int(i)]["keep"] = True
        biggest = max(range(len(reads)), key=lambda i: len(reads[i]["ranks"]))
        for i in [biggest] + [int(j) for j in rng.permutation(len(reads))]:
            if sum(1 for r in reads if r.get("keep")) >= keep["read"]:
                break
            reads[i]["keep"] = True
        cycle = lights + reads
        return [cycle[int(i)] for i in rng.permutation(len(cycle))]

    # -- set-up -------------------------------------------------------------

    def warm(self, sut, spans, log) -> list[dict]:
        """Commits the chain, then sends one of each request shape: a light
        round at each served height and reads of 1, 2, 3, 5 and 9 namespaces
        (the device search pads its queries to powers of two: 1..16)."""
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
        rng = np.random.default_rng([self.seed, 12])
        for h in self.heights:
            self._light(sut, h, rng, spans)
        names = self.chain.namespaces
        for n in (1, 2, 3, 5, 8):
            asked = names[:n] + ([self.absent] if n == 8 else [])
            with spans("namespaces_many"):
                sut.namespaces(tip, asked)
        return records

    def _light(self, sut, height: int, rng, spans):
        cells = [(int(r), int(c)) for r, c in rng.integers(
            0, 2 * self.k, size=(self.mix["cells_per_round"], 2))]
        with spans("sample_many"):
            try:
                return cells, sut.sample(height, cells)
            except Exception as e:  # a refused round is a failed one
                print(f"light round at height {height} refused: {e!r}",
                      flush=True)
                return cells, None

    # -- the window ---------------------------------------------------------

    def _client(self, ci: int, sut, deadline: float, spans, out: dict):
        rng = np.random.default_rng([self.seed, 13, ci])
        schedule = self.schedules[ci]
        names = self.chain.namespaces
        tip = self.heights[0]
        done = refused = reads = 0
        kept, read_shapes = [], []
        try:
            while time.perf_counter() < deadline:
                req = schedule[done % len(schedule)]
                if req["kind"] == "light":
                    height = self.heights[req["offset"]]
                    cells, reply = self._light(sut, height, rng, spans)
                    bad = (len(cells) if reply is None
                           else sut.refused_in(reply))
                    if req.get("keep") and reply is not None:
                        kept.append(("light", height, cells, reply))
                else:
                    reads += 1
                    asked = [names[r] for r in req["ranks"]]
                    if reads % self.mix["absent_every"] == 0:
                        asked.append(self.absent)
                    with spans("namespaces_many"):
                        try:
                            reply = sut.namespaces(tip, asked)
                        except Exception as e:  # refused: failed, not fatal
                            print(f"namespace read refused: {e!r}",
                                  flush=True)
                            reply = None
                    bad = (len(asked) if reply is None
                           else sut.refused_in(reply))
                    read_shapes.append((self.k, len(asked)))
                    if req.get("keep") and reply is not None:
                        kept.append(("read", tip, asked, reply))
                refused += bad
                done += 1
        except BaseException as e:  # read by window(); a thread must not die silently
            out["errors"].append(e)
        out["done"][ci] = done
        out["refused"][ci] = refused
        out["kept"][ci] = kept
        out["reads"][ci] = read_shapes

    def window(self, sut, seconds: float, spans) -> dict:
        n = self.mix["clients"]
        out = {"done": [0] * n, "refused": [0] * n, "kept": [[] for _ in range(n)],
               "reads": [[] for _ in range(n)], "errors": []}
        t_start = time.perf_counter()
        threads = [threading.Thread(
            target=self._client, name=f"bench-client-{ci}",
            args=(ci, sut, t_start + seconds, spans, out)) for ci in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        out["seconds"] = time.perf_counter() - t_start
        if out["errors"]:
            raise out["errors"][0]
        return out

    def units(self, records: dict) -> dict:
        return {"requests": sum(records["done"]),
                "namespace_reads": [x for c in records["reads"] for x in c]}

    def counts(self, records: dict) -> tuple[int, int]:
        return sum(records["done"]), sum(records["refused"])

    def end_to_end(self, records: dict) -> dict:
        return {"serve_rate": sum(records["done"]) / records["seconds"]}

    # -- correctness --------------------------------------------------------

    def collect(self, sut, records: dict, warm_records: list[dict]) -> dict:
        kept = []
        for per_client in records["kept"]:
            for kind, height, asked, reply in per_client:
                decoded = (sut.decode_samples(reply) if kind == "light"
                           else sut.decode_namespaces(reply))
                kept.append((kind, height, asked, decoded))
        return {"kept": kept, "refused": sum(records["refused"]),
                "blocks": {h: self.blocks[h] for h in self.heights}}

    def compare(self, collected: dict) -> dict:
        from lib import compare

        return compare.serve_cell(self, collected)


def prepare(cell, seed: int, seconds: float) -> Traffic:
    return Traffic(cell, seed)
