"""Closed-loop readers that are behind: light sweepers and namespace
followers walking a chain's OLD heights in order, against caches that hold
a few of them.

Set-up commits `setup_blocks` blocks of the `setup_mix` traffic (the
configuration's `stored_heights`); the window produces none. The
`swept_heights` oldest are swept, tip-(setup_blocks-1) .. tip-(setup_blocks-
swept_heights): client c owns the `range_heights` consecutive heights that
start `*_offsets[c]` heights into that range, all rotated by one amount the
seed draws; it asks one request at its height, moves to the next, and wraps
inside its own range. Ranges that do not overlap are what a DASer hands its
workers: no two readers ever ask one height, so none waits on another's
build and every request pays its own miss as long as the ranges together
are wider than the caches. (Walkers of equal speed on ONE shared ring fall
into step behind each other's builds and never part: the rate then swings
with how many do, PERF.md section 6.) A light request is `light_header(h)`
followed by `sample(h, cells_per_round seeded cells)`; a follower's request
is one namespace read at h (its own namespace, every `absent_every`-th read
one absent namespace besides). Every request runs under one benchmark span,
`catchup_request`, whatever its kind: a re-extend is set off under either.

Kept whole for the comparison (lib/compare.serve_cell): the warm-up's
requests, every `keep_every`-th request of each client, and one light
request at the tip after the window — a height that was resident before
the sweep and is not after it. Every reply is looked at for refusals.

It reuses `tip_readers` (the chain's set-up, the light round, the counts and
the comparison) the way `tip_readers` reuses `pfb_blocks`.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from lib import cells as cells_mod
from lib import tracing

_tip = cells_mod.load_module(
    "generators", "tip_readers",
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REQUEST_SPAN = "catchup_request"


def height_index(plan: dict, done: int, swept: int) -> int:
    """Where in the swept range a client's `done`-th request lands: `span`
    heights from its start, over and over."""
    return (plan["start"] + done % plan["span"]) % swept


class Traffic(_tip.Traffic):
    def __init__(self, cell, seed: int):
        stored = cell.config["stored_heights"]
        if cell.mix["setup_blocks"] != stored:
            raise cells_mod.CellError(
                f"the mix commits {cell.mix['setup_blocks']} blocks, the "
                f"configuration stores {stored}")
        # one amount for all clients; drawn before the base class lays out
        # their walks
        self.rotation = int(np.random.default_rng([seed, 21]).integers(
            0, cell.mix["swept_heights"]))
        super().__init__(cell, seed)
        self.swept: list[int] = []          # oldest first, set by warm()
        self.tip = 0
        self.warm_kept: list[tuple] = []

    def _schedule(self, client: int) -> dict:
        """One client's walk: its kind, where it starts, what it reads."""
        mix = self.mix
        span = mix["range_heights"]
        if client < mix["sweepers"]:
            return {"kind": "light", "span": span,
                    "start": mix["sweeper_offsets"][client] + self.rotation}
        f = client - mix["sweepers"]
        return {"kind": "read", "span": span,
                "start": mix["follower_offsets"][f] + self.rotation,
                "namespace": self.chain.namespaces[
                    mix["follower_namespace_ranks"][f]]}

    # -- one request --------------------------------------------------------

    def _light_request(self, sut, height: int, rng, spans):
        """(cells, reply or None): the header, then the samples."""
        with spans(REQUEST_SPAN):
            try:
                with spans("light_header"):
                    sut.light_header(height)
            except Exception as e:  # refused: failed, not fatal
                print(f"header at height {height} refused: {e!r}",
                      flush=True)
                return [], None
            return self._light(sut, height, rng, spans)

    def _read_request(self, sut, height: int, asked: list[bytes], spans):
        with spans(REQUEST_SPAN), spans("namespaces_many"):
            try:
                return sut.namespaces(height, asked)
            except Exception as e:  # refused: failed, not fatal
                print(f"namespace read at height {height} refused: {e!r}",
                      flush=True)
                return None

    # -- set-up -------------------------------------------------------------

    def warm(self, sut, spans, log) -> list[dict]:
        """Commits the chain; then a light request and a read at each of
        `warm_heights` old heights, reads of one namespace and of one plus
        the absent one (the device search pads its queries to powers of
        two: 1 and 2), before and after the light request, so that a miss
        has been paid under either kind. Ends with the warmer idle."""
        mix = self.mix
        if sut.is_reference:
            # the plain validator keeps max(served_heights, 8) blocks; it
            # has no cache to miss, so here its `keep` is its block store
            sut.keep = max(sut.keep, mix["setup_blocks"])
        records = []
        for _ in range(mix["setup_blocks"]):
            rec = self.chain.one_block(sut, spans)
            log(phase="setup_block", height=rec["produced"].height,
                square_size=rec["produced"].square_size,
                seconds=round(rec["loop_s"], 3))
            records.append(rec)
        self.tip = records[-1]["produced"].height
        oldest = self.tip - mix["setup_blocks"] + 1
        self.swept = list(range(oldest, oldest + mix["swept_heights"]))
        self.blocks = {r["produced"].height: r["produced"] for r in records}
        rng = np.random.default_rng([self.seed, 22])
        name = self.chain.namespaces[mix["follower_namespace_ranks"][0]]
        step = mix["swept_heights"] // mix["warm_heights"]
        for j in range(mix["warm_heights"]):
            height = self.swept[j * step]
            asked = [name] + ([self.absent] if j % 2 else [])
            first_read = j % 2
            if first_read:
                self._warm_read(sut, height, asked, spans)
            cells, reply = self._light_request(sut, height, rng, spans)
            if reply is not None:
                self.warm_kept.append(("light", height, cells, reply))
            if not first_read:
                self._warm_read(sut, height, asked, spans)
        sut.wait_warm(600)
        return records

    def _warm_read(self, sut, height: int, asked: list[bytes], spans):
        reply = self._read_request(sut, height, asked, spans)
        if reply is not None:
            self.warm_kept.append(("read", height, asked, reply))

    # -- the window ---------------------------------------------------------

    def _client(self, ci: int, sut, deadline: float, spans, out: dict):
        rng = np.random.default_rng([self.seed, 23, ci])
        plan = self.schedules[ci]
        keep_every = self.mix["keep_every"]
        done = refused = 0
        kept, read_shapes = [], []
        try:
            while time.perf_counter() < deadline:
                height = self.swept[height_index(plan, done,
                                                 len(self.swept))]
                if plan["kind"] == "light":
                    asked, reply = self._light_request(sut, height, rng,
                                                       spans)
                    bad = (self.mix["cells_per_round"] if reply is None
                           else sut.refused_in(reply))
                else:
                    asked = [plan["namespace"]]
                    if (done + 1) % self.mix["absent_every"] == 0:
                        asked.append(self.absent)
                    reply = self._read_request(sut, height, asked, spans)
                    bad = (len(asked) if reply is None
                           else sut.refused_in(reply))
                    read_shapes.append((self.k, len(asked)))
                # the 2nd, then every keep_every-th: with the mix's 9 and
                # absent_every 10, a follower's 20th read, which asks the
                # absent namespace besides, is among them
                if done % keep_every == 1 and reply is not None:
                    kept.append((plan["kind"], height, asked, reply))
                refused += bad
                done += 1
        except BaseException as e:  # read by window(); a thread must not die silently
            out["errors"].append(e)
        out["done"][ci] = done
        out["refused"][ci] = refused
        out["kept"][ci] = kept
        out["reads"][ci] = read_shapes

    def window(self, sut, seconds: float, spans) -> dict:
        name = self.mix["device_dispatch_counter"]
        before = sut.counters().get(name, 0)
        out = super().window(sut, seconds, spans)
        out["extends"] = sut.counters().get(name, 0) - before
        return out

    def units(self, records: dict) -> dict:
        """`square_size`: one entry per extend of the window, counted from
        the dispatch counter at the window's two ends (the floor of
        floors/extend_commit.py sums over it)."""
        return {**super().units(records),
                "extends": records["extends"],
                # walkers that coalesced on one build stay in step: equal
                # counts here are a convoy (PERF.md section 6, PR 28)
                "requests_by_client": records["done"],
                "square_size": [self.k] * records["extends"]}

    # -- correctness --------------------------------------------------------

    def collect(self, sut, records: dict, warm_records: list[dict]) -> dict:
        """The kept replies decoded, the warm-up's among them, and one more
        light request: the tip, resident before the sweep, evicted by it."""
        rng = np.random.default_rng([self.seed, 24])
        cells, reply = self._light_request(sut, self.tip, rng,
                                           tracing.Spans())
        closing = [("light", self.tip, cells, reply)] if reply is not None \
            else []
        refused = sum(records["refused"]) + (
            self.mix["cells_per_round"] if reply is None
            else sut.refused_in(reply))
        kept = []
        for kind, height, asked, got in (
                self.warm_kept + closing
                + [x for per_client in records["kept"] for x in per_client]):
            decoded = (sut.decode_samples(got) if kind == "light"
                       else sut.decode_namespaces(got))
            kept.append((kind, height, asked, decoded))
        touched = sorted({height for _kind, height, _a, _d in kept})
        return {"kept": kept, "refused": refused,
                "blocks": {h: self.blocks[h] for h in touched}}


def prepare(cell, seed: int, seconds: float) -> Traffic:
    return Traffic(cell, seed)
