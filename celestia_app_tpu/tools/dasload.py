"""dasload: the thousand-sampler DAS serving-plane load harness.

Models the north star's client shape — a large fleet of *dumb* samplers
(arXiv:1910.01247's light-client model) hammering one serving node — and
measures what the serving plane actually delivers under that
concurrency:

- every sampler is a thread holding ONE persistent HTTP/1.1 connection
  (``http.client.HTTPConnection`` keep-alive; urllib would re-connect
  per request and measure socket setup, not serving), all released
  together off a start barrier so the clock covers steady state only;
- each request models one height's DAS round: draw ``cells`` coordinates
  from the sampler's own rng and obtain their proof docs either LIVE
  (one batched ``POST /das/samples``) or from the height's static proof
  pack (``GET /das/pack/chunk`` covering the drawn cells, sha256-checked
  against the manifest);
- ``mode="auto"`` prefers the pack and falls back to live per height —
  the DASer's own policy — so ``pack_hit_ratio`` reports how much of the
  fleet's demand the static path absorbed.

Output (and the ``run_load`` return value) is one JSON report:
``samples_per_sec``, ``requests_per_sec``, ``p50_ms``/``p99_ms`` per
request, ``pack_hit_ratio``, error counts; docs/FORMATS.md §17.5 is the
schema. The benchmark cell ``bigblock-k256-das-http`` (PR 40) times the
same fleet model on the chip — 128 keep-alive samplers in 8 client
processes against ``NodeService``'s port — with a client of its own
(``benchmark/generators/http_sampler_client.py``), not this module, so
that no change to the program can move the yardstick.

Standalone use against any devnet:

    python -m celestia_app_tpu dasload --url http://127.0.0.1:26658 \
        --samplers 1000 --requests 3 --cells 16 --mode auto
"""

from __future__ import annotations

import hashlib
import http.client
import json
import random
import threading
import time
import urllib.parse

DEFAULT_SAMPLERS = 1000
DEFAULT_REQUESTS = 3
DEFAULT_CELLS = 16


class _Conn:
    """One sampler's persistent connection: keep-alive across requests,
    transparent single reconnect on a torn socket (the server's idle
    reaper or a request cap may close it mid-run)."""

    def __init__(self, url: str, timeout: float):
        p = urllib.parse.urlparse(url)
        self.host = p.hostname
        self.port = p.port or (443 if p.scheme == "https" else 80)
        self.timeout = timeout
        self.conn: http.client.HTTPConnection | None = None

    def _connect(self) -> http.client.HTTPConnection:
        if self.conn is None:
            self.conn = http.client.HTTPConnection(
                self.host, self.port, timeout=self.timeout)
        return self.conn

    def close(self) -> None:
        if self.conn is not None:
            try:
                self.conn.close()
            finally:
                self.conn = None

    def request(self, method: str, path: str,
                body: bytes | None = None) -> tuple[int, bytes]:
        """(status, body); one reconnect attempt on connection-level
        failure (keep-alive races are normal, not errors)."""
        for attempt in (0, 1):
            conn = self._connect()
            try:
                headers = {}
                if body is not None:
                    headers["Content-Type"] = "application/json"
                conn.request(method, path, body=body, headers=headers)
                r = conn.getresponse()
                return r.status, r.read()
            except (OSError, http.client.HTTPException):
                self.close()
                if attempt:
                    raise
        raise OSError("unreachable")


class _Stats:
    """The run's shared tally (lock-guarded; samplers report per
    request)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.latencies_ms: list[float] = []  # guarded-by: lock
        self.samples = 0          # guarded-by: lock
        self.pack_samples = 0     # guarded-by: lock
        self.live_samples = 0     # guarded-by: lock
        self.errors = 0           # guarded-by: lock
        self.chunk_mismatches = 0  # guarded-by: lock

    def note(self, dt_ms: float, samples: int, via_pack: bool) -> None:
        with self.lock:
            self.latencies_ms.append(dt_ms)
            self.samples += samples
            if via_pack:
                self.pack_samples += samples
            else:
                self.live_samples += samples

    def note_error(self) -> None:
        with self.lock:
            self.errors += 1

    def note_mismatch(self) -> None:
        with self.lock:
            self.chunk_mismatches += 1


def _percentile(sorted_ms: list[float], p: float) -> float:
    if not sorted_ms:
        return 0.0
    i = min(len(sorted_ms) - 1, int(p * (len(sorted_ms) - 1) + 0.5))
    return sorted_ms[i]


def _fetch_manifests(url: str, heights: list[int],
                     timeout: float) -> dict[int, dict | None]:
    """One manifest fetch per height, shared by the whole fleet (a CDN
    would cache these identically); None marks a pack-less height."""
    conn = _Conn(url, timeout)
    out: dict[int, dict | None] = {}
    for h in heights:
        try:
            status, body = conn.request("GET", f"/das/pack?height={h}")
            out[h] = json.loads(body) if status == 200 else None
        except (OSError, ValueError, http.client.HTTPException):
            out[h] = None
    conn.close()
    return out


def _fetch_draw_spaces(url: str, heights: list[int],
                       timeout: float) -> dict[int, tuple]:
    """height -> ("rs2d", width) | ("cmt", n_layer0): the live draw
    space per height, from one upfront /das/header fetch shared by the
    fleet — live samplers must draw over the REAL space (an unlearned
    width would sample a 2x2 corner and flatter the assembly path)."""
    conn = _Conn(url, timeout)
    out: dict[int, tuple] = {}
    for h in heights:
        space = ("rs2d", 2)
        try:
            status, body = conn.request("GET", f"/das/header?height={h}")
            if status == 200:
                doc = json.loads(body)
                if "square_width" in doc:
                    space = ("rs2d", int(doc["square_width"]))
                elif "k" in doc:
                    # CMT: light clients draw layer-0 coded symbols
                    # (FORMATS §16.3) — 2k² of them at rate 1/2
                    space = ("cmt", 2 * int(doc["k"]) ** 2)
        except (OSError, ValueError, http.client.HTTPException):
            pass
        out[h] = space
    conn.close()
    return out


def _sampler(tid: int, url: str, heights: list[int],
             manifests: dict[int, dict | None],
             spaces: dict[int, tuple], mode: str,
             requests: int, cells: int, timeout: float,
             barrier: threading.Barrier, stats: _Stats) -> None:
    rng = random.Random(0xDA5 + tid)
    conn = _Conn(url, timeout)
    try:
        barrier.wait()
    except threading.BrokenBarrierError:
        return
    for i in range(requests):
        h = heights[(tid + i) % len(heights)]
        m = manifests.get(h) if mode in ("pack", "auto") else None
        if mode == "pack" and m is None:
            stats.note_error()
            continue
        t0 = time.perf_counter()
        try:
            if m is not None:
                # chunk-granular sampling (the CMT/pack model): draw one
                # random cell, fetch THE chunk that covers it, verify
                # the bytes against the manifest — every doc the chunk
                # carries is a served, verifiable proof, which is the
                # whole economic point of static packs (one read serves
                # the neighborhood). One round-trip, like a live batch.
                n_cells = int(m["n_cells"])
                chunk_cells = int(m["chunk_cells"])
                ci = rng.randrange(n_cells) // chunk_cells
                status, body = conn.request(
                    "GET", f"/das/pack/chunk?height={h}&index={ci}")
                ok = status == 200
                if ok and (hashlib.sha256(body).hexdigest()
                           != m["chunk_hashes"][ci]):
                    stats.note_mismatch()
                    ok = False
                if ok:
                    served = min(chunk_cells, n_cells - ci * chunk_cells)
                    stats.note((time.perf_counter() - t0) * 1e3,
                               served, via_pack=True)
                    continue
                if mode == "pack":
                    stats.note_error()
                    continue
                # auto: fall through to live for this height
            # live assembly: the sampler's real draw shape over the
            # REAL sample space (fetched upfront per height) — the
            # server resolves the height once and proves each cell
            kind, n = spaces.get(h, ("rs2d", 2))
            if kind == "cmt":
                draw = [[0, rng.randrange(n)] for _ in range(cells)]
            else:
                draw = [[rng.randrange(n), rng.randrange(n)]
                        for _ in range(cells)]
            body = json.dumps({"height": h, "cells": draw}).encode()
            status, out = conn.request("POST", "/das/samples", body)
            if status != 200:
                stats.note_error()
                continue
            doc = json.loads(out)
            served = sum(1 for s in doc.get("samples", [])
                         if "error" not in s)
            stats.note((time.perf_counter() - t0) * 1e3, served,
                       via_pack=False)
        except (OSError, ValueError, KeyError,
                http.client.HTTPException):
            stats.note_error()
    conn.close()


def run_load(url: str, heights: list[int], samplers: int = DEFAULT_SAMPLERS,
             requests: int = DEFAULT_REQUESTS, cells: int = DEFAULT_CELLS,
             mode: str = "auto", timeout: float = 30.0) -> dict:
    """Drive ``samplers`` concurrent persistent-connection samplers at a
    serving node and return the aggregate report. ``mode``: "live"
    (always POST /das/samples), "pack" (pack chunks only; a pack-less
    height counts an error), "auto" (pack preferred, live fallback)."""
    if mode not in ("live", "pack", "auto"):
        raise ValueError(f"unknown dasload mode {mode!r}")
    manifests = (_fetch_manifests(url, heights, timeout)
                 if mode in ("pack", "auto") else {})
    spaces = (_fetch_draw_spaces(url, heights, timeout)
              if mode in ("live", "auto") else {})
    stats = _Stats()
    barrier = threading.Barrier(samplers + 1)
    threads = [
        threading.Thread(
            target=_sampler,
            args=(tid, url, heights, manifests, spaces, mode, requests,
                  cells, timeout, barrier, stats),
            daemon=True,
        )
        for tid in range(samplers)
    ]
    for t in threads:
        t.start()
    barrier.wait()  # every connection is up: the clock starts here
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t0
    lat = sorted(stats.latencies_ms)
    total = stats.samples
    return {
        "mode": mode,
        "samplers": samplers,
        "requests_per_sampler": requests,
        "cells_per_request": cells,
        "heights": len(heights),
        "wall_s": round(wall_s, 3),
        "requests_ok": len(lat),
        "errors": stats.errors,
        "chunk_hash_mismatches": stats.chunk_mismatches,
        "samples": total,
        "samples_per_sec": round(total / wall_s, 1) if wall_s else 0.0,
        "requests_per_sec": round(len(lat) / wall_s, 1) if wall_s
        else 0.0,
        "p50_ms": round(_percentile(lat, 0.50), 3),
        "p99_ms": round(_percentile(lat, 0.99), 3),
        "pack_hit_ratio": round(stats.pack_samples / total, 4)
        if total else 0.0,
    }


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="dasload",
        description="DAS serving-plane load harness (FORMATS §17.5)")
    ap.add_argument("--url", required=True)
    ap.add_argument("--samplers", type=int, default=DEFAULT_SAMPLERS)
    ap.add_argument("--requests", type=int, default=DEFAULT_REQUESTS)
    ap.add_argument("--cells", type=int, default=DEFAULT_CELLS)
    ap.add_argument("--mode", choices=("live", "pack", "auto"),
                    default="auto")
    ap.add_argument("--heights", default="",
                    help="comma-separated heights (default: the served "
                         "head's last 8)")
    ap.add_argument("--timeout", type=float, default=30.0)
    args = ap.parse_args(argv)
    if args.heights:
        heights = [int(x) for x in args.heights.split(",")]
    else:
        conn = _Conn(args.url, args.timeout)
        _status, body = conn.request("GET", "/das/head")
        head = int(json.loads(body)["height"])
        conn.close()
        heights = list(range(max(1, head - 7), head + 1))
    rep = run_load(args.url, heights, samplers=args.samplers,
                   requests=args.requests, cells=args.cells,
                   mode=args.mode, timeout=args.timeout)
    print(json.dumps(rep, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
