"""blobload: the rollup-reader read-plane load harness.

Models the north star's READ shape — a fleet of rollup followers each
pulling its namespace's blobs + proofs from one serving node — and
measures what the read plane delivers under that concurrency
(tools/dasload.py is the sampling-plane sibling; same harness shape:
persistent connections, barrier start, one JSON report):

- every reader is a thread holding ONE persistent HTTP/1.1 connection,
  released off a start barrier so the clock covers steady state only;
- ``mode="single"`` issues one ``GET /blob/get`` per (height, namespace)
  query — the per-request host loop the batched route is measured
  against;
- ``mode="batch"`` folds ``batch`` queries into one
  ``POST /blob/namespaces`` round-trip — the read plane's intended
  shape (one engine-gated dispatch resolves the whole batch);
- ``mode="pack"`` reads the namespace's doc out of the height's static
  blob pack (manifest position -> chunk index, chunk sha256-checked
  against the manifest).

Report: ``namespace_queries_per_sec``, per-request ``p50_ms``/``p99_ms``,
``present_ratio``, ``pack_hit_ratio``, error counts; docs/FORMATS.md
§21.5 is the schema. No benchmark cell runs it: not measured on the chip.

Standalone use against any devnet:

    python -m celestia_app_tpu blobload --url http://127.0.0.1:26658 \
        --readers 256 --requests 4 --mode batch --batch 64
"""

from __future__ import annotations

import hashlib
import json
import threading
import time

from celestia_app_tpu.tools.dasload import _Conn, _percentile

DEFAULT_READERS = 256
DEFAULT_REQUESTS = 4
DEFAULT_BATCH = 64


class _Stats:
    """The run's shared tally (lock-guarded; readers report per
    request)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.latencies_ms: list[float] = []  # guarded-by: lock
        self.queries = 0        # guarded-by: lock
        self.present = 0        # guarded-by: lock
        self.pack_queries = 0   # guarded-by: lock
        self.errors = 0         # guarded-by: lock
        self.chunk_mismatches = 0  # guarded-by: lock

    def note(self, dt_ms: float, queries: int, present: int,
             via_pack: bool) -> None:
        with self.lock:
            self.latencies_ms.append(dt_ms)
            self.queries += queries
            self.present += present
            if via_pack:
                self.pack_queries += queries

    def note_error(self) -> None:
        with self.lock:
            self.errors += 1

    def note_mismatch(self) -> None:
        with self.lock:
            self.chunk_mismatches += 1


def _fetch_manifests(url: str, heights: list[int],
                     timeout: float) -> dict[int, dict | None]:
    """One blob-pack manifest fetch per height, shared by the fleet (a
    CDN would cache these identically); None marks a pack-less
    height."""
    import http.client

    conn = _Conn(url, timeout)
    out: dict[int, dict | None] = {}
    for h in heights:
        try:
            status, body = conn.request("GET", f"/blob/pack?height={h}")
            out[h] = json.loads(body) if status == 200 else None
        except (OSError, ValueError, http.client.HTTPException):
            out[h] = None
    conn.close()
    return out


def _query_plan(tid: int, i: int, heights: list[int],
                namespaces: list[str], batch: int) -> list[tuple[int, str]]:
    """The (height, namespace) queries one request covers — a rotating
    deterministic schedule, so every run over the same inputs asks the
    same questions (reproducible load, no rng)."""
    out = []
    base = tid * DEFAULT_REQUESTS + i
    for j in range(batch):
        idx = base + j
        out.append((heights[idx % len(heights)],
                    namespaces[idx % len(namespaces)]))
    return out


def _reader(tid: int, url: str, heights: list[int], namespaces: list[str],
            manifests: dict[int, dict | None], mode: str, requests: int,
            batch: int, timeout: float, barrier: threading.Barrier,
            stats: _Stats) -> None:
    import http.client

    conn = _Conn(url, timeout)
    try:
        barrier.wait()
    except threading.BrokenBarrierError:
        return
    for i in range(requests):
        plan = _query_plan(tid, i, heights, namespaces,
                           batch if mode == "batch" else 1)
        t0 = time.perf_counter()
        try:
            if mode == "batch":
                body = json.dumps({"queries": [
                    {"height": h, "namespace": ns} for h, ns in plan
                ]}).encode()
                status, out = conn.request("POST", "/blob/namespaces",
                                           body)
                if status != 200:
                    stats.note_error()
                    continue
                docs = json.loads(out).get("queries", [])
                ok = [d for d in docs if "error" not in d]
                stats.note((time.perf_counter() - t0) * 1e3, len(ok),
                           sum(1 for d in ok if d.get("present")),
                           via_pack=False)
            elif mode == "pack":
                h, ns = plan[0]
                m = manifests.get(h)
                if not m or ns not in m.get("namespaces", []):
                    # pack-less height or unpacked (absent) namespace:
                    # the pack path cannot answer — counts an error so
                    # pack runs against absent namespaces are visible
                    stats.note_error()
                    continue
                ci = (m["namespaces"].index(ns)
                      // int(m["chunk_namespaces"]))
                status, body = conn.request(
                    "GET", f"/blob/pack/chunk?height={h}&index={ci}")
                if status != 200:
                    stats.note_error()
                    continue
                if (hashlib.sha256(body).hexdigest()
                        != m["chunk_hashes"][ci]):
                    stats.note_mismatch()
                    continue
                docs = json.loads(body)
                doc = next((d for d in docs
                            if d.get("namespace") == ns), None)
                if doc is None:
                    stats.note_error()
                    continue
                stats.note((time.perf_counter() - t0) * 1e3, 1,
                           1 if doc.get("present") else 0, via_pack=True)
            else:  # single
                h, ns = plan[0]
                status, body = conn.request(
                    "GET", f"/blob/get?height={h}&namespace={ns}")
                if status != 200:
                    stats.note_error()
                    continue
                doc = json.loads(body)
                stats.note((time.perf_counter() - t0) * 1e3, 1,
                           1 if doc.get("present") else 0, via_pack=False)
        except (OSError, ValueError, KeyError,
                http.client.HTTPException):
            stats.note_error()
    conn.close()


def run_load(url: str, heights: list[int], namespaces: list[str],
             readers: int = DEFAULT_READERS,
             requests: int = DEFAULT_REQUESTS, mode: str = "single",
             batch: int = DEFAULT_BATCH, timeout: float = 30.0) -> dict:
    """Drive ``readers`` concurrent persistent-connection namespace
    readers at a serving node and return the aggregate report.
    ``mode``: "single" (GET /blob/get per query), "batch" (POST
    /blob/namespaces with ``batch`` queries per request), "pack" (static
    chunk reads, sha256-verified)."""
    if mode not in ("single", "batch", "pack"):
        raise ValueError(f"unknown blobload mode {mode!r}")
    if not heights or not namespaces:
        raise ValueError("blobload needs heights and namespaces")
    manifests = (_fetch_manifests(url, heights, timeout)
                 if mode == "pack" else {})
    stats = _Stats()
    barrier = threading.Barrier(readers + 1)
    threads = [
        threading.Thread(
            target=_reader,
            args=(tid, url, heights, namespaces, manifests, mode,
                  requests, batch, timeout, barrier, stats),
            daemon=True,
        )
        for tid in range(readers)
    ]
    for t in threads:
        t.start()
    barrier.wait()  # every connection is up: the clock starts here
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t0
    lat = sorted(stats.latencies_ms)
    total = stats.queries
    return {
        "mode": mode,
        "readers": readers,
        "requests_per_reader": requests,
        "batch": batch if mode == "batch" else 1,
        "heights": len(heights),
        "namespaces": len(namespaces),
        "wall_s": round(wall_s, 3),
        "requests_ok": len(lat),
        "errors": stats.errors,
        "chunk_hash_mismatches": stats.chunk_mismatches,
        "namespace_queries": total,
        "namespace_queries_per_sec": round(total / wall_s, 1)
        if wall_s else 0.0,
        "requests_per_sec": round(len(lat) / wall_s, 1) if wall_s
        else 0.0,
        "p50_ms": round(_percentile(lat, 0.50), 3),
        "p99_ms": round(_percentile(lat, 0.99), 3),
        "present_ratio": round(stats.present / total, 4) if total
        else 0.0,
        "pack_hit_ratio": round(stats.pack_queries / total, 4) if total
        else 0.0,
    }


def _discover(url: str, timeout: float) -> tuple[list[int], list[str]]:
    """Default inputs: the served head's last 4 heights, and the union
    of their packed namespaces (absent packs leave the list empty — the
    caller must then pass --namespaces)."""
    conn = _Conn(url, timeout)
    _status, body = conn.request("GET", "/das/head")
    head = int(json.loads(body)["height"])
    heights = list(range(max(1, head - 3), head + 1))
    seen: list[str] = []
    for h in heights:
        try:
            status, body = conn.request("GET", f"/blob/pack?height={h}")
            if status != 200:
                continue
            for ns in json.loads(body).get("namespaces", []):
                if ns not in seen:
                    seen.append(ns)
        except (OSError, ValueError):
            continue
    conn.close()
    return heights, seen


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="blobload",
        description="read-plane namespace load harness (FORMATS §21.5)")
    ap.add_argument("--url", required=True)
    ap.add_argument("--readers", type=int, default=DEFAULT_READERS)
    ap.add_argument("--requests", type=int, default=DEFAULT_REQUESTS)
    ap.add_argument("--mode", choices=("single", "batch", "pack"),
                    default="batch")
    ap.add_argument("--batch", type=int, default=DEFAULT_BATCH)
    ap.add_argument("--heights", default="",
                    help="comma-separated heights (default: the served "
                         "head's last 4)")
    ap.add_argument("--namespaces", default="",
                    help="comma-separated namespace hex strings "
                         "(default: the heights' packed namespaces)")
    ap.add_argument("--timeout", type=float, default=30.0)
    args = ap.parse_args(argv)
    heights, namespaces = [], []
    if args.heights:
        heights = [int(x) for x in args.heights.split(",")]
    if args.namespaces:
        namespaces = [x.strip() for x in args.namespaces.split(",") if x]
    if not heights or not namespaces:
        d_heights, d_namespaces = _discover(args.url, args.timeout)
        heights = heights or d_heights
        namespaces = namespaces or d_namespaces
    if not namespaces:
        print(json.dumps({"error": "no namespaces discovered; pass "
                                   "--namespaces"}))
        return 2
    rep = run_load(args.url, heights, namespaces, readers=args.readers,
                   requests=args.requests, mode=args.mode,
                   batch=args.batch, timeout=args.timeout)
    print(json.dumps(rep, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
