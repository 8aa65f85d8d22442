"""Cross-node trace timeline: merge span tables into per-height waterfalls.

The consumer side of the observability plane (obs/spans.py): every node of
a devnet serves its span rows at ``/trace/spans``; this tool scrapes them
all, groups rows by trace_id (deterministic per height — `trace_id_for`),
and renders a text waterfall answering "where did block H spend its time
between proposer, followers, and light nodes". It is the analog of the
reference's e2e trace pullers (celestia-core pkg/trace + the testnet
tooling that tails BlockSummary/RoundState tables), upgraded with span
structure.

Library surface (used by tests and the CLI `timeline` command):
  scrape(urls)                 {node_label: [span rows]} over HTTP
  scrape_xfers(urls)           {node_label: [transfer ledger rows]}
  merge_spans(rows_by_node)    {trace_id: [rows tagged with "node"]}
  heights_of(merged)           {height: trace_id} for rows carrying one
  render_waterfall(rows)       the text waterfall for one trace
  collect(urls, height=None)   scrape + merge (+ height filter)

The boundary observatory's transfer ledger (obs/xfer.py) writes its
rows into the same per-App trace tables under the ``xfer`` table,
stamped with the covering span's trace id — `collect` scrapes them too
(``/trace/xfer``) and folds each one into its height's waterfall as a
leaf named ``xfer:<site> <dir> <bytes>B`` under the span that covered
the transfer, so a block's host↔device traffic renders inline with its
compute spans (and rides the --json dump for machine consumers).

The renderer needs only row dicts — in-process TraceTables output works
the same as scraped JSON, so a light node that serves no HTTP (an
embedded DASer) can hand its `daser.traces.read("spans")` rows straight
to merge_spans.
"""

from __future__ import annotations

import json

from celestia_app_tpu.obs import SPAN_TABLE
from celestia_app_tpu.obs.xfer import XFER_TABLE

BAR_WIDTH = 40


def fetch_node_spans(url: str, since: int = 0, limit: int = 10_000,
                     client=None) -> list[dict]:
    """Pull one node's span rows over HTTP (node service or validator
    service — both serve /trace/spans)."""
    from celestia_app_tpu.net import transport

    client = client or transport.DEFAULT
    doc = client.get(url.rstrip("/"),
                     f"/trace/{SPAN_TABLE}?since={since}&limit={limit}")
    return list(doc.get("rows", []))


def scrape(urls: list[str], since: int = 0,
           limit: int = 10_000) -> dict[str, list[dict]]:
    """{node_label: rows} for every reachable node; unreachable nodes
    yield an empty list (a partial devnet still renders)."""
    out: dict[str, list[dict]] = {}
    for url in urls:
        label = url.rstrip("/")
        try:
            out[label] = fetch_node_spans(url, since=since, limit=limit)
        except (OSError, ValueError, KeyError):
            out[label] = []
    return out


def fetch_node_xfers(url: str, since: int = 0, limit: int = 10_000,
                     client=None) -> list[dict]:
    """Pull one node's transfer-ledger rows (obs/xfer.py) over HTTP."""
    from celestia_app_tpu.net import transport

    client = client or transport.DEFAULT
    doc = client.get(url.rstrip("/"),
                     f"/trace/{XFER_TABLE}?since={since}&limit={limit}")
    return list(doc.get("rows", []))


def scrape_xfers(urls: list[str], since: int = 0,
                 limit: int = 10_000) -> dict[str, list[dict]]:
    """{node_label: ledger rows}; unreachable nodes yield []."""
    out: dict[str, list[dict]] = {}
    for url in urls:
        label = url.rstrip("/")
        try:
            out[label] = fetch_node_xfers(url, since=since, limit=limit)
        except (OSError, ValueError, KeyError):
            out[label] = []
    return out


def _xfer_as_span_row(row: dict) -> dict:
    """A ledger row shaped like a leaf span: named by call site + bytes,
    parented (via parent_id) under the span that covered the transfer.
    It carries no span_id — the renderer indents it one level below its
    parent."""
    return {
        **row,
        "table": XFER_TABLE,
        "name": (f"xfer:{row.get('site', '?')} {row.get('dir', '?')} "
                 f"{int(row.get('bytes', 0))}B"),
    }


def merge_spans(rows_by_node: dict[str, list[dict]]) -> dict[str, list[dict]]:
    """Group every node's span rows by trace_id, tagging each row with its
    source node. Rows inside a trace sort by start_unix — valid across
    processes on one host (the devnet case); cross-host clock skew only
    shifts bars, never the parent/child edges."""
    merged: dict[str, list[dict]] = {}
    for node, rows in rows_by_node.items():
        for row in rows:
            tid = row.get("trace_id")
            if not tid:
                continue
            merged.setdefault(tid, []).append({**row, "node": node})
    for rows in merged.values():
        rows.sort(key=lambda r: (r.get("start_unix", 0.0),
                                 r.get("_index", 0)))
    return merged


def heights_of(merged: dict[str, list[dict]]) -> dict[int, str]:
    """{height: trace_id} for traces whose rows carry a height attr."""
    out: dict[int, str] = {}
    for tid, rows in merged.items():
        for row in rows:
            h = row.get("height")
            if isinstance(h, int):
                out.setdefault(h, tid)
                break
    return out


def _depths(rows: list[dict]) -> dict[str, int]:
    by_id = {r.get("span_id"): r for r in rows if r.get("span_id")}

    def depth(row, hops=0) -> int:
        if hops > len(rows):  # defensive: a parent cycle must not hang
            return 0
        parent = by_id.get(row.get("parent_id"))
        if parent is None:
            return 0
        return 1 + depth(parent, hops + 1)

    return {sid: depth(row) for sid, row in by_id.items()}


def _host_account(row: dict) -> str:
    """`cpu … sys … flt … sw …` for a span row that carries any of the
    host's account (obs/spans.py writes `sys_ms`, `minflt`, `majflt`,
    `vcsw`, `icsw` only where non-zero): which span of which height
    faulted pages in, let go of the processor or was pre-empted."""
    flt, maj = row.get("minflt", 0), row.get("majflt", 0)
    vol, inv = row.get("vcsw", 0), row.get("icsw", 0)
    if not ("sys_ms" in row or flt or maj or vol or inv):
        return ""
    parts = [f"cpu {row.get('cpu_ms', 0.0):.1f}"]
    if "sys_ms" in row:
        parts.append(f"sys {row['sys_ms']:.1f}")
    if flt or maj:
        parts.append(f"flt {flt}" + (f"+{maj}maj" if maj else ""))
    if vol or inv:
        parts.append(f"sw {vol}v+{inv}i")
    return "  (" + " ".join(parts) + ")"


def render_waterfall(rows: list[dict], width: int = BAR_WIDTH) -> str:
    """One trace's rows -> a text waterfall: offset from the earliest
    span, indentation by parent depth, a proportional bar, the host's
    account where a row carries one, node label."""
    if not rows:
        return "(no spans)"
    t0 = min(r.get("start_unix", 0.0) for r in rows)
    t_end = max(r.get("start_unix", 0.0) + r.get("dur_ms", 0.0) / 1e3
                for r in rows)
    total_s = max(t_end - t0, 1e-9)
    depths = _depths(rows)

    def row_depth(row: dict) -> int:
        sid = row.get("span_id")
        if sid in depths:
            return depths[sid]
        # ledger rows (and any span-id-less leaf): one level below the
        # parent span that covered them; orphans sit at the root
        return depths.get(row.get("parent_id"), -1) + 1

    tid = rows[0].get("trace_id", "?")
    heights = {r["height"] for r in rows if isinstance(r.get("height"), int)}
    head = f"trace {tid}"
    if heights:
        head += f" (height {', '.join(str(h) for h in sorted(heights))})"
    lines = [head,
             f"{'offset':>10}  {'dur':>9}  span"]
    for row in sorted(rows, key=lambda r: (r.get("start_unix", 0.0),
                                           row_depth(r))):
        off_s = row.get("start_unix", 0.0) - t0
        dur_s = row.get("dur_ms", 0.0) / 1e3
        lo = min(int(off_s / total_s * width), width - 1)
        hi = min(max(int((off_s + dur_s) / total_s * width), lo + 1), width)
        bar = " " * lo + "#" * (hi - lo) + " " * (width - hi)
        indent = "  " * row_depth(row)
        name = row.get("name", "?")
        node = row.get("node", "")
        lines.append(
            f"{off_s * 1e3:8.1f}ms {row.get('dur_ms', 0.0):8.2f}ms "
            f"|{bar}| {indent}{name}" + _host_account(row)
            + (f"  [{node}]" if node else "")
        )
    return "\n".join(lines)


def collect(urls: list[str], height: int | None = None,
            since: int = 0, limit: int = 10_000,
            xfers: bool = True) -> dict:
    """Scrape + merge a devnet; optionally keep only the given height's
    trace. With `xfers` (default) the transfer-ledger rows of every node
    join their heights' traces as leaf rows (table == "xfer").
    Returns {"traces": {trace_id: rows}, "heights": {h: tid}}."""
    rows_by_node = scrape(urls, since=since, limit=limit)
    if xfers:
        for node, xrows in scrape_xfers(urls, since=since,
                                        limit=limit).items():
            rows_by_node[node] = (rows_by_node.get(node, [])
                                  + [_xfer_as_span_row(r) for r in xrows])
    merged = merge_spans(rows_by_node)
    heights = heights_of(merged)
    if height is not None:
        tid = heights.get(height)
        merged = {tid: merged[tid]} if tid else {}
        heights = {height: tid} if tid else {}
    return {"traces": merged, "heights": heights}


def report_text(doc: dict, last: int = 5) -> str:
    """Render the `last` most recent heights' waterfalls (all traces
    without a height attr are skipped — they are ad-hoc roots)."""
    heights = doc.get("heights", {})
    if not heights:
        return "(no height-bearing traces found)"
    chunks = []
    for h in sorted(heights)[-last:]:
        chunks.append(render_waterfall(doc["traces"][heights[h]]))
    return "\n\n".join(chunks)


def main(argv=None) -> int:
    """`python -m celestia_app_tpu.tools.timeline --nodes url1,url2`
    (the CLI `timeline` subcommand wraps this)."""
    import argparse

    ap = argparse.ArgumentParser(prog="timeline")
    ap.add_argument("--nodes", required=True,
                    help="comma-separated node/validator service URLs")
    ap.add_argument("--height", type=int, default=None)
    ap.add_argument("--since", type=int, default=0)
    ap.add_argument("--limit", type=int, default=10_000)
    ap.add_argument("--last", type=int, default=5,
                    help="render the N most recent heights (text mode)")
    ap.add_argument("--json", action="store_true",
                    help="dump the merged span rows as JSON instead")
    ap.add_argument("--no-xfer", action="store_true",
                    help="skip the transfer-ledger rows (/trace/xfer)")
    args = ap.parse_args(argv)
    doc = collect([u for u in args.nodes.split(",") if u],
                  height=args.height, since=args.since, limit=args.limit,
                  xfers=not args.no_xfer)
    if args.json:
        print(json.dumps(doc, indent=2, sort_keys=True))
    else:
        print(report_text(doc, last=args.last))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
