"""Bench trajectory differ: align committed BENCH rounds, flag regressions.

Bench history can be kept as ``BENCH_*.json`` rounds (one captured
``bench.py`` output per file; the repo itself commits none —
``PERF_LEDGER.jsonl`` is its record), but nothing reads them back — a perf
regression only surfaces when someone eyeballs two JSON files. This tool
makes the history executable, the way CI perf gates diff benchmark
archives:

- **Load + align.** Every round file is parsed into ``{metric: row}``
  regardless of shape: the capture shape (``{"n", "cmd", "rc", "tail",
  "parsed"}`` — every ``{"metric": …}`` JSON line in the tail is
  extracted, later lines superseding earlier ones) and the flat hardware
  shape (one metric dict + an ``extras`` list). Metrics align by name
  across rounds in natural round order (r01 < r02 < … < r10).
- **Trajectory.** One line per metric: the value at every round that
  measured it, the delta of the newest comparable pair, and a verdict.
- **Regression flagging.** Direction is inferred from the unit (``ms`` /
  ``s`` / ``%`` → lower is better; ``…/s`` throughput → higher is
  better; unknown units are reported but never judged). The newest
  comparable sample is checked against the previous one; beyond
  ``--tolerance`` (default 10%) the metric is REGRESSED and the exit
  code is 2 — CI-usable. Rows whose value is ``null`` (a run that
  measured nothing) are skipped, not judged.
- **Backend hygiene.** Rows labeled ``"backend": "cpu-fallback"``
  (FORMATS §12.2 — the same bench run on a machine with no accelerator)
  never enter a hardware comparison: a TPU round followed by a CPU
  round is a fleet change, not a regression. They still print, marked.

``bench.py --compare`` wraps this against the repo root; the module CLI
(``python -m celestia_app_tpu.tools.benchdiff``) takes any directory of
rounds. Exit codes: 0 clean, 2 regressions found, 1 usage error.
"""

from __future__ import annotations

import glob as globmod
import json
import os
import re

TOLERANCE = 0.10

_NUM_CHUNK = re.compile(r"(\d+)")

#: units where a larger value is an improvement
_HIGHER_UNITS = ("/s", "per_sec", "blocks/s", "proofs/s", "txs/s")
#: units where a smaller value is an improvement
_LOWER_UNITS = ("ms", "s", "%")


def _natural_key(label: str):
    return [int(c) if c.isdigit() else c
            for c in _NUM_CHUNK.split(label)]


def round_label(path: str) -> str:
    stem = os.path.splitext(os.path.basename(path))[0]
    return stem[len("BENCH_"):] if stem.startswith("BENCH_") else stem


def _metric_rows(doc) -> list[dict]:
    """Every metric row a round document carries, in document order."""
    rows: list[dict] = []
    if not isinstance(doc, dict):
        return rows
    if "metric" in doc:
        # flat hardware shape: the primary row + its extras list
        rows.append({k: v for k, v in doc.items() if k != "extras"})
        for extra in doc.get("extras") or []:
            if isinstance(extra, dict) and "metric" in extra:
                rows.append(extra)
        return rows
    # capture shape: JSON lines inside the tail, `parsed` as fallback
    for line in str(doc.get("tail", "")).splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if isinstance(row, dict) and "metric" in row:
            rows.append(row)
    if not rows and isinstance(doc.get("parsed"), dict) \
            and "metric" in doc["parsed"]:
        rows.append(doc["parsed"])
    return rows


def load_round(path: str) -> dict:
    """{metric: row} for one round file; later rows supersede earlier
    ones (the capture tail repeats a metric as probes retry)."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    out: dict[str, dict] = {}
    for row in _metric_rows(doc):
        out[str(row["metric"])] = row
    return out


def load_rounds(paths: list[str]) -> list[tuple[str, dict]]:
    """[(label, {metric: row})] in natural round order."""
    rounds = [(round_label(p), load_round(p)) for p in paths]
    rounds.sort(key=lambda lr: _natural_key(lr[0]))
    return rounds


def direction_of(metric: str, unit: str | None) -> str | None:
    """'lower' | 'higher' | None (unknown — never judged)."""
    u = (unit or "").strip()
    if any(h in u for h in _HIGHER_UNITS) or "per_sec" in metric:
        return "higher"
    if u in _LOWER_UNITS:
        return "lower"
    return None


def _comparable(row: dict) -> bool:
    """A sample that may enter a hardware comparison: numeric value,
    not flagged as the CPU fallback of a hardware bench."""
    v = row.get("value")
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        return False
    return row.get("backend") != "cpu-fallback"


def diff(rounds: list[tuple[str, dict]],
         tolerance: float = TOLERANCE) -> dict:
    """Align metrics across rounds and judge the newest comparable pair
    of each. Returns the machine report (also what --json prints):

      {"rounds": [labels], "tolerance": f,
       "metrics": {name: {"unit", "direction", "samples":
                          [{"round", "value", "backend"?, "skipped"?}],
                          "delta_pct", "status"}},
       "regressions": [names]}

    status: "ok" | "regressed" | "improved" | "n/a" (fewer than two
    comparable samples, or unknown direction)."""
    metrics: dict[str, dict] = {}
    for label, rows in rounds:
        for name, row in rows.items():
            m = metrics.setdefault(name, {"unit": None, "samples": []})
            if m["unit"] is None and row.get("unit"):
                m["unit"] = row["unit"]
            sample = {"round": label, "value": row.get("value")}
            if "backend" in row:
                sample["backend"] = row["backend"]
            if not _comparable(row):
                sample["skipped"] = True
            m["samples"].append(sample)
    regressions = []
    for name in sorted(metrics):
        m = metrics[name]
        m["direction"] = direction_of(name, m["unit"])
        usable = [s for s in m["samples"] if not s.get("skipped")]
        if len(usable) < 2 or m["direction"] is None:
            m["delta_pct"] = None
            m["status"] = "n/a"
            continue
        # judge only like against like: the newest sample vs the newest
        # PRIOR sample from the same backend class (a TPU round followed
        # by an unlabeled round is a fleet change, not a perf move)
        newest = usable[-1]
        prior = next((s for s in reversed(usable[:-1])
                      if s.get("backend") == newest.get("backend")), None)
        if prior is None:
            m["delta_pct"] = None
            m["status"] = "n/a"
            continue
        prev, last = prior["value"], newest["value"]
        if prev == 0:
            m["delta_pct"] = None
            m["status"] = "n/a"
            continue
        delta = (last - prev) / abs(prev)
        m["delta_pct"] = round(delta * 100.0, 2)
        worse = delta > tolerance if m["direction"] == "lower" \
            else delta < -tolerance
        better = delta < -tolerance if m["direction"] == "lower" \
            else delta > tolerance
        m["status"] = ("regressed" if worse
                       else "improved" if better else "ok")
        if worse:
            regressions.append(name)
    return {
        "rounds": [label for label, _rows in rounds],
        "tolerance": tolerance,
        "metrics": {k: metrics[k] for k in sorted(metrics)},
        "regressions": regressions,
    }


def report_text(report: dict) -> str:
    lines = [f"rounds: {' '.join(report['rounds'])}   "
             f"tolerance: {report['tolerance'] * 100:.0f}%"]
    for name, m in report["metrics"].items():
        traj = " -> ".join(
            f"{s['value']}" + ("[cpu]" if s.get("backend") == "cpu-fallback"
                               else "" if not s.get("skipped") else "[skip]")
            for s in m["samples"])
        delta = (f"{m['delta_pct']:+.1f}%" if m["delta_pct"] is not None
                 else "  --")
        unit = m["unit"] or "?"
        lines.append(f"{m['status']:>9}  {delta:>8}  {name} [{unit}]: "
                     f"{traj}")
    if report["regressions"]:
        lines.append("REGRESSED: " + ", ".join(report["regressions"]))
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    import sys

    ap = argparse.ArgumentParser(prog="benchdiff")
    ap.add_argument("--dir", default=".",
                    help="directory holding the BENCH_*.json rounds")
    ap.add_argument("--glob", default="BENCH_*.json")
    ap.add_argument("--tolerance", type=float, default=TOLERANCE,
                    help="fractional regression tolerance (0.10 = 10%%)")
    ap.add_argument("--metric", default=None,
                    help="only this metric")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    paths = sorted(globmod.glob(os.path.join(args.dir, args.glob)))
    if not paths:
        print(f"ERROR: no rounds match {args.glob} in {args.dir}",
              file=sys.stderr)
        return 1
    try:
        report = diff(load_rounds(paths), tolerance=args.tolerance)
    except (OSError, ValueError) as e:
        print(f"ERROR: unreadable round file: {e}", file=sys.stderr)
        return 1
    if args.metric:
        report["metrics"] = {k: v for k, v in report["metrics"].items()
                             if k == args.metric}
        report["regressions"] = [r for r in report["regressions"]
                                 if r == args.metric]
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(report_text(report))
    return 2 if report["regressions"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
