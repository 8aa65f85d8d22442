#!/usr/bin/env python3
"""The control of a cell's `correct`: the plain reference in the program's
place, once sound and once with each guarantee broken that the cell's mix
lists under `control_breaks` (reference/plain_node.py), at the cell's own
size, through the same traffic, window and comparison as run.py.

    python benchmark/control.py --workload <name> --seeds 1,2,3 --seconds 4

One JSON line per run. Exit 0 when every sound run came out correct and
every broken one not; 1 otherwise. Needs no chip (the plain validator is
numpy + hashlib); run.py's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

import run  # noqa: E402
from lib import cells  # noqa: E402
from reference.plain_node import PlainValidator  # noqa: E402


def plain(breaks):
    def make(cell, traffic):
        return PlainValidator(cell.config, traffic.accounts(),
                              traffic.client.sent, breaks=breaks)
    return make


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    device_doc = {"platform": "none", "kind": "plain reference", "count": 0}
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for breaks in [None] + list(cell.mix["control_breaks"]):
            out = run.run_cell(cell, seed, args.seconds, False, device_doc,
                               make_sut=plain(breaks))
            failing = {n: vl for n, vl in out["compared"].items()
                       if vl[0] > vl[1]}
            ok &= out["correct"] == (breaks is None)
            print(json.dumps({
                "control": cell.name, "seed": seed, "breaks": breaks,
                "correct": out["correct"], "failed": out["failed"],
                "failing": failing}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
