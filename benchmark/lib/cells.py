"""Everything a cell is made of, found by name.

`BENCHMARK.json` (the repo root's) lists cells and metrics; a cell names a
configuration (`configs/<config>.json`) and a traffic mix
(`traffic/<traffic>.json`); a mix names its generator
(`generators/<generator>.py`); a per-layer metric has `metrics/<name>.json`,
which names its reducer (`reducers/<reducer>.py`) and, for a roofline, its
floor (`floors/<floor>.py`). Nothing here, or in run.py, knows a cell, a mix
or a metric by name: adding one is adding files and manifest entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


class CellError(Exception):
    """The manifest or one of the files it names is missing or inconsistent."""


class WindowCutShort(Exception):
    """The traffic ran out before the window's seconds did: its rates would
    stand over a shorter time than asked for, so the run posts no number."""


def read_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CellError(f"{path}: {e}") from None


def load_manifest(bench_dir: str = BENCH_DIR) -> dict:
    return read_json(os.path.join(os.path.dirname(bench_dir),
                                   "BENCHMARK.json"))


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """`<bench_dir>/<kind>/<name>.py` as a module."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise CellError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    moves: str | None       # per-layer only
    spec: dict              # metrics/<name>.json, per-layer only


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    mix_name: str
    mix: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]
    bench_dir: str

    def generator(self):
        return load_module("generators", self.mix["generator"],
                           self.bench_dir)


def _in_cell(entry: dict, cell: str, reporting: set[str] | None) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return reporting is None or entry.get("moves") in reporting


def load_cell(name: str, bench_dir: str = BENCH_DIR) -> Cell:
    manifest = load_manifest(bench_dir)
    rows = [w for w in manifest["workloads"] if w["name"] == name]
    if not rows:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise CellError(f"no workload {name!r} in BENCHMARK.json ({known})")
    row = rows[0]
    configs = {c["name"]: c for c in manifest["configs"]}
    if row["config"] not in configs:
        raise CellError(f"workload {name!r} names config "
                        f"{row['config']!r}, which BENCHMARK.json lacks")
    config = read_json(os.path.join(
        os.path.dirname(bench_dir), configs[row["config"]]["file"]))
    mix = read_json(os.path.join(bench_dir, "traffic",
                                  f"{row['traffic']}.json"))
    end_to_end = [Metric(m["name"], m["unit"], None, {})
                  for m in manifest["end_to_end"] if _in_cell(m, name, None)]
    reporting = {m.name for m in end_to_end}
    per_layer = [
        Metric(m["name"], m["unit"], m["moves"], read_json(os.path.join(
            bench_dir, "metrics", f"{m['name']}.json")))
        for m in manifest["per_layer"] if _in_cell(m, name, reporting)]
    return Cell(name=name, chips=row["chips"], config_name=row["config"],
                config=config, mix_name=row["traffic"], mix=mix,
                end_to_end=end_to_end, per_layer=per_layer,
                bench_dir=bench_dir)
