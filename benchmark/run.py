#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: read the cell, require the chips it asks for, build traffic from
the seed, start the validator, warm only this cell's shapes, measure for
`--seconds`, compare what the window produced with the plain reference, print
one JSON line last on standard output. Everything a cell is made of is found
by name (lib/cells.py); nothing here knows a cell, a mix or a metric.

Exit codes: 0 a result line was printed (its `correct` may be false);
3 no TPU or the wrong number of chips; 4 a fallback counter fired or the
cell's device dispatch counter stayed 0 (a dead device path posts no number);
5 the traffic could not fill the window (a window cut short posts no number);
2 bad arguments or a broken manifest.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

from lib import cells, device, tracing  # noqa: E402


# A traced run measures at most this long: the device's line carries one
# event per executed HLO operation, 380,000 a second on the produce cells
# (200 MB of trace per 10 s), and per-layer metrics carry no bound.
TRACE_SECONDS_MAX = 10.0


class DeadDevicePath(Exception):
    """A fallback fired or the device path was never dispatched."""


EXIT_NO_CHIP, EXIT_DEAD_DEVICE_PATH, EXIT_WINDOW_CUT_SHORT = 3, 4, 5


@dataclasses.dataclass
class Reading:
    """What a per-layer reducer may read."""
    spans: dict
    counters: dict
    units: dict
    trace: dict | None
    peaks: dict
    bench_dir: str


def log(**doc) -> None:
    """Phase lines, counters and records: every line but the last."""
    print(json.dumps(doc), flush=True)


def real_validator(cell, traffic):
    from lib import sut

    return sut.Validator(cell.config, traffic.accounts())


def load_peaks(bench_dir: str, kind: str) -> dict:
    with open(os.path.join(bench_dir, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if kind not in table:
        raise cells.CellError(
            f"device kind {kind!r} is not in peaks.json "
            f"({', '.join(table)}): add it with its source, no default")
    return table[kind]


def gate(sut, mix: dict, totals: dict, delta: dict) -> None:
    if sut.is_reference:
        return
    from lib.sut import FALLBACK_COUNTERS

    fired = {n: totals.get(n, 0) for n in FALLBACK_COUNTERS
             if totals.get(n, 0)}
    if fired:
        raise DeadDevicePath(f"a fallback counter fired: {fired}")
    name = mix["device_dispatch_counter"]
    if delta.get(name, 0) <= 0:
        raise DeadDevicePath(
            f"{name} did not move in the window: the device path is dead")


def run_cell(cell, seed: int, seconds: float, trace: bool, device_doc: dict,
             make_sut=real_validator, t_process: float = T_PROCESS) -> dict:
    """The run after the chip gate. `make_sut` lets the control and the CPU
    tests put another system behind the same traffic."""
    if trace:
        seconds = min(seconds, TRACE_SECONDS_MAX)
    traffic = cell.generator().prepare(cell, seed, seconds)
    sut = make_sut(cell, traffic)
    try:
        warm_records = traffic.warm(sut, tracing.Spans(), log)
        log(phase="ready", **traffic.ready(warm_records, seconds))
        compiles = tracing.CompileCounter()
        spans = tracing.Spans(annotate=trace)
        profile = tracing.Profile(os.path.join(
            os.path.dirname(cell.bench_dir), ".bench_trace"))
        c0 = sut.counters()
        if trace:
            profile.start()
        compiles_before = compiles.count
        setup_s = time.perf_counter() - t_process
        with spans("window"):
            records = traffic.window(sut, seconds, spans)
        window_compiles = compiles.count - compiles_before
        trace_path = profile.stop() if trace else None
        c1 = sut.counters()
        peak = device.memory_peak_bytes()
        delta = {n: v - c0.get(n, 0) for n, v in c1.items()
                 if v != c0.get(n, 0)}
        delta["bench.window_compiles"] = window_compiles
        log(phase="window", seconds=records["seconds"], setup_s=setup_s,
            counters=delta, **{k: v for k, v in traffic.units(records).items()
                               if k not in ("square_size", "host_bytes",
                                            "namespace_reads")})
        gate(sut, cell.mix, c1, delta)
        attempted, failed = traffic.counts(records)
        collected = traffic.collect(sut, records, warm_records)
    finally:
        sut.close()
    t_ref = time.perf_counter()
    compared = traffic.compare(collected)
    log(phase="reference", seconds=time.perf_counter() - t_ref)
    correct = all(value <= limit for value, limit in compared.values()) \
        and failed == 0
    dev = {**device_doc, "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed}
    if not trace:
        values = {**traffic.end_to_end(records), "setup_s": setup_s}
        out["metrics"] = {m.name: {"value": values[m.name], "unit": m.unit}
                          for m in cell.end_to_end}
    else:
        from reducers import xplane

        reduced = xplane.reduce_events(xplane.read_events(trace_path))
        profile.discard()
        if reduced is None or reduced["busy_s"] <= 0:
            raise DeadDevicePath("the trace shows no operation on the device")
        reading = Reading(
            spans=spans.seconds, counters=delta,
            units=traffic.units(records), trace=reduced,
            peaks=load_peaks(cell.bench_dir, device_doc["kind"]),
            bench_dir=cell.bench_dir)
        out["metrics"] = {}
        for m in cell.per_layer:
            reducer = cells.load_module("reducers", m.spec["reducer"],
                                        cell.bench_dir)
            value = reducer.read(m.spec, reading)
            if value is not None:
                out["metrics"][m.name] = {"value": value, "unit": m.unit}
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["device"] = dev
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.load_cell(args.workload)
    except cells.CellError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    # the package first: it places the compile cache (JAX_COMPILATION_CACHE_DIR
    # where set, else <checkout>/.jax_cache) before JAX loads
    import celestia_app_tpu  # noqa: F401

    try:
        device_doc = device.require_tpu(cell.chips)
    except device.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    log(phase="device", device=device_doc, workload=cell.name,
        seed=args.seed, seconds=args.seconds, trace=args.trace,
        backend_up_s=time.perf_counter() - T_PROCESS)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       device_doc)
    except DeadDevicePath as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_DEAD_DEVICE_PATH
    except cells.WindowCutShort as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_WINDOW_CUT_SHORT
    for name, (value, limit) in out["compared"].items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
