"""The least time the chip could take for the work of one kind of device
program (the metric's floor, from shapes and the table of peaks) over the
time those programs ran in the traced window.

The metric's file names the programs: `program_prefix`, the start of their
name on the trace's "XLA Modules" line, and `span`, the benchmark span in
which the host sets them off. Three programs of this repo are all called
`jit_run`; the extend + commit pipeline is the one `produce_block` starts,
the namespace search the one `namespaces_many` starts (the prover's runs on
the warmer's thread; commit wakes it, so one of its runs can start before
`produce_block` has returned: `"which": "largest"` keeps, of the programs
that match, only the one that took most time, which the pipeline does).
Programs of other names, and of that name under other spans, are in neither
the floor nor the time. Nothing to read (no such program ran) gives nothing,
never 0."""

from lib import cells


def read(spec: dict, reading) -> float | None:
    trace = reading.trace
    if trace is None:
        return None
    programs = trace["programs_in_spans"].get(spec["span"], {})
    matching = [seconds for name, seconds in programs.items()
                if name.startswith(spec["program_prefix"])]
    if not matching or max(matching) <= 0:
        return None
    ran_s = max(matching) if spec.get("which") == "largest" else sum(matching)
    floor = cells.load_module("floors", spec["floor"], reading.bench_dir)
    seconds, _binds = floor.floor_seconds(reading.units, reading.peaks)
    if seconds <= 0:
        return None
    return 100.0 * seconds / ran_s
