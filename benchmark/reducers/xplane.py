"""From the profiler's trace to device busy time, the programs that took it
and the idle time laid against the benchmark's spans.

Two steps, so that the arithmetic is tested on a small recorded trace
(`tests/data/recorded_trace.json.gz`) without a chip:

  read_events(path)   .xplane.pb -> {"device_ops": {plane: [[start_ns,
                      dur_ns]]}, "device_programs": {plane: [[name, start_ns,
                      dur_ns]]}, "host_spans": [[name, start_ns, dur_ns]]}
  reduce_events(ev)   -> busy_s, window_s, device_ops, idle_gaps

What a v5e trace looks like (looked at by hand, PR 25): one plane per chip,
"/device:TPU:<n>". Its line "XLA Ops" has one event per executed HLO
operation (every trip of a while loop its own: 380,000 events per second of
this program), named by the operation's whole HLO text; busy time is the
union of those. Its line "XLA Modules" has one event per executed program,
named `jit_<function>(<fingerprint>)`: the only names a reader can use
today, so the breakdown's `device_ops` are programs. Host threads are lines
of "/host:CPU"; the benchmark's `bench.*` TraceAnnotations land on the
thread that made them. All planes share one clock.
"""

from __future__ import annotations

import numpy as np

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def read_events(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"device_ops": {}, "device_programs": {}, "host_spans": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["device_ops"][plane.name] = [
                        [int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
                elif line.name == PROGRAMS_LINE:
                    out["device_programs"][plane.name] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["host_spans"].append(
                            [e.name, int(e.start_ns), int(e.duration_ns)])
    return out


def union(starts, ends) -> tuple[np.ndarray, np.ndarray]:
    """Disjoint, sorted intervals covering the same points."""
    starts = np.asarray(starts, dtype=np.int64)
    ends = np.asarray(ends, dtype=np.int64)
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], ends[order]
    reach = np.maximum.accumulate(ends)
    first = np.concatenate(([True], starts[1:] > reach[:-1]))
    last = np.concatenate((first[1:], [True]))
    return starts[first], reach[last]


class Busy:
    """Covered nanoseconds of a set of disjoint sorted intervals."""

    def __init__(self, lo: np.ndarray, hi: np.ndarray):
        self.lo, self.hi = lo, hi
        self.before = np.concatenate(([0], np.cumsum(hi - lo)))

    def upto(self, x: int) -> int:
        i = int(np.searchsorted(self.lo, x, side="right"))
        if i == 0:
            return 0
        return int(self.before[i - 1]
                   + min(max(x - self.lo[i - 1], 0),
                         self.hi[i - 1] - self.lo[i - 1]))

    def within(self, a: int, b: int) -> int:
        return self.upto(b) - self.upto(a) if b > a else 0


def reduce_events(events: dict, top: int = 10) -> dict | None:
    """None when the trace holds no window span or no device line (nothing to
    read); otherwise seconds, averaged over the chips used.

    Idle time is laid against the benchmark's spans by name: for each name,
    the idle seconds inside the union of that name's spans (spans of several
    client threads overlap, so the names need not add up to the whole); what
    no span covers is `between_spans`. `programs_in_spans` gives, for each
    span name, the seconds of each device program that started while a span
    of that name was open: the only way to tell apart programs that share a
    name (`jit_run`) is by which call of the host set them off."""
    windows = [(s, s + d) for name, s, d in events["host_spans"]
               if name == WINDOW_SPAN]
    if not windows or not events["device_ops"]:
        return None
    w0, w1 = windows[0]
    by_name: dict[str, list] = {}
    for name, s, d in events["host_spans"]:
        if name != WINDOW_SPAN:
            by_name.setdefault(name[len(SPAN_PREFIX):], []).append(
                (max(s, w0), min(s + d, w1)))
    chips = len(events["device_ops"])
    busy_ns = 0
    gap_ns: dict[str, int] = {}
    for ops in events["device_ops"].values():
        arr = np.asarray(ops, dtype=np.int64).reshape(-1, 2)
        lo = np.clip(arr[:, 0], w0, w1)
        hi = np.clip(arr[:, 0] + arr[:, 1], w0, w1)
        busy = Busy(*union(lo[hi > lo], hi[hi > lo]))
        busy_ns += busy.within(w0, w1)
        covered = []
        for name, spans in by_name.items():
            s_lo, s_hi = union([a for a, b in spans if b > a],
                               [b for a, b in spans if b > a])
            idle = sum(int(b - a) - busy.within(int(a), int(b))
                       for a, b in zip(s_lo, s_hi))
            gap_ns[name] = gap_ns.get(name, 0) + idle
            covered += list(zip(s_lo, s_hi))
        c_lo, c_hi = union([a for a, _ in covered], [b for _, b in covered])
        in_spans = sum(int(b - a) - busy.within(int(a), int(b))
                       for a, b in zip(c_lo, c_hi))
        gap_ns["between_spans"] = (gap_ns.get("between_spans", 0)
                                   + (w1 - w0) - busy.within(w0, w1)
                                   - in_spans)
    program_ns: dict[str, int] = {}
    in_span_ns: dict[str, dict[str, int]] = {name: {} for name in by_name}
    unions = {name: union([a for a, b in spans if b > a],
                          [b for a, b in spans if b > a])
              for name, spans in by_name.items()}
    for programs in events["device_programs"].values():
        for name, s, d in programs:
            lo, hi = max(s, w0), min(s + d, w1)
            if hi <= lo:
                continue
            program_ns[name] = program_ns.get(name, 0) + hi - lo
            for span, (s_lo, s_hi) in unions.items():
                i = int(np.searchsorted(s_lo, lo, side="right")) - 1
                if i >= 0 and lo < s_hi[i]:
                    table = in_span_ns[span]
                    table[name] = table.get(name, 0) + hi - lo

    def rank(table: dict[str, int]) -> list:
        return [[n, v / 1e9 / chips] for n, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:top] if v > 0]

    return {"busy_s": busy_ns / 1e9 / chips, "window_s": (w1 - w0) / 1e9,
            "device_ops": rank(program_ns), "idle_gaps": rank(gap_ns),
            "programs_in_spans": {
                span: {n: v / 1e9 / chips for n, v in table.items()}
                for span, table in in_span_ns.items()}}
