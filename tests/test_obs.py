"""Observability plane: spans, histograms, exposition, JAX hooks, lint.

The ISSUE-4 acceptance stories:
- ONE deterministic trace_id covers spans from ≥2 distinct processes
  (proposer/follower validators, and a serving node + a DAS light node
  over real HTTP), reconstructed by tools/timeline.py;
- Registry timers are log-spaced bucketed histograms whose quantile
  estimates sit within a bucket width of numpy.percentile;
- the Prometheus page parses line-by-line (HELP/TYPE per family,
  histogram buckets cumulative, the max as a separate gauge — no
  summary type left);
- the jitted-pipeline compile counter increments exactly once per
  `jitted_pipeline(k)` cache miss, and the compile-vs-execute split is
  served on /metrics of BOTH HTTP services;
- no library module calls print (the structured-logger lint gate, same
  pattern as PR 3's urlopen gate).
"""

import json
import os
import re
import resource
import sys
import threading
import urllib.request

import numpy as np
import pytest

from celestia_app_tpu import obs
from celestia_app_tpu.utils import telemetry

sys.path.insert(0, os.path.dirname(__file__))
from test_consensus_multinode import CHAIN, _network  # noqa: E402


# ---------------------------------------------------------------------------
# histograms + exposition
# ---------------------------------------------------------------------------


def test_histogram_quantiles_within_a_bucket_width_of_numpy():
    reg = telemetry.Registry()
    rng = np.random.default_rng(7)
    values = rng.lognormal(mean=-6.0, sigma=1.5, size=4000)
    for v in values:
        reg.observe("lat", float(v))
    timer = reg.snapshot()["timers"]["lat"]
    assert timer["count"] == len(values)
    for q, key in ((50, "p50_s"), (95, "p95_s"), (99, "p99_s")):
        true = float(np.percentile(values, q))
        # the containing bucket of the TRUE percentile bounds the error
        import bisect

        i = bisect.bisect_left(telemetry.BUCKET_BOUNDS, true)
        lo = telemetry.BUCKET_BOUNDS[i - 1] if i > 0 else 0.0
        hi = telemetry.BUCKET_BOUNDS[min(i, len(telemetry.BUCKET_BOUNDS) - 1)]
        assert abs(timer[key] - true) <= (hi - lo) + 1e-12, (
            q, timer[key], true, lo, hi,
        )


def test_measure_since_source_compatible_and_labels():
    """Old call sites (name, t0) keep working; snapshot keeps the seed
    keys (count/total_s/max_s/last_s/avg_s) and adds quantiles."""
    import time

    reg = telemetry.Registry()
    t0 = time.perf_counter()
    dt = reg.measure_since("op", t0)
    assert dt >= 0.0
    t = reg.snapshot()["timers"]["op"]
    for key in ("count", "total_s", "max_s", "last_s", "avg_s",
                "p50_s", "p95_s", "p99_s"):
        assert key in t
    reg.incr("reqs", labels={"peer": "a"})
    reg.incr("reqs", 2, labels={"peer": "b"})
    reg.observe("lat", 0.01, labels={"peer": "a"})
    snap = reg.snapshot()
    assert snap["counters"]['reqs{peer="a"}'] == 1
    assert snap["counters"]['reqs{peer="b"}'] == 2
    assert snap["timers"]['lat{peer="a"}']["count"] == 1


_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? (-?[0-9]+(\.[0-9]+)?'
    r'([eE][+-]?[0-9]+)?|\+Inf|NaN)$'
)


def test_prometheus_exposition_parses_and_max_is_a_gauge():
    reg = telemetry.Registry()
    reg.incr("hits", 3)
    reg.incr("reqs", 1, labels={"peer": "val1"})
    reg.gauge("depth", 4.5)
    for v in (0.001, 0.002, 0.004, 0.5):
        reg.observe("lat", v)
    reg.observe("lat", 0.01, labels={"peer": "val1"})
    page = reg.prometheus()
    typed: dict[str, str] = {}
    helped: set[str] = set()
    for line in page.strip().splitlines():
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            typed[name] = kind
            continue
        assert _SAMPLE_RE.match(line), f"unparseable sample line: {line!r}"
    # every family has HELP and TYPE; nothing is a summary anymore
    assert set(typed) == helped
    assert "summary" not in typed.values()
    assert typed["celestia_lat_seconds"] == "histogram"
    # the nonstandard max lives in its OWN gauge family, not inside the
    # histogram (promtool-style parsers reject unknown suffixes there)
    assert typed["celestia_lat_seconds_max"] == "gauge"
    # buckets are cumulative and capped by the +Inf bucket == _count
    unlabeled = [
        line for line in page.splitlines()
        if line.startswith("celestia_lat_seconds_bucket{le=")
    ]
    counts = [int(line.rsplit(" ", 1)[1]) for line in unlabeled]
    assert counts == sorted(counts)
    inf = next(line for line in page.splitlines()
               if line.startswith('celestia_lat_seconds_bucket{le="+Inf"}'))
    count_line = next(line for line in page.splitlines()
                      if line.startswith("celestia_lat_seconds_count "))
    assert inf.rsplit(" ", 1)[1] == count_line.rsplit(" ", 1)[1] == "4"
    # labeled series share the family and carry their labels + le
    assert 'celestia_lat_seconds_bucket{peer="val1",le="+Inf"} 1' in page
    assert 'celestia_reqs_total{peer="val1"} 1' in page


# ---------------------------------------------------------------------------
# trace tables: bisect resume
# ---------------------------------------------------------------------------


def test_trace_tables_bisect_read_after_ring_trim():
    tt = telemetry.TraceTables()
    tt.MAX_ROWS = 100
    for i in range(250):
        tt.write("t", v=i)
    got = tt.read("t", since_index=200, limit=10)
    assert [r["_index"] for r in got] == list(range(200, 210))
    # the ring trimmed the front: a stale resume point lands on the
    # oldest surviving row, not on a full-table scan's phantom
    assert tt.read("t")[0]["_index"] == 150
    assert tt.read("t", since_index=500) == []
    assert len(tt.read("t", since_index=0, limit=1000)) == 100


def test_trace_tables_trim_follows_a_bound_lowered_later_and_a_reset():
    tt = telemetry.TraceTables()
    for i in range(50):
        tt.write("t", v=i)
    tt.MAX_ROWS = 10          # sim/engine.py lowers it on a live instance
    tt.write("t", v=50)
    rows = tt.read("t")
    assert [r["_index"] for r in rows] == list(range(41, 51))
    assert rows[-1]["v"] == 50
    tt.reset()
    tt.write("t", v=0)
    assert [r["_index"] for r in tt.read("t")] == [0]


# ---------------------------------------------------------------------------
# spans: nesting, gating, cross-process correlation
# ---------------------------------------------------------------------------


def test_span_nesting_and_deterministic_trace_id():
    tt = telemetry.TraceTables()
    tid = obs.trace_id_for(CHAIN, 7)
    assert tid == obs.trace_id_for(CHAIN, 7)  # deterministic
    assert tid != obs.trace_id_for(CHAIN, 8)
    with obs.span("root", traces=tt, trace_id=tid, height=7) as sp:
        with obs.span("child", k=4):
            pass
        sp.set(extra=1)
    rows = tt.read("spans")
    child, root = rows[0], rows[1]
    assert root["name"] == "root" and root["parent_id"] is None
    assert child["parent_id"] == root["span_id"]
    assert child["trace_id"] == root["trace_id"] == tid
    assert root["extra"] == 1 and root["height"] == 7
    assert root["dur_ms"] >= 0.0


def test_explicit_cross_trace_span_roots_instead_of_orphaning():
    """A span opened with an explicit trace_id DIFFERENT from the active
    parent's (blocksync pulling another height under a reactor.round
    span) must root in its own trace — a cross-trace parent edge would
    orphan it in per-trace merges."""
    tt = telemetry.TraceTables()
    tid1, tid2 = obs.trace_id_for(CHAIN, 1), obs.trace_id_for(CHAIN, 2)
    with obs.span("round", traces=tt, trace_id=tid1):
        with obs.span("blocksync.pull", trace_id=tid2):
            pass
    pull = tt.read("spans")[0]
    assert pull["trace_id"] == tid2
    assert pull["parent_id"] is None


def test_spans_disabled_by_gate():
    tt = telemetry.TraceTables()
    obs.set_enabled(False)
    try:
        with obs.span("root", traces=tt) as sp:
            sp.set(a=1)
    finally:
        obs.set_enabled(None)
    assert tt.read("spans") == []


def test_one_trace_id_spans_proposer_and_follower(tmp_path):
    """A 2-validator in-process devnet: the proposer's prepare span and
    the follower's process/apply spans carry the SAME deterministic
    trace id, merged by tools/timeline."""
    from celestia_app_tpu.tools import timeline

    net, _signer, _privs = _network(tmp_path, n=2, with_disk=False)
    blk, cert = net.produce_height(t=1_700_000_010.0)
    assert blk is not None and cert is not None
    tid = obs.trace_id_for(CHAIN, 1)
    rows_by_node = {
        n.name: n.app.traces.read("spans") for n in net.nodes
    }
    merged = timeline.merge_spans(rows_by_node)
    assert tid in merged
    trace = merged[tid]
    nodes = {r["node"] for r in trace}
    assert len(nodes) == 2, f"trace must span both validators: {nodes}"
    names = {r["name"] for r in trace}
    assert "prepare_proposal" in names  # the proposer's root
    assert "apply" in names             # every validator's commit path
    assert "wal.append" not in names or True  # wal only with disk homes
    assert timeline.heights_of(merged)[1] == tid


# ---------------------------------------------------------------------------
# the DAS round-trip: serving node + light node over real HTTP
# ---------------------------------------------------------------------------


def test_das_sample_roundtrip_joins_the_block_trace(tmp_path):
    """Acceptance: one deterministic trace_id covers spans from two
    distinct processes' planes — the serving/proposing node (scraped
    over HTTP /trace/spans) and a DAS light node — reconstructed by
    tools/timeline.py; the served sample span is REMOTE-PARENTED to the
    sampler's fetch span via the X-Celestia-Trace header."""
    from celestia_app_tpu.chain import light
    from celestia_app_tpu.chain.tx import MsgSend
    from celestia_app_tpu.das.checkpoint import CheckpointStore
    from celestia_app_tpu.das.daser import DASer, DASerConfig
    from celestia_app_tpu.service.server import NodeService
    from celestia_app_tpu.tools import timeline

    net, signer, privs = _network(tmp_path, with_disk=True)
    a0 = privs[0].public_key().address()
    a1 = privs[1].public_key().address()
    tx = signer.create_tx(a0, [MsgSend(a0, a1, 100)],
                          fee=2000, gas_limit=100_000)
    assert net.broadcast_tx(tx.encode())
    blk, cert = net.produce_height(t=1_700_000_010.0)
    assert blk is not None and cert is not None

    node = net.nodes[0]
    svc = NodeService(node, port=0)
    svc.serve_background()
    url = f"http://127.0.0.1:{svc.port}"
    try:
        trust = light.TrustedState(
            height=0, header_hash=b"",
            validators={n.address: n.priv.public_key().compressed
                        for n in net.nodes},
            powers={n.address: 10 for n in net.nodes},
        )
        daser = DASer(
            [url], light.LightClient(CHAIN, trust),
            CheckpointStore(str(tmp_path / "cp" / "cp.json")),
            cfg=DASerConfig(samples_per_header=4, workers=1, retries=2,
                            backoff=0.01),
            rng=np.random.default_rng(3), name="light0",
        )
        out = daser.sync()
        assert out["halted"] is None and out["sampled"] == [1]

        # the serving node's spans over REAL HTTP; the light node's and
        # the other validators' (the height-1 proposer is rotation-
        # dependent) in-process — one merge call covers both transports.
        # NOTE: LocalNetwork sorts its nodes by address, so the served
        # node's .name may collide with a peer's — label the HTTP scrape
        # distinctly.
        rows_by_node = {
            "serving-http": timeline.fetch_node_spans(url),
            "light0": daser.traces.read("spans"),
        }
        for n in net.nodes[1:]:
            rows_by_node[n.name] = n.app.traces.read("spans")
        tid = obs.trace_id_for(CHAIN, 1)
        merged = timeline.merge_spans(rows_by_node)
        assert tid in merged
        trace = merged[tid]
        assert {"serving-http", "light0"} <= {r["node"] for r in trace}
        by_name = {}
        for r in trace:
            by_name.setdefault(r["name"], []).append(r)
        assert "prepare_proposal" in by_name   # the proposer's side
        assert "das.sample_height" in by_name  # the light node's side
        # header propagation: the serve span's remote parent is one of
        # the light node's fetch spans
        fetch_ids = {r["span_id"] for r in by_name["das.fetch_cells"]}
        serve_parents = {r["parent_id"] for r in by_name["das.serve_sample"]}
        assert serve_parents & fetch_ids, (serve_parents, fetch_ids)
        # and the waterfall renders both processes in one timeline
        text = timeline.render_waterfall(trace)
        assert "das.serve_sample" in text and "das.sample_height" in text
        assert "[serving-http]" in text and "[light0]" in text
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# span totals, the profiler's clock, full collections (ISSUE 26)
# ---------------------------------------------------------------------------

TOTAL_FAMILIES = ("obs.span_n", "obs.span_wall_us", "obs.span_cpu_us")
# the host's account of a span (ISSUE 38): one getrusage(RUSAGE_THREAD) at
# each end beside the thread-CPU clock, on a kernel that keeps the account
ACCOUNT_FAMILIES = ("obs.span_sys_us", "obs.span_minflt", "obs.span_majflt",
                    "obs.span_vcsw", "obs.span_icsw")
ACCOUNT_ROW_FIELDS = ("sys_ms", "minflt", "majflt", "vcsw", "icsw")
# the kernel samples the user/system split at its tick (1-10 ms by its HZ),
# so one span's sys_ms is good to a tick; cpu_ms is the exact clock's
SYS_TICK_MS = 10.0
# the benchmark's own span names (benchmark/generators): a program span of
# one of these names would be added into the benchmark's row on the trace
BENCHMARK_SPAN_NAMES = {"window", "produce_block", "broadcast_txs",
                        "first_sample", "warm_wait", "sample_many",
                        "namespaces_many", "catchup_request",
                        "light_header"}


def _totals(name: str) -> tuple[int, int, int]:
    counters = telemetry.snapshot()["counters"]
    return tuple(counters.get(f'{family}{{name="{name}"}}', 0)
                 for family in TOTAL_FAMILIES)


@pytest.mark.parametrize("family", TOTAL_FAMILIES)
def test_span_totals_reach_the_counters_and_obey_the_gate(family):
    import time

    def level():
        return telemetry.snapshot()["counters"].get(
            f'{family}{{name="totals.probe"}}', 0)

    tt = telemetry.TraceTables()
    before = level()
    for _ in range(3):
        with obs.span("totals.probe", traces=tt):
            t_end = time.perf_counter() + 0.002
            while time.perf_counter() < t_end:  # burn CPU: both clocks move
                pass
    grown = level() - before
    # three occurrences; the loop ends by the wall clock, so 3 x 2 ms is
    # the least the wall total can grow; what the CPU clock saw of it is
    # the scheduler's to say under other workers
    if family == "obs.span_n":
        assert grown == 3
    elif family == "obs.span_wall_us":
        assert grown >= 6_000
    else:
        assert grown > 0
    row = tt.read("spans")[-1]
    assert 0 < row["cpu_ms"] <= row["dur_ms"] + 1.0
    obs.set_enabled(False)
    try:
        with obs.span("totals.probe", traces=tt):
            pass
    finally:
        obs.set_enabled(None)
    assert level() - before == grown   # the gate stops the totals too


def test_full_collection_is_a_span_in_the_totals():
    import gc

    n0, wall0, _cpu0 = _totals("gc.full")
    gc.collect(0)                       # a young collection: not counted
    assert _totals("gc.full")[0] == n0
    gc.collect()
    n1, wall1, _cpu1 = _totals("gc.full")
    assert n1 == n0 + 1 and wall1 > wall0
    obs.set_enabled(False)
    try:
        gc.collect()
    finally:
        obs.set_enabled(None)
    assert _totals("gc.full")[0] == n1


def _account(name: str) -> dict[str, int]:
    counters = telemetry.snapshot()["counters"]
    return {family: counters.get(f'{family}{{name="{name}"}}', 0)
            for family in ACCOUNT_FAMILIES}


def _fresh_32mib_faults() -> int:
    """The least minor faults a first-touched 32 MiB buffer must book:
    8,192 pages of 4 KiB — 16 of 2 MiB where transparent huge pages are
    on for every mapping."""
    try:
        with open("/sys/kernel/mm/transparent_hugepage/enabled",
                  encoding="ascii") as f:
            always = "[always]" in f.read()
    except OSError:
        always = False
    return 8 if always else 8_000


FRESH_32MIB_FAULTS = _fresh_32mib_faults()


class _FreshPagesWhenCollected:
    """Garbage only a full collection finds, whose finalizer first-touches
    a 32 MiB buffer: page faults INSIDE the collection."""

    def __init__(self):
        self.me = self

    def __del__(self):
        bytearray(32 << 20)


def _run_with_deadline(fn, seconds=20.0):
    """`fn` on a thread of its own; False if it is still running at the
    deadline (a thread that waits for a lock it holds never returns)."""
    import threading

    t = threading.Thread(target=fn, daemon=True)
    t.start()
    t.join(seconds)
    return not t.is_alive()


@pytest.mark.parametrize("lock_of", [
    lambda: __import__("celestia_app_tpu.obs.spans",
                       fromlist=["spans"])._totals_lock,
    lambda: __import__("celestia_app_tpu.obs.spans",
                       fromlist=["spans"])._publish_lock,
    lambda: telemetry._global._lock,
], ids=["totals_lock", "publish_lock", "registry_lock"])
def test_full_collection_under_a_held_lock_does_not_deadlock(lock_of):
    """The collector runs inside any allocation, so also on a thread that
    is inside `record_total`, the scrape's collector or the registry with
    its lock held: the gc hook may take none of them."""
    import gc

    from celestia_app_tpu.obs import spans

    lock = lock_of()
    n0 = _totals("gc.full")[0]
    flt0 = _account("gc.full")["obs.span_minflt"]

    def collect_while_holding():
        _FreshPagesWhenCollected()
        with lock:
            gc.collect()

    assert _run_with_deadline(collect_while_holding), \
        "the gc hook waited for a lock its own thread holds"
    assert _totals("gc.full")[0] == n0 + 1
    # the wider tuple is still one store: the collection's own account
    # (its finalizer's fresh 32 MiB) lands under gc.full with no lock —
    # on a kernel that keeps one, and stays absent elsewhere
    flt = _account("gc.full")["obs.span_minflt"] - flt0
    assert flt >= FRESH_32MIB_FAULTS if spans._thread_usage else flt == 0


def test_scrapes_and_spans_survive_collections_at_every_allocation():
    """Scrape and finish spans in a loop with the collector's thresholds
    at 1 (a collection at nearly every allocation, full ones among them)
    while another thread forces full collections: every scrape returns
    and no full collection is lost."""
    import gc
    import threading

    tt = telemetry.TraceTables()
    n0 = _totals("gc.full")[0]
    stop = threading.Event()
    forced = [0]

    def collector():
        while not stop.is_set():
            gc.collect()
            forced[0] += 1

    def scrape_and_span():
        for i in range(300):
            with obs.span(f"gc.storm.{i % 7}", traces=tt):
                pass
            telemetry.snapshot()

    old = gc.get_threshold()
    side = threading.Thread(target=collector, daemon=True)
    gc.set_threshold(1, 1, 1)
    try:
        side.start()
        done = _run_with_deadline(scrape_and_span, 60.0)
    finally:
        gc.set_threshold(*old)
        stop.set()
        side.join(20.0)
    assert done, "a scrape or a span exit never returned"
    assert not side.is_alive()
    assert _totals("gc.full")[0] - n0 >= forced[0] > 0


@pytest.mark.backend
@pytest.mark.parametrize("direction", ["h2d", "d2h"])
def test_a_transfer_that_raises_closes_its_clocks_and_counts_nothing(
        direction, monkeypatch):
    import jax

    from celestia_app_tpu.obs import spans, xfer

    closed = []
    real_close = spans.close
    monkeypatch.setattr(spans, "close",
                        lambda opened: closed.append(1) or real_close(opened))

    def boom(*_a, **_k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(
        jax, "device_put" if direction == "h2d" else "device_get", boom)
    name = f"xfer.{direction}:raise.probe"
    before, calls = _totals(name), xfer.totals()[f"{direction}_calls"]
    with pytest.raises(RuntimeError, match="device lost"):
        if direction == "h2d":
            xfer.to_device(b"abc", "raise.probe")
        else:
            xfer.to_host(b"abc", "raise.probe")
    assert closed == [1]               # the annotation was let go of
    assert _totals(name) == before     # and nothing was recorded
    assert xfer.totals()[f"{direction}_calls"] == calls


@pytest.mark.backend
def test_span_annotation_lands_in_a_profiler_trace(tmp_path):
    """With jax loaded, a span holds a TraceAnnotation for its lifetime:
    under a profiler session it lies on a host line of the same trace as
    the device's operations, under the benchmark's prefix."""
    import glob

    import jax
    import jax.numpy as jnp

    from celestia_app_tpu.obs import spans

    assert spans.ANNOTATION_PREFIX == "bench."  # what xplane.py keeps
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("annotated.probe", traces=telemetry.TraceTables()):
            jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert len(found) == 1
    data = jax.profiler.ProfileData.from_file(found[0])
    host = [p for p in data.planes if p.name.startswith("/host:")]
    if not host:
        pytest.skip("this backend's profiler writes no host plane")
    names = {e.name for p in host for line in p.lines for e in line.events}
    assert "bench.annotated.probe" in names


# -- the host's account of a span (ISSUE 38) ---------------------------------

def _kernel_keeps_the_account() -> bool:
    """The program's own probe (`spans._host_account`): Linux's
    RUSAGE_THREAD, and a process whose own fault count is not 0 — gVisor
    (the chips' hosts) has the attribute and counts nothing."""
    from celestia_app_tpu.obs import spans

    return spans._thread_usage is not None


needs_host_account = pytest.mark.skipif(
    not _kernel_keeps_the_account(),
    reason="this kernel keeps no per-thread account (non-Linux, gVisor)")


@pytest.fixture(scope="module")
def accounted():
    """One parent span over a child that first-touches 32 MiB and a
    sibling that does nothing; then a span that waits while a second
    thread's span first-touches 32 MiB of its own. Counter growth per
    name and the rows."""
    import time

    names = ("acct.parent", "acct.touch", "acct.quiet", "acct.waiter",
             "acct.other_thread", "acct.sleeps")
    before = {n: _account(n) for n in names}
    tt = telemetry.TraceTables()
    with obs.span("acct.parent", traces=tt):
        with obs.span("acct.touch", traces=tt):
            bytearray(32 << 20)
        with obs.span("acct.quiet", traces=tt):
            pass

    def other():
        with obs.span("acct.other_thread", traces=tt):
            bytearray(32 << 20)

    t = threading.Thread(target=other)
    with obs.span("acct.waiter", traces=tt):
        t.start()
        t.join(60)
    assert not t.is_alive()
    with obs.span("acct.sleeps", traces=tt):
        for _ in range(3):
            time.sleep(0.002)
    grown = {n: {f: v - before[n][f] for f, v in _account(n).items()}
             for n in names}
    return grown, {r["name"]: r for r in tt.read("spans")}


# (span, family, least, most) of the counters' growth; None = no bound
ACCOUNT_CASES = [
    # the faults and their system time land under the span that touched
    ("acct.touch", "obs.span_minflt", FRESH_32MIB_FAULTS, None),
    ("acct.touch", "obs.span_sys_us", 1, None),
    # ... not under a sibling's
    ("acct.quiet", "obs.span_minflt", 0, 0),
    ("acct.quiet", "obs.span_sys_us", 0, 0),
    # a span on a second thread books that thread's usage only
    ("acct.other_thread", "obs.span_minflt", FRESH_32MIB_FAULTS, None),
    ("acct.waiter", "obs.span_minflt", 0, 200),
    # waiting for the other thread is (at least) one voluntary switch
    ("acct.waiter", "obs.span_vcsw", 1, None),
    ("acct.sleeps", "obs.span_vcsw", 3, None),
]


@needs_host_account
@pytest.mark.parametrize(
    "name,family,least,most", ACCOUNT_CASES,
    ids=[f"{n}-{f.rsplit('_', 1)[1]}" for n, f, _l, _m in ACCOUNT_CASES])
def test_span_account_lands_under_the_span_that_paid(
        accounted, name, family, least, most):
    grown = accounted[0][name][family]
    assert grown >= least, (name, accounted[0][name])
    if most is not None:
        assert grown <= most, (name, accounted[0][name])


@needs_host_account
@pytest.mark.parametrize("family", ["obs.span_minflt", "obs.span_sys_us",
                                    "obs.span_vcsw", "obs.span_icsw",
                                    "obs.span_majflt"])
def test_a_childs_account_is_inside_its_parents(accounted, family):
    grown = accounted[0]
    assert grown["acct.parent"][family] >= \
        grown["acct.touch"][family] + grown["acct.quiet"][family]


@needs_host_account
@pytest.mark.parametrize("field", ACCOUNT_ROW_FIELDS)
def test_row_carries_an_account_field_only_when_non_zero(accounted, field):
    rows = accounted[1]
    # a quiet span's row is as long as it was before the account existed
    assert field not in rows["acct.quiet"], rows["acct.quiet"]
    loud = {"sys_ms": "acct.touch", "minflt": "acct.touch",
            "vcsw": "acct.sleeps"}.get(field)
    if loud is not None:
        assert rows[loud][field] > 0
        # the row and the totals say the same of the one occurrence
        family = ACCOUNT_FAMILIES[ACCOUNT_ROW_FIELDS.index(field)]
        grown = accounted[0][loud][family]
        assert rows[loud][field] == (
            pytest.approx(grown / 1000, abs=0.002) if field == "sys_ms"
            else grown)
    for row in rows.values():
        assert row.get(field, 1) != 0     # never written as a zero


@needs_host_account
def test_span_cpu_is_the_exact_clock_and_system_time_lies_inside_it():
    """What `obs.span_cpu_us` means did not change, nor how finely it is
    read: `time.thread_time_ns`, to the nanosecond (user + system of
    RUSAGE_THREAD is the same run time only as of the scheduler's last
    tick, so it does not stand in for the clock). The system half comes
    from the account and is good to a tick."""
    import time

    from celestia_app_tpu.obs import spans

    c0 = time.thread_time_ns()
    opened = spans.begin("acct.cpu")
    inner0 = time.thread_time_ns()
    bytearray(32 << 20)                        # system time
    t_end = time.perf_counter() + 0.05
    while time.perf_counter() < t_end:         # user time
        pass
    inner = time.thread_time_ns() - inner0
    wall_ns, cpu_ns, sys_ns, *_counts = spans.close(opened)
    outer = time.thread_time_ns() - c0
    assert inner <= cpu_ns <= outer            # no tick of slack
    assert 0 < sys_ns <= cpu_ns + SYS_TICK_MS * 1e6
    assert cpu_ns <= wall_ns


@pytest.mark.parametrize("field", ("cpu",) + ACCOUNT_ROW_FIELDS)
def test_where_the_kernel_keeps_no_account_the_fields_stay_absent(
        field, monkeypatch):
    """Non-Linux and gVisor: the thread-CPU clock as ever, and the five
    new families and row fields absent — not 0."""
    import time

    from celestia_app_tpu.obs import spans

    monkeypatch.setattr(spans, "_thread_usage", None)
    name = f"acct.no_account.{field}"
    tt = telemetry.TraceTables()
    with obs.span(name, traces=tt):
        bytearray(32 << 20)
        t_end = time.perf_counter() + 0.005
        while time.perf_counter() < t_end:
            pass
    row = tt.read("spans")[-1]
    counters = telemetry.snapshot()["counters"]
    if field == "cpu":
        assert row["cpu_ms"] > 0
        assert counters[f'obs.span_cpu_us{{name="{name}"}}'] > 0
    else:
        family = ACCOUNT_FAMILIES[ACCOUNT_ROW_FIELDS.index(field)]
        assert field not in row
        assert f'{family}{{name="{name}"}}' not in counters


@pytest.mark.parametrize("host,keeps", [
    ("linux", True), ("gvisor", False), ("no-rusage-thread", False)])
def test_the_account_is_read_only_on_a_kernel_that_counts(
        host, keeps, monkeypatch):
    """The one-time probe: gVisor has RUSAGE_THREAD and fills times only,
    so the attribute alone would publish false zeros there. No
    interpreter starts without a page fault: a process whose own count
    reads 0 runs on a kernel that does not count."""
    from types import SimpleNamespace

    from celestia_app_tpu.obs import spans

    if host == "no-rusage-thread":
        monkeypatch.delattr(resource, "RUSAGE_THREAD", raising=False)
    else:
        monkeypatch.setattr(resource, "RUSAGE_THREAD", 1, raising=False)
        faults = 1079 if host == "linux" else 0
        monkeypatch.setattr(
            resource, "getrusage",
            lambda who: SimpleNamespace(ru_minflt=faults, who=who))
    usage = spans._host_account()
    assert (usage is not None) is keeps
    if keeps:  # and what it reads is the calling THREAD's
        assert usage().who == resource.RUSAGE_THREAD


@needs_host_account
def test_begin_and_close_read_each_clock_and_the_account_once(monkeypatch):
    """One rusage read and one thread-CPU clock read at each end."""
    import time

    from celestia_app_tpu.obs import spans

    reads = []
    usage, clock = spans._thread_usage, time.thread_time_ns
    monkeypatch.setattr(spans, "_thread_usage",
                        lambda: reads.append("usage") or usage())
    monkeypatch.setattr(time, "thread_time_ns",
                        lambda: reads.append("cpu") or clock())
    with obs.span("acct.one_read", traces=telemetry.TraceTables()):
        pass
    # the CPU clock brackets the account, the wall clock both
    assert reads == ["cpu", "usage", "usage", "cpu"]


@pytest.mark.parametrize("row,says", [
    ({"cpu_ms": 37.94, "sys_ms": 20.81, "minflt": 8193, "vcsw": 3,
      "icsw": 1}, "(cpu 37.9 sys 20.8 flt 8193 sw 3v+1i)"),
    ({"cpu_ms": 0.4, "majflt": 2}, "(cpu 0.4 flt 0+2maj)"),
    ({"cpu_ms": 1.2, "vcsw": 5}, "(cpu 1.2 sw 5v+0i)"),
    ({"cpu_ms": 1.2}, None),
], ids=["loud", "major", "switches", "quiet"])
def test_timeline_appends_the_hosts_account_to_a_row_that_carries_one(
        row, says):
    from celestia_app_tpu.tools import timeline

    text = timeline.render_waterfall([{
        "trace_id": "t", "span_id": "s1", "parent_id": None,
        "name": "square.build", "start_unix": 1.0, "dur_ms": 40.0, **row}])
    line = text.splitlines()[-1]
    if says is None:
        assert line.endswith("| square.build")
    else:
        assert line.endswith("| square.build  " + says)


# -- the GIL sampler as a window counter (ISSUE 38) --------------------------


def _no_sampler_running(gil, seconds=2.0):
    import time

    gil.stop_all()
    deadline = time.time() + seconds
    while time.time() < deadline and _sampler_threads():
        time.sleep(0.01)


def _sampler_threads() -> list[str]:
    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith("gil-sampler-") and t.is_alive())


def _gil_window(seconds: float) -> tuple[int, int]:
    """(samples, oversleep us) the samplers added in `seconds`."""
    import time

    def levels():
        c = telemetry.snapshot()["counters"]
        return c.get("gil.samples", 0), c.get("gil.oversleep_us", 0)

    n0, us0 = levels()
    time.sleep(seconds)
    n1, us1 = levels()
    return n1 - n0, us1 - us0


@pytest.fixture
def tmp_app(tmp_path):
    from celestia_app_tpu.chain.app import App

    app = App(chain_id="gil38", engine="host", data_dir=str(tmp_path / "d"))
    try:
        yield app
    finally:
        app.close()


@pytest.mark.parametrize("first,built,running", [
    (None, 3, ["node"]),      # however many nodes: one sampler
    ("das", 2, ["das"]),      # none beside a service's that started first
    ("node", 2, ["node"]),    # the node service's own label: the same one
    (None, 0, []),            # no node, no sampler
], ids=["nodes-only", "service-first", "node-service-first", "no-node"])
def test_a_process_that_builds_nodes_runs_one_gil_sampler(
        tmp_app, first, built, running):
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.obs import gil

    _no_sampler_running(gil)
    try:
        if first is not None:
            assert gil.start(first) is True
        nodes = [Node(tmp_app) for _ in range(built)]
        assert len(nodes) == built
        assert gil.running() == running
        assert _sampler_threads() == [f"gil-sampler-{s}" for s in running]
    finally:
        _no_sampler_running(gil)


def test_node_starts_no_sampler_with_observability_off(tmp_app):
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.obs import gil

    _no_sampler_running(gil)
    obs.set_enabled(False)
    try:
        Node(tmp_app)
        assert gil.running() == [] and _sampler_threads() == []
    finally:
        obs.set_enabled(None)


@pytest.mark.parametrize("key", ["gil.samples", "gil.oversleep_us"])
def test_sampler_publishes_unlabelled_window_counters(key):
    """A window reads counters, and a metric file names ONE key whichever
    service the process runs: no `service` label on these two."""
    from celestia_app_tpu.obs import gil

    _no_sampler_running(gil)
    try:
        assert gil.start("t-window") is True
        samples, _us = _gil_window(gil.INTERVAL_S * 4)
        assert samples >= 2
        counters = telemetry.snapshot()["counters"]
        assert key in counters
        assert not [k for k in counters if k.startswith(key + "{")]
        # the operator's histogram and gauge stay, labelled (FORMATS 22)
        snap = telemetry.snapshot()
        assert 'gil.oversleep{service="t-window"}' in snap["timers"]
        assert 'gil.pressure{service="t-window"}' in snap["gauges"]
    finally:
        _no_sampler_running(gil)


def test_window_mean_oversleep_rises_under_busy_threads():
    """oversleep_us / samples over a window is the mean time a thread
    that wanted the interpreter waited for it: four busy-looping threads
    at CPython's 5 ms switch interval push it up by several ms."""
    import time

    from celestia_app_tpu.obs import gil

    _no_sampler_running(gil)
    stop = threading.Event()

    def spin(deadline):
        x = 0
        while not stop.is_set() and time.perf_counter() < deadline:
            x += 1

    try:
        assert gil.start("t-busy") is True
        time.sleep(gil.INTERVAL_S)           # past the first, partial wake
        n_idle, us_idle = _gil_window(0.6)
        # their own time limit: the spinners stop whatever the test does
        deadline = time.perf_counter() + 20.0
        spinners = [threading.Thread(target=spin, args=(deadline,),
                                     daemon=True) for _ in range(4)]
        for t in spinners:
            t.start()
        n_busy, us_busy = _gil_window(1.5)
        stop.set()
        for t in spinners:
            t.join(10)
        assert not any(t.is_alive() for t in spinners)
        assert n_idle >= 3 and n_busy >= 3, (n_idle, n_busy)
        assert us_busy / n_busy - us_idle / n_idle >= 5_000, (
            (n_idle, us_idle), (n_busy, us_busy))
    finally:
        stop.set()
        _no_sampler_running(gil)


def test_no_program_span_is_named_like_a_benchmark_span():
    """Every literal span name in the package (plus the transfer and
    collection names) against the benchmark's seven."""
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "celestia_app_tpu")
    opener = re.compile(
        r"(?:obs|spans)\.(?:span|resume)\(\s*(?:ctx,\s*)?[\"']([^\"']+)[\"']")
    names = {"gc.full"}
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn), encoding="utf-8") as f:
                    names |= set(opener.findall(f.read()))
    assert len(names) > 30 and "block.produce" in names \
        and "da.extend.run" in names
    assert not names & BENCHMARK_SPAN_NAMES
    assert not {n for n in names if n.startswith(("xfer.h2d:", "xfer.d2h:"))}
    assert all(not n.startswith("bench.") for n in names)


# -- nesting on the block path, at an 8x8 square on the CPU backend ---------

@pytest.fixture(scope="module")
def device_block(tmp_path_factory):
    from obs_drive import drive

    return drive("device", str(tmp_path_factory.mktemp("obs26") / "data"))


# (span, its parent, how often in one block's loop); None = a root
BLOCK_PATH = [
    ("node.broadcast_txs", None, 1),
    ("admission.prevalidate", "node.broadcast_txs", 1),
    ("admission.commitments", "admission.prevalidate", 1),
    ("admission.commit_pack", "admission.commitments", 1),
    ("admission.commit_dispatch", "admission.commitments", 1),
    ("admission.commit_fold", "admission.commitments", 1),
    ("admission.check_txs", "node.broadcast_txs", 1),
    ("block.produce", None, 1),
    ("prepare_proposal", "block.produce", 1),
    ("prepare.split_txs", "prepare_proposal", 1),
    ("prepare.ante_txs", "prepare_proposal", 1),
    ("square.build", "prepare_proposal", 1),
    ("da.ods_key", "prepare_proposal", 1),
    ("da.extend_shares", "prepare_proposal", 1),
    ("da.extend.run", "da.extend_shares", 1),
    ("process_proposal", "block.produce", 1),
    ("admission.prevalidate", "process_proposal", 1),
    ("process.parse_txs", "process_proposal", 1),
    ("commitments.resolve", "process_proposal", 1),
    ("process.ante_txs", "process_proposal", 1),
    ("square.construct", "process_proposal", 1),
    ("da.ods_key", "process_proposal", 1),
    ("finalize_block", "block.produce", 1),
    ("commit", "block.produce", 1),
    ("storage.save_block", "commit", 1),
    ("storage.block.encode", "storage.save_block", 1),
    ("storage.block.put", "storage.save_block", 1),
    ("storage.save_commit", "commit", 1),
    ("pool.recheck", "block.produce", 1),
    ("da.prover_warm", None, 1),
    ("proof.levels.run", "da.prover_warm", 2),       # row and column
    ("das.entry_build", None, 1),
    ("das.app_lock_wait", "das.entry_build", 1),
    ("query.rebuild_square", "das.entry_build", 1),
    ("storage.load_block", "query.rebuild_square", 1),
    ("da.ods_key", "das.entry_build", 1),            # a hit: no extend
    ("das.serve_sample", None, 1),
    ("das.build_provers", "das.serve_sample", 1),
    ("blob.namespaces_many", None, 1),
    ("blob.ns.search", "blob.namespaces_many", 1),
    ("blob.ns.proofs", "blob.namespaces_many", 1),
    ("blob.ns.encode", "blob.namespaces_many", 1),
]


@pytest.mark.backend
@pytest.mark.parametrize("name,parent,count", BLOCK_PATH)
def test_block_path_span_appears_once_under_its_parent(
        device_block, name, parent, count):
    rows = device_block["rows"]
    by_id = {r["span_id"]: r for r in rows}
    mine = [r for r in rows if r["name"] == name
            and by_id.get(r["parent_id"], {}).get("name") == parent]
    assert len(mine) == count, [
        (r["name"], by_id.get(r["parent_id"], {}).get("name"))
        for r in rows if r["name"] == name]
    for r in mine:
        assert r["cpu_ms"] <= r["dur_ms"] + 1.0
        if parent is not None:
            assert r["parent_id"] in by_id
        children = sum(c["dur_ms"] for c in rows
                       if c["parent_id"] == r["span_id"])
        assert children <= r["dur_ms"] + 0.5, (name, children, r["dur_ms"])


@pytest.mark.backend
def test_block_path_stays_inside_the_span_budget(device_block):
    """At most 40 spans a block (its transfers included), 6 a served
    request; the warmer's wait in its queue rides its span."""
    rows = device_block["rows"]
    by_id = {r["span_id"]: r for r in rows}

    def under(root_name):
        out = []
        for r in rows:
            top = r
            while top["parent_id"] in by_id:
                top = by_id[top["parent_id"]]
            if top["name"] == root_name:
                out.append(r)
        return out

    transfers = 4   # up and down: the extend, then each of two level passes
    assert len(under("block.produce")) + len(under("da.prover_warm")) \
        + transfers <= 40
    for request in ("das.entry_build", "das.serve_sample",
                    "blob.namespaces_many"):
        assert 1 <= len(under(request)) <= 6
    warm = [r for r in rows if r["name"] == "da.prover_warm"]
    assert warm[0]["queued_ms"] >= 0.0
    assert "stages" not in [r for r in rows
                            if r["name"] == "da.extend_shares"][0]
    tid = obs.trace_id_for(device_block["chain_id"], device_block["height"])
    assert {r["trace_id"] for r in rows
            if r["name"] in ("block.produce", "da.prover_warm",
                             "das.entry_build", "das.serve_sample")} == {tid}


def test_host_engine_process_opens_spans_and_wakes_no_backend(tmp_path):
    """The span plane never imports jax: a process that opens spans, moves
    bytes through no device and runs a full collection ends without it in
    sys.modules. A host-engine validator (whose `da/dah.py` imports jax
    for its types, as every App's does) then produces, serves and reads
    with spans on and initialises no backend; `da.extend.run` and
    `proof.levels.run` are the device branch's alone."""
    import subprocess

    code = (
        "import gc, json, sys\n"
        "sys.path.insert(0, %r)\n"
        "from celestia_app_tpu import obs\n"
        "from celestia_app_tpu.utils import telemetry\n"
        "with obs.span('probe.outer'):\n"
        "    with obs.span('probe.inner'):\n"
        "        gc.collect()\n"
        "telemetry.snapshot()\n"
        "jax_after_spans = 'jax' in sys.modules\n"
        "from obs_drive import drive\n"
        "out = drive('host', %r)\n"
        "from jax._src import xla_bridge\n"
        "counters = telemetry.snapshot()['counters']\n"
        "print(json.dumps({'jax_after_spans': jax_after_spans,\n"
        "    'backends': sorted(getattr(xla_bridge, '_backends', {})),\n"
        "    'names': sorted({r['name'] for r in out['rows']}),\n"
        "    'totals': sorted(k for k in counters\n"
        "                     if k.startswith('obs.span_n'))}))\n"
    ) % (os.path.dirname(os.path.abspath(__file__)), str(tmp_path / "data"))
    env = {k: v for k, v in os.environ.items() if k != "CELESTIA_OBS"}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout.strip().split("\n")[-1])
    assert doc["jax_after_spans"] is False
    assert doc["backends"] == []
    names = set(doc["names"])
    assert {"block.produce", "da.extend_shares", "da.prover_warm",
            "blob.ns.search", "das.entry_build"} <= names
    assert "da.extend.run" not in names and "proof.levels.run" not in names
    for name in ("probe.inner", "block.produce", "gc.full"):
        assert f'obs.span_n{{name="{name}"}}' in doc["totals"]
    assert not [t for t in doc["totals"] if "xfer." in t]


@pytest.mark.backend
@pytest.mark.parametrize("scope", ["rs_extend", "nmt_leaves", "nmt_inner",
                                   "data_root"])
def test_pipeline_stages_carry_named_scopes(scope):
    """The extend + commit program keeps its name (`run`); its stages are
    told apart by scope in the lowered text, for a per-kernel reader."""
    import jax
    import jax.numpy as jnp

    from celestia_app_tpu.da import eds

    text = eds.jitted_pipeline(8).lower(
        jax.ShapeDtypeStruct((8, 8, 512), jnp.uint8)).as_text(
            debug_info=True)
    assert f"{scope}/" in text or f'"{scope}"' in text
    assert "jit_run" in text or "jit(run)" in text


# ---------------------------------------------------------------------------
# JAX hooks: compile counter + the split on /metrics of BOTH services
# ---------------------------------------------------------------------------


@pytest.mark.backend
def test_compile_counter_once_per_pipeline_cache_miss():
    import jax.numpy as jnp

    from celestia_app_tpu.da import eds

    # all assertions are RELATIVE: the registry is process-global and
    # other tests in a full run may already have compiled this bucket
    eds.jitted_pipeline.cache_clear()
    k = 4
    label = f'{{fn="eds.pipeline[{k}]"}}'

    def counts():
        snap = telemetry.snapshot()
        return (
            snap["counters"].get("jax.compilations", 0),
            snap["timers"].get(f"jax.compile{label}", {}).get("count", 0),
            snap["timers"].get(f"jax.dispatch{label}", {}).get("count", 0),
        )

    c0, comp0, exec0 = counts()
    fn = eds.jitted_pipeline(k)
    assert eds.jitted_pipeline(k) is fn  # cache hit: no new compilation
    assert counts()[0] == c0 + 1  # exactly ONE per factory cache miss
    ods = jnp.zeros((k, k, 512), dtype=jnp.uint8)
    fn(ods)
    fn(ods)
    c1, comp1, exec1 = counts()
    assert c1 == c0 + 1           # invocations never count as compiles
    assert comp1 == comp0 + 1     # first call -> the compile histogram
    assert exec1 >= exec0 + 1     # later calls -> the execute histogram
    # the collector exports backend gauges without re-initializing it
    gauges = telemetry.snapshot()["gauges"]
    assert gauges.get("jax.jit_cache_size", 0) >= 1
    assert gauges.get("jax.device_count", 0) >= 1


@pytest.mark.backend
def test_metrics_pages_serve_histograms_and_jit_split(tmp_path):
    """/metrics on BOTH HTTP services (node + validator) serves histogram
    _bucket lines and the jax compile-vs-execute split."""
    import jax.numpy as jnp

    from celestia_app_tpu.da import eds
    from celestia_app_tpu.service.server import NodeService
    from celestia_app_tpu.service.validator_server import ValidatorService

    k = 4
    fn = eds.jitted_pipeline(k)
    fn(jnp.zeros((k, k, 512), dtype=jnp.uint8))
    fn(jnp.zeros((k, k, 512), dtype=jnp.uint8))

    net, _signer, _privs = _network(tmp_path, n=1, with_disk=False)
    node = net.nodes[0]
    node_svc = NodeService(node, port=0)
    node_svc.serve_background()
    val_svc = ValidatorService(node, port=0)
    val_svc.serve_background()
    try:
        for port in (node_svc.port, val_svc.port):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics"
            ) as r:
                assert r.status == 200
                page = r.read().decode()
            assert "_bucket{le=" in page
            assert "# HELP" in page
            assert "celestia_jax_compile_seconds_bucket" in page
            assert "celestia_jax_dispatch_seconds_count" in page
            assert "celestia_jax_compilations_total" in page
        # the validator service also serves the trace pull now
        with urllib.request.urlopen(
            f"http://127.0.0.1:{val_svc.port}/trace/spans"
        ) as r:
            doc = json.loads(r.read())
        assert "rows" in doc and "tables" in doc
    finally:
        val_svc.shutdown()
        node_svc.shutdown()


def test_debug_profile_endpoint(tmp_path):
    """POST /debug/profile captures an on-demand jax.profiler trace (jax
    is loaded in the test process via conftest)."""
    from celestia_app_tpu.service.server import NodeService

    net, _signer, _privs = _network(tmp_path, n=1, with_disk=False)
    svc = NodeService(net.nodes[0], port=0)
    svc.serve_background()
    try:
        out_dir = str(tmp_path / "prof")
        req = urllib.request.Request(
            f"http://127.0.0.1:{svc.port}/debug/profile",
            data=json.dumps({"seconds": 0.05, "dir": out_dir}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            with urllib.request.urlopen(req) as r:
                doc = json.loads(r.read())
            assert doc["dir"] == out_dir and os.path.isdir(out_dir)
        except urllib.error.HTTPError as e:
            # profiler backends vary across jax builds; a clean 4xx
            # refusal (never a 500) is acceptable where capture cannot run
            assert e.code == 400, e.read()
            assert "profil" in json.loads(e.read() or b"{}").get(
                "error", "profiler"
            ) or True
        # malformed duration is a client error on the OTHER service too
        bad = urllib.request.Request(
            f"http://127.0.0.1:{svc.port}/debug/profile",
            data=json.dumps({"seconds": 1e9}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad)
        assert ei.value.code == 400
        # an unwritable dir is a 400 (never a 500) and must NOT wedge
        # the endpoint into "capture already running" forever
        blocker = tmp_path / "not-a-dir"
        blocker.write_text("x")
        bad_dir = urllib.request.Request(
            f"http://127.0.0.1:{svc.port}/debug/profile",
            data=json.dumps({"seconds": 0.01,
                             "dir": str(blocker / "sub")}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad_dir)
        assert ei.value.code == 400
        body = json.loads(ei.value.read() or b"{}").get("error", "")
        assert "already running" not in body
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# the structured logger + the print lint gate
# ---------------------------------------------------------------------------


def test_logger_levels_and_json_mode(capsys):
    from celestia_app_tpu.obs import log as obs_log

    lg = obs_log.get_logger("test.obs")
    obs_log.configure(level="warning")
    try:
        lg.info("hidden")
        lg.warning("shown", height=3)
        err = capsys.readouterr().err
        assert "hidden" not in err
        assert "[test.obs] WARNING: shown height=3" in err
        obs_log.configure(level="info", json_mode=True)
        lg.error("boom", err=ValueError("x"))
        line = capsys.readouterr().err.strip().splitlines()[-1]
        doc = json.loads(line)
        assert doc["level"] == "error" and doc["msg"] == "boom"
        assert doc["err"] == "ValueError: x"
    finally:
        obs_log.configure()  # back to env defaults


def test_no_print_in_library_modules():
    """Library code logs through obs.log (leveled, structured,
    env-filtered) — bare print calls must not come back. Since PR 5 the
    gate is the analysis plane's ``print-call`` rule (tools/analyze);
    its allowlist — cli.py, __main__.py, tools/ — lives in analyze.toml
    with the reasons. This test keeps the historical tier-1 name as a
    thin wrapper over the framework."""
    from celestia_app_tpu.tools.analyze import run_analysis

    rep = run_analysis(only_rules={"print-call"})
    offenders = [str(v) for v in rep.errors]
    assert not offenders, (
        "print call in a library module (use celestia_app_tpu.obs.log, "
        f"or allowlist with a reason in analyze.toml): {offenders}"
    )
