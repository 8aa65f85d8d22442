"""Benchmark: full 128×128 block extend+commit, device vs CPU baseline.

Measures the flagship device program (da/eds.py: 2D GF(256) RS extension +
4k NMT axis roots + data root — the reference's `da.ExtendShares` +
`DAH.Hash()` chain, pkg/da/data_availability_header.go:65-108) on the default
JAX backend, and reports speedup vs the strongest CPU implementation in-tree
(native/baseline_pipeline.cc: AVX2 leopard-FFT RS encode + SHA-NI hashing —
the same per-core techniques the reference's Go stack uses). The reference's
own Go binary cannot be built here (no Go toolchain); the native pipeline is
the measured stand-in for BASELINE.md config 0, cached in bench_baseline.json,
and its data root is asserted bit-identical to this framework's pipelines.

Prints ONE JSON line:
  {"metric": "extend_commit_128_ms", "value": <device ms/block>,
   "unit": "ms", "vs_baseline": <cpu_ms / device_ms>}

The default mode measures in THIS process and needs a TPU: a run that
finds none fails (no CPU time is ever printed under a device metric's
name). One process owns the chip, so nothing here starts a child that
needs it.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

import celestia_app_tpu  # noqa: F401  (first: places the compile cache before JAX loads)

K = 128
BASELINE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bench_baseline.json")
# wall-clock the default mode may spend probing RS schedules before the
# full-pipeline compile + measurement
CALIBRATION_BUDGET_S = 210.0


def _bench_ods(k: int) -> np.ndarray:
    rng = np.random.default_rng(0)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    ods[..., :29] = 0
    ods[..., 28] = 7  # one user namespace, sorted layout
    return ods


def measure_baseline() -> tuple[float, str, str]:
    """Reference-class CPU pipeline: (ms, data_root_hex, methodology).

    Primary: the native C++ implementation (native/baseline_pipeline.cc —
    leopard-style AVX2 GF(2^8) FFT encode + SHA-NI NMT/Merkle hashing, the
    same techniques the reference's Go stack leans on via klauspost
    reedsolomon and crypto/sha256; single-threaded on this 1-vCPU machine,
    where the reference e2e benches use 8 CPUs). Falls back to the in-tree
    numpy/hashlib pipeline if the native build is unavailable.
    """
    from celestia_app_tpu.utils import native_baseline

    try:
        j = native_baseline.run(_bench_ods(K), reps=3)
        return (
            float(j["cpu_ms"]),
            j["data_root"],
            "native/baseline_pipeline.cc (AVX2 leopard-FFT RS + SHA-NI "
            "NMT/Merkle, 1 thread)",
        )
    except Exception as e:
        print(f"native baseline unavailable ({type(e).__name__}: {e}); "
              "falling back to numpy/hashlib fast_host", file=sys.stderr)
        from celestia_app_tpu.ops import leopard
        from celestia_app_tpu.utils import fast_host

        ods = _bench_ods(K)
        leopard.bit_matrix(K)
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            eds = fast_host.extend_square_fast(ods)
            fast_host.axis_roots_fast(eds)
            times.append(time.perf_counter() - t0)
        return (
            min(times) * 1000.0,
            "",
            "utils/fast_host (numpy BLAS bit-matmul RS + hashlib SHA-256)",
        )


def _slope_ns() -> tuple[int, int]:
    """Loop lengths for slope timing: long enough on accelerators to drown
    per-dispatch overhead, short on the CPU fallback where one block is
    seconds."""
    import jax

    if jax.devices()[0].platform == "cpu":
        return 1, 3
    return 4, 20


def _time_fn(run, ods, reps: int, fold=None) -> float:
    """Per-block ms as the SLOPE between an n_small- and an n_large-iteration
    device loop, each ended by a scalar host fetch.

    Per-call wall timing around an asynchronous dispatch measures the
    enqueue and the host round-trip, not the compute. Chaining the work
    n times inside ONE jitted fori_loop (the output of block i feeds block
    i+1, so nothing dead-code-eliminates) and fetching a 4-byte checksum
    gives t(n) = overhead + n*per_block; the slope cancels fetch latency,
    dispatch cost, and any async-queue artifacts on every backend.
    """
    import jax
    import jax.numpy as jnp

    if fold is None:
        def fold(c, y):
            # default: outputs are (eds, row_roots, col_roots, data_root);
            # the 32-byte root transitively depends on every EDS byte
            return c.at[0, 0, :32].set(c[0, 0, :32] ^ y[3])

    @jax.jit
    def loop(x, n):
        def body(i, c):
            return fold(c, run(c))

        c = jax.lax.fori_loop(0, n, body, x)
        return jnp.sum(c.astype(jnp.int32))

    n_small, n_large = _slope_ns()
    # compile once (dynamic trip count), warm both lengths
    np.asarray(loop(ods, n_small))
    np.asarray(loop(ods, n_large))

    def best(n: int) -> float:
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(loop(ods, n))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    slope = (best(n_large) - best(n_small)) / (n_large - n_small) * 1000.0
    # Host-clock jitter can make best(n_large) <= best(n_small) for very fast
    # fns; floor at a small positive value so callers never divide by zero
    # and a noise-zero probe cannot falsely win calibration's min().
    return max(slope, 0.05)


def _fold_extend(k: int):
    """Carry-fold for extend-only timing: xor all three parity quadrants
    back into the carry so each pass's full output stays live."""

    def fold(c, y):
        return c ^ y[:k, k:, :] ^ y[k:, :k, :] ^ y[k:, k:, :]

    return fold


def _check_baseline_root(root: bytes) -> None:
    """Loudly flag device/native divergence: the docstring's bit-compat claim
    is enforced here for every bench run that has a recorded baseline root."""
    if not os.path.exists(BASELINE_FILE):
        return
    with open(BASELINE_FILE) as f:
        base_root = json.load(f).get("data_root", "")
    if base_root and base_root != root.hex():
        global _ROOT_MISMATCH
        _ROOT_MISMATCH = True
        print("WARNING: device data root differs from native baseline root "
              f"({root.hex()[:16]} vs {base_root[:16]})", file=sys.stderr)


_ROOT_MISMATCH = False


def measure_device(reps: int = 5) -> tuple[float, str]:
    """Device pipeline (ms/block, sha_impl). The SHA-256 stage uses the
    Pallas register kernel by default on accelerators; if that fails to
    compile on the current toolchain, fall back to the jnp scan path and
    still report."""
    import jax

    from celestia_app_tpu.da import eds as eds_mod

    from celestia_app_tpu.ops import sha256 as sha_mod

    ods = jax.device_put(_bench_ods(K))
    if not sha_mod.use_pallas():
        ms = _time_fn(eds_mod.jitted_pipeline(K), ods, reps)
        root = bytes(np.asarray(eds_mod.jitted_pipeline(K)(ods)[3]))
        _check_baseline_root(root)
        return ms, "jnp"
    try:
        pallas_ms = _time_fn(eds_mod.jitted_pipeline(K), ods, reps)
        root_pallas = bytes(np.asarray(eds_mod.jitted_pipeline(K)(ods)[3]))
    except Exception as e:  # Pallas lowering/compile failure: degrade, don't die
        print(f"pallas path failed ({type(e).__name__}: {e}); "
              "retrying with CELESTIA_SHA256_IMPL=jnp", file=sys.stderr)
        pallas_ms, root_pallas = None, None
    # Cross-check the kernel against the jnp scan path before trusting it.
    saved = os.environ.get("CELESTIA_SHA256_IMPL")
    os.environ["CELESTIA_SHA256_IMPL"] = "jnp"
    try:
        eds_mod.jitted_pipeline.cache_clear()
        jnp_pipeline = eds_mod.jitted_pipeline(K)
        root_jnp = bytes(np.asarray(jnp_pipeline(ods)[3]))
        _check_baseline_root(root_jnp)
        if root_pallas == root_jnp:
            return pallas_ms, "pallas"
        if root_pallas is not None:
            print("pallas/jnp data-root MISMATCH; reporting jnp path",
                  file=sys.stderr)
        return _time_fn(jnp_pipeline, ods, reps), "jnp"
    finally:
        if saved is None:
            os.environ.pop("CELESTIA_SHA256_IMPL", None)
        else:
            os.environ["CELESTIA_SHA256_IMPL"] = saved
        eds_mod.jitted_pipeline.cache_clear()


def _probe_rs_schedules(ods, reps: int,
                        budget_s: float | None = None) -> dict[str, float]:
    """Time every (layout × dtype) RS schedule; shared by --stages and the
    child's calibration so the grid cannot drift between them.

    `budget_s` bounds total probing wall-clock (each first compile costs
    20-40 s on TPU; seven schedules could eat the whole attempt window):
    schedules are probed in priority order — round-4 slope timing on real
    silicon measured the fused Pallas pass at 2.7 ms vs 6.7 (batched/int8)
    and 4.7 (flat/bf16), so Pallas goes right after its cross-check
    reference — and probing stops when the budget is spent, keeping
    whatever was measured."""
    import jax

    from celestia_app_tpu.ops import rs

    t_start = time.monotonic()

    def over_budget() -> bool:
        return (budget_s is not None
                and time.monotonic() - t_start > budget_s)

    probes = {}
    fns = {}

    fold = _fold_extend(K)

    def probe_xla(layout: str, dtype: str) -> None:
        try:
            fn = jax.jit(rs.extend_square_fn(K, layout=layout, dtype=dtype))
            fns[f"{layout}/{dtype}"] = fn
            probes[f"{layout}/{dtype}"] = _time_fn(fn, ods, reps, fold=fold)
        except Exception as e:
            print(f"rs probe {layout}/{dtype} failed: {e}", file=sys.stderr)

    def probe_pallas() -> None:
        try:
            # the fused Pallas pass (unpack+matmul+pack in VMEM); fails
            # cleanly where Pallas cannot lower (e.g. CPU backend)
            fn = jax.jit(rs.extend_square_fn(K, layout="pallas"))
            ms = _time_fn(fn, ods, reps, fold=fold)
            # trust only a bit-identical kernel (cross-check vs the
            # compiled XLA reference probed just before)
            ref = fns.get("batched/int8") or fns.get("flat/int8")
            if ref is None:
                print("rs probe pallas/bf16: no XLA reference compiled; "
                      "result untrusted, discarded", file=sys.stderr)
            elif bool((fn(ods) == ref(ods)).all()):
                probes["pallas/bf16"] = ms
            else:
                print("rs probe pallas/bf16 MISMATCH vs XLA path; discarded",
                      file=sys.stderr)
        except Exception as e:
            print(f"rs probe pallas/bf16 failed: {e}", file=sys.stderr)

    # priority order: the cross-check reference first, then the fused
    # Pallas candidate (round-4 silicon winner at 2.7 ms), then the rest
    plan = [lambda: probe_xla("batched", "int8"),
            probe_pallas,
            lambda: probe_xla("flat", "bf16"),
            lambda: probe_xla("fused", "int8"),
            lambda: probe_xla("batched", "bf16"),
            lambda: probe_xla("flat", "int8"),
            lambda: probe_xla("fused", "bf16")]
    for i, step in enumerate(plan):
        if over_budget():
            print(f"rs probe budget spent after {i} schedules",
                  file=sys.stderr)
            break
        step()
    return probes


def measure_stages(reps: int = 10) -> None:
    """Report per-stage device timings to stderr (--stages), including the
    full RS schedule grid so the faster schedule on the actual hardware is
    visible."""
    import jax

    from celestia_app_tpu.da import eds as eds_mod
    from celestia_app_tpu.ops import rs

    ods = jax.device_put(_bench_ods(K))
    probes = _probe_rs_schedules(ods, reps)
    # attribute against the schedule the PIPELINE actually uses (env-driven)
    active = f"{rs._rs_layout()}/{rs._rs_dtype()}"
    extend_ms = probes.get(active, next(iter(probes.values())))
    try:
        full_ms = _time_fn(eds_mod.jitted_pipeline(K), ods, reps)
    except Exception as e:
        print(f"pallas path failed in --stages ({type(e).__name__}); "
              "using jnp", file=sys.stderr)
        os.environ["CELESTIA_SHA256_IMPL"] = "jnp"
        eds_mod.jitted_pipeline.cache_clear()
        full_ms = _time_fn(eds_mod.jitted_pipeline(K), ods, reps)

    # NMT+root stage ≈ full − extend (stages fuse inside one dispatch, so
    # subtraction is the honest attribution available without a profiler).
    probe_str = ", ".join(f"extend({k})={v:.2f} ms" for k, v in probes.items())
    print(
        f"stages: {probe_str}, full[{active}]={full_ms:.2f} ms, "
        f"nmt+root≈{full_ms - extend_ms:.2f} ms",
        file=sys.stderr,
    )


def measure_codec(ks=None) -> None:
    """Codec-plane bench (--codec): every REGISTERED DA commitment
    scheme head to head — 2D-RS+NMT (wire id 0), the CMT (1), the
    polar-coded PCMT (2) — per cost that matters at millions of
    sampling light clients. One BENCH JSON line:

      {"metric": "codec_head_to_head", "k": {"32": {scheme: {...}}, ...}}

    Per scheme at each k: `encode_ms` (one full commit dispatch, warm
    best-of-reps), `proof_bytes_per_sample` (EXACT canonical wire bytes
    of one sample proof, FORMATS §16.3/§16.6 — not JSON/base64
    inflation), `hashes_per_sample_verify` (sha256 invocations a
    verifier pays), `samples_to_99_confidence` (the scheme's own catch
    probability — 2D-RS's combinatorial 1/4 vs the coded-tree schemes'
    measured peeling thresholds), `commitment_bytes` (the once-per-
    block download: 4k NMT roots vs each tree's root hash list),
    `repair_ms` (reconstruction from a 1/4-erased block),
    `fraud_proof_bytes` + `fraud_verify_ms` (a BEFP's k shares vs ONE
    parity equation for cmt/pcmt — the three-way the PCMT exists for:
    it wins fraud-proof and commitment size, and PAYS for it in
    per-sample bytes and hash count; the bench reports the trade, not
    a winner). The acceptance gate — the paper's headline — stays CMT
    `proof_bytes_per_sample` strictly below 2D-RS at k=128.

    A second BENCH line, `rs_tunable_sweep`, sweeps the tunable-rate RS
    knob (ops/rs_tunable.py, arXiv:2201.08261): closed-form analytics
    plus a measured host-engine encode per in-field (k, n) point;
    combos past the GF(256) point budget are SKIPPED AND LOGGED, never
    silently dropped. Backend labeling per FORMATS §12.2
    (`"backend": "cpu-fallback"`).
    """
    import jax

    from celestia_app_tpu.da import codec as dacodec
    from celestia_app_tpu.testing import malicious

    if ks is None:
        ks = tuple(int(x) for x in os.environ.get(
            "CELESTIA_BENCH_CODEC_K", "32,128").split(","))
    reps = int(os.environ.get("CELESTIA_BENCH_CODEC_REPS", "3"))
    backend = jax.devices()[0].platform
    if backend == "cpu":
        backend = "cpu-fallback"
    out: dict = {}
    for k in ks:
        ods = _bench_ods(k)
        per_k: dict = {}
        for sid in dacodec.registered_ids():
            codec = dacodec.by_id(sid)
            name = codec.name
            entry = codec.compute_entry(ods)  # warm (jit compiles)
            encode_ms = None
            for _ in range(reps):
                t0 = time.perf_counter()
                codec.compute_entry(ods)
                dt = (time.perf_counter() - t0) * 1e3
                encode_ms = dt if encode_ms is None else min(encode_ms, dt)
            doc = codec.commitments_doc(entry)
            comm = codec.commitments_from_doc(doc, entry.data_root.hex(),
                                              k)
            space = codec.sample_space(comm)
            cell = space[len(space) // 3]
            sample_doc = codec.open_sample(entry, cell)
            assert codec.verify_sample(comm, sample_doc) is not None
            proof_bytes = codec.sample_wire_bytes(sample_doc, comm)
            commitment_bytes = (
                sum(len(h) for h in comm.root_hashes)
                if hasattr(comm, "root_hashes")  # cmt + pcmt
                else sum(len(r) for r in comm.row_roots)
                + sum(len(r) for r in comm.col_roots))
            # repair from a 1/4-erased block (seeded mask; the CMT seed
            # is pinned inside its peeling threshold — see ops/ldpc.py)
            rng = np.random.default_rng(1)
            n = len(space)
            drop = set(
                int(i) for i in rng.choice(n, size=n // 4, replace=False)
            )
            samples = {}
            for i, c in enumerate(space):
                if i not in drop:
                    d = codec.open_sample(entry, c)
                    got = codec.verify_sample(comm, d)
                    samples[c] = got[1]
            t0 = time.perf_counter()
            rec = codec.repair(comm, samples)
            repair_ms = (time.perf_counter() - t0) * 1e3
            assert np.array_equal(np.asarray(rec), ods)
            # incorrect-coding fraud: commit a corrupt symbol, prove it
            # (THE shared fixture, testing/malicious.py — same one the
            # conformance suite and the scenario matrix drive)
            bad, location, _withheld, _wire = \
                malicious.incorrect_coding_fixture(name, ods)
            bad_comm = bad.dah
            fp = codec.build_fraud_proof(bad, location)
            assert codec.verify_fraud_proof(bad_comm, fp) is True
            if hasattr(fp, "members"):  # one equation, cmt + pcmt
                fraud_bytes = sum(
                    codec.sample_wire_bytes(m.doc, bad_comm)
                    for m in fp.members)
            else:
                from celestia_app_tpu import appconsts

                fraud_bytes = sum(
                    len(s.share)
                    + len(s.proof.nodes) * appconsts.NMT_ROOT_SIZE
                    for s in fp.shares)
            fraud_ms = None
            for _ in range(reps):
                t0 = time.perf_counter()
                assert codec.verify_fraud_proof(bad_comm, fp) is True
                dt = (time.perf_counter() - t0) * 1e3
                fraud_ms = dt if fraud_ms is None else min(fraud_ms, dt)
            per_k[name] = {
                "encode_ms": round(encode_ms, 3),
                "proof_bytes_per_sample": proof_bytes,
                "hashes_per_sample_verify":
                    codec.hashes_per_sample_verify(comm),
                "samples_to_99_confidence":
                    codec.samples_for_confidence(0.99),
                "catch_probability": codec.catch_probability(),
                "commitment_bytes": commitment_bytes,
                "repair_ms": round(repair_ms, 3),
                "fraud_proof_bytes": fraud_bytes,
                "fraud_verify_ms": round(fraud_ms, 3),
            }
        out[str(k)] = per_k
    headline = None
    if "128" in out:
        headline = (out["128"]["cmt-ldpc"]["proof_bytes_per_sample"]
                    < out["128"]["rs2d-nmt"]["proof_bytes_per_sample"])
    print(json.dumps({
        "metric": "codec_head_to_head",
        "backend": backend,
        "k": out,
        "cmt_proof_smaller_at_128": headline,
    }))
    _measure_rs_tunable_sweep(backend)


def _measure_rs_tunable_sweep(backend: str) -> None:
    """The tunable-rate RS knob (ops/rs_tunable.py): per swept
    extension factor, the closed-form protocol analytics plus a
    measured host-engine 2D encode (the analytics are exact; only the
    encode wall time is hardware). FORMATS §16.7 pins the line."""
    from celestia_app_tpu.ops import rs_tunable

    k = int(os.environ.get("CELESTIA_BENCH_RS_SWEEP_K", "32"))
    factors = tuple(float(f) for f in os.environ.get(
        "CELESTIA_BENCH_RS_SWEEP_FACTORS", "1.25,1.5,2.0,3.0,9.0"
    ).split(","))
    ods = _bench_ods(k)
    points, skipped = [], []
    for f in factors:
        n = round(k * f)
        try:
            point = rs_tunable.analytics(k, n, n)
        except ValueError as e:
            # no silent caps: a factor past the GF(256) point budget is
            # reported as skipped, with the reason
            skipped.append({"factor": f, "n": n, "reason": str(e)})
            continue
        t0 = time.perf_counter()
        rect = rs_tunable.extend_2d(ods, n, n, "host")
        point["encode_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        point["factor"] = f
        assert rect.shape[0] == n and rect.shape[1] == n
        points.append(point)
    print(json.dumps({
        "metric": "rs_tunable_sweep",
        "backend": backend,
        "k": k,
        "points": points,
        "skipped": skipped,
    }))


def measure_proofs(n_proofs: int = 10_000) -> None:
    """BASELINE config 3: batched share-proof generation, proofs/sec.

    Builds the 128x128 block's row trees in one device pass
    (da/proof_device.BlockProver), then times assembling n_proofs share
    proofs (pure index arithmetic per proof). Prints its own JSON line;
    the driver's headline metric remains the default mode.
    """
    from celestia_app_tpu.da import dah as dah_mod
    from celestia_app_tpu.da import proof_device

    ods = _bench_ods(K)
    d, eds_obj, _ = dah_mod.new_dah_from_ods(ods)
    t0 = time.perf_counter()
    prover = proof_device.BlockProver(eds_obj, d)
    build_ms = (time.perf_counter() - t0) * 1000
    rng = np.random.default_rng(0)
    starts = rng.integers(0, K * K - 4, n_proofs)
    ns = bytes(29)
    t0 = time.perf_counter()
    for s0 in starts:
        prover.prove_shares(int(s0), int(s0) + 4, ns)
    dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "metric": "share_proofs_per_sec_128",
                "value": round(n_proofs / dt, 1),
                "unit": "proofs/s",
                "tree_build_ms": round(build_ms, 1),
            }
        )
    )


def _calibrate_rs_schedule() -> str:
    """Probe the four (layout × dtype) RS schedules briefly and pin the
    fastest via env BEFORE the pipeline traces — all four are bit-identical
    (tests/test_rs.py), so this is pure schedule selection on the actual
    hardware the measurement runs on."""
    import jax

    ods = jax.device_put(_bench_ods(K))
    probes = _probe_rs_schedules(ods, reps=3, budget_s=CALIBRATION_BUDGET_S)
    for name, ms in probes.items():
        print(f"rs probe {name}: {ms:.1f} ms", file=sys.stderr)
    if not probes:
        return "batched/int8"
    best = min(probes, key=probes.get)
    layout, dtype = best.split("/")
    os.environ["CELESTIA_RS_LAYOUT"] = layout
    os.environ["CELESTIA_RS_DTYPE"] = dtype
    return best


def measure_default() -> None:
    """The default mode: one measurement in THIS process, on a TPU."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"bench.py: no TPU (JAX's first device is {platform!r}); "
                 "extend_commit_128_ms is a device metric and is not "
                 "measured anywhere else")
    if os.path.exists(BASELINE_FILE):
        with open(BASELINE_FILE) as f:
            cpu_ms = json.load(f)["cpu_ms"]
    else:
        cpu_ms, _, _ = measure_baseline()

    rs_schedule = _calibrate_rs_schedule()
    device_ms, sha_impl = measure_device()
    out = {
        "metric": "extend_commit_128_ms",
        "value": round(device_ms, 3),
        "unit": "ms",
        "vs_baseline": round(cpu_ms / device_ms, 2),
        "sha_impl": sha_impl,
        "rs_schedule": rs_schedule,
        "backend": platform,
    }
    if _ROOT_MISMATCH:
        out["baseline_root_match"] = False
    print(json.dumps(out))


def _save_baseline() -> None:
    ms, root, impl = measure_baseline()
    with open(BASELINE_FILE, "w") as f:
        json.dump(
            {
                "metric": "extend_commit_128_ms",
                "cpu_ms": ms,
                "data_root": root,
                "impl": impl,
            },
            f,
            indent=2,
        )
        f.write("\n")
    print(f"baseline measured: {ms:.1f} ms ({impl}) -> {BASELINE_FILE}",
          file=sys.stderr)


def _stream_batched() -> None:
    from celestia_app_tpu.parallel import streaming

    print(json.dumps(streaming.bench_stream_batched()))


def main() -> None:
    if "--list" in sys.argv:
        for name in sorted(MODES):
            _fn, metrics, desc = MODES[name]
            print(f"--{name:<18} {desc}")
            print(f"  {'':<18} emits: {metrics}")
        return
    for name, (fn, _metrics, _desc) in MODES.items():
        if f"--{name}" in sys.argv:
            fn()
            return
    measure_default()


def measure_analyze(reps: int = 3) -> None:
    """Analysis-plane bench (--analyze): wall time of a full-tree run of
    every registered rule (tools/analyze, call-graph taint included)
    against the committed analyze.toml — the cost every tier-1 test run
    and pre-commit hook pays. Cold clears the per-file incremental
    cache first; warm re-runs against it (ISSUE 12 gate: warm ≤ cold/3
    — every file unchanged, so only the interprocedural re-link runs).
    Budget: < 10 s cold on CPU (pure-AST work). One BENCH JSON line:

      {"metric": "analyze_wall_s", ...,
       "analyze_cold_wall_s": F, "analyze_warm_wall_s": F,
       "analyze_effects_cold_wall_s": F, "analyze_effects_warm_wall_s": F}

    The effect pass (ISSUE 20: xfer-reach + lock-order +
    guarded-by-flow over the SCC summary fixpoint) is timed separately
    with its own cold/warm pair and the same warm ≤ cold/3 gate — the
    fragment cache must absorb the v4 effect facts too.
    """
    import os
    import tempfile

    from celestia_app_tpu.tools.analyze import run_analysis

    effect_rules = {"xfer-reach", "lock-order", "guarded-by-flow"}
    cache_path = os.path.join(tempfile.gettempdir(),
                              f"analyze_bench_cache_{os.getpid()}.json")
    best_cold = best_warm = None
    best_ecold = best_ewarm = None
    rep = None
    try:
        for _ in range(reps):
            if os.path.exists(cache_path):
                os.unlink(cache_path)
            cold = run_analysis(cache=cache_path)
            rep = warm = run_analysis(cache=cache_path)
            assert warm.cache_misses == 0, warm.cache_misses
            best_cold = (cold.wall_s if best_cold is None
                         else min(best_cold, cold.wall_s))
            best_warm = (warm.wall_s if best_warm is None
                         else min(best_warm, warm.wall_s))
        for _ in range(reps):
            if os.path.exists(cache_path):
                os.unlink(cache_path)
            ecold = run_analysis(cache=cache_path,
                                 only_rules=set(effect_rules))
            ewarm = run_analysis(cache=cache_path,
                                 only_rules=set(effect_rules))
            best_ecold = (ecold.wall_s if best_ecold is None
                          else min(best_ecold, ecold.wall_s))
            best_ewarm = (ewarm.wall_s if best_ewarm is None
                          else min(best_ewarm, ewarm.wall_s))
    finally:
        if os.path.exists(cache_path):
            os.unlink(cache_path)
    print(json.dumps({
        "metric": "analyze_wall_s",
        "analyze_wall_s": round(best_cold, 3),
        "analyze_cold_wall_s": round(best_cold, 3),
        "analyze_warm_wall_s": round(best_warm, 3),
        "warm_speedup": round(best_cold / max(best_warm, 1e-9), 1),
        "analyze_effects_cold_wall_s": round(best_ecold, 3),
        "analyze_effects_warm_wall_s": round(best_ewarm, 3),
        "files_scanned": rep.files_scanned,
        "rules_run": len(rep.rules_run),
        "violations": len(rep.violations),
        "errors": len(rep.errors),
        "waived": len(rep.waived),
        "budget_s": 10.0,
        "within_budget": best_cold < 10.0,
        "warm_within_third": best_warm <= best_cold / 3.0,
        "effects_warm_within_third": best_ewarm <= best_ecold / 3.0,
    }))


def measure_repair(reps: int | None = None) -> None:
    """Decode-plane bench (--repair). Two BENCH JSON lines:

      {"metric": "repair_128_ms", ...}  full 2D repair (da/repair.py
          batched sweep engine) of a ¼-erased k=128 EDS, measured for the
          two canonical masks — whole-columns-missing (the withholding
          shape: one shared erasure pattern, one fused decode matmul per
          sweep) and uniform-random cell loss (flaky-peer shape: distinct
          per-row patterns, scalar FWHT decode + batched device
          verification). Headline value is the whole-columns mask;
          acceptance is within 5x the same-backend extend+commit time
          measured in the SAME run.
      {"metric": "befp_verify_ms", ...}  da/fraud.verify_befp of a real
          k=128 bad-encoding proof (the DASer-fleet gossip-rate path).

    Backend labeling follows FORMATS §12.2: a CPU measurement is emitted
    as `"backend": "cpu-fallback"` so trajectory plots can tell labeled
    CPU stand-ins from TPU runs.
    """
    import jax

    from celestia_app_tpu.da import dah as dah_mod
    from celestia_app_tpu.da import eds as eds_mod
    from celestia_app_tpu.da import fraud, repair
    from celestia_app_tpu.ops import nmt
    from celestia_app_tpu.utils import telemetry

    if reps is None:
        # a CPU backend pays ~25 s/run at k=128; keep the whole mode
        # inside ~10 min there while accelerators get more samples
        reps = int(os.environ.get(
            "CELESTIA_BENCH_REPAIR_REPS",
            "2" if jax.devices()[0].platform == "cpu" else "5"))
    two_k = 2 * K
    ods = _bench_ods(K)
    # same-backend reference: the full extend+commit pipeline, warm-first
    # best-of-reps wall timing (the --admission scheme; each run ends in a
    # host fetch of the 32-byte data root, so the dispatch is complete —
    # the slope harness would cost 16 block executions, ~6 min on a CPU
    # backend, for the same answer)
    pipeline = eds_mod.jitted_pipeline(K)
    ods_dev = jax.device_put(ods)
    np.asarray(pipeline(ods_dev)[3])  # compile + warm
    extend_ms = None
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(pipeline(ods_dev)[3])
        dt = (time.perf_counter() - t0) * 1e3
        extend_ms = dt if extend_ms is None else min(extend_ms, dt)
    d, eds_obj, _ = dah_mod.new_dah_from_ods(ods)
    eds = np.asarray(eds_obj.squares)
    row_roots, col_roots = list(d.row_roots), list(d.col_roots)

    masks = {}
    m = np.ones((two_k, two_k), dtype=bool)
    m[:, ::4] = False  # every 4th extended column withheld: ¼ of cells
    masks["columns"] = m
    rng = np.random.default_rng(1)
    masks["random"] = rng.random((two_k, two_k)) >= 0.25

    timings, counter_split = {}, {}
    for name in ("columns", "random"):
        mask = masks[name]
        damaged = np.where(mask[..., None], eds, 0).astype(np.uint8)
        c0 = telemetry.snapshot().get("counters", {})
        out = repair.repair_eds(damaged, mask, row_roots, col_roots)
        assert np.array_equal(out, eds), f"repair({name}) diverged"
        c1 = telemetry.snapshot().get("counters", {})
        counter_split[name] = {
            key: c1.get(f"repair.{key}", 0) - c0.get(f"repair.{key}", 0)
            for key in ("axes_batched", "axes_scalar", "matrix_cache_hits",
                        "matrix_cache_misses")
        }
        best = None  # warm run above compiled every program; now measure
        for _ in range(reps):
            t0 = time.perf_counter()
            repair.repair_eds(damaged, mask, row_roots, col_roots)
            dt = (time.perf_counter() - t0) * 1e3
            best = dt if best is None else min(best, dt)
        timings[name] = best

    backend = jax.devices()[0].platform
    if backend == "cpu":
        backend = "cpu-fallback"
    print(json.dumps({
        "metric": "repair_128_ms",
        "value": round(timings["columns"], 2),
        "unit": "ms",
        "mask_columns_ms": round(timings["columns"], 2),
        "mask_random_ms": round(timings["random"], 2),
        "extend_commit_ms": round(extend_ms, 2),
        "vs_extend": round(timings["columns"] / extend_ms, 2),
        "within_5x_extend": timings["columns"] <= 5 * extend_ms,
        "counters": counter_split,
        "backend": backend,
    }), flush=True)

    # -- BEFP verification at gossip rate --------------------------------
    corrupt = eds.copy()
    corrupt[3, two_k - 1] ^= 0xFF  # row 3 is no longer a codeword
    t0 = time.perf_counter()
    bad_rows = nmt.eds_axis_roots(corrupt, np.arange(two_k), K)
    bad_cols = nmt.eds_axis_roots(
        np.ascontiguousarray(corrupt.transpose(1, 0, 2)),
        np.arange(two_k), K)
    d_bad = dah_mod.DataAvailabilityHeader(
        row_roots=tuple(r.tobytes() for r in bad_rows),
        col_roots=tuple(c.tobytes() for c in bad_cols),
    )
    commit_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    befp = fraud.generate_befp(dah_mod.ExtendedDataSquare(corrupt), "row", 3)
    generate_ms = (time.perf_counter() - t0) * 1e3
    assert fraud.verify_befp(d_bad, befp), "BEFP did not verify"
    best = None
    for _ in range(max(reps, 3)):
        t0 = time.perf_counter()
        ok = fraud.verify_befp(d_bad, befp)
        dt = (time.perf_counter() - t0) * 1e3
        best = dt if best is None else min(best, dt)
    print(json.dumps({
        "metric": "befp_verify_ms",
        "value": round(best, 2),
        "unit": "ms",
        "k": K,
        "verified_fraud": bool(ok),
        "generate_ms": round(generate_ms, 2),
        "commit_corrupt_ms": round(commit_ms, 2),
        "backend": backend,
    }), flush=True)


def measure_admission(n_sigs: int = 512, n_senders: int = 32,
                      ingest_senders: int = 16,
                      ingest_txs_per_sender: int = 32) -> None:
    """Admission-plane bench (--admission). Two BENCH JSON lines:

      {"metric": "sig_verify_per_sec", ...}  batched secp256k1 ECDSA
          verification throughput (ops/secp256k1: vmapped 10x26-limb
          field math, complete RCB point formulas, GLV-halved doubling
          chain; one jit dispatch per 512 lanes) against the scalar
          `_py_verify` baseline measured IN THE SAME RUN — acceptance is
          >= 10x scalar on CPU; the >= 100k/s figure stays the recorded
          target for a TPU run.
      {"metric": "mempool_ingest_txs_per_sec", ...}  CAT-pool ingest
          through the TWO-PHASE batched admission path
          (Node.broadcast_txs: one stateless batch-signature dispatch,
          then stateful per-tx CheckTx hitting the verified-sig cache) —
          directly comparable with the PR-2 scalar-path number from
          --mempool.
    """
    import random

    from celestia_app_tpu.chain import crypto
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.chain.tx import MsgSend
    from celestia_app_tpu.client.tx_client import Signer
    from celestia_app_tpu.ops import secp256k1 as fast

    # -- 1) raw signature-verification throughput ------------------------
    privs = [PrivateKey.from_seed(b"adm-%d" % (i % n_senders))
             for i in range(n_sigs)]
    items = []
    for i, p in enumerate(privs):
        msg = b"admission-bench-%d" % i
        items.append((p.public_key().compressed, p.sign(msg), msg))

    scalar_n = min(48, n_sigs)
    t0 = time.perf_counter()
    for it in items[:scalar_n]:
        assert crypto._py_verify(*it)
    scalar_per_sec = scalar_n / (time.perf_counter() - t0)

    t0 = time.perf_counter()
    mask = fast.verify_batch(items)
    first_s = time.perf_counter() - t0  # includes the one-time jit compile
    assert mask.all()
    best = first_s
    for _ in range(3):
        t0 = time.perf_counter()
        fast.verify_batch(items)
        best = min(best, time.perf_counter() - t0)
    batched_per_sec = n_sigs / best
    backend = "scalar-fallback"
    if fast.available():
        import jax

        backend = jax.devices()[0].platform
    print(json.dumps({
        "metric": "sig_verify_per_sec",
        "value": round(batched_per_sec, 1),
        "unit": "sigs/s",
        "scalar_per_sec": round(scalar_per_sec, 1),
        "vs_scalar": round(batched_per_sec / scalar_per_sec, 2),
        "batch": n_sigs,
        "compile_s": round(first_s - best, 2),
        "backend": backend,
        "tpu_target_per_sec": 100_000,
    }), flush=True)

    # -- 2) two-phase mempool ingest -------------------------------------
    chain = "admission-bench"
    iprivs = [PrivateKey.from_seed(b"ing-%d" % i)
              for i in range(ingest_senders)]
    addrs = [p.public_key().address() for p in iprivs]
    app = App(chain_id=chain, engine="auto")
    app.init_chain({
        "time_unix": 1_700_000_000.0,
        "accounts": [{"address": a.hex(), "balance": 10**12}
                     for a in addrs],
        "validators": [{"operator": addrs[0].hex(), "power": 10}],
    })
    signer = Signer(chain)
    for i, p in enumerate(iprivs):
        signer.add_account(p, number=i)
    rng = random.Random(0)
    raws: list[bytes] = []
    for _seq in range(ingest_txs_per_sender):
        for i, a in enumerate(addrs):
            tx = signer.create_tx(
                a, [MsgSend(a, addrs[(i + 1) % ingest_senders], 1)],
                fee=rng.randint(1_000, 100_000), gas_limit=100_000,
            )
            signer.accounts[a].sequence += 1
            raws.append(tx.encode())
    node = Node(app)
    t0 = time.perf_counter()
    results = node.broadcast_txs(raws)
    ingest_s = time.perf_counter() - t0
    admitted = sum(1 for r in results if r.code == 0)
    from celestia_app_tpu.utils import telemetry

    counters = telemetry.snapshot().get("counters", {})
    print(json.dumps({
        "metric": "mempool_ingest_txs_per_sec",
        "value": round(len(raws) / ingest_s, 1),
        "unit": "tx/s",
        "n_txs": len(raws),
        "admitted": admitted,
        "path": "two-phase-batched",
        "batch_verified": counters.get("admission.batch_verified", 0),
        "scalar_verified": counters.get("admission.sig_scalar_verified", 0),
    }), flush=True)

    # -- 3) traffic plane: the commitment half of phase 1 ----------------
    # a PFB burst through the same two-phase path, reported as the
    # commitment.* counter deltas (FORMATS §12.3; the throughput
    # head-to-head lives in --txsim — this line is the admission block's
    # counter surface)
    from celestia_app_tpu.da.blob import Blob
    from celestia_app_tpu.da.namespace import Namespace

    rng_np = np.random.default_rng(1)
    blob_raws = []
    # same lane count as the ingest burst (senders x txs_per_sender =
    # 512), so the phase-1 SIG batch reuses part 1/2's compiled bucket
    # and the measured rate prices admission, not a fresh jit compile
    for seq in range(ingest_txs_per_sender):
        for i, a in enumerate(addrs):
            blobs = [Blob(Namespace.v0(bytes([i + 1, (seq % 250) + 1]) * 5),
                          rng_np.integers(0, 256, 700, dtype=np.uint8)
                          .tobytes())]
            blob_raws.append(signer.create_pay_for_blobs(
                a, blobs, fee=300_000, gas_limit=5_000_000))
            signer.accounts[a].sequence += 1
    c0 = telemetry.snapshot().get("counters", {})
    t0 = time.perf_counter()
    blob_res = node.broadcast_txs(blob_raws)
    burst_s = time.perf_counter() - t0
    c1 = telemetry.snapshot().get("counters", {})

    def delta(name: str) -> int:
        return c1.get(name, 0) - c0.get(name, 0)

    print(json.dumps({
        "metric": "admission_commitment_batch",
        "value": round(len(blob_raws) / burst_s, 1),
        "unit": "blob-txs/s",
        "n_blob_txs": len(blob_raws),
        "admitted": sum(1 for r in blob_res if r.code == 0),
        "commitment_batch_dispatches": delta("commitment.batch_dispatches"),
        "commitment_batch_lanes": delta("commitment.batch_lanes"),
        "commitment_cache_hits": delta("commitment.cache_hits"),
        "commitment_recomputes": delta("commitment.recomputes"),
    }), flush=True)


def measure_mempool(n_senders: int = 16, txs_per_sender: int = 32) -> None:
    """Mempool plane microbench: CAT pool ingest (CheckTx + admission) and
    priority reap, pure host path (no device work). Signing happens before
    the clock starts — the measured path is what a node pays per inbound
    /broadcast_tx and per proposal. Prints two JSON lines:

      {"metric": "mempool_ingest_txs_per_sec", ...}
      {"metric": "mempool_reap_ms", ...}
    """
    import random

    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.chain.tx import MsgSend
    from celestia_app_tpu.client.tx_client import Signer

    chain = "mempool-bench"
    privs = [PrivateKey.from_seed(b"mp-%d" % i) for i in range(n_senders)]
    addrs = [p.public_key().address() for p in privs]
    app = App(chain_id=chain, engine="host")
    app.init_chain({
        "time_unix": 1_700_000_000.0,
        "accounts": [
            {"address": a.hex(), "balance": 10**12} for a in addrs
        ],
        "validators": [
            {"operator": addrs[0].hex(), "power": 10}
        ],
    })
    signer = Signer(chain)
    for i, p in enumerate(privs):
        signer.add_account(p, number=i)
    rng = random.Random(0)
    raws: list[bytes] = []
    for _seq in range(txs_per_sender):
        for i, a in enumerate(addrs):
            tx = signer.create_tx(
                a, [MsgSend(a, addrs[(i + 1) % n_senders], 1)],
                fee=rng.randint(1_000, 100_000), gas_limit=100_000,
            )
            signer.accounts[a].sequence += 1
            raws.append(tx.encode())

    node = Node(app)
    t0 = time.perf_counter()
    admitted = sum(1 for raw in raws if node.broadcast_tx(raw).code == 0)
    ingest_s = time.perf_counter() - t0
    reap_ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        reaped = node._reap()
        reap_ms.append((time.perf_counter() - t0) * 1e3)
    print(json.dumps({
        "metric": "mempool_ingest_txs_per_sec",
        "value": round(len(raws) / ingest_s, 1),
        "unit": "tx/s",
        "n_txs": len(raws),
        "admitted": admitted,
    }))
    print(json.dumps({
        "metric": "mempool_reap_ms",
        "value": round(min(reap_ms), 3),
        "unit": "ms",
        "pool_count": len(reaped),
    }))


def measure_slo(heights: int = 3) -> None:
    """Fleet SLO verdict bench (--slo): spin a live 2-validator HTTP
    devnet, let it commit, quiesce the reactors, then run the fleet-wide
    SLO engine (tools/fleetmon.py) against it — and prove the verdict is
    DETERMINISTIC: two scrapes of the same quiesced fleet state must
    produce byte-identical verdicts. One BENCH JSON line:

      {"metric": "slo_verdict_pass", "value": 1|0, "deterministic": ...}
    """
    import threading  # noqa: F401  (ValidatorService spawns threads)

    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.chain.reactor import ReactorConfig
    from celestia_app_tpu.chain import consensus as cons
    from celestia_app_tpu.service.validator_server import ValidatorService
    from celestia_app_tpu.tools import fleetmon

    privs = [PrivateKey.from_seed(b"slo-%d" % i) for i in range(2)]
    genesis = {
        "time_unix": 1_700_000_000.0,
        "accounts": [
            {"address": p.public_key().address().hex(), "balance": 10**12}
            for p in privs
        ],
        "validators": [
            {"operator": p.public_key().address().hex(), "power": 10,
             "pubkey": p.public_key().compressed.hex()}
            for p in privs
        ],
    }
    nodes = [cons.ValidatorNode(f"val{i}", p, genesis, "slo-bench")
             for i, p in enumerate(privs)]
    services = [ValidatorService(v) for v in nodes]
    for s in services:
        s.serve_background()
    urls = [f"http://127.0.0.1:{s.port}" for s in services]
    cfg = dict(timeout_propose=5.0, timeout_prevote=2.5,
               timeout_precommit=2.5, timeout_delta=0.5,
               block_interval=0.05, poll=0.01, gossip_timeout=1.5,
               sync_grace=0.5, breaker_reset=1.5)
    try:
        for i, s in enumerate(services):
            s.attach_reactor([u for j, u in enumerate(urls) if j != i],
                             ReactorConfig(**cfg))
        deadline = time.monotonic() + 120
        while (time.monotonic() < deadline
               and min(n.app.height for n in nodes) < heights):
            time.sleep(0.05)
        # quiesce: stop consensus, keep the HTTP planes serving — the
        # fleet state under judgment must hold still between scrapes
        for s in services:
            if s.reactor is not None:
                s.reactor.stop()
        rules = fleetmon.normalize_rules([
            {"name": "fleet-height", "source": "status", "path": "height",
             "op": ">=", "value": heights, "agg": "each"},
            {"name": "no-http-500", "metric": "http.500",
             "op": "==", "value": 0},
            {"name": "no-breaker-flaps", "metric": "net.breaker_open",
             "op": "==", "value": 0},
            {"name": "no-collector-errors",
             "metric": "telemetry.collector_errors",
             "op": "==", "value": 0},
            {"name": "commit-p99-budget", "metric": "commit",
             "kind": "p99", "op": "<=", "value": 60.0},
        ])
        v1 = fleetmon.evaluate(rules, fleetmon.scrape_fleet(
            urls, with_availability=False))
        v2 = fleetmon.evaluate(rules, fleetmon.scrape_fleet(
            urls, with_availability=False))
        deterministic = (fleetmon.verdict_bytes(v1)
                         == fleetmon.verdict_bytes(v2))
        print(json.dumps({
            "metric": "slo_verdict_pass",
            "value": 1 if v1["pass"] else 0,
            "unit": "bool",
            "deterministic": deterministic,
            "rules": len(rules),
            "failed": v1["failed"],
            "fleet_height": min(n.app.height for n in nodes),
        }), flush=True)
    finally:
        for s in services:
            try:
                s.shutdown()
            except Exception:
                pass


def run_compare() -> None:
    """Bench trajectory gate (--compare): align the repo's committed
    BENCH_*.json rounds (tools/benchdiff.py), print the per-metric
    trajectory, and exit 2 when the newest comparable sample of any
    metric regressed beyond tolerance — the CI gate over the committed
    perf history. cpu-fallback rounds never compare against hardware."""
    from celestia_app_tpu.tools import benchdiff

    here = os.path.dirname(os.path.abspath(__file__))
    raise SystemExit(benchdiff.main(["--dir", here]))


def measure_obs(blocks: int = 40, senders: int = 8) -> None:
    """Observability-plane overhead bench (--obs): the produce-block hot
    path with the FULL boundary observatory armed — spans + histograms +
    the transfer-ledger rows (obs/xfer.py, they follow the spans gate),
    per-site lock wait/hold profiling (racecheck, CELESTIA_LOCKPROF
    semantics flipped in-process), and a running GIL-pressure sampler
    (obs/gil.py) — vs the same path with everything off. One BENCH JSON
    line:

      {"metric": "obs_overhead_pct", ...}

    Each measured block carries real ante-checked MsgSend txs so the
    denominator is a representative block, not an empty square."""
    from celestia_app_tpu import obs as obs_mod
    from celestia_app_tpu.obs import gil
    from celestia_app_tpu.tools.analyze import racecheck
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.chain.tx import MsgSend
    from celestia_app_tpu.client.tx_client import Signer

    privs = [PrivateKey.from_seed(b"obs-%d" % i) for i in range(senders)]
    addrs = [p.public_key().address() for p in privs]

    def run(n_blocks: int) -> list:
        """Fresh node; per-block ms for n_blocks tx-bearing blocks."""
        app = App(chain_id="obs-bench", engine="host")
        app.init_chain({
            "time_unix": 1_700_000_000.0,
            "accounts": [
                {"address": a.hex(), "balance": 10**12} for a in addrs
            ],
            "validators": [{"operator": addrs[0].hex(), "power": 10}],
        })
        node = Node(app)
        signer = Signer("obs-bench")
        for i, p in enumerate(privs):
            signer.add_account(p, number=i)

        def submit_round():
            for i, a in enumerate(addrs):
                tx = signer.create_tx(
                    a, [MsgSend(a, addrs[(i + 1) % senders], 1)],
                    fee=2000, gas_limit=100_000,
                )
                signer.accounts[a].sequence += 1
                node.broadcast_tx(tx.encode())

        t_block = 1_700_000_001.0
        submit_round()
        node.produce_block(t=t_block)  # warm caches outside the clock
        per_block = []
        for _ in range(n_blocks):
            t_block += 1.0
            t0 = time.perf_counter()
            submit_round()
            node.produce_block(t=t_block)
            per_block.append((time.perf_counter() - t0) * 1e3)
        return per_block

    # INTERLEAVED off/on arms, compared at the per-block p10 floor: on
    # a shared box the run-to-run load swing dwarfs a single-digit
    # overhead (observed >60% spread across identical runs, and a load
    # spike in any single block poisons a per-run mean). Interleaving
    # gives both arms the same shot at the quiet windows; the low
    # percentile of each arm's per-block times keeps only those, which
    # is the number the <5% gate is actually about — what the
    # observatory adds to a block, not what the neighbors add to the
    # box. The ON side arms the whole observatory per pair: span rows +
    # xfer ledger rows (spans gate), lock wait/hold profiling (locks
    # created by the instrumented Apps are born AFTER install, so they
    # are tracked), and the GIL oversleep sampler.
    def run_off(n: int) -> list:
        obs_mod.set_enabled(False)
        return run(n)

    def run_on(n: int) -> list:
        obs_mod.set_enabled(True)
        racecheck.install()
        racecheck.set_order_tracking(False)
        racecheck.set_profiling(True)
        gil.start("bench")
        try:
            return run(n)
        finally:
            gil.stop_all()
            racecheck.set_profiling(False)
            racecheck.uninstall()

    off_blocks, on_blocks = [], []
    try:
        run_off(4)  # discard: allocator/caches warm on nobody's clock
        for pair in range(4):
            # alternate which arm goes first so neither systematically
            # inherits the colder (or busier) half of its pair
            if pair % 2 == 0:
                off_blocks += run_off(blocks)
                on_blocks += run_on(blocks)
            else:
                on_blocks += run_on(blocks)
                off_blocks += run_off(blocks)
    finally:
        obs_mod.set_enabled(None)  # back to the CELESTIA_OBS env gate

    def floor(xs: list) -> float:
        return sorted(xs)[len(xs) // 10]  # p10: the quiet-window block

    off_ms, on_ms = floor(off_blocks), floor(on_blocks)
    overhead_pct = (on_ms - off_ms) / off_ms * 100.0
    print(json.dumps({
        "metric": "obs_overhead_pct",
        "value": round(overhead_pct, 2),
        "unit": "%",
        "instrumented_ms_per_block": round(on_ms, 3),
        "off_ms_per_block": round(off_ms, 3),
        "blocks": blocks,
        "txs_per_block": senders,
    }))


def measure_chaos(heights: int = 12, lost: int = 8) -> None:
    """Fault-plane recovery bench (--chaos). Two BENCH JSON lines:

      {"metric": "crash_replay_ms", ...}        WAL replay wall time for a
          node that lost its last `lost` durable commits but kept the WAL
          (the crash-matrix recovery path, chain/consensus.replay_wal)
      {"metric": "chaos_heal_recovery_s", ...}  wall time from healing a
          seeded full partition of a 3-reactor devnet to its next
          committed height (blocks-to-liveness after heal)
    """
    import shutil
    import tempfile
    import threading

    from celestia_app_tpu import faults
    from celestia_app_tpu.chain import consensus as cons
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.chain.reactor import ReactorConfig
    from celestia_app_tpu.chain.storage import ChainDB
    from celestia_app_tpu.service.validator_server import ValidatorService

    def genesis_for(privs, powers):
        return {
            "time_unix": 1_700_000_000.0,
            "accounts": [
                {"address": p.public_key().address().hex(),
                 "balance": 10**12} for p in privs
            ],
            "validators": [
                {"operator": p.public_key().address().hex(), "power": w,
                 "pubkey": p.public_key().compressed.hex()}
                for p, w in zip(privs, powers)
            ],
        }

    # -- 1) crash-replay wall time ---------------------------------------
    tmp = tempfile.mkdtemp(prefix="chaos-bench-")
    try:
        priv = PrivateKey.from_seed(b"chaos-replay")
        genesis = genesis_for([priv], [10])
        data_dir = os.path.join(tmp, "data")
        node = cons.ValidatorNode("val0", priv, genesis, "chaos-bench",
                                  data_dir=data_dir)
        net = cons.LocalNetwork([node])
        t = 1_700_000_000.0
        for _ in range(heights):
            t += 1.0
            net.produce_height(t=t)
        node.app.close()
        # the crash: the last `lost` durable commits vanish, the WAL stays
        keep = heights - lost
        db = ChainDB(data_dir)
        db.delete_above(keep)
        # the native engine's tomb_above removes the (sole) LATEST record
        # outright; re-point it at the surviving height (the file engine
        # already did this inside delete_above — set_latest is idempotent)
        db.backend.set_latest(keep)
        db.close()
        node2 = cons.ValidatorNode("val0", priv, genesis, "chaos-bench",
                                   data_dir=data_dir)
        node2.app.load()
        assert node2.app.height == keep
        t0 = time.perf_counter()
        replayed = node2.replay_wal()
        replay_ms = (time.perf_counter() - t0) * 1e3
        node2.app.close()
        assert replayed == lost, (replayed, lost)
        print(json.dumps({
            "metric": "crash_replay_ms",
            "value": round(replay_ms, 2),
            "unit": "ms",
            "blocks_replayed": replayed,
            "per_block_ms": round(replay_ms / max(replayed, 1), 2),
        }), flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # -- 2) partition heal: blocks-to-liveness ---------------------------
    faults.reset(seed=7)
    privs = [PrivateKey.from_seed(b"chaos-%d" % i) for i in range(3)]
    genesis = genesis_for(privs, [10, 10, 10])
    nodes = [cons.ValidatorNode(f"val{i}", p, genesis, "chaos-bench")
             for i, p in enumerate(privs)]
    services = [ValidatorService(v) for v in nodes]
    for s in services:
        s.serve_background()
    urls = [f"http://127.0.0.1:{s.port}" for s in services]
    cfg = dict(timeout_propose=5.0, timeout_prevote=2.5,
               timeout_precommit=2.5, timeout_delta=0.5,
               block_interval=0.05, poll=0.01, gossip_timeout=1.5,
               sync_grace=0.5, breaker_reset=1.5)
    try:
        for i, s in enumerate(services):
            s.attach_reactor([u for j, u in enumerate(urls) if j != i],
                             ReactorConfig(**cfg))
        deadline = time.monotonic() + 120
        while (time.monotonic() < deadline
               and min(n.app.height for n in nodes) < 2):
            time.sleep(0.05)
        # isolate val0: no side holds >2/3 of 30 -> full stall
        ports = [s.port for s in services]
        faults.arm("net.request", "drop",
                   match={"owner": "^val0$"})
        faults.arm("net.request", "drop",
                   match={"owner": "^val[12]$", "peer": f":{ports[0]}$"})
        time.sleep(5.0)
        h0 = max(n.app.height for n in nodes)
        faults.disarm(point="net.request")
        t_heal = time.monotonic()
        deadline = time.monotonic() + 120
        while (time.monotonic() < deadline
               and max(n.app.height for n in nodes) <= h0):
            time.sleep(0.02)
        recovery_s = time.monotonic() - t_heal
        # liveness rate: heights committed in the 5 s after recovery
        h1 = max(n.app.height for n in nodes)
        time.sleep(5.0)
        rate = (max(n.app.height for n in nodes) - h1) / 5.0
        print(json.dumps({
            "metric": "chaos_heal_recovery_s",
            "value": round(recovery_s, 3),
            "unit": "s",
            "stalled_at": h0,
            "blocks_per_sec_after_heal": round(rate, 3),
        }), flush=True)
    finally:
        faults.reset()
        for s in services:
            try:
                s.shutdown()
            except Exception:
                pass


def measure_stream() -> None:
    """BASELINE config 4/5: streaming PrepareProposal — overlap host layout
    of block N+1 with device extend+commit of block N; prints blocks/s.
    See parallel/streaming.py."""
    from celestia_app_tpu.parallel import streaming

    print(json.dumps(streaming.bench_stream()))


def measure_stream_mesh() -> None:
    """BASELINE config 5: 256×256 streaming on an 8-device mesh — the
    sharded pipeline (two all-to-alls inside) streamed with host overlap;
    prints blocks/s. Virtual CPU devices demonstrate the same program when
    no multi-chip hardware is attached."""
    from celestia_app_tpu.parallel import streaming

    print(json.dumps(streaming.bench_stream_mesh()))


def measure_block(blocks: int | None = None, senders: int = 8) -> None:
    """Block-plane e2e bench (--block): the extend-once lifecycle end to
    end. Three BENCH JSON lines:

      {"metric": "block_e2e_ms", ...}       tx-bearing produce→commit wall
          time per block through Node.produce_block (prepare → process →
          finalize → commit — process hits the content-addressed EDS
          cache prepare populated, so the whole round dispatches exactly
          ONE extend; `extend_runs_per_block` reports the counter-
          verified figure).
      {"metric": "blocks_per_sec", ...}     inverse throughput over the
          same measured run.
      {"metric": "first_sample_after_commit_ms", ...}  first DAS sample
          after the final commit on the WARMED path (the commit handed
          its cache entry to the SampleCore with provers pre-built) vs
          the COLD rebuild path (caches cleared). The skip is counter-
          verified, not just faster wall time: the warm sample must show
          a `das.square_builds` delta of 0 and a `da.extend_runs` delta
          of 0, the cold one 1 and 1.

    Backend labeling follows FORMATS §12.2: a CPU measurement is emitted
    with `"backend": "cpu-fallback"`.
    """
    import shutil
    import tempfile

    import jax

    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.chain.tx import MsgSend
    from celestia_app_tpu.client.tx_client import Signer
    from celestia_app_tpu.das.server import SampleCore
    from celestia_app_tpu.utils import telemetry

    backend = jax.devices()[0].platform
    if blocks is None:
        blocks = int(os.environ.get(
            "CELESTIA_BENCH_BLOCKS", "10" if backend == "cpu" else "30"))
    if backend == "cpu":
        backend = "cpu-fallback"

    privs = [PrivateKey.from_seed(b"blk-%d" % i) for i in range(senders)]
    addrs = [p.public_key().address() for p in privs]
    tmp = tempfile.mkdtemp(prefix="block-bench-")
    app = App(chain_id="block-bench", engine="auto", data_dir=tmp)
    try:
        app.init_chain({
            "time_unix": 1_700_000_000.0,
            "accounts": [
                {"address": a.hex(), "balance": 10**12} for a in addrs
            ],
            "validators": [{"operator": addrs[0].hex(), "power": 10}],
        })
        node = Node(app)
        core = node.attach_das_core(SampleCore(app))
        signer = Signer("block-bench")
        for i, p in enumerate(privs):
            signer.add_account(p, number=i)

        def submit_round():
            for i, a in enumerate(addrs):
                tx = signer.create_tx(
                    a, [MsgSend(a, addrs[(i + 1) % senders], 1)],
                    fee=2000, gas_limit=100_000,
                )
                signer.accounts[a].sequence += 1
                node.broadcast_tx(tx.encode())

        def counters():
            return telemetry.snapshot().get("counters", {})

        def delta(c0, c1, key):
            return c1.get(key, 0) - c0.get(key, 0)

        t_block = 1_700_000_001.0
        submit_round()
        node.produce_block(t=t_block)  # compile + warm outside the clock
        app.da_warmer.wait_idle(60)

        c0 = counters()
        per_block = []
        t_run0 = time.perf_counter()
        for _ in range(blocks):
            t_block += 1.0
            submit_round()
            t0 = time.perf_counter()
            node.produce_block(t=t_block)
            per_block.append((time.perf_counter() - t0) * 1e3)
        run_s = time.perf_counter() - t_run0
        c1 = counters()
        extend_runs = delta(c0, c1, "da.extend_runs")
        print(json.dumps({
            "metric": "block_e2e_ms",
            "value": round(min(per_block), 3),
            "unit": "ms",
            "mean_ms": round(sum(per_block) / len(per_block), 3),
            "blocks": blocks,
            "txs_per_block": senders,
            "extend_runs_per_block": round(extend_runs / blocks, 3),
            "backend": backend,
        }), flush=True)
        print(json.dumps({
            "metric": "blocks_per_sec",
            "value": round(blocks / run_s, 3),
            "unit": "blocks/s",
            "blocks": blocks,
            "txs_per_block": senders,
            "backend": backend,
        }), flush=True)

        # -- first sample after commit: warmed vs cold -------------------
        app.da_warmer.wait_idle(60)
        height = app.height
        c_w0 = counters()
        t0 = time.perf_counter()
        core.sample(height, 0, 0)
        warm_ms = (time.perf_counter() - t0) * 1e3
        c_w1 = counters()
        warm_builds = delta(c_w0, c_w1, "das.square_builds")
        warm_extends = delta(c_w0, c_w1, "da.extend_runs")

        cold_core = SampleCore(app)  # no seed listener, fresh height LRU
        app.eds_cache.clear()  # the content cache must not rescue it
        c_c0 = counters()
        t0 = time.perf_counter()
        cold_core.sample(height, 0, 0)
        cold_ms = (time.perf_counter() - t0) * 1e3
        c_c1 = counters()
        print(json.dumps({
            "metric": "first_sample_after_commit_ms",
            "value": round(warm_ms, 3),
            "unit": "ms",
            "cold_ms": round(cold_ms, 3),
            "vs_cold": round(cold_ms / max(warm_ms, 1e-6), 1),
            "warm_square_builds": warm_builds,
            "warm_extend_runs": warm_extends,
            "cold_square_builds": delta(c_c0, c_c1, "das.square_builds"),
            "cold_extend_runs": delta(c_c0, c_c1, "da.extend_runs"),
            "skipped_square_build": warm_builds == 0 and warm_extends == 0,
            "backend": backend,
        }), flush=True)
    finally:
        app.close()
        shutil.rmtree(tmp, ignore_errors=True)


def measure_sync() -> None:
    """Sync-plane bench (--sync). Three BENCH JSON lines:

      {"metric": "snapshot_serve_ms", ...}   HTTP round-trip to serve the
          manifest list plus one chunk from the disk-backed snapshot
          store (never a capture, never under the service lock).
      {"metric": "blocksync_blocks_per_sec", ...}  verified replay rate
          of the pipelined range path (GET /gossip/commits + prefetch
          window) vs the per-height round-trip baseline, each measured
          over a real replay window of the SAME chain. A 70 ms
          per-request latency is injected via the fault plane — the
          network shape the reference's e2e benchmark models with
          BitTwister (test/e2e/benchmark/benchmark.go:110-117) — and
          labeled in the JSON; on bare localhost the replay loop is
          verification-bound either way and the round-trip being
          pipelined away would be invisible.
      {"metric": "state_sync_join_s", ...}   wall time for a fresh joiner
          to reach the tip of a `CELESTIA_BENCH_SYNC_BLOCKS` (default
          2000) block chain via chunked snapshot join + tail blocksync,
          against `full_replay_s` extrapolated from the measured
          per-height rate over the full chain length (flagged
          "estimated_from_window"; replaying thousands of blocks for
          real would measure the same per-height cost N more times).

    Backend labeling follows FORMATS §12.2 ("cpu-fallback" on CPU).
    """
    import shutil
    import tempfile
    import threading

    import jax

    from celestia_app_tpu import faults
    from celestia_app_tpu.chain import consensus as cons
    from celestia_app_tpu.chain import sync as sync_mod
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.chain.reactor import (
        ConsensusReactor,
        ReactorConfig,
    )
    from celestia_app_tpu.net import transport
    from celestia_app_tpu.service.validator_server import ValidatorService

    platform = jax.devices()[0].platform
    backend = "cpu-fallback" if platform == "cpu" else platform
    chain_id = "sync-bench"
    blocks = int(os.environ.get("CELESTIA_BENCH_SYNC_BLOCKS", "2000"))
    tail = 32  # heights past the newest snapshot (the join's replay tail)
    window = min(blocks, int(os.environ.get(
        "CELESTIA_BENCH_SYNC_WINDOW", "192")))
    base_window = min(blocks, int(os.environ.get(
        "CELESTIA_BENCH_SYNC_BASE_WINDOW", "96")))
    rtt_s = float(os.environ.get("CELESTIA_BENCH_SYNC_RTT_MS", "70")) / 1e3
    snap_interval = max(1, blocks // 4)

    def genesis_for(priv):
        return {
            "time_unix": 1_700_000_000.0,
            "accounts": [{"address": priv.public_key().address().hex(),
                          "balance": 10**12}],
            "validators": [{
                "operator": priv.public_key().address().hex(),
                "power": 10,
                "pubkey": priv.public_key().compressed.hex(),
            }],
        }

    def grow(vnode, reactor, n):
        for _ in range(n):
            height = vnode.app.height + 1
            last_cert = vnode.certificates.get(height - 1)
            block = vnode.propose(t=1_700_000_000.0 + height)
            bh = block.header.hash()
            digest = cons.Proposal.commit_info_digest(last_cert, ())
            sig = vnode.priv.sign(cons.Proposal.sign_bytes(
                chain_id, height, 0, bh, digest))
            prop = cons.Proposal(height, 0, block, vnode.address, sig,
                                 last_cert, ())
            vote = vnode._signed(height, bh, "precommit", 0)
            cert = cons.CommitCertificate(height, bh, (vote,), 0)
            vnode.apply(block, cert, absent_cert=last_cert)
            vnode.clear_lock()
            reactor._remember_commit(
                {"proposal": cons.proposal_to_json(prop),
                 "cert": cons.cert_to_json(cert)}, height)

    tmp = tempfile.mkdtemp(prefix="sync-bench-")
    faults.reset()
    try:
        priv = PrivateKey.from_seed(b"sync-bench-server")
        genesis = genesis_for(priv)
        server = cons.ValidatorNode(
            "srv", priv, genesis, chain_id,
            data_dir=os.path.join(tmp, "srv", "data"))
        svc = ValidatorService(server)
        reactor = ConsensusReactor(
            server, [], svc.lock,
            ReactorConfig(snapshot_interval=snap_interval,
                          snapshot_keep=2))
        svc.reactor = reactor  # serve routes only; loop not started
        svc.serve_background()
        url = f"http://127.0.0.1:{svc.port}"
        t_build0 = time.perf_counter()
        grow(server, reactor, blocks + tail)
        build_s = time.perf_counter() - t_build0
        target = server.app.height
        print(f"chain built: {target} heights in {build_s:.1f}s "
              f"(snapshots at interval {snap_interval})",
              file=sys.stderr, flush=True)

        # -- 1) snapshot_serve_ms (no injected latency: serve cost only)
        client = transport.PeerClient(name="sync-bench")
        reps = 20
        t0 = time.perf_counter()
        for _ in range(reps):
            snaps = client.get(url, "/sync/snapshots")["snapshots"]
            client.get(
                url,
                f"/sync/chunk?height={snaps[0]['height']}&index=0",
                raw=True,
            )
        serve_ms = (time.perf_counter() - t0) * 1e3 / reps
        print(json.dumps({
            "metric": "snapshot_serve_ms",
            "value": round(serve_ms, 3),
            "unit": "ms",
            "snapshots_on_disk": len(snaps),
            "n_chunks": snaps[0]["n_chunks"],
            "backend": backend,
        }), flush=True)

        # injected per-request latency (the reference's BitTwister shape):
        # applies to the JOINERS' outbound requests only — the serving
        # side makes none
        faults.arm("net.request", "delay", delay_s=rtt_s,
                   match={"owner": "^join-"})

        def joiner(name, **cfg):
            vnode = cons.ValidatorNode(
                name, PrivateKey.from_seed(name.encode()), genesis,
                chain_id, data_dir=os.path.join(tmp, name, "data"))
            defaults = dict(snapshot_interval=0, sync_grace=0.0,
                            gossip_timeout=10.0)
            r = ConsensusReactor(
                vnode, [url], threading.Lock(),
                ReactorConfig(**{**defaults, **cfg}))
            return vnode, r

        def replay_to(vnode, r, stop_height, budget_s=1800.0):
            with r._msg_lock:
                r._ahead = (stop_height + 1, url,
                            time.monotonic() - 10)
            deadline = time.monotonic() + budget_s
            while (vnode.app.height < stop_height
                   and time.monotonic() < deadline):
                r._maybe_catch_up()
            assert vnode.app.height >= stop_height, (
                f"{vnode.name} stuck at {vnode.app.height}")

        # -- 2) blocksync_blocks_per_sec: pipelined vs per-height -------
        vp, rp = joiner("join-pipe", statesync_gap=10**9)
        t0 = time.perf_counter()
        replay_to(vp, rp, window)
        pipe_s = time.perf_counter() - t0
        pipe_rate = window / pipe_s
        vb, rb = joiner("join-base", statesync_gap=10**9,
                        blocksync_pipeline=False)
        t0 = time.perf_counter()
        replay_to(vb, rb, base_window)
        base_s = time.perf_counter() - t0
        base_rate = base_window / base_s
        # differential check (untimed): walk both joiners to the SAME
        # height per-height (the two stop rules differ by one at window
        # boundaries), then the stores must be byte-identical — or the
        # speedup is measuring corruption
        while vb.app.height < vp.app.height:
            assert rb._replay_height(vb.app.height + 1, prefer=url)
        while vp.app.height < vb.app.height:
            assert rp._replay_height(vp.app.height + 1, prefer=url)
        assert vp.app.store.snapshot() == vb.app.store.snapshot(), (
            "pipelined and per-height replay diverged"
        )
        print(json.dumps({
            "metric": "blocksync_blocks_per_sec",
            "value": round(pipe_rate, 2),
            "unit": "blocks/s",
            "window_heights": window,
            "per_height_blocks_per_sec": round(base_rate, 2),
            "per_height_window_heights": base_window,
            "vs_per_height": round(pipe_rate / base_rate, 2),
            "injected_rtt_ms": rtt_s * 1e3,
            "backend": backend,
        }), flush=True)

        # -- 3) state_sync_join_s vs (estimated) full replay -------------
        vj, rj = joiner("join-snap", statesync_gap=tail)
        t0 = time.perf_counter()
        replay_to(vj, rj, target)
        join_s = time.perf_counter() - t0
        assert vj.app.last_app_hash == server.app.last_app_hash
        assert vj.app.last_block_hash == server.app.last_block_hash
        # full replay extrapolated from the measured per-height rate over
        # the same chain (labeled): replaying all N for real would just
        # re-measure base_rate N/base_window more times
        full_replay_s = target / base_rate
        print(json.dumps({
            "metric": "state_sync_join_s",
            "value": round(join_s, 2),
            "unit": "s",
            "chain_heights": target,
            "snapshot_height": target - target % snap_interval,
            "full_replay_s": round(full_replay_s, 1),
            "estimated_from_window": base_window,
            "vs_full_replay": round(full_replay_s / join_s, 1),
            "injected_rtt_ms": rtt_s * 1e3,
            "chain_build_s": round(build_s, 1),
            "backend": backend,
        }), flush=True)
        svc.shutdown()
        server.app.close()
        for v in (vp, vb, vj):
            v.app.close()
    finally:
        faults.reset()
        shutil.rmtree(tmp, ignore_errors=True)


def measure_serve() -> None:
    """Serving-plane bench (--serve). Per scheme, one BENCH JSON line:

      {"metric": "samples_served_per_sec", "scheme": S,
       "value": <pack-served samples/s>, "live_samples_per_sec": ...,
       "vs_live": ..., "p99_sample_ms": <live p99 per request>,
       "pack_p99_ms": ..., "pack_hit_ratio": ...,
       "sampler_round_trips_per_height": ..., "samplers": N, ...}

    Three measurements against one in-process devnet per scheme:

    - **live baseline**: `tools/dasload.py` drives N concurrent
      persistent-connection samplers (default 1000,
      ``CELESTIA_BENCH_SERVE_SAMPLERS``), each batching 16 drawn cells
      per request through the live `POST /das/samples` assembly path.
    - **pack-served**: the same fleet fetching static proof-pack chunks
      (`GET /das/pack/chunk`, sha256-verified against the manifest) for
      warm heights — no lock, no assembly; a chunk delivers every proof
      doc it covers, which is the pack model's serving economics.
    - **catch-up round-trips**: a real DASer (das/daser.py) light node
      catches up over the warm window via the multi-height batched
      sampler (one /das/headers + one grouped /das/samples per window);
      ``sampler_round_trips_per_height`` is the counter-verified
      sampling-path request count divided by heights sampled — the
      header-following (/ibc/header certificate) fetches are the light
      client's own sequential-verification cost, not the sampling
      plane's.

    Backend labeling follows FORMATS §12.2 ("cpu-fallback" on CPU).
    Env knobs: CELESTIA_BENCH_SERVE_SAMPLERS (1000), _REQUESTS (3),
    _K (16: the seeded load squares' ODS width), _WINDOW (8),
    _SCHEMES ("rs2d-nmt,cmt-ldpc").
    """
    import resource
    import shutil
    import tempfile

    import jax

    from celestia_app_tpu.chain import consensus as cons
    from celestia_app_tpu.chain import light as light_mod
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.da import edscache as edscache_mod
    from celestia_app_tpu.das.checkpoint import CheckpointStore
    from celestia_app_tpu.das.daser import DASer, DASerConfig
    from celestia_app_tpu.service.server import NodeService
    from celestia_app_tpu.tools import dasload
    from celestia_app_tpu.utils import telemetry

    platform = jax.devices()[0].platform
    backend = "cpu-fallback" if platform == "cpu" else platform
    samplers = int(os.environ.get("CELESTIA_BENCH_SERVE_SAMPLERS", "1000"))
    requests = int(os.environ.get("CELESTIA_BENCH_SERVE_REQUESTS", "3"))
    k_load = int(os.environ.get("CELESTIA_BENCH_SERVE_K", "16"))
    window = int(os.environ.get("CELESTIA_BENCH_SERVE_WINDOW", "8"))
    schemes = os.environ.get("CELESTIA_BENCH_SERVE_SCHEMES",
                             "rs2d-nmt,cmt-ldpc").split(",")
    # a thousand keep-alive samplers hold a thousand sockets each side
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < 4 * samplers:
        resource.setrlimit(resource.RLIMIT_NOFILE,
                           (min(4 * samplers, hard), hard))

    def genesis_for(priv):
        return {
            "time_unix": 1_700_000_000.0,
            "accounts": [{"address": priv.public_key().address().hex(),
                          "balance": 10**12}],
            "validators": [{
                "operator": priv.public_key().address().hex(),
                "power": 10,
                "pubkey": priv.public_key().compressed.hex(),
            }],
        }

    def grow(vnode, n):
        for _ in range(n):
            height = vnode.app.height + 1
            last_cert = vnode.certificates.get(height - 1)
            block = vnode.propose(t=1_700_000_000.0 + height)
            bh = block.header.hash()
            vote = vnode._signed(height, bh, "precommit", 0)
            cert = cons.CommitCertificate(height, bh, (vote,), 0)
            vnode.apply(block, cert, absent_cert=last_cert)
            vnode.clear_lock()

    def counters():
        return telemetry.snapshot().get("counters", {})

    for scheme in schemes:
        chain_id = f"serve-bench-{scheme}"
        tmp = tempfile.mkdtemp(prefix="serve-bench-")
        try:
            priv = PrivateKey.from_seed(b"serve-bench")
            genesis = genesis_for(priv)
            vnode = cons.ValidatorNode(
                "srv", priv, genesis, chain_id,
                data_dir=os.path.join(tmp, "srv", "data"),
                da_scheme=scheme, pack_keep=0)  # keep every pack
            svc = NodeService(vnode, port=0)
            svc.serve_background()
            url = f"http://127.0.0.1:{svc.port}"
            grow(vnode, window)
            vnode.app.da_warmer.wait_idle(60)
            # every chain height needs its pack for the warm window
            # (the warmer coalesces under rapid commits; build is
            # idempotent for the ones it did reach)
            for h in range(1, vnode.app.height + 1):
                vnode.app.pack_store.build(
                    h, svc.das_core._entry(h).cache_entry)

            # seeded load heights: k_load squares are the meatier
            # serving shape (the chain's own empty blocks are k=1)
            rng = np.random.default_rng(0)
            load_heights = []
            for i in range(4):
                ods = rng.integers(0, 256, size=(k_load, k_load, 512),
                                   dtype=np.uint8)
                ods[..., :29] = 0
                ods[..., 28] = 7
                entry = edscache_mod.compute_entry(ods, "host",
                                                   scheme=scheme)
                h = 1000 + i
                svc.das_core.seed_scheme_entry(h, entry)
                vnode.app.pack_store.build(h, entry)
                load_heights.append(h)

            live = dasload.run_load(url, load_heights,
                                    samplers=samplers, requests=requests,
                                    cells=16, mode="live")
            print(f"[{scheme}] live: {live['samples_per_sec']}/s "
                  f"p99 {live['p99_ms']}ms errors {live['errors']}",
                  file=sys.stderr, flush=True)
            pack = dasload.run_load(url, load_heights,
                                    samplers=samplers, requests=requests,
                                    cells=16, mode="pack")
            print(f"[{scheme}] pack: {pack['samples_per_sec']}/s "
                  f"p99 {pack['p99_ms']}ms errors {pack['errors']}",
                  file=sys.stderr, flush=True)

            # -- catch-up round trips: a real DASer over the warm window
            trust = light_mod.TrustedState(
                height=0, header_hash=b"",
                validators={vnode.address:
                            priv.public_key().compressed},
                powers={vnode.address: 10},
            )
            daser = DASer(
                [url], light_mod.LightClient(chain_id, trust),
                CheckpointStore(os.path.join(tmp, "cp.json")),
                cfg=DASerConfig(samples_per_header=16, workers=1,
                                job_size=window, retries=2,
                                backoff=0.01),
                rng=np.random.default_rng(7), name="serve-bench-daser",
            )
            c0 = counters()
            out = daser.sync()
            c1 = counters()
            trips = (c1.get("daser.sampling_round_trips", 0)
                     - c0.get("daser.sampling_round_trips", 0))
            heights_swept = (c1.get("daser.heights_swept", 0)
                             - c0.get("daser.heights_swept", 0))
            rtph = trips / max(1, heights_swept)
            sampled_ok = len(out.get("sampled", [])) == window
            vs_live = (pack["samples_per_sec"]
                       / max(1e-9, live["samples_per_sec"]))
            print(json.dumps({
                "metric": "samples_served_per_sec",
                "value": pack["samples_per_sec"],
                "unit": "samples/s",
                "scheme": scheme,
                "live_samples_per_sec": live["samples_per_sec"],
                "vs_live": round(vs_live, 2),
                "p99_sample_ms": live["p99_ms"],
                "pack_p99_ms": pack["p99_ms"],
                "pack_hit_ratio": pack["pack_hit_ratio"],
                "sampler_round_trips_per_height": round(rtph, 3),
                "window_heights": window,
                "window_sampled_ok": sampled_ok,
                "samplers": samplers,
                "requests_per_sampler": requests,
                "cells_per_request": 16,
                "load_square_k": k_load,
                "live_errors": live["errors"],
                "pack_errors": pack["errors"],
                "backend": backend,
            }), flush=True)
            svc.shutdown()
            vnode.app.close()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def measure_read() -> None:
    """Read-plane bench (--read). One BENCH JSON line:

      {"metric": "namespace_queries_per_sec", "value": <batched qps>,
       "single_queries_per_sec": ..., "batched_vs_single_ratio": ...,
       "single_p50_ms"/"single_p99_ms"/"batch_p50_ms"/"batch_p99_ms",
       "pack_queries_per_sec", "pack_vs_live_ratio", "present_ratio",
       "readers", "batch", "backend"}

    Three measurements against one in-process devnet carrying real PFB
    blob blocks (many distinct namespaces per height):

    - **single baseline**: `tools/blobload.py` drives N concurrent
      persistent-connection followers, each resolving one namespace per
      `GET /blob/get` round-trip — the per-request host reference loop
      (da/namespace_data.get_namespace_data per query).
    - **batched**: the same query stream folded ``batch`` queries per
      `POST /blob/namespaces` round-trip — one engine-gated batched
      search (da/namespace_device.py) resolves each height's whole
      batch. ``batched_vs_single_ratio`` is the ISSUE 16 gate (>= 5x at
      batch >= 64).
    - **pack-served**: static blob-pack chunk reads (sha256-verified),
      the CDN path; ``pack_vs_live_ratio`` is pack qps over single qps.

    Backend labeling follows FORMATS §12.2 ("cpu-fallback" on CPU).
    Env knobs: CELESTIA_BENCH_READ_READERS (64), _REQUESTS (6),
    _BATCH (64), _BLOCKS (3), _NS (48 distinct namespaces).
    """
    import resource
    import shutil
    import tempfile

    import jax

    from celestia_app_tpu.chain import consensus as cons
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.client.tx_client import Signer
    from celestia_app_tpu.da.blob import Blob
    from celestia_app_tpu.da.namespace import Namespace
    from celestia_app_tpu.service.server import NodeService
    from celestia_app_tpu.tools import blobload

    platform = jax.devices()[0].platform
    backend = "cpu-fallback" if platform == "cpu" else platform
    readers = int(os.environ.get("CELESTIA_BENCH_READ_READERS", "64"))
    requests = int(os.environ.get("CELESTIA_BENCH_READ_REQUESTS", "6"))
    batch = int(os.environ.get("CELESTIA_BENCH_READ_BATCH", "64"))
    blocks = int(os.environ.get("CELESTIA_BENCH_READ_BLOCKS", "3"))
    n_ns = int(os.environ.get("CELESTIA_BENCH_READ_NS", "48"))
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < 4 * readers:
        resource.setrlimit(resource.RLIMIT_NOFILE,
                           (min(4 * readers, hard), hard))

    chain_id = "read-bench"
    tmp = tempfile.mkdtemp(prefix="read-bench-")
    try:
        n_accounts = 8
        privs = [PrivateKey.from_seed(b"read-bench-%d" % i)
                 for i in range(n_accounts)]
        addrs = [p.public_key().address() for p in privs]
        genesis = {
            "time_unix": 1_700_000_000.0,
            "accounts": [{"address": a.hex(), "balance": 10**14}
                         for a in addrs],
            "validators": [{
                "operator": addrs[0].hex(),
                "power": 10,
                "pubkey": privs[0].public_key().compressed.hex(),
            }],
        }
        vnode = cons.ValidatorNode(
            "read", privs[0], genesis, chain_id,
            data_dir=os.path.join(tmp, "read", "data"),
            da_scheme="rs2d-nmt", pack_keep=0)
        signer = Signer(chain_id)
        for i, p in enumerate(privs):
            signer.add_account(p, number=i)
        svc = NodeService(vnode, port=0)
        svc.serve_background()
        url = f"http://127.0.0.1:{svc.port}"

        namespaces = [Namespace.v0(bytes([1 + i // 200, 1 + i % 200]) * 5)
                      for i in range(n_ns)]
        rng = np.random.default_rng(16)

        def pfb_blobs(height):
            # every namespace present at every height, blobs spread
            # over the accounts so each block carries n_accounts PFBs
            per_acct = [[] for _ in range(n_accounts)]
            for i, ns in enumerate(namespaces):
                size = int(rng.integers(400, 1200))
                per_acct[i % n_accounts].append(
                    Blob(ns, rng.integers(0, 256, size,
                                          dtype=np.uint8).tobytes()))
            return per_acct

        for _ in range(blocks):
            height = vnode.app.height + 1
            for a, blobs in zip(addrs, pfb_blobs(height)):
                raw = signer.create_pay_for_blobs(
                    a, blobs, fee=300_000, gas_limit=50_000_000)
                signer.accounts[a].sequence += 1
                vnode.add_tx(raw)
            last_cert = vnode.certificates.get(height - 1)
            block = vnode.propose(t=1_700_000_000.0 + height)
            bh = block.header.hash()
            vote = vnode._signed(height, bh, "precommit", 0)
            cert = cons.CommitCertificate(height, bh, (vote,), 0)
            vnode.apply(block, cert, absent_cert=last_cert)
            vnode.clear_lock()
        vnode.app.da_warmer.wait_idle(60)
        # the warmer coalesces under rapid commits; builds are
        # idempotent for the heights it did reach
        heights = list(range(1, vnode.app.height + 1))
        for h in heights:
            vnode.app.blob_pack_store.build(
                h, svc.das_core._entry(h).cache_entry)
        ns_hex = [ns.raw.hex() for ns in namespaces]

        single = blobload.run_load(url, heights, ns_hex,
                                   readers=readers, requests=requests,
                                   mode="single")
        print(f"single: {single['namespace_queries_per_sec']}/s "
              f"p99 {single['p99_ms']}ms errors {single['errors']}",
              file=sys.stderr, flush=True)
        batched = blobload.run_load(url, heights, ns_hex,
                                    readers=max(2, readers // 8),
                                    requests=requests, mode="batch",
                                    batch=batch)
        print(f"batch({batch}): "
              f"{batched['namespace_queries_per_sec']}/s "
              f"p99 {batched['p99_ms']}ms errors {batched['errors']}",
              file=sys.stderr, flush=True)
        pack = blobload.run_load(url, heights, ns_hex,
                                 readers=readers, requests=requests,
                                 mode="pack")
        print(f"pack: {pack['namespace_queries_per_sec']}/s "
              f"p99 {pack['p99_ms']}ms errors {pack['errors']}",
              file=sys.stderr, flush=True)

        single_qps = single["namespace_queries_per_sec"]
        batch_qps = batched["namespace_queries_per_sec"]
        pack_qps = pack["namespace_queries_per_sec"]
        print(json.dumps({
            "metric": "namespace_queries_per_sec",
            "value": batch_qps,
            "unit": "queries/s",
            "single_queries_per_sec": single_qps,
            "batched_vs_single_ratio": round(
                batch_qps / max(1e-9, single_qps), 2),
            "single_p50_ms": single["p50_ms"],
            "single_p99_ms": single["p99_ms"],
            "batch_p50_ms": batched["p50_ms"],
            "batch_p99_ms": batched["p99_ms"],
            "pack_queries_per_sec": pack_qps,
            "pack_vs_live_ratio": round(
                pack_qps / max(1e-9, single_qps), 2),
            "present_ratio": batched["present_ratio"],
            "heights": len(heights),
            "namespaces": n_ns,
            "readers": readers,
            "batch": batch,
            "single_errors": single["errors"],
            "batch_errors": batched["errors"],
            "pack_errors": pack["errors"],
            "backend": backend,
        }), flush=True)
        svc.shutdown()
        vnode.app.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure_txsim() -> None:
    """Traffic-plane bench (--txsim). Three BENCH JSON lines:

      {"metric": "blobs_per_sec", ...}  sustained blob load: N concurrent
          txsim sequences (tools/txsim.run_load — one Signer account and
          one persistent keep-alive HttpNodeClient each) submit PFB
          blobs over HTTP against a live in-process devnet whose
          producer commits blocks on an interval; every tx is
          confirm-polled, so the number is END-TO-END admission->commit
          blob throughput. Carries admission_commit p50/p99 and the run's
          acceptance counts.
      {"metric": "admission_commit_p99_ms", ...}  the same run's p99
          submit->commit latency as its own metric line.
      {"metric": "commitment_validate_per_sec", ...}  the tentpole's
          head-to-head: admission commitment validation CACHED (one
          batched prevalidation dispatch filling the
          VerifiedCommitmentCache, then per-tx lookups) vs the COLD
          per-tx host path (per-blob subtree-root MMRs in host Python,
          the reference's ValidateBlobTx shape) over the same
          >= 64-pending-blob set — acceptance is >= 3x at >= 64 blobs.

    Backend labeling follows FORMATS §12.2 ("cpu-fallback" on CPU).
    Env knobs: CELESTIA_BENCH_TXSIM_SEQUENCES (8), _TXS (8: per
    sequence), _BLOBS (128: head-to-head pending set),
    _BLOCK_TIME (0.2 s).
    """
    import jax

    from celestia_app_tpu import appconsts
    from celestia_app_tpu.chain import admission
    from celestia_app_tpu.chain import blob_validation
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.client.tx_client import Signer
    from celestia_app_tpu.da import blob as blob_mod
    from celestia_app_tpu.da.blob import Blob
    from celestia_app_tpu.da.namespace import Namespace
    from celestia_app_tpu.service.server import NodeService
    from celestia_app_tpu.tools import txsim
    from celestia_app_tpu.utils import telemetry

    platform = jax.devices()[0].platform
    backend = "cpu-fallback" if platform == "cpu" else platform
    n_seq = int(os.environ.get("CELESTIA_BENCH_TXSIM_SEQUENCES", "8"))
    txs_per_seq = int(os.environ.get("CELESTIA_BENCH_TXSIM_TXS", "8"))
    n_blobs = int(os.environ.get("CELESTIA_BENCH_TXSIM_BLOBS", "128"))
    block_time = float(os.environ.get("CELESTIA_BENCH_TXSIM_BLOCK_TIME",
                                      "0.2"))

    # -- 1) sustained load against a live devnet -------------------------
    import shutil
    import tempfile

    chain = "txsim-bench"
    privs = [PrivateKey.from_seed(b"txsim-bench-%d" % i)
             for i in range(n_seq)]
    addrs = [p.public_key().address() for p in privs]
    tmp = tempfile.mkdtemp(prefix="txsim-bench-")
    app = app_w = app_c = None
    try:
        # a data_dir so /abci_query path=tx (the confirm-polling route)
        # has a block store to resolve against — like any real devnet home
        app = App(chain_id=chain, engine="auto",
                  data_dir=os.path.join(tmp, "data"))
        app.init_chain({
            "time_unix": 1_700_000_000.0,
            "accounts": [{"address": a.hex(), "balance": 10**14}
                         for a in addrs],
            "validators": [{"operator": addrs[0].hex(), "power": 10}],
        })
        node = Node(app)
        svc = NodeService(node, port=0)
        svc.serve_background()
        url = f"http://127.0.0.1:{svc.port}"
        signer = Signer(chain)
        for i, p in enumerate(privs):
            signer.add_account(p, number=i)

        def produce():
            with svc.lock:
                node.produce_block()

        # warm the block pipeline's jit buckets before the measured window
        # (a live devnet is warm; the submit->commit latency must price the
        # traffic plane, not the first blocks' one-time compiles)
        rng_w = np.random.default_rng(9)
        for _r in range(3):
            for i, a in enumerate(addrs[:2]):
                wblobs = [Blob(Namespace.v0(bytes([99, i + 1]) * 5),
                               rng_w.integers(
                                   0, 256, int(rng_w.integers(100, 2000)),
                                   dtype=np.uint8).tobytes())]
                wraw = signer.create_pay_for_blobs(
                    a, wblobs, fee=300_000, gas_limit=5_000_000)
                if node.broadcast_tx(wraw).code == 0:
                    signer.accounts[a].sequence += 1
            produce()

        driver = txsim.BlockDriver(produce, block_time=block_time)
        driver.start()
        c0 = telemetry.snapshot().get("counters", {})
        try:
            rep = txsim.run_load(
                [url], signer, addrs,
                txsim.LoadConfig(blob_sequences=n_seq,
                                 txs_per_sequence=txs_per_seq,
                                 blob_sizes=(100, 2000), blobs_per_pfb=(1, 2),
                                 confirm_timeout_s=60.0, seed=0),
            )
        finally:
            driver.stop()
            svc.shutdown()
        c1 = telemetry.snapshot().get("counters", {})

        def delta(name: str) -> int:
            return c1.get(name, 0) - c0.get(name, 0)

        print(json.dumps({
            "metric": "blobs_per_sec",
            "value": rep.blobs_per_sec,
            "unit": "blobs/s",
            "sequences": rep.sequences,
            "txs_per_sequence": txs_per_seq,
            "pfbs_submitted": rep.pfbs_submitted,
            "pfbs_accepted": rep.pfbs_accepted,
            "pfbs_confirmed": rep.pfbs_confirmed,
            "blobs_confirmed": rep.blobs_confirmed,
            "bytes_submitted": rep.bytes_submitted,
            "admission_commit_p50_ms": rep.admission_commit_p50_ms,
            "admission_commit_p99_ms": rep.admission_commit_p99_ms,
            "blocks_produced": driver.blocks,
            "block_time_s": block_time,
            "resyncs": rep.resyncs,
            "errors": rep.errors,
            "commitment_cache_hits": delta("commitment.cache_hits"),
            "commitment_recomputes": delta("commitment.recomputes"),
            "backend": backend,
        }), flush=True)
        print(json.dumps({
            "metric": "admission_commit_p99_ms",
            "value": rep.admission_commit_p99_ms,
            "unit": "ms",
            "p50_ms": rep.admission_commit_p50_ms,
            "sequences": rep.sequences,
            "confirmed": rep.pfbs_confirmed + rep.sends_confirmed,
            "backend": backend,
        }), flush=True)

        # -- 2) cached vs cold commitment-validation throughput --------------
        # COLD is the reference's shape: every validation phase recomputes
        # each blob's commitment per tx in host Python (ValidateBlobTx in
        # both CheckTx and ProcessProposal). CACHED is this PR's shape: ONE
        # batched prevalidation dispatch at admission fills the
        # VerifiedCommitmentCache, and every validation phase after it
        # (CheckTx -> Prepare -> Process -> replay — 3+ passes per tx) is a
        # lookup + byte-compare. `value` is the per-pass cached validation
        # throughput (what each phase now pays); `admission_dispatch_s` and
        # `incl_dispatch_per_sec` price the one-time batch honestly.
        threshold = appconsts.subtree_root_threshold(1)
        # devnet-scale blobs (the reference txsim submits up to ~100 KB);
        # commitment cost scales with shares, so the size range is the knob
        # that decides how much each phase's recompute used to cost
        blob_lo_hi = [int(x) for x in os.environ.get(
            "CELESTIA_BENCH_TXSIM_BLOB_BYTES", "1000-16000").split("-")]

        def blob_tx_set(tag: bytes):
            # same seed per set: identical shapes (jit buckets stay warm
            # across sets), distinct namespaces keep the cache keys apart
            rng = np.random.default_rng(2)
            signer2 = Signer(chain)
            for i, p in enumerate(privs):
                signer2.add_account(p, number=i)
            raws = []
            for i in range(n_blobs):
                a = addrs[i % len(addrs)]
                size = int(rng.integers(blob_lo_hi[0], blob_lo_hi[1] + 1))
                blobs = [Blob(Namespace.v0(tag + bytes([i % 251, i // 251]) * 4),
                              rng.integers(0, 256, size, dtype=np.uint8)
                              .tobytes())]
                raws.append(signer2.create_pay_for_blobs(
                    a, blobs, fee=300_000, gas_limit=5_000_000))
                signer2.accounts[a].sequence += 1
            return [blob_mod.try_unmarshal_blob_tx(r) for r in raws], raws

        # warm the jit shape buckets so the dispatch number is steady-state
        # (the one-time compile is reported separately, like --admission)
        _warm_btxs, warm_raws = blob_tx_set(b"wa")
        app_w = App(chain_id=chain, engine="auto")
        t0 = time.perf_counter()
        admission.prevalidate_commitments(app_w, warm_raws)
        compile_s = time.perf_counter() - t0

        from celestia_app_tpu.da import commitment as commitment_mod

        cold_btxs, _ = blob_tx_set(b"co")
        cold_items = [(btx.blobs[0], btx) for btx in cold_btxs]
        # the commitment-validation component alone — the work the cache
        # eliminates from every phase (per-blob host subtree-root MMR +
        # byte-compare, the reference's ValidateBlobTx recompute):
        t0 = time.perf_counter()
        for blob, btx in cold_items:
            want = commitment_mod.create_commitment(blob, threshold)
            assert want is not None
        cold_s = time.perf_counter() - t0
        # and the whole validate_blob_tx (decode + gates + commitment), the
        # end-to-end per-phase cost:
        t0 = time.perf_counter()
        for btx in cold_btxs:
            blob_validation.validate_blob_tx(btx, threshold)  # per-tx host
        cold_full_s = time.perf_counter() - t0

        cached_btxs, cached_raws = blob_tx_set(b"ca")
        app_c = App(chain_id=chain, engine="auto")
        t0 = time.perf_counter()
        admission.prevalidate_commitments(app_c, cached_raws)
        dispatch_s = time.perf_counter() - t0
        cache = app_c.commitment_cache
        t0 = time.perf_counter()
        for btx in cached_btxs:
            blob = btx.blobs[0]
            got = cache.hit(cache.key(blob.namespace.raw, blob.share_version,
                                      blob.data, threshold))
            assert got is not None
        cached_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for btx in cached_btxs:
            blob_validation.validate_blob_tx(btx, threshold, cache=cache)
        cached_full_s = time.perf_counter() - t0

        cold_per_sec = n_blobs / cold_s
        cached_per_sec = n_blobs / cached_s
        print(json.dumps({
            "metric": "commitment_validate_per_sec",
            "value": round(cached_per_sec, 1),
            "unit": "blobs/s",
            "cold_per_sec": round(cold_per_sec, 1),
            "vs_cold": round(cached_per_sec / cold_per_sec, 2),
            "full_validate_per_sec": round(n_blobs / cached_full_s, 1),
            "full_validate_cold_per_sec": round(n_blobs / cold_full_s, 1),
            "full_vs_cold": round(cold_full_s / cached_full_s, 2),
            "pending_blobs": n_blobs,
            "blob_bytes": blob_lo_hi,
            "admission_dispatch_s": round(dispatch_s, 4),
            "incl_dispatch_per_sec": round(
                n_blobs / (dispatch_s + cached_s), 1),
            "compile_s": round(max(0.0, compile_s - dispatch_s), 2),
            "path": "one-batched-dispatch+cache-lookups vs per-tx-host",
            "backend": backend,
        }), flush=True)
    finally:
        # a failed run must not strand the temp block store or a
        # flock-holding App (review hardening)
        for a in (app, app_w, app_c):
            if a is not None:
                try:
                    a.close()
                except Exception:
                    pass
        shutil.rmtree(tmp, ignore_errors=True)


# -- mode registry (--list prints it) ----------------------------------------
# name -> (runner, emitted metrics, one-line description). The default
# invocation (no flag) runs the deadline-driven headline measurement
# (`extend_commit_128_ms`).
def measure_mesh() -> None:
    """Mesh-plane bench (--mesh). Three BENCH JSON lines:

      {"metric": "extend_commit_256_ms", ...}  one ODS -> device-resident
          entry (extend + NMT commit) through the mesh engine
          (parallel/mesh_engine.compute_entry_mesh: sharded shard_map
          pipeline, commitments fetched to host, EDS left on-mesh).
          k=256 is the target size; on the CPU fallback a smaller square
          is measured (GF(2^16) matmuls at k=256 take minutes of host
          time) and the JSON says so via "k"/"target_k".
      {"metric": "blocks_per_sec_batched", ...}  the produce path's
          multi-block batched dispatch (B squares per launch,
          device-resident entries) vs the per-block production pipeline
          (one dispatch + one full-EDS host fetch per block — what
          edscache.compute_entry's single-device path pays today).
          Counter-verified: "host_crossings_per_block" is the measured
          edscache.host_crossings delta per batched block (0 on the
          warmed produce path — nothing materializes until a proof is
          actually served). On the CPU fallback both paths run the same
          FLOPs on the same cores, so the dispatch-boundary cost the
          batching removes is modeled the way bench --sync models
          the network: an injected per-dispatch latency, LABELED
          "injected_rtt_ms" (default 70 ms on cpu-fallback — the
          reference e2e benchmark's BitTwister figure — 0 on real
          hardware, env CELESTIA_BENCH_MESH_RTT_MS); the uninjected
          ratio is also reported ("vs_per_block_raw").
      {"metric": "mesh_scaling_blocks_per_sec", ...}  device-count
          scaling curve of the same batched dispatch (1, 2, 4, ...
          devices; virtual CPU devices on the fallback).

    Pure-CPU runs are labeled "backend": "cpu-fallback" (FORMATS
    §12.2); sizes/batch via
    CELESTIA_BENCH_MESH_K / CELESTIA_BENCH_MESH_BATCH.
    """
    import jax

    from celestia_app_tpu.da import edscache
    from celestia_app_tpu.parallel import mesh as mesh_mod
    from celestia_app_tpu.parallel import mesh_engine, streaming
    from celestia_app_tpu.utils import telemetry

    devices = jax.devices()
    platform = devices[0].platform
    backend = "cpu-fallback" if platform == "cpu" else platform
    target_k = 256
    k = int(os.environ.get(
        "CELESTIA_BENCH_MESH_K", "256" if platform == "tpu" else "32"))
    batch = int(os.environ.get("CELESTIA_BENCH_MESH_BATCH", "8"))
    reps = int(os.environ.get("CELESTIA_BENCH_MESH_REPS", "3"))

    def _ods(seed: int) -> np.ndarray:
        o = np.random.default_rng(seed).integers(
            0, 256, size=(k, k, 512), dtype=np.uint8)
        o[..., :29] = 0
        o[..., 28] = 7
        return o

    def counters():
        return telemetry.snapshot().get("counters", {})

    def delta(c0, c1, key):
        return c1.get(key, 0) - c0.get(key, 0)

    # -- 1. extend+commit through the mesh engine ------------------------
    ods = _ods(0)
    edscache.compute_entry(ods, "mesh")  # compile + warm
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        entry = edscache.compute_entry(ods, "mesh")
        dt = (time.perf_counter() - t0) * 1e3
        best = dt if best is None else min(best, dt)
    mesh = mesh_engine.mesh_for(k)
    print(json.dumps({
        "metric": ("extend_commit_256_ms" if k == target_k
                   else f"extend_commit_{k}_ms"),
        "value": round(best, 3),
        "unit": "ms",
        "k": k,
        "target_k": target_k,
        "at_target_k": k == target_k,
        "devices": len(devices),
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "residency": entry.residency(),
        "backend": backend,
    }), flush=True)

    # -- 2. batched multi-block dispatch vs the per-block pipeline -------
    # its own square size: the dispatch-boundary effect needs per-block
    # compute small enough that the boundary is visible at all on one
    # core (k=8 on the fallback); real hardware measures the target size
    bk = int(os.environ.get(
        "CELESTIA_BENCH_MESH_BATCH_K",
        str(target_k) if platform == "tpu" else "8"))
    rtt_s = float(os.environ.get(
        "CELESTIA_BENCH_MESH_RTT_MS",
        "0" if platform == "tpu" else "70")) / 1e3

    def _ods_b(seed: int) -> np.ndarray:
        o = np.random.default_rng(seed).integers(
            0, 256, size=(bk, bk, 512), dtype=np.uint8)
        o[..., :29] = 0
        o[..., 28] = 7
        return o

    odses = [_ods_b(100 + i) for i in range(batch)]
    stack_b = np.stack(odses)
    # warm both paths' compiles out of the clock. The batched path uses
    # the engine-selection rules of the produce path itself: the mesh's
    # sharded pipeline when active for k (always on real multi-chip at
    # k>=256), the single-chip vmapped program otherwise — metric 3
    # isolates the mesh's own scaling.
    edscache.compute_entry(odses[0], "device")
    mesh_engine.compute_entries_batched(stack_b)

    # per-block production pipeline: one dispatch AND one full-EDS host
    # fetch per block (what the single-device compute_entry pays today —
    # the host-boundary cost ROADMAP item 4 names). Prover warm runs on
    # the background warmer thread in BOTH paths and is not clocked.
    def _measure(rtt: float):
        best_pb = best_b = None
        for _ in range(reps):
            t0 = time.perf_counter()
            for o in odses:
                edscache.compute_entry(o, "device")  # dispatch + fetch
                if rtt:
                    time.sleep(rtt)  # one boundary round-trip PER BLOCK
            dt = time.perf_counter() - t0
            best_pb = dt if best_pb is None else min(best_pb, dt)
        for _ in range(reps):
            t0 = time.perf_counter()
            mesh_engine.compute_entries_batched(stack_b)
            if rtt:
                time.sleep(rtt)  # one round-trip for the WHOLE batch
            dt = time.perf_counter() - t0
            best_b = dt if best_b is None else min(best_b, dt)
        return batch / best_pb, batch / best_b

    c0 = counters()
    raw_pb, raw_b = _measure(0.0)
    c1 = counters()
    if rtt_s:
        per_block_bps, batched_bps = _measure(rtt_s)
    else:
        per_block_bps, batched_bps = raw_pb, raw_b
    # crossings measured over the uninjected pass: reps batched runs +
    # reps*batch per-block runs; only the batched runs' entries are
    # device-resident, and nothing samples them, so the delta must be 0
    crossings = delta(c0, c1, "edscache.host_crossings") / (reps * batch)
    print(json.dumps({
        "metric": "blocks_per_sec_batched",
        "value": round(batched_bps, 3),
        "unit": "blocks/s",
        "k": bk,
        "batch": batch,
        "per_block_blocks_per_sec": round(per_block_bps, 3),
        "vs_per_block": round(batched_bps / max(per_block_bps, 1e-9), 2),
        "vs_per_block_raw": round(raw_b / max(raw_pb, 1e-9), 2),
        "injected_rtt_ms": rtt_s * 1e3,
        "host_crossings_per_block": round(crossings, 4),
        "extend_runs_per_block": round(
            delta(c0, c1, "da.extend_runs") / (2 * reps * batch), 3),
        "backend": backend,
    }), flush=True)

    # -- 3. device-count scaling curve -----------------------------------
    stack = np.stack([_ods(100 + i) for i in range(batch)])
    curve = []
    d = 1
    while d <= len(devices):
        if d == 1:
            from celestia_app_tpu.da import eds as eds_mod

            run = eds_mod.jitted_pipeline_batched(k)
        else:
            from celestia_app_tpu.parallel import sharded_eds

            run = sharded_eds.jitted_sharded_pipeline(
                mesh_mod.make_mesh(d, k=k, devices=devices[:d]), k)
        np.asarray(run(stack)[3])  # compile + warm (fetch, not b_u_r)
        best_d = None
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(run(stack)[3])
            dt = time.perf_counter() - t0
            best_d = dt if best_d is None else min(best_d, dt)
        curve.append({"devices": d,
                      "blocks_per_sec": round(batch / best_d, 3)})
        d *= 2
    print(json.dumps({
        "metric": "mesh_scaling_blocks_per_sec",
        "value": curve[-1]["blocks_per_sec"],
        "unit": "blocks/s",
        "k": k,
        "batch": batch,
        "scaling": curve,
        "backend": backend,
    }), flush=True)


def measure_scenario() -> None:
    """Scenario-plane bench (--scenario). One BENCH JSON line per
    (scenario, scheme) cell of the matrix — scheme ranging over EVERY
    registered wire id (rs2d-nmt, cmt-ldpc, pcmt-polar), so a new codec
    is judged under the identical seeded attacks by registration alone.
    Each line is the scenario's verdict (FORMATS §19.2):
    blocks_to_detection, liveness_gap_s, false_condemnation_rate,
    recovery_s, plus the event-trace digest — the determinism witness
    (same seed reprints identical lines).

    The matrix: honest (the zero-false-condemnation control),
    withholding at each scheme's recoverability threshold, committed
    incorrect coding escalated to a verified fraud proof, and a
    partition-heal churn — per scheme, all on one seeded virtual
    timeline per cell. Pure host/CPU work (consensus + sampling +
    repair at small k): no device involvement.

    The network-scale cells (ISSUE 18): `long-soak` (resource-churn
    soak under seeded PFB traffic + asymmetric per-message faults) and
    `fleet-scale` (1000+ continuation-driven lights over 1000+ virtual
    blocks, run TWICE per seed — the line carries
    verdict_bytes_identical) run on rs2d-nmt only: their subject is the
    scenario plane's scale and determinism, not the codec matrix.

    Knobs: CELESTIA_BENCH_SCENARIO_{VALIDATORS,LIGHTS,HEIGHTS,SEED},
    CELESTIA_BENCH_SCENARIOS (comma list to sub-select), and
    CELESTIA_BENCH_FLEET_{LIGHTS,HEIGHTS} for the fleet-scale cell."""
    import tempfile

    from celestia_app_tpu.sim import run_scenario, scenario_spec
    from celestia_app_tpu.sim.scenarios import verdict_bytes

    n_val = int(os.environ.get("CELESTIA_BENCH_SCENARIO_VALIDATORS", "8"))
    n_light = int(os.environ.get("CELESTIA_BENCH_SCENARIO_LIGHTS", "64"))
    heights = int(os.environ.get("CELESTIA_BENCH_SCENARIO_HEIGHTS", "5"))
    seed = int(os.environ.get("CELESTIA_BENCH_SCENARIO_SEED", "0"))
    fleet_lights = int(os.environ.get("CELESTIA_BENCH_FLEET_LIGHTS",
                                      "1000"))
    fleet_heights = int(os.environ.get("CELESTIA_BENCH_FLEET_HEIGHTS",
                                       "1000"))
    names = [s for s in os.environ.get(
        "CELESTIA_BENCH_SCENARIOS",
        "honest,withhold-threshold,incorrect-coding,partition-churn,"
        "long-soak,fleet-scale",
    ).split(",") if s]
    from celestia_app_tpu.da import codec as dacodec

    schemes = [dacodec.by_id(i).name for i in dacodec.registered_ids()]
    # the network-scale cells benchmark the scenario plane itself
    # (continuation fleet scale, soak churn, verdict determinism), not
    # the codec matrix — one scheme carries the claim
    single_scheme = {"long-soak", "fleet-scale"}
    for scenario in names:
        for scheme in (["rs2d-nmt"] if scenario in single_scheme
                       else schemes):
            if scenario == "fleet-scale":
                doc = scenario_spec(scenario, scheme=scheme, seed=seed,
                                    light_nodes=fleet_lights,
                                    heights=fleet_heights)
            elif scenario == "long-soak":
                doc = scenario_spec(scenario, scheme=scheme, seed=seed)
            else:
                doc = scenario_spec(scenario, scheme=scheme, seed=seed,
                                    validators=n_val,
                                    light_nodes=n_light,
                                    heights=heights)
            t0 = time.perf_counter()
            v = run_scenario(doc, workdir=tempfile.mkdtemp(
                prefix=f"bench-sim-{scenario}-"))
            wall = time.perf_counter() - t0
            line = {
                "metric": "scenario_verdict",
                "scenario": scenario,
                "scheme": scheme,
                "seed": seed,
                "validators": v["validators"],
                "light_nodes": v["light_nodes"],
                "heights_committed": v["heights_committed"],
                "blocks_to_detection": v["blocks_to_detection"],
                "liveness_gap_s": v["liveness_gap_s"],
                "false_condemnation_rate": v["false_condemnation_rate"],
                "recovery_s": v["recovery_s"],
                "light_halts": v["light_halts"],
                "unavailable_reports": v["unavailable_reports"],
                "events": v["events"],
                "trace_digest": v["trace_digest"],
                "sim_lights": v["sim_lights"],
                "sim_virtual_blocks": v["sim_virtual_blocks"],
                "peak_rss_bytes": v["peak_rss_bytes"],
                "wall_s": round(wall, 3),
                "backend": "host",
            }
            # per-op verdict blocks, present when the scenario arms them
            for block in ("traffic", "spam", "soak", "asym_msgs"):
                if v.get(block):
                    line[block] = v[block]
            if scenario == "fleet-scale":
                # the determinism claim IS the benchmark: same seed,
                # second full run, byte-identical canonical verdict
                t0 = time.perf_counter()
                v2 = run_scenario(doc, workdir=tempfile.mkdtemp(
                    prefix=f"bench-sim-{scenario}-"))
                line["rerun_wall_s"] = round(time.perf_counter() - t0, 3)
                line["verdict_bytes_identical"] = (
                    verdict_bytes(v) == verdict_bytes(v2))
            print(json.dumps(line), flush=True)


MODES = {
    "block": (measure_block,
              "block_e2e_ms, blocks_per_sec, first_sample_after_commit_ms",
              "extend-once block lifecycle: e2e commit + first sample"),
    "proofs": (measure_proofs, "share_proofs_per_sec_128",
               "batched share-proof serving throughput at k=128"),
    "admission": (measure_admission,
                  "sig_verify_per_sec, mempool_ingest_txs_per_sec",
                  "batched on-device secp256k1 + two-phase tx admission"),
    "repair": (measure_repair, "repair_128_ms, befp_verify_ms",
               "decode plane: 1/4-erased EDS repair + BEFP verification"),
    "codec": (measure_codec,
              "encode_ms, proof_bytes_per_sample, "
              "samples_to_99_confidence, repair_ms, fraud_verify_ms "
              "(per registered scheme) + rs_tunable_sweep",
              "DA commitment schemes head to head: 2D-RS+NMT vs CMT "
              "vs polar PCMT, plus the tunable-rate RS sweep"),
    "mempool": (measure_mempool,
                "mempool_ingest_txs_per_sec, mempool_reap_ms",
                "CAT pool ingest + priority reap throughput"),
    "chaos": (measure_chaos, "crash_replay_ms, chaos_heal_recovery_s",
              "fault plane: WAL crash replay + partition-heal liveness"),
    "scenario": (measure_scenario,
                 "scenario_verdict: blocks_to_detection, liveness_gap_s, "
                 "false_condemnation_rate, recovery_s, sim_lights, "
                 "sim_virtual_blocks, peak_rss_bytes (per scenario x "
                 "registered scheme: rs2d-nmt, cmt-ldpc, pcmt-polar) + "
                 "the long-soak and fleet-scale network cells",
                 "scenario plane: seeded virtual-time adversarial matrix "
                 "over the validator + light-node fleet, judged on "
                 "every registered wire id under identical seeds, plus "
                 "1000-light fleet determinism and long-horizon soak"),
    "sync": (measure_sync,
             "state_sync_join_s, blocksync_blocks_per_sec, "
             "snapshot_serve_ms",
             "sync plane: chunked state-sync join vs full replay"),
    "txsim": (measure_txsim,
              "blobs_per_sec, admission_commit_p99_ms, "
              "commitment_validate_per_sec",
              "traffic plane: sustained confirm-polled blob load at a "
              "live devnet + cached vs cold admission commitment "
              "validation"),
    "serve": (measure_serve,
              "samples_served_per_sec, sampler_round_trips_per_height, "
              "p99_sample_ms, pack_hit_ratio",
              "serving plane: pack-served vs live sampling under "
              "thousand-sampler load"),
    "read": (measure_read,
             "namespace_queries_per_sec, batched_vs_single_ratio, "
             "pack_vs_live_ratio, p99 per mode",
             "read plane: batched vs per-request namespace resolution "
             "+ static blob packs under concurrent followers"),
    "analyze": (measure_analyze,
                "analyze_cold_wall_s, analyze_warm_wall_s, "
                "analyze_effects_cold_wall_s, analyze_effects_warm_wall_s",
                "full-tree static analysis (call-graph taint + effect "
                "system) cold vs incremental-cache warm"),
    "obs": (measure_obs, "obs_overhead_pct",
            "observability overhead on the produce-block path"),
    "slo": (measure_slo,
            "slo_verdict_pass (+ deterministic verdict-bytes check)",
            "fleet-wide SLO verdict engine (tools/fleetmon.py) judged "
            "against a live, then quiesced, 2-validator HTTP devnet"),
    "compare": (run_compare,
                "per-metric trajectory across committed BENCH_*.json "
                "rounds; exit 2 on regression beyond tolerance",
                "bench history differ (tools/benchdiff.py): align "
                "rounds, flag regressions, CI-usable exit code"),
    "mesh": (measure_mesh,
             "extend_commit_256_ms, blocks_per_sec_batched, "
             "mesh_scaling_blocks_per_sec",
             "mesh plane: sharded extend+commit, multi-block batched "
             "dispatch with device-resident entries, device scaling"),
    "stream-mesh": (measure_stream_mesh,
                    "stream_mesh blocks/s (stderr+json)",
                    "multi-device sharded streaming pipeline"),
    "stream-batched": (_stream_batched, "stream_batched blocks/s",
                       "single-device batched block streaming"),
    "stream": (measure_stream, "stream blocks/s",
               "single-square streaming pipeline"),
    "stages": (measure_stages, "per-stage device timings (stderr)",
               "per-stage device timings of the extend+commit pipeline"),
    "measure-baseline": (_save_baseline,
                         "writes bench_baseline.json (cpu_ms, data_root)",
                         "record the native CPU baseline reference"),
}


if __name__ == "__main__":
    main()
