#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the validator's block path
still starts, and is right, on a TPU.

One process, the entry points an operator calls, sizes an operator would
call real:

  device      import the package, then JAX; require a TPU.
  validator   App(engine="device") + Node + SampleCore: blocks of PFB
              traffic that lay out a 64x64 square (the mainnet default
              max square) through broadcast_txs -> produce_block
              (CheckTx -> Prepare -> Process -> Finalize -> Commit), then
              DAS samples and a batched namespace read, all verified
              against the committed data root.
  host_ref    the identical txs through App(engine="host"): data roots,
              every row/column root and every app hash byte-identical.
  hard_cap    a seeded 128x128 ODS through compute_entry on "device" and
              "host": EDS bytes, axis roots, data root equal; one cell
              proved by the device prover and verified.
  counters    no fallback fired on the device-engine phases.

`--chips 4` runs ONLY the sharded path and what it is compared with: a
seeded k=256 ODS through compute_entry(ods, "device") on four chips (the
sharding rule takes k >= 256 there: parallel/mesh_engine.py), the
one-chip program on chip 0 and the host engine; the three data roots
equal, the resident entry spread over four devices, and 16 cells proved
on the chips from the resident square and the mesh entry's sharded level
passes (row and column axis: one gather each) equal to the host engine's
proofs, the entry still "device" and the square never brought down.

Any failed check or exception ends the run with a traceback and a
non-zero exit; no phase is skipped over. The LAST stdout line of a
passing run is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
`--rehearse-cpu` (tiny sizes, no TPU gate) exists to debug the control
flow off the chip and always ends `{"ok": false, "rehearsal": "cpu"}`.
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

T0 = 1_700_000_000.0

FALLBACK_COUNTERS = (
    "app.device_path_fallback",
    "mesh.engine_fallbacks",
    "mesh.unavailable",
    "blob.device_fallbacks",
    "admission.prevalidate_errors",
)


def emit(**doc) -> None:
    print(json.dumps(doc), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class Phase:
    """One phase line: total seconds, seconds to the first result and
    seconds for the rest (a warm compile cache shows as first_s
    collapsing), plus whatever the phase checked."""

    def __init__(self, name: str):
        self.name = name
        self.checked: dict = {}
        self._first = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def first_result(self) -> None:
        if self._first is None:
            self._first = time.perf_counter() - self._t0

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return  # the traceback IS the report; no ok line follows
        total = time.perf_counter() - self._t0
        first = total if self._first is None else self._first
        emit(phase=self.name, seconds=round(total, 3),
             first_s=round(first, 3), rest_s=round(total - first, 3),
             **self.checked)


class DeviceCounters:
    """Counter deltas summed over the device-engine sections only (the
    telemetry registry is process-global, and the host reference counts
    its own extends and signature batches into the same names)."""

    def __init__(self):
        self.total: dict[str, int] = {}

    @contextlib.contextmanager
    def section(self):
        from celestia_app_tpu.utils import telemetry

        c0 = dict(telemetry.snapshot()["counters"])
        yield
        c1 = telemetry.snapshot()["counters"]
        for name, v in c1.items():
            d = v - c0.get(name, 0)
            if d:
                self.total[name] = self.total.get(name, 0) + d

    def get(self, name: str) -> int:
        return self.total.get(name, 0)


def seeded_ods(k: int, seed: int):
    """A (k, k, 512) square of seeded bytes under sorted user namespaces
    (row-major non-decreasing, several shares each) — what a laid-out
    block looks like to the pipeline."""
    import numpy as np

    from celestia_app_tpu import appconsts

    rng = np.random.default_rng(seed)
    ods = rng.integers(0, 256, size=(k, k, appconsts.SHARE_SIZE),
                       dtype=np.uint8)
    ns = appconsts.NAMESPACE_SIZE
    ids = (np.arange(k * k, dtype=np.uint32) // 7 + 1).reshape(k, k)
    ods[..., :ns] = 0
    for b in range(4):
        ods[..., ns - 1 - b] = (ids >> (8 * b)) & 0xFF
    return ods


# ---------------------------------------------------------------------------
# one chip: the validator path
# ---------------------------------------------------------------------------


def build_traffic(chain_id: str, seed: int, blocks: int, senders: int,
                  blob_bytes: int, n_namespaces: int):
    """(privs, per-block list of raw BlobTxs, namespaces). Every block
    carries one PFB per sender (so `senders` signatures per block — one
    admission bucket), blobs spread over `n_namespaces` namespaces."""
    import numpy as np

    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.chain.modules import estimate_pfb_gas
    from celestia_app_tpu.client.tx_client import Signer
    from celestia_app_tpu.da.blob import Blob
    from celestia_app_tpu.da.namespace import Namespace

    rng = np.random.default_rng(seed)
    privs = [PrivateKey.from_seed(b"smoke-%d-%d" % (seed, i))
             for i in range(senders)]
    signer = Signer(chain_id)
    addrs = [signer.add_account(p, number=i) for i, p in enumerate(privs)]
    namespaces = [Namespace.v0(b"smoke" + bytes([seed % 256, i + 1]))
                  for i in range(n_namespaces)]
    gas = 2 * estimate_pfb_gas([blob_bytes])
    rounds = []
    for _ in range(blocks):
        raws = []
        for i, a in enumerate(addrs):
            blob = Blob(namespaces[i % n_namespaces],
                        rng.integers(0, 256, blob_bytes,
                                     dtype=np.uint8).tobytes())
            raws.append(signer.create_pay_for_blobs(
                a, [blob], fee=gas, gas_limit=gas))
            signer.accounts[a].sequence += 1
        rounds.append(raws)
    return privs, rounds, namespaces


def new_validator(chain_id: str, engine: str, privs, data_dir: str):
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.node import Node
    from celestia_app_tpu.das.server import SampleCore

    addrs = [p.public_key().address() for p in privs]
    app = App(chain_id=chain_id, engine=engine, data_dir=data_dir)
    app.init_chain({
        "time_unix": T0,
        "accounts": [{"address": a.hex(), "balance": 10**15}
                     for a in addrs],
        "validators": [{"operator": addrs[0].hex(), "power": 10}],
    })
    node = Node(app)
    core = node.attach_das_core(SampleCore(app))
    return app, node, core


def run_blocks(app, node, rounds, phase: Phase):
    """Each round through broadcast_txs -> produce_block. Returns one
    record per height: header fields, the cached entry's axis roots and
    the app hash."""
    out = []
    for i, raws in enumerate(rounds):
        t_block = time.perf_counter()
        results = node.broadcast_txs(raws)
        bad = [r.log for r in results if r.code != 0]
        check(not bad, f"CheckTx refused {len(bad)} txs: {bad[:2]}")
        block, tx_results = node.produce_block(t=T0 + 1 + i)
        check(len(block.txs) == len(raws),
              f"height {block.header.height}: {len(block.txs)} of "
              f"{len(raws)} txs made it into the block")
        bad = [r.log for r in tx_results if r.code != 0]
        check(not bad, f"DeliverTx failed for {len(bad)} txs: {bad[:2]}")
        entry = app.eds_cache.lookup_root(block.header.data_hash)
        check(entry is not None, "committed square is not in the EDS cache")
        out.append({
            "height": block.header.height,
            "square_size": block.header.square_size,
            "data_hash": block.header.data_hash,
            "row_roots": entry.dah.row_roots,
            "col_roots": entry.dah.col_roots,
            "app_hash": app.last_app_hash,
            "host_bytes_crossed": app.last_host_bytes_crossed,
            "seconds": round(time.perf_counter() - t_block, 3),
        })
        phase.first_result()
    check(app.da_warmer.wait_idle(600), "prover warmer did not go idle")
    return out


def verify_samples(core, record, seed: int, n: int) -> int:
    """n DAS samples through the attached core, each verified against
    the DAH that hashes to the committed data root."""
    import numpy as np

    from celestia_app_tpu.da import codec, sampling
    from celestia_app_tpu.das.daser import DASer

    height = record["height"]
    # raises unless the served roots hash to the committed data root
    dah = codec.get("rs2d-nmt").commitments_from_doc(
        core.header(height), record["data_hash"].hex(),
        record["square_size"])
    width = len(dah.row_roots)
    rng = np.random.default_rng(seed)
    cells = [(int(rng.integers(0, width)), int(rng.integers(0, width)))
             for _ in range(n)]
    docs = core.sample_many(height, cells)["samples"]
    for (row, col), doc in zip(cells, docs):
        check("error" not in doc, f"sample ({row},{col}) refused: {doc}")
        share, proof = DASer._decode_sample(doc)  # the light node's decoder
        check(sampling.verify_sample(dah, row, col, share, proof),
              f"sample ({row},{col}) at height {height} failed to verify")
    return len(docs)


def verify_namespace_reads(core, record, namespaces) -> dict:
    """One batched namespace read (the device search of
    da/namespace_device.py behind BlobCore.namespaces_many): every
    present namespace complete, one absent namespace proved absent."""
    from celestia_app_tpu.chain.query import share_proof_from_json
    from celestia_app_tpu.da import namespace_data as nsd
    from celestia_app_tpu.da.dah import DataAvailabilityHeader
    from celestia_app_tpu.da.namespace import Namespace
    from celestia_app_tpu.das.blob_server import BlobCore

    height = record["height"]
    dah = DataAvailabilityHeader(row_roots=record["row_roots"],
                                 col_roots=record["col_roots"])
    absent = Namespace.v0(b"smoke-none")
    queries = [ns.raw for ns in namespaces] + [absent.raw]
    out = BlobCore(core).namespaces_many(
        [{"height": height, "namespace": q.hex()} for q in queries])
    shares = 0
    for q, doc in zip(queries, out["queries"]):
        check("error" not in doc, f"namespace read refused: {doc}")
        check(doc["data_root"] == record["data_hash"].hex(),
              "namespace read names another data root")
        data = nsd.NamespaceData(
            namespace=q,
            shares=[base64.b64decode(s) for s in doc["shares"]],
            proof=(share_proof_from_json(doc["proof"])
                   if doc["proof"] else None),
        )
        check(nsd.verify_namespace_data(dah, q, data),
              f"namespace {q.hex()[-8:]} proof failed to verify")
        check(doc["present"] == (q != absent.raw),
              f"namespace {q.hex()[-8:]} presence is wrong")
        shares += len(data.shares)
    return {"namespaces_read": len(queries), "namespace_shares": shares}


def run_one_chip(args, sizes, counters: DeviceCounters) -> None:
    import numpy as np

    from celestia_app_tpu.da import edscache, sampling

    chain_id = "chip-smoke"
    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        privs, rounds, namespaces = build_traffic(
            chain_id, args.seed, sizes["blocks"], sizes["senders"],
            sizes["blob_bytes"], sizes["namespaces"])

        with Phase("validator") as ph, counters.section():
            app, node, core = new_validator(
                chain_id, "device", privs, os.path.join(tmp, "device"))
            dev = run_blocks(app, node, rounds, ph)
            squares = sorted({r["square_size"] for r in dev})
            check(squares == [sizes["square"]],
                  f"blocks laid out squares {squares}, "
                  f"wanted {sizes['square']}")
            sampled = sum(verify_samples(core, r, args.seed, 8)
                          for r in dev)
            reads = verify_namespace_reads(core, dev[-1], namespaces)
            ph.checked.update(
                engine="device", blocks=len(dev), square_size=squares[0],
                txs_per_block=sizes["senders"],
                blob_bytes_per_block=sizes["senders"] * sizes["blob_bytes"],
                samples_verified=sampled, **reads,
                host_bytes_crossed_per_block=[
                    r["host_bytes_crossed"] for r in dev],
                block_seconds=[r["seconds"] for r in dev],
            )
            app.close()

        with Phase("host_ref") as ph:
            happ, hnode, _hcore = new_validator(
                chain_id, "host", privs, os.path.join(tmp, "host"))
            ref = run_blocks(happ, hnode, rounds, ph)
            for d, h in zip(dev, ref):
                for key in ("height", "square_size", "data_hash",
                            "row_roots", "col_roots", "app_hash"):
                    check(d[key] == h[key],
                          f"height {d['height']}: {key} differs between "
                          "the device and host engines")
            ph.checked.update(
                engine="host", heights_equal=len(ref),
                block_seconds=[r["seconds"] for r in ref],
                data_roots=[r["data_hash"].hex() for r in ref],
                app_hashes=[r["app_hash"].hex() for r in ref],
            )
            happ.close()

        k = sizes["hard_cap"]
        ods = seeded_ods(k, args.seed)
        with Phase("hard_cap") as ph:
            with counters.section():
                entry = edscache.compute_entry(ods, "device")
                ph.first_result()
            ref_entry = edscache.compute_entry(ods, "host")
            check(np.array_equal(entry.eds.squares, ref_entry.eds.squares),
                  f"k={k}: EDS bytes differ between device and host")
            check(entry.dah.row_roots == ref_entry.dah.row_roots
                  and entry.dah.col_roots == ref_entry.dah.col_roots,
                  f"k={k}: axis roots differ between device and host")
            check(len(entry.dah.row_roots) == 2 * k
                  and entry.data_root == ref_entry.data_root,
                  f"k={k}: data root differs between device and host")
            with counters.section():
                prover = entry.get_prover()
            row, col = k + 3, 2 * k - 5  # a parity-quadrant cell
            share, proof = prover.prove_cell(row, col)
            check(sampling.verify_sample(entry.dah, row, col, share, proof),
                  f"k={k}: proved cell failed to verify")
            ph.checked.update(k=k, eds_shape=list(entry.eds.squares.shape),
                              data_root=entry.data_root.hex(),
                              cell_proved=[row, col])

        with Phase("counters") as ph:
            fired = {n: counters.get(n) for n in FALLBACK_COUNTERS}
            check(not any(fired.values()), f"a fallback fired: {fired}")
            check(counters.get("admission.batch_dispatches") > 0,
                  "no batched signature dispatch happened")
            check(counters.get("blob.device_batches") > 0,
                  "the namespace search never ran on the device")
            want = sizes["blocks"] + 1
            check(counters.get("da.extend_runs") == want,
                  f"da.extend_runs {counters.get('da.extend_runs')} != "
                  f"{want} (one per block, one for the hard cap)")
            ph.checked.update(
                fallbacks=fired,
                admission_batch_dispatches=counters.get(
                    "admission.batch_dispatches"),
                admission_batch_lanes=counters.get("admission.batch_lanes"),
                da_extend_runs=counters.get("da.extend_runs"),
                jax_compilations=counters.get("jax.compilations"),
            )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# four chips: the sharded path and what it is compared with
# ---------------------------------------------------------------------------


def run_four_chips(args, sizes, counters: DeviceCounters) -> None:
    import jax
    import numpy as np

    from celestia_app_tpu.da import edscache
    from celestia_app_tpu.da import eds as eds_mod
    from celestia_app_tpu.parallel import mesh_engine

    k = sizes["mesh_k"]
    ods = seeded_ods(k, args.seed)
    n_dev = len(jax.devices())
    if args.rehearse_cpu:
        # the rehearsal's small square shards as k=256 does on the chips
        mesh_engine.MESH_MIN_K = k

    with Phase("mesh") as ph, counters.section():
        entry = edscache.compute_entry(ods, "device")
        ph.first_result()
        check(isinstance(entry, edscache.DeviceEntry),
              "the device engine returned a host entry")
        devices = entry._eds_dev.sharding.device_set
        check(len(devices) == n_dev,
              f"the resident EDS sits on {len(devices)} of {n_dev} devices")
        ph.checked.update(
            k=k, engine="device", data_root=entry.data_root.hex(),
            eds_shape=list(entry._eds_dev.shape), eds_devices=len(devices),
            shard_shape=list(
                entry._eds_dev.addressable_shards[0].data.shape),
        )

    with Phase("one_chip_ref") as ph:
        out = eds_mod.jitted_pipeline(k)(
            jax.device_put(ods, jax.devices()[0]))
        root = bytes(jax.device_get(out[3]))
        ph.first_result()
        rows = tuple(bytes(r) for r in jax.device_get(out[1]))
        cols = tuple(bytes(c) for c in jax.device_get(out[2]))
        check(root == entry.data_root,
              f"k={k}: mesh and one-chip data roots differ")
        check(rows == entry.dah.row_roots and cols == entry.dah.col_roots,
              f"k={k}: mesh and one-chip axis roots differ")
        ph.checked.update(k=k, data_root=root.hex(),
                          devices=len(out[0].sharding.device_set))

    with Phase("host_ref") as ph:
        ref = edscache.compute_entry(ods, "host")
        check(ref.data_root == entry.data_root
              and ref.dah.row_roots == entry.dah.row_roots
              and ref.dah.col_roots == entry.dah.col_roots,
              f"k={k}: mesh and host commitments differ")
        ph.checked.update(k=k, engine="host",
                          data_root=ref.data_root.hex())

    with Phase("mesh_samples") as ph, counters.section():
        # the level passes over the square where it lies, sharded: on the
        # chips a plain jit of them is refused (a Mosaic kernel outside a
        # shard_map), which no CPU run can show
        entry.warm()
        ph.first_result()
        width = 2 * k
        cells = [(int(r), int(c)) for r, c in np.random.default_rng(
            args.seed).integers(0, width, size=(16, 2))]
        # the sixteen cells and their proof nodes cut on the chips: the
        # mesh entry has no host copy, and none is made
        for col in (False, True):
            got = entry.prove_cells(cells, col=col)
            want = ref.prove_cells(cells, col=col)
            for (r, c), g, w in zip(cells, got, want):
                check(g == w, f"k={k}: gathered and host "
                      f"{'column' if col else 'row'} proofs of ({r}, {c}) "
                      "differ")
        check(entry.residency() == "device",
              "proving sixteen cells brought the mesh entry's square down")
        ph.checked.update(
            k=k, samples=len(cells), axes=["row", "col"],
            residency=entry.residency(),
            level_devices=len(entry._levels_dev[0][0].sharding.device_set))

    with Phase("counters") as ph:
        fired = {n: counters.get(n) for n in FALLBACK_COUNTERS}
        check(not any(fired.values()), f"a fallback fired: {fired}")
        check(counters.get("da.extend_runs") == 1,
              "the mesh phase did not run exactly one extend")
        check(counters.get("mesh.sharded_level_passes") == 2,
              "the two level passes did not run sharded")
        eds_down = counters.get('xfer.d2h_calls{site="edscache.eds"}')
        check(eds_down == 0,
              f"the square came down {eds_down} times (xfer.d2h:edscache.eds)")
        check(counters.get('xfer.d2h_calls{site="proof.gather"}') == 2,
              "the two gathers did not come down through proof.gather")
        ph.checked.update(fallbacks=fired,
                          da_extend_runs=counters.get("da.extend_runs"))


# ---------------------------------------------------------------------------

REAL = {
    # 64 PFBs x 58 shares (478 + 57*482 bytes) + the PFB txs themselves
    # lay out 3.7k of the 4096 shares of a 64x64 square: 1.79 MB of blobs
    "blocks": 3, "senders": 64, "blob_bytes": 478 + 57 * 482,
    "namespaces": 8, "square": 64, "hard_cap": 128, "mesh_k": 256,
}
REHEARSAL = {
    "blocks": 2, "senders": 16, "blob_bytes": 478 + 2 * 482,
    "namespaces": 4, "square": 8, "hard_cap": 16, "mesh_k": 16,
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes, no TPU gate, never prints ok:true")
    args = ap.parse_args()
    if args.rehearse_cpu and args.chips > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.chips}").strip()

    t_start = time.perf_counter()
    with Phase("device") as ph:
        # the package first: it places the compile cache before JAX loads
        import celestia_app_tpu

        import jax

        devices = jax.devices()
        dev = {"platform": devices[0].platform,
               "kind": devices[0].device_kind, "count": len(devices)}
        ph.checked.update(
            device=dev, jax=jax.__version__,
            package=celestia_app_tpu.__version__,
            compile_cache=jax.config.jax_compilation_cache_dir,
        )
        if not args.rehearse_cpu:
            check(dev["platform"] == "tpu",
                  f"no TPU: JAX's first device is {dev['platform']!r} "
                  f"({dev['kind']}); this script measures nothing elsewhere")
        check(dev["count"] == args.chips,
              f"--chips {args.chips} needs exactly {args.chips} devices, "
              f"JAX reports {dev['count']}")

    sizes = REHEARSAL if args.rehearse_cpu else REAL
    counters = DeviceCounters()
    if args.chips == 1:
        run_one_chip(args, sizes, counters)
    else:
        run_four_chips(args, sizes, counters)

    emit(phase="total", seconds=round(time.perf_counter() - t_start, 3),
         seed=args.seed, chips=args.chips)
    if args.rehearse_cpu:
        emit(ok=False, rehearsal="cpu")
        return 1
    emit(ok=True, device=dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
