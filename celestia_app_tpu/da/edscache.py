"""Block plane: the extend-once lifecycle's content-addressed EDS/DAH cache.

The node used to pay the full RS-extend + NMT pipeline up to THREE times
per height: once at PrepareProposal (chain/app.py, result discarded), once
at ProcessProposal (the proposer re-validating its own block, and every
follower validating the gossiped one), and once more when the first light
client sampled the height (chain/query.build_prover rebuilding the square
from raw txs). Amortizing the RS/commitment work across protocol phases is
exactly the cost lever arXiv:2201.08261 optimizes for RS-based DA
protocols; this module is that amortization:

- **Content addressing.** Entries are keyed by ``sha256(ODS share bytes)``
  — a pure function of the data square itself, never of a height or a
  header field a peer claimed. A follower validating a gossiped proposal
  and the proposer validating its own construct the identical ODS from the
  txs, so both hit the same entry; a Byzantine header can never poison the
  cache, because the cached value is a pure function of the key (a wrong
  ``data_hash`` still fails the header comparison — the cache only changes
  who pays for recomputing the truth).

- **Engine-gated, bit-identical.** ``compute_entry`` is THE one
  ODS -> (EDS, row/col roots, data root) implementation for both the
  device path (da/eds.jitted_pipeline, one fused dispatch) and the host
  path (utils/fast_host BLAS+hashlib) — previously copy-pasted between
  ``App._pipeline``, ``chain/query.build_prover``, and
  ``das/server._build_prover``. The two engines are pinned byte-identical
  (tests/test_fast_host.py, tests/test_edscache.py), so a cache populated
  by either serves the other.

- **Lazy provers + background warmup.** Each entry carries its
  BlockProver (and the transposed col-axis prover BEFP escalation needs)
  built at most once, on demand, under the entry's own lock — or ahead of
  demand by ``ProverWarmer``, the single coalescing daemon thread
  ``App.commit`` hands each committed entry to. The warmer builds the
  provers and fans the entry out to registered DAS serving planes
  (``das/server.SampleCore.seed_cache_entry``) WITHOUT holding any
  service/consensus lock, so the first light-client sample after a commit
  is pure index arithmetic instead of a rebuild + re-extend.

- **Mesh engine + device residency (the mesh plane).** ``compute_entry``
  gains a fourth engine: ``"mesh"`` dispatches through the sharded
  shard_map pipeline (parallel/mesh_engine.py — k rows split over the
  ``seq`` ICI axis, bit-identical to the single-device program), and the
  auto/device engines route any square of ``k >= CELESTIA_MESH_MIN_K``
  (default 256) there automatically. The produce path's batched dispatch
  (chain/producer.py) inserts the same entry type, so an
  extend→commit→prover-warm chain hands device arrays, not bytes,
  between stages.

- **One entry class per engine class.** Every device-class engine —
  mesh, batched and the single-device program every k <= 128 square
  runs — returns a ``DeviceEntry``: the EDS and (once warmed) the NMT
  level arrays stay on device, only the commitment (4k axis roots + the
  data root) crosses inside ``compute_entry``, the provers' level passes
  read the resident array, and host bytes materialize lazily — only
  when a host prover or the square itself is asked for — each
  materialization counting ``edscache.host_crossings``. A batch of
  sampled cells is proved where the entry's bytes are
  (``prove_cells``): from the host copy where one exists or was
  started, else cut on the chip(s) by one program that brings only the
  shares and their proof nodes down. The single-device engine also
  STARTS the square's host copy right after the run
  (``obs/xfer.HostFetch``): a served height needs those bytes, and
  started there they land in the shadow of process → commit instead of
  blocking ``prepare_proposal``. ``EdsCacheEntry`` is the host engine's
  entry (and ``auto``'s counted fallback).

Telemetry: ``da.extend_runs`` (every real pipeline dispatch),
``edscache.{hits,misses,evictions,seeded}``, ``edscache.warm_coalesced``
(a pending warm superseded by a newer commit), ``edscache.warm_errors``,
``edscache.host_crossings`` (device-resident arrays materialized to
host), ``edscache.eds_fetch_started`` / ``eds_fetch_waited`` /
``eds_fetch_ready`` (a host copy of the square started by the
single-device engine; its first read found it still moving and blocked
/ found it landed). Wire/metric formats in docs/FORMATS.md §14 and §18; design in
docs/DESIGN.md "The block plane" and "The mesh plane".
"""

from __future__ import annotations

import collections
import hashlib
import os
import threading
import weakref

import numpy as np

from celestia_app_tpu import obs
from celestia_app_tpu.da.dah import (
    DataAvailabilityHeader,
    ExtendedDataSquare,
)
from celestia_app_tpu.obs import xfer
from celestia_app_tpu.utils import telemetry

# bounded LRU: at k=128 one entry holds ~32 MB of EDS plus ~24 MB of lazy
# row+col level arrays once warmed, so the default stays small — the
# lifecycle only ever needs the in-flight height plus a short serving tail
DEFAULT_MAX_ENTRIES = int(os.environ.get("CELESTIA_EDSCACHE_ENTRIES", "4"))

# the entry-count cap alone stops bounding memory once big squares are
# admitted: a k=512 entry is ~512 MB of EDS before levels, so four of
# them would silently pin >2 GB. The LRU is therefore ALSO bytes-aware:
# entries are charged a conservative static estimate (EDS bytes x2 —
# the x2 covers the row+col level arrays a warmed entry carries; see
# entry_nbytes) against CELESTIA_EDSCACHE_BYTES, and eviction runs while
# EITHER cap is exceeded. The newest entry is always retained even when
# it alone exceeds the byte budget (the in-flight height must be
# servable); at k <= 128 the default budget never binds, so historical
# behavior is unchanged.
DEFAULT_MAX_BYTES = int(os.environ.get("CELESTIA_EDSCACHE_BYTES",
                                       str(1 << 30)))


telemetry.set_help(
    "edscache.eds_fetch_started",
    "host copies of an extended square started right after its extend",
)
telemetry.set_help(
    "edscache.eds_fetch_waited",
    "first host reads of a square that found its copy still moving",
)
telemetry.set_help(
    "edscache.eds_fetch_ready",
    "first host reads of a square whose copy had already landed",
)


def entry_nbytes(entry) -> int:
    """Conservative byte charge for one cached entry: (2k)^2 x 512 of
    EDS, doubled for the per-orientation NMT level arrays a warmed entry
    holds (leaf level alone is (2k)^2 x 90 per orientation; inner levels
    add half that again). Entry types that know better (da/cmt.CmtEntry
    and friends) can expose their own ``nbytes()``."""
    own = getattr(entry, "nbytes", None)
    if callable(own):
        return int(own())
    two_k = 2 * entry.k
    return two_k * two_k * 512 * 2


def cache_key(ods: np.ndarray, scheme: str = "rs2d-nmt") -> bytes:
    """Content address of an original data square: sha256 over the ODS
    share bytes in row-major order. Shares are fixed-size (512 B) and the
    count is k*k, so the byte string determines the geometry — two squares
    collide iff they are the same square.

    Zero-copy: the usual producers (`Square.ods`, dah.shares_to_ods) hand
    over C-order arrays, so hashing goes straight over the buffer
    (`arr.data`) with no 8 MB `.tobytes()` staging copy at k=128; `ascontiguousarray` is a
    no-op then and only copies for exotic layouts. The hash itself is
    single-digit ms at k=128 (OpenSSL SHA-NI) against the 2-3 full
    extend+NMT dispatches per height it deduplicates.

    Non-default codec-plane schemes (da/codec.py) prefix their name so
    the same square encoded under two schemes occupies two entries;
    the default scheme's keys stay byte-identical to pre-plane keys."""
    arr = np.ascontiguousarray(ods)
    h = hashlib.sha256()
    if scheme != "rs2d-nmt":
        h.update(scheme.encode() + b"\x00")
    h.update(arr.data)
    return h.digest()


class EdsCacheEntry:
    """One cached extension: ``(eds, row_roots, col_roots, data_root)``
    plus the lazily-built proof machinery. The extension fields are
    immutable after construction; the provers build at most once, under
    the entry's own lock (never a service/consensus lock), so concurrent
    samplers of a fresh entry pay one level pass between them.

    The ``scheme``/``k``/``warm`` surface is the codec plane's common
    entry contract (da/codec.py): non-default schemes cache their own
    entry types (e.g. da/cmt.CmtEntry) in the same EdsCache."""

    scheme = "rs2d-nmt"

    def __init__(self, eds: ExtendedDataSquare,
                 dah: DataAvailabilityHeader, data_root: bytes,
                 levels=None):
        self._eds = eds
        self.dah = dah
        self.data_root = data_root
        # host-computed row NMT levels (utils/fast_host shape), carried
        # when the host pipeline produced them anyway; None on the device
        # path, where the prover's jitted level pass recomputes them
        self.levels = levels
        # one lock PER prover: a sampler needing the (already-built) row
        # prover must never queue behind the warmer's in-progress col
        # level pass — the two builds are independent
        self._row_lock = threading.Lock()
        self._col_lock = threading.Lock()
        self._prover = None  # guarded-by: _row_lock
        self._col_prover = None  # guarded-by: _col_lock

    @property
    def eds(self) -> ExtendedDataSquare:
        """The host extended square. A plain attribute read here; the
        device-resident subclass overrides this with a lazy,
        crossing-counted materialization."""
        return self._eds

    def residency(self) -> str:
        """Where the entry's square bytes live: "host" for the classic
        entry; the device-resident subclass reports "device" until a
        proof/serve path materializes, then "device+host"."""
        return "host"

    def get_prover(self, engine: str = "auto"):
        """The row-axis BlockProver, built once (engine-gated)."""
        # the build-once lock EXISTS to serialize this first build (jit
        # compile included); samplers queue here instead of rebuilding
        with self._row_lock:  # lint: disable=blocking-under-lock
            if self._prover is None:
                self._prover = build_block_prover(
                    self.eds, self.dah, engine, levels=self.levels
                )
            return self._prover

    def _transposed_dah(self) -> DataAvailabilityHeader:
        """The header of the transposed square, whose ROW trees are this
        square's column trees — the leaf-namespace rule is
        transpose-invariant (parity iff outside Q0 survives
        (r,c)->(c,r)), so a col-axis prover is a row prover over the
        transposed pair. Shared by both col-prover builds (base and
        device-resident)."""
        return DataAvailabilityHeader(
            row_roots=self.dah.col_roots,
            col_roots=self.dah.row_roots,
        )

    def _transposed_square(self):
        """(eds_t, dah_t) for a prover that runs its own level pass: it
        uploads (or BLAS-reads) the transpose, so a C-order copy."""
        # the HOST entry class: self.eds.squares is numpy here (the
        # device-resident twin is handed its levels and takes a view)
        eds_t = ExtendedDataSquare(
            np.ascontiguousarray(np.swapaxes(self.eds.squares, 0, 1))  # lint: disable=xfer-reach
        )
        return eds_t, self._transposed_dah()

    def get_col_prover(self, engine: str = "auto"):
        """Column-axis prover (BEFP escalation serving): see
        _transposed_square — same batched level pass, no per-cell
        hashing."""
        # build-once serialization, same reasoning as get_prover
        with self._col_lock:  # lint: disable=blocking-under-lock
            if self._col_prover is None:
                eds_t, dah_t = self._transposed_square()
                self._col_prover = build_block_prover(eds_t, dah_t, engine)
            return self._col_prover

    def proves_on_host(self, col: bool = False) -> bool:
        """Whether `prove_cells` reads this orientation's proofs from host
        arrays: always, for the host entry."""
        return True

    def prove_cells(self, cells, col: bool = False, engine: str = "auto"):
        """[(share bytes, NmtRangeProof)] for a batch of EXTENDED-square
        cells (row, col), each under its row root — or, with `col`, its
        column root: cell (r, c) lives at (c, r) of the transpose, its
        proof covers leaf range [r, r+1) under col_roots[c]. Index
        arithmetic over the orientation's host prover."""
        if col:
            prover = self.get_col_prover(engine)
            return [prover.prove_cell(c, r) for r, c in cells]
        prover = self.get_prover(engine)
        return [prover.prove_cell(r, c) for r, c in cells]

    @property
    def k(self) -> int:
        return self.eds.width // 2

    def warm(self, engine: str = "auto") -> None:
        """Pre-build both provers (the warmer's per-scheme hook)."""
        self.get_prover(engine)
        self.get_col_prover(engine)

    def warmed(self) -> bool:
        # fixed acquisition order (row, then col) — no other path nests
        # the two locks, so no inversion is possible
        with self._row_lock:
            row_ready = self._prover is not None
        with self._col_lock:
            return row_ready and self._col_prover is not None


class DeviceEntry(EdsCacheEntry):
    """The entry of every device-class engine: its big arrays live on
    device.

    Construction hands over the device EDS (sharded over the mesh when
    the sharded pipeline built it) plus the HOST commitments — axis
    roots and data root are what every protocol phase compares, and at
    4k x 90 B they are not worth keeping remote. Everything else obeys
    the device-residency contract:

    - ``warm()`` runs the row+col NMT *level* passes on device, over
      the resident array (``jnp.swapaxes`` on the chip for the column
      orientation), and keeps the results there — the prover-warm stage
      never sends the square up again. A square whose rows are split
      over a mesh is read where it lies: the same passes inside a
      ``shard_map`` on that mesh (``_level_pass``), the level stacks
      left split by tree — a plain jit over it is refused by the TPU
      compiler, which partitions no Pallas kernel.
    - ``.eds`` / the provers materialize host bytes lazily, only when
      asked for (share-range and tx proofs, namespace reads, the pack
      builders); every device->host array fetch counts
      ``edscache.host_crossings``.
    - ``prove_cells`` — a sampler's batch — looks at what the entry
      holds: host bytes (a copy landed or started, a built prover) are
      read as ever; an entry whose square lives only on the chip(s)
      gathers each cell's share and sibling nodes there
      (``gather_cells``: da/proof_device's gather, inside a
      ``shard_map`` over a sharded square) and stays "device".
    - The single-device engine hands over ``eds_fetch``, the host copy
      of the square it STARTED right after the run (obs/xfer
      ``HostFetch``): ``.eds`` then waits for what is left of that
      copy — nothing, once process -> commit have run in its shadow —
      and counts which it was (``edscache.eds_fetch_waited`` /
      ``eds_fetch_ready``). The mesh and batched engines start none
      (a produce chain nobody samples never crosses at all) and fetch
      on demand.

    Locking mirrors the base class's per-prover discipline: ONE lock
    per lazily-built resource (host EDS, row levels, col levels), so a
    sampler fetching the square never queues behind the warmer's
    in-progress col-orientation level pass (a first-call jit compile).
    Lock order: a prover lock (``_row_lock``/``_col_lock``, inherited)
    may take a resource lock inside it; the resource locks never nest
    with each other or with the prover locks, so no inversion is
    possible."""

    def __init__(self, eds_dev, dah: DataAvailabilityHeader,
                 data_root: bytes, eds_fetch=None):
        super().__init__(None, dah, data_root)
        self._eds_dev = eds_dev  # device (2k, 2k, 512), possibly sharded
        # _eds (inherited) is the lazily-materialized host square;
        # device-side NMT level stacks, row and col orientation
        self._eds_lock = threading.Lock()
        self._eds_fetch = eds_fetch  # guarded-by: _eds_lock
        self._levels_lock = threading.Lock()
        self._col_levels_lock = threading.Lock()
        self._levels_dev = None  # guarded-by: _levels_lock
        self._col_levels_dev = None  # guarded-by: _col_levels_lock

    @classmethod
    def from_commitments(cls, eds_dev, rows, cols, root, eds_fetch=None):
        """The entry over a device EDS and its HOST commitment arrays
        ((2k, 90) row and column roots, the 32-byte data root) — the one
        construction every device-class engine ends in."""
        dah = DataAvailabilityHeader(
            row_roots=tuple(bytes(r) for r in rows),
            col_roots=tuple(bytes(c) for c in cols),
        )
        return cls(eds_dev, dah, bytes(root), eds_fetch=eds_fetch)

    @property
    def k(self) -> int:
        # geometry from the device array's shape — never a host fetch
        return int(self._eds_dev.shape[0]) // 2

    def residency(self) -> str:
        # deliberately lock-free: this is availability-record telemetry
        # read per served response, and taking _eds_lock here would
        # stall every note behind an in-progress (possibly hundreds of
        # MB) materialization. The race is benign and one-directional:
        # _eds only ever goes None -> set
        return "device+host" if self._eds is not None else "device"

    @staticmethod
    def _crossing(what: str) -> None:
        telemetry.incr("edscache.host_crossings")
        telemetry.incr(f"edscache.host_crossings.{what}")

    @property
    def eds(self) -> ExtendedDataSquare:
        """Host square bytes, materialized on first need (one counted
        crossing; later reads are free): the started copy's result
        where the engine started one, a blocking fetch otherwise."""
        with self._eds_lock:
            if self._eds is None:
                t0 = telemetry.start_timer()
                fetch, self._eds_fetch = self._eds_fetch, None
                if fetch is not None:
                    telemetry.incr("edscache.eds_fetch_ready" if fetch.ready()
                                   else "edscache.eds_fetch_waited")
                    host = fetch.result()
                else:
                    host = xfer.to_host(self._eds_dev, "edscache.eds")
                self._eds = ExtendedDataSquare(host)
                self._crossing("eds")
                telemetry.measure_since("edscache.host_fetch", t0)
            return self._eds

    def _device_levels(self, col: bool):
        """Device NMT levels for one orientation, computed (and kept)
        on device at most once — the warm stage's unit of work. Each
        orientation has its own build-once lock (same policy as
        get_prover): concurrent warmers/provers pay one level pass (jit
        compile included) between them — and ONLY between them, the
        other orientation and the EDS fetch never queue here."""
        return self._device_col_levels() if col else \
            self._device_row_levels()

    def _run_levels(self, eds_dev):
        """One level pass over a resident (2k, 2k, 512) array, priced
        like the extend: dispatch -> every level ready."""
        import jax

        from celestia_app_tpu.da import proof_device

        with obs.span("proof.levels.run", k=self.k):
            return jax.block_until_ready(
                proof_device._jitted_row_levels(self.k)(eds_dev))

    def _run_levels_sharded(self, mesh, axis: str, col: bool):
        """The same pass over a square whose rows are split over a mesh:
        inside a shard_map on that mesh (the chip's compiler partitions
        no Pallas kernel outside one), each chip hashing the trees it
        holds, the level stacks left split by tree where the square is."""
        import jax

        from celestia_app_tpu.da import proof_device

        with obs.span("mesh.levels.run", k=self.k, chips=mesh.shape[axis],
                      col=col):
            levels = jax.block_until_ready(
                proof_device._jitted_sharded_levels(mesh, axis, self.k, col)(
                    self._eds_dev))
        telemetry.incr("mesh.sharded_level_passes")
        return levels

    def _level_pass(self, col: bool):
        """One orientation's level pass over whatever this entry holds."""
        from celestia_app_tpu.da import proof_device

        placed = proof_device.rows_sharded_over(self._eds_dev)
        if placed is not None:
            return self._run_levels_sharded(*placed, col)
        if col:
            import jax.numpy as jnp

            return self._run_levels(
                jnp.swapaxes(jnp.asarray(self._eds_dev), 0, 1))
        return self._run_levels(self._eds_dev)

    def _device_row_levels(self):
        # build-once serialization (see _device_levels)
        with self._levels_lock:  # lint: disable=blocking-under-lock
            if self._levels_dev is None:
                self._levels_dev = self._level_pass(col=False)
            return self._levels_dev

    def _device_col_levels(self):
        # build-once serialization (see _device_levels)
        with self._col_levels_lock:  # lint: disable=blocking-under-lock
            if self._col_levels_dev is None:
                self._col_levels_dev = self._level_pass(col=True)
            return self._col_levels_dev

    def _host_levels(self, col: bool):
        """Materialized level arrays for a prover build (one counted
        crossing per orientation; the ledger site is the prover's,
        whichever orientation and engine)."""
        levels = self._device_levels(col)
        t0 = telemetry.start_timer()
        out = [tuple(triple)
               for triple in xfer.to_host(list(levels), "proof.row_levels")]
        self._crossing("col_levels" if col else "levels")
        telemetry.measure_since("edscache.host_fetch", t0)
        return out

    def warm(self, engine: str = "auto") -> None:
        """Device-side warm: pre-run both orientations' level passes ON
        DEVICE. Provers (which need host bytes for share payloads) stay
        lazy — the first actual proof pays the materialization, counted;
        a produce->commit->warm chain that nobody samples never crosses
        the host boundary at all."""
        self._device_levels(col=False)
        self._device_levels(col=True)

    def warmed(self) -> bool:
        # fixed acquisition order (row, then col), same as the base
        # class's warmed(): nothing nests these two the other way
        with self._levels_lock:
            row_ready = self._levels_dev is not None
        with self._col_levels_lock:
            return row_ready and self._col_levels_dev is not None

    def get_prover(self, engine: str = "auto"):
        with self._row_lock:  # lint: disable=blocking-under-lock
            if self._prover is None:
                from celestia_app_tpu.da import proof_device

                # levels first: whatever is left of a started copy of
                # the square lands behind the level pass and its fetch
                levels = self._host_levels(col=False)
                self._prover = proof_device.BlockProver(
                    self.eds, self.dah, levels=levels)
            return self._prover

    def get_col_prover(self, engine: str = "auto"):
        with self._col_lock:  # lint: disable=blocking-under-lock
            if self._col_prover is None:
                from celestia_app_tpu.da import proof_device

                # the levels come from the chip, so nothing uploads the
                # transpose and a proof reads one cell of it at a time:
                # a view, not a 32 MiB copy at k=128
                levels = self._host_levels(col=True)
                eds_t = ExtendedDataSquare(
                    np.swapaxes(self.eds.squares, 0, 1))
                self._col_prover = proof_device.BlockProver(
                    eds_t, self._transposed_dah(), levels=levels)
            return self._col_prover

    @property
    def chips(self) -> int:
        """Chips the square's rows are split over (1: one chip holds
        it)."""
        from celestia_app_tpu.da import proof_device

        placed = proof_device.rows_sharded_over(self._eds_dev)
        return 1 if placed is None else placed[0].shape[placed[1]]

    def proves_on_host(self, col: bool = False) -> bool:
        """True once host bytes exist to read a proof from: a copy of the
        square that landed or that the engine started (every one-chip
        device engine starts one), or this orientation's built host
        prover. An entry whose square lives only on the chip(s) — the
        mesh and batched engines start no copy — proves there
        (`gather_cells`). Each look waits out a build in progress under
        the same lock: what it was building is then there to read."""
        with self._eds_lock:
            if self._eds is not None or self._eds_fetch is not None:
                return True
        if col:
            with self._col_lock:
                return self._col_prover is not None
        with self._row_lock:
            return self._prover is not None

    def prove_cells(self, cells, col: bool = False, engine: str = "auto"):
        """A batch of cells proved where the entry's bytes are: from the
        host copy or host prover where one exists (the base class's
        loop), else cut on the chip(s) — the entry looks at what it
        holds, as `_level_pass` looks at its array's sharding."""
        if self.proves_on_host(col):
            return super().prove_cells(cells, col, engine)
        return self.gather_cells(cells, col)

    def gather_cells(self, cells, col: bool = False):
        """[(share bytes, NmtRangeProof)] cut out of the resident square
        and the orientation's resident level stack by ONE program — each
        cell's share and the log2(2k) sibling nodes of its path — of
        which only the answer comes down (n x (512 + L x 90) B through
        the ledger site `proof.gather`): nothing materializes, the entry
        stays "device". The level stack is the warmer's (build-once
        under its lock), or this call's if it arrives first. The bytes
        are `BlockProver.prove_cell`'s for every cell."""
        import jax

        from celestia_app_tpu.da import proof_device

        width = 2 * self.k
        cells = [(int(r), int(c)) for r, c in cells]
        for r, c in cells:
            if not (0 <= r < width and 0 <= c < width):
                raise ValueError(
                    f"cell ({r}, {c}) outside the {width}x{width} square")
        # below the roots: a path's top node is a child of the root
        levels = list(self._device_levels(col))[:-1]
        index = np.zeros((2, proof_device.gather_bucket(len(cells))),
                         dtype=np.int32)
        index[0, :len(cells)] = [r for r, _ in cells]
        index[1, :len(cells)] = [c for _, c in cells]
        program, placement = proof_device.sample_gather_program(
            self._eds_dev, self.k, col)
        index_dev = xfer.to_device(index, "proof.gather",
                                   placement=placement)
        with obs.span("proof.gather.run", k=self.k, cells=len(cells),
                      col=col):
            answer = jax.block_until_ready(
                program(self._eds_dev, levels, index_dev))
        shares, nodes = xfer.to_host(answer, "proof.gather")
        return proof_device.gathered_proofs(cells, col, width, shares,
                                            nodes)


def compute_entry(ods: np.ndarray, engine: str = "auto",
                  scheme: str = "rs2d-nmt"):
    """THE encode+commit dispatch: ODS -> scheme entry, engine-gated.

    ``engine="device"`` requires the jax path (raises on failure),
    ``"host"`` never touches jax (a host-engine process must not
    initialise an accelerator backend it does not own), ``"auto"`` tries
    device and degrades loudly,
    ``"mesh"`` prefers the sharded multi-device pipeline
    (parallel/mesh_engine.py; returns a device-resident ``DeviceEntry``)
    whenever the square can shard, and is device-class otherwise — an
    unshardable square (the k=1 empty block) or a mesh failure takes the
    single-device jax path, never the host fallback; under auto/device,
    squares of ``k >= CELESTIA_MESH_MIN_K`` (default 256) take the mesh
    automatically when one exists, degrading to the single-device path
    on failure (counted). All four engines are pinned bit-identical.
    Every call is one real encode dispatch and counts ``da.extend_runs``
    — the telemetry pin tests assert at most one per (node, height),
    whichever scheme the chain runs. The default scheme's single-device
    body below is the pre-codec-plane pipeline, untouched (byte-identity
    pinned in tests/test_codec_iface.py); other schemes dispatch through
    the codec registry's raw encode hook (da/codec.py) — an unknown
    scheme raises BEFORE the counter moves (no phantom extend_runs), and
    "mesh" maps to "auto" for them (the sharded program is the default
    codec's)."""
    if scheme != "rs2d-nmt":
        from celestia_app_tpu.da import codec as codec_mod

        codec = codec_mod.get(scheme)  # CodecError on unknown schemes
        telemetry.incr("da.extend_runs")
        return codec._encode_impl(
            ods, "auto" if engine == "mesh" else engine
        )
    telemetry.incr("da.extend_runs")
    if engine in ("mesh", "device", "auto"):
        from celestia_app_tpu.parallel import mesh_engine

        k = int(ods.shape[0])
        if (engine == "mesh" and mesh_engine.mesh_for(k) is not None) \
                or (engine != "mesh" and mesh_engine.mesh_active_for(k)):
            try:
                return mesh_engine.compute_entry_mesh(ods)
            except Exception:
                # the single-device program computes the identical
                # bytes — degrade loudly and continue below. "mesh" is
                # device-class: an unshardable square (k=1 empty block)
                # or a mesh failure takes the single-device jax path,
                # and only a jax failure there raises.
                telemetry.incr("mesh.engine_fallbacks")
    if engine in ("device", "auto", "mesh"):
        try:
            import jax

            from celestia_app_tpu.da import eds as eds_mod

            ods_dev = xfer.to_device(ods, "edscache.compute_entry")
            # THE blocking device timer of the block path: dispatch ->
            # all four outputs ready.
            with obs.span("da.extend.run", k=int(ods.shape[0])):
                eds_dev, rows, cols, root = jax.block_until_ready(
                    eds_mod.jitted_pipeline(ods.shape[0])(ods_dev))
            # only the commitment crosses here (4k x 90 B + 32 B: all a
            # proposal compares); the square stays on the chip, and its
            # host copy is started, not waited for — the first proof or
            # serve path that needs bytes (DeviceEntry.eds) waits for
            # what is left of it
            fetch = xfer.HostFetch(eds_dev, "edscache.compute_entry")
            telemetry.incr("edscache.eds_fetch_started")
            rows_h, cols_h, root_h = xfer.to_host(
                (rows, cols, root), "edscache.compute_entry"
            )
            return DeviceEntry.from_commitments(
                eds_dev, rows_h, cols_h, root_h, eds_fetch=fetch)
        except Exception:
            if engine in ("device", "mesh"):
                raise
            # engine=auto: count the silent degrade — a node that
            # quietly lost its accelerator should show it in /metrics
            telemetry.incr("app.device_path_fallback")
    # host path: BLAS+hashlib (utils/fast_host), bit-equal to the device
    # path and the refimpl oracle. The row levels come out of the same
    # pass that yields the row roots, so they ride the entry for free —
    # a later prover build on this entry is pure reshaping. Big squares
    # (k >= 256, the GF(2^16) code fast_host's BLAS formulation does not
    # cover) take Leopard's quasilinear host FFT encoder instead — the
    # NMT/level passes below are field-agnostic — so a host-engine
    # validator can follow a k=256/512 mesh chain.
    from celestia_app_tpu.ops import leopard
    from celestia_app_tpu.ops import rs as rs_ops
    from celestia_app_tpu.utils import fast_host, merkle_host

    if leopard.uses_gf16(ods.shape[0]):
        eds_arr = rs_ops.extend_square_np(ods)
    else:
        eds_arr = fast_host.extend_square_fast(ods)
    k = eds_arr.shape[0] // 2
    levels = fast_host.nmt_levels_fast(
        fast_host._axis_leaf_ns(eds_arr, k), eds_arr
    )
    lm, lx, lv = levels[-1]
    rows = np.concatenate([lm[:, 0], lx[:, 0], lv[:, 0]], axis=1)
    eds_t = np.swapaxes(eds_arr, 0, 1)
    cols = fast_host.nmt_roots_fast(
        fast_host._axis_leaf_ns(eds_t, k), eds_t
    )
    root = merkle_host.hash_from_leaves(
        [bytes(r) for r in rows] + [bytes(c) for c in cols]
    )
    dah = DataAvailabilityHeader(
        row_roots=tuple(bytes(r) for r in rows),
        col_roots=tuple(bytes(c) for c in cols),
    )
    return EdsCacheEntry(ExtendedDataSquare(eds_arr), dah, root,
                         levels=levels)


def build_block_prover(eds: ExtendedDataSquare,
                       dah: DataAvailabilityHeader,
                       engine: str = "auto", levels=None):
    """THE engine-gated BlockProver constructor — the one copy of what
    chain/query.build_prover and das/server._build_prover used to
    duplicate (they must stay bit-identical; now they are by
    construction). Precomputed host ``levels`` win regardless of engine
    (they are byte-identical to the jitted pass and already paid for).
    ``engine="mesh"`` is device-class here: prover level passes are a
    single-dispatch program either way (DeviceEntry overrides its own
    prover builds to reuse on-mesh levels before this is reached)."""
    from celestia_app_tpu.da import proof_device

    if levels is not None:
        return proof_device.BlockProver(eds, dah, levels=levels)
    if engine in ("device", "auto", "mesh"):
        try:
            return proof_device.BlockProver(eds, dah)  # jitted level pass
        except Exception:
            if engine in ("device", "mesh"):
                raise
            telemetry.incr("app.device_path_fallback")
    from celestia_app_tpu.utils import fast_host

    k = eds.width // 2
    levels = fast_host.nmt_levels_fast(
        fast_host._axis_leaf_ns(eds.squares, k), eds.squares
    )
    return proof_device.BlockProver(eds, dah, levels=levels)


class EdsCache:
    """Bounded, thread-safe, content-addressed LRU of EdsCacheEntry.

    A secondary index maps ``data_root -> key`` so the commit path — which
    holds a Block (header with data_hash), not a Square — can find the
    entry ProcessProposal populated. The index is safe because the data
    root is itself a pure function of the ODS bytes the key hashes: two
    different squares cannot share a root without a sha256 collision."""

    def __init__(self, max_entries: int | None = None,
                 max_bytes: int | None = None):
        self.max_entries = (DEFAULT_MAX_ENTRIES if max_entries is None
                            else max_entries)
        self.max_bytes = (DEFAULT_MAX_BYTES if max_bytes is None
                          else max_bytes)
        _caches.add(self)  # the residency gauge collector walks live caches
        self._lock = threading.Lock()
        self._entries: collections.OrderedDict[bytes, EdsCacheEntry] = \
            collections.OrderedDict()  # guarded-by: _lock
        self._by_root: dict[bytes, bytes] = {}  # guarded-by: _lock
        self._nbytes = 0  # charged-byte total  # guarded-by: _lock
        # LRU churn evidence for soak verdicts: per-instance (the
        # process-global telemetry counter aggregates every cache)
        self.evictions = 0  # guarded-by: _lock

    def get(self, key: bytes) -> EdsCacheEntry | None:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                telemetry.incr("edscache.misses")
                return None
            self._entries.move_to_end(key)
            telemetry.incr("edscache.hits")
            return entry

    def put(self, key: bytes, entry: EdsCacheEntry) -> EdsCacheEntry:
        """Insert (idempotent: a racing earlier insert wins, so every
        caller holds the SAME object and lazy prover work is never
        duplicated). Returns the resident entry."""
        with self._lock:
            kept = self._entries.get(key)
            if kept is None:
                self._entries[key] = entry
                self._by_root[entry.data_root] = key
                self._nbytes += entry_nbytes(entry)
                kept = entry
            self._entries.move_to_end(key)
            # evict while EITHER cap is exceeded — but always retain the
            # newest entry (the in-flight height must stay servable even
            # when a single big-square entry exceeds the byte budget)
            while len(self._entries) > 1 and (
                    len(self._entries) > self.max_entries
                    or self._nbytes > self.max_bytes):
                _, old = self._entries.popitem(last=False)
                self._by_root.pop(old.data_root, None)
                self._nbytes -= entry_nbytes(old)
                self.evictions += 1
                telemetry.incr("edscache.evictions")
            return kept

    def lookup_root(self, data_root: bytes) -> EdsCacheEntry | None:
        """Commit-side lookup by the header's data_hash (no ODS in hand).
        Does not count hits/misses — it is bookkeeping, not a serving
        path; a miss just means the DAS plane warms lazily instead."""
        with self._lock:
            key = self._by_root.get(data_root)
            if key is None:
                return None
            self._entries.move_to_end(key)
            return self._entries[key]

    def get_or_compute(self, ods: np.ndarray, engine: str = "auto",
                       scheme: str = "rs2d-nmt") -> EdsCacheEntry:
        """The lifecycle read path: one encode per (scheme, content),
        ever."""
        key = cache_key(ods, scheme)
        entry = self.get(key)
        if entry is not None:
            return entry
        return self.put(key, compute_entry(ods, engine, scheme))

    def entry_for_square(self, square, engine: str = "auto",
                         scheme: str = "rs2d-nmt") -> EdsCacheEntry:
        """`get_or_compute` from a laid-out Square — its array,
        ``square.ods``, is the square; no share list is walked or joined —
        with the two phases every caller of the lifecycle pays priced
        apart: ``da.ods_key`` (the content address of the array + the
        lookup, hit or miss) and, on a miss only, ``da.extend_shares``
        (upload, device run and download are priced inside compute_entry:
        ``xfer.*:edscache.compute_entry``, ``da.extend.run``). The
        proposer's phases (App._data_root) and a read of a height nobody
        holds (chain/query.build_prover_entry) both come through here."""
        with obs.span("da.ods_key", k=square.size) as sp:
            ods = square.ods
            key = cache_key(ods, scheme)
            entry = self.get(key)
            sp.set(hit=entry is not None)
        if entry is None:
            with obs.span("da.extend_shares", k=square.size,
                          engine=engine, scheme=scheme):
                entry = self.put(key, compute_entry(ods, engine, scheme))
        return entry

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._by_root.clear()
            self._nbytes = 0

    def nbytes(self) -> int:
        """Charged-byte total of resident entries (static estimates —
        see entry_nbytes)."""
        with self._lock:
            return self._nbytes

    def residency_counts(self) -> dict[str, int]:
        """Resident entries bucketed by ``residency()`` state — the
        scrape-time source of the ``edscache.resident_entries{state=…}``
        gauges (PR 13 exposed the splits only inside /das/availability
        records; fleetmon and external scrapers need them in /metrics)."""
        with self._lock:
            entries = list(self._entries.values())
        counts = {"host": 0, "device": 0, "device+host": 0}
        for entry in entries:
            state = entry.residency()
            counts[state] = counts.get(state, 0) + 1
        return counts

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# Scrape-time residency gauges: every live cache in the process (weakly
# held — a dropped cache stops being counted) contributes its per-state
# entry counts. Registered once at import; the collector runs before
# each snapshot()/prometheus(), so /metrics always reflects the current
# device/host split without a background thread.
_caches: "weakref.WeakSet[EdsCache]" = weakref.WeakSet()


def _residency_collector() -> None:
    counts = {"host": 0, "device": 0, "device+host": 0}
    for cache in list(_caches):
        for state, n in cache.residency_counts().items():
            counts[state] = counts.get(state, 0) + n
    for state, n in sorted(counts.items()):
        telemetry.gauge(
            "edscache.resident_entries", n, labels={"state": state}
        )


telemetry.register_collector(_residency_collector)


class ProverWarmer:
    """Single coalescing background warmup worker.

    ``schedule`` replaces the pending slot (only the NEWEST commit
    matters — a blocksync batch replaying 64 heights must not queue 64
    prover builds; superseded slots count ``edscache.warm_coalesced``)
    and starts a worker thread if none is running. The worker builds the
    entry's row and col provers and hands the entry to every registered
    listener (the DAS serving planes' ``seed_cache_entry``), all WITHOUT
    holding any caller lock, then exits when the slot drains — so idle
    processes carry no thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self._pending = None  # guarded-by: _lock
        self._worker_alive = False  # guarded-by: _lock
        self._idle = threading.Event()
        self._idle.set()

    def schedule(self, height: int, entry: EdsCacheEntry, listeners,
                 engine: str = "auto", traces=None,
                 chain_id: str = "", pack_store=None,
                 blob_pack_store=None) -> None:
        with self._lock:
            if self._pending is not None:
                telemetry.incr("edscache.warm_coalesced")
            self._pending = (height, entry, tuple(listeners), engine,
                             traces, chain_id, pack_store,
                             blob_pack_store, telemetry.start_timer())
            self._idle.clear()
            if not self._worker_alive:
                self._worker_alive = True
                threading.Thread(
                    target=self._run, daemon=True,
                    name="edscache-warmer",
                ).start()

    def _run(self) -> None:
        while True:
            with self._lock:
                item, self._pending = self._pending, None
                if item is None:
                    self._worker_alive = False
                    self._idle.set()
                    return
            (height, entry, listeners, engine, traces, chain_id,
             pack_store, blob_pack_store, t_scheduled) = item
            # schedule -> pick-up: thread start, or the block before
            # still warming
            queued_ms = telemetry.elapsed_ms(t_scheduled)
            log = obs.get_logger("da.edscache")
            try:
                # the warm span joins the height's deterministic trace, so
                # the timeline waterfall shows prover warmup hanging off
                # the same trace id commit/first-sample use
                with obs.span(
                    "da.prover_warm", traces=traces,
                    trace_id=obs.trace_id_for(chain_id, height),
                    height=height, k=entry.k, engine=engine,
                    scheme=entry.scheme, queued_ms=queued_ms,
                ):
                    entry.warm(engine)
            except Exception as e:
                # warmup is an optimization: a failure must never take
                # the process down, but it must be visible
                telemetry.incr("edscache.warm_errors")
                log.error("prover warmup failed", height=height, err=e)
                continue  # an unwarmable entry must not be seeded
            for listener in listeners:
                try:
                    listener(height, entry)
                except Exception as e:
                    # isolate per listener: one broken serving core must
                    # not starve the others of the seed
                    telemetry.incr("edscache.seed_errors")
                    log.error("seed listener failed", height=height,
                              listener=getattr(listener, "__qualname__",
                                               str(listener)), err=e)
            if pack_store is not None:
                # serving plane (das/packs.py): the warmer owns warm
                # time, so this is where the height's static proof pack
                # is precomputed — provers are already built, so pack
                # assembly is pure index arithmetic + JSON + fsync.
                # Packs are an optimization: failure is counted and
                # logged, never fatal, and serving falls back to live
                # assembly.
                try:
                    with obs.span(
                        "packs.build", traces=traces,
                        trace_id=obs.trace_id_for(chain_id, height),
                        height=height, scheme=entry.scheme,
                    ):
                        pack_store.build(height, entry)
                except Exception as e:
                    telemetry.incr("packs.build_errors")
                    log.error("proof-pack build failed", height=height,
                              err=e)
            if blob_pack_store is not None:
                # read plane (das/blob_packs.py): warm time is also when
                # the height's per-namespace blob pack is precomputed —
                # provers are built, so each namespace's response is
                # index arithmetic + JSON + fsync. Same contract as the
                # sample packs: counted on failure, never fatal, live
                # queries keep serving.
                try:
                    with obs.span(
                        "blobpacks.build", traces=traces,
                        trace_id=obs.trace_id_for(chain_id, height),
                        height=height, scheme=entry.scheme,
                    ):
                        blob_pack_store.build(height, entry)
                except Exception as e:
                    telemetry.incr("blobpacks.build_errors")
                    log.error("blob-pack build failed", height=height,
                              err=e)

    def wait_idle(self, timeout: float | None = None) -> bool:
        """Block until no warm work is pending or running (tests, bench
        measurement points)."""
        return self._idle.wait(timeout)
