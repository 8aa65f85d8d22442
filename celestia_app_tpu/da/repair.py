"""2D EDS repair: reconstruct a damaged extended square from any
sufficient subset of shares, verifying every axis against its committed
NMT root.

Reference parity: rsmt2d's `ExtendedDataSquare.Repair` (the API light
nodes and full nodes use to rebuild a block from sampled/gossiped shares;
rsmt2d repair.go `solveCrossword`). The algorithm is the same crossword
fixpoint: any row or column with ≥ k of its 2k shares present is decoded,
its recomputed NMT root is compared to the DAH's committed root, and the
recovered shares unlock further axes; iterate to fixpoint.

Two engines, bit-identical on every solvable mask (tier-1 differential
sweep, tests/test_repair.py):

- **batched** (default): the device-resident sweep engine. Per sweep,
  unverified axes are grouped by erasure pattern; each pattern's fused
  (2k, k) GF decode matrix (ops/leopard_decode.fused_decode_matrix,
  LRU-cached per (k, pattern) — the precomputed-decode-matrix technique
  of arXiv:2108.02692) reconstructs ALL axes sharing the pattern in one
  MXU bit-matmul (ops/rs.repair_axes_fn), rows and columns alike. Axis
  verification is batched too: every completed axis's NMT root is
  recomputed in one vmapped device reduction per sweep
  (ops/nmt.eds_axis_roots), and fully-present axes take the rsmt2d
  re-encode codeword check as one batched re-extend + compare. A pattern
  group smaller than CELESTIA_REPAIR_MIN_BATCH pays the scalar FWHT
  solver only when its decode closure has not already COMPILED this
  batch bucket (jit compiles per shape; batches pad to power-of-two
  buckets so per-pattern compiles are bounded) — a warm singleton still
  takes the matmul path.
- **scalar** (engine="scalar" / CELESTIA_REPAIR_ENGINE=scalar): the
  host-side per-axis path — Leopard's FWHT error-locator decode
  (ops/rs.repair_axis) plus a host NmtTree per axis — kept as the
  independent differential reference.

Mesh sharding (the mesh plane): when the sharding rule
`parallel/mesh_engine.mesh_active_for(k)` holds — k at or above
`MESH_MIN_K` with two or more devices — the batched engine's two
device programs run sharded over the flat device list: the per-pattern
fused decode matmul (ops/rs._RepairAxesRunner) and the per-sweep NMT
root reduction (ops/nmt.eds_axis_roots) both split their pow2-padded
batch dimension across devices before dispatch. The programs themselves
are untouched (jit partitions by input sharding), so mesh-sharded and
single-device sweeps are bit-identical by construction — k=256/512
repair is the same crossword, spread over the ICI.

Byzantine detection: when the input shares are AUTHENTIC (each proven
against the DAH before being fed here — the caller's job, as in DAS), a
root mismatch on a repaired or fully-present axis means the block
producer committed a NON-CODEWORD. That axis is exactly what a
bad-encoding fraud proof indicts: the raised `BadEncodingError` carries
(axis, index) ready for `da/fraud.generate_befp` (specs fraud_proofs.md;
rsmt2d ErrByzantineData semantics). Root-gating alone does NOT suffice
under batching: the matmul reconstructs from the first k sorted present
positions, and a corrupt present share OUTSIDE that use-set would leave
a root that matches the committed non-codeword (the reconstruction of
the missing cells equals what the producer committed). So the batched
path re-encode-checks every present position against the matmul output
(at the use positions the match holds by construction); a mismatching
axis holds inconsistent authentic shares and is re-decoded with the
scalar FWHT path, making its bytes — and therefore its root verdict —
identical to the scalar engine's on EVERY input, not just solvable
masks. Consistent axes get only their missing positions written back.
Error attribution is deterministic in both engines: rows are verified
before columns within a sweep, each in ascending index order, and for a
fully-present axis the re-encode check precedes the root check.
"""

from __future__ import annotations

import os

import numpy as np

from celestia_app_tpu import appconsts
from celestia_app_tpu import obs
from celestia_app_tpu.ops import rs
from celestia_app_tpu.utils import nmt_host, telemetry

NS = appconsts.NAMESPACE_SIZE
SHARE = appconsts.SHARE_SIZE


def _min_device_batch() -> int:
    """Pattern groups below this size take the scalar FWHT solver UNLESS
    their decode closure has already compiled this batch bucket (compile
    cost is the only reason to prefer scalar; a compiled shape has
    none). Mirrors the admission plane's CELESTIA_ADMISSION_MIN_BATCH
    convention."""
    try:
        return max(1, int(os.environ.get("CELESTIA_REPAIR_MIN_BATCH", "2")))
    except ValueError:
        return 2


def _engine() -> str:
    return os.environ.get("CELESTIA_REPAIR_ENGINE", "batched")


class BadEncodingError(Exception):
    """A verified-share axis failed its committed root: the producer
    committed a non-codeword (rsmt2d ErrByzantineData). Carries what
    generate_befp needs to build the fraud proof."""

    def __init__(self, axis: str, index: int):
        self.axis = axis
        self.index = index
        super().__init__(
            f"{axis} {index} does not match its committed root: "
            "the square is not a valid codeword (bad encoding)"
        )


def _axis_root(slab: np.ndarray, axis: str, index: int, k: int) -> bytes:
    """Committed-root recomputation for one full axis of 2k shares, using
    the ONE leaf-namespace rule shared with the fraud prover
    (da/fraud.leaf_ns) — repair and BEFP verification must agree on leaf
    construction or the BadEncodingError handoff breaks."""
    from celestia_app_tpu.da.fraud import leaf_ns

    tree = nmt_host.NmtTree()
    for j in range(2 * k):
        r, c = (index, j) if axis == "row" else (j, index)
        share = slab[j].tobytes()
        tree.leaves.append((leaf_ns(r, c, share, k), share))
    return nmt_host.serialize(tree.root())


def _validate(symbols, present, row_roots, col_roots):
    symbols = np.array(symbols, dtype=np.uint8, copy=True)
    present = np.array(present, dtype=bool, copy=True)
    two_k = symbols.shape[0]
    if symbols.shape != (two_k, two_k, SHARE):
        raise ValueError(f"bad square shape {symbols.shape}")
    if present.shape != (two_k, two_k):
        raise ValueError(f"bad mask shape {present.shape}")
    if len(row_roots) != two_k or len(col_roots) != two_k:
        raise ValueError("need 2k row roots and 2k col roots")
    return symbols, present, two_k


def _unsolvable(present: np.ndarray) -> ValueError:
    missing = int((~present).sum())
    return ValueError(
        f"unsolvable erasure pattern: {missing} shares still "
        "missing and no row or column has k known shares"
    )


def repair_eds(
    symbols: np.ndarray,
    present: np.ndarray,
    row_roots: list[bytes],
    col_roots: list[bytes],
    *,
    engine: str | None = None,
    traces=None,
) -> np.ndarray:
    """Rebuild the full (2k, 2k, 512) EDS from the shares marked present.

    `symbols` may hold arbitrary bytes at missing positions; `present` is
    the (2k, 2k) bool mask of authentic shares. Raises ValueError when the
    erasure pattern is unsolvable, BadEncodingError when a completed axis
    contradicts its committed root. Returns the repaired square; on
    success every row/column root has been verified.

    `engine` picks "batched" (device sweep engine, the default) or
    "scalar" (per-axis host reference); `traces` pins the span sink
    (a light node passes its own TraceTables)."""
    engine = engine or _engine()
    if engine not in ("batched", "scalar"):
        raise ValueError(f"repair engine must be 'batched' or 'scalar', "
                         f"not {engine!r}")
    symbols, present, two_k = _validate(symbols, present,
                                        row_roots, col_roots)
    if engine == "scalar":
        return _repair_scalar(symbols, present, row_roots, col_roots,
                              two_k, traces)
    return _repair_batched(symbols, present, row_roots, col_roots,
                           two_k, traces)


# ---------------------------------------------------------------------------
# scalar engine: the per-axis host reference (FWHT decode + host NmtTree)
# ---------------------------------------------------------------------------


def _repair_scalar(symbols, present, row_roots, col_roots, two_k,
                   traces) -> np.ndarray:
    k = two_k // 2
    verified_rows = [False] * two_k
    verified_cols = [False] * two_k

    def _is_codeword(slab: np.ndarray) -> bool:
        """rsmt2d's re-encode check: a FULLY-PRESENT axis must itself be
        a valid codeword (re-extend its systematic half, demand byte
        identity). Axes completed by decoding are root-gated against the
        commitment, but a fully-present axis would otherwise sail
        through on a root match alone — committed trees over a
        non-codeword match their own leaves (rsmt2d ErrByzantineData
        covers exactly this)."""
        rec = rs.repair_axis(slab, list(range(k)))
        return bool(np.array_equal(rec.reshape(two_k, SHARE),
                                   np.asarray(slab)))

    def _finish_row(r: int, check_rs: bool = False) -> None:
        if check_rs and not _is_codeword(symbols[r]):
            raise BadEncodingError("row", r)
        if _axis_root(symbols[r], "row", r, k) != row_roots[r]:
            raise BadEncodingError("row", r)
        verified_rows[r] = True

    def _finish_col(c: int, check_rs: bool = False) -> None:
        if check_rs and not _is_codeword(symbols[:, c, :]):
            raise BadEncodingError("col", c)
        if _axis_root(symbols[:, c, :], "col", c, k) != col_roots[c]:
            raise BadEncodingError("col", c)
        verified_cols[c] = True

    sweep = 0
    while True:
        sweep += 1
        progress = False
        with obs.span("da.repair.sweep", traces=traces, engine="scalar",
                      sweep=sweep):
            for r in range(two_k):
                if verified_rows[r]:
                    continue
                n = int(present[r].sum())
                if n == two_k:
                    _finish_row(r, check_rs=True)
                    progress = True
                elif n >= k:
                    rec = rs.repair_axis(
                        symbols[r], list(np.flatnonzero(present[r]))
                    )
                    symbols[r] = rec.reshape(two_k, SHARE)
                    telemetry.incr("repair.axes_scalar")
                    _finish_row(r)
                    present[r] = True
                    progress = True
            for c in range(two_k):
                if verified_cols[c]:
                    continue
                n = int(present[:, c].sum())
                if n == two_k:
                    _finish_col(c, check_rs=True)
                    progress = True
                elif n >= k:
                    rec = rs.repair_axis(
                        symbols[:, c, :], list(np.flatnonzero(present[:, c]))
                    )
                    symbols[:, c, :] = rec.reshape(two_k, SHARE)
                    telemetry.incr("repair.axes_scalar")
                    _finish_col(c)
                    present[:, c] = True
                    progress = True
        if all(verified_rows) and all(verified_cols):
            return symbols
        if not progress:
            raise _unsolvable(present)


# ---------------------------------------------------------------------------
# batched engine: per-pattern matmul decode + per-sweep batched verification
# ---------------------------------------------------------------------------


def _axis_slab(symbols: np.ndarray, axis: str, i: int) -> np.ndarray:
    return symbols[i] if axis == "row" else symbols[:, i, :]


def _decode_phase(symbols, present, axis: str, verified, two_k: int) -> tuple:
    """Decode every repairable axis of one orientation. Returns
    (completed, full_set): `completed` is the ascending list of axis
    indices now holding all 2k shares (decoded this phase or fully
    present on entry), `full_set` the subset that was fully present
    (those owe the re-encode codeword check)."""
    k = two_k // 2
    min_batch = _min_device_batch()
    counts = present.sum(axis=1) if axis == "row" else present.sum(axis=0)
    full, patterns = [], {}
    for i in range(two_k):
        if verified[i]:
            continue
        n = int(counts[i])
        if n == two_k:
            full.append(i)
        elif n >= k:
            mask = present[i] if axis == "row" else present[:, i]
            patterns.setdefault(
                tuple(np.flatnonzero(mask).tolist()), []
            ).append(i)
    decoded = []
    for pattern, axes in patterns.items():
        if len(axes) >= min_batch:
            run = rs.repair_axes_fn(k, pattern)
        else:
            # cached-singleton policy: one atomic get (no peek-then-build
            # race), gated on THIS batch bucket having executed — a cold
            # small group goes scalar, never a jit build or retrace
            run = rs.repair_axes_get(k, pattern, batch_size=len(axes))
        if run is not None:
            # one fused decode+re-encode bit-matmul for the whole group.
            # The re-encode from the first k sorted present positions must
            # REPRODUCE every present share (at the use positions it does
            # so by construction; beyond them it is the rsmt2d consistency
            # check): a mismatching axis holds inconsistent authentic
            # shares, and it is re-decoded with the scalar FWHT path so
            # its bytes — and the root verdict they produce — are
            # identical to the scalar engine's. Consistent axes get ONLY
            # their missing positions written back.
            pres = list(pattern)
            miss = sorted(set(range(two_k)) - set(pattern))
            out = np.asarray(
                run(np.stack([_axis_slab(symbols, axis, i) for i in axes]))
            )
            n_batched = 0
            for b, i in enumerate(axes):
                slab = _axis_slab(symbols, axis, i)
                if np.array_equal(out[b, pres, :], slab[pres]):
                    if axis == "row":
                        symbols[i, miss, :] = out[b, miss, :]
                    else:
                        symbols[miss, i, :] = out[b, miss, :]
                    n_batched += 1
                else:
                    telemetry.incr("repair.inconsistent_axes")
                    _scalar_decode_axis(symbols, axis, i, pattern, two_k)
            if n_batched:
                telemetry.incr("repair.axes_batched", n_batched)
            if n_batched != len(axes):
                telemetry.incr("repair.axes_scalar", len(axes) - n_batched)
        else:
            for i in axes:
                _scalar_decode_axis(symbols, axis, i, pattern, two_k)
            telemetry.incr("repair.axes_scalar", len(axes))
        decoded += axes
    return sorted(full + decoded), set(full)


def _scalar_decode_axis(symbols, axis: str, i: int, pattern, two_k) -> None:
    rec = rs.repair_axis(
        _axis_slab(symbols, axis, i), list(pattern)
    ).reshape(two_k, SHARE)
    if axis == "row":
        symbols[i] = rec
    else:
        symbols[:, i, :] = rec


def _verify_phase(symbols, present, axis: str, verified, roots,
                  completed, full_set, two_k: int, traces) -> bool:
    """Batched verification of every axis completed this phase: ONE
    device NMT reduction recomputes all their roots, one batched
    re-extend covers the fully-present axes' codeword checks. Raises
    BadEncodingError at the lowest failing index (fully-present axes
    fail their re-encode check before their root check, matching the
    scalar engine's attribution)."""
    from celestia_app_tpu.ops import nmt

    if not completed:
        return False
    k = two_k // 2
    slabs = (symbols[completed] if axis == "row"
             else np.stack([symbols[:, c, :] for c in completed]))
    with obs.span("da.repair.verify_roots", traces=traces, axis=axis,
                  axes=len(completed)):
        codeword_ok = {}
        if full_set:
            ordered = sorted(full_set)
            pos = {i: b for b, i in enumerate(completed)}
            full_slabs = slabs[[pos[i] for i in ordered]]
            rec = np.asarray(
                rs.repair_axes_fn(k, tuple(range(two_k)))(full_slabs)
            )
            for b, i in enumerate(ordered):
                codeword_ok[i] = bool(np.array_equal(rec[b], full_slabs[b]))
        got = nmt.eds_axis_roots(slabs, completed, k)
    for b, i in enumerate(completed):
        if i in full_set and not codeword_ok[i]:
            raise BadEncodingError(axis, i)
        if got[b].tobytes() != roots[i]:
            raise BadEncodingError(axis, i)
        verified[i] = True
        if axis == "row":
            present[i] = True
        else:
            present[:, i] = True
    return True


def _repair_batched(symbols, present, row_roots, col_roots, two_k,
                    traces) -> np.ndarray:
    verified_rows = [False] * two_k
    verified_cols = [False] * two_k
    sweep = 0
    while True:
        sweep += 1
        progress = False
        with obs.span("da.repair.sweep", traces=traces, engine="batched",
                      sweep=sweep) as sp:
            for axis, verified, roots in (
                ("row", verified_rows, row_roots),
                ("col", verified_cols, col_roots),
            ):
                completed, full_set = _decode_phase(
                    symbols, present, axis, verified, two_k
                )
                if _verify_phase(symbols, present, axis, verified, roots,
                                 completed, full_set, two_k, traces):
                    progress = True
            sp.set(progress=progress)
        if all(verified_rows) and all(verified_cols):
            return symbols
        if not progress:
            raise _unsolvable(present)
