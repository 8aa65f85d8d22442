"""2D EDS repair (rsmt2d ExtendedDataSquare.Repair parity): crossword
reconstruction from partial shares, root verification per axis, byzantine
(bad-encoding) detection feeding the fraud-proof machinery — plus the
batched-vs-scalar differential sweep pinning the device sweep engine
bit-identical to the per-axis host reference."""

import numpy as np
import pytest

from celestia_app_tpu.da import dah as dah_mod
from celestia_app_tpu.da import fraud
from celestia_app_tpu.da import repair
from celestia_app_tpu.ops import rs
from celestia_app_tpu.utils import telemetry


def _counters() -> dict:
    return dict(telemetry.snapshot().get("counters", {}))


def _delta(before: dict, after: dict, name: str) -> int:
    return after.get(name, 0) - before.get(name, 0)


def _square(k=4, seed=0):
    rng = np.random.default_rng(seed)
    ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    ods[..., :29] = 0
    ods[..., 28] = 9
    return ods


def _committed(ods):
    d, eds_obj, _root = dah_mod.new_dah_from_ods(ods)
    return d, np.asarray(eds_obj.squares)


def test_repair_from_random_erasures():
    """Half the shares erased uniformly at random: the crossword solver
    recovers the exact square and verifies every axis root."""
    k = 4
    ods = _square(k)
    d, eds = _committed(ods)
    rng = np.random.default_rng(7)
    present = rng.random((2 * k, 2 * k)) < 0.5
    # guarantee solvability seed: at least one row fully present
    present[0] = True
    damaged = np.where(present[..., None], eds, 0).astype(np.uint8)
    out = repair.repair_eds(damaged, present,
                            list(d.row_roots), list(d.col_roots))
    np.testing.assert_array_equal(out, eds)


def test_repair_from_single_quadrant():
    """Q0 alone (the original data square) reconstructs everything —
    the DA property the 2D code exists for."""
    k = 4
    ods = _square(k, seed=3)
    d, eds = _committed(ods)
    present = np.zeros((2 * k, 2 * k), dtype=bool)
    present[:k, :k] = True  # only Q0
    damaged = np.where(present[..., None], eds, 0).astype(np.uint8)
    out = repair.repair_eds(damaged, present,
                            list(d.row_roots), list(d.col_roots))
    np.testing.assert_array_equal(out, eds)


def test_repair_needs_iteration():
    """A pattern no single pass solves: Q3 alone has k full parity rows,
    whose repair unlocks columns, which unlock the rest."""
    k = 4
    ods = _square(k, seed=5)
    d, eds = _committed(ods)
    present = np.zeros((2 * k, 2 * k), dtype=bool)
    present[k:, k:] = True  # only Q3
    damaged = np.where(present[..., None], eds, 0).astype(np.uint8)
    out = repair.repair_eds(damaged, present,
                            list(d.row_roots), list(d.col_roots))
    np.testing.assert_array_equal(out, eds)


def test_unsolvable_pattern_raises():
    """k-1 shares per row and column can never reach the k threshold."""
    k = 4
    ods = _square(k, seed=6)
    d, eds = _committed(ods)
    present = np.zeros((2 * k, 2 * k), dtype=bool)
    present[: k - 1, : k - 1] = True  # 3x3 block: every axis < k known
    damaged = np.where(present[..., None], eds, 0).astype(np.uint8)
    with pytest.raises(ValueError, match="unsolvable"):
        repair.repair_eds(damaged, present,
                          list(d.row_roots), list(d.col_roots))


def test_byzantine_square_raises_and_feeds_fraud_proof():
    """A producer commits roots over a NON-codeword: repair of authentic
    shares contradicts a committed root -> BadEncodingError, and the
    indicted axis yields a verifiable bad-encoding fraud proof."""
    k = 4
    ods = _square(k, seed=8)
    honest_eds = rs.extend_square_np(ods)
    corrupt = honest_eds.copy()
    corrupt[1, 2 * k - 1] ^= 0xFF  # row 1 is no longer a codeword
    # the malicious producer commits THIS square (blind trees)
    from tests.test_fraud import _dah_of

    d_bad = _dah_of(corrupt)
    # an honest repairer gathers shares proven against d_bad, with the
    # corrupted cell among the missing ones
    present = np.ones((2 * k, 2 * k), dtype=bool)
    present[1, k:] = False  # row 1's parity half missing -> gets repaired
    damaged = np.where(present[..., None], corrupt, 0).astype(np.uint8)
    with pytest.raises(repair.BadEncodingError) as exc:
        repair.repair_eds(damaged, present,
                          list(d_bad.row_roots), list(d_bad.col_roots))
    axis, index = exc.value.axis, exc.value.index
    assert (axis, index) == ("row", 1)
    # the indicted axis produces a fraud proof the network accepts
    befp = fraud.generate_befp(
        dah_mod.ExtendedDataSquare(corrupt), axis, index
    )
    assert fraud.verify_befp(d_bad, befp)


def test_batched_device_repair_matches_per_axis():
    """TPU-native batched repair (one MXU bit-matmul for a whole batch of
    axes sharing one erasure pattern — the missing-columns case) is
    bit-identical to the per-axis Leopard decoder."""
    k = 8
    ods = _square(k, seed=11)
    eds = rs.extend_square_np(ods)
    rng = np.random.default_rng(2)
    # a shared pattern: 6 of 16 columns missing
    missing = set(rng.choice(2 * k, size=6, replace=False).tolist())
    present = tuple(j for j in range(2 * k) if j not in missing)
    damaged = eds.copy()
    for j in missing:
        damaged[:, j, :] = 0

    run = rs.repair_axes_fn(k, present)
    out = np.asarray(run(damaged))  # all 2k rows in one batch
    np.testing.assert_array_equal(out, eds)

    # cross-check one row against the per-axis FWHT decode path
    row3 = rs.repair_axis(damaged[3], list(present))
    np.testing.assert_array_equal(out[3], row3.reshape(2 * k, -1))


def test_batched_device_repair_gf16_subprocess():
    """Same batched repair through the GF(2^16) codec (threshold lowered in
    a subprocess so k=8 uses the 16-bit field at CI-affordable size)."""
    import os
    import subprocess
    import sys

    code = r"""
import numpy as np
from celestia_app_tpu.ops import leopard, rs
assert leopard.uses_gf16(8)
k = 8
rng = np.random.default_rng(31)
ods = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
ods[..., :29] = 0
eds = rs.extend_square_np(ods)
# the square is the plain encode under the published 64-byte block, so
# what the repairs below recover is recovered under that mapping
import gf16_plain
np.testing.assert_array_equal(eds, gf16_plain.extend(ods))
assert not np.array_equal(eds[0, k:], gf16_plain.parity_adjacent_pairs(ods[0]))
# 10 present positions (>= k), spanning data and parity halves
present = (0, 1, 2, 3, 8, 9, 10, 11, 12, 13)
damaged = eds.copy()
for j in range(2 * k):
    if j not in present:
        damaged[:, j, :] = 0
run = rs.repair_axes_fn(k, present)
out = np.asarray(run(damaged))
np.testing.assert_array_equal(out, eds)
# the per-axis decoders (FWHT and matrix inversion) agree
for r in (0, 5, 11, 15):
    np.testing.assert_array_equal(
        rs.repair_axis(damaged[r], list(present)), eds[r])
    np.testing.assert_array_equal(
        rs.repair_axis_matrix(damaged[r], list(present)), eds[r])
print("GF16-BATCH-REPAIR-OK")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["CELESTIA_GF16_THRESHOLD"] = "4"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.abspath(__file__)),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "GF16-BATCH-REPAIR-OK" in r.stdout


def test_repair_eds_batched_path_with_byzantine_row():
    """The in-repair batched fast path (several rows sharing one missing-
    columns pattern) must still flag a byzantine axis: the re-encoded row
    contradicts the committed root even though the batch repaired it."""
    k = 4
    ods = _square(k, seed=13)
    honest = rs.extend_square_np(ods)
    corrupt = honest.copy()
    corrupt[2, 2 * k - 2] ^= 0x55  # row 2: inconsistent codeword
    from tests.test_fraud import _dah_of

    d_bad = _dah_of(corrupt)
    present = np.ones((2 * k, 2 * k), dtype=bool)
    present[:, k:] = False  # parity COLUMNS missing: all rows share pattern
    damaged = np.where(present[..., None], corrupt, 0).astype(np.uint8)
    with pytest.raises(repair.BadEncodingError) as exc:
        repair.repair_eds(damaged, present,
                          list(d_bad.row_roots), list(d_bad.col_roots))
    assert (exc.value.axis, exc.value.index) == ("row", 2)

    # and the honest square through the same shape repairs cleanly
    d_ok, eds_ok = _committed(ods)
    damaged_ok = np.where(present[..., None], eds_ok, 0).astype(np.uint8)
    out = repair.repair_eds(damaged_ok, present,
                            list(d_ok.row_roots), list(d_ok.col_roots))
    np.testing.assert_array_equal(out, eds_ok)


# ---------------------------------------------------------------------------
# the batched sweep engine: differential parity, cache policy, telemetry
# ---------------------------------------------------------------------------


def _outcome(damaged, present, d, engine):
    """(kind, payload) summary of one repair run, comparable across
    engines: ("ok", square) | ("bad", axis, index) | ("unsolvable",)."""
    try:
        out = repair.repair_eds(damaged, present,
                                list(d.row_roots), list(d.col_roots),
                                engine=engine)
        return ("ok", out)
    except repair.BadEncodingError as e:
        return ("bad", e.axis, e.index)
    except ValueError as e:
        assert "unsolvable" in str(e)
        return ("unsolvable",)


def test_differential_sweep_random_masks():
    """Randomized masks/seeds: the batched engine is byte-identical to the
    scalar reference on every solvable mask and raises the same
    unsolvable error on the rest."""
    k = 4
    ods = _square(k, seed=21)
    d, eds = _committed(ods)
    saw_ok = saw_unsolvable = False
    for seed in range(10):
        rng = np.random.default_rng(300 + seed)
        p = rng.uniform(0.12, 0.65)
        present = rng.random((2 * k, 2 * k)) < p
        damaged = np.where(present[..., None], eds, 0).astype(np.uint8)
        got_b = _outcome(damaged, present, d, "batched")
        got_s = _outcome(damaged, present, d, "scalar")
        assert got_b[0] == got_s[0], (seed, got_b[0], got_s[0])
        if got_b[0] == "ok":
            saw_ok = True
            np.testing.assert_array_equal(got_b[1], got_s[1])
            np.testing.assert_array_equal(got_b[1], eds)
        else:
            saw_unsolvable = True
    assert saw_ok and saw_unsolvable, "sweep must exercise both outcomes"


def test_differential_sweep_byzantine_attribution():
    """Randomized byzantine squares: both engines raise BadEncodingError
    with the IDENTICAL (axis, index) — the handoff generate_befp needs."""
    from tests.test_fraud import _dah_of

    k = 4
    saw_bad = 0
    for seed in range(8):
        rng = np.random.default_rng(500 + seed)
        ods = _square(k, seed=40 + seed)
        corrupt = rs.extend_square_np(ods)
        r0, c0 = int(rng.integers(0, 2 * k)), int(rng.integers(0, 2 * k))
        corrupt[r0, c0] ^= 0xA5
        d_bad = _dah_of(corrupt)
        present = rng.random((2 * k, 2 * k)) < 0.75
        damaged = np.where(present[..., None], corrupt, 0).astype(np.uint8)
        got_b = _outcome(damaged, present, d_bad, "batched")
        got_s = _outcome(damaged, present, d_bad, "scalar")
        assert got_b[0] == got_s[0], (seed, got_b[0], got_s[0])
        if got_b[0] == "ok":
            np.testing.assert_array_equal(got_b[1], got_s[1])
        else:
            assert got_b == got_s, (seed, got_b, got_s)
        if got_b[0] == "bad":
            saw_bad += 1
    assert saw_bad >= 4, "corruption must be detected in most draws"


def test_byzantine_at_fully_present_stage():
    """A fully-present non-codeword axis is caught by the BATCHED
    re-encode check with the same attribution as the scalar path."""
    from tests.test_fraud import _dah_of

    k = 4
    corrupt = rs.extend_square_np(_square(k, seed=17))
    corrupt[2, 2 * k - 1] ^= 0x0F
    d_bad = _dah_of(corrupt)
    present = np.ones((2 * k, 2 * k), dtype=bool)
    damaged = corrupt.copy()
    for engine in ("batched", "scalar"):
        with pytest.raises(repair.BadEncodingError) as exc:
            repair.repair_eds(damaged, present,
                              list(d_bad.row_roots), list(d_bad.col_roots),
                              engine=engine)
        assert (exc.value.axis, exc.value.index) == ("row", 2), engine


def test_byzantine_at_batched_column_stage():
    """Whole ROWS missing -> every column shares one erasure pattern and
    the COLUMN side takes the batched matmul; a committed corruption in
    the missing region is caught at column verification, same (axis,
    index) in both engines."""
    from tests.test_fraud import _dah_of

    k = 4
    corrupt = rs.extend_square_np(_square(k, seed=19))
    corrupt[5, 2] ^= 0x3C  # inside the withheld rows: cols must catch it
    d_bad = _dah_of(corrupt)
    present = np.ones((2 * k, 2 * k), dtype=bool)
    present[k:, :] = False  # bottom half of rows withheld
    damaged = np.where(present[..., None], corrupt, 0).astype(np.uint8)
    before = _counters()
    for engine in ("batched", "scalar"):
        with pytest.raises(repair.BadEncodingError) as exc:
            repair.repair_eds(damaged, present,
                              list(d_bad.row_roots), list(d_bad.col_roots),
                              engine=engine)
        assert (exc.value.axis, exc.value.index) == ("col", 2), engine
    # the batched engine really did decode columns via the matmul path
    assert _delta(before, _counters(), "repair.axes_batched") >= 1


def test_decode_matrix_cache_hit_miss():
    """First repair of a fresh shared pattern misses the decode-matrix
    cache once per distinct pattern; an identical repair afterwards is
    all hits and still bit-identical."""
    k = 4
    ods = _square(k, seed=23)
    d, eds = _committed(ods)
    present = np.ones((2 * k, 2 * k), dtype=bool)
    present[:, ::4] = False  # ¼ of cells: one pattern shared by all rows
    damaged = np.where(present[..., None], eds, 0).astype(np.uint8)

    rs.repair_axes_cache_clear()
    before = _counters()
    out = repair.repair_eds(damaged, present,
                            list(d.row_roots), list(d.col_roots))
    np.testing.assert_array_equal(out, eds)
    mid = _counters()
    # one miss for the shared row pattern, one for the fully-present
    # re-encode check pattern the column side uses; zero hits required
    assert _delta(before, mid, "repair.matrix_cache_misses") == 2
    assert _delta(before, mid, "repair.axes_batched") == 2 * k

    out = repair.repair_eds(damaged, present,
                            list(d.row_roots), list(d.col_roots))
    np.testing.assert_array_equal(out, eds)
    after = _counters()
    assert _delta(mid, after, "repair.matrix_cache_misses") == 0
    assert _delta(mid, after, "repair.matrix_cache_hits") == 2
    assert _delta(mid, after, "repair.axes_batched") == 2 * k


def test_singleton_cached_pattern_takes_matmul_path():
    """A pattern group of ONE axis goes scalar only while its decode
    closure is uncached; once cached, the same singleton takes the
    batched matmul path (the `len(rows) < 2` skip is gone)."""
    k = 4
    ods = _square(k, seed=27)
    d, eds = _committed(ods)
    present = np.ones((2 * k, 2 * k), dtype=bool)
    present[3, [5, 6]] = False  # exactly one repairable row
    pattern = tuple(np.flatnonzero(present[3]).tolist())
    damaged = np.where(present[..., None], eds, 0).astype(np.uint8)

    rs.repair_axes_cache_clear()
    before = _counters()
    out = repair.repair_eds(damaged, present,
                            list(d.row_roots), list(d.col_roots))
    np.testing.assert_array_equal(out, eds)
    mid = _counters()
    assert _delta(before, mid, "repair.axes_scalar") == 1
    assert not rs.repair_axes_cached(k, pattern)

    # prime by EXECUTING at batch 1 (building alone leaves the bucket
    # uncompiled, and an uncompiled bucket must not gate onto the matmul)
    rs.repair_axes_fn(k, pattern)(np.zeros((1, 2 * k, 512), np.uint8))
    assert rs.repair_axes_cached(k, pattern)
    assert rs.repair_axes_get(k, pattern, batch_size=1) is not None
    out = repair.repair_eds(damaged, present,
                            list(d.row_roots), list(d.col_roots))
    np.testing.assert_array_equal(out, eds)
    after = _counters()
    assert _delta(mid, after, "repair.axes_scalar") == 0
    assert _delta(mid, after, "repair.axes_batched") == 1


def test_corrupt_present_share_outside_use_set():
    """Root-gating's blind spot: a corrupt PRESENT share beyond the first
    k sorted present positions — the matmul reconstructs the missing
    cells from clean shares, reproducing the committed (non-codeword)
    root exactly. The batched engine must still raise, with the scalar
    engine's attribution, under cold AND warm decode caches."""
    from tests.test_fraud import _dah_of

    k = 4
    corrupt = rs.extend_square_np(_square(k, seed=37))
    corrupt[7, 7] ^= 0x55  # committed, present, outside use-set {0,1,2,3}
    d_bad = _dah_of(corrupt)
    present = np.zeros((2 * k, 2 * k), dtype=bool)
    present[:k, :] = True          # rows 0-3 fully present (honest)
    present[4:7, :3] = True        # rows 4-6 under-provisioned (n < k)
    present[7, [0, 1, 2, 3, 7]] = True  # row 7: corrupt share at 7
    damaged = np.where(present[..., None], corrupt, 0).astype(np.uint8)
    pattern = (0, 1, 2, 3, 7)

    rs.repair_axes_cache_clear()
    outcomes = []
    for label in ("scalar", "batched-cold", "batched-warm"):
        engine = "scalar" if label == "scalar" else "batched"
        if label == "batched-warm":
            # execute at batch 1 so the singleton takes the matmul path
            rs.repair_axes_fn(k, pattern)(
                np.zeros((1, 2 * k, 512), np.uint8))
        before = _counters()
        with pytest.raises(repair.BadEncodingError) as exc:
            repair.repair_eds(damaged, present,
                              list(d_bad.row_roots), list(d_bad.col_roots),
                              engine=engine)
        outcomes.append((exc.value.axis, exc.value.index))
        if label == "batched-warm":
            # the matmul path ran, flagged the inconsistency, and fell
            # back to the FWHT decode for that axis
            assert _delta(before, _counters(),
                          "repair.inconsistent_axes") >= 1
    assert len(set(outcomes)) == 1, outcomes


def test_unsolvable_error_parity():
    """Both engines refuse the same unsolvable mask with the same error."""
    k = 4
    ods = _square(k, seed=29)
    d, eds = _committed(ods)
    present = np.zeros((2 * k, 2 * k), dtype=bool)
    present[: k - 1, : k - 1] = True
    damaged = np.where(present[..., None], eds, 0).astype(np.uint8)
    for engine in ("batched", "scalar"):
        with pytest.raises(ValueError, match="unsolvable"):
            repair.repair_eds(damaged, present,
                              list(d.row_roots), list(d.col_roots),
                              engine=engine)


def test_repair_spans_land_in_caller_tables():
    """The sweep engine's obs spans (da.repair.sweep,
    da.repair.verify_roots) record into the TraceTables the caller pins —
    the DASer passes its own, so repair cost shows per-height in the
    light node's waterfall."""
    k = 4
    ods = _square(k, seed=33)
    d, eds = _committed(ods)
    present = np.ones((2 * k, 2 * k), dtype=bool)
    present[:, ::4] = False
    damaged = np.where(present[..., None], eds, 0).astype(np.uint8)
    traces = telemetry.TraceTables()
    repair.repair_eds(damaged, present,
                      list(d.row_roots), list(d.col_roots), traces=traces)
    rows = traces.read("spans")
    names = [r["name"] for r in rows]
    assert "da.repair.sweep" in names
    assert "da.repair.verify_roots" in names
    sweep = next(r for r in rows if r["name"] == "da.repair.sweep")
    assert sweep["engine"] == "batched"
    verify = [r for r in rows if r["name"] == "da.repair.verify_roots"]
    assert {v["axis"] for v in verify} == {"row", "col"}
    # nested spans share the sweep's trace id (the waterfall join)
    assert all(v["trace_id"] == sweep["trace_id"] for v in verify)


def test_eds_axis_roots_matches_host_trees():
    """The batched device NMT primitive (ops/nmt.eds_axis_roots) is
    byte-identical to the host NmtTree over rows AND columns, including
    padded batch buckets (n not a power of two)."""
    from celestia_app_tpu.ops import nmt

    k = 4
    ods = _square(k, seed=31)
    _, eds = _committed(ods)
    rows = [0, 3, 6]  # pads 3 -> bucket 4
    got = nmt.eds_axis_roots(eds[rows], rows, k)
    for b, r in enumerate(rows):
        assert got[b].tobytes() == repair._axis_root(eds[r], "row", r, k)
    cols = [1, 4, 5, 7, 2]  # pads 5 -> bucket 8
    slabs = np.stack([eds[:, c, :] for c in cols])
    got = nmt.eds_axis_roots(slabs, cols, k)
    for b, c in enumerate(cols):
        assert got[b].tobytes() == repair._axis_root(
            eds[:, c, :], "col", c, k)
