"""Malicious-proposer fixtures: build blocks that honest validators must reject.

Reference parity: test/util/malicious/ —
  tree.go:19-60            BlindTree: an NMT that skips namespace-ordering
                           verification (ForceAddLeaf instead of Push), so a
                           malicious proposer can still produce axis roots
                           over an invalid share ordering.
  out_of_order_builder.go  OutOfOrderExport: swaps two blobs in the square.
  out_of_order_prepare.go  OutOfOrderPrepareProposal: honest tx filtering,
                           malicious square + commitment.

These fixtures exist so tests can assert the *honest* ProcessProposal path
rejects each class of malice (the reference additionally uses them to source
fraud proofs)."""

from __future__ import annotations

import numpy as np

from celestia_app_tpu import appconsts
from celestia_app_tpu.chain.block import Block, Header
from celestia_app_tpu.da import dah as dah_mod
from celestia_app_tpu.da import namespace as ns_mod
from celestia_app_tpu.utils import merkle_host, nmt_host

NS = appconsts.NAMESPACE_SIZE


class BlindNmtTree(nmt_host.NmtTree):
    """NMT that accepts leaves in any namespace order (malicious/tree.go)."""

    def push(self, ns: bytes, data: bytes) -> None:  # ForceAddLeaf
        self.leaves.append((ns, data))


def swap_first_two_blobs(square) -> np.ndarray:
    """A copy of the square's (k, k, 512) array with the first two blobs'
    share ranges swapped (OutOfOrderExport, out_of_order_builder.go:62-79).
    Requires >= 2 blobs."""
    shares = square.ods.reshape(-1, appconsts.SHARE_SIZE).copy()
    keys = sorted(square.blob_start_indexes.keys())
    if len(keys) < 2:
        raise ValueError("need at least two blobs to swap")
    (i0, j0), (i1, j1) = keys[0], keys[1]
    s0 = square.blob_start_indexes[(i0, j0)]
    c0 = square.pfbs[i0].blobs[j0].share_count()
    s1 = square.blob_start_indexes[(i1, j1)]
    c1 = square.pfbs[i1].blobs[j1].share_count()
    if c0 != c1:
        # swap equal-length prefixes so the layout geometry stays identical
        c0 = c1 = min(c0, c1)
    a = shares[s0 : s0 + c0].copy()
    shares[s0 : s0 + c0] = shares[s1 : s1 + c1]
    shares[s1 : s1 + c1] = a
    return shares.reshape(square.ods.shape)


def blind_dah(ods: np.ndarray):
    """DAH over an (invalidly ordered) ODS using blind trees: the malicious
    analog of utils/refimpl.pipeline_host — an honest NmtTree would raise."""
    from celestia_app_tpu.utils import refimpl

    eds = refimpl.extend_square_host(ods)
    two_k = eds.shape[0]
    k = two_k // 2

    def tree_root(axis_get, axis_index) -> bytes:
        tree = BlindNmtTree()
        for j in range(two_k):
            share = axis_get(j).tobytes()
            in_q0 = axis_index < k and j < k
            ns = share[:NS] if in_q0 else ns_mod.PARITY_NS_RAW
            tree.push(ns, share)
        return nmt_host.serialize(tree.root())

    rows = [tree_root(lambda j, r=r: eds[r, j], r) for r in range(two_k)]
    cols = [tree_root(lambda j, c=c: eds[j, c], c) for c in range(two_k)]
    root = merkle_host.hash_from_leaves(rows + cols)
    return dah_mod.DataAvailabilityHeader(tuple(rows), tuple(cols)), root


def out_of_order_prepare(app, raw_txs: list[bytes], t: float) -> Block:
    """Malicious PrepareProposal: honest filtering and square build, then the
    first two blobs swapped and the data root recomputed with blind trees
    (out_of_order_prepare.go:18-76)."""
    honest = app.prepare_proposal(raw_txs, t=t)
    sq = honest.square if hasattr(honest, "square") else None
    block = honest.block if hasattr(honest, "block") else honest
    if sq is None:
        raise ValueError("prepare_proposal result carries no square")
    _, root = blind_dah(swap_first_two_blobs(sq))
    import dataclasses

    # replace ONLY the data root: every other header field (including any
    # added later, like validators_hash) stays honest, so ProcessProposal's
    # rejection exercises the data-root check and nothing else
    forged = dataclasses.replace(block.header, data_hash=root)
    return Block(header=forged, txs=block.txs)


def cmt_bad_parity_entry(ods: np.ndarray, equation: int,
                         xor_byte: int = 0x5A,
                         engine: str = "host"):
    """Malicious CMT producer (codec plane, da/cmt.py): encode the ODS
    honestly, then corrupt base-layer parity symbol `equation` BEFORE
    hashing — the commitments bind the corrupt symbol, so sampling alone
    verifies it, and only the peeling decoder's parity-equation audit
    (one violated equation = the whole fraud proof) can convict. The CMT
    analog of blind_dah's committed non-codeword."""
    from celestia_app_tpu.da import cmt

    honest = cmt.build_layers(ods, engine)
    k = ods.shape[0]
    n_data0 = k * k
    layer0 = honest.layers[0].copy()
    layer0[n_data0 + equation, 0] ^= xor_byte
    # rebuild every layer ABOVE the corruption from the corrupt hashes
    # (the producer commits a self-consistent tree over bad symbols)
    layers = [layer0]
    hash_lists = [cmt._hash_symbols(layer0, engine)]
    data = hash_lists[0].reshape(-1, cmt.Q * cmt.HASH_BYTES)
    for _ in cmt.layer_plan(k)[1:]:
        from celestia_app_tpu.ops import ldpc

        parity = ldpc.encode(data, engine)
        coded = np.concatenate([data, parity], axis=0)
        hash_lists.append(cmt._hash_symbols(coded, engine))
        layers.append(coded)
        data = hash_lists[-1].reshape(-1, cmt.Q * cmt.HASH_BYTES)
    commitments = cmt.CmtCommitments(
        k=k, root_hashes=tuple(bytes(h) for h in hash_lists[-1]))
    return cmt.CmtEntry(commitments, layers, hash_lists)


def pcmt_bad_parity_entry(ods: np.ndarray, equation: int | None = None,
                          xor_byte: int = 0x5A,
                          engine: str = "host"):
    """Malicious PCMT producer (codec plane, da/pcmt.py): polar-encode
    the ODS honestly, corrupt ONE non-data committed class BEFORE
    hashing, and grow the whole hash tree over the result — the
    commitments bind the corrupt class, sampling alone verifies it, and
    only the SC peeling decoder's check audit can convict. With
    ``equation`` the corrupt class is that check's lowest non-data
    member; by default it is the lowest check-constrained non-data
    class. The provable location — (0, lowest check containing the
    corrupt class), which is what ``repair`` raises when that check's
    members are all served — rides on the entry as
    ``entry.fraud_location``."""
    from celestia_app_tpu.da import pcmt
    from celestia_app_tpu.ops import polar

    k = ods.shape[0]
    g = polar.geometry(k * k)
    data = np.ascontiguousarray(ods, dtype=np.uint8).reshape(
        k * k, appconsts.SHARE_SIZE)
    base = polar.encode(data, engine).copy()
    is_data = np.zeros(g.C, dtype=bool)
    is_data[g.data_class] = True
    if equation is None:
        in_check = np.zeros(g.C, dtype=bool)
        in_check[g.checks.ravel()] = True
        target = int(np.flatnonzero(~is_data & in_check)[0])
    else:
        cand = [int(x) for x in g.checks[equation] if not is_data[x]]
        if not cand:
            raise ValueError(
                f"check {equation} has only data members; pick another")
        target = min(cand)
    base[target, 0] ^= xor_byte
    entry = pcmt.build_from_base(ods, base, engine)
    containing = np.flatnonzero((g.checks == target).any(axis=1))
    entry.fraud_location = (0, int(containing[0]))
    return entry


def incorrect_coding_fixture(scheme: str, ods: np.ndarray,
                             engine: str = "host"):
    """THE scheme-keyed committed-non-codeword fixture: returns (entry,
    location, withheld_cells, wire_id) for any registered scheme — the
    one hook sim/scenarios.py drives, so judging a new
    codec needs a fixture here and no if-chains there. ``location`` is
    what the scheme's repair provably raises; ``withheld_cells`` is a
    quarter-ish withholding set that forces samplers to escalate while
    keeping the fraud location's members served (the proof must stay
    assemblable from served symbols)."""
    k = ods.shape[0]
    if scheme == "rs2d-nmt":
        entry = rs2d_bad_parity_entry(ods, row=1)
        # half the bad row withheld: samplers escalate, yet the
        # orthogonal-proof BEFP still finds its k members
        return entry, ("row", 1), [(1, j) for j in range(k)], 0
    if scheme == "cmt-ldpc":
        from celestia_app_tpu.da import cmt as cmt_mod

        bad_eq = 3
        entry = cmt_bad_parity_entry(ods, equation=bad_eq,
                                     engine=engine)
        comm = entry.commitments
        members = set(cmt_mod.equation_members(comm, 0, bad_eq))
        candidates = [i for i in range(comm.n_base)
                      if i not in members]
        withheld = [(0, i) for i in candidates[: comm.n_base // 4]]
        return entry, (0, bad_eq), withheld, 1
    if scheme == "pcmt-polar":
        from celestia_app_tpu.da import pcmt as pcmt_mod

        entry = pcmt_bad_parity_entry(ods, engine=engine)
        location = entry.fraud_location
        comm = entry.commitments
        members = set(pcmt_mod.equation_members(
            comm, location[0], location[1]))
        candidates = [i for i in range(comm.n_base)
                      if i not in members]
        withheld = [(0, i) for i in candidates[: comm.n_base // 4]]
        return entry, location, withheld, 2
    raise ValueError(f"no malicious fixture for scheme {scheme!r}")


def rs2d_bad_parity_entry(ods: np.ndarray, row: int = 1,
                          xor_byte: int = 0x5A):
    """Malicious 2D-RS producer (codec plane): extend honestly, corrupt
    one parity cell of `row`, and commit NMT trees over the RESULT — a
    committed non-codeword whose samples all verify, convictable only by
    a BEFP. The one shared fixture for the rs2d fraud accept/reject
    conformance and the --codec bench (duplicate copies of a
    security-sensitive fixture drift)."""
    from celestia_app_tpu.da import edscache as edscache_mod
    from celestia_app_tpu.utils import fast_host

    k = ods.shape[0]
    eds = fast_host.extend_square_fast(ods).copy()
    eds[row, k + 2] ^= xor_byte
    rows, cols = fast_host.axis_roots_fast(eds)
    dah = dah_mod.DataAvailabilityHeader(
        row_roots=tuple(bytes(r) for r in rows),
        col_roots=tuple(bytes(c) for c in cols),
    )
    return edscache_mod.EdsCacheEntry(
        dah_mod.ExtendedDataSquare(eds), dah, dah.hash())
