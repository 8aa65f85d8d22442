"""Codec plane: the shared conformance suite + byte-identity pins.

Two halves (ISSUE 10):

1. **Byte-identity regression.** The 2D-RS+NMT pipeline moved behind the
   codec interface (da/codec.py, da/codec_rs2d.py); its outputs must be
   byte-identical to the pre-refactor code. The FROZEN_* constants were
   generated from the pre-refactor tree (commit 9f3ebae) on both the
   host and device engines — data roots, DAH hashes, sample-proof node
   bytes, and the empty-block root. If any of these change, consensus
   forked.

2. **Conformance.** Every registered scheme must pass the same
   contract: deterministic encode/commit (host ≡ device bit-identical),
   sample-proof roundtrip + tamper rejection, repair at the scheme's
   declared erasure threshold, and incorrect-coding fraud proofs that
   verify against a malicious producer's commitments and REJECT against
   honest ones.

Heavy CMT sweeps (k >= 128 device matmuls) are tier-2 (`slow`).
"""

import dataclasses
import hashlib

import numpy as np
import pytest

from celestia_app_tpu import appconsts
from celestia_app_tpu.da import cmt as cmt_mod
from celestia_app_tpu.da import codec as dacodec
from celestia_app_tpu.da import dah as dah_mod
from celestia_app_tpu.da import edscache as edscache_mod
from celestia_app_tpu.da import sampling
from celestia_app_tpu.ops import ldpc, polar
from celestia_app_tpu.testing import malicious

# registry-driven (ISSUE 17): registering a codec IS opting into the
# whole conformance suite — no hand-listed scheme pair to forget
SCHEMES = tuple(dacodec.by_id(i).name for i in dacodec.registered_ids())
ENGINES = ("host", "device")  # device == jax-cpu under tier-1

# generated pre-refactor (see module docstring); identical on both
# engines there, so one constant pins both here
FROZEN_RS2D_ROOT = {
    4: "8776b4ab08ecbd258744a5f3c0c885269a8ca7c71b050aca462b47c761a3eea4",
    8: "2aa3a4d105771026327f37b52021f434ff754bd74d1f6c26b6fdcaa2c1ba06b0",
}
FROZEN_RS2D_ROW0 = "0449b4972ba7b28ec8d9303cda1558de"
# sha256 over share||proof-nodes of prove_cell(1, 2), plus its geometry
FROZEN_RS2D_PROOF = {
    4: ("c0f0201595786346c446411d28ad51590d6524c237e41e92c46ae666c1a38615",
        2, 3, 8, 3),
    8: ("110328654cff83c55b6c762401c1f07d2539c230f72d653c320a094a3205373a",
        2, 3, 16, 4),
}
FROZEN_MIN_ROOT = {
    # the reference MinDataAvailabilityHeader hash (pre-refactor value)
    "rs2d-nmt":
        "3d96b7d238e7e0456f6af8e7cdf0a67bd6cf9c2089ecb559c659dcaa1f880353",
    # CMT empty-block root: pure function of (tail share, q, d, root_max)
    "cmt-ldpc":
        "b14c97a1825a294c0cd9727539c36e8a7b14976b2dd29e7895b79075f1425da7",
    # PCMT empty-block root: pure function of (tail share, Q, ROOT_MAX,
    # the polar frozen-set construction and the DOMAIN string)
    "pcmt-polar":
        "ea8f58f171338ec6e9acb8d41651279bdae26755a3e24835d5415a70f4af04e1",
}
# wire-stability pins for the new scheme: these change IFF the CMT
# construction (ldpc tables, layer plan, domain string) changes — which
# is a consensus break and must be deliberate
FROZEN_CMT_ROOT = {
    4: "ecb93696cccd83f43aa92b324296a17fce6c5b3b24c136f50b1e3ed57e3b36da",
    8: "e8bb3e85b5bfae79438fd436acd1afa22d002a679395c861bb9fba59dfb893ea",
}
# same contract for wire id 2: a changed root means the polar frozen-set
# construction, pruned-graph geometry, layer plan, or domain changed —
# a consensus break that must be deliberate
FROZEN_PCMT_ROOT = {
    4: "30cd7537522eb44d4daf235a253a29f8336f694626039a4e85b505605fb15986",
    8: "fe7c3a6cd47a6cb58244971c39e663f46456c8e8e5fb0b47e00c9f1a5a9154cd",
}


def _ods(k: int, seed: int = 7) -> np.ndarray:
    rng = np.random.RandomState(seed)
    return rng.randint(0, 256, size=(k, k, appconsts.SHARE_SIZE),
                       dtype=np.uint8)


def _commitments(codec, entry, k):
    return codec.commitments_from_doc(
        codec.commitments_doc(entry), entry.data_root.hex(), k)


def _bad_entry(scheme: str, ods: np.ndarray):
    """(malicious entry, commitments, fraud location) per scheme, via
    THE scheme-keyed fixture (malicious.incorrect_coding_fixture — the
    same constructor sim/scenarios.py and the --codec bench drive), so
    a new codec's fraud conformance needs a fixture there and nothing
    here. ``entry.dah`` is every scheme's commitments object."""
    entry, location, _withheld, _wire = malicious.incorrect_coding_fixture(
        scheme, ods)
    return entry, entry.dah, location


# ---------------------------------------------------------------------------
# 1. byte-identity: the refactored default scheme vs frozen vectors
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("k", [4, 8])
def test_rs2d_byte_identity_vs_frozen_vectors(k, engine):
    entry = edscache_mod.compute_entry(_ods(k), engine)
    assert entry.data_root.hex() == FROZEN_RS2D_ROOT[k]
    assert entry.dah.hash().hex() == FROZEN_RS2D_ROOT[k]
    assert entry.dah.row_roots[0].hex().startswith(FROZEN_RS2D_ROW0)
    share, proof = entry.get_prover().prove_cell(1, 2)
    digest = hashlib.sha256(b"".join([share] + proof.nodes)).hexdigest()
    want_digest, start, end, total, n_nodes = FROZEN_RS2D_PROOF[k]
    assert digest == want_digest
    assert (proof.start, proof.end, proof.total, len(proof.nodes)) \
        == (start, end, total, n_nodes)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("k", [4, 8])
def test_rs2d_codec_interface_is_the_same_pipeline(k, engine):
    """The codec-object route and the direct edscache route are the SAME
    dispatch — roots, commitments doc, and cache keys all agree."""
    codec = dacodec.get("rs2d-nmt")
    ods = _ods(k)
    via_codec = codec.compute_entry(ods, engine)
    direct = edscache_mod.compute_entry(ods, engine)
    assert via_codec.data_root == direct.data_root
    assert via_codec.dah.row_roots == direct.dah.row_roots
    assert edscache_mod.cache_key(ods) \
        == edscache_mod.cache_key(ods, "rs2d-nmt")


def test_min_roots_pinned_per_scheme():
    for scheme in SCHEMES:
        assert dah_mod.min_data_root(scheme).hex() \
            == FROZEN_MIN_ROOT[scheme], scheme
    # the default call keeps its historical return type and value
    d = dah_mod.min_dah()
    assert d.hash().hex() == FROZEN_MIN_ROOT["rs2d-nmt"]
    assert len(d.row_roots) == 2


def test_cmt_roots_pinned():
    codec = dacodec.get("cmt-ldpc")
    for k, want in FROZEN_CMT_ROOT.items():
        assert codec.compute_entry(_ods(k), "host").data_root.hex() \
            == want


def test_pcmt_roots_pinned():
    codec = dacodec.get("pcmt-polar")
    for k, want in FROZEN_PCMT_ROOT.items():
        assert codec.compute_entry(_ods(k), "host").data_root.hex() \
            == want


# ---------------------------------------------------------------------------
# 2. the shared conformance suite, parametrized over schemes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("k", [4, 8])
def test_encode_commit_deterministic_and_engine_identical(scheme, k):
    codec = dacodec.get(scheme)
    ods = _ods(k)
    a = codec.compute_entry(ods, "host")
    b = codec.compute_entry(ods, "host")
    d = codec.compute_entry(ods, "device")
    assert a.data_root == b.data_root == d.data_root
    assert codec.commitments_doc(a) == codec.commitments_doc(d)
    if hasattr(a, "layers"):  # cmt-ldpc and pcmt-polar
        # bit-identical all the way down: every layer's coded symbols
        # and hash lists, not just the root
        for la, ld in zip(a.layers, d.layers):
            assert np.array_equal(la, ld)
        for ha, hd in zip(a.hash_lists, d.hash_lists):
            assert np.array_equal(ha, hd)


@pytest.mark.parametrize("scheme", SCHEMES)
def test_sample_proof_roundtrip_and_tamper_rejection(scheme):
    import base64

    k = 8
    codec = dacodec.get(scheme)
    ods = _ods(k)
    entry = codec.compute_entry(ods, "host")
    comm = _commitments(codec, entry, k)
    space = codec.sample_space(comm)
    probe = [space[0], space[len(space) // 2], space[-1]]
    payload_key = "share" if scheme == "rs2d-nmt" else "symbol"
    for cell in probe:
        doc = codec.open_sample(entry, cell)
        got = codec.verify_sample(comm, doc)
        assert got is not None and got[0] == cell
        # payload tamper
        raw = bytearray(base64.b64decode(doc[payload_key]))
        raw[0] ^= 1
        bad = {**doc, payload_key: base64.b64encode(bytes(raw)).decode()}
        assert codec.verify_sample(comm, bad) is None
        # wrong-position replay: the proof must bind the coordinates
        if scheme == "rs2d-nmt":
            moved = {**doc, "row": (doc["row"] + 1)
                     % len(comm.row_roots)}
        else:
            moved = {**doc, "index": (doc["index"] + 1) % comm.n_base}
        got2 = codec.verify_sample(comm, moved)
        assert got2 is None or got2[0] != cell
        # proof-node tamper
        if scheme == "rs2d-nmt":
            nodes = list(doc["proof"]["nodes"])
            if nodes:
                n0 = bytearray(base64.b64decode(nodes[0]))
                n0[0] ^= 1
                nodes[0] = base64.b64encode(bytes(n0)).decode()
                bad2 = {**doc, "proof": {**doc["proof"], "nodes": nodes}}
                assert codec.verify_sample(comm, bad2) is None
        else:
            steps = [list(s) for s in doc["steps"]]
            if steps:
                s0 = bytearray(base64.b64decode(steps[0][0]))
                s0[0] ^= 1
                steps[0][0] = base64.b64encode(bytes(s0)).decode()
                assert codec.verify_sample(
                    comm, {**doc, "steps": steps}) is None
    # wire accounting is exact and positive
    doc = codec.open_sample(entry, probe[0])
    wire = (codec.sample_wire_bytes(doc)
            if scheme == "rs2d-nmt" else codec.sample_wire_bytes(doc, comm))
    assert wire > appconsts.SHARE_SIZE


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("scheme", SCHEMES)
def test_repair_at_declared_threshold(scheme, engine):
    """Drop exactly the scheme's declared erasure fraction (seeded mask)
    and reconstruct the ODS bit-for-bit, on both engines."""
    k = 8
    codec = dacodec.get(scheme)
    ods = _ods(k)
    entry = codec.compute_entry(ods, "host")
    comm = _commitments(codec, entry, k)
    space = codec.sample_space(comm)
    n = len(space)
    rng = np.random.RandomState(11)
    drop = set(
        int(i)
        for i in rng.choice(n, size=(n * codec.CATCH_BP) // 10000,
                            replace=False))
    samples = {}
    for i, cell in enumerate(space):
        if i not in drop:
            got = codec.verify_sample(
                comm, codec.open_sample(entry, cell))
            assert got is not None
            samples[cell] = got[1]
    rec = codec.repair(comm, samples, engine)
    assert np.array_equal(np.asarray(rec), ods)


@pytest.mark.parametrize("scheme", SCHEMES)
def test_repair_below_threshold_is_unavailable_not_fraud(scheme):
    k = 8
    codec = dacodec.get(scheme)
    ods = _ods(k)
    entry = codec.compute_entry(ods, "host")
    comm = _commitments(codec, entry, k)
    space = codec.sample_space(comm)
    # serve only a sliver: far below any scheme's repair threshold
    keep = space[: max(2, len(space) // 16)]
    samples = {}
    for cell in keep:
        got = codec.verify_sample(comm, codec.open_sample(entry, cell))
        samples[cell] = got[1]
    with pytest.raises(ValueError):
        codec.repair(comm, samples, "host")


@pytest.mark.parametrize("scheme", SCHEMES)
def test_fraud_proof_accept_and_reject(scheme):
    k = 8
    codec = dacodec.get(scheme)
    ods = _ods(k)
    bad_entry, bad_comm, location = _bad_entry(scheme, ods)
    proof = codec.build_fraud_proof(bad_entry, location)
    # convicts the malicious commitments...
    assert codec.verify_fraud_proof(bad_comm, proof) is True
    # ...but NOT the honest ones for the same data
    honest = codec.compute_entry(ods, "host")
    honest_comm = _commitments(codec, honest, k)
    assert codec.verify_fraud_proof(honest_comm, proof) is False
    # and an honest entry cannot be convicted by its own equation
    honest_proof = codec.build_fraud_proof(honest, location)
    assert codec.verify_fraud_proof(honest_comm, honest_proof) is False


def test_cmt_repair_detects_and_attributes_bad_encoding():
    """The peeling-decoder fraud path end to end at the codec level: a
    committed bad parity symbol surfaces as CmtBadEncodingError with the
    exact (layer, equation), only when every member was served."""
    k = 8
    codec = dacodec.get("cmt-ldpc")
    ods = _ods(k)
    entry, comm, (layer, eq) = _bad_entry("cmt-ldpc", ods)
    space = codec.sample_space(comm)
    samples = {}
    for cell in space:
        got = codec.verify_sample(comm, codec.open_sample(entry, cell))
        assert got is not None  # sampling alone cannot see the fraud
        samples[cell] = got[1]
    with pytest.raises(cmt_mod.CmtBadEncodingError) as exc:
        codec.repair(comm, samples, "host")
    assert (exc.value.layer, exc.value.equation) == (layer, eq)
    # withholding a member of the bad equation: inconsistency remains
    # but is no longer attributable — unavailable, not fraud
    members = cmt_mod.equation_members(comm, layer, eq)
    short = {c: s for c, s in samples.items() if c != (0, members[0])}
    with pytest.raises(ValueError) as exc2:
        codec.repair(comm, short, "host")
    assert not isinstance(exc2.value, cmt_mod.CmtBadEncodingError)


def test_pcmt_repair_detects_and_attributes_bad_encoding():
    """The SC peeling decoder's fraud path end to end at the codec
    level: a committed bad base-layer class surfaces as
    PcmtBadEncodingError with the exact (layer, equation) the fixture
    predicted, only when every check member was served."""
    from celestia_app_tpu.da import pcmt as pcmt_mod

    k = 8
    codec = dacodec.get("pcmt-polar")
    ods = _ods(k)
    entry, comm, (layer, eq) = _bad_entry("pcmt-polar", ods)
    space = codec.sample_space(comm)
    samples = {}
    for cell in space:
        got = codec.verify_sample(comm, codec.open_sample(entry, cell))
        assert got is not None  # sampling alone cannot see the fraud
        samples[cell] = got[1]
    with pytest.raises(pcmt_mod.PcmtBadEncodingError) as exc:
        codec.repair(comm, samples, "host")
    assert (exc.value.layer, exc.value.equation) == (layer, eq)
    # withholding a member of the bad check: inconsistency remains but
    # is no longer attributable — unavailable, not fraud
    members = pcmt_mod.equation_members(comm, layer, eq)
    short = {c: s for c, s in samples.items() if c != (0, members[0])}
    with pytest.raises(ValueError) as exc2:
        codec.repair(comm, short, "host")
    assert not isinstance(exc2.value, pcmt_mod.PcmtBadEncodingError)


def test_pcmt_multilayer_proof_walk_and_step_tamper():
    """k=16 is the smallest square whose PCMT telescopes (2 layers):
    the sample proof carries one batch-subtree step, and tampering any
    sibling on the walk must kill verification. (k=4/8 are single-layer
    — their proofs have zero steps — so the shared roundtrip test at
    k=8 never exercises this path for pcmt.)"""
    import base64

    from celestia_app_tpu.da import pcmt as pcmt_mod

    k = 16
    codec = dacodec.get("pcmt-polar")
    ods = _ods(k)
    entry = codec.compute_entry(ods, "host")
    comm = _commitments(codec, entry, k)
    assert len(comm.plan) == 2
    space = codec.sample_space(comm)
    for cell in (space[0], space[len(space) // 2], space[-1]):
        doc = codec.open_sample(entry, cell)
        assert len(doc["steps"]) == 1
        assert len(doc["steps"][0]) == pcmt_mod.LOG2Q
        got = codec.verify_sample(comm, doc)
        assert got is not None and got[0] == cell
        for s in range(pcmt_mod.LOG2Q):
            steps = [list(st) for st in doc["steps"]]
            sib = bytearray(base64.b64decode(steps[0][s]))
            sib[0] ^= 1
            steps[0][s] = base64.b64encode(bytes(sib)).decode()
            assert codec.verify_sample(
                comm, {**doc, "steps": steps}) is None
    # wire accounting: symbol + varints + LOG2Q siblings per step
    doc = codec.open_sample(entry, space[0])
    want = (len(base64.b64decode(doc["symbol"]))
            + pcmt_mod.LOG2Q * pcmt_mod.HASH_BYTES + 2)
    assert codec.sample_wire_bytes(doc, comm) == want
    assert codec.hashes_per_sample_verify(comm) \
        == 1 + (pcmt_mod.LOG2Q + 1)


# ---------------------------------------------------------------------------
# the LDPC kernels: engine identity + construction determinism
# ---------------------------------------------------------------------------


def test_ldpc_encode_and_peel_host_device_identical():
    n = 128
    rng = np.random.RandomState(0)
    data = rng.randint(0, 256, size=(n, 64), dtype=np.uint8)
    assert np.array_equal(ldpc.encode(data, "host"),
                          ldpc.encode(data, "device"))
    coded = np.concatenate([data, ldpc.encode(data, "host")], axis=0)
    known = np.ones(2 * n, dtype=bool)
    known[rng.choice(2 * n, size=n // 2, replace=False)] = False
    syms = np.where(known[:, None], coded, 0).astype(np.uint8)
    out_h, kn_h, _ = ldpc.peel_host(syms, known)
    out_d, kn_d, _ = ldpc.peel(syms, known, "device")
    assert np.array_equal(out_h, out_d)
    assert np.array_equal(kn_h, kn_d)
    assert kn_h.all() and np.array_equal(out_h, coded)
    # identity must hold on INCONSISTENT input too (fraud repair runs
    # the decoder over a committed non-codeword)
    bad = coded.copy()
    bad[n + 3, 0] ^= 0xFF
    syms2 = np.where(known[:, None], bad, 0).astype(np.uint8)
    out_h2, kn_h2, _ = ldpc.peel_host(syms2, known)
    out_d2, kn_d2, _ = ldpc.peel(syms2, known, "device")
    assert np.array_equal(out_h2, out_d2)
    assert np.array_equal(kn_h2, kn_d2)
    viol = ldpc.check_equations(bad, np.ones(2 * n, dtype=bool))
    assert 3 in viol


def test_ldpc_construction_deterministic_and_regular():
    idx = ldpc.parity_indices(256)
    idx2 = ldpc.parity_indices(256)
    assert idx is idx2  # cached, immutable
    assert idx.shape == (256, ldpc.DEGREE)
    # distinct members per equation (a duplicate would XOR-cancel)
    for row in idx:
        assert len(set(int(x) for x in row)) == ldpc.DEGREE
    m = ldpc.membership(256)
    assert m.shape == (256, 512)
    assert (m.sum(axis=1) == ldpc.DEGREE + 1).all()


# ---------------------------------------------------------------------------
# the polar kernels: engine identity + construction determinism
# ---------------------------------------------------------------------------


def test_polar_encode_and_peel_host_device_identical():
    n_data = 64
    g = polar.geometry(n_data)
    rng = np.random.RandomState(0)
    data = rng.randint(0, 256, size=(n_data, 64), dtype=np.uint8)
    coded_h = polar.encode_host(data)
    coded_d = polar.encode(data, "device")
    assert np.array_equal(coded_h, coded_d)
    # systematic: the data classes carry the data verbatim
    assert np.array_equal(coded_h[g.data_class], data)
    known = np.ones(g.C, dtype=bool)
    known[rng.choice(g.C, size=g.C // 4, replace=False)] = False
    syms = np.where(known[:, None], coded_h, 0).astype(np.uint8)
    out_h, kn_h, _ = polar.peel_host(n_data, syms, known)
    out_d, kn_d, _ = polar.peel(n_data, syms, known, "device")
    assert np.array_equal(out_h, out_d)
    assert np.array_equal(kn_h, kn_d)
    assert kn_h.all() and np.array_equal(out_h, coded_h)
    # identity must hold on INCONSISTENT input too (fraud repair runs
    # the decoder over a committed non-codeword)
    bad = coded_h.copy()
    target = int(g.checks[3, 0])
    bad[target, 0] ^= 0xFF
    syms2 = np.where(known[:, None], bad, 0).astype(np.uint8)
    out_h2, kn_h2, _ = polar.peel_host(n_data, syms2, known)
    out_d2, kn_d2, _ = polar.peel(n_data, syms2, known, "device")
    assert np.array_equal(out_h2, out_d2)
    assert np.array_equal(kn_h2, kn_d2)
    viol = polar.check_equations(n_data, bad, np.ones(g.C, dtype=bool))
    assert viol.size > 0 and 3 in set(int(v) for v in viol)


def test_polar_construction_deterministic_and_well_formed():
    g = polar.geometry(64)
    assert g is polar.geometry(64)  # cached, immutable
    # every surviving check is degree-3 over committed classes; no
    # forced-zero class survived pruning
    assert g.checks.shape[1] == 3
    assert (g.checks >= 0).all() and (g.checks < g.C).all()
    for row in g.checks:
        assert len(set(int(x) for x in row)) == 3
    # data classes are distinct committed classes
    assert len(set(int(x) for x in g.data_class)) == g.n_data
    # the informed frozen set is up-closed under bitwise domination
    # (superset rows are always at least as reliable)
    a = set(int(x) for x in g.A)
    for i in g.A:
        for b in range(g.m):
            assert int(i) | (1 << b) in a
    # the committed-class counts the layer plans and docs rely on
    assert polar.geometry(16).C == 76
    assert polar.geometry(64).C == 431
    assert polar.geometry(256).C == 2227


@pytest.mark.slow
def test_cmt_k128_engine_identity():
    """The k=128 base layer (16384-symbol matmul buckets) host ≡ device;
    tier-2: the dense device GEMMs take minutes on a CPU backend."""
    codec = dacodec.get("cmt-ldpc")
    ods = _ods(128, seed=3)
    a = codec.compute_entry(ods, "host")
    d = codec.compute_entry(ods, "device")
    assert a.data_root == d.data_root


# ---------------------------------------------------------------------------
# scheme threading: headers, cache keys, snapshots, confidence
# ---------------------------------------------------------------------------


def test_header_scheme_id_back_compat():
    from celestia_app_tpu.chain import consensus
    from celestia_app_tpu.chain.block import Header

    base = dict(
        chain_id="codec-test", height=3, time_unix=1_700_000_000.0,
        data_hash=b"\x11" * 32, square_size=4, app_hash=b"\x22" * 32,
        proposer=b"\x33" * 20, app_version=1,
        last_block_hash=b"\x44" * 32, validators_hash=b"\x55" * 32,
    )
    h0 = Header(**base)  # default scheme
    h1 = Header(**base, da_scheme=1)
    # absent scheme id ⇒ scheme 0, and the encoding is UNCHANGED by the
    # codec plane: a scheme-0 header must not carry the suffix
    assert h0.encode() == Header(**base, da_scheme=0).encode()
    assert h1.encode() != h0.encode()
    assert h1.encode().startswith(h0.encode())
    # JSON round-trips; scheme-0 docs stay key-identical to old docs
    d0 = consensus.header_to_json(h0)
    d1 = consensus.header_to_json(h1)
    assert "da_scheme" not in d0
    assert d1["da_scheme"] == 1
    assert consensus.header_from_json(d0) == h0
    assert consensus.header_from_json(d1) == h1


def test_process_proposal_rejects_scheme_mismatch():
    import sys
    sys.path.insert(0, "tests")
    from test_consensus_multinode import CHAIN, _genesis

    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.crypto import PrivateKey

    privs = [PrivateKey.from_seed(bytes([9]))]
    proposer = privs[0].public_key().address()
    cmt_app = App(chain_id=CHAIN, engine="host", da_scheme="cmt-ldpc")
    cmt_app.init_chain(_genesis(privs))
    prop = cmt_app.prepare_proposal([], t=1_700_000_010.0,
                                    proposer=proposer)
    assert prop.block.header.da_scheme == dacodec.SCHEME_CMT
    assert cmt_app.process_proposal(prop.block) is True
    rs_app = App(chain_id=CHAIN, engine="host")
    rs_app.init_chain(_genesis(privs))
    assert rs_app.process_proposal(prop.block) is False
    # and the converse: a cmt node rejects an rs2d proposal
    rs_prop = rs_app.prepare_proposal([], t=1_700_000_010.0,
                                      proposer=proposer)
    assert rs_app.process_proposal(rs_prop.block) is True
    assert cmt_app.process_proposal(rs_prop.block) is False
    # the forged-scheme variant: same commitments, lying id
    forged = dataclasses.replace(prop.block.header, da_scheme=0)
    forged_block = dataclasses.replace(prop.block, header=forged)
    assert rs_app.process_proposal(forged_block) is False


def test_process_proposal_refuses_unregistered_id_before_encode():
    """ISSUE 17 satellite: a header carrying a wire id NO build
    registers is refused up front — the scheme check runs before any
    encode work, so the node never pays for (or crashes in) a codec it
    does not have."""
    import sys
    sys.path.insert(0, "tests")
    from test_consensus_multinode import CHAIN, _genesis

    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.crypto import PrivateKey
    from celestia_app_tpu.utils import telemetry

    privs = [PrivateKey.from_seed(bytes([9]))]
    proposer = privs[0].public_key().address()
    app = App(chain_id=CHAIN, engine="host")
    app.init_chain(_genesis(privs))
    prop = app.prepare_proposal([], t=1_700_000_010.0,
                                proposer=proposer)
    forged = dataclasses.replace(prop.block.header, da_scheme=7)
    forged_block = dataclasses.replace(prop.block, header=forged)
    c0 = telemetry.snapshot()["counters"].get("da.extend_runs", 0)
    assert app.process_proposal(forged_block) is False
    c1 = telemetry.snapshot()["counters"].get("da.extend_runs", 0)
    assert c1 == c0  # refused BEFORE any encode dispatch


def test_snapshot_bootstrap_refuses_unregistered_scheme():
    """A manifest naming a scheme this build does not register is
    refused loudly before any chunk verification or store work."""
    import sys
    sys.path.insert(0, "tests")
    from test_consensus_multinode import CHAIN, _genesis

    from celestia_app_tpu.chain import consensus
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.crypto import PrivateKey

    privs = [PrivateKey.from_seed(bytes([9]))]
    app = App(chain_id=CHAIN, engine="host")
    app.init_chain(_genesis(privs))
    manifest, chunks = consensus.snapshot_app_chunks(app)
    forged = {**manifest, "da_scheme": "quux-codec"}
    before = app.last_app_hash
    with pytest.raises(ValueError, match="quux-codec"):
        consensus.state_sync_bootstrap(app, forged, chunks)
    assert app.last_app_hash == before  # nothing was adopted


def test_edscache_keys_are_scheme_disjoint():
    ods = _ods(4)
    cache = edscache_mod.EdsCache(max_entries=4)
    rs = cache.get_or_compute(ods, "host")
    cm = cache.get_or_compute(ods, "host", "cmt-ldpc")
    assert rs.scheme == "rs2d-nmt" and cm.scheme == "cmt-ldpc"
    assert rs.data_root != cm.data_root
    assert len(cache) == 2
    # both root-indexed for the commit path
    assert cache.lookup_root(rs.data_root) is rs
    assert cache.lookup_root(cm.data_root) is cm
    # cmt entries satisfy the block-plane entry contract
    assert cm.k == 4 and cm.dah.hash() == cm.data_root
    cm.warm()  # no-op, must not raise


def test_snapshot_manifest_carries_scheme_and_bootstrap_refuses():
    import sys
    sys.path.insert(0, "tests")
    from test_consensus_multinode import CHAIN, _genesis

    from celestia_app_tpu.chain import consensus
    from celestia_app_tpu.chain import sync as sync_mod
    from celestia_app_tpu.chain.app import App
    from celestia_app_tpu.chain.crypto import PrivateKey

    privs = [PrivateKey.from_seed(bytes([9]))]
    cmt_app = App(chain_id=CHAIN, engine="host", da_scheme="cmt-ldpc")
    cmt_app.init_chain(_genesis(privs))
    manifest, chunks = consensus.snapshot_app_chunks(cmt_app)
    assert sync_mod.manifest_scheme(manifest) == "cmt-ldpc"
    rs_app = App(chain_id=CHAIN, engine="host")
    rs_app.init_chain(_genesis(privs))
    rs_manifest, rs_chunks = consensus.snapshot_app_chunks(rs_app)
    # default-scheme manifests carry NO scheme key: their digests (which
    # key on-disk restore resume state) are unchanged by the codec plane
    assert "da_scheme" not in rs_manifest
    assert sync_mod.manifest_scheme(rs_manifest) == "rs2d-nmt"
    with pytest.raises(ValueError, match="scheme"):
        consensus.state_sync_bootstrap(rs_app, manifest, chunks)
    with pytest.raises(ValueError, match="scheme"):
        consensus.state_sync_bootstrap(cmt_app, rs_manifest, rs_chunks)
    # same-scheme adoption still works
    joiner = App(chain_id=CHAIN, engine="host", da_scheme="cmt-ldpc")
    joiner.init_chain(_genesis(privs))
    consensus.state_sync_bootstrap(joiner, manifest, chunks)
    assert joiner.last_app_hash == cmt_app.last_app_hash


def test_confidence_is_per_scheme_on_the_codec_interface():
    rs = dacodec.get("rs2d-nmt")
    cm = dacodec.get("cmt-ldpc")
    # the historical helper is exactly the default scheme's instance
    for s in (1, 8, 16):
        assert sampling.withholding_catch_confidence(s) \
            == rs.confidence(s) == 1.0 - 0.75 ** s
        assert sampling.catch_confidence(s, "cmt-ldpc") \
            == cm.confidence(s)
    assert rs.samples_for_confidence(0.99) == 17
    assert cm.samples_for_confidence(0.99) == \
        sampling.samples_for_confidence(0.99, "cmt-ldpc")
    with pytest.raises(dacodec.CodecError):
        dacodec.get("no-such-scheme")
    assert dacodec.by_id(0) is rs and dacodec.by_id(1) is cm
    assert dacodec.by_id(2) is dacodec.get("pcmt-polar")
    assert dacodec.registered_ids() == [0, 1, 2]


def test_unknown_scheme_errors_name_the_id_and_list_registered():
    """ISSUE 17 satellite: whoever hits a wire id or name this build
    does not carry sees exactly what it DOES carry."""
    with pytest.raises(dacodec.CodecError) as exc:
        dacodec.by_id(7)
    msg = str(exc.value)
    assert "7" in msg
    for part in ("0=rs2d-nmt", "1=cmt-ldpc", "2=pcmt-polar"):
        assert part in msg
    with pytest.raises(dacodec.CodecError) as exc2:
        dacodec.get("no-such-scheme")
    msg2 = str(exc2.value)
    assert "no-such-scheme" in msg2 and "2=pcmt-polar" in msg2
