"""Polar Coded Merkle Tree: the third DA commitment scheme
(arXiv:2201.07287, frozen-set design per arXiv:2301.08295).

Where the CMT (da/cmt.py) codes each tree layer with a sparse LDGM code,
the PCMT codes it with a polar code and commits the code's *pruned
factor graph* (ops/polar.py): every committed class — data, coded
output, and surviving intermediate stage value — is hashed, and the
degree-3 XOR checks between classes are the parity equations that give
peeling repair and one-violated-equation fraud proofs. The layering
mirrors the CMT: hash the base layer's committed classes, batch the
hashes into data symbols of the next layer, polar-code THAT layer, and
repeat until a layer has <= ROOT_MAX committed classes, whose hash list
is published outright as the block commitment; the 32-byte data root is
one sha256 over the parameterized root hash list (FORMATS §16.5).

One structural departure from the CMT's flat q=8 hash batching, forced
by measurement: the pruned polar graph commits ~2.4-7.3 classes per
data symbol *growing with log n* (ops/polar.py geometry; the factor-
graph interior is what buys polar its detection economics), so a flat
q=8 batch would never telescope — C_j/8 >= D_j from k=16 up. PCMT
therefore batches Q=64 hashes per parent data symbol and aggregates
each batch with a depth-6 binary Merkle subtree whose ROOT (32 bytes)
is the parent symbol. A sample proof step then carries 6 sibling
hashes (192 B) instead of 63 (2016 B), and the layer recursion shrinks
by ~Q/(C/D) ≈ 9-13x per step — at k=128 the tree telescopes in a few
layers and a sample proof stays smaller than both other schemes
(byte counts; no timing measured).

Sampling threshold: light clients draw uniformly over the C_0 BASE
committed classes (each sample's proof carries one batch-subtree path
and one committed class of every upper layer — the CMT trick, polar
flavored). CATCH_BP declares 1/4: the pruned-graph peeling decoder
recovers from a uniformly random 25-30% erasure of the committed
classes with zero failures across 60 seeded trials at every deployed
size (D = 16 through 16384, measured before this module was written),
so a withholder must hide beyond that fraction to threaten recovery.
Like the CMT's, this threshold is empirical-random, not combinatorial —
the paper's informed frozen-set design *shrinks* stopping sets rather
than excluding them.

Engine gating mirrors da/cmt.py: "device" demands jax (polar bit-matmul
butterflies + batched sha256 on device), "host" never touches it,
"auto" degrades loudly; the engines are pinned bit-identical in
tests/test_codec_iface.py, including SC-decode on inconsistent fraud
inputs.
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

from celestia_app_tpu import appconsts
from celestia_app_tpu.da import codec as codec_mod
from celestia_app_tpu.da.cmt import _hash_symbols
from celestia_app_tpu.da.shares import uvarint
from celestia_app_tpu.ops import polar

# hash-batch width: Q hashes of layer j aggregate (via a depth-LOG2Q
# binary subtree) into ONE 32-byte data symbol of layer j+1
Q = 64
LOG2Q = 6
HASH_BYTES = 32
# stop coding when a layer commits <= ROOT_MAX classes; its hash list
# IS the published commitment (same 16 KB ceiling as the CMT's)
ROOT_MAX = 512
DOMAIN = b"PCMT\x01"


class PcmtBadEncodingError(codec_mod.BadEncodingDetected):
    """A degree-3 check over commitment-verified classes is violated:
    the producer committed an invalid codeword at (layer, equation)."""

    def __init__(self, layer: int, equation: int):
        super().__init__(
            (layer, equation),
            f"bad PCMT encoding: layer {layer} equation {equation}")
        self.layer = layer
        self.equation = equation


def layer_plan(k: int) -> list[tuple[int, int]]:
    """[(n_data, sym_bytes)] per layer, base first — a pure function of
    k (the committed-class counts come from ops/polar.geometry, itself
    a pure function of n_data)."""
    plan = [(k * k, appconsts.SHARE_SIZE)]
    while polar.geometry(plan[-1][0]).C > ROOT_MAX:
        c = polar.geometry(plan[-1][0]).C
        plan.append((-(-c // Q), HASH_BYTES))
    return plan


def _layer_c(plan: list[tuple[int, int]], layer: int) -> int:
    return polar.geometry(plan[layer][0]).C


@dataclasses.dataclass(frozen=True)
class PcmtCommitments:
    """The per-block commitment a light client holds: parameters + the
    top layer's hash list. ``hash()`` is the header's data root."""

    k: int
    root_hashes: tuple[bytes, ...]

    def hash(self) -> bytes:
        out = bytearray(DOMAIN)
        out += uvarint(self.k) + uvarint(Q) + uvarint(ROOT_MAX)
        out += uvarint(len(self.root_hashes))
        for h in self.root_hashes:
            out += h
        return hashlib.sha256(bytes(out)).digest()

    @property
    def plan(self) -> list[tuple[int, int]]:
        return layer_plan(self.k)

    @property
    def n_base(self) -> int:
        """Base-layer committed class count — the sample space size."""
        return polar.geometry(self.k * self.k).C

    def validate_basic(self) -> None:
        plan = self.plan
        want = _layer_c(plan, len(plan) - 1)
        if len(self.root_hashes) != want:
            raise codec_mod.CodecError(
                f"root hash count {len(self.root_hashes)} != {want} "
                f"for k={self.k}")
        for h in self.root_hashes:
            if len(h) != HASH_BYTES:
                raise codec_mod.CodecError("root hash has size != 32")


class PcmtEntry:
    """One encoded block: every layer's committed class values, hash
    lists, and batch subtrees. Duck-compatible with the block plane's
    EdsCacheEntry surface (da/edscache.py)."""

    scheme = codec_mod.PCMT_NAME

    def __init__(self, commitments: PcmtCommitments,
                 layers: list[np.ndarray],
                 hash_lists: list[np.ndarray],
                 subtrees: list[list[np.ndarray]],
                 ods: np.ndarray):
        self.commitments = commitments
        self.layers = layers  # [(C_j, S_j) u8 committed values]
        self.hash_lists = hash_lists  # [(C_j, 32) u8]
        # per non-top layer: LOG2Q+1 levels, level 0 = zero-padded
        # leaf hashes (Q*D_{j+1}, 32), level LOG2Q = batch roots
        self.subtrees = subtrees
        self._ods = np.ascontiguousarray(ods, dtype=np.uint8)
        self.data_root = commitments.hash()
        self.eds = None

    @property
    def dah(self):
        return self.commitments

    @property
    def k(self) -> int:
        return self.commitments.k

    def ods(self) -> np.ndarray:
        k = self.commitments.k
        return self._ods.reshape(k, k, appconsts.SHARE_SIZE)

    def warm(self) -> None:
        """Proof machinery (hash lists + subtrees) is built at encode —
        nothing to pre-build."""


def _subtree_levels(hashes: np.ndarray, n_batches: int,
                    engine: str) -> list[np.ndarray]:
    """Aggregate a layer's hash list into n_batches Q-wide binary
    Merkle subtrees; level 0 is the zero-padded leaves, the last level
    the (n_batches, 32) batch roots — layer j+1's data symbols."""
    padded = np.zeros((n_batches * Q, HASH_BYTES), dtype=np.uint8)
    padded[: len(hashes)] = hashes
    levels = [padded]
    cur = padded
    for _ in range(LOG2Q):
        cur = _hash_symbols(cur.reshape(-1, 2 * HASH_BYTES), engine)
        levels.append(cur)
    return levels


def build_from_base(ods: np.ndarray, base_vals: np.ndarray,
                    engine: str = "auto") -> PcmtEntry:
    """Hash-and-aggregate pipeline from given BASE committed values up
    to the root hash list. Split out of build_layers so the malicious
    fixture (testing/malicious.py) can grow a self-consistent tree over
    a corrupt base codeword."""
    k = ods.shape[0]
    plan = layer_plan(k)
    layers = [base_vals]
    hash_lists: list[np.ndarray] = []
    subtrees: list[list[np.ndarray]] = []
    vals = base_vals
    for j in range(len(plan)):
        hashes = _hash_symbols(vals, engine)
        hash_lists.append(hashes)
        if j + 1 < len(plan):
            levels = _subtree_levels(hashes, plan[j + 1][0], engine)
            subtrees.append(levels)
            vals = polar.encode(levels[-1], engine)
            layers.append(vals)
    commitments = PcmtCommitments(
        k=k, root_hashes=tuple(bytes(h) for h in hash_lists[-1]))
    return PcmtEntry(commitments, layers, hash_lists, subtrees, ods)


def build_layers(ods: np.ndarray, engine: str = "auto") -> PcmtEntry:
    """The encode pipeline: ODS -> PcmtEntry."""
    k = ods.shape[0]
    data = np.ascontiguousarray(ods, dtype=np.uint8).reshape(
        k * k, appconsts.SHARE_SIZE)
    return build_from_base(ods, polar.encode(data, engine), engine)


# ---------------------------------------------------------------------------
# sample proofs
# ---------------------------------------------------------------------------


def _b64(b: bytes) -> str:
    import base64

    return base64.b64encode(b).decode()


def open_sample(entry: PcmtEntry, layer: int, index: int) -> dict:
    """Serve committed class (layer, index) with its layered inclusion
    proof: LOG2Q batch-subtree siblings per step; the recomputed batch
    root IS the parent layer's data symbol, whose committed position is
    derived from the (deterministic) parent geometry."""
    plan = entry.commitments.plan
    if not 0 <= layer < len(plan):
        raise codec_mod.CodecError(f"no PCMT layer {layer}")
    if not 0 <= index < _layer_c(plan, layer):
        raise codec_mod.CodecError(
            f"class {index} outside layer {layer} "
            f"({_layer_c(plan, layer)} classes)")
    steps: list[list[str]] = []
    pos = index
    for j in range(layer, len(plan) - 1):
        levels = entry.subtrees[j]
        idx = pos
        sibs = []
        for lvl in range(LOG2Q):
            sibs.append(bytes(levels[lvl][idx ^ 1]))
            idx >>= 1
        steps.append([_b64(s) for s in sibs])
        parent_geom = polar.geometry(plan[j + 1][0])
        pos = int(parent_geom.data_class[pos // Q])
    return {
        "layer": layer,
        "index": index,
        "symbol": _b64(bytes(entry.layers[layer][index])),
        "steps": steps,
    }


def verify_sample(commitments: PcmtCommitments, doc: dict):
    """Check one served sample doc. Returns ((layer, index), symbol
    bytes) when the symbol is committed at that position, None on ANY
    failure (malformed, wrong size, wrong path, unbound root)."""
    import base64

    try:
        layer = int(doc["layer"])
        index = int(doc["index"])
        symbol = base64.b64decode(doc["symbol"])
        steps = doc["steps"]
    except (KeyError, TypeError, ValueError):
        return None
    plan = commitments.plan
    if not 0 <= layer < len(plan):
        return None
    if not 0 <= index < _layer_c(plan, layer) \
            or len(symbol) != plan[layer][1]:
        return None
    if not isinstance(steps, list) or len(steps) != len(plan) - 1 - layer:
        return None
    h = hashlib.sha256(symbol).digest()
    pos = index
    try:
        for j, step in zip(range(layer, len(plan) - 1), steps):
            if len(step) != LOG2Q:
                return None
            sibs = [base64.b64decode(s) for s in step]
            if any(len(s) != HASH_BYTES for s in sibs):
                return None
            idx = pos
            for sib in sibs:
                h = hashlib.sha256(
                    sib + h if idx & 1 else h + sib).digest()
                idx >>= 1
            # h is now the batch root == the parent data symbol
            parent_geom = polar.geometry(plan[j + 1][0])
            pos = int(parent_geom.data_class[pos // Q])
            h = hashlib.sha256(h).digest()
    except (TypeError, ValueError):
        return None
    if h != commitments.root_hashes[pos]:
        return None
    return (layer, index), symbol


def sample_wire_bytes(commitments: PcmtCommitments, doc: dict) -> int:
    """Canonical binary size of the proof (FORMATS §16.6): varint layer
    + varint index + symbol + LOG2Q*32 per step."""
    plan = commitments.plan
    layer = int(doc["layer"])
    return (len(uvarint(layer)) + len(uvarint(int(doc["index"])))
            + plan[layer][1]
            + len(doc["steps"]) * LOG2Q * HASH_BYTES)


# ---------------------------------------------------------------------------
# repair (SC peeling) + incorrect-coding fraud proofs
# ---------------------------------------------------------------------------


def repair(commitments: PcmtCommitments, samples: dict,
           engine: str = "auto") -> np.ndarray:
    """Reconstruct the ODS from verified samples {(layer, index):
    bytes}. Base-layer classes feed the SC peeling decoder; a violated
    check whose members were ALL served with proofs raises
    PcmtBadEncodingError (the fraud location a light node can prove
    from served symbols alone). A peel that stalls before recovering
    every data class raises ValueError (below threshold: withholding,
    not provably mis-coded). On success the recovered data's full
    recommitment must reproduce the committed root — a mismatch means
    an upper layer was mis-coded (not provable from base samples
    alone)."""
    plan = commitments.plan
    k = commitments.k
    d0, s0 = plan[0]
    g = polar.geometry(d0)
    base = {i: b for (layer, i), b in samples.items() if layer == 0}
    if not base:
        raise ValueError("no base-layer samples to reconstruct from")
    vals = np.zeros((g.C, s0), dtype=np.uint8)
    known = np.zeros(g.C, dtype=bool)
    for i, b in sorted(base.items()):
        vals[i] = np.frombuffer(b, dtype=np.uint8)
        known[i] = True
    vals, known, _sweeps = polar.peel(d0, vals, known, engine)
    violated = polar.check_equations(d0, vals, known)
    for eq in violated:
        members = equation_members(commitments, 0, int(eq))
        if all(m in base for m in members):
            raise PcmtBadEncodingError(0, int(eq))
    if violated.size:
        raise ValueError(
            f"PCMT layer 0 inconsistent at equations "
            f"{violated[:4].tolist()} but members were not all served")
    if not known[g.data_class].all():
        raise ValueError(
            f"below peeling threshold: "
            f"{int((~known[g.data_class]).sum())} of {d0} data classes "
            f"unrecovered")
    ods = vals[g.data_class].reshape(k, k, appconsts.SHARE_SIZE)
    rebuilt = build_layers(ods, engine)
    if rebuilt.data_root != commitments.hash():
        raise ValueError(
            "recovered data does not reproduce the committed root: an "
            "upper PCMT layer was mis-coded (fetch its symbols to "
            "prove)")
    return ods


def equation_members(commitments: PcmtCommitments, layer: int,
                     equation: int) -> list[int]:
    """Committed-class indices of one check's three members at a layer
    (deterministic pruned-graph construction) — the exact member order
    a PcmtFraudProof must carry."""
    g = polar.geometry(commitments.plan[layer][0])
    return [int(x) for x in g.checks[equation]]


@dataclasses.dataclass(frozen=True)
class PcmtSymbolWithProof:
    index: int  # committed-class index within the equation's layer
    symbol: bytes
    doc: dict  # the served sample doc (carries the layered proof)


@dataclasses.dataclass(frozen=True)
class PcmtFraudProof:
    """One violated degree-3 check: three members, each carried with
    its inclusion proof. O(1) in the block size."""

    layer: int
    equation: int
    members: tuple[PcmtSymbolWithProof, ...]


def generate_fraud(entry: PcmtEntry, layer: int,
                   equation: int) -> PcmtFraudProof:
    """Full-node side: assemble the proof from an entry it holds."""
    members = equation_members(entry.commitments, layer, equation)
    return PcmtFraudProof(
        layer=layer,
        equation=equation,
        members=tuple(
            PcmtSymbolWithProof(
                index=m,
                symbol=bytes(entry.layers[layer][m]),
                doc=open_sample(entry, layer, m),
            )
            for m in members
        ),
    )


def verify_fraud(commitments: PcmtCommitments,
                 proof: PcmtFraudProof) -> bool:
    """True iff the proof demonstrates the commitments commit an
    invalid codeword: every member symbol verifies against the
    commitments AT the positions the (deterministically recomputed)
    check demands, and the three members XOR to non-zero. False for
    malformed proofs and for honest blocks."""
    try:
        plan = commitments.plan
        if not 0 <= proof.layer < len(plan):
            return False
        g = polar.geometry(plan[proof.layer][0])
        if not 0 <= proof.equation < len(g.checks):
            return False
        expected = equation_members(commitments, proof.layer,
                                    proof.equation)
        if [m.index for m in proof.members] != expected:
            return False
        syms: list[bytes] = []
        for m in proof.members:
            got = verify_sample(commitments, m.doc)
            if got is None:
                return False
            (layer, index), symbol = got
            if layer != proof.layer or index != m.index \
                    or symbol != m.symbol:
                return False
            syms.append(symbol)
        acc = (np.frombuffer(syms[0], dtype=np.uint8)
               ^ np.frombuffer(syms[1], dtype=np.uint8))
        return not np.array_equal(
            acc, np.frombuffer(syms[2], dtype=np.uint8))
    except (KeyError, TypeError, ValueError, IndexError,
            AttributeError):
        # AttributeError: a proof routed against the wrong scheme's
        # commitments object is malformed input, not a crash
        return False


# ---------------------------------------------------------------------------
# the Codec implementation
# ---------------------------------------------------------------------------


class PcmtCodec(codec_mod.Codec):
    scheme_id = codec_mod.SCHEME_PCMT
    name = codec_mod.PCMT_NAME
    CATCH_BP = 2500  # declared sampling threshold (module docstring)

    def compute_entry(self, ods: np.ndarray,
                      engine: str = "auto") -> PcmtEntry:
        from celestia_app_tpu.da import edscache

        return edscache.compute_entry(ods, engine, scheme=self.name)

    def _encode_impl(self, ods: np.ndarray,
                     engine: str = "auto") -> PcmtEntry:
        return build_layers(ods, engine)

    def commitments_doc(self, entry) -> dict:
        c = entry.dah
        return {
            "scheme": self.name,
            "k": c.k,
            "q": Q,
            "root_max": ROOT_MAX,
            "root_hashes": [h.hex() for h in c.root_hashes],
            "data_root": entry.data_root.hex(),
        }

    def commitments_from_doc(self, doc: dict, data_root_hex: str,
                             square_size: int) -> PcmtCommitments:
        try:
            if int(doc["q"]) != Q or int(doc["root_max"]) != ROOT_MAX:
                raise codec_mod.CodecError(
                    "served PCMT parameters differ from this build's")
            c = PcmtCommitments(
                k=int(doc["k"]),
                root_hashes=tuple(
                    bytes.fromhex(h) for h in doc["root_hashes"]),
            )
        except (KeyError, TypeError, ValueError) as e:
            raise codec_mod.CodecError(
                f"malformed PCMT commitments doc: {e}") from None
        c.validate_basic()
        if c.k != square_size:
            raise codec_mod.CodecError(
                "served PCMT k contradicts the header square size")
        if c.hash().hex() != data_root_hex:
            raise codec_mod.CodecError(
                "served PCMT commitments do not bind to the data root")
        return c

    def sample_space(self, commitments) -> list[tuple[int, int]]:
        # base layer only: each sample's proof carries one class of
        # every upper layer, implicitly sampling them (the CMT trick)
        return [(0, i) for i in range(commitments.n_base)]

    def open_sample(self, entry, cell: tuple[int, int]) -> dict:
        return open_sample(entry, cell[0], cell[1])

    def verify_sample(self, commitments, doc: dict):
        return verify_sample(commitments, doc)

    def sample_wire_bytes(self, doc: dict, commitments=None) -> int:
        if commitments is None:
            raise codec_mod.CodecError(
                "pcmt wire size needs commitments")
        return sample_wire_bytes(commitments, doc)

    def hashes_per_sample_verify(self, commitments) -> int:
        # the symbol hash, then LOG2Q subtree nodes + one parent-symbol
        # hash per layer step
        return 1 + (len(commitments.plan) - 1) * (LOG2Q + 1)

    def repair(self, commitments, samples: dict,
               engine: str = "auto") -> np.ndarray:
        return repair(commitments, samples, engine)

    def build_fraud_proof(self, entry, location) -> PcmtFraudProof:
        layer, equation = location
        return generate_fraud(entry, layer, equation)

    def verify_fraud_proof(self, commitments, proof) -> bool:
        return verify_fraud(commitments, proof)

    def fraud_proof_type(self) -> type:
        return PcmtFraudProof

    def fraud_cells(self, commitments, location) -> list[tuple]:
        layer, equation = location
        return [(layer, m)
                for m in equation_members(commitments, layer, equation)]

    def fraud_proof_from_members(self, commitments, location,
                                 members: list[tuple]) -> PcmtFraudProof:
        layer, equation = location
        return PcmtFraudProof(
            layer=layer, equation=equation,
            members=tuple(
                PcmtSymbolWithProof(index=cell[1], symbol=payload,
                                    doc=doc)
                for cell, payload, doc in members
            ),
        )


codec_mod.register(PcmtCodec())
