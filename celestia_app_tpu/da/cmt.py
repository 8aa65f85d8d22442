"""Coded Merkle Tree: the second DA commitment scheme (arXiv:1910.01247).

Where the default scheme commits a 2D-RS square with 4k NMTs, CMT codes
the k*k ODS shares with a rate-1/2 sparse LDGM code (ops/ldpc.py), hashes
the 2k^2 coded symbols, batches every q=8 hashes into one data symbol of
the next layer, codes THAT layer the same way, and repeats until the
coded layer is small enough (<= ROOT_MAX symbols) to publish its hash
list outright as the block commitment. The 32-byte data root is one
sha256 over the parameterized root hash list (FORMATS §16.2).

Why a second scheme at all (the north star's economics):

- **Per-sample proof bytes.** A sample proof is the base symbol plus q-1
  sibling hashes per layer step — 512 + 3*224 + varints = 1187 canonical
  wire bytes at k=128 (FORMATS §16.3) against 2D-RS+NMT's
  512 + 8*90 + varints = 1238 (and 4 sha256 invocations to verify
  against 9): strictly smaller (byte counts; no timing measured).
- **O(1) fraud proofs.** Incorrect coding is proven by ONE violated
  parity equation — d+1 symbols with their inclusion proofs (~12 KB at
  k=128) — against a BEFP's k shares + orthogonal proofs (~160 KB).
- **Peeling repair.** Reconstruction is iterative degree-1 resolution
  (masked matmul sweeps, ops/ldpc.peel), not per-axis RS decoding.

Sampling threshold: light clients draw uniformly over the 2k^2 BASE
coded symbols (each sample's proof carries — and therefore implicitly
samples — one symbol of every upper layer, the CMT trick). CATCH_BP
declares 1/4: ops/ldpc.py's degree-8 construction peels a 1/4-erased
layer w.h.p. at every deployed size (measured, margin documented there),
so a withholder must hide beyond that fraction to threaten recovery and
each uniform sample then catches it with probability > 1/4. Unlike the
2D-RS bound this threshold is empirical-random, not combinatorial —
adversarially-shaped stopping sets below it are not excluded by
construction (the paper's hand-designed ensembles bound them; ours pins
the threshold by test).

Engine gating mirrors da/edscache.compute_entry: "device" demands jax
(LDPC bit-matmul + batched sha256 on device), "host" never touches it
(XOR-gather + hashlib), "auto" degrades loudly; the two are pinned
bit-identical in tests/test_codec_iface.py.
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

from celestia_app_tpu import appconsts
from celestia_app_tpu.da import codec as codec_mod
from celestia_app_tpu.da.shares import uvarint
from celestia_app_tpu.ops import ldpc

# hash-batch width: q hashes of layer j form one data symbol of layer j+1
Q = 8
HASH_BYTES = 32
# stop coding when a layer has <= ROOT_MAX coded symbols; its hash list
# IS the published commitment (16 KB ceiling — a third of a k=128 DAH)
ROOT_MAX = 512
DOMAIN = b"CMT\x01"


class CmtBadEncodingError(codec_mod.BadEncodingDetected):
    """A parity equation over commitment-verified symbols is violated:
    the producer committed an invalid codeword at (layer, equation)."""

    def __init__(self, layer: int, equation: int):
        super().__init__(
            (layer, equation),
            f"bad CMT encoding: layer {layer} equation {equation}")
        self.layer = layer
        self.equation = equation


def layer_plan(k: int) -> list[tuple[int, int]]:
    """[(n_data, sym_bytes)] per layer, base first — a pure function of
    k, so every node derives identical geometry from the header alone."""
    plan = [(k * k, appconsts.SHARE_SIZE)]
    while 2 * plan[-1][0] > ROOT_MAX:
        plan.append(((2 * plan[-1][0]) // Q, Q * HASH_BYTES))
    return plan


@dataclasses.dataclass(frozen=True)
class CmtCommitments:
    """The per-block commitment a light client holds: parameters + the
    top layer's hash list. ``hash()`` is the header's data root."""

    k: int
    root_hashes: tuple[bytes, ...]

    def hash(self) -> bytes:
        out = bytearray(DOMAIN)
        out += uvarint(self.k) + uvarint(Q) + uvarint(ldpc.DEGREE)
        out += uvarint(ROOT_MAX) + uvarint(len(self.root_hashes))
        for h in self.root_hashes:
            out += h
        return hashlib.sha256(bytes(out)).digest()

    @property
    def plan(self) -> list[tuple[int, int]]:
        return layer_plan(self.k)

    @property
    def n_base(self) -> int:
        return 2 * self.k * self.k

    def validate_basic(self) -> None:
        plan = self.plan
        if len(self.root_hashes) != 2 * plan[-1][0]:
            raise codec_mod.CodecError(
                f"root hash count {len(self.root_hashes)} != "
                f"{2 * plan[-1][0]} for k={self.k}")
        for h in self.root_hashes:
            if len(h) != HASH_BYTES:
                raise codec_mod.CodecError("root hash has size != 32")


def _hash_symbols(symbols: np.ndarray, engine: str) -> np.ndarray:
    """(n, S) u8 -> (n, 32) u8 sha256 digests, engine-gated (vmapped
    device SHA-256 vs hashlib over memoryview slices), bit-identical."""
    # host coded symbols (np.concatenate output), never a device value
    symbols = np.ascontiguousarray(symbols, dtype=np.uint8)  # lint: disable=xfer-reach
    if engine == "auto" and not ldpc.auto_wants_device():
        # CPU "auto": OpenSSL SHA-NI via hashlib beats the jnp scan path
        # by far (same gating reasoning as ops/ldpc.auto_wants_device)
        from celestia_app_tpu.utils import fast_host

        return fast_host._sha_many(symbols)
    if engine in ("device", "auto"):
        try:
            from celestia_app_tpu.obs import xfer
            from celestia_app_tpu.ops import sha256 as sha_mod

            return xfer.to_host(
                sha_mod.sha256(
                    xfer.to_device(symbols, "cmt.hash_symbols")),
                "cmt.hash_symbols")
        except Exception:
            if engine == "device":
                raise
            from celestia_app_tpu.utils import telemetry

            telemetry.incr("app.device_path_fallback")
    from celestia_app_tpu.utils import fast_host

    return fast_host._sha_many(symbols)


class CmtEntry:
    """One encoded block: every layer's coded symbols + hash lists.
    Duck-compatible with the block plane's EdsCacheEntry surface
    (da/edscache.py): ``scheme``/``data_root``/``dah``/``k``/``warm``."""

    scheme = codec_mod.CMT_NAME

    def __init__(self, commitments: CmtCommitments,
                 layers: list[np.ndarray],
                 hash_lists: list[np.ndarray]):
        self.commitments = commitments
        self.layers = layers  # [(n_coded_j, S_j) u8]
        self.hash_lists = hash_lists  # [(n_coded_j, 32) u8]
        self.data_root = commitments.hash()
        # the block plane stores no EDS for this scheme; samplers get
        # symbols, never raw square cells
        self.eds = None

    @property
    def dah(self):
        """The scheme's commitments object (the ``.dah`` slot of the
        extend-once lifecycle carries 'whatever binds to data_root')."""
        return self.commitments

    @property
    def k(self) -> int:
        return self.commitments.k

    def ods(self) -> np.ndarray:
        k = self.commitments.k
        return self.layers[0][: k * k].reshape(
            k, k, appconsts.SHARE_SIZE)

    def warm(self) -> None:
        """Proof machinery is the hash lists, already built at encode —
        nothing to pre-build (the warmer calls this for every scheme)."""


def build_layers(ods: np.ndarray,
                 engine: str = "auto") -> CmtEntry:
    """The encode pipeline: ODS -> CmtEntry. Layer j's coded symbols are
    [data || ldpc parity]; its hash list feeds layer j+1's data."""
    k = ods.shape[0]
    # the ODS argument is host bytes by codec contract (admission hands
    # the encode pipeline numpy shares)
    data = np.ascontiguousarray(ods, dtype=np.uint8).reshape(  # lint: disable=xfer-reach
        k * k, appconsts.SHARE_SIZE)
    layers: list[np.ndarray] = []
    hash_lists: list[np.ndarray] = []
    plan = layer_plan(k)
    for depth, (_n_data, _sym) in enumerate(plan):
        parity = ldpc.encode(data, engine)
        coded = np.concatenate([data, parity], axis=0)
        hashes = _hash_symbols(coded, engine)
        layers.append(coded)
        hash_lists.append(hashes)
        if depth + 1 < len(plan):
            data = hashes.reshape(-1, Q * HASH_BYTES)
    commitments = CmtCommitments(
        k=k, root_hashes=tuple(bytes(h) for h in hash_lists[-1]))
    return CmtEntry(commitments, layers, hash_lists)


# ---------------------------------------------------------------------------
# sample proofs
# ---------------------------------------------------------------------------


def _b64(b: bytes) -> str:
    import base64

    return base64.b64encode(b).decode()


def open_sample(entry: CmtEntry, layer: int, index: int) -> dict:
    """Serve coded symbol (layer, index) with its layered inclusion
    proof: q-1 sibling hashes per step up to the root hash list."""
    plan = entry.commitments.plan
    if not 0 <= layer < len(plan):
        raise codec_mod.CodecError(f"no CMT layer {layer}")
    n_coded = 2 * plan[layer][0]
    if not 0 <= index < n_coded:
        raise codec_mod.CodecError(
            f"symbol {index} outside layer {layer} ({n_coded} symbols)")
    steps: list[list[str]] = []
    pos = index
    for j in range(layer, len(plan) - 1):
        base = (pos // Q) * Q
        off = pos % Q
        sibs = [
            bytes(entry.hash_lists[j][base + t])
            for t in range(Q) if t != off
        ]
        steps.append([_b64(s) for s in sibs])
        pos //= Q
    return {
        "layer": layer,
        "index": index,
        "symbol": _b64(bytes(entry.layers[layer][index])),
        "steps": steps,
    }


def verify_sample(commitments: CmtCommitments, doc: dict):
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
    n_coded = 2 * plan[layer][0]
    if not 0 <= index < n_coded or len(symbol) != plan[layer][1]:
        return None
    if not isinstance(steps, list) or len(steps) != len(plan) - 1 - layer:
        return None
    h = hashlib.sha256(symbol).digest()
    pos = index
    try:
        for step in steps:
            if len(step) != Q - 1:
                return None
            sibs = [base64.b64decode(s) for s in step]
            if any(len(s) != HASH_BYTES for s in sibs):
                return None
            off = pos % Q
            parent = b"".join(sibs[:off]) + h + b"".join(sibs[off:])
            h = hashlib.sha256(parent).digest()
            pos //= Q
    except (TypeError, ValueError):
        return None
    if h != commitments.root_hashes[pos]:
        return None
    return (layer, index), symbol


def sample_wire_bytes(commitments: CmtCommitments, doc: dict) -> int:
    """Canonical binary size of the proof (FORMATS §16.3): varint layer +
    varint index + symbol + (q-1)*32 per step."""
    import base64

    plan = commitments.plan
    layer = int(doc["layer"])
    return (len(uvarint(layer)) + len(uvarint(int(doc["index"])))
            + plan[layer][1]
            + len(doc["steps"]) * (Q - 1) * HASH_BYTES)


# ---------------------------------------------------------------------------
# repair (peeling) + incorrect-coding fraud proofs
# ---------------------------------------------------------------------------


def repair(commitments: CmtCommitments, samples: dict,
           engine: str = "auto") -> np.ndarray:
    """Reconstruct the ODS from verified samples {(layer, index): bytes}.

    Base-layer symbols feed the peeling decoder; a violated parity
    equation whose members are ALL commitment-verified raises
    CmtBadEncodingError (the fraud location a light node can prove from
    served symbols alone). A peel that stalls before recovering every
    data symbol raises ValueError (below threshold: withholding, but not
    provably mis-coded). On success the recovered data's full
    recommitment must reproduce the committed root — a mismatch means an
    upper layer was mis-coded; it is reported (not provable from base
    samples alone; upper-layer equations need their own served symbols,
    which `DASer._build_cmt_fraud` fetches by (layer, index))."""
    plan = commitments.plan
    k = commitments.k
    n_data0, sym0 = plan[0]
    n0 = 2 * n_data0
    base = {i: b for (layer, i), b in samples.items() if layer == 0}
    if not base:
        raise ValueError("no base-layer samples to reconstruct from")
    symbols = np.zeros((n0, sym0), dtype=np.uint8)
    known = np.zeros(n0, dtype=bool)
    for i, b in sorted(base.items()):
        symbols[i] = np.frombuffer(b, dtype=np.uint8)
        known[i] = True
    symbols, known, _sweeps = ldpc.peel(symbols, known, engine)
    violated = ldpc.check_equations(symbols, known)
    for eq in violated:
        members = equation_members(commitments, 0, int(eq))
        if all(m in base for m in members):
            raise CmtBadEncodingError(0, int(eq))
    if violated.size:
        # inconsistent, but some member was only peeled, never served
        # with a proof: cannot attribute to a provable equation
        raise ValueError(
            f"CMT layer 0 inconsistent at equations "
            f"{violated[:4].tolist()} but members were not all served")
    if not known[:n_data0].all():
        raise ValueError(
            f"below peeling threshold: {int((~known[:n_data0]).sum())} "
            f"of {n_data0} data symbols unrecovered")
    ods = symbols[:n_data0].reshape(k, k, appconsts.SHARE_SIZE)
    rebuilt = build_layers(ods, engine)
    if rebuilt.data_root != commitments.hash():
        raise ValueError(
            "recovered data does not reproduce the committed root: an "
            "upper CMT layer was mis-coded (fetch its symbols to prove)")
    return ods


def equation_members(commitments: CmtCommitments, layer: int,
                     equation: int) -> list[int]:
    """Coded indices of one parity equation's members at a layer: the d
    data neighbors (deterministic ldpc construction) then the parity
    symbol itself — the exact member order a CmtFraudProof must carry."""
    n_data = commitments.plan[layer][0]
    idx = ldpc.parity_indices(n_data)
    return [int(m) for m in idx[equation]] + [n_data + equation]


@dataclasses.dataclass(frozen=True)
class CmtSymbolWithProof:
    index: int  # coded index within the equation's layer
    symbol: bytes
    doc: dict  # the served sample doc (carries the layered proof)


@dataclasses.dataclass(frozen=True)
class CmtFraudProof:
    """One violated parity equation: d data members + the parity member,
    each carried with its inclusion proof. O(1) in the block size."""

    layer: int
    equation: int
    members: tuple[CmtSymbolWithProof, ...]


def generate_fraud(entry: CmtEntry, layer: int,
                   equation: int) -> CmtFraudProof:
    """Full-node side: assemble the proof from an entry it holds."""
    members = equation_members(entry.commitments, layer, equation)
    return CmtFraudProof(
        layer=layer,
        equation=equation,
        members=tuple(
            CmtSymbolWithProof(
                index=m,
                symbol=bytes(entry.layers[layer][m]),
                doc=open_sample(entry, layer, m),
            )
            for m in members
        ),
    )


def verify_fraud(commitments: CmtCommitments,
                 proof: CmtFraudProof) -> bool:
    """True iff the proof demonstrates the commitments commit an invalid
    codeword: every member symbol verifies against the commitments AT
    the positions the (deterministically recomputed) equation demands,
    and the XOR of the data members differs from the parity member.
    False for malformed proofs and for honest blocks."""
    try:
        plan = commitments.plan
        if not 0 <= proof.layer < len(plan):
            return False
        n_data = plan[proof.layer][0]
        if not 0 <= proof.equation < n_data:
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
        acc = np.zeros(plan[proof.layer][1], dtype=np.uint8)
        for s in syms[:-1]:
            acc ^= np.frombuffer(s, dtype=np.uint8)
        return not np.array_equal(
            acc, np.frombuffer(syms[-1], dtype=np.uint8))
    except (KeyError, TypeError, ValueError, IndexError,
            AttributeError):
        # AttributeError: a proof routed against the wrong scheme's
        # commitments object (no .plan / .root_hashes) is malformed
        # input, not a crash
        return False


# ---------------------------------------------------------------------------
# the Codec implementation
# ---------------------------------------------------------------------------


class CmtCodec(codec_mod.Codec):
    scheme_id = codec_mod.SCHEME_CMT
    name = codec_mod.CMT_NAME
    CATCH_BP = 2500  # declared sampling threshold (see module docstring)

    def compute_entry(self, ods: np.ndarray,
                      engine: str = "auto") -> CmtEntry:
        from celestia_app_tpu.da import edscache

        return edscache.compute_entry(ods, engine, scheme=self.name)

    def _encode_impl(self, ods: np.ndarray,
                     engine: str = "auto") -> CmtEntry:
        return build_layers(ods, engine)

    def commitments_doc(self, entry) -> dict:
        c = entry.dah
        return {
            "scheme": self.name,
            "k": c.k,
            "q": Q,
            "degree": ldpc.DEGREE,
            "root_max": ROOT_MAX,
            "root_hashes": [h.hex() for h in c.root_hashes],
            "data_root": entry.data_root.hex(),
        }

    def commitments_from_doc(self, doc: dict, data_root_hex: str,
                             square_size: int) -> CmtCommitments:
        try:
            if (int(doc["q"]) != Q or int(doc["degree"]) != ldpc.DEGREE
                    or int(doc["root_max"]) != ROOT_MAX):
                raise codec_mod.CodecError(
                    "served CMT parameters differ from this build's")
            c = CmtCommitments(
                k=int(doc["k"]),
                root_hashes=tuple(
                    bytes.fromhex(h) for h in doc["root_hashes"]),
            )
        except (KeyError, TypeError, ValueError) as e:
            raise codec_mod.CodecError(
                f"malformed CMT commitments doc: {e}") from None
        c.validate_basic()
        if c.k != square_size:
            raise codec_mod.CodecError(
                "served CMT k contradicts the header square size")
        if c.hash().hex() != data_root_hex:
            raise codec_mod.CodecError(
                "served CMT commitments do not bind to the data root")
        return c

    def sample_space(self, commitments) -> list[tuple[int, int]]:
        # base layer only: each sample's proof carries one symbol of
        # every upper layer, implicitly sampling them (the CMT trick)
        return [(0, i) for i in range(commitments.n_base)]

    def open_sample(self, entry, cell: tuple[int, int]) -> dict:
        return open_sample(entry, cell[0], cell[1])

    def verify_sample(self, commitments, doc: dict):
        return verify_sample(commitments, doc)

    def sample_wire_bytes(self, doc: dict, commitments=None) -> int:
        if commitments is None:
            raise codec_mod.CodecError("cmt wire size needs commitments")
        return sample_wire_bytes(commitments, doc)

    def hashes_per_sample_verify(self, commitments) -> int:
        return len(commitments.plan)  # symbol hash + one per step

    def repair(self, commitments, samples: dict,
               engine: str = "auto") -> np.ndarray:
        return repair(commitments, samples, engine)

    def build_fraud_proof(self, entry, location) -> CmtFraudProof:
        layer, equation = location
        return generate_fraud(entry, layer, equation)

    def verify_fraud_proof(self, commitments, proof) -> bool:
        return verify_fraud(commitments, proof)

    def fraud_proof_type(self) -> type:
        return CmtFraudProof

    def fraud_cells(self, commitments, location) -> list[tuple]:
        layer, equation = location
        return [(layer, m)
                for m in equation_members(commitments, layer, equation)]

    def fraud_proof_from_members(self, commitments, location,
                                 members: list[tuple]) -> CmtFraudProof:
        layer, equation = location
        return CmtFraudProof(
            layer=layer, equation=equation,
            members=tuple(
                CmtSymbolWithProof(index=cell[1], symbol=payload,
                                   doc=doc)
                for cell, payload, doc in members
            ),
        )


codec_mod.register(CmtCodec())
