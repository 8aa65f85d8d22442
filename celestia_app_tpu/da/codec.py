"""The codec plane: pluggable DA commitment schemes behind one interface.

The reference hard-wires a single DA construction — 2D Reed-Solomon over
GF(2^8) committed with NMTs (pkg/da, pkg/wrapper) — and until this module
so did this repo. Four of the five PAPERS.md entries are *alternative*
commitment constructions (Coded Merkle Tree arXiv:1910.01247 and its
polar-coded variants, RS-protocol trade-offs arXiv:2201.08261), each with
different bytes-per-sample / samples-to-confidence / fraud-proof-size
economics — the costs that dominate at millions of sampling light
clients. This registry makes the scheme an explicit, header-committed
choice instead of an assumption:

- ``Codec`` is the interface a scheme implements: encode ODS → extended
  payload + commitments + 32-byte data root; open/verify sample proofs;
  repair from a symbol subset; build/verify incorrect-coding fraud
  proofs; and the scheme's own confidence arithmetic (the per-sample
  catch probability differs per construction — the old hard-coded
  ``1-(3/4)^s`` is just the 2D-RS instance).
- The registry binds compact wire ids: scheme id 0 is the 2D-RS+NMT
  default (``da/codec_rs2d.py``, byte-identical to the pre-codec-plane
  pipeline — pinned against frozen vectors), id 1 the TPU-native Coded
  Merkle Tree (``da/cmt.py``). Headers carry the id (absent ⇒ 0, so
  every pre-plane hash is unchanged); ProcessProposal rejects proposals
  whose scheme differs from the node's configured codec; snapshots and
  DAS serving docs carry the scheme name.

Confidence helpers live here (not in the per-scheme modules) for the
same reason ``da/sampling.py`` keeps them: they are light-client-side
float math, outside the det-float consensus scope the scheme modules
ride in.

Design: docs/DESIGN.md "The codec plane"; wire formats: docs/FORMATS.md
§16.
"""

from __future__ import annotations

import math

# Wire scheme ids (FORMATS §16.1): headers encode the id (absent/0 =
# rs2d-nmt for back-compat), JSON surfaces carry the name.
SCHEME_RS2D = 0
SCHEME_CMT = 1
SCHEME_PCMT = 2

RS2D_NAME = "rs2d-nmt"
CMT_NAME = "cmt-ldpc"
PCMT_NAME = "pcmt-polar"


class CodecError(ValueError):
    """Malformed scheme input (unknown scheme, bad proof shape, ...)."""


class BadEncodingDetected(Exception):
    """Base of every scheme's incorrect-coding detection: repair() found
    the commitments provably commit an invalid codeword. ``location`` is
    the scheme's fraud coordinate (("row", 1) for rs2d-nmt, (layer,
    equation) for cmt-ldpc) — exactly what ``build_fraud_proof`` /
    ``fraud_cells`` consume, so the DASer's escalation path is
    scheme-generic (das/daser.py catches THIS type, never a concrete
    scheme's)."""

    def __init__(self, location: tuple, msg: str):
        super().__init__(msg)
        self.location = location


class Codec:
    """One DA commitment scheme. Stateless: entries carry the per-block
    payload; the codec owns the algorithms and parameters.

    The scheme's *entry* objects (returned by ``compute_entry``) share a
    small duck-typed surface with the block plane (da/edscache.py):
    ``.scheme`` (name), ``.data_root`` (32 bytes), ``.dah`` (the
    commitments object: a DataAvailabilityHeader for rs2d-nmt, a
    CmtCommitments for cmt-ldpc — both with ``.hash() == data_root``),
    ``.k`` (ODS width) and ``.warm()`` (pre-build proof machinery
    off the hot path)."""

    scheme_id: int
    name: str

    # basis points of the per-sample withholding catch probability: the
    # fraction of the scheme's sampleable units an adversary must
    # withhold before data becomes unrecoverable (10000 = certainty)
    CATCH_BP: int

    # -- encode / commit -------------------------------------------------

    def compute_entry(self, ods, engine: str = "auto"):
        """(k, k, 512) u8 ODS -> scheme entry (commitments + payload +
        data root). THE one encode dispatch — engine-gated, host ≡
        device bit-identical, counts ``da.extend_runs``."""
        raise NotImplementedError

    def _encode_impl(self, ods, engine: str = "auto"):
        """Raw encode hook `da/edscache.compute_entry` resolves through
        the registry (it owns the front door: the ``da.extend_runs``
        counter and the default scheme's inline pipeline). Non-default
        schemes implement this; callers use ``compute_entry``."""
        raise NotImplementedError

    def min_entry(self, engine: str = "host"):
        """Entry of the minimum (empty-block) square: one tail-padding
        share — the scheme's genesis/empty data root."""
        import numpy as np

        from celestia_app_tpu.da import shares as shares_mod

        share = np.frombuffer(shares_mod.tail_padding_share(),
                              dtype=np.uint8)
        return self.compute_entry(share.reshape(1, 1, -1), engine)

    # -- commitments on the wire ----------------------------------------

    def commitments_doc(self, entry) -> dict:
        """The scheme-specific half of the /das/header JSON payload."""
        raise NotImplementedError

    def commitments_from_doc(self, doc: dict, data_root_hex: str,
                             square_size: int):
        """Parse + VERIFY a served commitments doc against the certified
        data root and header square size; raises CodecError if it does
        not bind. Returns the commitments object."""
        raise NotImplementedError

    # -- sampling --------------------------------------------------------

    def sample_space(self, commitments) -> list[tuple[int, int]]:
        """Every sampleable cell as a wire (a, b) pair — (row, col) of
        the extended square for rs2d-nmt, (layer, index) for cmt-ldpc.
        Light clients draw uniformly from this space."""
        raise NotImplementedError

    def open_sample(self, entry, cell: tuple[int, int]) -> dict:
        """Serve one cell: the sample JSON doc (FORMATS §7.2 / §16.3)."""
        raise NotImplementedError

    def verify_sample(self, commitments, doc: dict):
        """Verify one served sample doc against trusted commitments.
        Returns (cell, payload_bytes) on success, None on any failure."""
        raise NotImplementedError

    def sample_wire_bytes(self, doc: dict, commitments=None) -> int:
        """Exact canonical binary size of one sample proof (FORMATS
        §16.3) — the per-sample cost on the wire (NOT the JSON/base64
        transport inflation). Schemes whose wire
        size depends on geometry take the commitments too."""
        raise NotImplementedError

    def hashes_per_sample_verify(self, commitments) -> int:
        """SHA-256 compression *invocations* a verifier pays per sample
        (tree nodes for rs2d, one hash per layer step + the symbol hash
        for cmt)."""
        raise NotImplementedError

    # -- repair / fraud --------------------------------------------------

    def repair(self, commitments, samples: dict, engine: str = "auto"):
        """Reconstruct the full ODS from verified samples
        ({cell: payload bytes}). Raises the scheme's bad-encoding error
        (carrying the fraud location) when the commitments provably
        commit an invalid codeword, ValueError when simply short of the
        repair threshold. Returns the (k, k, 512) ODS."""
        raise NotImplementedError

    def build_fraud_proof(self, entry, location):
        """Producer/full-node side: the compact incorrect-coding proof
        for a bad location a repair attempt surfaced."""
        raise NotImplementedError

    def verify_fraud_proof(self, commitments, proof) -> bool:
        """Light-node side: True iff the proof demonstrates the
        commitments commit an invalid codeword."""
        raise NotImplementedError

    def fraud_proof_type(self) -> type:
        """The scheme's fraud-proof class. Gossip surfaces (the light
        client's submit_fraud_proof) resolve the codec from the proof's
        TYPE via the registry — adding a scheme never grows an if-chain
        there."""
        raise NotImplementedError

    def fraud_cells(self, commitments, location) -> list[tuple]:
        """The sample cells a light node must hold (served + verified)
        to assemble the fraud proof for ``location`` — what the DASer's
        scheme-generic escalation fetches (schemes whose fraud proofs
        cannot be assembled from served cells need not implement)."""
        raise NotImplementedError

    def fraud_proof_from_members(self, commitments, location,
                                 members: list[tuple]):
        """Assemble the proof from served members: ``members`` is one
        (cell, payload, sample-doc) triple per ``fraud_cells`` cell, in
        order."""
        raise NotImplementedError

    # -- confidence arithmetic (per-scheme; light-client math) -----------

    def catch_probability(self) -> float:
        """Per-sample probability a borderline withholding attack loses
        the sample (the scheme's availability threshold)."""
        return self.CATCH_BP / 10000.0

    def confidence(self, samples: int) -> float:
        """1 - (1 - catch)^s: availability confidence after s verified
        samples."""
        return 1.0 - (1.0 - self.catch_probability()) ** samples

    def samples_for_confidence(self, target: float = 0.99) -> int:
        """Smallest s with confidence(s) >= target."""
        if not 0.0 < target < 1.0:
            raise CodecError(f"confidence target {target} not in (0, 1)")
        miss = 1.0 - self.catch_probability()
        return max(1, math.ceil(math.log(1.0 - target) / math.log(miss)))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Codec] = {}
_BY_ID: dict[int, Codec] = {}


def register(codec: Codec) -> Codec:
    """Bind a codec under its name AND wire id (idempotent re-register
    of the same name replaces it — test fixtures re-import freely)."""
    _REGISTRY[codec.name] = codec
    _BY_ID[codec.scheme_id] = codec
    return codec


def _ensure_builtin() -> None:
    # lazy: the scheme modules import da/edscache & ops/, which must not
    # load at `import celestia_app_tpu.da.codec` time (cli --help paths)
    if RS2D_NAME not in _REGISTRY:
        from celestia_app_tpu.da import codec_rs2d  # noqa: F401
    if CMT_NAME not in _REGISTRY:
        from celestia_app_tpu.da import cmt  # noqa: F401
    if PCMT_NAME not in _REGISTRY:
        from celestia_app_tpu.da import pcmt  # noqa: F401


def _registered_desc() -> str:
    """'id=name' listing for unknown-scheme errors: whoever hits a wire
    id or name this build does not carry should see exactly what it
    DOES carry (tests pin both the id and the names appear)."""
    return ", ".join(
        f"{i}={_BY_ID[i].name}" for i in sorted(_BY_ID))


def get(name: str) -> Codec:
    """Codec by scheme name; raises CodecError for unknown schemes."""
    _ensure_builtin()
    codec = _REGISTRY.get(name)
    if codec is None:
        raise CodecError(
            f"unknown DA scheme {name!r} "
            f"(registered: {_registered_desc()})")
    return codec


def by_id(scheme_id: int) -> Codec:
    """Codec by wire id (header da_scheme field; absent ⇒ 0 = rs2d)."""
    _ensure_builtin()
    codec = _BY_ID.get(scheme_id)
    if codec is None:
        raise CodecError(
            f"unknown DA scheme id {scheme_id} "
            f"(registered: {_registered_desc()})")
    return codec


def default() -> Codec:
    return get(RS2D_NAME)


def names() -> list[str]:
    _ensure_builtin()
    return sorted(_REGISTRY)


def registered_ids() -> list[int]:
    """Sorted wire ids of every registered scheme — what the shared
    conformance suite (tests/test_codec_iface.py) parametrizes over, so
    a new scheme is conformance-covered by registration alone."""
    _ensure_builtin()
    return sorted(_BY_ID)
