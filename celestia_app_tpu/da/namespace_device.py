"""Batched namespace-range search over the resident NMT level stacks.

The read plane's resolver (PAPER §1's millions-of-readers workload,
reference ``pkg/proof`` + the x/blob query surface): one serving node
answers many ``(namespace, height)`` queries per request, so the
per-query host scan in `da/namespace_data.get_namespace_data` — k²
Python slice-compares per query — must become ONE dispatch over the
whole batch. The level-0 ``mins`` of the prover's cached row trees
(da/proof_device.BlockProver.levels — the arrays the block lifecycle's
device pass already produced) ARE the Q0 share namespaces, so the
namespace → share-range search is a single vectorized equality over a
``(queries, k², 29)`` comparison, on device (one jitted dispatch) or on
host SIMD — no square traversal, no per-share Python.

Byte-identity contract: the search only picks each query's contiguous
hit range; proof assembly then runs the SAME ``prover.prove_shares`` /
absence-successor walk the host reference runs, so every returned
`NamespaceData` is byte-identical to `get_namespace_data`'s — pinned
per engine in tests/test_read_plane.py.

Engine gating is the edscache/commitment_device playbook:

- "host" never imports (let alone dispatches) jax — a host-engine
  process must not initialise an accelerator backend it does not own;
- "device"/"mesh" run the jitted search, but a dispatch failure here
  falls back to the host pass COUNTED (``blob.device_fallbacks``),
  never raised — reads are a serving surface, not a consensus phase;
- "auto" uses the device only at/above the ``CELESTIA_BLOB_MIN_BATCH``
  gate (below it the fixed dispatch overhead loses to host SIMD).

The small share→namespace helpers at the bottom are THE one
implementation the DA service's prove_shares route and the blob pack
builder share (service/da_service.py, das/blob_packs.py).
"""

from __future__ import annotations

import functools
import os

import numpy as np

from celestia_app_tpu import appconsts
from celestia_app_tpu.da.namespace_data import (
    NamespaceData,
    _root_window,
    get_namespace_data,
)
from celestia_app_tpu.utils import telemetry

NS = appconsts.NAMESPACE_SIZE


def _min_device_batch() -> int:
    """Queries below this gate resolve on host even under engine="auto"
    (env knob CELESTIA_BLOB_MIN_BATCH; det-reach barrier — both paths
    are pinned byte-identical, so the knob can only move work, never
    change bytes)."""
    return int(os.environ.get("CELESTIA_BLOB_MIN_BATCH", "16"))


# -- shared share→namespace helpers (one implementation; satellite of the
#    read plane: service/da_service.py and das/blob_packs.py call these) --


def decode_namespace(value: str) -> bytes:
    """Hex-decode a namespace request field ('' stays empty — callers
    may default it from a share). Raises ValueError on non-hex input."""
    return bytes.fromhex(value)


def parse_namespace(value: str) -> bytes:
    """Strict form: hex-decode AND require exactly 29 bytes."""
    ns = decode_namespace(value)
    if len(ns) != NS:
        raise ValueError(f"namespace must be {NS} bytes, got {len(ns)}")
    return ns


def share_namespace(share) -> bytes:
    """The 29-byte namespace prefix of one share (bytes or an ODS array
    cell)."""
    if isinstance(share, (bytes, bytearray, memoryview)):
        return bytes(share[:NS])
    return np.asarray(share).tobytes()[:NS]


def leaf_namespaces(prover) -> np.ndarray:
    """(k², 29) uint8: every Q0 share's namespace in row-major order,
    read straight off the prover's resident level-0 ``mins`` (an NMT
    leaf's min IS its namespace) — no ODS materialization, which on a
    mesh DeviceEntry would cost a device→host crossing."""
    from celestia_app_tpu.obs import xfer

    mins = prover.levels[0][0]
    k = prover.k
    # a mesh DeviceEntry keeps `mins` resident: the k×k corner crosses
    # the boundary counted; host provers pass through copy-free
    sub = xfer.ensure_host(mins[:k, :k], "namespace.leaf_mins")
    # reshape of the strided corner always lands in fresh C-order
    # memory (and a materialized device slice is already contiguous)
    return sub.reshape(k * k, NS)


# -- the batched search -----------------------------------------------------


def _as_query_matrix(namespaces) -> np.ndarray:
    """(Q, 29) uint8 from the query namespaces; validates lengths with
    the host reference's error."""
    for ns in namespaces:
        if len(ns) != NS:
            raise ValueError(f"namespace must be {NS} bytes")
    return np.frombuffer(b"".join(namespaces), dtype=np.uint8).reshape(
        len(namespaces), NS
    )


def _search_host(leaf_ns: np.ndarray, qs: np.ndarray):
    """(starts, ends, counts) per query — one SIMD pass, no Python per
    share. Namespaces compare as fixed-width void scalars (memcmp), so
    the (Q, k²) equality matrix is the only intermediate."""
    n = leaf_ns.shape[0]
    void = np.dtype((np.void, NS))
    leaf_v = np.ascontiguousarray(leaf_ns).view(void).reshape(n)
    qs_v = np.ascontiguousarray(qs).view(void).reshape(qs.shape[0])
    eq = qs_v[:, None] == leaf_v[None, :]
    idx = np.arange(n)
    starts = np.where(eq, idx, n).min(axis=1)
    ends = np.where(eq, idx + 1, 0).max(axis=1)
    return starts, ends, eq.sum(axis=1)


@functools.lru_cache(maxsize=None)
def _jitted_search(n_leaves: int, n_queries: int):
    """Compiled (leaf_ns, qs) -> (starts, ends, counts); query counts
    are padded to powers of two by the caller so the compile cache stays
    small."""
    import jax
    import jax.numpy as jnp

    def run(leaf_ns: "jax.Array", qs: "jax.Array"):
        eq = jnp.all(leaf_ns[None, :, :] == qs[:, None, :], axis=-1)
        idx = jnp.arange(n_leaves, dtype=jnp.int32)
        starts = jnp.min(jnp.where(eq, idx, n_leaves), axis=1)
        ends = jnp.max(jnp.where(eq, idx + 1, 0), axis=1)
        return starts, ends, jnp.sum(eq.astype(jnp.int32), axis=1)

    return jax.jit(run)


# queries never legitimately target the parity namespace (it labels
# extended-quadrant shares only), so it is the safe device-pad value
_PAD_NS = b"\xff" * NS


def _search_device(leaf_ns: np.ndarray, qs: np.ndarray):
    """One engine dispatch for the whole batch. May raise (jax missing,
    backend error, OOM) — the caller degrades to the host pass, counted."""
    q = qs.shape[0]
    padded = 1 << max(0, (q - 1)).bit_length()
    if padded != q:
        pad = np.frombuffer(_PAD_NS * (padded - q),
                            dtype=np.uint8).reshape(padded - q, NS)
        qs = np.concatenate([qs, pad], axis=0)
    starts, ends, counts = _jitted_search(leaf_ns.shape[0], padded)(
        leaf_ns, qs
    )
    return (np.asarray(starts)[:q], np.asarray(ends)[:q],
            np.asarray(counts)[:q])


def _absence_data(prover, namespace: bytes) -> NamespaceData:
    """The host reference's absence walk, verbatim semantics
    (da/namespace_data.get_namespace_data lines after the hit scan):
    first straddling Q0 row window → one-leaf successor proof; no
    straddling row → no proof needed."""
    k = prover.k
    ods = prover.eds.squares
    for r in range(k):
        lo, hi = _root_window(prover.dah.row_roots[r])
        if lo <= namespace <= hi:
            succ = next(
                c for c in range(k)
                if ods[r, c, :NS].tobytes() > namespace
            )
            pf = prover.prove_shares(
                r * k + succ, r * k + succ + 1,
                ods[r, succ, :NS].tobytes(),
            )
            return NamespaceData(namespace=namespace, shares=[], proof=pf)
    return NamespaceData(namespace=namespace, shares=[], proof=None)


def get_namespace_data_batched(prover, namespaces,
                               engine: str = "auto") -> list[NamespaceData]:
    """Resolve many namespace queries against one block in one pass.

    Returns one `NamespaceData` per query, in request order, each
    byte-identical to ``get_namespace_data(prover, ns)`` (pinned in
    tests/test_read_plane.py). The search runs batched (device or host
    SIMD per the engine gate); proof assembly is the host reference's
    own machinery either way."""
    namespaces = list(namespaces)
    if not namespaces:
        return []
    qs = _as_query_matrix(namespaces)
    leaf_ns = leaf_namespaces(prover)
    want_device = engine in ("device", "mesh") or (
        engine == "auto" and len(namespaces) >= _min_device_batch()
    )
    starts = None
    if want_device and engine != "host":
        try:
            starts, ends, counts = _search_device(leaf_ns, qs)
            telemetry.incr("blob.device_batches")
        except Exception:
            # reads are a serving surface: a failed dispatch or missing jax
            # degrades to the host pass, loudly counted, never raised
            telemetry.incr("blob.device_fallbacks")
            starts = None
    if starts is None:
        starts, ends, counts = _search_host(leaf_ns, qs)
    out: list[NamespaceData] = []
    for i, namespace in enumerate(namespaces):
        count = int(counts[i])
        if count == 0:
            out.append(_absence_data(prover, namespace))
            continue
        start, end = int(starts[i]), int(ends[i])
        if end - start != count:
            raise AssertionError(
                "namespace shares are not contiguous: square is not sorted"
            )
        pf = prover.prove_shares(start, end, namespace)
        out.append(NamespaceData(
            namespace=namespace,
            shares=[bytes(s) for s in pf.data],
            proof=pf,
        ))
    return out


__all__ = [
    "NS",
    "decode_namespace",
    "parse_namespace",
    "share_namespace",
    "leaf_namespaces",
    "get_namespace_data",
    "get_namespace_data_batched",
]
