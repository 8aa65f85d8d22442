"""celestia_app_tpu — a TPU-native data-availability framework.

A from-scratch rebuild of the capabilities of celestia-app (the Celestia
DA network's state machine) designed TPU-first:

- The compute core — 2D Reed-Solomon extension of the data square, namespaced
  Merkle tree (NMT) hashing, share commitments and inclusion proofs — runs as
  batched GF(256) bit-matrix matmuls (MXU) and vectorized SHA-256 (VPU/Pallas)
  under ``jax.jit``, with static power-of-two shape buckets.
- The protocol plane — deterministic square layout, PrepareProposal /
  ProcessProposal / CheckTx semantics, the PayForBlobs state machine, gas and
  fee rules — runs host-side in deterministic Python.
- Multi-chip scaling shards the extended square per-row over a
  ``jax.sharding.Mesh`` with XLA collectives (all-to-all transpose between the
  row and column passes, all-gather of axis roots).

Layout:
  appconsts   protocol constants (immutable / versioned / governed layers)
  ops         device kernels: GF(256) RS codec, SHA-256, NMT reduction, Merkle
  da          data-availability pipeline: namespaces, shares, square layout,
              EDS extension, DA header, commitments, proofs
  chain       ABCI-shaped state machine: app, ante, modules (blob/bank/auth/
              mint/signal/minfee), tx codec
  parallel    device-mesh sharded execution of the DA pipeline
  client      tx signer / client
  utils       host-side reference implementations and helpers
"""

__version__ = "0.1.0"

# Runtime lock-order detection (the analysis plane's dynamic half):
# CELESTIA_RACE=1 wraps threading.Lock/RLock before any submodule
# creates one, so chaos/stress runs — including their subprocess
# nodes, which inherit the env — record lock acquisition order and
# surface ABBA inversions. CELESTIA_LOCKPROF=1 installs the SAME
# wrapper but for contention profiling (per-creation-site lock.wait
# histograms + hold gauges in /metrics) — order bookkeeping stays off
# unless CELESTIA_RACE asks for it. See tools/analyze/racecheck.py.
import os as _os

# The persistent compile cache, placed before anything imports JAX (JAX
# reads the variable once, at import) and WITHOUT importing it: a
# host-engine process must stay off the accelerator runtime. An operator's
# JAX_COMPILATION_CACHE_DIR wins; otherwise the cache sits at a fixed path
# in the checkout — the path is part of the cache key, so it never moves.
_os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), ".jax_cache"),
)

_race = _os.environ.get("CELESTIA_RACE", "").strip() == "1"
_lockprof = _os.environ.get("CELESTIA_LOCKPROF", "").strip() == "1"
if _race or _lockprof:
    from celestia_app_tpu.tools.analyze import racecheck as _racecheck

    _racecheck.install()
    _racecheck.set_order_tracking(_race)
    _racecheck.set_profiling(_lockprof)
