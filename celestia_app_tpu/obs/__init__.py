"""The observability plane: spans, structured logging, JAX profiling.

Five sub-modules, one import surface (``from celestia_app_tpu import
obs``):

- ``obs.spans`` — context-manager span API over the columnar TraceTables
  with DETERMINISTIC per-height trace ids (``trace_id_for(chain_id, h)``)
  so proposer, followers, and DAS light nodes correlate without clock
  sync; HTTP propagation via the ``X-Celestia-Trace`` header.
- ``obs.log`` — the leveled structured stderr logger library modules use
  instead of calling ``print`` (lint-enforced).
- ``obs.jax_profile`` — the compile-vs-execute split for the jitted
  pipelines, device gauges, and the /debug/profile capture worker.
- ``obs.xfer`` — the host↔device transfer ledger: every device_put /
  device_get in the tree routes through ``xfer.to_device``/``to_host``
  so bytes, calls, and latency are attributed per call-site label, and
  ``xfer.no_implicit_transfers()`` turns stray implicit copies into
  hard errors for tier-1 residency pins.
- ``obs.gil`` — GIL-pressure oversleep samplers (one per HTTP service,
  one for a process's first ``Node`` where none runs; the unlabelled
  ``gil.samples`` / ``gil.oversleep_us`` counters are a window's mean
  wait for the interpreter) and the ``process.peak_rss_bytes`` /metrics
  gauge (collector registers on import of this package).

Histograms/labels/Prometheus exposition live in utils/telemetry.py (the
metric registry predates this package and everything already imports it).
docs/DESIGN.md "The observability plane" has the span model; FORMATS §10
the wire formats.
"""

from celestia_app_tpu.obs import gil  # noqa: F401  (registers the peak-RSS collector)
from celestia_app_tpu.obs.log import get_logger  # noqa: F401
from celestia_app_tpu.obs.spans import (  # noqa: F401
    NOOP,
    SPAN_TABLE,
    TRACE_HEADER,
    Span,
    begin_request,
    capture,
    enabled,
    end_request,
    http_header,
    resume,
    route_profile,
    route_trace,
    serve_metrics,
    set_enabled,
    span,
    trace_id_for,
)
from celestia_app_tpu.obs.xfer import (  # noqa: F401
    ImplicitTransferError,
    no_implicit_transfers,
    to_device,
    to_host,
)
