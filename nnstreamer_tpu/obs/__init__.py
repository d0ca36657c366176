"""nnstreamer_tpu.obs — the unified observability plane (L7).

Reference analog: the GstShark/NNShark tracer ecosystem the reference
delegates profiling to (arxiv 1901.04985, SURVEY §5.1) — but where
GstShark aggregates per-element, this package is REQUEST-scoped and
cross-subsystem. Three pieces, one contract (near-zero cost when idle):

* :mod:`.context` — request-scoped distributed tracing. A
  :class:`~.context.TraceContext` minted where a request enters
  (``QueryClient.request()``, serving admission) propagates through
  fabric retries/hedges (child span per attempt), across the query wire
  (``meta["trace"]``), into the serving batcher (batch spans *link* to
  the N coalesced request spans) and fused device segments
  (``fused:<head>..<tail>`` spans). Export: Perfetto/chrome-trace JSON.
  Gated on one module global (:data:`~.context.TRACING`). The serving
  loop's program spans (:func:`~.context.span`: ``serving.pass`` and the
  phases under it) are always on instead and also write into the JAX
  profiler's trace, so they, and so far only they, lie in a
  ``utils.trace.jax_trace`` XPlane on the device's time base.

* :mod:`.metrics` — a Prometheus-style registry serving, service,
  fabric, queue, and fusion sources publish into; rendered at the
  control plane's ``GET /metrics`` route and by
  ``python -m nnstreamer_tpu obs metrics``.

* :mod:`.flight` — the always-on crash flight recorder: a lock-free
  bounded ring of recent control-plane events (state transitions,
  evictions, crashes, spans) dumped into ``CrashReport`` postmortems and
  on DEGRADED transitions, so "why did it stall" is answerable after
  the fact.

* :mod:`.profile` — the continuous profiler: wall time attributed per
  element / fused segment / queue-wait hop into mergeable
  streaming-quantile digests (:class:`~.profile.QuantileDigest`),
  persisted as **profile artifacts** keyed by (topology hash, caps,
  model version) with load/merge/diff APIs — the placement planner's
  and AOT cache's input. Surfaced at ``GET /profile`` and
  ``python -m nnstreamer_tpu obs profile|top``.

* :mod:`.slo` — declarative per-service objectives (p99 latency, error
  rate, availability, memory pressure, output quality) evaluated from
  the same windowed digests with multi-window burn-rate alerting:
  breaches record flight events, export ``nns_slo_*`` gauges, and flip
  the bound Service to DEGRADED through the existing health path.

* :mod:`.quality` — the data plane's numerical health: sampled tensor
  taps on pad hops and fused-segment outputs (NaN/Inf/zero counts,
  moments, a log-bucket value sketch), per-edge baselines persisted in
  the artifact's ``quality`` section, PSI drift scoring against them,
  and the canary promotion quality gate (``QualityGate`` /
  ``CanaryQuality`` — service/models.py refuses promotion with a typed
  ``QualityGateError`` on divergence).

* :mod:`.fleet` — the cross-PROCESS join: a :class:`~.fleet.FleetView`
  scrapes every subprocess replica's control endpoint on a tick thread
  and merges the planes (digests exactly, memory max-watermark, quality
  additively, flight by timestamp), stitches distributed traces across
  the process boundary into one Perfetto document, and serves the SLO
  engine / autoscaler fleet-merged burn windows. ``nns_fleet_*``
  gauges, ``GET /fleet``, ``obs fleet``. :mod:`.promtext` is the shared
  Prometheus text-format parser the scraper and the tests read
  ``GET /metrics`` with.

See docs/observability.md for the span model, propagation rules,
profiling/SLO/quality semantics, the fleet scrape/merge contract, and
the metric name catalog.
"""
from . import (  # noqa: F401
    context,
    fleet,
    flight,
    memory,
    metrics,
    profile,
    promtext,
    quality,
    slo,
)
from .fleet import FleetView  # noqa: F401
from .memory import AdmissionGuard, MemoryAccountant  # noqa: F401
from .quality import (  # noqa: F401
    CanaryQuality,
    QualityAccountant,
    QualityGate,
    TensorHealth,
)
from .context import (  # noqa: F401
    Span,
    TraceContext,
    disable_tracing,
    enable_tracing,
    export_chrome_trace,
    finished_spans,
    record_span,
    span,
    spans_for_trace,
    start_span,
)
from .flight import FlightRecorder  # noqa: F401
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricError,
    Registry,
    default_registry,
    render,
)
from .profile import (  # noqa: F401
    ProfileArtifact,
    ProfileStore,
    Profiler,
    QuantileDigest,
    WindowedSeries,
    topology_hash,
)
from .slo import SloEngine, SLObjective  # noqa: F401

__all__ = [
    "AdmissionGuard",
    "CanaryQuality",
    "Counter",
    "FleetView",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MemoryAccountant",
    "MetricError",
    "QualityAccountant",
    "QualityGate",
    "TensorHealth",
    "ProfileArtifact",
    "ProfileStore",
    "Profiler",
    "QuantileDigest",
    "Registry",
    "SLObjective",
    "SloEngine",
    "Span",
    "TraceContext",
    "WindowedSeries",
    "context",
    "default_registry",
    "fleet",
    "disable_tracing",
    "enable_tracing",
    "export_chrome_trace",
    "finished_spans",
    "flight",
    "memory",
    "metrics",
    "profile",
    "promtext",
    "quality",
    "record_span",
    "render",
    "slo",
    "span",
    "spans_for_trace",
    "start_span",
    "topology_hash",
]
