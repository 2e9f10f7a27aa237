"""Request-lifecycle observability (docs/observability.md).

Three export surfaces over one clock-injectable `RequestTimeline` stamped
inside the engine:

- Prometheus histograms (metrics.py): TTFT, inter-token latency, queue
  wait, e2e, decode-step and prefill-chunk durations, XLA compile counts.
- OpenTelemetry spans (spans.py + tracing.py): W3C traceparent propagated
  EPP → replica → downstream hops, with engine queue/prefill/decode child
  spans and lifecycle span events.
- Introspection endpoints (introspection.py): GET /admin/telemetry
  (rolling percentiles + recent timelines) and POST /admin/profile
  (on-demand jax.profiler capture).
"""

from .introspection import (  # noqa: F401
    PROFILER_KEY,
    ProfilerBusyError,
    ProfilerSession,
    register_observability_routes,
)
from .spans import emit_timeline_spans  # noqa: F401
from .timeline import (  # noqa: F401
    CPU_COLUMNS,
    DELIVERIES,
    DISPATCH_COLUMNS,
    KV_WRITE_PATHS,
    PARTS,
    PAUSES,
    PHASES,
    DispatchPhases,
    RequestTimeline,
    TimelineRecorder,
    percentiles,
)
