"""repro.obs — dependency-free structured telemetry (DESIGN.md §2.8).

Two halves, one record stream:

* :mod:`repro.obs.trace` — nestable :func:`span`\\ s and point
  :func:`counter_event`\\ s in a bounded ring, exported as
  schema-versioned JSONL stamped with git sha / backend / jax version;
  once jax is imported, spans also annotate the profiler's host timeline
  (``repro.<path>``) and JAX's compile steps arrive as ``jit.*`` counters.
* :mod:`repro.obs.metrics` — a process-global registry of counters,
  gauges, and fixed-bucket histograms (p50/p99 without stored samples),
  exportable as BENCH JSON, JSONL records, or Prometheus text.

Both are stdlib-only and safe to import anywhere in the repo — including
before jax — so every layer (challenge, stream, serve, benchmarks) wires
through the same two globals.
"""
from .trace import (  # noqa: F401
    SCHEMA_VERSION,
    Span,
    Tracer,
    counter_event,
    export_jsonl,
    get_tracer,
    jit_compile_count,
    read_jsonl,
    reset_tracer,
    run_context,
    span,
)
from .metrics import (  # noqa: F401
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    reset_registry,
)

__all__ = [
    "SCHEMA_VERSION",
    "Span",
    "Tracer",
    "span",
    "counter_event",
    "get_tracer",
    "reset_tracer",
    "run_context",
    "export_jsonl",
    "read_jsonl",
    "jit_compile_count",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "reset_registry",
    "DEFAULT_LATENCY_BUCKETS",
]
