"""Serving observability layer (DESIGN §13).

metrics — dependency-free registry of counters / gauges / fixed-bucket
          histograms with labels, Prometheus text exposition, a JSON
          snapshot, and the repo's one exact-percentile implementation;
trace   — request-lifecycle tracer (submit → queued → admitted →
          prefill_chunk(s) → first_token → decode/spec rounds →
          preempt/re-prefill → finish) exporting Chrome trace-event
          JSON (Perfetto-loadable) and JSONL;
clock   — the ONE monotonic source every lifecycle timestamp routes
          through (``obs.now``): Request stamps, TTFT/ITL observation,
          deadline arithmetic, rate-limit refills and trace timestamps
          all read the same clock, so histograms and spans agree
          exactly (DESIGN §16);
spans   — the phase names of a serving and a training step and
          ``span(name, **counts)``, a profiler ``TraceAnnotation`` on
          the device trace's clock. The one module here that imports
          jax, so it is imported on its own (``repro.obs.spans``).

Everything is host-side python over state the engine already fetched:
instrumentation adds zero device→host transfers (the transfer-counting
tests run with metrics AND tracing enabled) and zero recompiles (the
compile-count regression test pins it).
"""

from repro.obs.clock import now
from repro.obs.metrics import (
    LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    percentile,
)
from repro.obs.trace import Tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "LATENCY_BUCKETS",
    "MetricsRegistry",
    "NullRegistry",
    "Tracer",
    "now",
    "percentile",
]
