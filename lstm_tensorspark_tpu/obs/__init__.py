"""Unified telemetry plane: one process-wide registry of counters,
gauges, and fixed-bucket streaming histograms, exposed two ways —
``GET /metrics`` Prometheus text exposition on the serve HTTP endpoint
(serve/server.py) and histogram summaries inside the ``/stats`` JSON.
(Spans are not this package's: ``utils/tracing.span`` writes them to the
profiler's trace and the ``--trace`` file; where a span and a histogram
cover the same lines the histogram reads the span's stamps.)

Production TPU serving treats step-time/throughput telemetry and
per-request latency breakdowns as first-class (PAPERS.md, "Scalable
Training of Language Models using JAX pjit and TPUv4"): the K-vs-latency
and prefix-cache tradeoffs are tunable from a LIVE server only if the
server itself reports TTFT/ITL/queue-wait distributions, not just
loadgen-side percentiles.

Recording sites (all take a registry parameter, defaulting to
``REGISTRY``; ``NULL_REGISTRY`` disables with no-op instruments):

- serve/batcher.py — queue depth/wait, scheduler-iteration duration,
  per-request TTFT + inter-token-latency histograms, window-K choice,
  prefill-chunk progress, request outcomes;
- serve/engine.py — per-phase compile counts (at trace time),
  window-dispatch timestamps for dispatch→fetch readback latency;
- serve/state_cache.py — state-cache evictions/swaps, prefix-cache
  hit/miss/insert/evict/invalidate;
- train/loop.py — step time, tokens/s, anomalous steps;
- supervise.py — restarts, backoff time, poison/stall verdicts.
"""

from .metrics import (
    DEFAULT_LATENCY_BUCKETS,
    NULL_REGISTRY,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    parse_exposition,
)

__all__ = [
    "Counter",
    "DEFAULT_LATENCY_BUCKETS",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullRegistry",
    "NULL_REGISTRY",
    "REGISTRY",
    "parse_exposition",
]
