"""Unified telemetry for the training runtime (SURVEY.md §6).

The reference's observability was Hadoop progress counters, log4j, and the
MixServer's JMX beans. After the ingest-pipeline, fused-dispatch, and
fault-tolerance rounds the rebuild had four disjoint counter surfaces
(PipelineStats, the stager's stack/megabatch counters, MixClient/MixServer
counters, CheckpointManager) and a loss-cadence jsonl stream — but no way
to answer "where did this step's time go" or "is this live run healthy"
without reading bench output. This package is the layer that unifies them:

- :mod:`trace` — low-overhead span tracing (monotonic clock, thread-safe
  ring buffer, one attribute check when disabled) wired into the hot path
  at its real seams: ingest prep, megabatch stacking, h2d staging, the
  jitted (mega)step dispatch, MIX exchanges, checkpoint saves. Per-stage
  ``{count, total_s, cpu_s, p50, p99}`` rollups land in the jsonl metrics stream
  at the loss-fold cadence; the raw spans export as Chrome-trace JSON
  (chrome://tracing / Perfetto) alongside ``jax.profiler``.
- :mod:`registry` — the central counter registry every subsystem registers
  with; ``registry.snapshot()`` is ONE merged, JSON-ready dict
  (pipeline/stager, train progress, mix client+server, checkpoints, span
  rollups, metrics-stream health).
- :mod:`http` — opt-in single-threaded HTTP surface (``-obs_port``):
  ``/snapshot`` (JSON) and ``/metrics`` (Prometheus text exposition) off
  the registry — the MixServer's JMX peer, back.
- :mod:`report` — the ``hivemall_tpu obs <metrics.jsonl>`` terminal
  summary (rates, stage breakdown, breaker state, checkpoint age).
- :mod:`histo` — cumulative fixed-bucket histograms (the Prometheus
  ``_bucket/_sum/_count`` primitive) feeding serve request-latency and
  batch-size families on ``/metrics``, and window diffs in :mod:`slo`.
- :mod:`slo` — the fleet SLO engine: ring time series over serving
  totals, 5 m / 1 h error-budget burn rates (``/slo``), and in-tree
  changefinder drift detection over the latency and prediction-score
  streams (``slo_drift`` events in the metrics jsonl).

See docs/OBSERVABILITY.md for the event schema, span names, and the
"Serving traces and SLOs" tier (request-scoped trace propagation across
the serving fleet, per-hop latency breakdowns, burn-rate math).
"""

from .histo import Histogram
from .registry import Registry, registry
from .slo import SloEngine
from .trace import Tracer, get_tracer, mint_trace_id

__all__ = ["Registry", "registry", "Tracer", "get_tracer",
           "mint_trace_id", "Histogram", "SloEngine"]
