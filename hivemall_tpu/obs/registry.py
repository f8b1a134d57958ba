"""Central counter registry — every subsystem's metrics in ONE snapshot.

The reference scattered its counters across Hadoop MapredContext, log4j
and the MixServer's JMX beans; the rebuild likewise grew four disjoint
surfaces (PipelineStats, MixClient/MixServer counters(), CheckpointManager,
Meter). This registry is the merge point: subsystems register a named
zero-argument provider returning a JSON-ready dict, and ``snapshot()``
calls them all into one record — the payload of the ``train_done`` /
``telemetry`` jsonl events, the ``/snapshot`` HTTP endpoint, and (flattened)
the ``/metrics`` Prometheus exposition.

Contract for providers:

- cheap and non-blocking: snapshot() may be called from another thread
  WHILE a fit is running (the live-surface case), so a provider must never
  sync the device, take a long lock, or mutate trainer state;
- JSON-ready: dicts/lists/str/numbers/bools/None only;
- failure-isolated: a provider that raises yields an ``{"error": ...}``
  section, never a broken snapshot.

Registration is last-wins by section name (a new trainer's ``pipeline``
provider replaces the previous trainer's) and providers should hold their
subject weakly — the registry is process-global and must not keep dead
trainers alive.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List

__all__ = ["Registry", "registry"]

Provider = Callable[[], dict]


class Registry:
    """Named sections of JSON-ready counters, merged on demand."""

    def __init__(self):
        self._lock = threading.Lock()
        self._providers: Dict[str, Provider] = {}

    def register(self, name: str, provider: Provider) -> str:
        """Bind ``name`` to ``provider`` (last registration wins). Returns
        the name so callers can later :meth:`unregister` it."""
        if not callable(provider):
            raise TypeError(f"provider for {name!r} must be callable")
        with self._lock:
            self._providers[name] = provider
        return name

    def unregister(self, name: str) -> None:
        with self._lock:
            self._providers.pop(name, None)

    def sections(self) -> List[str]:
        with self._lock:
            return sorted(self._providers)

    def snapshot(self) -> dict:
        """One merged, JSON-ready dict: ``{"ts": ..., section: {...}}``.
        Provider failures are isolated into their own section — a broken
        subsystem must never take the whole surface down."""
        with self._lock:
            providers = list(self._providers.items())
        out: dict = {"ts": round(time.time(), 3)}
        for name, fn in providers:
            try:
                out[name] = fn()
            except Exception as e:          # noqa: BLE001 — isolation is the point
                out[name] = {"error": f"{type(e).__name__}: {e}"}
        return out


#: The process-wide registry. Subsystems register themselves on
#: construction (LearnerBase: pipeline/train/mix; CheckpointManager:
#: checkpoint; MixServer: mix_server; MetricsStream: metrics_stream;
#: Tracer: spans). The defaults below guarantee the acceptance sections
#: exist in every snapshot even before a subsystem comes up.
#:
#: Stub contract (hardened after the PR 7/8 key-drift recurrences, pinned
#: by tests/test_obs.py::test_stub_sections_match_live_providers): every
#: stub's KEY SET mirrors its live provider's snapshot exactly — gauges a
#: dashboard keys on never appear/vanish across subsystem lifecycle. The
#: inactive forms trainers/managers return when their subsystem is down
#: reuse these same dicts, so the two can never drift apart.

#: MixClient.counters() + the "active" discriminator
MIX_STUB = {"active": False, "exchanges": 0, "reconnects": 0,
            "dropped_exchanges": 0, "transport_errors": 0,
            "breaker_trips": 0, "breaker_state": "closed",
            "touched_overflow": 0, "alive": False}
#: CheckpointManager.obs_section()
CHECKPOINT_STUB = {"configured": False, "dir": None, "every": 0,
                   "keep": 0, "last_saved_step": None,
                   "age_seconds": None, "bundles": 0}
#: SloEngine.obs_section() in its fresh (no samples) state
SLO_STUB = {"configured": False, "samples": 0, "target_p99_ms": None,
            "target_availability": None, "drift_latency_events": 0,
            "drift_score_events": 0, "retrain_wanted": 0,
            "retrain_acked": 0}
#: serve.fleet.ReplicaManager.obs_section()
FLEET_STUB = {"replicas": 0, "ready": 0, "respawns": 0, "rolls": 0,
              "roll_failures": 0, "rejected_bundles": 0,
              "fleet_step": None, "model_steps": {},
              "replica_platforms": {},
              "replica_rss_bytes": {}, "arena_mapped_bytes": {}}
#: serve.promote.PromotionController.obs_section() /
#: serve.fleet.ReplicaManager.promotion_section() in their inactive form
#: (copy via serve.promote.promotion_stub — the nested canary dict must
#: not be shared mutable state)
PROMOTION_STUB = {"configured": False, "promoted_step": None,
                  "state": None, "candidates": 0, "gate_passes": 0,
                  "gate_failures": 0, "arena_published": 0,
                  "promotions": 0, "rollbacks": 0,
                  "quarantined": 0,
                  "canary": {"active": False, "step": None, "cohort": 0,
                             "age_seconds": None},
                  "shadow": {"mirrored": 0, "dropped": 0, "rows": 0},
                  "last_verdict": None, "retrain_wanted": 0,
                  "retrain_acked": 0}
#: serve.retrain.RetrainController.obs_section() in its inactive form
#: (copy via serve.retrain.retrain_stub — the nested replay dict must
#: not be shared mutable state)
RETRAIN_STUB = {"configured": False, "state": "idle", "attempts": 0,
                "successes": 0, "rejections": 0, "rollbacks": 0,
                "flaps": 0, "votes_seen": 0, "votes_acked": 0,
                "cooldown_remaining_s": 0.0, "child_alive": False,
                "candidate_step": None, "last_trigger_reason": None,
                "last_error": None,
                "replay": {"rows": 0, "rows_dropped": 0, "segments": 0,
                           "pending_rows": 0}}
#: serve.retrieve.RetrievalEngine.obs_section() in its inactive form
#: (copy via serve.retrieve.retrieval_stub — the nested index/arena
#: dicts must not be shared mutable state)
RETRIEVAL_STUB = {"configured": False, "algo": None, "follow": None,
                  "ready": False, "model_step": None,
                  "model_age_seconds": None, "bundle_age_seconds": None,
                  "model_path": None, "reloads": 0, "reload_failures": 0,
                  "watching": False, "precision": None, "tier": None,
                  "max_k": 0, "rescore_backend": None,
                  "queries_user": 0, "queries_item": 0,
                  "queries_lsh": 0, "queries_exact": 0,
                  "empty_candidates": 0, "last_reload_error": None,
                  "index": {"tables": 0, "bits": 0, "rows": 0,
                            "buckets": 0, "max_bucket": 0,
                            "mean_bucket": 0.0, "build_seconds": 0.0,
                            "recall_at_k": 0.0},
                  "arena": {"active": False, "mapped_bytes": 0,
                            "loads": 0, "publishes": 0},
                  "plane": None}
#: io.bulk.BulkProgress.obs_section() before any bulk job ran — the
#: offline scoring plane's section, key-for-key the live provider's shape
BULK_STUB = {"active": False, "input": None, "output": None,
             "backend": None, "precision": None, "workers": 0,
             "shards_total": 0, "shards_done": 0, "rows_scored": 0,
             "rows_per_sec": 0.0, "worker_utilization": 0.0,
             "elapsed_seconds": 0.0, "model_step": None, "bundle": None}

registry = Registry()
registry.register("mix", lambda: dict(MIX_STUB))
registry.register("checkpoint", lambda: dict(CHECKPOINT_STUB))
# io.shard_cache overrides this with its live counters on import (the
# first cache-aware fit); until then the section reports unconfigured
# zeros so the acceptance surface is shape-stable in every snapshot
registry.register("ingest_cache", lambda: {
    "configured": False, "hits": 0, "misses": 0, "invalid": 0,
    "rebuilds": 0, "build_failed": 0, "bytes_mmapped": 0,
    "bytes_written": 0, "canonicalizer": "unresolved"})
# serve.fleet.ReplicaManager overrides this with its live replica/roll
# counters when a fleet is running in this process
registry.register("fleet", lambda: dict(FLEET_STUB))
# obs.slo.SloEngine overrides this with live burn rates when a serve
# surface configures an SLO
registry.register("slo", lambda: dict(SLO_STUB))
# serve.promote.PromotionController / serve.fleet.ReplicaManager override
# this with live gate/canary/rollback state when promotion is gated
registry.register("promotion", lambda: {**PROMOTION_STUB,
                                        "canary":
                                        dict(PROMOTION_STUB["canary"]),
                                        "shadow":
                                        dict(PROMOTION_STUB["shadow"])})
# serve.retrain.RetrainController overrides this with the live retrain
# state machine when the autopilot is running
registry.register("retrain", lambda: {**RETRAIN_STUB,
                                      "replay":
                                      dict(RETRAIN_STUB["replay"])})
# serve.retrieve.RetrievalEngine overrides this with the live factor
# index/query counters when a retrieval plane is serving in this process
registry.register("retrieval", lambda: {
    **RETRIEVAL_STUB, "index": dict(RETRIEVAL_STUB["index"]),
    "arena": dict(RETRIEVAL_STUB["arena"])})
# io.bulk.bulk_predict overrides this with live shard/rows-per-sec
# progress while a bulk scoring job runs in this process
registry.register("bulk", lambda: dict(BULK_STUB))
# obs.devprof.DevProf overrides this with live compile/retrace/memory
# telemetry on first use (any trainer construction)
from .devprof import devprof_stub  # noqa: E402 — stub needs the dict shape
registry.register("devprof", devprof_stub)
# obs.flight.get_flight overrides this with the live ring's self-census
# (events written, overwrites, utilization) on first use
from .flight import flight_stub  # noqa: E402 — stub needs the dict shape
registry.register("flight", flight_stub)
