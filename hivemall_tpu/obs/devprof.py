"""Training-side deep profiling — compile/retrace telemetry, device-memory
accounting, and step-time drift (docs/OBSERVABILITY.md "Training
profiling").

The serving tier got request tracing, latency histograms and an SLO
engine (PR 8); this module is the TRAINING half's equivalent depth. The
repo's single biggest self-inflicted perf hazard is silent recompilation:
every trainer family enforces one-compile-per-config by hand (the
module-level ``lru_cache`` factories in models/linear|fm|word2vec|
topicmodel|anomaly, ``models.base.shared_step``, the megastep cache in
ops/scan, the bucketed scorers in io.sparse) — word2vec measured ~5 s of
wasted XLA compile per duplicate instance, LDA 1.5 s of a 2.3 s bench —
and until now NOTHING watched that discipline at runtime. Three watches
live here:

**Compile telemetry.** Two attribution layers feed one ledger:

- the factory layer: every compile factory is wrapped by
  :func:`instrument_factory`, so a cache MISS (a fresh closure actually
  built) records a per-``(model, fn)`` build count + wall time, a
  ``compile.<model>.<fn>`` span, and — for shape-driven factories — the
  shape bucket. Shape-bucketed scoring (io.sparse.score_batches, the
  serve engine's warmup peer) reports first-use of each (B, L) bucket
  through :meth:`DevProf.note_bucket`.
- the XLA layer: a ``jax.monitoring`` listener counts every backend
  compile (``/jax/core/compile/backend_compile_duration``) and trace
  (``jaxpr_trace_duration``) with wall time — the ground truth the
  factory layer attributes. A fresh closure that BYPASSES the factories
  (the exact disease) still lands here.

**No-retrace sentinel.** ``arm()`` marks warmup complete; any XLA
backend compile observed while armed is a RETRACE: counted, timed,
recorded as a ``compile.retrace`` span, and emitted as a ``retrace``
event into the metrics jsonl. The sentinel auto-arms at the first
``train_done`` (one completed fit = the process's compile warmup), so a
second same-config trainer that re-compiles — the word2vec disease —
flags itself in telemetry with no harness involved. tests/test_devprof.py
holds the sentinel as an invariant: warm epoch, ``arm()``, second epoch
must add ZERO compiles, and a deliberately-injected fresh-closure
duplicate trainer must be caught.

**Device-memory accounting + drift.** :meth:`sample_memory` reads
``device.memory_stats()`` (None on the CPU backend — degrades to zeros)
and ``jax.live_arrays()`` into live HBM/host gauges, sampled at the
trainer's ``-telemetry_every`` cadence and kept fresh for ``/snapshot``/
``/metrics`` scrapes; the megastep dispatch boundary (ops.scan) tracks
peak-bytes-in-use per fused dispatch. The live-bytes stream feeds the
in-tree dual-stage :class:`~hivemall_tpu.models.anomaly.ChangeFinder`
(the same detector PR 8 pointed at serving latency) → ``mem_drift``
events; per-dispatch wall time feeds a second detector →
``train_drift`` events. Both detectors self-calibrate their thresholds
(Welford mean + ``sigma`` stds of their own score streams, the obs.slo
recipe) so no absolute threshold needs tuning per model.

**Profiler capture.** ``HIVEMALL_TPU_PROF=<dir>`` (legacy spelling
``HIVEMALL_TPU_PROFILE`` still honored) captures a ``jax.profiler``
trace of the first ``fit()`` in the process — routed through here so the
capture records a ``profile.capture`` span and a ``profile`` jsonl
event instead of being an invisible side effect.

Cost contract (the obs module's standing rule): everything is ~free when
idle. The monitoring listener only runs when XLA compiles (never on the
steady-state hot path); ``note_dispatch`` is one attribute check until
:meth:`activate` (``-telemetry_every``/``-obs_port``/
``HIVEMALL_TPU_DEVPROF=1``) turns the drift watches on; memory sampling
happens at telemetry cadence, never per step.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional, Tuple

__all__ = ["DevProf", "DriftWatch", "get_devprof", "instrument_factory",
           "devprof_stub"]

#: keys of the ``memory`` sub-dict — zeros until the first sample so the
#: section is shape-stable (obs.registry stub contract)
_MEM_KEYS = ("live_arrays", "live_bytes", "bytes_in_use",
             "peak_bytes_in_use", "bytes_limit")


def devprof_stub() -> dict:
    """The shape of the ``devprof`` registry section before (or without)
    a live :class:`DevProf` — mirrors :meth:`DevProf.obs_section` key for
    key (pinned by tests/test_obs.py's stub-vs-live check)."""
    return {
        "active": False, "armed": False,
        "compiles": 0, "compile_seconds": 0.0, "traces": 0,
        "retraces": 0, "retrace_seconds": 0.0,
        "builds": {}, "build_seconds": 0.0, "shape_buckets": 0,
        "dispatches": 0, "dispatch_seconds": 0.0,
        "memory": {k: 0 for k in _MEM_KEYS},
        "peak_dispatch_bytes": 0,
        "drift": {"train_events": 0, "mem_events": 0},
        "profile_captures": 0,
    }


class DriftWatch:
    """One scalar stream -> drift events, the obs.slo recipe factored out:
    a dual-stage :class:`~hivemall_tpu.models.anomaly.ChangeFinder`
    (stage-1 outlier catches step regressions, stage-2 change catches
    gradual drifts; PAPER.md [B]) with Welford-self-calibrated
    ``mu + sigma*std`` thresholds per score stream. A flagged update is
    counted and emitted as an ``<event>`` record into the metrics jsonl
    — next to train/serve telemetry, no external alerting stack."""

    def __init__(self, series: str, event: str, *, sigma: float = 6.0,
                 warmup: int = 32):
        # lazy import: watching is opt-in, importing obs.devprof must not
        # pull the anomaly module (and numpy SDAR state) everywhere
        from ..models.anomaly import ChangeFinder
        self.series = series
        self.event = event
        self.sigma = float(sigma)
        self.warmup = int(warmup)
        self._cf = ChangeFinder()
        self._stats = {s: [0, 0.0, 0.0]        # n, mean, M2 per stage
                       for s in ("outlier", "change")}
        self._lock = threading.Lock()
        self.n = 0
        self.events = 0

    def update(self, x: float, **extra) -> Optional[dict]:
        """Feed one value; returns the emitted event dict when the update
        crossed a self-calibrated threshold, else None. Serialized: the
        memory watch can be fed from both the telemetry cadence and a
        scrape-freshness resample, and SDAR state must not interleave."""
        with self._lock:
            outlier, change = self._cf.update(float(x))
            self.n += 1
            flagged = None
            for stage, score in (("outlier", outlier), ("change", change)):
                st = self._stats[stage]
                st[0] += 1
                n = st[0]
                delta = score - st[1]
                st[1] += delta / n
                st[2] += delta * (score - st[1])
                if n <= self.warmup:
                    continue
                std = (st[2] / max(1, n - 1)) ** 0.5
                if std > 0 and score > st[1] + self.sigma * std:
                    flagged = flagged or stage
            if not flagged:
                return None
            self.events += 1
        ev = {"series": self.series, "stage": flagged,
              "value": round(float(x), 6),
              "outlier_score": round(float(outlier), 4),
              "change_score": round(float(change), 4), **extra}
        from ..utils.metrics import get_stream
        get_stream().emit(self.event, **ev)
        return ev


class DevProf:
    """The process-wide training profiler (:func:`get_devprof`).

    Thread-safe: the monitoring listener fires from whichever thread
    compiles (serve warmup threads, the fit loop), scrape threads read
    :meth:`obs_section` concurrently, and one lock guards the (scalar)
    counter updates."""

    def __init__(self):
        self._lock = threading.Lock()
        self.active = False                 # drift watches + mem cadence
        self.armed = False                  # no-retrace sentinel
        # XLA layer (jax.monitoring ground truth)
        self.compiles = 0
        self.compile_s = 0.0
        self.traces = 0
        self.retraces = 0
        self.retrace_s = 0.0
        # factory layer (attribution)
        self.builds: Dict[str, dict] = {}   # "model.fn" -> {count, seconds}
        self.build_s = 0.0
        self._buckets: set = set()          # (site, B, L) first-use
        # dispatch / memory
        self.dispatches = 0
        self.dispatch_s = 0.0
        self._mem: Dict[str, int] = {k: 0 for k in _MEM_KEYS}
        self._mem_ts = 0.0
        self.peak_dispatch_bytes = 0
        self.profile_captures = 0
        self._profiled = False              # first-fit-only capture latch
        self._train_watch: Optional[DriftWatch] = None
        self._mem_watch: Optional[DriftWatch] = None
        self._register_monitoring()

    # -- XLA compile layer ---------------------------------------------------
    def _register_monitoring(self) -> None:
        """Hook ``jax.monitoring`` duration events. Listener registration
        is global and append-only in jax, so this runs once per DevProf
        (one DevProf per process via get_devprof); failure degrades to
        factory-layer-only telemetry — profiling never takes training
        down."""
        try:
            import jax.monitoring as monitoring

            def on_duration(event: str, duration: float, **kw) -> None:
                if event.endswith("/backend_compile_duration"):
                    self._record_compile(float(duration))
                elif event.endswith("/jaxpr_trace_duration"):
                    with self._lock:
                        self.traces += 1

            monitoring.register_event_duration_secs_listener(on_duration)
        except Exception:                   # noqa: BLE001 — fail soft
            pass

    def _record_compile(self, dur: float) -> None:
        with self._lock:
            self.compiles += 1
            self.compile_s += dur
            retrace = self.armed
            if retrace:
                self.retraces += 1
                self.retrace_s += dur
        from .trace import get_tracer
        tracer = get_tracer()
        if tracer.enabled:
            tracer.add_span("compile.retrace" if retrace else "compile.xla",
                            dur)
        if retrace:
            # the sentinel's whole point: a post-warmup compile must land
            # in the stream where `hivemall_tpu obs` and the CI guard see
            # it, not only in a counter
            from ..utils.metrics import get_stream
            get_stream().emit("retrace", seconds=round(dur, 6),
                              compiles=self.compiles,
                              retraces=self.retraces)

    # -- sentinel ------------------------------------------------------------
    def arm(self) -> "DevProf":
        """Warmup is over: from here every XLA compile is a retrace."""
        self.armed = True
        return self

    def disarm(self) -> "DevProf":
        self.armed = False
        return self

    def note_train_done(self) -> None:
        """Auto-arm at the first completed fit: one full run compiles
        every shape a config needs, so later compiles in the same process
        are exactly the duplicate-instance disease the factories exist to
        prevent. Harness code that intentionally compiles new configs
        (test suites) sees retrace COUNTERS grow, never a
        failure — the CI guard reads a delta over an explicitly armed
        window instead."""
        self.armed = True

    # -- factory layer -------------------------------------------------------
    def record_build(self, model: str, fn: str, seconds: float,
                     shape: Optional[Tuple[int, ...]] = None) -> None:
        key = f"{model}.{fn}"
        with self._lock:
            b = self.builds.get(key)
            if b is None:
                b = self.builds[key] = {"count": 0, "seconds": 0.0}
            b["count"] += 1
            b["seconds"] = round(b["seconds"] + seconds, 6)
            self.build_s += seconds
            if shape is not None:
                self._buckets.add((key,) + tuple(shape))
        from .trace import get_tracer
        tracer = get_tracer()
        if tracer.enabled:
            tracer.add_span(f"compile.{key}", seconds)

    def note_bucket(self, site: str, *shape: int) -> None:
        """First use of a (site, shape-bucket) — the moment a bucketed
        scorer's next call will compile. Dedup'd, so steady-state scoring
        costs one set-lookup."""
        key = (site,) + tuple(int(s) for s in shape)
        if key in self._buckets:
            return
        with self._lock:
            self._buckets.add(key)

    # -- dispatch / drift ----------------------------------------------------
    def activate(self) -> "DevProf":
        """Turn on the drift watches + scrape-time memory freshness
        (``-telemetry_every`` / ``-obs_port`` / HIVEMALL_TPU_DEVPROF=1
        route here). Idempotent."""
        if not self.active:
            self._train_watch = DriftWatch("step_ms", "train_drift")
            self._mem_watch = DriftWatch("live_bytes", "mem_drift")
            self.active = True
        return self

    def note_dispatch(self, dur_s: float, steps: int = 1) -> None:
        """Per-dispatch wall time from the trainer's host boundary. One
        attribute check when inactive; when active, feeds the per-STEP
        wall (ms) into the train drift detector."""
        if not self.active:
            return
        with self._lock:
            self.dispatches += 1
            self.dispatch_s += dur_s
        w = self._train_watch
        if w is not None:
            w.update(dur_s / max(1, steps) * 1000.0)

    def note_megastep(self) -> None:
        """Called by ops.scan's megastep wrapper after each fused
        dispatch: track the device allocator's peak-bytes high-water mark
        across dispatches (None on backends without memory_stats)."""
        if not self.active:
            return
        try:
            import jax
            peak = sum(int((d.memory_stats() or {})
                           .get("peak_bytes_in_use") or 0)
                       for d in jax.local_devices())
        except Exception:                   # noqa: BLE001 — obs only
            return
        if peak > self.peak_dispatch_bytes:
            self.peak_dispatch_bytes = peak

    # -- memory --------------------------------------------------------------
    def sample_memory(self) -> dict:
        """One gauge sample: allocator stats summed over every local
        device (a GSPMD process drives several — a leak on device 3 must
        not hide behind device 0) + live jax.Array census. Feeds the
        mem-drift detector when active. Cheap enough for the telemetry
        cadence, NOT for the per-step path."""
        rec = {k: 0 for k in _MEM_KEYS}
        try:
            import jax
            for dev in jax.local_devices():
                stats = dev.memory_stats() or {}
                rec["bytes_in_use"] += int(stats.get("bytes_in_use") or 0)
                rec["peak_bytes_in_use"] += int(
                    stats.get("peak_bytes_in_use") or 0)
                rec["bytes_limit"] += int(stats.get("bytes_limit") or 0)
            arrs = jax.live_arrays()
            rec["live_arrays"] = len(arrs)
            rec["live_bytes"] = int(sum(getattr(a, "nbytes", 0)
                                        for a in arrs))
        except Exception:                   # noqa: BLE001 — a failed
            return dict(self._mem)          # sample keeps the last gauge
        with self._lock:
            self._mem = rec
            self._mem_ts = time.monotonic()
        if self.active and self._mem_watch is not None:
            # live-bytes in MB: keeps the SDAR state in a well-scaled
            # range (raw byte counts in the 1e9s degrade its f64 moments
            # no differently, but MB reads better in the event records)
            self._mem_watch.update(rec["live_bytes"] / 1e6)
        return rec

    def _fresh_memory(self, max_age: float = 2.0) -> dict:
        """The last sample, refreshed inline when a scrape finds it stale
        and the watch is active (a live fit with -obs_port but without
        -telemetry_every would otherwise serve startup zeros forever)."""
        if self.active and time.monotonic() - self._mem_ts > max_age:
            return self.sample_memory()
        return dict(self._mem)

    # -- profiler capture (HIVEMALL_TPU_PROF) --------------------------------
    @staticmethod
    def profile_dir() -> Optional[str]:
        """The documented env var, with the pre-unification spelling kept
        as an alias so existing launch scripts don't silently lose their
        profiles."""
        return (os.environ.get("HIVEMALL_TPU_PROF")
                or os.environ.get("HIVEMALL_TPU_PROFILE"))

    def start_profile_once(self) -> Optional[str]:
        """Start a jax.profiler trace for the FIRST fit in the process
        when ``HIVEMALL_TPU_PROF=<dir>`` is set; returns the capture dir
        (pass it to :meth:`stop_profile`) or None."""
        prof_dir = self.profile_dir()
        if not prof_dir or self._profiled:
            return None
        self._profiled = True
        try:
            import jax
            jax.profiler.start_trace(prof_dir)
        except Exception as e:              # noqa: BLE001 — fail soft,
            import warnings                 # but LOUDLY: the latch is set,
            warnings.warn(                  # no later fit will retry
                f"HIVEMALL_TPU_PROF capture into {prof_dir!r} failed "
                f"({type(e).__name__}: {e}); no profile will be written "
                f"this process", RuntimeWarning, stacklevel=2)
            return None
        self._prof_t0 = time.perf_counter()
        return prof_dir

    def stop_profile(self, prof_dir: Optional[str]) -> None:
        """Stop a capture started by :meth:`start_profile_once`: emits a
        ``profile.capture`` span and a ``profile`` jsonl event carrying
        the dir, so the capture is discoverable from the stream."""
        if not prof_dir:
            return
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:              # noqa: BLE001 — fail soft but
            import warnings                 # loudly (an unwritable dir
            warnings.warn(                  # often only fails at stop)
                f"HIVEMALL_TPU_PROF capture into {prof_dir!r} failed at "
                f"stop ({type(e).__name__}: {e}); the profile was lost",
                RuntimeWarning, stacklevel=2)
            return
        dur = time.perf_counter() - getattr(self, "_prof_t0",
                                            time.perf_counter())
        self.profile_captures += 1
        from .trace import get_tracer
        tracer = get_tracer()
        if tracer.enabled:
            tracer.add_span("profile.capture", dur)
        from ..utils.metrics import get_stream
        get_stream().emit("profile", dir=prof_dir,
                          seconds=round(dur, 3))

    # -- obs -----------------------------------------------------------------
    def obs_section(self) -> dict:
        """The ``devprof`` registry section (key set mirrored by
        :func:`devprof_stub`): flattens to ``/metrics`` gauges, rides
        ``/snapshot`` and the ``telemetry``/``train_done`` events."""
        with self._lock:
            builds = {k: dict(v) for k, v in self.builds.items()}
            d = {
                "active": self.active, "armed": self.armed,
                "compiles": self.compiles,
                "compile_seconds": round(self.compile_s, 6),
                "traces": self.traces,
                "retraces": self.retraces,
                "retrace_seconds": round(self.retrace_s, 6),
                "builds": builds,
                "build_seconds": round(self.build_s, 6),
                "shape_buckets": len(self._buckets),
                "dispatches": self.dispatches,
                "dispatch_seconds": round(self.dispatch_s, 6),
                "peak_dispatch_bytes": self.peak_dispatch_bytes,
                "drift": {
                    "train_events": (self._train_watch.events
                                     if self._train_watch else 0),
                    "mem_events": (self._mem_watch.events
                                   if self._mem_watch else 0)},
                "profile_captures": self.profile_captures,
            }
        d["memory"] = self._fresh_memory()
        return d

    def _register_obs(self) -> None:
        from .registry import registry
        registry.register("devprof", self.obs_section)


_devprof: Optional[DevProf] = None
_devprof_lock = threading.Lock()


def get_devprof() -> DevProf:
    """The process-wide profiler, constructed (and registered as the obs
    registry's ``devprof`` section) on first use. HIVEMALL_TPU_DEVPROF=1
    activates the drift watches immediately."""
    global _devprof
    if _devprof is None:
        with _devprof_lock:
            if _devprof is None:
                dp = DevProf()
                if os.environ.get("HIVEMALL_TPU_DEVPROF", "") not in ("", "0"):
                    dp.activate()
                dp._register_obs()
                _devprof = dp
    return _devprof


def instrument_factory(model: str, fn_name: str, *,
                       shape_args: Tuple[int, ...] = ()):
    """Wrap a module-level ``lru_cache`` compile factory so cache MISSES
    (fresh closures actually built) record into the devprof ledger:

        @instrument_factory("linear", "step")
        @lru_cache(maxsize=128)
        def _linear_step_cached(...): ...

    ``shape_args`` names positional-arg indexes carrying shape-bucket
    dimensions (e.g. the packed-wrapper's (B, L)), recorded per bucket.
    The wrapped factory keeps ``cache_info``/``cache_clear`` and exposes
    the underlying cache as ``__wrapped__`` (the fresh-closure injection
    path of the CI guard digs through it on purpose)."""
    import functools

    def deco(cached):
        # serialize calls through THIS factory: miss detection diffs the
        # shared lru miss counter, and a concurrent miss on another key
        # would otherwise attribute a bogus near-zero build to a hit.
        # Builds are closure construction (microseconds — the XLA compile
        # happens at first call), so the lock costs nothing measurable;
        # no instrumented factory calls another, so no nesting deadlock.
        lock = threading.Lock()

        @functools.wraps(cached)
        def wrapper(*args, **kwargs):
            with lock:
                before = cached.cache_info().misses
                t0 = time.perf_counter()
                out = cached(*args, **kwargs)
                missed = cached.cache_info().misses != before
                dur = time.perf_counter() - t0
            if missed:
                shape = tuple(args[i] for i in shape_args) or None
                get_devprof().record_build(model, fn_name, dur, shape=shape)
            return out

        wrapper.cache_info = cached.cache_info
        wrapper.cache_clear = cached.cache_clear
        wrapper.__wrapped__ = cached
        return wrapper

    return deco
