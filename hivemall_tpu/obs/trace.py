"""Span tracing — where did this step's wall time go.

A :class:`Tracer` records named monotonic-clock spans into a thread-safe
ring buffer plus per-stage aggregates, mirroring ``MetricsStream``'s
contract: when disabled (the default) a span costs ONE attribute check and
returns a shared no-op context manager — the hot path pays nothing.

Span sites (the training pipeline's real seams — docs/OBSERVABILITY.md):

========================  ===================================================
``source.wait_shard``     ParquetStream blocked on the next decoded shard
``source.decode``         one shard read and decoded on the ``pq-decode``
                          thread, ``args.rows`` its rows (work, not a wait)
``source.assemble``       one source batch: row gather + padding (the
                          shard's concat/shuffle/take rides on its first
                          batch); never the time suspended at ``yield``
``source.note_batch``     the trainer's per-batch stream-order hook
``pairs.track``           FFM: one batch's observed pairs merged, on the
                          ``pairs-track`` thread
``ingest.prep``           host batch prep (IngestPipeline worker fn, both
                          the pool workers and the sequential fallback)
``ingest.cache``          one batch assembled from the packed shard cache
``ingest.wait_prep``      the pipeline's consumer blocked on the pool's next
                          prepared batch (none in the sequential fallback)
``ingest.wait_slot``      ``ingest-source`` blocked on the full queue of
                          prepared batches
``stager.stack``          K-step megabatch stacking (MegabatchStager)
``init.state``            the trainer's state out of its one jitted
                          initialiser, ``args.bytes`` what it made
``h2d.stage``             host->device transfer (prefetch.stage_batch)
``h2d.shard``             the same under ``-mesh`` from the thread that
                          dispatches (no prefetcher staged the input)
``feed.wait_slot``        ``h2d-prefetch`` blocked handing a staged input
                          over: the consumer has not taken the previous one
``loop.wait_input``       the train loop blocked on its next staged input
``dispatch.step``         one jitted step dispatch (host-side boundary)
``dispatch.megastep``     one fused K-step lax.scan dispatch
``loop.fold_loss``        the loss fold's ``float()``: the one place the
                          train loop blocks on the device
``loop.cadence``          what a cadence boundary emits after the fold
``mix.exchange``          one MIX exchange incl. retries + fold-back
``checkpoint.save``       one atomic bundle save
========================  ===================================================

Every span records its thread (id and name), a process-unique ``id`` and
the ``parent`` id of the innermost span open on the same thread when it
began. It has two clocks: ``dur`` is wall time, ``cpu`` the seconds its
thread was on a CPU meanwhile (``time.thread_time()`` at both ends), so
``dur - cpu`` is what the thread spent waiting inside the span: for the
GIL, for a page fault, on a blocking call. Two ordinals tie the spans of
one unit of work together across threads: ``batch``, a source batch's
position in its stream (each stage counts what passes it, in order, from
0), and ``seq``, a dispatch's position, given where the stager emits it
and carried on the megabatch object through the prefetcher to the
dispatch. Both ride positionally — ``span(name, seq, batch)`` — so a
disabled tracer builds nothing at the call site.

One clock: while a ``jax.profiler`` session is live (the harness's, or
``HIVEMALL_TPU_PROF``), every ``span()`` of an enabled tracer is also
a ``jax.profiler.TraceAnnotation`` of the same name with ``seq`` /
``batch`` / ``args`` as its stats, so the program's spans sit in the
xplane's host plane on the profiler's clock beside the device ops. The
tracer does not ask whether a session is live: an annotation outside one
costs under a microsecond. (``add_span`` records an interval that is
already over, which an annotation cannot; those stay Chrome-only.)

Host-side semantics: a dispatch span measures the host's time in the
dispatch call (on CPU that is the synchronous step; on accelerators it is
dispatch latency — the async compute tail lands in the NEXT blocking
boundary). Rollups emit as ``span_rollup`` jsonl events at the trainer's
loss-fold cadence; the raw ring exports as Chrome-trace JSON
(``chrome://tracing`` / Perfetto) for deep dives alongside
``jax.profiler``.

Activation: ``HIVEMALL_TPU_TRACE=1`` enables the process tracer;
``HIVEMALL_TPU_TRACE=/path/trace.json`` additionally writes the Chrome
export there at ``train_done``. Or drive it explicitly via
``get_tracer().enable()``.

Request-scoped tracing (docs/OBSERVABILITY.md "Serving traces and
SLOs"): a serving request sampled by the fleet router (or carrying an
explicit ``x-hivemall-trace`` header) flows its trace id through
:meth:`Tracer.context` — a thread-local tag that every span completed
inside the ``with`` block records into its Chrome-export ``args``. The
export timestamps are WALL-CLOCK anchored (epoch microseconds), so the
router's and each replica's independently-recorded spans line up on one
Perfetto timeline when merged (each process keeps its own ``pid``); the
router's ``/trace`` endpoint does exactly that merge. Disabled-tracer
cost is unchanged: ``span()``/``context()`` stay one attribute check
returning a shared no-op.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, Optional

__all__ = ["Tracer", "get_tracer", "mint_trace_id"]

_RING = 8192          # completed spans kept for the Chrome export
_RESERVOIR = 512      # per-stage duration reservoir for p50/p99


class _NullSpan:
    """Shared no-op context manager — the disabled-tracer fast path.
    ``with tracer.span(...) as sp`` binds None here and the live span
    when enabled: a site that learns its ``args`` only inside the block
    sets ``sp.args`` under ``if sp is not None``."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()

# per-process salt keeps minted ids unique across replica restarts on one
# host (pid alone recycles); 2 bytes is plenty for a serving fleet
_TRACE_SALT = int.from_bytes(os.urandom(2), "big")
_trace_seq = itertools.count(1)
_span_ids = itertools.count(1)


def mint_trace_id() -> str:
    """A new request trace id: ``<pid>-<salt>-<seq>`` hex — unique across
    the processes of one fleet without any coordination."""
    return f"{os.getpid():x}-{_TRACE_SALT:04x}-{next(_trace_seq):x}"


class _TraceCtx:
    """Thread-local trace tag: spans completed inside the block record
    ``tag`` into their Chrome-export args. Nestable (restores the outer
    tag on exit); created only when the tracer is enabled AND a request
    is actually traced, so the untraced hot path never sees it."""

    __slots__ = ("_tls", "tag", "_prev")

    def __init__(self, tls, tag: str):
        self._tls = tls
        self.tag = tag

    def __enter__(self):
        self._prev = getattr(self._tls, "trace", None)
        self._tls.trace = self.tag
        return self

    def __exit__(self, *exc):
        self._tls.trace = self._prev
        return False


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` where this process has imported
    jax, else None: without jax no profiler session can be live, and a
    process that never needed jax (the fleet router) is not made to
    import it for its spans."""
    jax = sys.modules.get("jax")
    return None if jax is None else jax.profiler.TraceAnnotation


class _Span:
    __slots__ = ("_tracer", "name", "seq", "batch", "args", "id", "parent",
                 "t0", "cpu", "_ann")

    def __init__(self, tracer: "Tracer", name: str, seq, batch, args):
        self._tracer = tracer
        self.name = name
        self.seq = seq
        self.batch = batch
        self.args = args

    def __enter__(self):
        tls = self._tracer._tls
        stack = getattr(tls, "stack", None)
        if stack is None:
            stack = tls.stack = []
        self.parent = stack[-1].id if stack else None
        self.id = next(_span_ids)
        stack.append(self)
        cls = _annotation_cls()
        if cls is None:
            self._ann = None
        else:
            kw = dict(self.args) if self.args else {}
            if self.seq is not None:
                kw["seq"] = self.seq
            if self.batch is not None:
                kw["batch"] = self.batch
            self._ann = cls(self.name, **kw)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        self.cpu = time.thread_time()   # read last, and first at the end:
        return self                     # the CPU clock lies inside `dur`

    def __exit__(self, *exc):
        self.cpu = time.thread_time() - self.cpu
        dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._tls.stack.pop()
        self._tracer._record(self.name, self.t0, dur, span=self)
        return False


class _Stage:
    __slots__ = ("count", "total_s", "cpu_s", "durs")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.cpu_s = 0.0
        self.durs: deque = deque(maxlen=_RESERVOIR)


def _pctl(sorted_durs, q: float) -> float:
    return sorted_durs[min(len(sorted_durs) - 1,
                           int(q * (len(sorted_durs) - 1) + 0.5))]


class Tracer:
    """Thread-safe span recorder with per-stage rollups.

    Spans may complete concurrently on ingest workers, the prefetcher
    thread, and the train loop; one lock guards the (cheap) aggregate
    update. ``span()`` when disabled allocates nothing and takes no lock.
    """

    def __init__(self, enabled: bool = False, ring: int = _RING):
        self.enabled = bool(enabled)
        self.export_path: Optional[str] = None
        # shows as the Chrome-export process name next to the pid, so a
        # merged fleet trace reads router/replica instead of bare pids
        self.process_label = f"pid{os.getpid()}"
        self._lock = threading.Lock()
        self._stages: Dict[str, _Stage] = {}
        self._events: deque = deque(maxlen=max(1, ring))
        #: spans evicted from the full ring before export — a silent wrap
        #: used to make a Chrome export look complete when it wasn't;
        #: surfaced as ``spans.dropped`` in the registry and /metrics
        self.dropped = 0
        self._tls = threading.local()
        # paired clocks: spans time with the monotonic perf counter, the
        # export anchors them to the wall clock so independently-recorded
        # processes share one timeline when their exports merge
        self._origin = time.perf_counter()
        self._origin_wall = time.time()

    # -- control -------------------------------------------------------------
    def enable(self) -> "Tracer":
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    def reset(self) -> None:
        """Drop all recorded spans and aggregates (tests, run boundaries)."""
        with self._lock:
            self._stages.clear()
            self._events.clear()
            self.dropped = 0

    # -- recording -----------------------------------------------------------
    def span(self, name: str, seq: Optional[int] = None,
             batch: Optional[int] = None, args: Optional[dict] = None):
        """Context manager timing one span. ~Free when disabled: one
        attribute check, shared no-op object, no allocation — which is
        why ``seq`` and ``batch`` are plain parameters (pass them
        positionally on per-batch paths) and there is no ``**kwargs``:
        that would build a dict per call, tracer on or off. ``args``
        (small scalars) is for the rare site with more to say; build it
        under an ``enabled`` check."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, seq, batch, args)

    def context(self, trace_id: Optional[str]):
        """Tag every span completed in this ``with`` block (on THIS
        thread) with ``trace_id`` — the request-scoped tracing seam.
        One attribute check + shared no-op when disabled or untagged."""
        if not self.enabled or not trace_id:
            return _NULL_SPAN
        return _TraceCtx(self._tls, trace_id)

    def add_span(self, name: str, dur_s: float,
                 trace: Optional[str] = None) -> None:
        """Record an already-measured span ending ~now (the router's
        forward loop measures across retries and can't wrap a single
        ``with``). Its parent is the innermost span open on this thread
        now; it has no CPU clock (the interval is over). No-op when
        disabled."""
        if not self.enabled:
            return
        self._record(name, time.perf_counter() - dur_s, dur_s, trace=trace)

    def _record(self, name: str, t0: float, dur: float,
                trace: Optional[str] = "\0tls",
                span: Optional[_Span] = None) -> None:
        thread = threading.current_thread()
        tid = thread.ident
        if trace == "\0tls":             # default: the thread's context tag
            trace = getattr(self._tls, "trace", None)
        if span is not None:
            cpu = span.cpu
            more = (thread.name, span.id, span.parent, span.seq, span.batch,
                    span.args, cpu)
        else:
            cpu = None
            stack = getattr(self._tls, "stack", None)
            more = (thread.name, next(_span_ids),
                    stack[-1].id if stack else None, None, None, None, cpu)
        with self._lock:
            st = self._stages.get(name)
            if st is None:
                st = self._stages[name] = _Stage()
            st.count += 1
            st.total_s += dur
            if cpu is not None:
                st.cpu_s += cpu
            st.durs.append(dur)
            if len(self._events) == self._events.maxlen:
                self.dropped += 1        # ring full: the append below
            self._events.append((name, t0, dur, tid, trace) + more)

    # -- reading -------------------------------------------------------------
    def rollup(self) -> Dict[str, dict]:
        """Per-stage ``{count, total_s, cpu_s, p50, p99}``: ``cpu_s`` is
        the part of ``total_s`` the spans' threads were on a CPU
        (``add_span`` intervals add none); percentiles over the last
        ``_RESERVOIR`` spans of each stage. JSON-ready; safe to call
        from any thread while spans are being recorded."""
        with self._lock:
            items = [(name, st.count, st.total_s, st.cpu_s, list(st.durs))
                     for name, st in self._stages.items()]
        out: Dict[str, dict] = {}
        for name, count, total, cpu, durs in sorted(items):
            durs.sort()
            out[name] = {
                "count": count,
                "total_s": round(total, 6),
                "cpu_s": round(cpu, 6),
                "p50": round(_pctl(durs, 0.50), 6) if durs else 0.0,
                "p99": round(_pctl(durs, 0.99), 6) if durs else 0.0,
            }
        return out

    def chrome_dict(self) -> dict:
        """The span ring as a Chrome-trace dict (``ph: "X"`` complete
        events). Timestamps are wall-clock epoch MICROSECONDS (the
        monotonic span clock re-anchored through the paired origins), so
        exports from different processes merge onto one timeline — the
        fleet router concatenates replicas' ``traceEvents`` under their
        own pids to render one request as one cross-process flame.
        ``args`` carries ``id``, ``thread`` and, where set, ``parent``,
        ``seq``, ``batch``, ``trace``, ``cpu`` (thread-CPU microseconds,
        like ``dur``) and the span's own ``args``."""
        with self._lock:
            events = list(self._events)
        pid = os.getpid()
        wall0 = self._origin_wall - self._origin
        out = []
        for (name, t0, dur, tid, trace, tname, sid, parent, seq, batch,
             extra, cpu) in events:
            args = dict(extra) if extra else {}
            args.update(id=sid, thread=tname)
            for key, v in (("parent", parent), ("seq", seq),
                           ("batch", batch), ("trace", trace)):
                if v is not None:
                    args[key] = v
            if cpu is not None:
                args["cpu"] = round(cpu * 1e6, 3)
            out.append({"name": name, "ph": "X", "cat": "hivemall_tpu",
                        "ts": round((wall0 + t0) * 1e6, 3),
                        "dur": round(dur * 1e6, 3), "pid": pid, "tid": tid,
                        "args": args})
        # metadata last: consumers indexing traceEvents[0] still see the
        # first real span; viewers read ph:"M" anywhere in the list
        out.append({"name": "process_name", "ph": "M", "pid": pid,
                    "tid": 0, "args": {"name": self.process_label}})
        return {"displayTimeUnit": "ms", "traceEvents": out}

    def export_chrome(self, path: str) -> str:
        """Write :meth:`chrome_dict` as JSON — open in chrome://tracing
        or Perfetto. Returns ``path``."""
        with open(path, "w") as f:
            json.dump(self.chrome_dict(), f)
        return path

    def maybe_export(self) -> Optional[str]:
        """Chrome export to ``export_path`` when configured (the
        ``HIVEMALL_TPU_TRACE=<path>.json`` contract); never raises —
        export is observability, not training."""
        if not (self.enabled and self.export_path):
            return None
        try:
            return self.export_chrome(self.export_path)
        except OSError:
            return None


_tracer: Optional[Tracer] = None


def get_tracer() -> Tracer:
    """The process-wide tracer, bound to ``$HIVEMALL_TPU_TRACE`` on first
    use (unset/"0" = disabled; a ``*.json`` value doubles as the Chrome
    export path) and registered as the obs registry's ``spans`` section."""
    global _tracer
    if _tracer is None:
        env = os.environ.get("HIVEMALL_TPU_TRACE", "")
        t = Tracer(enabled=bool(env) and env != "0")
        if env.endswith(".json"):
            t.export_path = env
        _tracer = t
        from .registry import registry
        # per-stage rollup dicts plus the ring-overflow counter — readers
        # of the section must tolerate the one int among dict values
        # (obs.report / obs.smoke skip non-dict entries)
        registry.register("spans",
                          lambda: {**t.rollup(), "dropped": t.dropped})
    return _tracer
