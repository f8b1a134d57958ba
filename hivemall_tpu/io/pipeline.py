"""Parallel host ingest pipeline — multi-worker batch prep, in order.

Why: host batch prep (string parse -> pad -> ``canonicalize_fieldmajor`` ->
``pack_unit_fieldmajor``) run as single-threaded Python ahead of a depth-2
``DevicePrefetcher`` leaves the chip waiting for its input; how much it
waits in the benchmark's cell is PERF.md section 5's
``loop.wait_input_share``. This is SURVEY §8's hard part verbatim
("the input path ... can easily be the bottleneck, not the TPU"); the
reference never met it because Hadoop amortized ingest across mappers.

:class:`IngestPipeline` shards the prep function over a pool of workers —
threads by default: the heavy kernels (``canonicalize_fieldmajor``,
``pack_unit_fieldmajor``, the padding fancy-indexing) are NumPy and release
the GIL — and delivers results **in the source order** with bounded
backpressure, so host prep, h2d transfer (``DevicePrefetcher``) and device
compute form a three-stage pipeline instead of two serialized legs::

    stats = PipelineStats()
    it = IngestPipeline(ds.batches(bs), trainer._preprocess_train_batch,
                        workers=4, stats=stats)
    for staged in DevicePrefetcher(it, depth=2, stats=stats):
        step(params, staged)

Ordering: a submitter thread walks the source iterator (serially — Python
generators are not thread-safe, and trainer hooks like ``_note_batch``
depend on stream order), submits each item to the pool, and enqueues the
FUTURES in submission order into a bounded queue; the consumer resolves
them in that same order. N-worker output is therefore the same batches in
the same order as the sequential path, and a worker exception surfaces on
the consumer within one batch (the failed future's ``result()`` raises)
instead of hanging the stream.

``workers<=1`` is a STRICT sequential fallback: no threads, no queue —
``next(src)`` then ``fn(item)`` inline, bit-exact with ``map(fn, src)``.

Every stage exports lightweight counters through :class:`PipelineStats`
(batches prepared/staged, per-stage busy and wait seconds, queue
occupancy) so later ingest work can see *where* the wall goes.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Iterator, Optional

from ..obs.trace import get_tracer

__all__ = ["PipelineStats", "IngestPipeline", "auto_workers"]

_STOP = object()


class _SourceError:
    """Marker carrying an exception raised by the SOURCE iterator (not a
    worker); the consumer re-raises it in stream position."""

    def __init__(self, e: BaseException):
        self.e = e


def drain_until_dead(q: "queue.Queue", thread: threading.Thread,
                     timeout: float = 5.0, cancel: bool = False) -> None:
    """Shared close() engine for producer-thread + bounded-queue stages
    (IngestPipeline, DevicePrefetcher): repeatedly drain ``q`` so a
    producer blocked on a full queue wakes, until ``thread`` exits or
    ``timeout`` elapses (a producer wedged OUTSIDE a queue op — e.g. a
    device_put that never returns — must not turn close() into a permanent
    hang; the daemon thread is abandoned instead). Leftover items,
    including any sentinel, are cleared; ``cancel=True`` also cancels
    drained futures."""
    deadline = time.monotonic() + timeout
    while thread.is_alive() and time.monotonic() < deadline:
        try:
            item = q.get_nowait()
            if cancel and hasattr(item, "cancel"):
                item.cancel()
        except queue.Empty:
            thread.join(timeout=0.05)
    while True:
        try:
            item = q.get_nowait()
            if cancel and hasattr(item, "cancel"):
                item.cancel()
        except queue.Empty:
            break


def _timed_call(fn, item, batch=None):
    """Module-level so ProcessPoolExecutor can pickle the task (a bound
    pipeline method would drag the queue/lock along). Returns (result,
    seconds) so prep time is measured in the worker, recorded by the
    consumer. ``batch`` is the item's ordinal in the source's order.
    The ``ingest.prep`` span is likewise recorded IN the worker
    thread — the tracer's ring is thread-safe, and worker-side spans are
    what the obs rollup attributes prep time with (process pools record
    into the child's tracer, which is lost — thread pools are the default
    and the traced configuration)."""
    t0 = time.perf_counter()
    with get_tracer().span("ingest.prep", None, batch):
        out = fn(item)
    return out, time.perf_counter() - t0


def auto_workers() -> int:
    """Default prep-worker count: leave one core for the training loop /
    device runtime, cap at 8 (past that the bounded queue and the h2d link
    are the limiters, not prep parallelism)."""
    return max(1, min(8, (os.cpu_count() or 2) - 1))


@dataclass
class PipelineStats:
    """Lightweight cross-stage counters for the ingest pipeline.

    One instance is shared by every stage of a fit: the prep pool
    (:class:`IngestPipeline`), the h2d stage (``DevicePrefetcher``) and the
    consuming train loop. Busy seconds are summed across workers (they can
    exceed wall time under parallelism); wait seconds are the time a stage
    spent BLOCKED on its neighbour — the direct reading of where the wall
    goes: large ``consume_wait_seconds`` means input-bound, large
    ``prep_backpressure_seconds`` means compute/transfer-bound.
    """

    workers: int = 0                       # prep pool size (0 = no pipeline)
    pool: str = "none"                     # "none" | "thread" | "process"
    batches_prepared: int = 0              # prep outputs (fn() completions)
    prep_seconds: float = 0.0              # summed in-worker fn() time
    prep_wait_seconds: float = 0.0         # consumer blocked on prep output
    prep_backpressure_seconds: float = 0.0  # submitter blocked on full queue
    batches_staged: int = 0                # h2d stage outputs (device_put)
    stage_seconds: float = 0.0             # summed device_put time
    consume_wait_seconds: float = 0.0      # train loop blocked on h2d output
    steps_per_dispatch: int = 1            # fused-dispatch window K
    megabatches_staged: int = 0            # K-step windows stacked
    stack_seconds: float = 0.0             # host stacking time (stager)
    singles_flushed: int = 0               # K=1 fallbacks (ragged/kind-mix)
    cache_batches: int = 0                 # batches served from the packed
                                           # shard cache (no live prep ran)
    cache_assemble_seconds: float = 0.0    # mmap gather + buffer re-slice
    queue_occupancy_sum: int = 0           # qsize sampled at each get
    queue_samples: int = 0
    queue_peak: int = 0
    worker_errors: int = 0                 # prep fn() raised (re-raised in
                                           # stream position by the consumer)
    source_errors: int = 0                 # source iterator raised
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    def add(self, **kw: float) -> None:
        with self._lock:
            for k, v in kw.items():
                setattr(self, k, getattr(self, k) + v)

    def sample_queue(self, qsize: int) -> None:
        with self._lock:
            self.queue_occupancy_sum += qsize
            self.queue_samples += 1
            if qsize > self.queue_peak:
                self.queue_peak = qsize

    @property
    def avg_queue_occupancy(self) -> float:
        return (self.queue_occupancy_sum / self.queue_samples
                if self.queue_samples else 0.0)

    def as_dict(self) -> dict:
        """JSON-ready snapshot (the registry's ``pipeline`` section)."""
        return {
            "workers": self.workers,
            "pool": self.pool,
            "batches_prepared": self.batches_prepared,
            "prep_seconds": round(self.prep_seconds, 4),
            "prep_wait_seconds": round(self.prep_wait_seconds, 4),
            "prep_backpressure_seconds":
                round(self.prep_backpressure_seconds, 4),
            "batches_staged": self.batches_staged,
            "stage_seconds": round(self.stage_seconds, 4),
            "consume_wait_seconds": round(self.consume_wait_seconds, 4),
            "steps_per_dispatch": self.steps_per_dispatch,
            "megabatches_staged": self.megabatches_staged,
            "stack_seconds": round(self.stack_seconds, 4),
            "singles_flushed": self.singles_flushed,
            "cache_batches": self.cache_batches,
            "cache_assemble_seconds": round(self.cache_assemble_seconds, 4),
            "avg_queue_occupancy": round(self.avg_queue_occupancy, 3),
            "queue_peak": self.queue_peak,
            "worker_errors": self.worker_errors,
            "source_errors": self.source_errors,
        }


class IngestPipeline:
    """Map ``fn`` over ``src`` with ``workers`` pool workers, delivering
    results in source order with bounded backpressure.

    ``pool="thread"`` (default) suits NumPy-heavy prep (releases the GIL);
    ``pool="process"`` is for string-parse-heavy sources where the prep is
    Python-bound — ``fn`` and the items must then be picklable, which rules
    out bound trainer methods (use a module-level parse function).

    ``depth`` bounds the prepared-but-unconsumed batches (default
    ``2*workers``); total in-flight work is ``depth`` queued + ``workers``
    executing + one pending submit.
    """

    def __init__(self, src: Iterable[Any], fn: Callable[[Any], Any], *,
                 workers: Optional[int] = None, depth: Optional[int] = None,
                 pool: str = "thread",
                 stats: Optional[PipelineStats] = None):
        if pool not in ("thread", "process"):
            raise ValueError(f"pool must be 'thread' or 'process': {pool!r}")
        self._workers = auto_workers() if workers is None or workers <= 0 \
            else int(workers)
        self.stats = stats if stats is not None else PipelineStats()
        self.stats.workers = self._workers
        self._fn = fn
        self._n = 0                     # items taken (`batch` of the spans)
        self._closed = threading.Event()
        if self._workers <= 1:
            # strict sequential fallback: no threads, no queue — bit-exact
            # with map(fn, src) (single-worker behavior is the pre-pipeline
            # contract tests pin)
            self.stats.pool = "none"
            self._src: Optional[Iterator[Any]] = iter(src)
            self._exec = None
            return
        import concurrent.futures as cf
        import multiprocessing as mp
        from ..utils.device import host_only_worker
        self.stats.pool = pool
        self._src = None
        self._q: queue.Queue = queue.Queue(
            maxsize=max(1, depth if depth is not None else 2 * self._workers))
        self._exec = (cf.ThreadPoolExecutor(self._workers,
                                            thread_name_prefix="ingest")
                      if pool == "thread"
                      # spawn, never fork: the parent is multithreaded
                      # (JAX, this pipeline) and a forked child that
                      # touches jax deadlocks
                      else cf.ProcessPoolExecutor(
                          self._workers, mp_context=mp.get_context("spawn"),
                          initializer=host_only_worker))
        # the submitter closure captures LOCALS only, never self (a thread
        # is a GC root: a closure over self would keep an abandoned
        # pipeline reachable forever and __del__ could never run close())
        q, closed, ex, stats = self._q, self._closed, self._exec, self.stats
        tracer = get_tracer()

        def submit_loop(it: Iterator[Any]) -> None:
            try:
                for n, item in enumerate(it):
                    f = ex.submit(_timed_call, fn, item, n)
                    # the span and the counter: the same seconds
                    with tracer.span("ingest.wait_slot", None, n):
                        t0 = time.perf_counter()
                        q.put(f)        # blocking; close() drains to wake
                        dt = time.perf_counter() - t0
                    stats.add(prep_backpressure_seconds=dt)
                    if closed.is_set():
                        f.cancel()
                        return          # consumer abandoned the stream
            except BaseException as e:  # src iteration failed: surface it
                q.put(_SourceError(e))
            finally:
                # the sentinel MUST reach the consumer or next() blocks
                # forever; close() keeps draining until this thread exits,
                # so a blocked put always wakes
                q.put(_STOP)

        self._submitter = threading.Thread(target=submit_loop,
                                           args=(iter(src),), daemon=True,
                                           name="ingest-source")
        self._submitter.start()

    # -- consumer side ------------------------------------------------------
    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        if self._closed.is_set():
            raise StopIteration
        if self._exec is None:          # sequential fallback
            item = next(self._src)      # StopIteration ends the stream
            t0 = time.perf_counter()
            try:
                with get_tracer().span("ingest.prep", None, self._n):
                    out = self._fn(item)
            except BaseException:
                self.stats.add(worker_errors=1)
                self._closed.set()
                raise
            self._n += 1
            self.stats.add(prep_seconds=time.perf_counter() - t0,
                           batches_prepared=1)
            return out
        # ingest.wait_prep and prep_wait_seconds: the same seconds, this
        # thread blocked on the pool (the wait for the stream's end too)
        with get_tracer().span("ingest.wait_prep", None, self._n):
            t0 = time.perf_counter()
            fut = self._q.get()         # blocking; sentinel always arrives
            if fut is not _STOP and not isinstance(fut, _SourceError):
                self.stats.sample_queue(self._q.qsize())
                try:
                    # a worker's exception re-raises HERE, within one
                    # batch of where it fired
                    out, dt = fut.result()
                except BaseException:
                    self.stats.add(worker_errors=1)
                    self.close()
                    raise
            waited = time.perf_counter() - t0
        self.stats.add(prep_wait_seconds=waited)
        if fut is _STOP:
            self._closed.set()
            self._submitter.join()
            self._exec.shutdown(wait=False)
            raise StopIteration
        if isinstance(fut, _SourceError):
            self.stats.add(source_errors=1)
            self.close()
            raise fut.e
        self._n += 1
        self.stats.add(prep_seconds=dt, batches_prepared=1)
        return out

    def close(self) -> None:
        """Release the submitter + pool (early exit; safe to call twice)."""
        self._closed.set()
        if self._exec is None:
            return
        drain_until_dead(self._q, self._submitter, cancel=True)
        self._exec.shutdown(wait=False)

    def __del__(self):
        try:
            self.close()
        except BaseException:
            pass
