"""Device prefetcher — overlap host batch prep with TPU compute.

Reference context (SURVEY.md §8 hard parts): "the input path (hashing +
batching on host) can easily be the bottleneck, not the TPU". The reference
has no analog (Hadoop feeds rows to the UDTF synchronously); on TPU the
host→device link is latency the training step should never wait on. A
worker thread stages upcoming batches with ``jax.device_put`` while the
current step runs, keeping ``depth`` batches in flight — the same
double-buffering idea as the Pallas DMA pipeline, at the input-pipeline
level.

Usage:
    for batch in DevicePrefetcher(ds.batches(bs), depth=2):
        step(params, batch)           # batch arrays already on device

LearnerBase.fit uses this automatically on accelerator backends; with
``-ingest_workers > 1`` the source is an :class:`io.pipeline.IngestPipeline`
and the two stages share one :class:`io.pipeline.PipelineStats`.

All queue operations BLOCK (no poll loops): the end of the stream is a
poison pill the worker always delivers, and ``close()`` wakes a worker
blocked on a full queue by draining until the thread exits. The previous
0.1 s timeout-poll put/get loops burned a core and added up to 100 ms
latency per batch at shutdown boundaries.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Iterable, Iterator, Optional

import jax
import numpy as np

from ..obs.trace import get_tracer
from .sparse import MegaBatch, PackedBatch, PackedMegaBatch, SparseBatch

__all__ = ["DevicePrefetcher", "MegabatchStager", "stage_batch"]

_STOP = object()


def stage_batch(b, sharding=None, span: str = "h2d.stage"):
    """Traced wrapper (``h2d.stage`` span) over :func:`_stage_batch` —
    the transfer is the seam the obs rollup attributes h2d time with.
    A trainer that places input from the thread that dispatches names
    that span ``h2d.shard``."""
    with get_tracer().span(span, getattr(b, "seq", None)):
        return _stage_batch(b, sharding)


def _stage_batch(b, sharding=None):
    """device_put every array of one batch: on the default device, or,
    given a trainer's ``sharding(ndim, row_axis)`` (a -mesh trainer's
    ``_input_sharding``), in the sharding it names for an array whose
    batch rows lie along ``row_axis`` (None: no rows, replicated).
    ``val=None`` (unit-value elision, see SparseBatch) and ``field=None``
    are preserved — skipping the val transfer is the point: it is a third
    of the batch bytes, and the jitted unit-val step variants rebuild val
    from idx on device for free.
    A PackedBatch stages its single uint8 buffer — ONE transfer.

    Megabatches (MegaBatch / PackedMegaBatch — K stacked steps, ONE
    transfer) additionally BLOCK until the transfer completes before
    returning: the MegabatchStager upstream reuses its staging buffers
    across windows, and the reuse contract is "the previous window's
    h2d is done by the time the next window stacks" (this staging call
    and the next stack run on the same prefetcher worker thread, so
    blocking here is exactly that barrier). Transfer/compute overlap is
    untouched — the consumer thread keeps running the train step."""
    def put(a, row_axis=None):
        if sharding is None:
            return jax.device_put(a)
        return jax.device_put(a, sharding(np.ndim(a), row_axis))
    if isinstance(b, PackedBatch):
        return PackedBatch(put(b.buf), b.B, b.L, b.n_valid, seq=b.seq)
    if isinstance(b, PackedMegaBatch):
        staged = PackedMegaBatch(put(b.buf), b.B, b.L, nv=b.nv,
                                 nv_dev=put(b.nv), seq=b.seq)
        jax.block_until_ready((staged.buf, staged.nv_dev))
        return staged
    if isinstance(b, MegaBatch):
        staged = MegaBatch(put(b.idx, 1),
                           None if b.val is None else put(b.val, 1),
                           put(b.label, 1),
                           None if b.field is None else put(b.field, 1),
                           nv=b.nv, nv_dev=put(b.nv),
                           fieldmajor=b.fieldmajor, seq=b.seq)
        jax.block_until_ready(
            [a for a in (staged.idx, staged.val, staged.label,
                         staged.field, staged.nv_dev) if a is not None])
        return staged
    return SparseBatch(put(b.idx, 0),
                       None if b.val is None else put(b.val, 0),
                       put(b.label, 0),
                       None if b.field is None else put(b.field, 0),
                       b.n_valid, fieldmajor=b.fieldmajor, seq=b.seq)


class DevicePrefetcher:
    """Iterate ``src`` with up to ``depth`` device-staged batches in flight.

    The worker thread only calls device_put (thread-safe in JAX) and dies
    with the iterator; errors in ``src`` re-raise in the consumer thread.
    Single-consumer: ``__next__`` and ``close()`` are meant to be called
    from one thread (the pattern every fit loop follows).

    ``stats`` (optional PipelineStats) records the h2d stage: batches
    staged, summed device_put seconds, and the consumer's blocked-on-get
    wait — the three numbers that say whether the wall is transfer-bound.
    ``sharding`` is a -mesh trainer's placement (see :func:`_stage_batch`).
    """

    def __init__(self, src: Iterable[SparseBatch], depth: int = 2,
                 sharding=None, stats=None):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._errbox: list = []         # worker's exception, surfaced on next()
        self._closed = threading.Event()
        self._stats = stats

        # the worker closure captures LOCALS only, never self: a closure
        # over self would keep an abandoned prefetcher reachable forever
        # (the thread is a GC root), so __del__ could never fire to
        # release a worker blocked on a full queue
        q, closed, errbox = self._q, self._closed, self._errbox
        tracer = get_tracer()

        def work():
            try:
                for b in src:
                    t0 = time.perf_counter()
                    staged = stage_batch(b, sharding)
                    if stats is not None:
                        stats.add(stage_seconds=time.perf_counter() - t0,
                                  batches_staged=1)
                    # blocking put: no poll loop. If the consumer abandons
                    # the stream, close() drains the queue until this
                    # thread exits, so a put blocked on a full queue
                    # always wakes. feed.wait_slot: the consumer has
                    # not taken the previous input yet.
                    with tracer.span("feed.wait_slot",
                                     getattr(staged, "seq", None)):
                        q.put(staged)
                    if closed.is_set():
                        return          # consumer abandoned the stream
            except BaseException as e:          # surfaced on next()
                errbox.append(e)
            finally:
                # the poison pill MUST reach the consumer or __next__
                # blocks forever; a blocked put here is woken by close()'s
                # drain-until-exit loop exactly like the staging put above
                q.put(_STOP)

        self._thread = threading.Thread(target=work, daemon=True,
                                        name="h2d-prefetch")
        self._thread.start()

    def close(self) -> None:
        """Release the worker (called on early exit; safe to call twice).
        Drains the queue until the worker exits so a blocked put wakes;
        bounded at 5 s so a device_put that never returns can't turn
        close() into a permanent hang (the daemon thread is abandoned)."""
        self._closed.set()
        from .pipeline import drain_until_dead
        drain_until_dead(self._q, self._thread)

    def __iter__(self) -> Iterator[SparseBatch]:
        return self

    def __next__(self) -> SparseBatch:
        if self._closed.is_set():       # closed stream ends, never hangs
            raise StopIteration
        t0 = time.perf_counter()
        item = self._q.get()            # blocking; the pill always arrives
        if self._stats is not None:
            self._stats.add(consume_wait_seconds=time.perf_counter() - t0)
        if item is _STOP:
            self._closed.set()          # further next() calls end immediately
            self._thread.join()
            if self._errbox:
                raise self._errbox[0]
            raise StopIteration
        return item

    def __del__(self):
        # actually release the worker: setting the event alone left a
        # worker blocked on a full queue alive until process exit
        try:
            self.close()
        except BaseException:
            pass


class MegabatchStager:
    """Stack runs of K consecutive same-kind prepared batches into one
    megabatch (io.sparse.MegaBatch / PackedMegaBatch) so the dispatch
    path pays ONE h2d transfer and ONE jitted call per K optimizer steps
    (``-steps_per_dispatch``; ops.scan runs the K steps as a lax.scan).

    Synchronous iterator — no thread of its own; it runs on whichever
    thread consumes it (the DevicePrefetcher worker in the standard
    stack, the train loop when prefetch is off).

    Grouping: a window only ever holds batches of one KIND — same class
    (SparseBatch vs PackedBatch), same array shapes, same val/field
    presence, same fieldmajor flag. Unit-value elision therefore
    survives stacking: an idx-only window stays idx-only (no val array
    is ever materialized for it), and a real-valued batch arriving
    mid-window flushes the window instead of poisoning it. Flushed
    partials — ragged tails (last window < K), kind changes, stream end
    — fall back to the K=1 path one batch at a time, so every batch
    trains in source order exactly once either way.

    Staging-buffer reuse: on accelerator backends the stacked arrays are
    written into a per-kind ring of TWO pinned staging buffer sets. The
    downstream ``stage_batch`` blocks until each megabatch's transfer
    completes (same thread), so when window N stacks, window N-1's
    transfer is done and N-2's buffers are free — no copy races. On the
    CPU backend buffers are freshly allocated instead: device_put there
    may alias host memory, so reuse could corrupt a batch mid-step.

    ``stats`` (PipelineStats) records stack time, megabatches staged and
    singles flushed — the dispatch-overhead decomposition the
    benchmark's first-dispatch check and chip_smoke.py read.

    ``reuse=True`` is only valid when a DevicePrefetcher consumes this
    stager on its worker thread (its ``stage_batch`` provides the
    transfer-complete barrier the ring depends on); callers feeding
    megabatches straight into the train loop (prefetch off)
    must leave it False — there device_put/dispatch is async and a
    reused buffer could be rewritten mid-transfer."""

    def __init__(self, src: Iterable, k: int, stats=None,
                 reuse: bool = False):
        if k < 2:
            raise ValueError(f"MegabatchStager needs k >= 2, got {k}")
        self._src = iter(src)
        self._k = int(k)
        self._stats = stats
        if stats is not None:
            stats.steps_per_dispatch = self._k
        self._window: list = []
        self._out: list = []            # flushed items pending emission
        self._done = False
        self._reuse = bool(reuse) and jax.default_backend() != "cpu"
        self._rings: dict = {}          # kind key -> [bufset, bufset]
        self._ring_pos: dict = {}
        self._n_in = 0                  # source batches taken (`batch`)
        self._n_out = 0                 # items emitted (`seq`)

    # -- kind/grouping -------------------------------------------------------
    @staticmethod
    def _kind(b):
        if isinstance(b, PackedBatch):
            return ("packed", b.B, b.L, int(b.buf.size))
        if isinstance(b, SparseBatch) and isinstance(b.idx, np.ndarray):
            return ("sparse", b.idx.shape, b.val is None,
                    b.field is None, b.fieldmajor)
        return None                     # device-staged/foreign: never stack

    # -- staging-buffer ring -------------------------------------------------
    def _staging(self, key, shapes_dtypes):
        """One SET of stacked staging buffers for ``key`` — a dict of
        np arrays matching (name -> (shape, dtype)). Ring of two on
        accelerators (see class docstring); fresh allocation on CPU."""
        def alloc():
            return {name: np.empty(shape, dtype)
                    for name, (shape, dtype) in shapes_dtypes.items()}
        if not self._reuse:
            return alloc()
        ring = self._rings.get(key)
        if ring is None:
            ring = [None, None]
            self._rings[key] = ring
            self._ring_pos[key] = 0
        pos = self._ring_pos[key]
        self._ring_pos[key] = 1 - pos
        bufs = ring[pos]
        if bufs is None or any(bufs[n].shape != sd[0] or bufs[n].dtype != sd[1]
                               for n, sd in shapes_dtypes.items()):
            bufs = alloc()
            ring[pos] = bufs
        return bufs

    def _stack(self, window):
        # seq: the ordinal this dispatch gets when emitted (whatever is
        # queued in _out goes first); batch: the window's first source
        # batch (the window is the last len(window) batches taken)
        with get_tracer().span("stager.stack", self._n_out + len(self._out),
                               self._n_in - len(window)):
            return self._stack_inner(window)

    def _stack_inner(self, window):
        t0 = time.perf_counter()
        K = len(window)
        first = window[0]
        nv = np.asarray(
            [(b.n_valid if b.n_valid is not None else b.batch_size)
             for b in window], np.int32)
        if isinstance(first, PackedBatch):
            bufs = self._staging(self._kind(first),
                                 {"buf": ((K, first.buf.size), np.uint8)})
            for i, b in enumerate(window):
                bufs["buf"][i] = b.buf
            out = PackedMegaBatch(bufs["buf"], first.B, first.L, nv=nv)
        else:
            spec = {"idx": ((K,) + first.idx.shape, np.int32),
                    "label": ((K,) + first.label.shape, np.float32)}
            if first.val is not None:
                spec["val"] = ((K,) + first.val.shape, np.float32)
            if first.field is not None:
                spec["field"] = ((K,) + first.field.shape, np.int32)
            bufs = self._staging(self._kind(first), spec)
            for i, b in enumerate(window):
                bufs["idx"][i] = b.idx
                bufs["label"][i] = b.label
                if "val" in bufs:
                    bufs["val"][i] = b.val
                if "field" in bufs:
                    bufs["field"][i] = b.field
            out = MegaBatch(bufs["idx"], bufs.get("val"), bufs["label"],
                            bufs.get("field"), nv=nv,
                            fieldmajor=first.fieldmajor)
        if self._stats is not None:
            self._stats.add(stack_seconds=time.perf_counter() - t0,
                            megabatches_staged=1)
        return out

    def _flush(self, full: bool) -> None:
        """Move the current window to the output queue: stacked when it
        reached K, one-at-a-time (K=1 fallback) otherwise."""
        if not self._window:
            return
        if full:
            self._out.append(self._stack(self._window))
        else:
            self._out.extend(self._window)
            if self._stats is not None:
                self._stats.add(singles_flushed=len(self._window))
        self._window = []

    def __iter__(self):
        return self

    def __next__(self):
        while not self._out:
            if self._done:
                raise StopIteration
            try:
                b = next(self._src)
            except StopIteration:
                self._done = True
                self._flush(full=False)        # ragged tail -> K=1 path
                continue
            self._n_in += 1
            kind = self._kind(b)
            if kind is None:
                self._flush(full=False)
                self._out.append(b)
                if self._stats is not None:
                    self._stats.add(singles_flushed=1)
                continue
            if self._window and self._kind(self._window[0]) != kind:
                self._flush(full=False)        # kind change -> K=1 path
            self._window.append(b)
            if len(self._window) >= self._k:
                self._flush(full=True)
        out = self._out.pop(0)
        if hasattr(out, "seq"):         # stacked windows and singles alike
            out.seq = self._n_out
        self._n_out += 1
        return out
