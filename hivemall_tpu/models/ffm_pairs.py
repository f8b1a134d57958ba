"""Observed (feature, field) pairs of an FFM trainer.

Joint-layout model emission enumerates the pairs training saw
(``FFMTrainer._observed_pairs``). They are kept as packed int64 keys
``feature_id * F + field`` in one sorted array, no Python tuples.

On the streaming path the per-batch ``np.unique`` over every live slot
(1.28M keys a batch at the flagship's shape, ~50 ms) must not run on the
one thread that feeds the chip: inside :meth:`ObservedPairs.streaming` the
source thread's :meth:`~ObservedPairs.note` only hands the batch's host
arrays to a tracker thread, through a bounded queue (back-pressure, never
an unbounded backlog of 10 MB batches). Readers wait for the backlog
first (:meth:`~ObservedPairs.keys`), so they see every batch handed over, which
is every batch whose step has been applied: the source runs ahead of the
dispatch, never behind it.
"""

from __future__ import annotations

import contextlib
import queue
import threading

import numpy as np

__all__ = ["ObservedPairs"]


class ObservedPairs:
    THREAD_NAME = "pairs-track"
    BACKLOG = 4              # batches queued ahead of the tracker, at most
    _COMPACT_FLOOR = 1 << 20

    def __init__(self, num_fields: int):
        self.F = int(num_fields)
        self._lock = threading.Lock()
        self._merged = np.zeros(0, np.int64)    # sorted, unique
        self._pending: list = []                # unique per chunk, unmerged
        self._n_pending = 0
        self._q: "queue.Queue | None" = None    # set while a stream is tracked
        self._fault: "BaseException | None" = None

    # -- merging (any thread) -------------------------------------------------
    def add(self, keys: np.ndarray) -> None:
        """Merge packed keys, in any order and with repeats. Chunks are
        merged into the sorted array only once they outgrow it, so a long
        stream sorts each key O(log) times, not once a batch."""
        keys = np.unique(keys)
        with self._lock:
            self._pending.append(keys)
            self._n_pending += len(keys)
            if self._n_pending > max(len(self._merged), self._COMPACT_FLOOR):
                self._compact()

    def add_batch(self, idx, fld, val) -> None:
        """Merge one padded batch's live (val != 0) slots (host arrays)."""
        live = val != 0
        self.add(idx[live].astype(np.int64) * self.F
                 + fld[live].astype(np.int64))

    def _compact(self) -> None:      # lock held
        if self._pending:
            self._merged = np.unique(
                np.concatenate([self._merged] + self._pending))
            self._pending = []
            self._n_pending = 0

    # -- the streaming path ---------------------------------------------------
    def note(self, idx, fld, val) -> None:
        """Record one stream batch. Inside :meth:`streaming` this only
        queues the arrays (blocking while ``BACKLOG`` batches wait) and
        raises a fault the tracker has met; outside it merges inline."""
        # stream batches are host arrays already: np.asarray copies nothing
        item = (np.asarray(idx), np.asarray(fld), np.asarray(val))
        q = self._q
        if q is None:
            self.add_batch(*item)
            return
        self._raise_fault()
        q.put(item)

    @contextlib.contextmanager
    def streaming(self, tracer):
        """Run the tracker thread for the body's duration. On the way out
        the backlog is drained and the thread joined, whether the body
        returned or raised; a fault of the tracker is raised here when the
        body itself did not raise."""
        self._fault = None
        q: queue.Queue = queue.Queue(maxsize=self.BACKLOG)
        t = threading.Thread(target=self._run, args=(q, tracer),
                             name=self.THREAD_NAME, daemon=True)
        self._q = q
        t.start()
        try:
            yield
        finally:
            self._q = None           # a straggler's note() merges inline
            q.put(None)
            t.join()
        self._raise_fault()

    def _run(self, q: queue.Queue, tracer) -> None:
        n = 0                        # ordinal among the batches handed over
        while True:
            item = q.get()
            try:
                if item is None:
                    return
                if self._fault is None:   # after a fault: drain, so that
                    with tracer.span("pairs.track", None, n):  # put() wakes
                        self.add_batch(*item)
            except BaseException as e:    # surfaces in note() / streaming()
                self._fault = e
            finally:
                n += 1
                q.task_done()

    def _raise_fault(self) -> None:
        if self._fault is not None:
            raise self._fault

    # -- readers --------------------------------------------------------------
    def keys(self) -> np.ndarray:
        """Every distinct packed key seen so far, sorted. Waits first until
        every batch handed over so far has been merged."""
        q = self._q
        if q is not None:
            q.join()
        self._raise_fault()
        with self._lock:
            self._compact()
            return self._merged
