"""Observability: per-host jsonl metrics stream + step timing + profiler.

Reference (SURVEY.md §6 "Tracing / profiling"): Hivemall itself has no
tracing subsystem — trainers report progress through Hadoop's MapredContext
counters (`reportProgress`), log via log4j, and the MixServer exposes JMX
metrics. The rebuild's equivalent is this module: a line-per-event jsonl
stream each host appends to (the Hadoop-counter analog), a rolling
examples/sec meter (the BASELINE primary metric), and a `jax.profiler`
trace context for deep dives.

Activation: set ``HIVEMALL_TPU_METRICS=<path>`` (or ``-`` for stderr) and
every trainer emits records at its loss-fold cadence with zero config; or
construct a ``MetricsStream`` explicitly and pass it around. When the env
var is unset the module-level stream is a no-op with one attribute check of
overhead per emit.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, IO, Optional

__all__ = ["MetricsStream", "Meter", "get_stream"]


class Meter:
    """Rolling examples/sec over a sliding window of (time, count) marks."""

    def __init__(self, window: float = 30.0):
        self.window = window
        self._marks: deque = deque()    # (monotonic time, cumulative count)
        self.total = 0

    def add(self, n: int) -> None:
        now = time.monotonic()
        self.total += n
        self._marks.append((now, self.total))
        lo = now - self.window
        while len(self._marks) > 2 and self._marks[0][0] < lo:
            self._marks.popleft()

    @property
    def rate(self) -> float:
        if len(self._marks) < 2:
            return 0.0
        (t0, c0), (t1, c1) = self._marks[0], self._marks[-1]
        return (c1 - c0) / max(t1 - t0, 1e-9)


class MetricsStream:
    """Append-only jsonl event stream, one file per host process.

    Records carry {ts, host, pid, event, ...fields}. Failure to write is
    swallowed after disabling the stream — observability must never take
    training down (the reference's counters are likewise fire-and-forget) —
    but every event lost that way is COUNTED (``dropped_events``) and
    surfaced through the obs registry's ``metrics_stream`` section, so a
    silent disk-full at hour 3 of a soak shows up in the snapshot instead
    of as a mysteriously short file.

    Thread-safety: emits may arrive from the train loop, ingest workers,
    and the prefetcher thread at once; one lock serializes the write so
    lines are never interleaved/torn (json encoding happens outside it).

    Rotation: ``HIVEMALL_TPU_METRICS_MAX_MB=<float>`` bounds an owned-file
    sink for long soaks — past the limit the file rotates to ``<path>.1``
    (one generation, overwriting the previous) and a fresh file continues.
    """

    def __init__(self, sink: "str | IO[str] | None"):
        self._fh: Optional[IO[str]] = None
        self._own = False
        self._path: Optional[str] = None
        self._failed = False             # write failure disabled the stream
        self.dropped_events = 0          # events lost to failures post-open
        self.rotations = 0
        self._bytes = 0
        self._lock = threading.Lock()
        self._max_bytes = 0
        try:
            mb = float(os.environ.get("HIVEMALL_TPU_METRICS_MAX_MB") or 0)
            self._max_bytes = int(mb * 1e6) if mb > 0 else 0
        except ValueError:
            pass
        if sink == "-":
            self._fh = sys.stderr
        elif isinstance(sink, str):
            try:
                self._fh = open(sink, "a", buffering=1)
                self._own = True
                self._path = sink
                self._bytes = os.path.getsize(sink)
            except OSError as e:            # fail soft: bad path must not
                print(f"hivemall_tpu: metrics sink {sink!r} unusable ({e}); "
                      "metrics disabled", file=sys.stderr)
        elif sink is not None:
            self._fh = sink
        self._host = socket.gethostname()
        self._pid = os.getpid()

    @property
    def enabled(self) -> bool:
        return self._fh is not None

    def emit(self, event: str, **fields: Any) -> None:
        if self._fh is None:
            if self._failed:             # disabled BY failure: count the loss
                self.dropped_events += 1
            return
        rec: Dict[str, Any] = {"ts": round(time.time(), 3),
                               "host": self._host, "pid": self._pid,
                               "event": event}
        rec.update(fields)
        try:
            # default=str: registry providers are a public surface and a
            # numpy scalar slipping into a counter dict must degrade to a
            # stringified value, never take training down mid-emit
            line = json.dumps(rec, default=str) + "\n"
        except (TypeError, ValueError):    # circular refs etc.: drop it
            self.dropped_events += 1
            return
        with self._lock:
            if self._fh is None:         # lost a race with a failing writer
                self.dropped_events += 1
                return
            try:
                self._fh.write(line)
            except (OSError, ValueError):
                self._fh = None          # fail soft, never raise mid-train
                self._failed = True
                self.dropped_events += 1
                return
            self._bytes += len(line)
            if (self._max_bytes and self._own and self._path
                    and self._bytes >= self._max_bytes):
                self._rotate()

    def _rotate(self) -> None:
        """Size-based rotation (lock held): current file -> <path>.1
        (replacing the previous generation), fresh file continues. Any
        failure degrades to the fail-soft disable, counted as a drop."""
        try:
            self._fh.close()
            os.replace(self._path, self._path + ".1")
            self._fh = open(self._path, "a", buffering=1)
            self._bytes = 0
            self.rotations += 1
        except OSError:
            self._fh = None
            self._failed = True
            self.dropped_events += 1

    def counters(self) -> Dict[str, Any]:
        """Health surface for the obs registry (``metrics_stream``)."""
        return {"enabled": self.enabled, "dropped_events": self.dropped_events,
                "rotations": self.rotations, "path": self._path}

    def close(self) -> None:
        with self._lock:
            if self._own and self._fh is not None:
                self._fh.close()
            self._fh = None


_stream: Optional[MetricsStream] = None


def _stream_counters() -> Dict[str, Any]:
    # reads the module global so monkeypatched/replaced streams are the
    # ones reported (tests and obs.smoke install streams by assigning
    # M._stream directly, never calling get_stream)
    return _stream.counters() if _stream is not None else {}


def _register_stream_section() -> None:
    # at import, not inside get_stream(): the section must exist no
    # matter HOW the stream is installed (env-bound via get_stream, or
    # direct module-global assignment)
    from ..obs.registry import registry
    registry.register("metrics_stream", _stream_counters)


_register_stream_section()


def get_stream() -> MetricsStream:
    """The process-wide stream, bound to $HIVEMALL_TPU_METRICS on first use."""
    global _stream
    if _stream is None:
        _stream = MetricsStream(os.environ.get("HIVEMALL_TPU_METRICS"))
    return _stream


def close_stream() -> None:
    """Close and unbind the process-wide stream (smoke/driver teardown —
    the leaktrack census counts a still-open sink as a leak once its
    run is over). The next :func:`get_stream` re-binds from the env."""
    global _stream
    if _stream is not None:
        _stream.close()
        _stream = None
