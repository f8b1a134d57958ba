"""FD/socket/thread leak census sanitizer (docs/STATIC_ANALYSIS.md).

The dynamic twin of graftcheck's GC12: where the static rule reasons
about resource lifetimes it can SEE in the source, this module counts
the resources a process actually HOLDS and fails the run when the
census grows across a full traffic + reload + drain + shutdown cycle —
the leak classes that survive static analysis (handles parked in C
extensions, caches that "own" a socket nobody releases, threads whose
join was skipped on one path).

How it works, when enabled:

- :func:`enable` wraps the creation surface so every resource born
  afterwards is attributed to its creation stack: ``socket.socket`` (a
  subclass — ``create_connection``/``create_server``/``accept`` all
  construct through the module-level class, so they inherit tracking),
  ``builtins.open`` and ``os.fdopen`` (the returned file object is
  registered), ``mmap.mmap`` (a subclass) and ``threading.Thread.start``
  (the creation stack rides on the thread object).
- :func:`snapshot` records the baseline at smoke start: the set of open
  fd numbers (``/proc/self/fd``) and the set of live threads.
- :func:`check_and_report` runs after drain/shutdown: a ``gc.collect``
  sweeps dropped-but-uncollected handles (GC lag is not a leak), then
  every TRACKED resource that is still open and was created after the
  snapshot is a leak, as is every post-snapshot thread still alive
  (after a short grace for threads mid-join). Each leak is reported
  with its creation stack and appended to the JSONL artifact
  (``HIVEMALL_TPU_LEAKTRACK_LOG``) the way tsan races are. The RAW fd
  delta (tracked or not) is always reported as context — untracked
  growth (a C extension, the JAX runtime) logs as ``fd_delta`` info
  but only tracked leaks fail the gate, so the sanitizer stays
  deterministic on hosts whose runtime lazily opens fds.

Gating: ``HIVEMALL_TPU_LEAKTRACK=1`` turns :func:`maybe_enable` on (the
serve/fleet/retrain smokes call it before building anything); the
benchmark never enables it — a sanitizer build is never a perf build.

Known limitations: resources created BEFORE :func:`enable` are
invisible (enable first, construct second); fd-level growth without a
tracked owner is reported, not failed; a resource handed to a child
process is the child's business (each process runs its own census).
"""

from __future__ import annotations

import builtins
import gc
import json
import mmap as _mmap_mod
import os
import socket as _socket_mod
import sys
import threading
import time
import traceback
import weakref
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["enable", "disable", "enabled", "maybe_enable", "snapshot",
           "census", "check_and_report", "leaks", "selfcheck_leak",
           "log_offset", "report_child_leaks", "ENV_FLAG", "ENV_LOG"]

ENV_FLAG = "HIVEMALL_TPU_LEAKTRACK"
ENV_LOG = "HIVEMALL_TPU_LEAKTRACK_LOG"

_STACK_LIMIT = 12
_THREAD_GRACE_S = 2.0            # a drained worker may be mid-join

_enabled = False
_orig_socket = _socket_mod.socket
_orig_open = builtins.open
_orig_fdopen = os.fdopen
_orig_mmap = _mmap_mod.mmap
_orig_thread_start = threading.Thread.start

#: tracked live resources: obj -> (kind, created_monotonic, stack)
_tracked: "weakref.WeakKeyDictionary[Any, Tuple[str, float, str]]" = \
    weakref.WeakKeyDictionary()
_snap: Optional[dict] = None


def _stack() -> str:
    return "".join(traceback.format_stack(sys._getframe(2),
                                          limit=_STACK_LIMIT))


def _register(obj: Any, kind: str) -> None:
    try:
        _tracked[obj] = (kind, time.monotonic(), _stack())
    except TypeError:
        pass                             # un-weakref-able: skip


class _TrackedSocket(_orig_socket):
    """socket.socket twin that records its creation stack. accept() and
    create_connection construct through the module-level class, so
    every socket born while the sanitizer is on is attributed."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        _register(self, "socket")


class _TrackedMmap(_orig_mmap):
    def __new__(cls, *a, **kw):
        m = super().__new__(cls, *a, **kw)
        _register(m, "mmap")
        return m


def _tracked_open(*a, **kw):
    f = _orig_open(*a, **kw)
    _register(f, "file")
    return f


def _tracked_fdopen(*a, **kw):
    f = _orig_fdopen(*a, **kw)
    _register(f, "file")
    return f


def _tracked_thread_start(self: threading.Thread) -> None:
    if getattr(self, "_leaktrack_stack", None) is None:
        try:
            self._leaktrack_stack = _stack()      # type: ignore[attr]
            self._leaktrack_started = time.monotonic()  # type: ignore
        except AttributeError:
            pass
    _orig_thread_start(self)


def enable() -> None:
    """Turn creation tracking on. Call BEFORE constructing the system
    under test — resources born earlier have no creation stack and are
    judged only through the raw fd delta."""
    global _enabled
    if _enabled:
        return
    _enabled = True
    _socket_mod.socket = _TrackedSocket      # type: ignore[misc]
    _mmap_mod.mmap = _TrackedMmap            # type: ignore[misc]
    builtins.open = _tracked_open            # type: ignore[assignment]
    os.fdopen = _tracked_fdopen              # type: ignore[assignment]
    threading.Thread.start = _tracked_thread_start  # type: ignore[misc]


def disable() -> None:
    """Restore the original creation surface (test hygiene; tracked
    state persists until :func:`reset`)."""
    global _enabled
    if not _enabled:
        return
    _enabled = False
    _socket_mod.socket = _orig_socket        # type: ignore[misc]
    _mmap_mod.mmap = _orig_mmap              # type: ignore[misc]
    builtins.open = _orig_open               # type: ignore[assignment]
    os.fdopen = _orig_fdopen                 # type: ignore[assignment]
    threading.Thread.start = _orig_thread_start  # type: ignore[misc]


def enabled() -> bool:
    return _enabled


def maybe_enable() -> bool:
    """Enable iff the ``HIVEMALL_TPU_LEAKTRACK`` env flag is set (the
    smoke entry points call this first thing, then :func:`snapshot`).
    Explicit negatives — ``0``/``false``/``no``/``off`` — stay off."""
    val = os.environ.get(ENV_FLAG, "").strip().lower()
    if val not in ("", "0", "false", "no", "off"):
        enable()
    return _enabled


def reset() -> None:
    global _snap
    _tracked.clear()
    _snap = None


def _fd_set() -> frozenset:
    try:
        return frozenset(int(x) for x in os.listdir("/proc/self/fd"))
    except OSError:                      # non-procfs host: count-free
        return frozenset()


def snapshot() -> dict:
    """Record the census baseline: open fd numbers, live threads, and
    the moment — resources created after this point must be gone again
    by :func:`check_and_report`."""
    global _snap
    _snap = {
        "t": time.monotonic(),
        "fds": _fd_set(),
        "threads": frozenset(id(t) for t in threading.enumerate()),
    }
    return _snap


def _is_open(obj: Any, kind: str) -> bool:
    try:
        if kind == "socket":
            return obj.fileno() != -1
        if kind == "file":
            return not obj.closed
        if kind == "mmap":
            return not obj.closed
    except (OSError, ValueError):
        return False
    return False


def census() -> Dict[str, Any]:
    """The live resource census: tracked open handles created after the
    snapshot (with stacks), post-snapshot live threads, raw fd delta."""
    gc.collect()                         # GC lag is not a leak
    base = _snap or {"t": -1.0, "fds": frozenset(),
                     "threads": frozenset()}
    tracked: List[dict] = []
    for obj, (kind, t, stack) in list(_tracked.items()):
        if t < base["t"] or not _is_open(obj, kind):
            continue
        try:
            fd = obj.fileno()
        except (OSError, ValueError, AttributeError):
            fd = None
        tracked.append({"kind": kind, "fd": fd, "stack": stack,
                        "repr": repr(obj)[:200]})
    threads: List[dict] = []
    for t in threading.enumerate():
        if id(t) in base["threads"] or t is threading.current_thread():
            continue
        if isinstance(t, threading._DummyThread):
            continue                     # a C runtime thread that once
            #                              called into Python — not ours
            #                              to join, not attributable
        threads.append({"kind": "thread", "name": t.name,
                        "daemon": t.daemon,
                        "stack": getattr(t, "_leaktrack_stack",
                                         "<started before enable()>")})
    now_fds = _fd_set()
    return {
        "tracked": tracked,
        "threads": threads,
        "fd_delta": len(now_fds) - len(base["fds"]),
        "new_fds": sorted(now_fds - base["fds"]),
    }


def _threads_linger() -> bool:
    """Cheap post-snapshot-thread liveness probe for the grace loop —
    :func:`census` costs a full ``gc.collect`` and must not run at
    50 ms cadence."""
    base = (_snap or {}).get("threads", frozenset())
    for t in threading.enumerate():
        if id(t) in base or t is threading.current_thread():
            continue
        if isinstance(t, threading._DummyThread):
            continue
        return True
    return False


def leaks(grace_s: float = _THREAD_GRACE_S) -> Dict[str, Any]:
    """The failing subset of :func:`census`: tracked handles still open
    + post-snapshot threads still alive after ``grace_s`` (a drained
    worker may be mid-join — polling beats a false positive). The
    grace loop polls raw thread liveness; the one real census (with its
    ``gc.collect``) runs after the threads settle."""
    deadline = time.monotonic() + grace_s
    while _threads_linger() and time.monotonic() < deadline:
        time.sleep(0.05)
    return census()


def _emit(record: dict) -> None:
    path = os.environ.get(ENV_LOG)
    if not path:
        return
    data = (json.dumps(record) + "\n").encode("utf-8")
    try:
        # one O_APPEND write per record: replicas share the artifact
        # with the manager, exactly like the tsan race log
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        try:
            os.write(fd, data)
        finally:
            os.close(fd)
    except OSError:
        pass                             # the log is best-effort


def log_offset() -> int:
    """Byte offset of the shared JSONL artifact (0 when unset/absent).
    Record it at smoke start, then hand it to
    :func:`report_child_leaks` so the scan covers exactly THIS run's
    appended records — CI legs share one artifact file."""
    path = os.environ.get(ENV_LOG)
    if not path:
        return 0
    try:
        return os.path.getsize(path)
    except OSError:
        return 0


def report_child_leaks(offset: int, label: str = "leaktrack") -> int:
    """Fold CHILD-process censuses into the parent gate: replica
    workers run their own :func:`check_and_report` on drain (label
    ``replica:<port> ...``) and append to the shared artifact via the
    inherited env. Returns the summed leak count of ``replica:``
    summaries appended after ``offset``, replaying each to stderr."""
    path = os.environ.get(ENV_LOG)
    if not path:
        return 0
    total = 0
    try:
        with _orig_open(path, "r", encoding="utf-8") as fh:
            fh.seek(offset)
            for line in fh:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue            # torn concurrent line: skip
                if (rec.get("kind") == "summary"
                        and rec.get("leaks", 0)
                        and str(rec.get("label", "")).startswith(
                            "replica:")):
                    total += int(rec["leaks"])
                    print(f"{label}: CHILD LEAK {rec['label']}: "
                          f"{rec['leaks']} leak(s), fd delta "
                          f"{rec.get('fd_delta', 0):+d}",
                          file=sys.stderr)
    except OSError:
        return 0
    return total


def check_and_report(label: str = "leaktrack") -> int:
    """End-of-run gate for the smokes: after drain/shutdown, report
    every attributed leak (tracked handle or thread) to stderr and the
    JSONL artifact, report the raw fd delta as context, and return the
    leak count (nonzero fails the smoke)."""
    got = leaks()
    n = len(got["tracked"]) + len(got["threads"])
    for rec in got["tracked"] + got["threads"]:
        kind = rec["kind"]
        what = rec.get("repr") or rec.get("name")
        print(f"{label}: LEAK {kind} {what} still open after "
              f"drain/shutdown\n--- created at:\n{rec['stack']}",
              file=sys.stderr)
        _emit({"label": label, **rec})
    _emit({"label": label, "kind": "summary", "leaks": n,
           "fd_delta": got["fd_delta"], "new_fds": got["new_fds"]})
    print(f"{label}: {n} leak(s), fd delta {got['fd_delta']:+d} "
          f"({'sanitizer on' if _enabled else 'sanitizer OFF'})",
          file=sys.stderr)
    return n


# -- selfcheck: a seeded fd leak ---------------------------------------------

def selfcheck_leak() -> Tuple[bool, str]:
    """Non-vacuity proof, run by ``graftcheck --selfcheck``: seed a
    socketpair leak (held open across the census) and demand it is
    caught with a creation stack; then close it and demand silence —
    a sanitizer that cannot fail is not a gate. Restores the global
    state it found."""
    global _snap
    was_enabled = _enabled
    saved_snap = _snap
    saved_tracked = list(_tracked.items())
    keep: List[Any] = []
    try:
        enable()
        snapshot()
        a, b = _socket_mod.socketpair()
        keep.extend((a, b))              # the "leak": refs held, no close
        got = leaks(grace_s=0.0)
        seeded = [r for r in got["tracked"] if r["kind"] == "socket"]
        if len(seeded) < 2:
            return False, (f"seeded socketpair leak NOT detected "
                           f"(got {len(seeded)} tracked sockets — "
                           f"sanitizer is vacuous)")
        if "selfcheck_leak" not in seeded[0]["stack"]:
            return False, "leak attributed to the wrong creation stack"
        a.close()
        b.close()
        clean = leaks(grace_s=0.0)
        if clean["tracked"]:
            return False, (f"closed twin still reported "
                           f"{len(clean['tracked'])} leak(s) "
                           f"(false positive)")
        return True, ("seeded socketpair leak detected with creation "
                      "stack; closed twin clean")
    finally:
        for s in keep:
            try:
                s.close()
            except OSError:
                pass
        reset()
        # a caller with a LIVE census (smoke-side in-process selfcheck)
        # gets its snapshot and tracked registry back — resetting them
        # would both false-positive on pre-existing threads and drop
        # real tracked leaks at its own check_and_report
        for obj, rec in saved_tracked:
            try:
                _tracked[obj] = rec
            except TypeError:
                pass
        _snap = saved_snap
        if not was_enabled:
            disable()


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m hivemall_tpu.testing.leaktrack",
        description="FD/socket/thread leak census sanitizer "
                    "(docs/STATIC_ANALYSIS.md)")
    ap.add_argument("--selfcheck", action="store_true",
                    help="prove the sanitizer catches a seeded fd leak "
                         "and passes its closed twin")
    args = ap.parse_args(argv)
    if args.selfcheck:
        ok, detail = selfcheck_leak()
        print(f"leaktrack --selfcheck: {detail}",
              file=sys.stderr if not ok else sys.stdout)
        return 0 if ok else 1
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
