"""The host feed's account of a traced window, thread by thread, from the
program's own spans (`program_trace.program_spans`: name, start, length,
and in `args` the thread, the parent, the ordinals and `cpu`, the
thread-CPU microseconds of the span).

The feed is every thread but the one that dispatches. Two kinds:

- a LOOP thread (`h2d-prefetch`, `ingest-source`) runs one loop from the
  stream's start to its end and blocks only inside its wait spans
  (`WAITS`), so it is tiled: window = waits + top-level work spans +
  remainder, and the remainder is by construction the program's own
  Python between spans, or the thread waiting for the GIL there. It is
  busy for its window less its waits;
- a SERVICE thread (`pq-decode_N`, `ingest_N`, `pairs-track`) sleeps on a
  queue between jobs with no span: it is busy for its top-level spans.

A thread's window runs from the window's start to the end of its own last
span: the feed stops when the harness tells it to, and the window itself
only when every dispatch in flight has retired (seconds later where many
are in flight), and a thread that has ended is not busy. How much of the
stream passed a thread in its window is read off its spans' own ordinals,
in dispatches: the range of `batch` over the batches a dispatch fuses
(a pool worker sees every n-th batch and the whole range), else the range
of `seq`, else the `rows` of the shards it decoded over a dispatch's rows.
A thread's cycle is its busy seconds over those dispatches: its busy
milliseconds a dispatch. Where the largest cycle meets the device's time a
dispatch (`steps_per_dispatch` x `step.device_ms`), the cell turns
host-bound. That is a thread's view. The feed is a chain (a thread also
waits for the one before it, and with one shard decoded ahead the two do
not overlap fully), so a loop thread also has `free_ms`: its window less
its waits for a SLOT only, a dispatch: what a dispatch takes it when
nothing downstream holds it up. On the thread that stages, in a window
without a loss fold, that is the feed's own period.

A span cut by the window's start counts for its part inside, its CPU
seconds in proportion. Without `cpu` on any span (a program from before the
spans had that clock, which also lacks the wait spans that tile a thread)
there is no account: None."""

from __future__ import annotations

from typing import Dict, List, Optional

from . import program_trace

WAITS = ("source.wait_shard", "feed.wait_slot", "ingest.wait_prep",
         "ingest.wait_slot")
# of those, the waits for the stage DOWNSTREAM to take what is ready
SLOT_WAITS = ("feed.wait_slot", "ingest.wait_slot")
LOOP_THREADS = ("h2d-prefetch", "ingest-source")
DISPATCHES = ("dispatch.megastep", "dispatch.step")
# work that blocks by design (the transfer is waited for in the span):
# left out of a thread's off-CPU share
BLOCKING_WORK = ("h2d.stage",)


def _dispatches(ords: dict, batches_per_dispatch: int,
                rows_per_dispatch: int) -> float:
    for key, per in (("batch", batches_per_dispatch), ("seq", 1)):
        if ords[key]:
            return (max(ords[key]) - min(ords[key]) + 1) / per
    return ords["rows"] / rows_per_dispatch


def accounts(spans: List[dict], t0: float, batches_per_dispatch: int,
             rows_per_dispatch: int) -> Optional[Dict[str, dict]]:
    """{thread: account} over `spans` (all ending inside the window that
    began at `t0`). An account: `kind`, `window_s`, `dispatches` (of the
    stream, passed in that window), `wait_s`, `work_s` (top-level spans
    that are no waits), `remainder_s` and `free_ms` (loop threads),
    `busy_s`, `cycle_ms`, `work_cpu_s` / `work_wall_s` (top level,
    `BLOCKING_WORK` left out), and `spans`: {name: {"n", "wall_s",
    "cpu_s"}} of its top-level spans."""
    if not any("cpu" in s["args"] for s in spans):
        return None
    dispatching = {s["args"].get("thread") for s in spans
                   if s["name"] in DISPATCHES}
    threads: Dict[str, dict] = {}
    for s in spans:
        thread, args = s["args"].get("thread"), s["args"]
        if thread in dispatching or "parent" in args:
            continue
        start = max(s["start"], t0)
        wall = s["start"] + s["dur"] - start
        cpu = args.get("cpu", 0.0) * 1e-6 \
            * (wall / s["dur"] if s["dur"] else 1.0)
        acc = threads.setdefault(thread, {
            "end": t0, "spans": {},
            "ords": {"batch": set(), "seq": set(), "rows": 0}})
        acc["end"] = max(acc["end"], start + wall)
        for key in ("batch", "seq"):
            if args.get(key) is not None:
                acc["ords"][key].add(args[key])
        acc["ords"]["rows"] += args.get("rows", 0)
        by = acc["spans"].setdefault(s["name"],
                                     {"n": 0, "wall_s": 0.0, "cpu_s": 0.0})
        by["n"] += 1
        by["wall_s"] += wall
        by["cpu_s"] += cpu
    for thread, acc in threads.items():
        loop = thread.startswith(LOOP_THREADS)
        window = acc.pop("end") - t0
        n = _dispatches(acc.pop("ords"), batches_per_dispatch,
                        rows_per_dispatch)
        by = acc["spans"]
        wait = sum(v["wall_s"] for k, v in by.items() if k in WAITS)
        slot = sum(v["wall_s"] for k, v in by.items() if k in SLOT_WAITS)
        work = sum(v["wall_s"] for k, v in by.items() if k not in WAITS)
        busy = window - wait if loop else work
        own = [v for k, v in by.items()
               if k not in WAITS and k not in BLOCKING_WORK]
        acc.update(kind="loop" if loop else "service", window_s=window,
                   dispatches=n, wait_s=wait, work_s=work, busy_s=busy,
                   remainder_s=window - wait - work if loop else None,
                   free_ms=1e3 * (window - slot) / n if loop and n else None,
                   cycle_ms=1e3 * busy / n if n else 0.0,
                   work_wall_s=sum(v["wall_s"] for v in own),
                   work_cpu_s=sum(v["cpu_s"] for v in own))
    return threads or None


def window_accounts(ctx: dict) -> Optional[Dict[str, dict]]:
    if ctx.get("job") != "stream":
        return None
    w = ctx["window"]
    return accounts(program_trace.program_spans(ctx), w["t0"],
                    w["steps_per_dispatch"],
                    w["steps_per_dispatch"] * w["batch"])


def _busiest(acc: Optional[Dict[str, dict]], kind: Optional[str] = None
             ) -> Optional[dict]:
    threads = [t for t in (acc or {}).values()
               if kind is None or t["kind"] == kind]
    return max(threads, key=lambda t: t["cycle_ms"]) if threads else None


def cycle_ms(ctx: dict) -> Optional[float]:
    """The busiest feed thread's busy milliseconds a dispatch."""
    t = _busiest(window_accounts(ctx))
    return t["cycle_ms"] if t else None


def unattributed_share(ctx: dict) -> Optional[float]:
    """On the busiest LOOP thread: the share of its busy time that none
    of its spans covers."""
    t = _busiest(window_accounts(ctx), "loop")
    return 100.0 * t["remainder_s"] / t["busy_s"] \
        if t and t["busy_s"] > 0 else None


def offcpu_share(ctx: dict) -> Optional[float]:
    """On the thread `cycle_ms` chose: the share of its top-level work
    spans' wall time that the thread was not on a CPU (`h2d.stage`, which
    waits for its transfer by design, left out)."""
    t = _busiest(window_accounts(ctx))
    return 100.0 * (1.0 - t["work_cpu_s"] / t["work_wall_s"]) \
        if t and t["work_wall_s"] > 0 else None


def pairs_track_ms(ctx: dict) -> Optional[float]:
    """Mean length of `pairs.track` (FFM's pair tracker, one span a
    batch on its own thread) in the window."""
    if ctx.get("job") != "stream":
        return None
    durs = [s["dur"] for s in program_trace.program_spans(ctx)
            if s["name"] == "pairs.track"]
    return 1e3 * sum(durs) / len(durs) if durs else None
