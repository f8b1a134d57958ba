"""``hivemall_tpu obs <metrics.jsonl>`` — live-run summary off the stream.

Tails/aggregates a jsonl metrics file (the ``HIVEMALL_TPU_METRICS`` sink)
into a terminal summary: event counts, current training rate, the
per-stage span breakdown (from the latest ``span_rollup``), MIX breaker
state and checkpoint age (from the latest registry snapshot carried by
``telemetry`` / ``train_done`` events), and metrics-stream health
(dropped events, rotations). ``--follow`` re-renders as the file grows —
the poor ops engineer's ``watch`` for a soak run.

Robustness contract: a metrics file from a live (or crashed) run may end
in a torn line and may interleave events from several trainers;
unparsable lines are counted, never fatal. Follow mode is built for
soaks: each tick reads only the appended bytes and folds them into
BOUNDED incremental aggregates (counts + newest record per event type) —
memory and per-tick work stay O(1) in the file's history.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

__all__ = ["load_events", "summarize", "render_file", "render_slo",
           "render_slo_source", "parse_since"]


def parse_since(v) -> Optional[float]:
    """The shared ``--since`` grammar (``obs`` and ``obs postmortem``):
    values under 1e9 are "seconds ago" (``--since 300`` = the last five
    minutes), larger values are an absolute epoch timestamp."""
    if v is None:
        return None
    s = float(v)
    # event `ts` fields are wall-clock epoch by schema; a relative
    # --since can only anchor against wall "now"
    return time.time() - s if s < 1e9 else s  # graftcheck: disable=GC02


class _TailState:
    """Bounded aggregates over a stream of events: per-event counts,
    the newest record per event type, the newest registry snapshot, the
    ts range, and the unparsable-line count. Everything the renderer
    needs, in O(1) memory."""

    def __init__(self, since: Optional[float] = None):
        self.counts: Dict[str, int] = {}
        self.last: Dict[str, dict] = {}
        self.snapshot: Optional[dict] = None
        self.t_lo: Optional[float] = None
        self.t_hi: Optional[float] = None
        self.bad = 0
        self.total = 0
        self.since = since

    def add(self, rec: dict) -> None:
        name = rec["event"]
        ts0 = rec.get("ts")
        if self.since is not None and isinstance(ts0, (int, float)) \
                and ts0 < self.since:
            return                       # --since: before the window
        self.total += 1
        self.counts[name] = self.counts.get(name, 0) + 1
        self.last[name] = rec
        ts = rec.get("ts")
        if isinstance(ts, (int, float)):
            self.t_lo = ts if self.t_lo is None else min(self.t_lo, ts)
            self.t_hi = ts if self.t_hi is None else max(self.t_hi, ts)
        if name == "telemetry" and isinstance(rec.get("snapshot"), dict):
            self.snapshot = rec["snapshot"]
        elif name == "train_done" and isinstance(rec.get("telemetry"),
                                                 dict):
            self.snapshot = rec["telemetry"]

    def feed_lines(self, raw: bytes) -> None:
        """Fold the complete jsonl lines in ``raw`` into the aggregates;
        unparsable lines are counted in ``bad``."""
        for line in raw.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                self.bad += 1
                continue
            if isinstance(rec, dict) and "event" in rec:
                self.add(rec)
            else:
                self.bad += 1


def load_events(path: str) -> Tuple[List[dict], int]:
    """All parsable events in ``path`` plus the count of unparsable lines
    (torn tail of a live run, partial writes after a crash)."""
    events: List[dict] = []
    bad = 0
    with open(path, "rb") as f:
        for line in f.read().splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                bad += 1
                continue
            if isinstance(rec, dict) and "event" in rec:
                events.append(rec)
            else:
                bad += 1
    return events, bad


def _fmt_s(v: float) -> str:
    return f"{v * 1e3:.2f}ms" if v < 1.0 else f"{v:.3f}s"


def _render(state: _TailState, path: str = "",
            now: Optional[float] = None) -> str:
    now = time.time() if now is None else now
    if not state.total:
        return (f"obs: {path or 'stream'}: no parsable events"
                + (f" ({state.bad} unparsable lines)" if state.bad else ""))
    out: List[str] = []
    span_s = 0.0
    if state.t_lo is not None and state.t_hi is not None:
        span_s = max(0.0, state.t_hi - state.t_lo)
    head = (f"obs: {path or 'stream'} — {state.total} events over "
            f"{span_s:.1f}s")
    if state.bad:
        head += f" ({state.bad} unparsable lines)"
    out.append(head)
    out.append("events: " + ", ".join(
        f"{k} x{v}" for k, v in sorted(state.counts.items())))

    # newest progress record wins: a finished run's train_done carries the
    # final step/examples, a live run only has train_step so far
    candidates = [r for r in (state.last.get("train_step"),
                              state.last.get("train_done")) if r]
    step = max(candidates, key=lambda r: r.get("step", 0), default=None)
    if step is not None:
        line = (f"train:  {step.get('trainer', '?')} step {step.get('step')}"
                f"  examples {step.get('examples')}")
        if "examples_per_sec" in step:
            line += f"  rate {step['examples_per_sec']}/s"
        if "avg_loss" in step:
            line += f"  avg_loss {step['avg_loss']}"
        if state.counts.get("train_done"):
            line += "  [done]"
        out.append(line)

    roll = state.last.get("span_rollup")
    snap = state.snapshot
    tail = (snap or {}).get("train") or {}
    if tail.get("tail_distinct_steps") or tail.get("tail_dense_steps"):
        # which tail the minibatch step took (ops/fm.py rows_update), on
        # how many steps the gather in front read through the batch's
        # distinct rows too (gather_rows), on how many the tail's
        # scatter-add summed them in fast memory in one pass, and, of a
        # step over a mesh's blocks, on how many the slab crossed the mesh
        # as the blocks' compact tables (ffm_slab_over_blocks)
        steps = tail["tail_distinct_steps"] + tail["tail_dense_steps"]
        line = (f"tail:   distinct-row x{tail['tail_distinct_steps']}  "
                f"dense x{tail['tail_dense_steps']}  distinct rows/step "
                f"{tail.get('distinct_rows', 0) / max(1, steps):.0f}  "
                f"gather on the distinct rows x"
                f"{tail.get('gather_compact_steps', 0)}  "
                f"scatter in fast memory x"
                f"{tail.get('scatter_compact_steps', 0)}")
        if "exchange_compact_steps" in tail:
            line += (f"  exchange of compact tables x"
                     f"{tail['exchange_compact_steps']}")
        out.append(line)
    stages = (roll or {}).get("stages") \
        or ((snap or {}).get("spans") if snap else None)
    if stages:
        # the spans section carries one scalar beside the stage dicts
        # (`dropped`, the ring-overflow counter) — filter to real stages
        stages = {n: s for n, s in stages.items() if isinstance(s, dict)}
    if stages:
        total = sum(s.get("total_s", 0.0) for s in stages.values()) or 1.0
        out.append("stages (latest rollup):")
        width = max(len(n) for n in stages)
        for name in sorted(stages,
                           key=lambda n: -stages[n].get("total_s", 0.0)):
            s = stages[name]
            out.append(
                f"  {name:<{width}}  count {s.get('count', 0):>7}  "
                f"total {_fmt_s(s.get('total_s', 0.0)):>9}  "
                f"cpu {_fmt_s(s.get('cpu_s', 0.0)):>9}  "
                f"p50 {_fmt_s(s.get('p50', 0.0)):>9}  "
                f"p99 {_fmt_s(s.get('p99', 0.0)):>9}  "
                f"({100.0 * s.get('total_s', 0.0) / total:4.1f}%)")
        dropped = ((snap or {}).get("spans") or {}).get("dropped")
        if isinstance(dropped, int) and dropped > 0:
            out.append(f"  (span ring overflowed: {dropped} spans dropped)")

    dp = (snap or {}).get("devprof") or {}
    if dp.get("compiles") or dp.get("active"):
        line = (f"profile: compiles {dp.get('compiles', 0)} "
                f"({_fmt_s(dp.get('compile_seconds', 0.0))})"
                f"  retraces {dp.get('retraces', 0)}")
        builds = dp.get("builds") or {}
        if builds:
            n_builds = sum(b.get("count", 0) for b in builds.values()
                           if isinstance(b, dict))
            line += f"  builds {n_builds} ({len(builds)} factories)"
        if dp.get("shape_buckets"):
            line += f"  buckets {dp['shape_buckets']}"
        out.append(line)
        mem = dp.get("memory") or {}
        if mem.get("live_bytes") or mem.get("bytes_in_use"):
            line = (f"memory: live {mem.get('live_bytes', 0) / 1e6:.1f}MB "
                    f"in {mem.get('live_arrays', 0)} arrays")
            if mem.get("bytes_in_use"):
                line += f"  in_use {mem['bytes_in_use'] / 1e6:.1f}MB"
            if dp.get("peak_dispatch_bytes"):
                line += (f"  dispatch_peak "
                         f"{dp['peak_dispatch_bytes'] / 1e6:.1f}MB")
            out.append(line)
        drift = dp.get("drift") or {}
        if drift.get("train_events") or drift.get("mem_events"):
            out.append(f"drift:  train x{drift.get('train_events', 0)}  "
                       f"mem x{drift.get('mem_events', 0)}")

    if snap:
        mix = snap.get("mix") or {}
        if mix.get("active"):
            out.append(
                f"mix:    breaker {mix.get('breaker_state', '?')}"
                f"  exchanges {mix.get('exchanges', 0)}"
                f"  dropped {mix.get('dropped_exchanges', 0)}"
                f"  transport_errors {mix.get('transport_errors', 0)}"
                f"  alive {mix.get('alive')}")
        ms = snap.get("metrics_stream") or {}
        if ms:
            out.append(f"stream: dropped_events {ms.get('dropped_events', 0)}"
                       f"  rotations {ms.get('rotations', 0)}")

    ck = state.last.get("checkpoint")
    if ck is not None:
        # event `ts` fields are wall-clock by schema (cross-process jsonl
        # merge); diffing against wall "now" is the only coherent read
        age = now - ck.get("ts", now)  # graftcheck: disable=GC02
        where = ck.get("path", "?")
        at = (f"step {ck['step']}" if "step" in ck
              else f"epoch {ck.get('epoch', '?')}")
        out.append(f"ckpt:   last at {at}, {age:.1f}s ago -> {where}")

    # promotion control plane (docs/RELIABILITY.md "Promotion and
    # rollback"): the registry section when a snapshot carries one, plus
    # the newest gate/rollback events from the stream itself
    promo = (snap or {}).get("promotion") or {}
    has_events = any(state.counts.get(e) for e in
                     ("promotion", "promotion_gate", "promotion_rollback",
                      "retrain_wanted"))
    if promo.get("configured") or has_events:
        line = (f"promo:  step {promo.get('promoted_step', '?')} "
                f"[{promo.get('state', '?')}]"
                f"  gate {promo.get('gate_passes', 0)} pass"
                f"/{promo.get('gate_failures', 0)} fail"
                f"  promotions {promo.get('promotions', 0)}"
                f"  rollbacks {promo.get('rollbacks', 0)}"
                f"  retrain_wanted {promo.get('retrain_wanted', 0)}"
                f"/acked {promo.get('retrain_acked', 0)}")
        canary = promo.get("canary") or {}
        if canary.get("active"):
            line += (f"  [canary step {canary.get('step')} x"
                     f"{canary.get('cohort')} baking "
                     f"{canary.get('age_seconds')}s]")
        out.append(line)
        g = state.last.get("promotion_gate")
        if g is not None:
            line = (f"  gate:  {g.get('verdict', '?')} "
                    f"{g.get('bundle', '?')} (step {g.get('step')})")
            if g.get("reasons"):
                line += f" — {g['reasons'][0]}"
            out.append(line)
        rb = state.last.get("promotion_rollback")
        if rb is not None:
            out.append(f"  rollback: {rb.get('bundle', '?')} — "
                       f"{rb.get('reason', '?')}")

    # retrain autopilot (docs/RELIABILITY.md "Autonomous retraining"):
    # the registry section when a snapshot carries one, plus the newest
    # state-transition event from the stream
    rt = (snap or {}).get("retrain") or {}
    if rt.get("configured") or state.counts.get("retrain"):
        line = (f"retrain: [{rt.get('state', '?')}]"
                f"  attempts {rt.get('attempts', 0)}"
                f"  ok {rt.get('successes', 0)}"
                f"  rejected {rt.get('rejections', 0)}"
                f"  rollbacks {rt.get('rollbacks', 0)}"
                f"  flaps {rt.get('flaps', 0)}"
                f"  votes {rt.get('votes_seen', 0)}"
                f"/acked {rt.get('votes_acked', 0)}")
        rp = rt.get("replay") or {}
        if rp.get("rows"):
            line += (f"  replay {rp.get('rows', 0)} rows/"
                     f"{rp.get('segments', 0)} seg")
        out.append(line)
        ev = state.last.get("retrain")
        if ev is not None and (ev.get("reason") or ev.get("outcome")):
            out.append(f"  last: {ev.get('outcome') or ev.get('state')}"
                       f" — {ev.get('reason', '?')}")

    # bulk offline scoring (docs/PERFORMANCE.md "Bulk scoring"): a live
    # job's registry section when a snapshot carries one; otherwise the
    # newest `bulk` stream event (the job emits its section per shard)
    bk = (snap or {}).get("bulk") or {}
    if not (bk.get("active") or bk.get("rows_scored")):
        bk = state.last.get("bulk") or bk
    if bk.get("active") or bk.get("rows_scored"):
        out.append(
            f"bulk:   [{'scoring' if bk.get('active') else 'done'}]"
            f"  shards {bk.get('shards_done', 0)}"
            f"/{bk.get('shards_total', 0)}"
            f"  rows {bk.get('rows_scored', 0)}"
            f"  rate {bk.get('rows_per_sec', 0)}/s"
            f"  backend {bk.get('backend') or '?'}"
            f"/{bk.get('precision') or '?'}"
            f"  workers {bk.get('workers', 0)}"
            f" util {bk.get('worker_utilization', 0)}")

    # black-box flight recorder (docs/OBSERVABILITY.md "Flight
    # recorder"): the ring's self-census when a snapshot carries one
    fli = (snap or {}).get("flight") or {}
    if fli.get("enabled") or fli.get("events"):
        out.append(
            f"flight: [{'recording' if fli.get('enabled') else 'closed'}]"
            f"  events {fli.get('events', 0)}"
            f"  dropped {fli.get('dropped', 0)}"
            f"  truncated {fli.get('truncated', 0)}"
            f"  util {fli.get('utilization', 0.0)}"
            f"  ring {fli.get('path') or '?'}")
    return "\n".join(out)


def summarize(events: List[dict], bad: int = 0, path: str = "",
              now: Optional[float] = None,
              since: Optional[float] = None) -> str:
    """Render the summary text for one loaded event list."""
    state = _TailState(since=since)
    for rec in events:
        state.add(rec)
    state.bad = bad
    return _render(state, path=path, now=now)


class _FollowTail:
    """One incremental follow of a metrics jsonl path: each :meth:`tick`
    folds only the bytes appended since the last tick into the bounded
    aggregates and returns the re-rendered summary (or None when nothing
    new landed). Factored out of :func:`render_file` so the
    rotation-under-follow contract is testable without driving a thread
    through the sleep loop.

    Rotation contract (``HIVEMALL_TPU_METRICS_MAX_MB``): when
    ``MetricsStream._rotate`` replaces ``<path>`` with a FRESH file (the
    old generation moves to ``<path>.1``), the tail detects the inode
    change and REOPENS ``<path>`` from offset 0 — it never opens
    ``<path>.1``, so rotated-away history is not replayed into the
    aggregates (events already folded stay folded; a generation rotated
    fully away between ticks is lost, by design). A bare truncation
    (same inode, smaller size) likewise restarts from the head. A stat
    or open that lands in the replace window (file briefly absent)
    retries next tick."""

    def __init__(self, path: str, since: Optional[float] = None):
        self.path = path
        self.state = _TailState(since=since)
        self._offset = 0
        self._ino: Optional[int] = None

    def tick(self) -> Optional[str]:
        try:
            st = os.stat(self.path)
        except FileNotFoundError:
            # rotation window: MetricsStream._rotate has os.replace'd
            # the file and not yet re-opened it — retry next tick
            return None
        size = st.st_size
        # rotation = a FRESH file replaced the tailed one (inode change —
        # size alone can't tell when the new file already grew past the
        # old offset) or in-place truncation: restart from the head.
        if self._ino is None:
            self._ino = st.st_ino
        elif st.st_ino != self._ino:
            self._ino, self._offset = st.st_ino, 0
        if size < self._offset:
            self._offset = 0
        if size <= self._offset:
            return None
        try:
            with open(self.path, "rb") as f:
                f.seek(self._offset)
                data = f.read()
        except FileNotFoundError:        # rotated between stat and open
            return None
        nl = data.rfind(b"\n")
        if nl < 0:                       # complete lines only; the torn
            return None                  # tail waits for its newline
        self._offset += nl + 1
        self.state.feed_lines(data[:nl + 1])
        return _render(self.state, path=self.path)


def render_file(path: str, follow: bool = False,
                interval: float = 2.0,
                since: Optional[float] = None) -> int:
    """Print the summary for ``path``; with ``follow`` re-render whenever
    the file grows (Ctrl-C exits). Returns a process exit code.

    Follow mode tails INCREMENTALLY via :class:`_FollowTail`: each tick
    reads only the appended bytes, folds them into the bounded
    aggregates, and defers a partial trailing line — a record mid-write
    is read whole on the next tick, never counted as torn. A file
    replaced by ``HIVEMALL_TPU_METRICS_MAX_MB`` rotation is reopened
    from its head without replaying ``<path>.1``."""
    if not os.path.exists(path):
        print(f"obs: {path}: no such file", file=sys.stderr)
        return 1
    if not follow:
        events, bad = load_events(path)
        print(summarize(events, bad, path=path, since=since))
        return 0
    tail = _FollowTail(path, since=since)
    try:
        while True:
            out = tail.tick()
            if out is not None:
                print(out)
                print("-" * 60)
            time.sleep(max(0.1, interval))
    except KeyboardInterrupt:
        return 0


# --- serving SLO report (docs/OBSERVABILITY.md "Serving traces and SLOs")


def render_slo(slo: dict, source: str = "") -> str:
    """Human rendering of a serve/router ``/slo`` payload: targets, the
    per-window burn-rate table, and recent drift events."""
    t = slo.get("targets") or {}
    out = [f"slo: {source or 'serving'} — targets: "
           f"p99 <= {t.get('p99_ms', '?')}ms, "
           f"availability >= {t.get('availability', '?')}"
           f"  ({slo.get('samples', 0)} samples)"]
    wins = slo.get("windows") or {}
    if not wins:
        out.append("  no samples yet")
    for name in sorted(wins, key=lambda k: wins[k].get("seconds", 0)):
        w = wins[name]
        p99 = w.get("p99_ms")
        out.append(
            f"  {name:>3}: qps {w.get('qps', 0):>8}  "
            f"avail {w.get('availability', 1.0):.6f} "
            f"(burn {w.get('availability_burn_rate', 0.0):g}x)  "
            f"p99 {('%.1fms' % p99) if p99 is not None else '—':>9}  "
            f"over-slo {100.0 * w.get('frac_over_slo', 0.0):.2f}% "
            f"(burn {w.get('latency_burn_rate', 0.0):g}x)")
    sc = slo.get("score")
    if sc:
        out.append(f"  score: mean {sc.get('mean')}  std {sc.get('std')}")
    dr = slo.get("drift") or {}
    out.append(f"  drift: latency x{dr.get('latency_events', 0)}  "
               f"score x{dr.get('score_events', 0)}  "
               f"retrain_wanted x{dr.get('retrain_wanted', 0)} "
               f"(acked x{dr.get('retrain_acked', 0)})")
    for ev in (dr.get("recent") or [])[-4:]:
        out.append(f"    [{ev.get('series')}] change "
                   f"{ev.get('change_score')} at value {ev.get('value')} "
                   f"(ts {ev.get('ts')})")
    return "\n".join(out)


def _fetch_slo(source: str) -> dict:
    if source.startswith(("http://", "https://")):
        import urllib.request
        url = source.rstrip("/")
        if not url.endswith("/slo"):
            url += "/slo"
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.loads(resp.read())
    with open(source, "rb") as f:
        return json.loads(f.read())


def render_slo_source(source: str, follow: bool = False,
                      interval: float = 2.0) -> int:
    """``hivemall_tpu obs --slo <url-or-file>``: fetch and render the SLO
    report; ``--follow`` re-renders on the poll interval."""
    try:
        print(render_slo(_fetch_slo(source), source=source))
    except (OSError, ValueError) as e:
        print(f"obs --slo: {source}: {e}", file=sys.stderr)
        return 1
    if not follow:
        return 0
    try:
        while True:
            time.sleep(max(0.1, interval))
            try:
                print("-" * 60)
                print(render_slo(_fetch_slo(source), source=source))
            except (OSError, ValueError) as e:
                print(f"obs --slo: {source}: {e}", file=sys.stderr)
    except KeyboardInterrupt:
        return 0
