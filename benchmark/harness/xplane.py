"""From a profiler trace (.xplane.pb) to device busy time, the operations
that took most of it, and the idle gaps by what the host was doing.

Device operations are the events of the "XLA Ops" line of each
`/device:` plane; on a CPU rehearsal, where operations run on host
threads, they are the host events that carry an `hlo_op` stat. Times are
nanoseconds from the start of the profiler session; `sync_offset_s`
carries them onto the host's perf_counter clock through the
`bench_sync` annotation the harness writes as the trace starts."""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SYNC_NAME = "bench_sync"
_SHAPE = re.compile(r"(?:pred|[a-z]+\d+)\[[\d,]*\]")
_HLO = re.compile(r"^%?([\w.\-]+) = \(?((?:pred|[a-z]+\d+)\[[\d,]*\])?")
# operations that only wrap others: their time is their children's
_WRAPPERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def start(trace_dir: str) -> float:
    """Start a profiler session writing under `trace_dir` (emptied first)
    and mark it with the sync annotation; returns the perf_counter instant
    of the mark."""
    import shutil
    import time
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    sync_perf = time.perf_counter()
    with jax.profiler.TraceAnnotation(SYNC_NAME):
        pass
    return sync_perf


def reduce_window(trace_dir: str, sync_perf: float,
                  window: Tuple[float, float],
                  host_spans: List[Tuple[str, float, float]]) -> dict:
    """`reduce` of the stopped session under `trace_dir`, with `window` and
    `host_spans` given on the perf_counter clock."""
    path = find_xplane(trace_dir)
    if path is None:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    raw = read(path)
    if raw["sync_s"] is None:
        raise RuntimeError(f"the trace holds no {SYNC_NAME} annotation")
    off = sync_perf - raw["sync_s"]               # trace clock -> perf
    return reduce(raw, (window[0] - off, window[1] - off),
                  [(n, s - off, d) for n, s, d in host_spans])


def _stat(event, *names):
    for k, v in event.stats:
        if k in names:
            return v
    return None


def op_name(text: str) -> str:
    """`fusion.49 f32[4194304,128]` from the HLO line a TPU trace names an
    operation by (`%fusion.49 = f32[4194304,128]{1,0:T(8,128)} fusion(...)`;
    of a tuple, the first shape); other names pass through."""
    m = _HLO.match(text)
    if not m:
        return text
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def is_wrapper(name: str) -> bool:
    return name.lstrip("%").startswith(_WRAPPERS)


def read(path: str) -> dict:
    """{"planes": {plane: [(start_s, dur_s, name, module)]}, "sync_s": float
    or None} with one entry per device (or, on the CPU, one for all host
    threads that ran operations)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes: Dict[str, list] = {}
    sync = None
    for plane in data.planes:
        device = plane.name.startswith("/device:") \
            and "CUSTOM" not in plane.name.upper()
        for line in plane.lines:
            if device:
                if line.name != "XLA Ops":
                    continue
                out = planes.setdefault(plane.name, [])
                for e in line.events:
                    out.append((e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                op_name(e.name), ""))
                continue
            for e in line.events:
                if e.name == SYNC_NAME and sync is None:
                    sync = e.start_ns * 1e-9
                elif not plane.name.startswith("/device:") \
                        and e.duration_ns > 0 \
                        and _stat(e, "hlo_op") is not None:
                    planes.setdefault("/host:CPU", []).append(
                        (e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         op_name(e.name), str(_stat(e, "hlo_module") or "")))
    return {"planes": planes, "sync_s": sync}


def busy_intervals(events: list) -> List[Tuple[float, float]]:
    """Union of [start, end) over one plane's operations."""
    out: List[Tuple[float, float]] = []
    for s, d, *_ in sorted(events):
        e = s + d
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(trace: dict, window: Tuple[float, float],
           host_spans: List[Tuple[str, float, float]],
           top: int = 10) -> dict:
    """`window` and `host_spans` [(name, start_s, dur_s)] are on the
    trace's clock. Busy seconds are averaged over the device planes; the
    operation table is summed over them; the gaps are the first plane's,
    each charged to the host span that covers most of it (`no_span` where
    none does)."""
    w0, w1 = window
    planes = trace["planes"]
    if not planes:
        return {"busy_s": 0.0, "window_s": w1 - w0, "n_planes": 0,
                "ops": {}, "device_ops": [], "idle_gaps": []}
    busy, ops = [], {}
    gaps: Dict[str, float] = {}
    for i, (name, events) in enumerate(sorted(planes.items())):
        inside = [ev for ev in events if ev[0] + ev[1] > w0 and ev[0] < w1]
        iv = [(max(s, w0), min(e, w1)) for s, e in busy_intervals(inside)]
        busy.append(sum(e - s for s, e in iv))
        for s, d, op, _ in inside:
            if not is_wrapper(op):
                ops[op] = ops.get(op, 0.0) + min(s + d, w1) - max(s, w0)
        if i:
            continue
        edges = [w0] + [t for s, e in iv for t in (s, e)] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 <= 0:
                continue
            best, cover = "no_span", 0.0
            for sname, s, d in host_spans:
                c = min(g1, s + d) - max(g0, s)
                if c > cover:
                    best, cover = sname, c
            if cover < 0.5 * (g1 - g0):
                best = "no_span"
            gaps[best] = gaps.get(best, 0.0) + (g1 - g0)
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])
    return {"busy_s": sum(busy) / len(busy), "window_s": w1 - w0,
            "n_planes": len(busy), "ops": ops,
            "device_ops": [[k.replace(" ", "_"), v] for k, v in ranked[:top]],
            "idle_gaps": [[k, v] for k, v in
                          sorted(gaps.items(), key=lambda kv: -kv[1])[:top]]}
