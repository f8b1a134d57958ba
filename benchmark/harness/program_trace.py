"""What the program says about itself in a traced run, for the readers
under layer_metrics/ that need more than `ctx` carries:

- the phase scope of every device operation (`hm.gather`, `hm.grad`,
  `hm.scatter`, `hm.update`; the program wraps its step's phases in
  `jax.named_scope`). The trace itself records the name stack each
  operation was traced under (`jit(megastep)/hm.scan/while/body/
  closed_call/hm.gather/gather`): on a device plane as the `tf_op` stat
  of the operation's event METADATA; for operations that ran on the host
  (a CPU rehearsal), in the module's HLO, which the profiler writes into
  the plane `/host:metadata` (`metadata.op_name` of each instruction).
  A fusion is counted as the phase of the instruction the compiler names
  it after (the fusion's own name stack), whole; an operation the
  compiler made itself carries no name stack and is unscoped.
  `ProfileData` shows an event's own stats and neither of these, so the
  protobuf is read directly, with the few fields needed declared here.
- the program tracer's spans with their `args` (`seq`, `batch`, `thread`,
  `parent`), from its Chrome export; the harness's `ctx["spans"]` keeps
  name, start and length only.
- both on one clock: an enabled tracer mirrors each span into the live
  profiler session as an annotation of the same name, so the trace file
  holds the program's spans beside the device's module runs.

`ctx` carries neither the cell's name nor the trace's directory, so the
trace read is the newest `*.xplane.pb` under `benchmark/.run/*/trace/`
(one process runs one cell, and `xplane.start` empties the directory
first). With a program that has no scopes, args or mirrored spans, as the
parent of the PR that added this file, everything here returns None."""

from __future__ import annotations

import functools
import glob
import os
import re
import statistics
import time
from typing import Dict, List, Optional, Tuple

from . import common, xplane

PHASES = ("hm.gather", "hm.grad", "hm.scatter", "hm.update")
_SCOPE = re.compile(r"^hm\.\w+$")
SOURCE_SPANS = ("source.assemble", "source.note_batch",
                "source.convert_labels")


# -- the trace file, read as a protobuf ---------------------------------------

def _field(msg, name, number, kind, label=1, type_name=None):
    f = msg.field.add()
    f.name, f.number, f.type, f.label = name, number, kind, label
    if type_name:
        f.type_name = ".hm_xplane." + type_name


@functools.lru_cache(maxsize=None)
def _messages() -> dict:
    """Message classes for the parts of xplane.proto and hlo.proto read
    here (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto:
    field numbers are theirs; fields left out are skipped by the parser).
    A map is read as what it is on the wire, repeated key/value entries."""
    from google.protobuf import descriptor_pb2 as d
    from google.protobuf import descriptor_pool, message_factory
    I64, U64, DBL, STR, BYT, MSG = 3, 4, 1, 9, 12, 11
    fd = d.FileDescriptorProto(name="hm_xplane.proto", package="hm_xplane",
                               syntax="proto3")
    spec = {
        "XSpace": [("planes", 1, MSG, 3, "XPlane")],
        "XPlane": [("name", 2, STR), ("lines", 3, MSG, 3, "XLine"),
                   ("event_metadata", 4, MSG, 3, "EventMetadataEntry"),
                   ("stat_metadata", 5, MSG, 3, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, I64),
                               ("value", 2, MSG, 1, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, I64),
                              ("value", 2, MSG, 1, "XStatMetadata")],
        "XLine": [("name", 2, STR), ("timestamp_ns", 3, I64),
                  ("events", 4, MSG, 3, "XEvent")],
        "XEvent": [("metadata_id", 1, I64), ("offset_ps", 2, I64),
                   ("duration_ps", 3, I64), ("stats", 4, MSG, 3, "XStat")],
        "XStat": [("metadata_id", 1, I64), ("double_value", 2, DBL),
                  ("uint64_value", 3, U64), ("int64_value", 4, I64),
                  ("str_value", 5, STR), ("bytes_value", 6, BYT),
                  ("ref_value", 7, U64)],
        "XEventMetadata": [("name", 2, STR), ("stats", 5, MSG, 3, "XStat")],
        "XStatMetadata": [("name", 2, STR)],
        "HloProto": [("hlo_module", 1, MSG, 1, "HloModule")],
        "HloModule": [("name", 1, STR),
                      ("computations", 3, MSG, 3, "HloComputation")],
        "HloComputation": [("instructions", 2, MSG, 3, "HloInstruction")],
        "HloInstruction": [("name", 1, STR),
                           ("metadata", 7, MSG, 1, "OpMetadata")],
        "OpMetadata": [("op_name", 2, STR)],
    }
    for name, fields in spec.items():
        msg = fd.message_type.add(name=name)
        for f in fields:
            _field(msg, *f)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName("hm_xplane." + name)) for name in spec}


def newest_xplane() -> Optional[str]:
    found = glob.glob(os.path.join(common.RUN_DIR, "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def read_space(path: str):
    space = _messages()["XSpace"]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _stat_value(stat, names: dict):
    """The one value an XStat holds (a `oneof` in xplane.proto, declared
    flat here: whichever is set; a zero reads as 0)."""
    for kind in ("str_value", "int64_value", "uint64_value", "double_value",
                 "bytes_value"):
        v = getattr(stat, kind)
        if v:
            return v
    return names.get(stat.ref_value) if stat.ref_value else 0


def _is_device(plane) -> bool:
    return plane.name.startswith("/device:") \
        and "CUSTOM" not in plane.name.upper()


# -- phase scopes -------------------------------------------------------------

def op_paths(path: str) -> Dict[str, str]:
    """{instruction name: the name stack it was traced under}. From the
    HLO of every module the trace holds (where two modules use one name
    the larger module's wins: the step's program dwarfs the helpers),
    then from the device planes' own `tf_op`, which wins where both
    speak."""
    msgs = _messages()
    modules = []
    device: Dict[str, str] = {}
    for plane in read_space(path).planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            for stat in entry.value.stats:
                kind = names.get(stat.metadata_id)
                if kind == "Hlo Proto" and plane.name == "/host:metadata":
                    hlo = msgs["HloProto"]()
                    hlo.ParseFromString(stat.bytes_value)
                    modules.append(hlo.hlo_module)
                elif kind == "tf_op" and _is_device(plane):
                    op = xplane.op_name(entry.value.name).split(" ")[0]
                    device[op] = str(_stat_value(stat, names)).rstrip(":")
    out: Dict[str, str] = {}
    for mod in sorted(modules, key=lambda m: sum(
            len(c.instructions) for c in m.computations)):
        for comp in mod.computations:
            for ins in comp.instructions:
                out[ins.name] = ins.metadata.op_name
    out.update(device)
    return out


def phase_of(op_path: str) -> Optional[str]:
    """The innermost `hm.*` scope of a name stack; `hm.scan` alone (the
    scan's own slicing and stacking) is no phase."""
    for part in reversed(op_path.split("/")):
        if _SCOPE.match(part) and part != "hm.scan":
            return part
    return None


def phase_table(ops: Dict[str, float], paths: Dict[str, str]
                ) -> List[Tuple[str, float, Optional[str]]]:
    """[(operation, seconds, phase or None)] for `ctx["trace"]["ops"]`,
    whose keys are `xplane.op_name`s (`fusion.48 f32[4194304,128]`)."""
    return [(op, secs, phase_of(paths.get(op.split(" ")[0], "")))
            for op, secs in ops.items()]


def phase_seconds(ctx: dict) -> Optional[Dict[str, float]]:
    """Device-operation seconds of the window by phase, `unscoped` for the
    rest; None without a trace, or where no operation of the window
    carries a phase scope (a program without scopes)."""
    if ctx.get("job") != "stream" or not ctx.get("trace") \
            or not ctx["trace"]["ops"]:
        return None
    path = newest_xplane()
    if path is None:
        return None
    out = dict.fromkeys(PHASES + ("unscoped",), 0.0)
    for _, secs, phase in phase_table(ctx["trace"]["ops"], op_paths(path)):
        out[phase or "unscoped"] += secs
    return out if any(out[p] for p in PHASES) else None


def phase_ms(ctx: dict, phase: str) -> Optional[float]:
    """One phase's device milliseconds per step of the window."""
    secs = phase_seconds(ctx)
    steps = ctx["window"]["steps"] if secs else 0
    return 1e3 * secs[phase] / steps if steps else None


# -- the program's spans, with their args ------------------------------------

def all_spans() -> List[dict]:
    """The process tracer's ring as {"name", "start", "dur", "args"} with
    `start` on the perf_counter clock (the clock of `ctx["window"]`; the
    export is on the wall clock). The readers run in the job's process,
    after it, so the tracer still holds the run's spans."""
    from hivemall_tpu.obs.trace import get_tracer
    wall_minus_perf = time.time() - time.perf_counter()
    return [{"name": ev["name"], "start": ev["ts"] * 1e-6 - wall_minus_perf,
             "dur": ev["dur"] * 1e-6, "args": ev.get("args") or {}}
            for ev in get_tracer().chrome_dict()["traceEvents"]
            if ev.get("ph") == "X"]


def program_spans(ctx: dict) -> List[dict]:
    """`all_spans` that end inside the window."""
    t0, t1 = ctx["window"]["t0"], ctx["window"]["t1"]
    return [s for s in all_spans() if t0 <= s["start"] + s["dur"] <= t1]


def source_batch_ms(ctx: dict) -> Optional[float]:
    """Mean serial cost of one source batch on the source thread: the
    seconds of `source.assemble`, `source.note_batch` and
    `source.convert_labels` over the batches assembled."""
    if ctx.get("job") != "stream":
        return None
    spans = [s for s in program_spans(ctx) if s["name"] in SOURCE_SPANS]
    batches = {s["args"].get("batch") for s in spans
               if s["name"] == "source.assemble"}
    batches.discard(None)
    if not batches:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / len(batches)


# -- spans and module runs on one clock ---------------------------------------

def _annotations(path: str, names: Tuple[str, ...]) -> List[dict]:
    """The mirrored spans in the trace's host planes: {"name", "start",
    "end", "seq"} on the trace's clock, in order of start."""
    out = []
    for plane in read_space(path).planes:
        if plane.name.startswith("/device:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        wanted = {e.key: e.value.name for e in plane.event_metadata
                  if e.value.name in names}
        if not wanted:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.metadata_id not in wanted:
                    continue
                start = line.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12
                seq = next((_stat_value(s, stat_names) for s in ev.stats
                            if stat_names.get(s.metadata_id) == "seq"), None)
                out.append({"name": wanted[ev.metadata_id], "start": start,
                            "end": start + ev.duration_ps * 1e-12,
                            "seq": None if seq is None else int(seq)})
    return sorted(out, key=lambda a: a["start"])


def module_runs(path: str) -> List[Tuple[float, float]]:
    """[(start_s, dur_s)] of the runs of the module that took most of the
    first device plane's `XLA Modules` line (the step's program: loss
    sums and the like run as small modules of their own). Without a
    device plane, as on a CPU rehearsal, the runs are rebuilt from the
    host's operation events: one run per `run_id` of the busiest
    `hlo_module`."""
    space = read_space(path)
    by_name: Dict[str, list] = {}
    for plane in sorted(space.planes, key=lambda p: p.name):
        if not _is_device(plane):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                by_name.setdefault(names.get(ev.metadata_id, ""), []).append(
                    (line.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12,
                     ev.duration_ps * 1e-12))
        break
    if not by_name:
        runs: Dict[tuple, list] = {}
        for plane in space.planes:
            if plane.name.startswith("/device:"):
                continue
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            for line in plane.lines:
                for ev in line.events:
                    st = {stat_names.get(s.metadata_id):
                          _stat_value(s, stat_names) for s in ev.stats}
                    if "hlo_op" not in st or ev.duration_ps <= 0:
                        continue
                    start = line.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12
                    r = runs.setdefault((st.get("hlo_module"),
                                         st.get("run_id")), [start, start])
                    r[0] = min(r[0], start)
                    r[1] = max(r[1], start + ev.duration_ps * 1e-12)
        for (module, _), (s, e) in runs.items():
            by_name.setdefault(str(module), []).append((s, e - s))
    if not by_name:
        return []
    return sorted(max(by_name.values(),
                      key=lambda runs: sum(d for _, d in runs)))


def feed_leads(path: str, spans: List[dict]) -> Optional[List[dict]]:
    """For each dispatch of the traced session, how long its staged input
    waited before the chip began it: [{"seq", "lead_s", "run_start"}] on
    the trace's clock. The k-th `dispatch.megastep` annotation of the
    trace pairs with the k-th run of the step's module; the input of
    dispatch `seq` was staged when `h2d.stage` `seq` ended (where no
    prefetcher stages, as on the CPU and under a mesh, when
    `stager.stack` `seq` did). The first staged inputs predate the
    session, so those ends come from `spans` (the tracer's export, on
    the wall clock), carried onto the trace's clock by the dispatch
    spans, which both hold. None if the counts do not pair."""
    disp = [a for a in _annotations(path, ("dispatch.megastep",))
            if a["seq"] is not None]
    runs = module_runs(path)
    if not disp or len(disp) != len(runs):
        return None
    own = {s["args"].get("seq"): s for s in spans
           if s["name"] == "dispatch.megastep"}
    shifts = [a["start"] - own[a["seq"]]["start"] for a in disp
              if a["seq"] in own]
    if not shifts:
        return None
    shift = statistics.median(shifts)           # spans' clock -> trace's
    staged: Dict[int, float] = {}
    for name in ("stager.stack", "h2d.stage"):  # h2d.stage, where there
        for s in spans:                         # is one, is the later
            if s["name"] == name and s["args"].get("seq") is not None:
                staged[s["args"]["seq"]] = s["start"] + s["dur"] + shift
    out = []
    for a, (run_start, _) in zip(disp, runs):
        if a["seq"] in staged:
            out.append({"seq": a["seq"], "run_start": run_start,
                        "lead_s": run_start - staged[a["seq"]]})
    return out or None


def window_leads(ctx: dict) -> Optional[List[dict]]:
    """`feed_leads` of the dispatches made inside the window."""
    if ctx.get("job") != "stream" or not ctx.get("trace"):
        return None
    path = newest_xplane()
    if path is None:
        return None
    spans = all_spans()
    leads = feed_leads(path, spans)
    if leads is None:
        return None
    t0, t1 = ctx["window"]["t0"], ctx["window"]["t1"]
    inside = {s["args"].get("seq") for s in spans
              if s["name"] == "dispatch.megastep"
              and t0 <= s["start"] + s["dur"] <= t1}
    return [ld for ld in leads if ld["seq"] in inside] or None
