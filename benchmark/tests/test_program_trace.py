"""The readers of what the program says about itself (harness/
program_trace.py): phase scopes recovered from recorded traces with the
scopes planted in them (record_scoped_trace.py wrote them: one on the
CPU, one on the chip), spans and module runs paired on one clock, a toy
run that reports the nine metrics built on them, and a program without
scopes or span ids (the parent of the PR that brought them) reading as
nothing, not as an error."""

import glob
import json
import os

import pytest

from harness import program_trace, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")
SCOPED = sorted(glob.glob(os.path.join(DATA, "scoped_*.xplane.pb")))
UNSCOPED = sorted(glob.glob(os.path.join(DATA, "small_*.xplane.pb")))

NEW = ["step.gather_ms", "step.grad_ms", "step.scatter_ms", "step.update_ms",
       "step.unscoped_share", "source.assemble_ms", "loop.wait_input_share",
       "feed.lead_ms", "idle.no_span_share"]


def _reduced(path):
    meta = json.load(open(path.replace(".xplane.pb", ".json")))
    raw = xplane.read(path)
    off = meta["sync_perf"] - raw["sync_s"]
    return meta, xplane.reduce(raw, (meta["t0"] - off, meta["t1"] - off), [])


def test_phase_of_takes_the_innermost_scope():
    f = program_trace.phase_of
    assert f("jit(m)/hm.scan/while/body/closed_call/hm.gather/gather") \
        == "hm.gather"
    assert f("jit(m)/hm.scan/while/body/closed_call/hm.grad/"
             "transpose(jvp())/mul") == "hm.grad"
    assert f("jit(m)/hm.update/hm.scatter/add") == "hm.scatter"
    assert f("jit(m)/hm.scan/while/body/dynamic_slice") is None   # bare scan
    assert f("jit(m)/whm.gather/x") is None and f("") is None


def test_scoped_traces_are_there():
    assert {os.path.basename(p) for p in SCOPED} >= {"scoped_cpu.xplane.pb",
                                                     "scoped_tpu.xplane.pb"}


@pytest.mark.parametrize("path", SCOPED, ids=os.path.basename)
def test_planted_scopes_and_their_seconds_are_recovered(path):
    meta, r = _reduced(path)
    table = program_trace.phase_table(r["ops"], program_trace.op_paths(path))
    by_phase = {}
    for _, secs, phase in table:
        by_phase[phase] = by_phase.get(phase, 0.0) + secs
    # every planted phase took time, the one not planted took none, and
    # something (the scan's own copies) ran outside any phase
    for phase in meta["planted"]:
        assert by_phase.get(phase, 0.0) > 0.0, phase
    assert set(by_phase) - {None} == set(meta["planted"])
    assert by_phase.get(None, 0.0) > 0.0
    # nothing is lost or counted twice: the phases and the rest are the
    # operations' seconds, which on a device plane are the busy seconds
    # (no operation overlaps another once wrappers are left out; the
    # CPU's run on several threads at once)
    assert sum(by_phase.values()) == pytest.approx(sum(r["ops"].values()))
    if "tpu" in os.path.basename(path):
        assert sum(by_phase.values()) == pytest.approx(r["busy_s"], rel=0.02)
    # by name, where the compiler kept the op apart
    named = {op.split(" ")[0].split(".")[0]: phase for op, _, phase in table}
    if "dot_general" in named:
        assert named["dot_general"] == "hm.grad"


@pytest.mark.parametrize("path", SCOPED, ids=os.path.basename)
def test_dispatches_pair_with_module_runs_on_one_clock(path, monkeypatch):
    meta, _ = _reduced(path)
    runs = program_trace.module_runs(path)
    assert len(runs) == meta["calls"]
    leads = program_trace.feed_leads(path, meta["program_spans"])
    assert [ld["seq"] for ld in leads] == list(range(meta["calls"]))
    window = meta["t1"] - meta["t0"]
    for ld, (start, _) in zip(leads, runs):
        assert ld["run_start"] == start
        # staged before it ran, by less than the recording lasted; the
        # first input was staged before the session began and still pairs
        assert 0.0 < ld["lead_s"] < window + 1.0
    # each input waited through at least the 20 ms sleep before its call
    assert min(ld["lead_s"] for ld in leads) > 0.015
    # no staged instant to measure from: no number
    short = [s for s in meta["program_spans"] if s["name"] != "h2d.stage"]
    assert program_trace.feed_leads(path, short) is None
    # a dispatch more than the device plane shows runs: no pairing
    monkeypatch.setattr(program_trace, "module_runs", lambda p: runs[:-1])
    assert program_trace.feed_leads(path, meta["program_spans"]) is None


@pytest.mark.parametrize("path", UNSCOPED, ids=os.path.basename)
def test_a_program_without_scopes_or_ids_reads_as_nothing(path, monkeypatch):
    """The parent's traces: operations but no phase scope, no mirrored
    spans. Every reader returns None and none raises."""
    meta, r = _reduced(path)
    monkeypatch.setattr(program_trace, "newest_xplane", lambda: path)
    monkeypatch.setattr(program_trace, "all_spans", lambda: [
        {"name": n, "start": s, "dur": d, "args": {}}
        for n, s, d in [("dispatch.megastep", meta["t0"] + 0.01, 0.001),
                        ("stager.stack", meta["t0"], 0.001)]])
    ctx = {"job": "stream", "trace": r, "spans": [],
           "window": {"t0": meta["t0"], "t1": meta["t1"], "steps": 12,
                      "seconds": meta["t1"] - meta["t0"]}}
    assert program_trace.phase_seconds(ctx) is None
    assert program_trace.phase_ms(ctx, "hm.grad") is None
    assert program_trace.source_batch_ms(ctx) is None
    assert program_trace.window_leads(ctx) is None
    assert program_trace.feed_leads(path, program_trace.all_spans()) is None


def test_every_listed_metric_has_its_reader_and_the_new_ones_their_entry():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = [m["name"] for m in bench["per_layer"]]
    for name in listed:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           f"{name}.py")), name
    assert listed[-len(NEW):] == NEW                 # appended, in order
    layers = {m["layer"] for m in bench["per_layer"][:-len(NEW)]}
    for m in bench["per_layer"][-len(NEW):]:
        assert m["workloads"] == ["fm_criteo.stream"]
        assert m["moves"] == "train_rate" and m["layer"] in layers


def test_toy_run_reports_the_nine(capsys):
    """On the CPU every one of the nine finds something to read: the
    rehearsal's operations carry the scopes through the HLO the trace
    holds, the stager's `seq` stands in for h2d.stage's where no
    prefetcher runs, and the host's operation events for module runs."""
    from test_harness_toy import run_cell
    res = run_cell(capsys, "fm_criteo.stream", seed=2 ** 31 + 9, trace=1,
                   seconds=2.0)
    assert res["correct"] is True
    got = res["metrics"]
    assert set(NEW) <= set(got), set(NEW) - set(got)
    phases = sum(got[f"step.{p}_ms"]["value"]
                 for p in ("gather", "grad", "scatter", "update"))
    total = phases / (1.0 - got["step.unscoped_share"]["value"] / 100.0)
    assert total == pytest.approx(got["step.device_ms"]["value"], rel=0.15)
    assert 0.0 <= got["loop.wait_input_share"]["value"] <= 100.0
    assert 0.0 <= got["idle.no_span_share"]["value"] <= 100.0
    assert got["source.assemble_ms"]["value"] > 0.0
    assert got["feed.lead_ms"]["value"] > 0.0
    # the spans of the old readers still resolve
    assert {"dispatch.host_ms", "stage.h2d_share"} <= set(got)
