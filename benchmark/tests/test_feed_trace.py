"""The feed's account of a window (harness/feed_trace.py) on a hand-made
ring whose numbers are worked out by hand: two loop threads, two service
threads and the dispatching one, with known waits and CPU seconds; the
four metrics built on it and their entries; a ring from before the spans
had a CPU clock reading as nothing; and a toy run that reports them."""

import json
import os

import pytest

from harness import feed_trace, program_trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

ALL = ["fm_criteo.stream", "ffm_criteo_joint.stream",
       "ffm_criteo_joint_tp4.stream_mesh", "logreg_criteo.stream"]
NEW = {"feed.cycle_ms": ("ms", ALL),
       "feed.unattributed_share": ("%", ALL),
       "feed.offcpu_share": ("%", ALL),
       "pairs.track_ms": ("ms", ALL[1:3])}
T0 = 100.0
ROWS = 64                               # a batch's, and here a dispatch's


def _span(name, thread, start, dur, cpu=None, **args):
    args["thread"] = thread
    if cpu is not None:
        args["cpu"] = cpu * 1e6         # microseconds, as the export has it
    return {"name": name, "start": start, "dur": dur, "args": args}


def ring(decode_second=0.2, cpu=True):
    """Four dispatches of one batch each, one every 0.25 s; a shard holds
    two dispatches' rows.

    `h2d-prefetch`, a cycle: source.assemble 0.10 (0.08 on the CPU), 0.02
    of nothing, stager.stack 0.03 (0.03), h2d.stage 0.04 (0.004),
    feed.wait_slot 0.05, 0.01 of nothing. The first assemble began 0.05 s
    before the window (0.15 long, 0.12 on the CPU: two thirds count), and
    has a child that must not count twice. Last span ends at 100.99.
    `ingest-source`: 0.3 waiting for a shard, 0.2 of work on batches 0
    and 3, 0.4 blocked on the full queue. `pq-decode_0`: two shards, 0.4
    (0.3) and
    `decode_second` (half on the CPU), the last ending at 100.95.
    `pairs-track`: 30 and 50 ms, batches 0 and 3. `MainThread`
    dispatches."""
    c = (lambda v: v) if cpu else (lambda v: None)
    out = []
    for k in range(4):
        s = T0 + 0.25 * k
        if k == 0:
            out.append(_span("source.assemble", "h2d-prefetch", s - 0.05,
                             0.15, c(0.12), id=1, batch=0))
            out.append(_span("source.gather", "h2d-prefetch", s, 0.05,
                             c(0.05), id=2, parent=1))
        else:
            out.append(_span("source.assemble", "h2d-prefetch", s, 0.10,
                             c(0.08), batch=k))
        out.append(_span("stager.stack", "h2d-prefetch", s + 0.12, 0.03,
                         c(0.03), seq=k))
        out.append(_span("h2d.stage", "h2d-prefetch", s + 0.15, 0.04,
                         c(0.004), seq=k))
        out.append(_span("feed.wait_slot", "h2d-prefetch", s + 0.19, 0.05,
                         c(0.0), seq=k))
        out.append(_span("loop.wait_input", "MainThread", s, 0.19, c(0.0)))
        out.append(_span("dispatch.megastep", "MainThread", s + 0.19, 0.002,
                         c(0.002), seq=k))
    out += [
        _span("source.wait_shard", "ingest-source", T0, 0.3, c(0.0)),
        _span("source.note_batch", "ingest-source", T0 + 0.3, 0.1, c(0.05),
              batch=0),
        _span("source.note_batch", "ingest-source", T0 + 0.4, 0.1, c(0.05),
              batch=3),
        _span("ingest.wait_slot", "ingest-source", T0 + 0.5, 0.4, c(0.0),
              batch=3),
        _span("source.decode", "pq-decode_0", T0 + 0.1, 0.4, c(0.3),
              rows=2 * ROWS),
        _span("source.decode", "pq-decode_0", T0 + 0.95 - decode_second,
              decode_second, c(decode_second / 2), rows=2 * ROWS),
        _span("pairs.track", "pairs-track", T0 + 0.2, 0.03, c(0.03),
              batch=0),
        _span("pairs.track", "pairs-track", T0 + 0.4, 0.05, c(0.02),
              batch=3),
    ]
    return out


def ctx(monkeypatch, spans, job="stream"):
    monkeypatch.setattr(program_trace, "all_spans", lambda: spans)
    return {"job": job, "window": {"t0": T0, "t1": T0 + 2.0, "batch": ROWS,
                                   "steps_per_dispatch": 1}}


def test_accounts_tile_each_loop_thread(monkeypatch):
    acc = feed_trace.window_accounts(ctx(monkeypatch, ring()))
    assert set(acc) == {"h2d-prefetch", "ingest-source", "pq-decode_0",
                        "pairs-track"}
    # every thread saw four dispatches pass: by its batches' range, or
    # by the rows of the shards it decoded
    assert [t["dispatches"] for t in acc.values()] == [4.0] * 4
    h = acc["h2d-prefetch"]
    assert h["kind"] == "loop"
    assert h["window_s"] == pytest.approx(0.99)
    assert h["wait_s"] == pytest.approx(0.20)
    assert h["work_s"] == pytest.approx(4 * 0.17)
    assert h["remainder_s"] == pytest.approx(0.11)
    assert h["wait_s"] + h["work_s"] + h["remainder_s"] \
        == pytest.approx(h["window_s"])
    assert h["busy_s"] == pytest.approx(0.79)
    assert h["cycle_ms"] == pytest.approx(1e3 * 0.79 / 4)
    # what a dispatch takes the thread when nothing downstream holds it
    # up: its window less its waits for a slot
    assert h["free_ms"] == pytest.approx(1e3 * 0.79 / 4)
    # the span cut by the window's start: its part inside, its CPU
    # seconds in proportion; its child not at all
    a = h["spans"]["source.assemble"]
    assert (a["n"], a["wall_s"]) == (4, pytest.approx(0.40))
    assert a["cpu_s"] == pytest.approx(0.32)
    assert "source.gather" not in h["spans"]
    s = acc["ingest-source"]
    assert (s["kind"], s["window_s"], s["wait_s"], s["busy_s"]) == (
        "loop", pytest.approx(0.9), pytest.approx(0.7), pytest.approx(0.2))
    assert s["remainder_s"] == pytest.approx(0.0, abs=1e-9)
    assert s["free_ms"] == pytest.approx(1e3 * 0.5 / 4)  # shard wait in
    d = acc["pq-decode_0"]
    assert (d["kind"], d["remainder_s"]) == ("service", None)
    assert d["busy_s"] == pytest.approx(0.6)
    assert d["cycle_ms"] == pytest.approx(1e3 * 0.6 / 4)


@pytest.mark.parametrize("decode_second,cycle,offcpu", [
    # the loop thread is the busiest: off-CPU over assemble + stack
    (0.2, 1e3 * 0.79 / 4, 100 * (1 - 0.44 / 0.52)),
    # a busier decode thread takes the cycle and the off-CPU share over
    (0.45, 1e3 * 0.85 / 4, 100 * (1 - 0.525 / 0.85)),
], ids=["loop_busiest", "service_busiest"])
def test_readers_give_the_numbers_worked_out_by_hand(
        monkeypatch, decode_second, cycle, offcpu):
    c = ctx(monkeypatch, ring(decode_second))
    assert feed_trace.cycle_ms(c) == pytest.approx(cycle)
    assert feed_trace.offcpu_share(c) == pytest.approx(offcpu)
    # always the busiest LOOP thread's
    assert feed_trace.unattributed_share(c) == pytest.approx(
        100 * 0.11 / 0.79)
    assert feed_trace.pairs_track_ms(c) == pytest.approx(40.0)


def _reader(name):
    from harness import common
    return common.load_module(
        os.path.join(BENCH, "layer_metrics", f"{name}.py"),
        "layer_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_has_its_reader_and_its_entry(monkeypatch, name):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = bench["per_layer"]
    assert [m["name"] for m in entries[-len(NEW):]] == list(NEW)  # appended
    (m,) = [m for m in entries if m["name"] == name]
    unit, cells = NEW[name]
    assert m == {"name": name, "unit": unit, "better": "lower",
                 "source": "program_span", "layer": "host feed, whole",
                 "moves": "train_rate", "workloads": cells}
    value = _reader(name).read(ctx(monkeypatch, ring()))
    assert isinstance(value, float) and value > 0.0


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("case", ["no_cpu_clock", "empty", "not_stream"])
def test_readers_return_none_without_their_spans(monkeypatch, name, case):
    """A program from before this clock (the parent: spans with no `cpu`
    and none of the waits; its `pairs.track` is there and still reads), an
    empty ring, a job that is not `stream`: None, never an error."""
    spans = {"no_cpu_clock": ring(cpu=False), "empty": [],
             "not_stream": ring()}[case]
    c = ctx(monkeypatch, spans, "predict_open_loop" if case == "not_stream"
            else "stream")
    value = _reader(name).read(c)
    if (name, case) == ("pairs.track_ms", "no_cpu_clock"):
        assert value == pytest.approx(40.0)
    else:
        assert value is None


def test_toy_run_reports_the_feed(capsys):
    """On the CPU nothing prefetches, so the feed runs on the dispatching
    thread and the one feed thread left is `pq-decode`, a service thread:
    a cycle and an off-CPU share, no unattributed share; the FFM cell's
    tracker thread reads too."""
    from test_harness_toy import run_cell
    res = run_cell(capsys, "ffm_criteo_joint.stream", seed=2 ** 31 + 9,
                   trace=1, seconds=2.0)
    assert res["correct"] is True
    got = res["metrics"]
    assert got["feed.cycle_ms"]["value"] > 0.0
    assert 0.0 <= got["feed.offcpu_share"]["value"] <= 100.0
    assert got["pairs.track_ms"]["value"] > 0.0
    assert "feed.unattributed_share" not in got
