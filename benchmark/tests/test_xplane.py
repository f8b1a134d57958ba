"""The reduction from a profiler trace to busy time, operation table and
idle gaps, checked on small recorded traces kept beside this file
(record_small_trace.py wrote them: three calls of a scanned step with
20 ms sleeps between)."""

import glob
import json
import os

import pytest

from harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACES = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


def test_op_name_reads_hlo_lines():
    assert xplane.op_name(
        "%fusion.52 = f32[4194304,164]{1,0:T(8,128)} fusion(s32[1277952] "
        "%get-tuple-element.241)") == "fusion.52 f32[4194304,164]"
    assert xplane.op_name(
        "%sub.2 = (bf16[8,164]{1,0:T(8,128)(2,1)}, f32[8,164]{1,0}) fusion("
    ) == "sub.2 bf16[8,164]"
    assert xplane.op_name("dot_general.1") == "dot_general.1"
    assert xplane.is_wrapper(xplane.op_name("%while.2 = (s32[]{:T(128)}, "))
    assert not xplane.is_wrapper("fusion.52 f32[4,4]")


def test_busy_intervals_merge_overlaps():
    ev = [(0.0, 1.0, "a", ""), (0.5, 1.0, "b", ""), (3.0, 1.0, "c", ""),
          (3.2, 0.1, "d", "")]
    assert xplane.busy_intervals(ev) == [(0.0, 1.5), (3.0, 4.0)]


def test_reduce_charges_gaps_to_the_covering_span():
    trace = {"planes": {"/device:TPU:0": [(1.0, 1.0, "op x", ""),
                                          (3.0, 1.0, "while.1", ""),
                                          (3.0, 0.5, "op y", "")]},
             "sync_s": 0.0}
    r = xplane.reduce(trace, (0.0, 5.0), [("stager.stack", 2.0, 1.0)])
    assert r["busy_s"] == pytest.approx(2.0)
    assert r["window_s"] == pytest.approx(5.0)
    assert r["ops"] == {"op x": pytest.approx(1.0),
                        "op y": pytest.approx(0.5)}      # wrapper left out
    gaps = dict(r["idle_gaps"])
    assert gaps["stager.stack"] == pytest.approx(1.0)
    assert gaps["no_span"] == pytest.approx(2.0)
    assert sum(gaps.values()) == pytest.approx(5.0 - r["busy_s"])


@pytest.mark.parametrize("path", TRACES, ids=os.path.basename)
def test_recorded_trace(path):
    meta = json.load(open(path.replace(".xplane.pb", ".json")))
    raw = xplane.read(path)
    assert raw["sync_s"] is not None and raw["planes"]
    off = meta["sync_perf"] - raw["sync_s"]
    spans = [(n, s - off, d) for n, s, d in meta["spans"]]
    r = xplane.reduce(raw, (meta["t0"] - off, meta["t1"] - off), spans)
    window = meta["t1"] - meta["t0"]
    assert r["window_s"] == pytest.approx(window)
    # three sleeps of 20 ms are idle; something ran in between
    assert 0.0 < r["busy_s"] < window - 0.05
    assert dict(r["idle_gaps"]).get("sleep", 0.0) > 0.05
    assert r["device_ops"] and len(r["device_ops"]) <= 10
    assert not any(xplane.is_wrapper(n) for n in r["ops"])
    idle = sum(v for _, v in r["idle_gaps"])
    if r["n_planes"] == 1 and len(r["idle_gaps"]) < 10:
        assert idle == pytest.approx(window - r["busy_s"], rel=1e-6)
