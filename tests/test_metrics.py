"""Observability subsystem (SURVEY.md §6): jsonl stream, meter, trainer hook."""

import json
import time

import numpy as np

from hivemall_tpu.models.linear import GeneralClassifier
from hivemall_tpu.utils import metrics as M


def test_meter_rate():
    m = M.Meter(window=60.0)
    m.add(100)
    time.sleep(0.05)
    m.add(100)
    assert m.total == 200
    assert m.rate > 0


def test_stream_disabled_is_noop():
    s = M.MetricsStream(None)
    assert not s.enabled
    s.emit("anything", x=1)      # must not raise


def test_stream_writes_jsonl(tmp_path):
    p = tmp_path / "m.jsonl"
    s = M.MetricsStream(str(p))
    s.emit("ev", a=1)
    s.emit("ev", a=2)
    s.close()
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    assert [r["a"] for r in recs] == [1, 2]
    assert all(r["event"] == "ev" and "ts" in r and "host" in r
               for r in recs)


def test_trainer_emits_stream(tmp_path, monkeypatch):
    p = tmp_path / "train.jsonl"
    monkeypatch.setattr(M, "_stream", M.MetricsStream(str(p)))
    rng = np.random.default_rng(0)
    tr = GeneralClassifier("-mini_batch 16 -dims 1024")
    for i in range(40):
        x = rng.normal(size=3)
        y = 1 if x.sum() > 0 else -1
        tr.process([f"f{j}:{x[j]:.4f}" for j in range(3)], y)
    rows = list(tr.close())
    assert rows
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    done = [r for r in recs if r["event"] == "train_done"]
    assert len(done) == 1
    assert done[0]["examples"] == 40
    assert done[0]["trainer"] == tr.NAME
    M._stream.close()
    monkeypatch.setattr(M, "_stream", None)


def test_stream_bad_path_fails_soft(capsys):
    s = M.MetricsStream("/nonexistent-dir-xyz/m.jsonl")
    assert not s.enabled
    s.emit("ev", a=1)            # still a no-op, no raise
    assert "metrics disabled" in capsys.readouterr().err
