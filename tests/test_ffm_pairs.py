"""FFM observed-pair tracking off the source thread (models/ffm_pairs.py).

The parent ran ``np.unique`` and a ``set.update`` of Python tuples inside
``source.note_batch`` on the thread that feeds the chip. Now that thread only
hands the batch over; these tests hold the tracker to the parent's answer at
every point that reads the pairs, and to leaving nothing behind.
"""
import threading
import time

import numpy as np
import pytest

from hivemall_tpu.io.sparse import SparseDataset
from hivemall_tpu.models.ffm_pairs import ObservedPairs
from hivemall_tpu.models.fm import FFMTrainer

B, L, F, K, DIMS = 128, 16, 16, 8, 1 << 16


def _cfg(layout):
    extra = {"joint": "-halffloat", "dense": "-ffm_table dense"}[layout]
    dims = 1 << 10 if layout == "dense" else DIMS
    return (f"-dims {dims} -factors {K} -fields {F} -mini_batch {B} "
            f"-opt adagrad -classification -seed 5 {extra}")


def _ds(n=5 * B + 40, seed=12, dims=DIMS, fields=True):
    rng = np.random.default_rng(seed)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    val = np.ones((n, L), np.float32)
    val[rng.random((n, L)) < 0.1] = 0.0        # dead slots: never observed
    fld = np.tile(np.arange(L, dtype=np.int32), (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    return SparseDataset(idx.ravel(), np.arange(0, n * L + 1, L,
                                                dtype=np.int64),
                         val.ravel(), lab, fld.ravel() if fields else None)


def _parent_answer(batches):
    """What the parent's _note_batch + _observed_pairs gave: np.unique over
    every batch's live (idx, field)."""
    keys = [np.zeros(0, np.int64)]
    for b in batches:
        live = b.val != 0
        keys.append(b.idx[live].astype(np.int64) * F
                    + b.field[live].astype(np.int64))
    ii, ff = np.divmod(np.unique(np.concatenate(keys)), F)
    return ii.astype(np.int32), ff.astype(np.int32)


def _tracker_threads():
    return [t for t in threading.enumerate()
            if t.name == ObservedPairs.THREAD_NAME]


def _assert_pairs(trainer, batches):
    got, want = trainer._observed_pairs(), _parent_answer(batches)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert got[0].dtype == got[1].dtype == np.int32


@pytest.mark.parametrize("workers", [1, 2])
@pytest.mark.parametrize("layout", ["joint"])
def test_observed_pairs_after_fit_stream_equal_the_parents(layout, workers):
    ds = _ds()
    t = FFMTrainer(_cfg(layout) + f" -ingest_workers {workers}")
    t.fit_stream(ds.batches(B, shuffle=True, seed=3))
    assert not _tracker_threads()
    _assert_pairs(t, list(ds.batches(B, shuffle=True, seed=3)))
    names = {r[0] for r in t.model_rows()}
    assert names >= {str(i) for i in _parent_answer(
        ds.batches(B, shuffle=False))[0][:50].tolist()}


def test_dense_layout_and_fieldless_batches_track_nothing():
    d = FFMTrainer(_cfg("dense"))
    d.fit_stream(_ds(dims=1 << 10).batches(B))
    assert d._observed_pairs() is None and not _tracker_threads()
    j = FFMTrainer(_cfg("joint"))
    j._note_batch(next(_ds(fields=False).batches(B)))    # field is None
    assert j._observed_pairs() is None


@pytest.mark.parametrize("reader", ["model_rows", "save_bundle"])
def test_pairs_complete_between_two_fit_streams(tmp_path, reader):
    a, b = _ds(seed=1), _ds(seed=2)
    t = FFMTrainer(_cfg("joint"))
    t.fit_stream(a.batches(B))
    if reader == "model_rows":
        rows = list(t.model_rows())
        assert len(rows) == 1 + len(_parent_answer(a.batches(B))[0])
    else:
        t.save_bundle(str(tmp_path / "mid.npz"))
    _assert_pairs(t, list(a.batches(B)))
    t.fit_stream(b.batches(B))
    _assert_pairs(t, list(a.batches(B)) + list(b.batches(B)))
    assert not _tracker_threads()


def test_a_reader_mid_stream_sees_every_applied_batch(tmp_path):
    """on_dispatch runs after a dispatch's steps are applied: a checkpoint's
    or a scrape's view of the pairs there has every batch up to it."""
    ds = _ds()
    batches = list(ds.batches(B))
    t = FFMTrainer(_cfg("joint") + " -steps_per_dispatch 1")
    applied, seen = [], []

    def on_dispatch(seq, steps, examples):
        applied.append(steps)
        want = _parent_answer(batches[:sum(applied)])
        got = t._observed_pairs()
        seen.append(set(zip(want[0].tolist(), want[1].tolist()))
                    <= set(zip(got[0].tolist(), got[1].tolist())))

    t.fit_stream(iter(batches), on_dispatch=on_dispatch)
    assert sum(applied) == len(batches) and all(seen)
    _assert_pairs(t, batches)


def test_a_fault_in_the_tracker_raises_from_fit_stream(monkeypatch):
    calls = []

    def faulty(self, idx, fld, val):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("planted in the tracker")

    monkeypatch.setattr(ObservedPairs, "add_batch", faulty)
    t = FFMTrainer(_cfg("joint"))
    with pytest.raises(RuntimeError, match="planted in the tracker"):
        t.fit_stream(_ds().batches(B))
    assert not _tracker_threads()
    with pytest.raises(RuntimeError, match="planted"):
        t._observed_pairs()          # incomplete pairs are not handed out
    monkeypatch.undo()
    t.fit_stream(_ds().batches(B))   # a new stream starts clean
    assert t._observed_pairs() is not None


def test_no_tracker_thread_outlives_a_failing_step(monkeypatch):
    t = FFMTrainer(_cfg("joint"))
    n = []

    def boom(b):
        n.append(1)
        if len(n) == 3:
            raise ValueError("planted in the step")
        return real(b)

    real = t._dispatch
    monkeypatch.setattr(t, "_dispatch", boom)
    with pytest.raises(ValueError, match="planted in the step"):
        t.fit_stream(_ds().batches(B))
    assert not _tracker_threads()


def test_note_batch_does_no_unique_on_the_source_thread(monkeypatch):
    """Inside fit_stream every add_batch runs on the tracker's thread, under
    a pairs.track span carrying the batch's ordinal."""
    from hivemall_tpu.obs.trace import get_tracer
    where = []
    real = ObservedPairs.add_batch

    def spy(self, idx, fld, val):
        where.append(threading.current_thread().name)
        return real(self, idx, fld, val)

    monkeypatch.setattr(ObservedPairs, "add_batch", spy)
    tr = get_tracer()
    tr.reset()
    tr.enable()
    try:
        FFMTrainer(_cfg("joint")).fit_stream(_ds().batches(B))
        spans = [e for e in tr.chrome_dict()["traceEvents"]
                 if e["ph"] == "X" and e["name"] == "pairs.track"]
    finally:
        tr.disable()
        tr.reset()
    assert where == [ObservedPairs.THREAD_NAME] * 6
    assert [e["args"]["batch"] for e in spans] == list(range(6))
    assert {e["args"]["thread"] for e in spans} == {ObservedPairs.THREAD_NAME}


def test_backlog_is_bounded_by_back_pressure(monkeypatch):
    """A tracker slower than the source holds the source back; at most
    BACKLOG batches wait and one is in hand."""
    gate = threading.Event()
    real = ObservedPairs.add_batch

    def slow(self, idx, fld, val):
        gate.wait(10)
        return real(self, idx, fld, val)

    monkeypatch.setattr(ObservedPairs, "add_batch", slow)
    from hivemall_tpu.obs.trace import get_tracer
    p = ObservedPairs(F)
    handed = []

    def source():
        for k in range(12):
            p.note(np.full((1, 1), k + 1, np.int32),
                   np.zeros((1, 1), np.int32), np.ones((1, 1), np.float32))
            handed.append(k)

    with p.streaming(get_tracer()):
        th = threading.Thread(target=source)
        th.start()
        deadline = time.monotonic() + 5
        while len(handed) < p.BACKLOG + 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)
        assert len(handed) == p.BACKLOG + 1      # the 6th put is blocked
        gate.set()
        th.join(10)
        assert not th.is_alive()
    np.testing.assert_array_equal(p.keys(), (np.arange(12) + 1) * F)
    assert not _tracker_threads()


def test_keys_merge_in_chunks_like_one_unique(monkeypatch):
    monkeypatch.setattr(ObservedPairs, "_COMPACT_FLOOR", 64)
    rng = np.random.default_rng(0)
    p = ObservedPairs(F)
    chunks = [rng.integers(0, 5000, rng.integers(0, 300)) for _ in range(60)]
    for c in chunks:
        p.add(c.astype(np.int64))
    np.testing.assert_array_equal(p.keys(), np.unique(np.concatenate(chunks)))
    assert p.keys().dtype == np.int64


def test_process_path_records_every_slot_of_a_row():
    """The UDTF path (process -> _flush_chunk) records a row's pairs whatever
    their value, as it did with the set of tuples."""
    t = FFMTrainer(f"-dims {DIMS} -factors {K} -fields {F} -mini_batch 4 "
                   "-classification")
    rows = [["0:11:1.0", "1:12:0.0"], ["0:11:1.0", "3:99:2.0"],
            ["2:7:1.0"], ["1:12:1.0"]]
    for r in rows:
        t.process(r, 1)
    ii, ff = t._observed_pairs()
    assert list(zip(ii.tolist(), ff.tolist())) == \
        [(7, 2), (11, 0), (12, 1), (99, 3)]
