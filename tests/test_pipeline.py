"""Parallel host ingest pipeline: order, bit-exactness, errors, metrics."""

import threading
import time

import numpy as np
import pytest

from conftest import assert_batches_equal as _assert_batches_equal
from hivemall_tpu.io.libsvm import synthetic_classification
from hivemall_tpu.io.pipeline import (IngestPipeline, PipelineStats,
                                      auto_workers)


def test_nworker_prep_matches_sequential_in_order():
    """N-worker prep produces byte-identical batches in identical order vs
    the sequential path — seeded shuffle included."""
    ds, _ = synthetic_classification(400, 12, seed=11)

    def prep(b):
        # a non-trivial deterministic transform (scales + re-types)
        return type(b)(b.idx * np.int32(3), b.val * 2.0, b.label,
                       b.field, n_valid=b.n_valid, fieldmajor=b.fieldmajor)

    seq = list(map(prep, ds.batches(32, shuffle=True, seed=5)))
    par = list(IngestPipeline(ds.batches(32, shuffle=True, seed=5), prep,
                              workers=4))
    assert len(par) == len(seq)
    for a, b in zip(seq, par):
        _assert_batches_equal(a, b)


def test_worker_exception_propagates_within_one_batch():
    ds, _ = synthetic_classification(640, 8, seed=12)

    def src():
        for i, b in enumerate(ds.batches(32, shuffle=False)):
            yield (i, b)

    def prep(t):
        i, b = t
        if i == 5:           # deterministic per batch, not per worker order
            raise RuntimeError("prep blew up")
        return b

    got = 0
    it = IngestPipeline(src(), prep, workers=4)
    with pytest.raises(RuntimeError, match="prep blew up"):
        for _ in it:
            got += 1
    assert got == 5          # delivered everything before the failed batch


def test_source_error_propagates():
    def bad_src():
        ds, _ = synthetic_classification(64, 8, seed=13)
        yield from ds.batches(16, shuffle=False)
        raise RuntimeError("source io died")

    with pytest.raises(RuntimeError, match="source io died"):
        list(IngestPipeline(bad_src(), lambda b: b, workers=3))


def test_sequential_fallback_uses_no_threads():
    ds, _ = synthetic_classification(100, 8, seed=14)
    # compare thread SETS, not counts: leftover daemon threads from earlier
    # tests may die mid-test and an exact active_count() equality flakes
    before = set(threading.enumerate())
    out = list(IngestPipeline(ds.batches(16, shuffle=False), lambda b: b,
                              workers=1))
    assert len(out) == 7
    assert not (set(threading.enumerate()) - before)


def test_close_releases_workers_after_abandon():
    ds, _ = synthetic_classification(640, 8, seed=15)
    it = IngestPipeline(ds.batches(16, shuffle=False), lambda b: b,
                        workers=3)
    next(it)
    it.close()
    assert not it._submitter.is_alive()
    with pytest.raises(StopIteration):
        next(it)


def test_stats_populated():
    ds, _ = synthetic_classification(320, 8, seed=16)
    stats = PipelineStats()
    out = list(IngestPipeline(ds.batches(32, shuffle=False), lambda b: b,
                              workers=2, stats=stats))
    assert stats.batches_prepared == len(out) == 10
    assert stats.workers == 2 and stats.pool == "thread"
    d = stats.as_dict()
    for k in ("prep_seconds", "prep_wait_seconds",
              "prep_backpressure_seconds", "stage_seconds",
              "consume_wait_seconds", "avg_queue_occupancy", "queue_peak",
              "batches_prepared", "batches_staged"):
        assert k in d


def test_process_pool_with_picklable_fn():
    ds, _ = synthetic_classification(96, 8, seed=17)
    src = list(ds.batches(16, shuffle=False))
    seq = [_double_idx(b) for b in src]
    par = list(IngestPipeline(iter(src), _double_idx, workers=2,
                              pool="process"))
    for a, b in zip(seq, par):
        _assert_batches_equal(a, b)


def _double_idx(b):
    return type(b)(b.idx * np.int32(2), b.val, b.label, b.field,
                   n_valid=b.n_valid, fieldmajor=b.fieldmajor)


def test_ffm_process_pool_prep_bit_exact():
    """-ingest_pool process on the flagship prep (canonicalize + pack via
    the picklable FFMPrep config, NOT a bound trainer method): bit-exact
    vs the thread pool and the sequential path, in order."""
    import json
    from hivemall_tpu.io.sparse import SparseDataset
    from hivemall_tpu.models.fm import FFMTrainer

    rng = np.random.default_rng(23)
    n, L, F = 256, 8, 8
    idx = rng.integers(1, 2048, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32) % F, (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L, dtype=np.int64)
    ds = SparseDataset(idx.ravel(), indptr, np.ones(n * L, np.float32),
                       lab, fld.ravel())
    cfg = ("-dims 2048 -factors 2 -fields 8 -mini_batch 64 "
           "-classification -iters 2")
    seq = FFMTrainer(cfg + " -ingest_workers 1").fit(ds)
    thr = FFMTrainer(cfg + " -ingest_workers 3 -ingest_pool thread").fit(ds)
    prc = FFMTrainer(cfg + " -ingest_workers 2 -ingest_pool process").fit(ds)
    assert prc.pipeline_stats.pool == "process"
    s = json.dumps(seq.model_table(), sort_keys=True, default=str)
    assert s == json.dumps(thr.model_table(), sort_keys=True, default=str)
    assert s == json.dumps(prc.model_table(), sort_keys=True, default=str)


def test_process_pool_without_picklable_prep_falls_back_to_threads():
    """A trainer whose parallel prep leg is bound-only must warn and run
    the thread pool instead of crashing in the child."""
    from hivemall_tpu.models.linear import GeneralClassifier

    class BoundPrep(GeneralClassifier):
        def _preprocess_train_parallel(self, batch):
            return batch

    ds, _ = synthetic_classification(128, 8, seed=24)
    t = BoundPrep("-dims 256 -mini_batch 32 -ingest_workers 2 "
                  "-ingest_pool process")
    with pytest.warns(RuntimeWarning, match="picklable"):
        t.fit(ds)
    assert t.pipeline_stats.pool == "thread"
    assert t.pipeline_stats.batches_prepared > 0


def test_base_trainer_process_pool_matches_sequential():
    from hivemall_tpu.models.linear import GeneralClassifier

    ds, _ = synthetic_classification(300, 20, seed=25)
    opts = "-dims 512 -loss logloss -opt adagrad -mini_batch 32"
    seq = GeneralClassifier(opts + " -ingest_workers 1").fit(ds)
    prc = GeneralClassifier(opts + " -ingest_workers 2 "
                                   "-ingest_pool process").fit(ds)
    np.testing.assert_array_equal(np.asarray(seq.w), np.asarray(prc.w))


def test_backpressure_bounds_inflight():
    """A slow consumer must not let the pipeline race ahead unbounded."""
    produced = []

    def src():
        for i in range(50):
            produced.append(i)
            yield i

    it = IngestPipeline(src(), lambda x: x, workers=2, depth=2)
    next(it)
    time.sleep(0.2)          # give the submitter time to run ahead
    # depth(2) queued + 2 executing + 1 pending put + 1 consumed, plus a
    # small scheduling margin — far below the 50-item source
    assert len(produced) <= 8
    it.close()


def test_auto_workers_positive():
    assert auto_workers() >= 1


def test_close_idempotent_all_modes():
    """close() is safe to call repeatedly, before or after consumption,
    in both threaded and sequential modes (the trainer's finally block and
    __del__ can both fire)."""
    ds, _ = synthetic_classification(160, 8, seed=30)
    it = IngestPipeline(ds.batches(16, shuffle=False), lambda b: b,
                        workers=3)
    next(it)
    it.close()
    it.close()                                # second close: no-op
    assert not it._submitter.is_alive()
    with pytest.raises(StopIteration):
        next(it)
    # close before any consumption
    it2 = IngestPipeline(ds.batches(16, shuffle=False), lambda b: b,
                         workers=3)
    it2.close()
    it2.close()
    assert not it2._submitter.is_alive()
    # sequential fallback has no threads to release but must stay safe
    it3 = IngestPipeline(ds.batches(16, shuffle=False), lambda b: b,
                         workers=1)
    next(it3)
    it3.close()
    it3.close()
    with pytest.raises(StopIteration):
        next(it3)


def test_drain_until_dead_wedged_producer_cancels():
    """The cancel=True path with a producer wedged OUTSIDE a queue op
    (e.g. a device_put that never returns): drain must give up after its
    timeout — abandoning the daemon thread — while still emptying the
    queue and cancelling every drained future."""
    import queue

    from hivemall_tpu.io.pipeline import drain_until_dead

    wedge = threading.Event()
    th = threading.Thread(target=wedge.wait, daemon=True)
    th.start()

    class _Fut:
        def __init__(self):
            self.cancelled = False

        def cancel(self):
            self.cancelled = True

    q: "queue.Queue" = queue.Queue()
    futs = [_Fut() for _ in range(3)]
    for f in futs:
        q.put(f)
    t0 = time.monotonic()
    drain_until_dead(q, th, timeout=0.2, cancel=True)
    assert time.monotonic() - t0 < 2.0       # returned despite live thread
    assert th.is_alive()                     # wedged producer abandoned
    assert q.empty()
    assert all(f.cancelled for f in futs)
    wedge.set()
    th.join(1)


def test_fit_ingest_workers_matches_sequential():
    """-ingest_workers N produces the same model as the sequential path."""
    from hivemall_tpu.models.linear import GeneralClassifier

    ds, _ = synthetic_classification(300, 20, seed=18)
    opts = "-dims 512 -loss logloss -opt adagrad -mini_batch 32 -iters 3"
    seq = GeneralClassifier(opts + " -ingest_workers 1").fit(ds)
    par = GeneralClassifier(opts + " -ingest_workers 4").fit(ds)
    np.testing.assert_array_equal(np.asarray(seq.w), np.asarray(par.w))
    assert par.pipeline_stats.batches_prepared > 0
    assert seq.pipeline_stats.batches_prepared > 0   # sequential also counts


def test_fit_stream_ingest_workers_matches_sequential():
    from hivemall_tpu.models.linear import GeneralClassifier

    ds, _ = synthetic_classification(256, 16, seed=19)
    opts = "-dims 512 -loss logloss -opt adagrad -mini_batch 32"
    seq = GeneralClassifier(opts + " -ingest_workers 1")
    seq.fit_stream(ds.batches(32, shuffle=False))
    par = GeneralClassifier(opts + " -ingest_workers 3")
    par.fit_stream(ds.batches(32, shuffle=False))
    np.testing.assert_array_equal(np.asarray(seq.w), np.asarray(par.w))


def test_ffm_fit_ingest_workers_matches_sequential():
    """The flagship path: canonicalize + (packed) prep across workers is
    bit-identical to sequential, shuffle included."""
    import json
    from hivemall_tpu.io.sparse import SparseDataset
    from hivemall_tpu.models.fm import FFMTrainer

    rng = np.random.default_rng(20)
    n, L, F = 256, 8, 8
    idx = rng.integers(1, 2048, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32) % F, (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L, dtype=np.int64)
    ds = SparseDataset(idx.ravel(), indptr, np.ones(n * L, np.float32),
                       lab, fld.ravel())
    cfg = ("-dims 2048 -factors 2 -fields 8 -mini_batch 64 "
           "-classification -iters 2")
    a = FFMTrainer(cfg + " -ingest_workers 1").fit(ds)
    b = FFMTrainer(cfg + " -ingest_workers 3").fit(ds)
    sa = json.dumps(a.model_table(), sort_keys=True, default=str)
    sb = json.dumps(b.model_table(), sort_keys=True, default=str)
    assert sa == sb


def test_elision_latch_deterministic_on_mixed_dataset():
    """The unit-value elision latch is stream-order state: it must run on
    the serial leg, so a MIXED dataset (real-valued batches before
    unit-valued ones) preps to identical representations under N workers
    as sequentially — batch for batch, val=None included."""
    from hivemall_tpu.io.sparse import SparseBatch, SparseDataset
    from hivemall_tpu.models.linear import GeneralClassifier

    rng = np.random.default_rng(22)
    n, L = 320, 4
    idx = rng.integers(1, 200, (n, L)).astype(np.int32)
    val = np.ones((n, L), np.float32)
    val[:40] = rng.uniform(0.5, 1.5, (40, L))   # first batches non-unit
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L, dtype=np.int64)
    ds = SparseDataset(idx.ravel(), indptr, val.ravel(), lab)

    def run(workers):
        t = GeneralClassifier("-dims 256 -mini_batch 32")
        closers = []
        t.opts["ingest_workers"] = workers
        out = list(t._ingest_iter(ds.batches(32, shuffle=False), closers))
        for c in closers:
            c()
        return out

    for a, b in zip(run(1), run(4)):
        _assert_batches_equal(a, b)
        assert a.val is not None       # latch tripped by the first batch


def test_parquet_decode_ahead_bit_exact():
    """Decode-ahead only moves the shard read/parse off the consuming
    thread; shuffled epoch batches stay bit-identical."""
    pytest.importorskip("pyarrow")
    import tempfile
    from hivemall_tpu.io.arrow import ParquetStream, write_parquet_shards
    from hivemall_tpu.io.sparse import SparseDataset

    rng = np.random.default_rng(21)
    n, L = 300, 6
    idx = rng.integers(1, 512, (n, L)).astype(np.int32)
    lab = rng.normal(0, 1, n).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L, dtype=np.int64)
    ds = SparseDataset(idx.ravel(), indptr,
                       rng.uniform(0.5, 1.5, n * L).astype(np.float32), lab)
    with tempfile.TemporaryDirectory() as tmp:
        write_parquet_shards(ds, tmp, rows_per_shard=64)
        sync = ParquetStream(tmp, decode_ahead=0)
        ahead = ParquetStream(tmp, decode_ahead=2)
        a = list(sync.batches(32, epochs=2, shuffle=True, seed=9))
        b = list(ahead.batches(32, epochs=2, shuffle=True, seed=9))
        assert len(a) == len(b) > 0
        for x, y in zip(a, b):
            _assert_batches_equal(x, y)
        assert ahead.stats.batches_prepared == len(ahead.files) * 2
