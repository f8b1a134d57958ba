"""The train path names its own time (docs/OBSERVABILITY.md "Span tracing"):
spans carry thread, parent and the ids of the unit of work they belong to
(`batch`, `seq`); the sites sit where the work happens and not around a
wait or a suspended generator; the step's phases are named scopes in the
lowered program; an enabled tracer mirrors its spans into a live
jax.profiler session, on the profiler's clock; and the public hooks
(`on_dispatch`, `loss_sink`) see what the private ones see."""

import glob
import inspect
import itertools
import os
import queue
import threading
import time
import tracemalloc

import jax
import numpy as np
import pytest
from conftest import tracer_spans as _spans

from hivemall_tpu.io.arrow import (ParquetStream, _concat_datasets,
                                   _take_rows, write_parquet_shards)
from hivemall_tpu.io.pipeline import IngestPipeline, PipelineStats
from hivemall_tpu.io.prefetch import DevicePrefetcher
from hivemall_tpu.io.sparse import SparseBatch, SparseDataset
from hivemall_tpu.models.fm import FFMTrainer, FMTrainer
from hivemall_tpu.models.linear import GeneralClassifier
from hivemall_tpu.obs.trace import _NULL_SPAN, Tracer, get_tracer



def _ds(n=2200, L=8, dims=1 << 12, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    return SparseDataset(idx.ravel(), np.arange(0, n * L + 1, L),
                         np.ones(n * L, np.float32), lab)


def _shards(tmp_path, n=2200, rows_per_shard=500, **kw):
    d = str(tmp_path / "shards")
    write_parquet_shards(_ds(n, **kw), d, rows_per_shard=rows_per_shard)
    return d


LINEAR = "-dims 4096 -loss logloss -opt adagrad -reg no -mini_batch 64"


# --- what a span records -----------------------------------------------------

def _record_nested(t):
    with t.span("outer", 7):
        with t.span("inner", None, 3):
            pass
        t.add_span("measured", 0.001)
    return "outer"


def _record_cross_thread(t):
    def work():
        with t.span("inner", None, 3):
            t.add_span("measured", 0.001)
    with t.span("outer", 7):
        th = threading.Thread(target=work, name="worker-x")
        th.start()
        th.join()
    return None


@pytest.mark.parametrize("record", [_record_nested, _record_cross_thread])
def test_span_records_parent_thread_and_ids(record):
    t = Tracer(enabled=True)
    inner_parent = record(t)
    by = {e["name"]: e for e in _spans(t)}
    outer, inner, measured = by["outer"], by["inner"], by["measured"]
    assert outer["args"]["seq"] == 7 and "parent" not in outer["args"]
    assert outer["args"]["thread"] == threading.current_thread().name
    assert outer["tid"] == threading.get_ident()
    assert inner["args"]["batch"] == 3 and "seq" not in inner["args"]
    ids = {e["args"]["id"] for e in by.values()}
    assert len(ids) == 3
    if inner_parent:
        # nested on one thread: the innermost open span is the parent,
        # for an interval handed over ready-measured too
        assert inner["args"]["parent"] == outer["args"]["id"]
        assert measured["args"]["parent"] == outer["args"]["id"]
        assert inner["tid"] == outer["tid"]
    else:
        # another thread's open span is nobody's parent here
        assert "parent" not in inner["args"]
        assert inner["args"]["thread"] == "worker-x"
        assert inner["tid"] != outer["tid"]
        assert measured["args"]["parent"] == inner["args"]["id"]


def test_span_args_dict_reaches_the_export():
    t = Tracer(enabled=True)
    with t.span("s", 1, 2, {"rows": 64}):
        pass
    args = _spans(t, "s")[0]["args"]
    assert (args["seq"], args["batch"], args["rows"]) == (1, 2, 64)


@pytest.mark.parametrize("site", [
    ("dispatch.megastep", 5, 9), ("h2d.stage", 5, None),
    ("source.assemble", None, 9), ("feed.wait_slot", 5, None),
    ("ingest.wait_prep", None, 9), ("ingest.wait_slot", None, 9),
    ("source.decode", None, None)], ids=lambda site: site[0])
def test_disabled_span_builds_nothing_at_the_new_sites(site):
    """`span(name, seq, batch)` of a disabled tracer: the shared no-op, no
    `**kwargs` in the signature (that alone would build a dict a call),
    and not a byte allocated over a thousand calls (the `with` protocol
    binds `__enter__`/`__exit__` itself, whatever the object: left out).
    `as sp` binds None, which is how a site that sets `sp.args` inside
    the block knows to build nothing."""
    t = Tracer(enabled=False)
    assert t.span(*site) is _NULL_SPAN
    with t.span(*site) as sp:
        assert sp is None
    kinds = {p.kind for p in inspect.signature(t.span).parameters.values()}
    assert inspect.Parameter.VAR_KEYWORD not in kinds
    span = t.span
    calls = itertools.repeat(None, 1000)
    tracemalloc.start()
    try:
        span("warm", 5, 9)
        tracemalloc.reset_peak()
        before = tracemalloc.get_traced_memory()[0]
        for _ in calls:
            span(*site)
        now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert now == before and peak == before
    assert t.rollup() == {}


# --- two clocks ----------------------------------------------------------------

def _sleeps(seconds):
    time.sleep(seconds)


def _spins(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.mark.parametrize("body,on_cpu", [(_sleeps, False), (_spins, True)])
def test_span_records_the_threads_cpu_seconds_beside_its_wall(body, on_cpu):
    """`args.cpu` (microseconds, like `dur`) is what the thread ran inside
    the span: far under `dur` where it slept, within 20% of it where it
    spun. Read in sums over a few spans: a host may tick its thread
    clock in steps of 10 ms (the chip machine's does), and a spin can
    lose its core to a neighbour on a shared host, so the best of three
    attempts counts."""
    shares = []
    for _ in range(3):
        t = Tracer(enabled=True)
        for _ in range(5):
            with t.span("s"):
                body(0.03)
        evs = _spans(t, "s")
        assert all(e["dur"] >= 30_000 and e["args"]["cpu"] >= 0
                   for e in evs)
        shares.append(sum(e["args"]["cpu"] for e in evs)
                      / sum(e["dur"] for e in evs))
        if not on_cpu or abs(shares[-1] - 1.0) <= 0.2:
            break
    if on_cpu:
        assert min(abs(s - 1.0) for s in shares) <= 0.2, shares
    else:
        assert shares[0] < 0.2


@pytest.mark.parametrize("measured", [False, True],
                         ids=["span", "add_span"])
def test_rollup_carries_cpu_seconds(measured):
    """`rollup()` has `cpu_s` beside `total_s` for every stage; an
    interval handed over ready-measured (`add_span`) has no CPU clock:
    no `args.cpu`, and nothing added to its stage's `cpu_s`."""
    t = Tracer(enabled=True)
    if measured:
        t.add_span("s", 0.02)
    else:
        with t.span("s"):
            _spins(0.05)
    st = t.rollup()["s"]
    assert set(st) == {"count", "total_s", "cpu_s", "p50", "p99"}
    (ev,) = _spans(t, "s")
    if measured:
        assert st["cpu_s"] == 0.0 and "cpu" not in ev["args"]
    else:
        assert 0.0 < st["cpu_s"] <= st["total_s"] + 0.011   # a tick's room
        assert st["cpu_s"] == pytest.approx(ev["args"]["cpu"] * 1e-6,
                                            abs=2e-6)


# --- the feed's waits ----------------------------------------------------------

WAITS = ("source.wait_shard", "feed.wait_slot", "ingest.wait_prep",
         "ingest.wait_slot")


def _seconds(events):
    return sum(e["dur"] for e in events) * 1e-6


class _TimedQueue(queue.Queue):
    """A queue that keeps what each thread spent inside `put`."""

    puts = None                       # [(thread name, seconds)], set per test

    def put(self, item, *a, **kw):
        t0 = time.perf_counter()
        super().put(item, *a, **kw)
        self.puts.append((threading.current_thread().name,
                          time.perf_counter() - t0))


def test_prefetcher_blocked_on_its_queue_is_a_span(tracer, monkeypatch):
    """`feed.wait_slot`: `h2d-prefetch` blocked on `q.put(staged)` because
    the consumer has not taken the previous input; it carries the staged
    input's `seq`, and its seconds are what the thread spent in `put`."""
    monkeypatch.setattr(_TimedQueue, "puts", [])
    monkeypatch.setattr(queue, "Queue", _TimedQueue)
    n, nap = 6, 0.03
    src = [SparseBatch(np.ones((4, 2), np.int32), None,
                       np.ones(4, np.float32), seq=i) for i in range(n)]
    got = []
    for b in DevicePrefetcher(iter(src), depth=1):
        time.sleep(nap)               # the slow consumer
        got.append(b.seq)
    assert got == list(range(n))
    waits = _spans(tracer, "feed.wait_slot")
    assert [e["args"]["seq"] for e in waits] == list(range(n))
    assert {e["args"]["thread"] for e in waits} == {"h2d-prefetch"}
    assert all("parent" not in e["args"] for e in waits)
    # one staged input sits in the queue, so all but the first two puts
    # wait out a nap of the consumer's; the pill's put is no span
    puts = [s for name, s in _TimedQueue.puts if name == "h2d-prefetch"]
    assert len(puts) == n + 1
    assert _seconds(waits) == pytest.approx(sum(puts[:n]), abs=1e-3 * n)
    assert _seconds(waits) > (n - 2) * nap * 0.8
    # a wait burns no CPU
    assert sum(e["args"]["cpu"] for e in waits) < 0.2 * sum(
        e["dur"] for e in waits)


@pytest.mark.parametrize("slow", ["fn", "consumer"])
def test_pipeline_waits_are_spans_equal_to_their_counters(tracer, slow):
    """`ingest.wait_prep` (the consumer blocked on the pool, beside
    `prep_wait_seconds`) and `ingest.wait_slot` (`ingest-source` blocked
    on the full queue, beside `prep_backpressure_seconds`): each span
    total equals its counter within a millisecond a span."""
    n, nap = 12, 0.02
    stats = PipelineStats()

    def fn(x):
        if slow == "fn":
            time.sleep(nap)
        return x

    out = []
    for x in IngestPipeline(iter(range(n)), fn, workers=2, depth=2,
                            stats=stats):
        if slow == "consumer":
            time.sleep(nap)
        out.append(x)
    assert out == list(range(n))
    prep = _spans(tracer, "ingest.wait_prep")
    slot = _spans(tracer, "ingest.wait_slot")
    me = threading.current_thread().name
    # one wait a batch and one for the stream's end, on the consumer's
    # thread; one put a batch on the submitter's
    assert [e["args"]["batch"] for e in prep] == list(range(n)) + [n]
    assert {e["args"]["thread"] for e in prep} == {me}
    assert [e["args"]["batch"] for e in slot] == list(range(n))
    assert {e["args"]["thread"] for e in slot} == {"ingest-source"}
    assert _seconds(prep) == pytest.approx(stats.prep_wait_seconds,
                                           abs=1e-3 * len(prep))
    assert _seconds(slot) == pytest.approx(stats.prep_backpressure_seconds,
                                           abs=1e-3 * len(slot))
    # a slow pool keeps the consumer waiting (and, the queue full, the
    # submitter too); a slow consumer keeps the submitter waiting alone
    assert _seconds(prep if slow == "fn" else slot) > 0.25 * n * nap
    if slow == "consumer":
        assert _seconds(prep) < 0.25 * n * nap


def test_sequential_pipeline_has_no_wait_spans(tracer):
    assert list(IngestPipeline(iter(range(5)), lambda x: x, workers=1)) \
        == list(range(5))
    assert len(_spans(tracer, "ingest.prep")) == 5
    assert not _spans(tracer, "ingest.wait_prep")
    assert not _spans(tracer, "ingest.wait_slot")


@pytest.mark.parametrize("decode_ahead", [1, 0])
def test_shard_decode_is_a_span_on_the_decode_thread(tracer, tmp_path,
                                                     decode_ahead):
    """`source.decode`: one span a shard through `_shard()` on the
    `pq-decode` thread, with the shard's rows, the same seconds as
    `stream.stats.prep_seconds`. With `decode_ahead=0` nothing new:
    `source.wait_shard` is the decode there."""
    d = _shards(tmp_path, n=1000, rows_per_shard=300)      # 4 shards
    stream = ParquetStream(d, decode_ahead=decode_ahead)
    assert sum(1 for _ in stream.batches(64, epochs=1, seed=1)) == 16
    decodes = _spans(tracer, "source.decode")
    assert len(_spans(tracer, "source.wait_shard")) == 4
    if not decode_ahead:
        assert not decodes
        return
    assert sorted(e["args"]["rows"] for e in decodes) == [100, 300, 300, 300]
    assert all(e["args"]["thread"].startswith("pq-decode") for e in decodes)
    assert all("parent" not in e["args"] for e in decodes)
    assert _seconds(decodes) == pytest.approx(stream.stats.prep_seconds,
                                              abs=1e-3 * len(decodes))


@pytest.mark.parametrize("workers", [1, 2])
def test_feed_loop_threads_are_tiled_by_waits_and_work(tracer, tmp_path,
                                                       workers):
    """A loop thread of the feed (`h2d-prefetch`; with a prep pool also
    `ingest-source`): its window = its wait spans + its top-level work
    spans + a remainder (the program's own Python between spans), with
    remainder >= 0. That needs every wait to be a top-level span and no
    span enveloping the loop."""
    d = _shards(tmp_path)
    t = GeneralClassifier(f"{LINEAR} -steps_per_dispatch 4 "
                          f"-ingest_workers {workers}")
    _prefetching(t)
    t.fit_stream(ParquetStream(d).batches(64, epochs=1, seed=3))
    loops = {"h2d-prefetch"} | ({"ingest-source"} if workers > 1 else set())
    by_thread = {}
    for e in _spans(tracer):
        by_thread.setdefault(e["args"]["thread"], []).append(e)
    assert loops <= set(by_thread)
    for name in loops:
        evs = by_thread[name]
        top = [e for e in evs if "parent" not in e["args"]]
        waits = [e for e in evs if e["name"] in WAITS]
        assert waits and all("parent" not in e["args"] for e in waits)
        work = [e for e in top if e["name"] not in WAITS]
        assert work
        window = max(e["ts"] + e["dur"] for e in evs) \
            - min(e["ts"] for e in evs)
        covered = sum(e["dur"] for e in top)
        remainder = window - covered
        # top-level spans of one thread never overlap, so they fit the
        # window (3 us of rounding a span in the export)
        assert remainder >= -3.0 * len(top), (name, remainder)
        assert sum(e["dur"] for e in waits) + sum(e["dur"] for e in work) \
            + remainder == pytest.approx(window)
        top.sort(key=lambda e: e["ts"])
        for a, b in zip(top, top[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 3.0, (name, a, b)
    # what each loop thread waits for
    names = {n: {e["name"] for e in by_thread[n]} for n in loops}
    assert "feed.wait_slot" in names["h2d-prefetch"]
    if workers > 1:
        assert "ingest.wait_prep" in names["h2d-prefetch"]
        assert {"source.wait_shard", "ingest.wait_slot"} \
            <= names["ingest-source"]
    else:
        assert "source.wait_shard" in names["h2d-prefetch"]
    assert tracer.dropped == 0


# --- ids through the pipeline -------------------------------------------------

def _prefetching(trainer):
    """Have fit_stream stage through a DevicePrefetcher, as it does on an
    accelerator (on the CPU it feeds the loop straight from the stager),
    so that h2d.stage runs on its own thread."""
    wrap = trainer._wrap_megabatch
    trainer._wrap_megabatch = lambda it, *, prefetch: DevicePrefetcher(
        wrap(it, prefetch=False), stats=trainer.pipeline_stats)


@pytest.mark.parametrize("workers", [1, 2])
def test_seq_ties_h2d_stage_to_its_dispatch(tracer, tmp_path, workers):
    d = _shards(tmp_path)                       # 2200 rows: 34 batches + 1
    K = 4
    t = GeneralClassifier(f"{LINEAR} -steps_per_dispatch {K} "
                          f"-ingest_workers {workers}")
    _prefetching(t)
    seen = []
    t.fit_stream(ParquetStream(d).batches(64, epochs=1, seed=3),
                 on_dispatch=lambda seq, steps, ex: seen.append((seq, steps)))
    mega = _spans(tracer, "dispatch.megastep")
    single = _spans(tracer, "dispatch.step")
    assert len(mega) == 35 // K and len(single) == 35 % K
    # in dispatch order seq counts up from 0, singles of the ragged tail
    # included, and on_dispatch saw the same numbers
    order = sorted(mega + single, key=lambda e: e["ts"])
    assert [e["args"]["seq"] for e in order] == list(range(len(order)))
    assert seen == [(i, K if i < len(mega) else 1)
                    for i in range(len(order))]
    staged = {e["args"]["seq"]: e for e in _spans(tracer, "h2d.stage")}
    stacked = {e["args"]["seq"]: e for e in _spans(tracer, "stager.stack")}
    assert sorted(staged) == list(range(len(order)))
    for e in mega:
        seq = e["args"]["seq"]
        h2d, stack = staged[seq], stacked[seq]
        # staged before it was dispatched, stacked before it was staged,
        # on the prefetcher's thread and not on the loop's
        assert h2d["ts"] + h2d["dur"] <= e["ts"]
        assert stack["ts"] + stack["dur"] <= h2d["ts"]
        assert h2d["args"]["thread"] == "h2d-prefetch" != e["args"]["thread"]
        assert stack["args"]["batch"] == seq * K
    # every source batch has its ordinal on each stage it passed
    for name in ("source.assemble", "source.note_batch", "ingest.prep"):
        assert sorted(e["args"]["batch"] for e in _spans(tracer, name)) \
            == list(range(35)), name
    assert tracer.dropped == 0


def test_loop_spans_cover_waits_folds_and_cadence(tracer):
    ds = _ds(64 * 260)                     # 260 steps: one 256-step fold
    t = GeneralClassifier(f"{LINEAR} -steps_per_dispatch 4")
    t.fit(ds, epochs=1, shuffle=False, prefetch=True)
    waits = _spans(tracer, "loop.wait_input")
    assert len(waits) == 65 + 1            # one per dispatch + the end
    loop_thread = {e["tid"] for e in _spans(tracer, "dispatch.megastep")}
    assert {e["tid"] for e in waits} == loop_thread
    assert len(_spans(tracer, "loop.fold_loss")) >= 1
    folds = _spans(tracer, "loop.fold_loss")
    cadence = _spans(tracer, "loop.cadence")
    assert len(cadence) == 1
    # the fold is outside the cadence span: the two do not overlap
    f, c = folds[0], cadence[0]
    assert f["ts"] + f["dur"] <= c["ts"]


def test_source_assemble_leaves_out_the_consumers_time(tracer, tmp_path):
    """A slow consumer sits between two `next()` calls, where the
    generator is suspended at `yield`: no span may cover that."""
    d = _shards(tmp_path, n=1000, rows_per_shard=300)
    nap = 0.03
    n = 0
    t0 = time.perf_counter()
    for _ in ParquetStream(d).batches(64, epochs=1, seed=1):
        time.sleep(nap)
        n += 1
    wall = time.perf_counter() - t0
    spans = _spans(tracer, "source.assemble")
    assert n == 16 and [e["args"]["batch"] for e in spans] == list(range(16))
    busy = sum(e["dur"] for e in spans) * 1e-6
    assert max(e["dur"] for e in spans) * 1e-6 < nap
    assert busy < wall - n * nap + 0.005
    # the wait for a decoded shard has a span of its own, outside these
    waits = _spans(tracer, "source.wait_shard")
    assert len(waits) == 4
    for w in waits:
        assert not any(e["ts"] < w["ts"] + w["dur"] and w["ts"]
                       < e["ts"] + e["dur"] for e in spans)


def _parent_batches(stream, batch_size, *, epochs, shuffle, seed, max_len):
    """ParquetStream.batches as it was before it had spans (commit
    26868bc), kept here as the oracle for bit-identical shuffles."""
    L = max_len or stream.max_row_len
    rng = np.random.default_rng(seed)
    for _ in range(epochs):
        order = rng.permutation(len(stream.files)) if shuffle \
            else np.arange(len(stream.files))
        carry = None
        for ds in stream._iter_shards([stream.files[fi] for fi in order]):
            if carry is not None:
                ds = _concat_datasets(carry, ds)
                carry = None
            n = len(ds)
            n_full = (n // batch_size) * batch_size
            row_order = rng.permutation(n) if shuffle else np.arange(n)
            full = _take_rows(ds, row_order[:n_full])
            yield from full.batches(batch_size, shuffle=False, max_len=L)
            if n_full < n:
                carry = _take_rows(ds, row_order[n_full:])
        if carry is not None and len(carry):
            yield from carry.batches(batch_size, shuffle=False, max_len=L)


@pytest.mark.parametrize("shuffle,batch,rows_per_shard,traced", [
    (True, 64, 300, False), (True, 64, 300, True), (False, 64, 300, False),
    (True, 512, 300, False),       # shards smaller than a batch: carries
])
def test_stream_batches_bit_identical_to_the_unspanned_loop(
        tmp_path, shuffle, batch, rows_per_shard, traced):
    d = _shards(tmp_path, n=1000, rows_per_shard=rows_per_shard)
    tr = get_tracer()
    tr.reset()
    if traced:
        tr.enable()
    try:
        got = list(ParquetStream(d).batches(batch, epochs=2, shuffle=shuffle,
                                            seed=11, max_len=8))
    finally:
        tr.disable()
        tr.reset()
    want = list(_parent_batches(ParquetStream(d), batch, epochs=2,
                                shuffle=shuffle, seed=11, max_len=8))
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g.n_valid == w.n_valid
        for a in ("idx", "val", "label"):
            np.testing.assert_array_equal(getattr(g, a), getattr(w, a))


# --- phase scopes in the step -------------------------------------------------

def _fm(extra):
    return FMTrainer(f"-dims 4096 -factors 5 -mini_batch 64 -classification "
                     f"{extra}")


def _ffm(extra):
    return FFMTrainer(f"-dims 4096 -factors 4 -fields 8 -mini_batch 64 "
                      f"-opt adagrad -classification {extra}")


def _rda():
    from hivemall_tpu.models.linear import GeneralClassifier
    return GeneralClassifier("-loss logloss -opt adagrad -dims 4096 "
                             "-mini_batch 64")


@pytest.mark.parametrize("make,step", [
    (lambda: _fm("-opt adagrad"), "_step"),                # minibatch
    (lambda: _fm("-opt adagrad -fm_update occurrence"), "_step"),  # fused
    (lambda: _ffm(""), "_step_fm_unit"),                   # the flagship's
    (lambda: _rda(), "_step"),           # ops/linear.py, AdaGrad-RDA (PR 33)
], ids=["fm_minibatch", "fm_fused", "ffm_fused", "linear_rda"])
def test_megastep_lowering_names_the_phases(make, step):
    import jax.numpy as jnp
    from hivemall_tpu.ops.scan import SCOPES, megastep_for
    t = make()
    core = getattr(t, step)
    mega = megastep_for(core, none_val=(step == "_step")).__wrapped__
    K, B, L = 2, 64, 8
    idx = jnp.ones((K, B, L), jnp.int32)
    nv = jnp.full((K,), B, jnp.int32)
    lab = jnp.ones((K, B), jnp.float32)
    text = mega.lower(t.params, t.opt_state, 0.0, nv, idx, None, lab, None,
                      None).as_text(debug_info=True)
    for scope in ("hm.scan", "hm.gather", "hm.grad", "hm.scatter",
                  "hm.update"):
        assert f"{scope}/" in text, scope
    # the scopes' version is in the module's name: the persistent compile
    # cache's key holds the name and leaves scopes out
    assert f"megastep_{SCOPES}" in text


# --- one clock -----------------------------------------------------------------

def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, dict(e.stats), e.start_ns,
                            e.duration_ns))
    return out


def test_spans_are_annotations_inside_a_profiler_session_only(
        tracer, tmp_path):
    with tracer.span("outside.before", 1):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("inside.outer", 4, 2):
            with tracer.span("inside.inner"):
                time.sleep(0.002)
        tracer.disable()
        with tracer.span("inside.disabled"):
            pass
        tracer.enable()
    finally:
        jax.profiler.stop_trace()
    with tracer.span("outside.after"):
        pass
    events = {name: (stats, start, dur)
              for name, stats, start, dur in _host_events(str(tmp_path))}
    assert "inside.outer" in events and "inside.inner" in events
    assert not {"outside.before", "outside.after",
                "inside.disabled"} & set(events)
    stats, start, dur = events["inside.outer"]
    assert int(stats["seq"]) == 4 and int(stats["batch"]) == 2
    _, istart, idur = events["inside.inner"]
    assert start <= istart and istart + idur <= start + dur   # one clock
    assert idur >= 2_000_000
    # the tracer's own ring holds all four enabled spans either way
    assert {e["name"] for e in _spans(tracer)} == {
        "outside.before", "inside.outer", "inside.inner", "outside.after"}


# --- public hooks beside the private ones --------------------------------------

@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("sink", ["list", "callable"])
def test_public_hooks_see_what_the_private_ones_see(tmp_path, k, sink):
    d = _shards(tmp_path, n=1000, rows_per_shard=400)
    t = GeneralClassifier(f"{LINEAR} -steps_per_dispatch {k}")
    got = []
    t.loss_sink = got if sink == "list" else got.append
    t._trace_losses = []
    private, public = [], []
    inner = t._dispatch

    def dispatch(b):
        ex, step = t._examples, t._t
        inner(b)
        private.append((len(private), t._t - step, t._examples - ex))

    t._dispatch = dispatch
    t.fit_stream(ParquetStream(d).batches(64, epochs=1, seed=5),
                 on_dispatch=lambda *a: public.append(a))
    assert public == private and len(public) == (16 if k == 1 else 4)
    assert sum(ex for _, _, ex in public) == 1000
    assert got == t._trace_losses and len(got) == 16
    assert all(isinstance(v, float) for v in got)


def test_ffm_replay_form_refuses_on_dispatch(tmp_path):
    t = _ffm("-pack_input on")
    with pytest.raises(ValueError, match="single-stream"):
        t.fit_stream(lambda: iter(()), epochs=2, on_dispatch=print)
