"""Training-side deep profiling (obs.devprof, docs/OBSERVABILITY.md
"Training profiling"): compile/retrace telemetry, the no-retrace
sentinel, device-memory accounting, drift watches, and the devprof
surface on /snapshot + /metrics."""

import io
import json

import numpy as np
import pytest

import hivemall_tpu.utils.metrics as M
from hivemall_tpu.io.sparse import SparseDataset
from hivemall_tpu.models.linear import GeneralClassifier, _linear_step_cached
from hivemall_tpu.obs.devprof import (DriftWatch, devprof_stub, get_devprof,
                                      instrument_factory)
from hivemall_tpu.obs.registry import registry


def _dataset(n=256, L=8, dims=1 << 10, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    return SparseDataset(idx.ravel(),
                         np.arange(0, n * L + 1, L, dtype=np.int64),
                         np.ones(n * L, np.float32), lab)


@pytest.fixture
def sink_stream():
    """Capture the metrics jsonl into a StringIO for the test's scope."""
    sink = io.StringIO()
    old = M._stream
    M._stream = M.MetricsStream(sink)
    try:
        yield sink
    finally:
        M._stream = old


def _events(sink):
    return [json.loads(line) for line in sink.getvalue().splitlines()
            if line]


# --- factory instrumentation -------------------------------------------------


def test_instrument_factory_counts_builds_only_on_miss():
    from functools import lru_cache

    dp = get_devprof()

    @instrument_factory("testmodel", "step")
    @lru_cache(maxsize=8)
    def factory(a, b):
        return (a, b)

    before = dict(dp.builds.get("testmodel.step") or {"count": 0})
    factory(1, 2)
    factory(1, 2)          # cache hit: no build
    factory(3, 4)          # second distinct config
    b = dp.builds["testmodel.step"]
    assert b["count"] - before["count"] == 2
    assert b["seconds"] >= 0.0
    # the lru surface survives the wrapper (tests/injection paths use it)
    assert factory.cache_info().hits >= 1
    raw = factory
    while hasattr(raw, "__wrapped__"):
        raw = raw.__wrapped__
    assert raw(1, 2) == (1, 2)


def test_shape_bucket_dedup():
    dp = get_devprof()
    n0 = len(dp._buckets)
    dp.note_bucket("test_site", 64, 16)
    dp.note_bucket("test_site", 64, 16)      # dup: no growth
    dp.note_bucket("test_site", 128, 16)
    assert len(dp._buckets) == n0 + 2


# --- no-retrace sentinel -----------------------------------------------------


def _unwrapped(factory):
    """The uncached builder under a config-cached step factory."""
    while hasattr(factory, "__wrapped__"):
        factory = factory.__wrapped__
    return factory


def _linear_case():
    dims, B = 1 << 10, 64
    opts = f"-dims {dims} -mini_batch {B} -opt adagrad"

    def inject(t):
        t._step = _unwrapped(_linear_step_cached)(
            "hingeloss", "adagrad", str(t.opts.eta), float(t.opts.eta0),
            t.opts.total_steps, t.opts.power_t, str(t.opts.reg),
            t.opts["lambda"], t.opts.l1_ratio)

    return _dataset(dims=dims), (lambda: GeneralClassifier(opts)), inject


def _ffm_case():
    """The flagship's recipe at toy size: Criteo-shaped rows (one feature
    per field) through the joint layout's three cached steps."""
    from hivemall_tpu.models.fm import FFMTrainer, _ffm_step_fused_cached
    n, L, dims = 256, 8, 1 << 12
    rng = np.random.default_rng(21)
    ds = SparseDataset(
        rng.integers(1, dims, n * L).astype(np.int32),
        np.arange(0, n * L + 1, L, dtype=np.int64),
        np.ones(n * L, np.float32),
        (rng.integers(0, 2, n) * 2 - 1).astype(np.float32),
        np.tile(np.arange(L, dtype=np.int32), n))
    opts = (f"-dims {dims} -factors 2 -fields {L} -mini_batch 64 "
            f"-opt adagrad -classification -halffloat")

    def inject(t):
        o = t.opts
        head = (t._loss_name, *t._opt_key,
                (o.lambda0, o.lambda_w, o.lambda_v), t.F, t.k)
        raw = _unwrapped(_ffm_step_fused_cached)
        t._step = raw(*head, False, False)
        t._step_fm = raw(*head, True, False)
        t._step_fm_unit = raw(*head, True, True)

    return ds, (lambda: FFMTrainer(opts)), inject


@pytest.mark.parametrize("case", [_linear_case, _ffm_case],
                         ids=["linear", "ffm"])
def test_warmed_epoch_adds_zero_compiles_and_injection_is_caught(
        sink_stream, case):
    """The acceptance invariant: with the config caches intact a warmed
    epoch (and a duplicate-config trainer) adds ZERO XLA compiles; a
    fresh closure bypassing the factory compiles and is flagged as a
    `retrace` — counter + jsonl event."""
    dp = get_devprof()
    ds, make, inject = case()
    t = make()
    t.fit(ds, epochs=1, shuffle=False)          # warmup epoch
    dp.arm()
    try:
        c0, r0 = dp.compiles, dp.retraces
        t.fit(ds, epochs=1, shuffle=False)
        assert dp.compiles == c0, "warmed epoch recompiled"
        t2 = make()                             # dup config, caches intact
        t2.fit(ds, epochs=1, shuffle=False)
        assert dp.compiles == c0, "cached duplicate-config recompiled"
        # the disease: fresh jitted closures instead of the cached steps
        t3 = make()
        inject(t3)
        t3.fit(ds, epochs=1, shuffle=False)
        assert dp.compiles > c0 and dp.retraces > r0
        evs = _events(sink_stream)
        retr = [e for e in evs if e["event"] == "retrace"]
        assert retr and retr[0]["seconds"] > 0
    finally:
        dp.disarm()


def test_train_done_auto_arms():
    dp = get_devprof()
    dp.disarm()
    t = GeneralClassifier("-dims 256 -mini_batch 32")
    t.fit(_dataset(n=64, dims=256), epochs=1, shuffle=False)
    assert dp.armed        # one completed fit = warmup over
    dp.disarm()


# --- memory accounting -------------------------------------------------------


def test_sample_memory_gauges():
    dp = get_devprof()
    rec = dp.sample_memory()
    assert set(rec) == {"live_arrays", "live_bytes", "bytes_in_use",
                        "peak_bytes_in_use", "bytes_limit"}
    # a trainer's tables are live jax arrays — the census must see bytes
    t = GeneralClassifier("-dims 4096 -mini_batch 32")
    rec = dp.sample_memory()
    assert rec["live_arrays"] >= 1
    assert rec["live_bytes"] >= 4096 * 4
    assert t is not None


def test_telemetry_cadence_carries_devprof_memory(sink_stream):
    t = GeneralClassifier("-dims 512 -mini_batch 32 -telemetry_every 4")
    t.fit(_dataset(n=256, dims=512), epochs=1, shuffle=False)
    tele = [e for e in _events(sink_stream) if e["event"] == "telemetry"]
    assert tele
    dp_sec = tele[-1]["snapshot"]["devprof"]
    assert dp_sec["memory"]["live_bytes"] > 0
    assert dp_sec["dispatches"] > 0


# --- drift watches -----------------------------------------------------------


def test_drift_watch_flags_step_regression(sink_stream):
    """A sustained 50x step-time regression after a stable warmup must
    cross the self-calibrated threshold and emit the named event."""
    rng = np.random.default_rng(3)
    w = DriftWatch("step_ms", "train_drift", warmup=16)
    for _ in range(64):
        w.update(1.0 + 0.01 * rng.standard_normal())
    assert w.events == 0
    for _ in range(32):
        w.update(50.0 + 0.01 * rng.standard_normal())
    assert w.events >= 1
    evs = [e for e in _events(sink_stream) if e["event"] == "train_drift"]
    assert evs and evs[0]["series"] == "step_ms"
    assert evs[0]["stage"] in ("outlier", "change")


# --- registry + HTTP surface -------------------------------------------------


def test_devprof_section_on_snapshot_and_metrics():
    from hivemall_tpu.obs.http import ObsServer
    import urllib.request

    get_devprof()                       # ensure the live provider is in
    t = GeneralClassifier("-dims 256 -mini_batch 32")
    t.fit(_dataset(n=64, dims=256), epochs=1, shuffle=False)
    srv = ObsServer(0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        snap = json.loads(urllib.request.urlopen(
            base + "/snapshot", timeout=10).read())
        assert "devprof" in snap
        assert snap["devprof"]["compiles"] >= 0
        assert set(devprof_stub()) == set(snap["devprof"])
        text = urllib.request.urlopen(
            base + "/metrics", timeout=10).read().decode()
        assert "hivemall_tpu_devprof_compiles" in text
        assert "hivemall_tpu_devprof_retraces" in text
        assert "hivemall_tpu_devprof_memory_live_bytes" in text
        assert "hivemall_tpu_spans_dropped" in text
    finally:
        srv.stop()


def test_profile_env_routes_through_devprof(tmp_path, monkeypatch,
                                            sink_stream):
    """HIVEMALL_TPU_PROF=<dir> captures a jax.profiler trace of the
    first fit and emits a `profile` event carrying the dir."""
    dp = get_devprof()
    if dp._profiled:
        pytest.skip("a profile was already captured in this process")
    prof_dir = str(tmp_path / "prof")
    monkeypatch.setenv("HIVEMALL_TPU_PROF", prof_dir)
    t = GeneralClassifier("-dims 256 -mini_batch 32")
    t.fit(_dataset(n=64, dims=256), epochs=1, shuffle=False)
    evs = [e for e in _events(sink_stream) if e["event"] == "profile"]
    assert evs and evs[0]["dir"] == prof_dir
    import os
    assert os.path.isdir(prof_dir)
