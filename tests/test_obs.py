"""Unified telemetry tests (docs/OBSERVABILITY.md): span tracer, central
registry, Prometheus/HTTP surface, metrics-stream hardening, and the
under-concurrency guarantees — spans from multi-worker ingest and faulted
MIX exchanges are complete, the jsonl stream is never torn, and the
registry snapshot stays stable while a fit is running."""

import json
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

import hivemall_tpu.utils.metrics as M
from hivemall_tpu.io.sparse import SparseBatch
from hivemall_tpu.models.linear import GeneralClassifier
from hivemall_tpu.obs.http import ObsServer, to_prometheus
from hivemall_tpu.obs.registry import Registry, registry
from hivemall_tpu.obs.trace import Tracer, get_tracer


@pytest.fixture
def tracer():
    """The process tracer, enabled and reset for one test, always left
    disabled+clean (it is process-global)."""
    t = get_tracer()
    t.reset()
    t.enable()
    yield t
    t.disable()
    t.reset()


def _batches(n, bs=16, dims=256, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        idx = rng.integers(1, dims, (bs, 4)).astype(np.int32)
        val = rng.normal(size=(bs, 4)).astype(np.float32)
        lab = (rng.integers(0, 2, bs) * 2 - 1).astype(np.float32)
        out.append(SparseBatch(idx, val, lab))
    return out


# --- Tracer ----------------------------------------------------------------

def test_tracer_disabled_is_noop():
    t = Tracer(enabled=False)
    s1, s2 = t.span("a"), t.span("b")
    assert s1 is s2                     # shared null object, no allocation
    assert t.span("h2d.stage", 7) is s1 and t.span("c", None, 3) is s1
    with s1:
        pass
    assert t.rollup() == {}


def test_tracer_records_rollup_percentiles():
    t = Tracer(enabled=True)
    for dur in (0.001, 0.002, 0.003):
        with t.span("stage"):
            time.sleep(dur)
    r = t.rollup()
    assert set(r) == {"stage"}
    st = r["stage"]
    assert st["count"] == 3
    assert st["total_s"] >= 0.006
    assert 0 < st["p50"] <= st["p99"]
    t.reset()
    assert t.rollup() == {}


def test_tracer_thread_safe_recording():
    t = Tracer(enabled=True)

    def work():
        for _ in range(200):
            with t.span("conc"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert t.rollup()["conc"]["count"] == 800


def test_tracer_chrome_export(tmp_path):
    t = Tracer(enabled=True)
    with t.span("exported"):
        pass
    p = str(tmp_path / "trace.json")
    assert t.export_chrome(p) == p
    trace = json.loads(open(p).read())
    evs = trace["traceEvents"]
    assert evs and evs[0]["name"] == "exported" and evs[0]["ph"] == "X"
    assert evs[0]["dur"] >= 0 and "ts" in evs[0]


def test_tracer_ring_is_bounded():
    t = Tracer(enabled=True, ring=8)
    for _ in range(100):
        with t.span("r"):
            pass
    assert len(t._events) == 8          # ring, not unbounded growth
    assert t.rollup()["r"]["count"] == 100   # aggregates keep the truth


# --- Registry --------------------------------------------------------------

def test_registry_snapshot_merges_and_overrides():
    r = Registry()
    r.register("a", lambda: {"x": 1})
    r.register("a", lambda: {"x": 2})   # last wins
    r.register("b", lambda: {"y": True})
    snap = r.snapshot()
    assert snap["a"] == {"x": 2} and snap["b"] == {"y": True}
    assert "ts" in snap
    r.unregister("a")
    assert "a" not in r.snapshot()


def test_registry_provider_failure_is_isolated():
    r = Registry()
    r.register("bad", lambda: 1 / 0)
    r.register("good", lambda: {"ok": 1})
    snap = r.snapshot()
    assert snap["good"] == {"ok": 1}
    assert "ZeroDivisionError" in snap["bad"]["error"]


def test_global_registry_has_default_sections():
    snap = registry.snapshot()
    assert "mix" in snap and "checkpoint" in snap


def test_trainer_registers_pipeline_and_train_sections():
    tr = GeneralClassifier("-dims 128 -mini_batch 8")
    tr.fit_stream(iter(_batches(4, bs=8, dims=128)))
    snap = registry.snapshot()
    assert snap["train"]["trainer"] == "train_classifier"
    assert snap["train"]["step"] == 4
    assert snap["pipeline"]["batches_prepared"] == 4


def test_new_trainer_resets_mix_and_checkpoint_sections(tmp_path):
    """A later trainer without a mixer/autosaver must not inherit a still-
    alive earlier trainer's mix/checkpoint sections — construction is the
    reset (last-wins registration, every section trainer-bound)."""
    from hivemall_tpu.parallel.mix_service import MixServer
    srv = MixServer().start()
    try:
        a = GeneralClassifier(
            f"-dims 64 -mini_batch 8 -mix 127.0.0.1:{srv.port} "
            f"-mix_threshold 1 -mix_timeout 0.3 "
            f"-checkpoint_dir {tmp_path / 'ck'} -checkpoint_every 2")
        a.fit_stream(iter(_batches(4, bs=8, dims=64)))
        snap = registry.snapshot()
        assert snap["mix"]["active"] is True
        assert snap["checkpoint"]["configured"] is True
        b = GeneralClassifier("-dims 64 -mini_batch 8")   # a stays alive
        snap = registry.snapshot()
        # the inactive forms are the SHARED registry stubs (full key
        # mirrors of the live providers, so dashboards keep their keys)
        from hivemall_tpu.obs.registry import CHECKPOINT_STUB, MIX_STUB
        assert snap["mix"] == MIX_STUB
        assert snap["checkpoint"] == CHECKPOINT_STUB
        assert a is not b                                 # keep a referenced
        a._mixer.close_group()
    finally:
        srv.stop()


# --- Prometheus / HTTP surface ---------------------------------------------

def test_to_prometheus_exposition_format():
    text = to_prometheus({"ts": 1.5,
                          "pipeline": {"batches": 3, "busy_s": 0.25,
                                       "name": "skipped-string"},
                          "train": {"examples": 44776121,
                                    "ts": 1754180000.123},
                          "mix": {"alive": True,
                                  "nested": {"deep": 7}}})
    lines = text.splitlines()
    assert text.endswith("\n")
    assert "hivemall_tpu_pipeline_batches 3" in lines
    assert "hivemall_tpu_pipeline_busy_s 0.25" in lines
    assert "hivemall_tpu_mix_alive 1" in lines
    assert "hivemall_tpu_mix_nested_deep 7" in lines
    # full precision: %g-style 6-sig-digit truncation would corrupt
    # large counters and epoch timestamps
    assert "hivemall_tpu_train_examples 44776121" in lines
    assert "hivemall_tpu_train_ts 1754180000.123" in lines
    assert not any("skipped-string" in l for l in lines)
    # exposition validity: every non-comment line is `name value`
    metric = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]* -?[0-9.eE+-]+$")
    for l in lines:
        assert l.startswith(("# TYPE ", "# HELP ")) or metric.match(l), l


def test_to_prometheus_name_collision_disambiguated():
    """Sanitization is lossy ('a.b' and 'a_b' flatten to one name): two
    families under one name is invalid exposition, so the later arrival
    must be renamed with a _dup suffix and the event surfaced as a
    name_collisions gauge."""
    text = to_prometheus({"sec": {"a.b": 1, "a_b": 2,
                                  "a-b": 3}})     # three-way collision
    lines = text.splitlines()
    # keys walk in sorted order: 'a-b' arrives first and keeps the name
    assert "hivemall_tpu_sec_a_b 3" in lines
    assert "hivemall_tpu_sec_a_b_dup2 1" in lines
    assert "hivemall_tpu_sec_a_b_dup3 2" in lines
    assert "hivemall_tpu_name_collisions 2" in lines
    # HELP carries each family's TRUE dot-path, so the rename is
    # recoverable from the scrape itself
    assert "# HELP hivemall_tpu_sec_a_b_dup2 sec.a.b" in lines
    # emitted names are unique — the invalid-exposition hazard is gone
    names = [l.split()[0] for l in lines if not l.startswith("#")]
    assert len(names) == len(set(names))
    # still grammar-valid exposition
    metric = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]* -?[0-9.eE+-]+$")
    for l in lines:
        assert l.startswith(("# TYPE ", "# HELP ")) or metric.match(l), l


def test_to_prometheus_no_false_collision():
    """Distinct dot-paths that sanitize to distinct names must NOT pay
    the _dup rename, and the collisions gauge must stay absent."""
    text = to_prometheus({"pipeline": {"batches": 1},
                          "train": {"batches": 2}})
    assert "hivemall_tpu_pipeline_batches 1" in text
    assert "hivemall_tpu_train_batches 2" in text
    assert "_dup" not in text and "name_collisions" not in text


def test_to_prometheus_empty_histogram_and_nonfinite():
    """An empty histogram (no observations yet) exports sum/count only;
    NaN/inf gauge values export as Prometheus' case-insensitive
    'nan'/'inf' literals instead of corrupting the exposition."""
    text = to_prometheus({
        "serve": {"lat": {"_type": "histogram", "buckets": [],
                          "sum": 0.0, "count": 0},
                  "bad": float("nan"),
                  "hot": float("inf"),
                  "cold": float("-inf")}})
    lines = text.splitlines()
    assert "hivemall_tpu_serve_lat_sum 0.0" in lines
    assert "hivemall_tpu_serve_lat_count 0" in lines
    assert not any("_bucket" in l for l in lines)
    assert "hivemall_tpu_serve_bad nan" in lines
    assert "hivemall_tpu_serve_hot inf" in lines
    assert "hivemall_tpu_serve_cold -inf" in lines


def test_flight_section_round_trips_through_obs_server(tmp_path):
    """The flight recorder's self-census scrapes end to end: /snapshot
    carries the section (path included), /metrics its numeric gauges."""
    from hivemall_tpu.obs.flight import configure_flight
    from hivemall_tpu.obs.registry import registry as process_registry
    fr = configure_flight(str(tmp_path), label="scrape")
    srv = ObsServer(0, obs_registry=process_registry).start()
    try:
        fr.record("req.admit", req=1, rows=2)
        fr.record("req.admit", req=2, rows=2)
        base = f"http://127.0.0.1:{srv.port}"
        snap = json.loads(urllib.request.urlopen(f"{base}/snapshot",
                                                 timeout=5).read())
        assert snap["flight"]["enabled"] is True
        assert snap["flight"]["events"] == 2
        assert snap["flight"]["label"] == "scrape"
        assert snap["flight"]["path"] == fr.path
        text = urllib.request.urlopen(f"{base}/metrics",
                                      timeout=5).read().decode()
        lines = text.splitlines()
        assert "hivemall_tpu_flight_enabled 1" in lines
        assert "hivemall_tpu_flight_events 2" in lines
        assert "hivemall_tpu_flight_dropped 0" in lines
        assert "hivemall_tpu_flight_ring_slots 4096" in lines
    finally:
        srv.stop()
        configure_flight(None)


def test_obs_http_server_snapshot_and_metrics():
    r = Registry()
    r.register("unit", lambda: {"value": 42})
    srv = ObsServer(0, obs_registry=r).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        snap = json.loads(urllib.request.urlopen(f"{base}/snapshot",
                                                 timeout=5).read())
        assert snap["unit"]["value"] == 42
        resp = urllib.request.urlopen(f"{base}/metrics", timeout=5)
        assert "text/plain" in resp.headers["Content-Type"]
        assert "hivemall_tpu_unit_value 42" in resp.read().decode()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=5)
    finally:
        srv.stop()


def test_obs_http_idle_connection_cannot_wedge_server():
    """A client that connects and never sends a request (half-open TCP,
    port scanner) must not block the single-threaded server forever —
    the handler timeout closes it and the next scrape succeeds."""
    import socket
    r = Registry()
    r.register("unit", lambda: {"value": 1})
    srv = ObsServer(0, obs_registry=r).start()
    srv._httpd.RequestHandlerClass.timeout = 0.3   # keep the test fast
    try:
        idle = socket.create_connection(("127.0.0.1", srv.port))
        try:
            snap = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/snapshot", timeout=10).read())
            assert snap["unit"]["value"] == 1
        finally:
            idle.close()
    finally:
        srv.stop()


# --- MetricsStream hardening -----------------------------------------------

class _FailingIO:
    """IO stub whose write starts failing after ``ok`` successes."""

    def __init__(self, ok: int):
        self.ok = ok
        self.lines = []

    def write(self, s):
        if self.ok <= 0:
            raise OSError("disk full")
        self.ok -= 1
        self.lines.append(s)


def test_stream_counts_dropped_events_after_write_failure():
    io = _FailingIO(ok=2)
    s = M.MetricsStream(io)
    s.emit("a")
    s.emit("b")
    assert s.dropped_events == 0 and len(io.lines) == 2
    s.emit("c")                          # write fails -> disable + count
    assert not s.enabled and s.dropped_events == 1
    s.emit("d")                          # post-disable emits keep counting
    s.emit("e")
    assert s.dropped_events == 3
    assert s.counters()["dropped_events"] == 3


def test_stream_never_counts_drops_when_deliberately_disabled():
    s = M.MetricsStream(None)
    s.emit("a")
    assert s.dropped_events == 0


def test_stream_size_rotation(tmp_path, monkeypatch):
    monkeypatch.setenv("HIVEMALL_TPU_METRICS_MAX_MB", "0.0005")  # 500 bytes
    p = str(tmp_path / "m.jsonl")
    s = M.MetricsStream(p)
    for i in range(40):
        s.emit("ev", i=i, pad="x" * 64)
    s.close()
    assert s.rotations >= 1
    assert os.path.exists(p + ".1")
    # every surviving line in both generations is intact jsonl
    for path in (p, p + ".1"):
        for line in open(path):
            assert json.loads(line)["event"] == "ev"


# --- telemetry emission from the fit loop ----------------------------------

def test_telemetry_every_and_train_done_snapshot(tmp_path, monkeypatch):
    p = tmp_path / "t.jsonl"
    monkeypatch.setattr(M, "_stream", M.MetricsStream(str(p)))
    tr = GeneralClassifier("-dims 128 -mini_batch 8 -telemetry_every 4")
    tr.fit_stream(iter(_batches(10, bs=8, dims=128)))
    M._stream.close()
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    tele = [r for r in recs if r["event"] == "telemetry"]
    assert len(tele) == 2                # steps 4 and 8 of 10
    assert all("pipeline" in r["snapshot"] and "train" in r["snapshot"]
               for r in tele)
    done = [r for r in recs if r["event"] == "train_done"]
    assert len(done) == 1
    for section in ("pipeline", "train", "mix", "checkpoint", "spans"):
        assert section in done[0]["telemetry"]


def test_ffm_multi_epoch_stream_emits_one_train_done(tmp_path, monkeypatch):
    """FFM's multi-epoch fit_stream wrapper runs one base fit_stream per
    epoch; the run must still report exactly ONE train_done record."""
    from hivemall_tpu.models.fm import FFMTrainer
    p = tmp_path / "ffm.jsonl"
    monkeypatch.setattr(M, "_stream", M.MetricsStream(str(p)))
    rng = np.random.default_rng(5)

    def epoch():
        for _ in range(4):
            idx = rng.integers(1, 64, (8, 4)).astype(np.int32)
            fld = np.tile(np.arange(4, dtype=np.int32), (8, 1))
            lab = (rng.integers(0, 2, 8) * 2 - 1).astype(np.float32)
            yield SparseBatch(idx, np.ones((8, 4), np.float32), lab, fld)

    tr = FFMTrainer("-dims 64 -factors 2 -fields 4 -classification "
                    "-mini_batch 8")
    tr.fit_stream(epoch, epochs=3)
    M._stream.close()
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    done = [r for r in recs if r["event"] == "train_done"]
    assert len(done) == 1
    assert done[0]["step"] == tr._t      # the FINAL step, all epochs in


def test_span_rollup_emitted_at_fold_cadence(tmp_path, monkeypatch, tracer):
    p = tmp_path / "r.jsonl"
    monkeypatch.setattr(M, "_stream", M.MetricsStream(str(p)))
    tr = GeneralClassifier("-dims 128 -mini_batch 8")
    tr.fit_stream(iter(_batches(260, bs=8, dims=128)))
    M._stream.close()
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    rolls = [r for r in recs if r["event"] == "span_rollup"]
    assert len(rolls) == 1               # one 256-step boundary crossed
    stages = rolls[0]["stages"]
    assert stages["dispatch.step"]["count"] >= 256
    assert stages["ingest.prep"]["count"] >= 256
    assert {"count", "total_s", "p50", "p99"} <= set(
        stages["dispatch.step"])


def test_epoch_checkpoint_event_via_shared_helper(tmp_path, monkeypatch):
    """Both epoch-bundle sites (base + fm adareg) now ride
    _save_epoch_bundle/_emit_checkpoint_event; the event schema is one."""
    from hivemall_tpu.io.libsvm import synthetic_classification
    p = tmp_path / "c.jsonl"
    monkeypatch.setattr(M, "_stream", M.MetricsStream(str(p)))
    ds, _ = synthetic_classification(64, 16, seed=3)
    ck = str(tmp_path / "ck")
    tr = GeneralClassifier(f"-dims 128 -mini_batch 16 -iters 2 "
                           f"-checkpoint_dir {ck}")
    tr.fit(ds)
    M._stream.close()
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    cks = [r for r in recs if r["event"] == "checkpoint"]
    assert [r["epoch"] for r in cks] == [1, 2]
    assert all(r["trainer"] == "train_classifier" and "path" in r
               for r in cks)


# --- concurrency: the live-surface guarantees ------------------------------

def test_concurrent_workers_spans_and_stable_snapshot(tmp_path, monkeypatch,
                                                      tracer):
    """Spans from ingest_workers>1 pipeline workers land complete, the
    jsonl stream has no interleaved/torn lines, and registry.snapshot()
    called from another thread DURING the fit never fails or blocks."""
    p = tmp_path / "conc.jsonl"
    monkeypatch.setattr(M, "_stream", M.MetricsStream(str(p)))
    tr = GeneralClassifier("-dims 256 -mini_batch 16 -ingest_workers 3")
    stop = threading.Event()
    snaps, errors = [], []

    def poll():
        while not stop.is_set():
            try:
                snaps.append(registry.snapshot())
            except Exception as e:      # noqa: BLE001 — the assertion
                errors.append(e)
            time.sleep(0.002)

    poller = threading.Thread(target=poll)
    poller.start()
    try:
        tr.fit_stream(iter(_batches(300, bs=16, dims=256)))
    finally:
        stop.set()
        poller.join()
    M._stream.close()
    assert not errors
    assert len(snaps) > 2
    assert all("pipeline" in s and "spans" in s for s in snaps)
    # every line written under concurrency parses — no torn writes
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    assert {"train_step", "span_rollup", "train_done"} <= \
        {r["event"] for r in recs}
    roll = tr._tracer.rollup()
    assert roll["ingest.prep"]["count"] == 300     # every worker span landed
    assert roll["dispatch.step"]["count"] == 300


def test_mix_exchange_spans_under_faults(tracer):
    """FlakyProxy-faulted MIX exchanges still record complete
    mix.exchange spans (one per exchange window, faults absorbed inside),
    and the registry's mix section tracks the client."""
    from hivemall_tpu.parallel.mix_service import MixServer
    from hivemall_tpu.testing.faults import FlakyProxy
    srv = MixServer().start()
    proxy = FlakyProxy(("127.0.0.1", srv.port),
                       schedule={1: "rst", 3: "drop"}).start()
    try:
        clf = GeneralClassifier(
            f"-dims 32 -mini_batch 4 -eta fixed -eta0 0.5 -reg no "
            f"-mix 127.0.0.1:{proxy.port} -mix_threshold 1 "
            f"-mix_timeout 0.3 -mix_backoff 0.01")
        for _ in range(12):
            clf.process(["1:1.0"], 1)
            clf.process(["2:1.0"], -1)
            clf._flush()
        roll = tracer.rollup()
        assert roll["mix.exchange"]["count"] == clf._mixer.exchanges
        assert clf._mixer.exchanges > 0
        assert proxy.faults_applied >= 1          # the faults really fired
        snap = registry.snapshot()
        assert snap["mix"]["active"] is True
        assert snap["mix"]["exchanges"] == clf._mixer.exchanges
        clf._mixer.close_group()
    finally:
        proxy.stop()
        srv.stop()


# --- obs CLI ---------------------------------------------------------------

def test_obs_cli_renders_stream(tmp_path, capsys):
    from hivemall_tpu.cli.main import main
    p = tmp_path / "s.jsonl"
    lines = [
        {"ts": 1.0, "event": "train_step", "trainer": "t", "step": 256,
         "examples": 4096, "examples_per_sec": 100.0, "avg_loss": 0.5},
        {"ts": 2.0, "event": "span_rollup", "trainer": "t", "step": 256,
         "stages": {"dispatch.step": {"count": 256, "total_s": 1.0,
                                      "p50": 0.004, "p99": 0.01}}},
        {"ts": 3.0, "event": "checkpoint", "trainer": "t", "step": 256,
         "path": "/tmp/x.npz"},
    ]
    p.write_text("\n".join(json.dumps(r) for r in lines)
                 + "\n{torn-line")
    assert main(["obs", str(p)]) == 0
    out = capsys.readouterr().out
    assert "train_step x1" in out
    assert "dispatch.step" in out
    assert "unparsable" in out           # the torn tail is counted, not fatal
    assert "ckpt:" in out


def test_obs_cli_missing_file(capsys):
    from hivemall_tpu.cli.main import main
    assert main(["obs", "/nonexistent/x.jsonl"]) == 1


# --- Histogram primitive + Prometheus histogram families --------------------

def test_histogram_cumulative_buckets_and_quantile():
    from hivemall_tpu.obs.histo import Histogram, quantile_from_buckets
    h = Histogram([0.001, 0.01, 0.1])
    for v in (0.0005, 0.001, 0.005, 0.05, 5.0):
        h.observe(v)
    s = h.snapshot()
    assert s["_type"] == "histogram"
    # le semantics: a value exactly on a bound counts into that bucket
    assert s["buckets"] == [[0.001, 2], [0.01, 3], [0.1, 4], ["+Inf", 5]]
    assert s["count"] == 5 and abs(s["sum"] - 5.0565) < 1e-9
    # interpolated quantile stays inside the winning bucket
    q = quantile_from_buckets(s["buckets"], 0.5)
    assert 0.001 <= q <= 0.01
    # +Inf winner clamps to the largest finite bound
    assert quantile_from_buckets(s["buckets"], 0.999) == 0.1
    assert quantile_from_buckets([], 0.99) == 0.0


def test_histogram_concurrent_observers_lose_nothing():
    from hivemall_tpu.obs.histo import Histogram
    h = Histogram([1.0, 10.0])
    n, threads = 2000, 4

    def work():
        for i in range(n):
            h.observe(0.5 if i % 2 else 5.0)

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    s = h.snapshot()
    assert s["count"] == n * threads
    assert s["buckets"][-1][1] == n * threads


def _parse_prometheus_strict(text):
    """Strict text-format 0.0.4 grammar: returns {family: (type, samples)}
    and asserts every line is a well-formed HELP/TYPE/sample line, HELP
    and TYPE precede their family's samples exactly once, histogram
    families carry monotonic _bucket series + _sum/_count."""
    name_re = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
    sample_re = re.compile(
        rf"^({name_re})(?:\{{le=\"([^\"]+)\"\}})? (-?[0-9.eE+-]+|NaN)$")
    help_re = re.compile(rf"^# HELP ({name_re}) (.+)$")
    type_re = re.compile(rf"^# TYPE ({name_re}) (gauge|histogram|counter)$")
    assert text.endswith("\n")
    families = {}
    cur = None
    for line in text.splitlines():
        m = help_re.match(line)
        if m:
            assert m.group(1) not in families, f"duplicate HELP {line}"
            families[m.group(1)] = {"type": None, "samples": []}
            cur = m.group(1)
            continue
        m = type_re.match(line)
        if m:
            assert m.group(1) == cur, f"TYPE without HELP: {line}"
            assert families[cur]["type"] is None, f"duplicate TYPE {line}"
            families[cur]["type"] = m.group(2)
            continue
        m = sample_re.match(line)
        assert m, f"unparsable exposition line: {line!r}"
        base = m.group(1)
        fam = base
        for suffix in ("_bucket", "_sum", "_count"):
            if base.endswith(suffix) and base[:-len(suffix)] in families \
                    and families[base[:-len(suffix)]]["type"] == "histogram":
                fam = base[:-len(suffix)]
        assert fam in families and families[fam]["type"], \
            f"sample before its TYPE: {line!r}"
        float(m.group(3))                # value must parse
        families[fam]["samples"].append((base, m.group(2), m.group(3)))
    for fam, rec in families.items():
        if rec["type"] != "histogram":
            continue
        buckets = [(le, float(v)) for n_, le, v in rec["samples"]
                   if n_ == fam + "_bucket"]
        counts = [v for _, v in buckets]
        assert counts == sorted(counts), f"{fam} buckets not monotonic"
        assert buckets[-1][0] == "+Inf"
        total = [float(v) for n_, _, v in rec["samples"]
                 if n_ == fam + "_count"]
        assert total and total[0] == counts[-1]
    return families


def test_to_prometheus_strict_grammar_with_histograms():
    """Satellite: the exposition parses under a strict grammar even with
    hostile snapshot keys (dots/dashes/leading digits) and histogram
    leaves; histogram series are monotonic with +Inf == _count."""
    from hivemall_tpu.obs.histo import Histogram
    h = Histogram([0.005, 0.05, 0.5])
    for v in (0.001, 0.01, 0.1, 1.0):
        h.observe(v)
    text = to_prometheus({
        "ts": 1754180000.123,
        "serve": {"request_latency_seconds": h.snapshot(),
                  "batch_hist": {"16": 3, "2": 1},
                  "qps": 12.5, "ready": True, "model_path": "/x.npz"},
        "9section": {"with.dots": 1, "and-dashes": 2},
    })
    fams = _parse_prometheus_strict(text)
    lat = "hivemall_tpu_serve_request_latency_seconds"
    assert fams[lat]["type"] == "histogram"
    assert ('%s_bucket' % lat, "+Inf", "4") in fams[lat]["samples"]
    # sanitization: dots/dashes -> underscores, leading digit guarded by
    # the name regex (the section rides behind the prefix)
    assert "hivemall_tpu_9section_with_dots" in fams
    assert "hivemall_tpu_9section_and_dashes" in fams
    assert fams["hivemall_tpu_serve_qps"]["type"] == "gauge"
    # a name that would START with a digit gets the underscore prefix
    from hivemall_tpu.obs.http import _metric_name
    assert _metric_name(["9lives", "x"]) == "_9lives_x"


# --- request-scoped tracing -------------------------------------------------

def test_tracer_context_tags_spans_into_chrome_args(tracer):
    with tracer.span("untagged"):
        pass
    with tracer.context("req-42"):
        with tracer.span("tagged"):
            pass
        # nesting restores the outer tag
        with tracer.context("inner"):
            with tracer.span("nested"):
                pass
        with tracer.span("tagged2"):
            pass
    tracer.add_span("explicit", 0.001, trace="req-42")
    evs = tracer.chrome_dict()["traceEvents"]
    by_name = {e["name"]: e for e in evs if e.get("ph") == "X"}
    assert "trace" not in by_name["untagged"]["args"]
    assert by_name["tagged"]["args"]["trace"] == "req-42"
    assert by_name["nested"]["args"]["trace"] == "inner"
    assert by_name["tagged2"]["args"]["trace"] == "req-42"
    assert by_name["explicit"]["args"]["trace"] == "req-42"
    # wall-clock anchoring: ts is epoch microseconds, so independently
    # recorded processes merge onto one timeline
    now_us = time.time() * 1e6
    # deliberate wall anchor: trace ts IS epoch time (merged timelines)
    assert abs(by_name["tagged"]["ts"] - now_us) < 60e6  # graftcheck: disable=GC02
    # the export names its process (the merged fleet view's labels)
    metas = [e for e in evs if e.get("ph") == "M"]
    assert metas and metas[0]["args"]["name"] == tracer.process_label


def test_tracer_context_disabled_is_noop():
    t = Tracer(enabled=False)
    ctx = t.context("x")
    with ctx:
        with t.span("s"):
            pass
    assert t.chrome_dict()["traceEvents"][:-1] == []   # only metadata


def test_mint_trace_id_unique():
    from hivemall_tpu.obs.trace import mint_trace_id
    ids = {mint_trace_id() for _ in range(100)}
    assert len(ids) == 100


# --- obs --follow under metrics rotation ------------------------------------

def test_follow_tail_survives_rotation(tmp_path):
    """Satellite: `obs --follow` keeps tailing across a
    HIVEMALL_TPU_METRICS_MAX_MB rotation — the replaced <path> is
    reopened from its head and <path>.1 is never replayed. The rotation
    here is the exact MetricsStream._rotate sequence (os.replace to
    <path>.1, fresh file continues), driven by hand so every phase is
    deterministic."""
    from hivemall_tpu.obs.report import _FollowTail

    def emit(path, event, **fields):
        with open(path, "a") as f:
            f.write(json.dumps({"ts": 1.0, "event": event, **fields})
                    + "\n")

    p = str(tmp_path / "m.jsonl")
    tail = _FollowTail(p)
    emit(p, "pre_rotation", i=0)
    emit(p, "archived_only", i=1)
    assert tail.tick() is not None
    assert tail.state.counts == {"pre_rotation": 1, "archived_only": 1}
    # rotation: current file -> <path>.1, FRESH file continues — while
    # the follower is mid-tail
    os.replace(p, p + ".1")
    emit(p, "post_rotation", i=2)
    out = tail.tick()                    # inode change -> reopen from 0
    assert out is not None
    assert tail.state.counts.get("post_rotation") == 1
    # no replay: the archived generation's events were folded exactly
    # once (when they were still in <path>), never re-read from <path>.1
    assert tail.state.counts["pre_rotation"] == 1
    assert tail.state.counts["archived_only"] == 1
    # a tick landing IN the replace window (file briefly absent) retries
    os.replace(p, p + ".1")
    assert tail.tick() is None           # no file yet — no crash, no .1
    emit(p, "second_generation", i=3)
    tail.tick()
    assert tail.state.counts.get("second_generation") == 1
    assert tail.state.counts["post_rotation"] == 1   # still exactly once


def test_stream_rotation_under_live_follow(tmp_path, monkeypatch):
    """The integrated version: a real MetricsStream rotating under the
    size cap while a follower tails it — post-rotation events are seen,
    nothing read from <path> is double-counted."""
    from hivemall_tpu.obs.report import _FollowTail
    monkeypatch.setenv("HIVEMALL_TPU_METRICS_MAX_MB", "0.0005")  # 500 B
    p = str(tmp_path / "m.jsonl")
    s = M.MetricsStream(p)
    tail = _FollowTail(p)
    seen = 0
    for i in range(40):
        s.emit("ev", i=i, pad="x" * 64)
        if i % 5 == 0:
            tail.tick()
            seen = tail.state.counts.get("ev", 0)
            assert seen <= i + 1         # never double-counts a line
    assert s.rotations >= 1
    s.emit("final", i=99)
    s.close()
    tail.tick()
    assert tail.state.counts.get("final") == 1
    assert tail.state.counts.get("ev", 0) <= 40


def test_render_slo_report():
    from hivemall_tpu.obs.report import render_slo
    text = render_slo({
        "targets": {"p99_ms": 50.0, "availability": 0.999},
        "samples": 12,
        "windows": {"5m": {"seconds": 300.0, "qps": 10.0,
                           "availability": 0.995,
                           "availability_burn_rate": 5.0,
                           "p99_ms": 80.0, "frac_over_slo": 0.04,
                           "latency_burn_rate": 4.0}},
        "score": {"mean": 0.5, "std": 0.1},
        "drift": {"latency_events": 2, "score_events": 0,
                  "recent": [{"series": "latency_ms", "value": 80.0,
                              "change_score": 9.1, "ts": 1.0}]},
    }, source="http://x/slo")
    assert "burn 5x" in text and "80.0ms" in text
    assert "latency x2" in text and "change 9.1" in text


# --- stub-vs-live key contract (ISSUE 9 satellite: the drift recurred in
# PR 7 and PR 8 hardening — now every registered stub is pinned against
# its live provider's snapshot keys) -----------------------------------------


def test_stub_sections_match_live_providers(tmp_path):
    """Every registry-default stub section's key set must EXACTLY match
    its live provider's snapshot keys (in the provider's canonical fresh
    state), for all sections — a dashboard keyed on a gauge must never
    see it appear/vanish across subsystem lifecycle."""
    from hivemall_tpu.obs.registry import (CHECKPOINT_STUB, FLEET_STUB,
                                           MIX_STUB, SLO_STUB)

    # mix: MixClient.counters() + the active discriminator (ctor is lazy,
    # no connect)
    from hivemall_tpu.parallel.mix_service import MixClient
    client = MixClient("127.0.0.1:1", group="stubcheck")
    live = {"active": True, **client.counters()}
    assert set(MIX_STUB) == set(live), "mix stub drifted from live keys"

    # checkpoint: CheckpointManager.obs_section()
    from hivemall_tpu.io.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path / "ck"), "stubcheck", keep=1,
                            every=1)
    assert set(CHECKPOINT_STUB) == set(mgr.obs_section()), \
        "checkpoint stub drifted from live keys"

    # slo: SloEngine.obs_section() in its fresh (no samples) state
    from hivemall_tpu.obs.slo import SloEngine
    eng = SloEngine()
    assert set(SLO_STUB) == set(eng.obs_section()), \
        "slo stub drifted from live keys"

    # fleet: ReplicaManager.obs_section() (construction does not spawn)
    from hivemall_tpu.serve.fleet import ReplicaManager
    fm = ReplicaManager("train_classifier",
                        checkpoint_dir=str(tmp_path / "fleet"), replicas=1)
    assert set(FLEET_STUB) == set(fm.obs_section()), \
        "fleet stub drifted from live keys"

    # ingest_cache: the shard-cache counters override the registry stub
    # at import — compare against the stub registered BEFORE that import
    # by rebuilding its dict from the live as_dict
    from hivemall_tpu.io.shard_cache import counters as cache_counters
    stub_keys = {"configured", "hits", "misses", "invalid", "rebuilds",
                 "build_failed", "bytes_mmapped", "bytes_written",
                 "canonicalizer"}
    assert stub_keys == set(cache_counters.as_dict()), \
        "ingest_cache stub drifted from live keys"

    # promotion: PromotionController.obs_section() (gate never run) and
    # the fleet manager's promotion_section() must both mirror the stub
    from hivemall_tpu.serve.promote import (PromotionController,
                                            PromotionGate, promotion_stub)
    gate = PromotionGate("train_classifier", "-dims 64")
    ctrl = PromotionController(str(tmp_path / "promo"), gate)
    assert set(promotion_stub()) == set(ctrl.obs_section()), \
        "promotion stub drifted from controller live keys"
    pm = ReplicaManager("train_classifier",
                        checkpoint_dir=str(tmp_path / "promo"),
                        replicas=1, promote=True)
    assert set(promotion_stub()) == set(pm.promotion_section()), \
        "promotion stub drifted from fleet manager live keys"
    assert set(promotion_stub()["canary"]) \
        == set(pm.promotion_section()["canary"])

    # retrain: RetrainController.obs_section() (never triggered) must
    # mirror RETRAIN_STUB key-for-key, nested replay dict included
    from hivemall_tpu.serve.retrain import (RetrainController,
                                            retrain_stub)
    rc = RetrainController("train_classifier", "-dims 64",
                           checkpoint_dir=str(tmp_path / "retrain"))
    assert set(retrain_stub()) == set(rc.obs_section()), \
        "retrain stub drifted from live keys"
    assert set(retrain_stub()["replay"]) \
        == set(rc.obs_section()["replay"])

    # retrieval: RetrievalEngine.obs_section() over a real factor
    # bundle (the engine has no lazy-construct path — it loads at init),
    # nested index/arena dicts included
    import numpy as np
    from hivemall_tpu.models.mf import MFTrainer
    from hivemall_tpu.serve.retrieve import RetrievalEngine, retrieval_stub
    opts = "-factors 4 -users 8 -items 16 -mini_batch 64 -iters 1"
    t = MFTrainer(opts)
    rng = np.random.default_rng(3)
    t.fit(rng.integers(0, 8, 256), rng.integers(0, 16, 256),
          rng.normal(3, 1, 256).astype(np.float32), epochs=1)
    bdir = tmp_path / "retrieval"
    bdir.mkdir()
    bp = str(bdir / "train_mf_sgd-step000004.npz")
    t.save_bundle(bp)
    reng = RetrievalEngine("train_mf_sgd", opts, bundle=bp,
                           checkpoint_dir=None, rescore="numpy")
    try:
        live = reng.obs_section()
        assert set(retrieval_stub()) == set(live), \
            "retrieval stub drifted from live keys"
        assert set(retrieval_stub()["index"]) == set(live["index"])
        assert set(retrieval_stub()["arena"]) == set(live["arena"])
    finally:
        reng.close()

    # bulk: BulkProgress.obs_section() (no job run) must mirror
    # BULK_STUB key-for-key — the offline-scoring plane's section
    from hivemall_tpu.io.bulk import BulkProgress
    from hivemall_tpu.obs.registry import BULK_STUB
    assert set(BULK_STUB) == set(BulkProgress().obs_section()), \
        "bulk stub drifted from live keys"

    # devprof: the stub constructor IS the contract
    from hivemall_tpu.obs.devprof import devprof_stub, get_devprof
    live_dp = get_devprof().obs_section()
    assert set(devprof_stub()) == set(live_dp), \
        "devprof stub drifted from live keys"
    assert set(devprof_stub()["memory"]) == set(live_dp["memory"])
    assert set(devprof_stub()["drift"]) == set(live_dp["drift"])

    # flight: FlightRecorder.obs_section() — dark AND recording forms
    # must both mirror the stub (the checkpoint-dir ReplicaManagers
    # above flipped the process recorder on; leave it dark again)
    from hivemall_tpu.obs.flight import (FlightRecorder, configure_flight,
                                         flight_stub)
    assert flight_stub() == FlightRecorder().obs_section(), \
        "flight stub drifted from live keys"
    lfr = FlightRecorder().open(str(tmp_path / "parity.ring"))
    lfr.record("x")
    assert set(flight_stub()) == set(lfr.obs_section()), \
        "flight stub drifted from recording-state live keys"
    lfr.close()
    configure_flight(None)

    # trainer-inactive forms reuse the SAME stub dicts (pinned here so a
    # future inline dict can't drift silently)
    tr = GeneralClassifier("-dims 64 -mini_batch 8")
    snap = registry.snapshot()
    assert snap["mix"] == MIX_STUB
    assert snap["checkpoint"] == CHECKPOINT_STUB
    assert tr is not None


# --- span-ring overflow accounting (ISSUE 9 satellite) ----------------------


def test_span_ring_overflow_counts_dropped():
    t = Tracer(enabled=True, ring=4)
    for i in range(10):
        with t.span(f"s{i % 2}"):
            pass
    assert t.dropped == 6                  # 10 recorded into a 4-ring
    assert len(t.chrome_dict()["traceEvents"]) == 4 + 1   # + metadata
    t.reset()
    assert t.dropped == 0


def test_spans_dropped_surfaces_in_registry_and_metrics(tracer):
    with tracer.span("x"):
        pass
    snap = registry.snapshot()
    assert isinstance(snap["spans"]["dropped"], int)
    text = to_prometheus(snap)
    assert "hivemall_tpu_spans_dropped" in text
    # the obs report renders a snapshot whose spans section carries the
    # scalar beside the stage dicts without tripping over it
    from hivemall_tpu.obs.report import summarize
    out = summarize([{"event": "train_done", "ts": 1.0,
                      "telemetry": snap}])
    assert "stages" in out


# --- histo.quantile_from_buckets edge cases (ISSUE 9 satellite) -------------


def test_quantile_from_buckets_edge_cases():
    from hivemall_tpu.obs.histo import quantile_from_buckets as q

    # empty histogram
    assert q([], 0.99) == 0.0
    # zero-total histogram
    assert q([[0.1, 0], [0.5, 0], ["+Inf", 0]], 0.5) == 0.0
    # all mass in +Inf: clamps to the largest finite bound
    assert q([[0.1, 0], [0.5, 0], ["+Inf", 10]], 0.99) == 0.5
    # single (+Inf-only) bucket: nothing finite to clamp to
    assert q([["+Inf", 5]], 0.5) == 0.0
    # single finite bucket: interpolates inside [0, bound]
    v = q([[0.25, 4], ["+Inf", 4]], 0.5)
    assert 0.0 < v <= 0.25
    # zero-width interpolation: the winning bucket is empty (cum ==
    # prev_cum) — returns the bound instead of dividing by zero
    assert q([[0.1, 0], [0.2, 5], ["+Inf", 5]], 0.0) == 0.1
    # monotonicity across the bucket edge
    assert q([[0.1, 5], [0.2, 10], ["+Inf", 10]], 0.25) <= \
        q([[0.1, 5], [0.2, 10], ["+Inf", 10]], 0.75)
