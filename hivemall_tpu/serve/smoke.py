"""Serve smoke — run by run_tests.sh (docs/SERVING.md).

The acceptance surface of the online-serving subsystem, seconds-scale:

1. a checkpoint trained in-process is served over HTTP and concurrent
   single-row predicts COALESCE (observed mean batch rows > 1 — the
   dynamic micro-batcher actually batching, not a degenerate 1-row loop);
2. served probabilities BIT-MATCH the offline ``predict_proba`` on the
   same feature strings (same hashing path, same kernels, same sigmoid);
3. request p99 latency stays under a budget (post-warmup — the engine
   pre-compiles its batch buckets at startup, so no request pays XLA);
4. a NEWER checkpoint written mid-traffic is hot-reloaded without a
   single in-flight request failing, and /healthz reflects the new step;
5. the obs registry surfaces the ``serve`` section through the server's
   own /snapshot and /metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

from ..utils.net import http_get as _get


def _post(url: str, obj: dict, timeout: float = 30.0) -> dict:
    req = urllib.request.Request(
        url, json.dumps(obj).encode(), {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _train_bundle(ckdir: str, opts: str, ds, epochs: int = 1):
    """Train (or continue training) and drop a step-named bundle into the
    shared checkpoint dir — the shape a live trainer's autosave produces."""
    from ..models.linear import GeneralClassifier
    t = GeneralClassifier(opts)
    from ..io.checkpoint import newest_bundle
    nb = newest_bundle(ckdir, t.NAME)
    if nb is not None:
        t.load_bundle(nb[1])
    for _ in range(epochs):
        t.fit(ds)
    path = os.path.join(ckdir, f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(path)
    return t, path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="hivemall_tpu.serve.smoke")
    ap.add_argument("--rows", type=int, default=400)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--p99-budget-ms", type=float, default=1500.0,
                    help="per-request p99 wall budget (generous: CPU CI)")
    ap.add_argument("--plane", default="threaded",
                    choices=("threaded", "evloop"),
                    help="serving plane under test (docs/SERVING.md "
                         "'Serving planes')")
    args = ap.parse_args(argv)
    # lockset race sanitizer (HIVEMALL_TPU_TSAN=1): enable BEFORE any
    # serve object exists so every lock in the system is born wrapped;
    # a sanitizer build is never a perf build, so the latency budget
    # relaxes (correctness checks — bit-match, zero drops — stay hard)
    from ..testing import tsan
    if tsan.maybe_enable():
        args.p99_budget_ms *= 3
        print(f"serve smoke: tsan sanitizer ON (p99 budget relaxed to "
              f"{args.p99_budget_ms}ms)", file=sys.stderr)
    # leak census sanitizer (HIVEMALL_TPU_LEAKTRACK=1): snapshot BEFORE
    # any serve object exists; the census re-runs after the full
    # traffic + reload + drain + shutdown cycle and any tracked
    # fd/socket/thread still alive fails the smoke
    from ..testing import leaktrack
    if leaktrack.maybe_enable():
        print("serve smoke: leaktrack sanitizer ON", file=sys.stderr)
        leaktrack.snapshot()
    tmp = tempfile.mkdtemp(prefix="hivemall_tpu_serve_smoke_")
    try:
        rc = _run(args, tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if leaktrack.enabled():
        n = leaktrack.check_and_report("serve smoke leaktrack")
        print(f"serve smoke leak_census: {'OK' if n == 0 else 'FAILED'} "
              f"({n} leaked resource(s) after shutdown)",
              file=sys.stderr)
        rc += 1 if n else 0      # counts wrap mod 256 in exit codes —
        #                          a 256-leak run must not read as 0
    return rc


def _run(args, tmp: str) -> int:
    from ..io.libsvm import synthetic_classification
    from ..io.sparse import SparseDataset
    from ..serve.engine import PredictEngine
    from ..serve.http import PredictServer

    opts = "-dims 4096 -loss logloss -opt adagrad -mini_batch 64"
    ds, _ = synthetic_classification(args.rows, 256, seed=7)
    trainer, _ = _train_bundle(tmp, opts, ds)

    # the request corpus: feature STRINGS (the wire format), fed
    # identically to the offline reference and the server
    rows = []
    for i in range(args.requests):
        idx, val = ds.row(i % args.rows)
        rows.append([f"{int(a)}:{float(v)!r}" for a, v in zip(idx, val)])
    parsed = [trainer._parse_row(r) for r in rows]
    ref = trainer.predict_proba(
        SparseDataset.from_rows(parsed, [1.0] * len(parsed)))

    # warmup_len matches the corpus row width so the pre-compiled
    # buckets are the ones traffic hits (p99 measures serving, not XLA)
    engine = PredictEngine("train_classifier", opts, checkpoint_dir=tmp,
                           watch_interval=0.2,
                           warmup_len=max(len(r) for r in rows))
    if args.plane == "evloop":
        from ..serve.evloop import EvloopPredictServer as _ServerCls
    else:
        _ServerCls = PredictServer
    srv = _ServerCls(engine, port=0, max_delay_ms=10.0).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        return _drive(args, tmp, ds, rows, ref, engine, srv, base)
    finally:
        srv.stop()


def _drive(args, tmp, ds, rows, ref, engine, srv, base) -> int:
    failures = []

    def check(name, ok, detail=""):
        print(f"serve smoke {name}: {'OK' if ok else 'FAILED'} {detail}",
              file=sys.stderr)
        if not ok:
            failures.append(name)

    # -- concurrent predicts: coalescing + bit-match + latency ------------
    # each worker holds ONE keep-alive connection (HTTP/1.1 — the
    # PredictServer reuse path runs in CI)
    from .http import KeepAliveClient
    scores = [None] * len(rows)
    lat = [0.0] * len(rows)
    errs = []
    pos = iter(range(len(rows)))
    lock = threading.Lock()

    def worker():
        cli = KeepAliveClient("127.0.0.1", srv.port)
        while True:
            with lock:
                i = next(pos, None)
            if i is None:
                cli.close()
                return
            t0 = time.perf_counter()
            try:
                code, r = cli.post_json("/predict", {"rows": [rows[i]]})
                assert code == 200, (code, r)
                scores[i] = r["scores"][0]
            except Exception as e:     # noqa: BLE001 — collected
                errs.append(f"req {i}: {e}")
            lat[i] = time.perf_counter() - t0

    ts = [threading.Thread(target=worker) for _ in range(args.threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    check("requests", not errs, f"({len(rows)} requests, "
                                f"{len(errs)} errors) {errs[:2]}")
    # failed requests leave None behind — score them NaN so the remaining
    # checks still report instead of crashing the smoke mid-drive
    got = np.asarray([np.nan if s is None else s for s in scores],
                     np.float32)
    check("bit_match", np.array_equal(got, ref),
          f"(max abs diff {np.abs(got - ref).max():.2e})")
    st = srv.batcher.stats()
    check("coalescing", st["mean_batch_rows"] > 1.0,
          f"(mean batch {st['mean_batch_rows']}, "
          f"{st['batches']} batches / {st['requests']} requests)")
    p99 = float(np.percentile(np.asarray(lat) * 1000, 99))
    check("p99_latency", p99 <= args.p99_budget_ms,
          f"({p99:.1f}ms vs budget {args.p99_budget_ms}ms)")

    # -- hot reload mid-traffic ------------------------------------------
    stop = threading.Event()
    traffic_errs = []

    def traffic():
        i = 0
        while not stop.is_set():
            try:
                _post(base + "/predict", {"rows": [rows[i % len(rows)]]})
            except Exception as e:     # noqa: BLE001 — collected
                traffic_errs.append(str(e))
            i += 1

    tt = [threading.Thread(target=traffic) for _ in range(4)]
    for t in tt:
        t.start()
    old_step = engine.model_step
    t2, _ = _train_bundle(tmp, "-dims 4096 -loss logloss -opt adagrad "
                               "-mini_batch 64", ds)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and engine.model_step < t2._t:
        time.sleep(0.1)
    stop.set()
    for t in tt:
        t.join()
    check("hot_reload", engine.model_step == t2._t,
          f"(step {old_step} -> {engine.model_step}, "
          f"expected {t2._t}, reloads {engine.reloads})")
    check("reload_no_drops", not traffic_errs,
          f"({len(traffic_errs)} failed during reload) {traffic_errs[:2]}")
    hz = json.loads(_get(base + "/healthz"))
    check("healthz", hz.get("status") == "ok"
          and hz.get("model_step") == engine.model_step, f"({hz})")

    # -- obs surface ------------------------------------------------------
    snap = json.loads(_get(base + "/snapshot"))
    sv = snap.get("serve", {})
    need = ("qps", "queue_depth", "batch_hist", "shed", "model_step",
            "model_age_seconds")
    missing = [k for k in need if k not in sv]
    check("obs_snapshot", not missing, f"(missing {missing})")
    prom = _get(base + "/metrics").decode()
    check("obs_metrics", "hivemall_tpu_serve_model_step" in prom
          and "hivemall_tpu_serve_qps" in prom)

    # -- lockset sanitizer verdict (only when HIVEMALL_TPU_TSAN=1) --------
    from ..testing import tsan
    if tsan.enabled():
        check("tsan_races",
              tsan.check_and_report("serve smoke tsan") == 0)

    print(f"serve smoke: {len(failures)} failures", file=sys.stderr)
    return len(failures)


if __name__ == "__main__":
    sys.exit(main())
