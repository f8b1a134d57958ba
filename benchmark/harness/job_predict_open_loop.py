"""Job kind `predict_open_loop`: an in-process PredictServer over a
PredictEngine at default precision (the jitted scorer), loaded from a
bundle the normal path produced, under an open-loop generator in a child
process that never touches JAX.

Set-up: rows and one dispatch's shards from the seed, a short fit_stream
(so the weights are not the initial ones), save_bundle, PredictEngine
(which warms its batch buckets), PredictServer, the generator's child.
The window is the schedule's `seconds` after a lead-in; every request due
in it is timed from its due instant; every answer is awaited before the
run ends. `correct` holds a sample of the answers, drawn from the seed
with the longest request in it, against the reference's own scores: the
reference trains the same dispatch from the seed and scores the same
rows."""

from __future__ import annotations

import itertools
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from . import check, common, data, loadgen, xplane
from .job_stream import build_trainer, steps_per_dispatch, train_counters

TRACE_SECONDS = 10.0
FAILED_MS = 60000.0            # a request with no answer counts as this late


def _hops(records) -> dict:
    out: dict = {}
    for r in records:
        for part in (r.get("hop") or "").split(","):
            k, _, v = part.partition("=")
            if v:
                out.setdefault(k.strip(), []).append(float(v))
    return out


def setup(env: dict) -> dict:
    """Everything up to a listening server; returns the context the window
    and the check read."""
    import jax
    cfg, traffic, args = env["cfg"], env["traffic"], env["args"]
    model = cfg["model"]
    seed = common.seed31(args.seed)
    B, F = int(model["mini_batch"]), int(model["fields"])
    timings = {}
    work_dir = os.path.join(common.RUN_DIR, env["workload"])
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(os.path.join(work_dir, "ck"))

    from hivemall_tpu.io.arrow import ParquetStream
    from hivemall_tpu.obs.devprof import get_devprof
    from hivemall_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    dp = get_devprof()
    compile_s0 = dp.compile_s

    t = common.now()
    trainer = build_trainer(cfg, seed)
    K = steps_per_dispatch(cfg)
    timings["construct_s"] = common.now() - t
    t = common.now()
    n_rows = int(traffic["train_dispatches"]) * K * B
    spec = data.RowSpec(cfg["data"], int(model["dims"]))
    gen_ids, gen_labels = data.make_rows(spec, n_rows, args.seed)
    shard_dir = os.path.join(work_dir, "shards")
    data.write_shards(gen_ids, gen_labels, shard_dir,
                      int(traffic["rows_per_shard"]),
                      with_fields=bool(cfg.get("needs_fields")))
    timings["data_s"] = common.now() - t

    t = common.now()
    fed = list(itertools.islice(
        ParquetStream(shard_dir).batches(B, epochs=1, max_len=F),
        n_rows // B))
    train = {"ids": np.stack([np.asarray(b.idx) for b in fed]),
             "labels": np.stack([np.asarray(b.label) for b in fed])}
    trainer.fit_stream(iter(fed))
    jax.block_until_ready((trainer.params, trainer.opt_state))
    del fed
    step = train_counters()["step"]
    timings["train_s"] = common.now() - t

    t = common.now()
    bundle = os.path.join(work_dir, "ck",
                          f"{trainer.NAME}-step{step:010d}.npz")
    trainer.save_bundle(bundle)
    timings["bundle_bytes"] = os.path.getsize(bundle)
    trainer.params = trainer.opt_state = None
    del trainer
    timings["bundle_save_s"] = common.now() - t

    t = common.now()
    from hivemall_tpu.serve.engine import PredictEngine
    from hivemall_tpu.serve.http import PredictServer
    srv_kw = traffic["server"]
    engine = PredictEngine(cfg["catalog"], f"{cfg['options']} -seed {seed}",
                           bundle=bundle, warmup_len=F,
                           max_batch=int(srv_kw["max_batch"]))
    os.remove(bundle)                        # loaded: keep the disk small
    if env.get("break_engine"):
        env["break_engine"](engine)          # tests plant a fault here
    server = PredictServer(engine, port=0,
                           max_delay_ms=float(srv_kw["max_delay_ms"]))
    server.start()
    timings["engine_s"] = common.now() - t
    timings["compile_s"] = dp.compile_s - compile_s0
    return {"env": env, "cfg": cfg, "traffic": traffic, "seed": seed,
            "engine": engine, "server": server, "train": train,
            "gen": (gen_ids, gen_labels), "step": step, "timings": timings,
            "work_dir": work_dir, "dp": dp}


def _spec(ctx, rate: float, seconds: float, seed: int) -> dict:
    tr, model = ctx["traffic"], ctx["cfg"]["model"]
    return {"seed": int(seed), "rate_rps": float(rate),
            "seconds": float(seconds), "lead_s": float(tr["lead_s"]),
            "host": "127.0.0.1", "port": int(ctx["server"].port),
            "connections": int(tr["connections"]),
            "hot_connections": int(tr["hot_connections"]),
            "hot_share": float(tr["hot_share"]),
            "rows_median": tr["rows_median"], "rows_p95": tr["rows_p95"],
            "rows_max": int(tr["rows_max"]),
            "pool_rows": int(tr["pool_rows"]),
            "timeout_s": float(tr["timeout_s"]),
            "data": ctx["cfg"]["data"], "dims": int(model["dims"])}


def window(ctx: dict, rate: float, seconds: float, seed: int,
           trace: bool) -> dict:
    """One measured window at `rate`: start the child, wait for it, read
    what it recorded."""
    import jax
    srv = ctx["server"]
    spec = _spec(ctx, rate, seconds, seed)
    spec_path = os.path.join(ctx["work_dir"], "loadgen_spec.json")
    out_path = os.path.join(ctx["work_dir"], "loadgen_out.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    tracer = None
    if trace:
        from hivemall_tpu.obs.trace import get_tracer
        tracer = get_tracer().enable()
    clock = common.SpanClock()
    child_env = {k: v for k, v in os.environ.items()
                 if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    t_spawn = common.now()
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(loadgen.__file__), spec_path,
         out_path], stdout=subprocess.PIPE, env=child_env, text=True)
    try:
        line = child.stdout.readline()
        if not line.startswith("ready "):
            raise RuntimeError(f"load generator did not start: {line!r}")
        ctx["timings"]["loadgen_start_s"] = common.now() - t_spawn
        mono_to_perf = time.perf_counter() - time.monotonic()
        t0 = float(line.split()[1]) + spec["lead_s"] + mono_to_perf
        t1 = t0 + seconds
        trace_dir = os.path.join(ctx["work_dir"], "trace")
        sync_perf = None
        time.sleep(max(0.0, t0 - common.now() - (0.5 if trace else 0.0)))
        compiles0 = ctx["dp"].compiles
        b0 = (srv.batcher.batches, srv.batcher.batch_rows_sum)
        if trace:
            sync_perf = xplane.start(trace_dir)
        time.sleep(max(0.0, t1 - common.now()))
        if trace:
            jax.profiler.stop_trace()
            tracer.disable()
        b1 = (srv.batcher.batches, srv.batcher.batch_rows_sum)
        rc = child.wait(timeout=spec["timeout_s"] + 30.0)
        if rc != 0:
            raise RuntimeError(f"load generator exited with {rc}")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        child.stdout.close()
    got = common.load_json(out_path)
    lead = spec["lead_s"]
    by_i = {r["i"]: r for r in got["results"]}
    lat, late, records, failed = [], [], [], 0
    for i, due in enumerate(got["due"]):
        if not (lead <= due < lead + seconds):
            continue
        r = by_i.get(i)
        ok = (r is not None and r["status"] == 200
              and r["scores"] is not None
              and len(r["scores"]) == got["rows"][i])
        if ok:
            lat.append((r["done"] - due) * 1e3)
            late.append(max(0.0, (r["sent"] - due) * 1e3))
            records.append(dict(r, due=due, rows=got["rows"][i],
                                start=got["start"][i]))
        else:
            failed += 1
            lat.append(FAILED_MS)
    # the work completed inside the window, whenever it was due: rows of
    # the requests answered in full between its first and last instant
    rows_done = sum(got["rows"][r["i"]] for r in got["results"]
                    if lead <= r["done"] < lead + seconds
                    and r["status"] == 200 and r["scores"] is not None
                    and len(r["scores"]) == got["rows"][r["i"]])
    out = {"t0": t0, "t1": t1, "seconds": seconds, "rate_rps": rate,
           "rows_done": rows_done,
           "retried": sum(1 for r in got["results"] if r.get("retried")),
           "attempted": len(lat), "failed": failed, "latency_ms": lat,
           "late_ms": late, "records": records, "hops": _hops(records),
           "batcher": {"batches": b1[0] - b0[0], "rows": b1[1] - b0[1]},
           "compiles": ctx["dp"].compiles - compiles0,
           "spec": spec, "spans": [], "trace": None}
    if trace:
        out["spans"] = clock.spans(tracer, t0, t1)
        out["trace"] = xplane.reduce_window(
            trace_dir, sync_perf, (max(t0, sync_perf), t1), out["spans"])
    return out


def teardown(ctx: dict) -> None:
    ctx["server"].stop()
    ctx["engine"].close()
    ctx["engine"] = ctx["server"] = None
    shutil.rmtree(os.path.join(ctx["work_dir"], "ck"), ignore_errors=True)


def sample_records(records: list, n: int, seed: int) -> list:
    """`n` answered requests drawn from the seed, the longest among them."""
    if not records:
        return []
    rng = np.random.default_rng([int(seed), 0x5A3D])
    longest = max(range(len(records)), key=lambda i: records[i]["rows"])
    pick = set(rng.choice(len(records), min(n, len(records)),
                          replace=False).tolist()) | {longest}
    return [records[i] for i in sorted(pick)]


def served_numbers(ctx: dict, win: dict) -> dict:
    """score_gap: the widest gap between a served score and the
    reference's for the same row, over the sampled requests.
    answers_missing: requests of the window that got no answer, a wrong
    number of scores or another model's step."""
    cfg, seed = ctx["cfg"], ctx["seed"]
    reference = common.family_module("reference", cfg["family"])
    sample = sample_records(win["records"],
                            int(ctx["traffic"]["sample_requests"]),
                            win["spec"]["seed"])
    pool = loadgen.pool_rows(win["spec"])
    ids = [loadgen.request_ids(pool, r["start"], r["rows"]) for r in sample]
    wrong_step = sum(1 for r in win["records"] if r["step"] != ctx["step"])
    numbers = {"answers_missing": float(win["failed"] + wrong_step)}
    if not sample:
        numbers["score_gap"] = float("inf")
        return numbers
    gen_ids, gen_labels = ctx["gen"]
    S, B, F = ctx["train"]["ids"].shape
    rowno, missed = data.match_rows(gen_ids, gen_labels,
                                    ctx["train"]["ids"].reshape(-1, F),
                                    ctx["train"]["labels"].reshape(-1))
    if missed:
        numbers["score_gap"] = float("inf")
        return numbers
    all_ids = np.concatenate(ids)
    ref = reference.run(cfg, seed, gen_ids[rowno].reshape(S, B, F),
                        gen_labels[rowno].reshape(S, B), extra_ids=all_ids)
    want = reference.score(cfg, ref, all_ids)
    got = np.concatenate([np.asarray(r["scores"], np.float64)
                          for r in sample])
    numbers["score_gap"] = float(np.max(np.abs(got - want)))
    numbers["_rows_compared"] = len(got)
    return numbers


def run(env: dict) -> dict:
    args, traffic = env["args"], env["traffic"]
    ctx = setup(env)
    seconds = min(float(args.seconds), TRACE_SECONDS) if args.trace \
        else float(args.seconds)
    try:
        win = window(ctx, float(traffic["rate_rps"]), seconds, args.seed,
                     bool(args.trace))
        peak = common.memory_peak_bytes()
    finally:
        teardown(ctx)
    t = common.now()
    numbers = served_numbers(ctx, win)
    ctx["timings"]["rows_compared"] = numbers.pop("_rows_compared", 0)
    ctx["timings"]["check_s"] = common.now() - t
    verdict = check.verdict(
        numbers, ctx["cfg"]["correct"]["predict_open_loop"]["limits"])
    lat = np.asarray(win["latency_ms"])
    return {"end_to_end": {"predict_p50": float(np.percentile(lat, 50)),
                           "predict_p95": float(np.percentile(lat, 95)),
                           "setup_s": win["t0"] - env["t_start"]},
            "attempted": win["attempted"], "failed": win["failed"],
            "memory_peak_bytes": peak, "verdict": verdict,
            "window": {"seconds": win["seconds"], "rate_rps": win["rate_rps"],
                       "requests": win["attempted"],
                       "rows_done": win["rows_done"],
                       "retried": win["retried"],
                       "compiles": win["compiles"],
                       "batches": win["batcher"]["batches"],
                       "rows": win["batcher"]["rows"]},
            "hops": win["hops"], "batcher": win["batcher"],
            "loadgen": {"late_ms": win["late_ms"]},
            "latency_ms": win["latency_ms"],
            "timings": ctx["timings"], "spans": win["spans"],
            "trace": win["trace"]}
