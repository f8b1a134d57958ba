#!/usr/bin/env python3
"""Find the knee of the serving cell once, on the chip: one set-up, then a
short window at each of a list of rates. A rate is sustained when nothing
failed or was shed and the backlog did not grow (the second half of the
window is no slower than the first beyond noise). The traffic file's
`rate_rps` is then fixed at 0.8 x the highest sustained rate.

    python3 benchmark/tests/sweep_knee.py --workload <cell> \\
        --rates 20,40,60,80,100 [--seconds 12] [--seed 5]"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=12.0)
    p.add_argument("--seed", type=int, default=5)
    p.add_argument("--seeds", default="",
                   help="one seed per rate (default: --seed, --seed+1, ...): "
                        "the same rate and seed twice shows what the system "
                        "alone varies by")
    args = p.parse_args()
    from harness import common
    common.compile_cache_here()
    import jax
    import numpy as np
    import run as bench_run
    from harness import job_predict_open_loop as job
    toy = os.environ.get("JAX_PLATFORMS") == "cpu"
    from run_unlisted import bench_file_for
    cell = bench_run.load_cell(args.workload, toy,
                               bench_file_for(args.workload))
    env = {"cfg": cell["cfg"], "traffic": cell["traffic"],
           "workload": "sweep_knee", "t_start": T_START,
           "args": argparse.Namespace(seed=args.seed, seconds=args.seconds,
                                      trace=0)}
    ctx = job.setup(env)
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "timings": ctx["timings"],
                      "device": str(jax.devices()[0])}), flush=True)
    try:
        rates = [float(r) for r in args.rates.split(",")]
        seeds = [int(s) for s in args.seeds.split(",")] if args.seeds \
            else [args.seed + n for n in range(len(rates))]
        for rate, seed in zip(rates, seeds):
            win = job.window(ctx, rate, args.seconds, seed, False)
            lat = np.asarray(win["latency_ms"])
            half = len(lat) // 2
            print(json.dumps({
                "rate_rps": rate, "seed": seed, "requests": len(lat),
                "failed": win["failed"], "retried": win["retried"],
                "rows_per_s_done": win["rows_done"] / win["seconds"],
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p50_first_half_ms": float(np.median(lat[:half])),
                "p50_second_half_ms": float(np.median(lat[half:])),
                "late_p95_ms": float(np.percentile(win["late_ms"], 95))
                if win["late_ms"] else None,
                "mean_batch_rows": win["batcher"]["rows"]
                / max(1, win["batcher"]["batches"]),
                "queue_ms_p50": float(np.median(win["hops"]["queue"]))
                if win["hops"].get("queue") else None,
                "predict_ms_p50": float(np.median(win["hops"]["predict"]))
                if win["hops"].get("predict") else None,
                "parse_ms_p50": float(np.median(win["hops"]["parse"]))
                if win["hops"].get("parse") else None}), flush=True)
    finally:
        job.teardown(ctx)
    return 0


if __name__ == "__main__":
    sys.exit(main())
