#!/usr/bin/env python3
"""One traced run of a cell on the chip, and with it the feed's account
thread by thread (harness/feed_trace.py), which the result line has no
room for: PERF.md section 5's per-thread table is this script's output.

    python3 benchmark/tests/feed_account.py --workload fm_criteo.stream \\
        --seed 4001 [--seconds 30] [--out chiprun_out]

Beside the account it checks what the account rests on, over the whole of
the window's `fit_stream` call (the tracer is on from its start and its
`PipelineStats` are born there): each wait span's total against the
counter that sums the same seconds, `source.decode` against the stream's
decode seconds, and `spans.dropped`."""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--out", default="chiprun_out")
    p.add_argument("--toy", action="store_true")
    args = p.parse_args(argv)

    import run as bench_run
    sys.path.insert(0, bench_run.ROOT)
    from hivemall_tpu.io import arrow
    streams, trainers = [], []
    init = arrow.ParquetStream.__init__

    def keep(self, *a, **kw):
        init(self, *a, **kw)
        streams.append(self)

    arrow.ParquetStream.__init__ = keep
    cmd = ["--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", "1"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(cmd + (["--toy"] if args.toy else []),
                            break_trainer=trainers.append)
    lines = out.getvalue().strip().splitlines()
    sys.stdout.write("\n".join(lines[:-1]) + "\n")
    if rc != 0:
        return rc
    res = json.loads(lines[-1])

    from harness import feed_trace, program_trace
    from hivemall_tpu.obs.trace import get_tracer
    spans = program_trace.all_spans()
    w = res["notes"]["window"]
    inside = [s for s in spans if w["t0"] <= s["start"] + s["dur"] <= w["t1"]]
    totals = {}
    for s in spans:
        totals[s["name"]] = totals.get(s["name"], 0.0) + s["dur"]
    pipe, decode = trainers[0].pipeline_stats, streams[-1].stats
    pairs = {"ingest.wait_prep": pipe.prep_wait_seconds,
             "ingest.wait_slot": pipe.prep_backpressure_seconds,
             "source.decode": decode.prep_seconds,
             "source.wait_shard": decode.prep_wait_seconds,
             "feed.wait_slot": None}        # no counter sums this one
    report = {
        "workload": args.workload, "seed": args.seed,
        "correct": res["correct"], "device": res["device"],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "idle_gaps": (res.get("breakdown") or {}).get("idle_gaps"),
        "window": w,
        "account": feed_trace.accounts(
            inside, w["t0"], w["steps_per_dispatch"],
            w["steps_per_dispatch"] * w["batch"]),
        "span_against_counter": {
            name: {"span_s": totals.get(name, 0.0), "counter_s": counter}
            for name, counter in pairs.items()},
        "spans_dropped": get_tracer().dropped,
        # every span that ended in the window, for a look at one shard or
        # one dispatch: [name, thread, start - t0, dur, cpu, batch, seq]
        "spans": [[s["name"], s["args"].get("thread"),
                   round(s["start"] - w["t0"], 6), round(s["dur"], 6),
                   s["args"].get("cpu"), s["args"].get("batch"),
                   s["args"].get("seq")]
                  for s in sorted(inside, key=lambda s: s["start"])],
    }
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"feed_account_{args.workload}_"
                                  f"{args.seed}.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report["metrics"]))
    print(f"spans dropped {report['spans_dropped']}")
    for name, t in sorted((report["account"] or {}).items()):
        print(f"{name:14s} {t['kind']:7s} window {t['window_s']:.3f} "
              f"dispatches {t['dispatches']:.2f} wait {t['wait_s']:.3f} "
              f"work {t['work_s']:.3f} remainder {t['remainder_s']} busy "
              f"{t['busy_s']:.3f} cycle_ms {t['cycle_ms']:.1f} free_ms "
              f"{t['free_ms']} work cpu/wall {t['work_cpu_s']:.3f}/"
              f"{t['work_wall_s']:.3f}")
        for sname, v in sorted(t["spans"].items()):
            print(f"    {sname:20s} n {v['n']:5d} wall {v['wall_s']:.4f} "
                  f"cpu {v['cpu_s']:.4f}")
    for name, v in report["span_against_counter"].items():
        print(f"{name}: spans {v['span_s']:.4f} s, counter {v['counter_s']}")
    print(lines[-1])
    return 0


if __name__ == "__main__":
    sys.exit(main())
