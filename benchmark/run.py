#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <config>.<traffic> --seed n \\
        --seconds s --trace 0|1

Everything a cell needs is found by the names in BENCHMARK.json:
benchmark/configs/<config>.json, benchmark/traffic/<traffic>.json, the job
kind the traffic file names (benchmark/harness/job_<kind>.py), the family's
reference and work functions, and one reader per per-layer metric under
benchmark/layer_metrics/. The last line of standard output is the result
object; everything else is printed before it or on standard error.

It needs a TPU with the chips the cell asks for and exits non-zero without
one. `--toy` (tests only) runs the same code at the toy sizes the files
carry, on a CPU asked for by name, and says so in its result."""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _fail(msg: str, code: int = 2):
    print(f"benchmark: {msg}", file=sys.stderr)
    sys.exit(code)


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--toy", action="store_true",
                   help="tests only: toy sizes on JAX_PLATFORMS=cpu")
    return p.parse_args(argv)


def load_cell(workload: str, toy: bool, bench_file: str = "") -> dict:
    from harness import common
    bench = common.load_json(bench_file
                             or os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        _fail(f"no workload {workload!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = common.load_json(os.path.join(ROOT, config["file"]))
    traffic = common.load_json(os.path.join(
        HERE, "traffic", f"{cell['traffic']}.json"))
    if toy:
        cfg = common.merge(cfg, cfg.get("toy"))
        traffic = common.merge(traffic, traffic.get("toy"))
    return {"bench": bench, "cell": cell, "cfg": cfg, "traffic": traffic}


def metrics_of(bench: dict, workload: str, group: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def layer_values(bench: dict, workload: str, ctx: dict) -> dict:
    """Each per-layer metric the cell lists, from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    from harness import common
    out = {}
    for m in metrics_of(bench, workload, "per_layer"):
        path = os.path.join(HERE, "layer_metrics", f"{m['name']}.py")
        reader = common.load_module(
            path, "layer_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, **hooks) -> int:
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "hivemall_tpu")):
        _fail("the system under test (hivemall_tpu/) is not in this "
              "checkout", 3)
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    if args.toy and os.environ.get("JAX_PLATFORMS") != "cpu":
        _fail("--toy runs only with JAX_PLATFORMS=cpu")
    cell = load_cell(args.workload, args.toy, hooks.pop("bench_file", ""))
    from harness import check, common
    common.compile_cache_here()
    device = common.device_info()
    chips = int(cell["cell"]["chips"])
    if not args.toy and (device["platform"] != "tpu"
                         or device["count"] < chips):
        _fail(f"needs {chips} TPU chip(s); JAX found {device}")
    print(f"benchmark: {args.workload} seed {args.seed} on {device}"
          f"{' (toy sizes, a rehearsal: no number here is a device number)' if args.toy else ''}",
          flush=True)

    job = common.load_module(
        os.path.join(HERE, "harness", f"job_{cell['traffic']['job']}.py"),
        f"harness.job_{cell['traffic']['job']}")
    env = {"cfg": cell["cfg"], "traffic": cell["traffic"], "args": args,
           "workload": args.workload, "chips": chips, "t_start": T_START,
           **hooks}
    res = job.run(env)

    bench = cell["bench"]
    if args.trace:
        ctx = dict(res, job=cell["traffic"]["job"], cfg=cell["cfg"],
                   traffic=cell["traffic"],
                   work=common.family_module("work", cell["cfg"]["family"]),
                   peaks=(None if device["platform"] == "cpu"
                          else common.peaks_for(device["kind"])))
        metrics = layer_values(bench, args.workload, ctx)
    else:
        metrics = {m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in metrics_of(bench, args.workload, "end_to_end")}
    device["memory_peak_bytes"] = int(res["memory_peak_bytes"])
    out = {"correct": bool(res["verdict"]["correct"]),
           "attempted": int(res["attempted"]), "failed": int(res["failed"]),
           "metrics": metrics, "device": device}
    if args.trace and res.get("trace"):
        device["busy_s"] = float(res["trace"]["busy_s"])
        device["window_s"] = float(res["trace"]["window_s"])
        out["breakdown"] = {"device_ops": res["trace"]["device_ops"],
                            "idle_gaps": res["trace"]["idle_gaps"]}
    if args.toy:
        out["rehearsal"] = "toy sizes on the CPU: not a chip run"
    out["notes"] = {"timings": res["timings"],
                    "window": {k: v for k, v in res["window"].items()
                               if not isinstance(v, dict)}}
    out["compared"] = res["verdict"]["compared"]
    sys.stdout.flush()
    check.print_compared(out["compared"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
