#!/usr/bin/env python3
"""Run a cell whose files are in the tree but which BENCHMARK.json does not
list yet (PERF.md, Open questions): the same command, against a copy of
BENCHMARK.json with the cell's entries from `unlisted_cells.json` laid on
it. The tests use it, and so does the session that brings such a cell to
the chip before it lists it.

    python3 benchmark/tests/run_unlisted.py --workload <config>.<traffic> \\
        -- --seed n --seconds s --trace 0|1
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def with_cell(bench: dict, name: str) -> dict:
    """`bench` with the unlisted cell `name`, its configuration, and the
    metrics it reports. A training cell joins every metric that
    `fm_criteo.stream` reports; the fragment's own metrics name their
    cells themselves."""
    with open(os.path.join(HERE, "unlisted_cells.json")) as f:
        frag = json.load(f)
    if any(w["name"] == name for w in bench["workloads"]):
        return bench
    cell = dict(next(w for w in frag["workloads"] if w["name"] == name))
    if cell.pop("train"):
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "fm_criteo.stream" in m.get("workloads", []):
                m["workloads"].append(name)
    if not any(c["name"] == cell["config"] for c in bench["configs"]):
        bench["configs"].append(next(c for c in frag["configs"]
                                     if c["name"] == cell["config"]))
    bench["workloads"].append(cell)
    for group in ("end_to_end", "per_layer"):
        have = {m["name"] for m in bench[group]}
        bench[group] += [m for m in frag[group]
                         if name in m["workloads"] and m["name"] not in have]
    return bench


def bench_file_for(workload: str) -> str:
    """The path of a BENCHMARK.json that lists `workload`, written under
    the benchmark's scratch directory."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = with_cell(json.load(f), workload)
    path = os.path.join(BENCH, ".run", "BENCHMARK.unlisted.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("rest", nargs=argparse.REMAINDER)
    args = p.parse_args()
    import run as bench_run
    rest = [a for a in args.rest if a != "--"]
    return bench_run.main(["--workload", args.workload] + rest,
                          bench_file=bench_file_for(args.workload))


if __name__ == "__main__":
    sys.exit(main())
