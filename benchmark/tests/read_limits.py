#!/usr/bin/env python3
"""Read, on the chip and at a cell's own sizes, the numbers its limits are
set from: what sound runs of the program give over many seeds (the lower
reading), what the control gives (the reference in the precision below the
one the configuration states, put in the program's place) and what each
planted fault gives (the upper readings). One process, many seeds, no
measured window for training; short windows at the cell's own rate for
serving. Prints one JSON line per seed on standard output.

    python3 benchmark/tests/read_limits.py --workload <cell> \\
        --seeds 11,12,13 [--control-seeds 3] [--model-seeds 2]

Not run by the benchmark's own runs or by pytest."""

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


def train_cell(cell, seeds, n_control):
    import jax
    import numpy as np
    from harness import check, common, data, job_stream
    from hivemall_tpu.io.arrow import ParquetStream
    cfg = cell["cfg"]
    model = cfg["model"]
    B, F = int(model["mini_batch"]), int(model["fields"])
    reference = common.family_module("reference", cfg["family"])
    control = cfg["correct"]["stream"]["control_precision"]
    spec = data.RowSpec(cfg["data"], int(model["dims"]))
    shard_dir = os.path.join(common.RUN_DIR, "read_limits", "shards")
    for n, big_seed in enumerate(seeds):
        seed = common.seed31(big_seed)
        trainer = job_stream.build_trainer(cfg, seed)
        K = job_stream.steps_per_dispatch(cfg)
        ids, labels = data.make_rows(spec, K * B, big_seed)
        data.write_shards(ids, labels, shard_dir, K * B // 2,
                          with_fields=bool(cfg.get("needs_fields")))
        first = list(ParquetStream(shard_dir).batches(B, epochs=1, max_len=F))
        prog = job_stream.first_dispatch(trainer, cfg, reference, first)
        trainer.params = trainer.opt_state = None
        del trainer, first
        numbers = job_stream.compare_first_dispatch(
            cfg, seed, reference, prog, ids, labels)
        line = {"seed": big_seed, "seconds": numbers.pop("_seconds"),
                "program": numbers}      # "_step_loss_gaps" rides along
        if n < n_control:
            S = prog["ids"].shape[0]
            rid, rlab = prog["ids"], prog["labels"]
            init = reference.initial_rows(cfg, seed, prog["keys"])
            ref = reference.run(cfg, seed, rid, rlab, init=init)
            for name, kw in (("control", {"precision": control}),
                             ("half_batch", {"fault": "half_batch"})):
                bad = reference.run(cfg, seed, rid, rlab, init=init, **kw)
                line[name] = check.train_numbers(bad, ref)
            same = dict(ref, after=ref["before"],
                        gg={k: np.zeros_like(v) for k, v in ref["gg"].items()})
            line["state_unchanged"] = check.train_numbers(same, ref)
        print(json.dumps(line), flush=True)


def serve_cell(cell, seeds, n_control, model_seeds, seconds):
    import argparse as ap
    import numpy as np
    from harness import common, loadgen, job_predict_open_loop as job
    cfg, traffic = cell["cfg"], cell["traffic"]
    control = cfg["correct"]["predict_open_loop"]["control_precision"]
    per_model = -(-len(seeds) // model_seeds)
    done = 0
    for m in range(model_seeds):
        mine = seeds[m * per_model:(m + 1) * per_model]
        if not mine:
            break
        env = {"cfg": cfg, "traffic": traffic, "workload": "read_limits",
               "t_start": T_START,
               "args": ap.Namespace(seed=mine[0], seconds=seconds, trace=0)}
        ctx = job.setup(env)
        wins = []
        try:
            for s in mine:
                wins.append((s, job.window(ctx, float(traffic["rate_rps"]),
                                           seconds, s, False)))
        finally:
            job.teardown(ctx)
        for s, win in wins:
            line = {"seed": s, "model_seed": mine[0],
                    "requests": win["attempted"],
                    "program": job.served_numbers(ctx, win)}
            if done < n_control:
                # the control in the program's place: its scores for the
                # same rows against the reference's
                reference = common.family_module("reference", cfg["family"])
                sample = job.sample_records(
                    win["records"], int(traffic["sample_requests"]), s)
                pool = loadgen.pool_rows(win["spec"])
                ids = np.concatenate([loadgen.request_ids(
                    pool, r["start"], r["rows"]) for r in sample])
                tr = ctx["train"]
                init = reference.initial_rows(
                    cfg, ctx["seed"], reference.table_keys(
                        cfg, np.concatenate([tr["ids"].reshape(-1),
                                             ids.reshape(-1)])))
                ref = reference.run(cfg, ctx["seed"], tr["ids"], tr["labels"],
                                    extra_ids=ids, init=init)
                low = reference.run(cfg, ctx["seed"], tr["ids"], tr["labels"],
                                    extra_ids=ids, init=init,
                                    precision=control)
                gap = np.abs(reference.score(cfg, low, ids)
                             - reference.score(cfg, ref, ids))
                line["control"] = {"score_gap": float(gap.max()),
                                   "rows": int(len(gap))}
                altered = np.asarray(sample[0]["scores"], np.float64)
                line["altered_answer"] = {"score_gap": float(np.max(np.abs(
                    np.roll(altered, 1) - altered)))} if len(altered) > 1 \
                    else None
                done += 1
            print(json.dumps(line), flush=True)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--model-seeds", type=int, default=2)
    p.add_argument("--seconds", type=float, default=6.0)
    args = p.parse_args()
    from harness import common
    common.compile_cache_here()
    import jax
    import run as bench_run
    toy = os.environ.get("JAX_PLATFORMS") == "cpu"
    from run_unlisted import bench_file_for
    cell = bench_run.load_cell(args.workload, toy,
                               bench_file_for(args.workload))
    seeds = [int(s) for s in args.seeds.split(",")]
    print(json.dumps({"workload": args.workload, "toy": toy,
                      "device": str(jax.devices()[0])}), flush=True)
    if cell["traffic"]["job"] == "stream":
        train_cell(cell, seeds, args.control_seeds)
    else:
        serve_cell(cell, seeds, args.control_seeds, args.model_seeds,
                   args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
