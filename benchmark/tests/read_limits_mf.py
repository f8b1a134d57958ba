#!/usr/bin/env python3
"""Read, on the chip and at the cell's own sizes, the numbers the limits of
`mf_hugewiki.stream_ratings` are set from (`read_limits.py` knows the
`stream` and the serving jobs only): what sound first dispatches of the
program give over many seeds, and, reference against reference, the
control (bfloat16 in the program's place), half of every step's ratings
left out, and the state left unchanged. One process, many seeds, no
measured window. One JSON line per seed.

    python3 benchmark/tests/read_limits_mf.py --seeds 11,12,13 \\
        [--control-seeds 2]

Not run by the benchmark's own runs or by pytest."""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))
CELL = "mf_hugewiki.stream_ratings"


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=2)
    args = p.parse_args()
    import numpy as np
    from harness import check, common, job_stream, ratings
    from harness import job_stream_ratings as job
    common.compile_cache_here()
    import jax
    import run as bench_run
    toy = os.environ.get("JAX_PLATFORMS") == "cpu"
    cell = bench_run.load_cell(CELL, toy)
    cfg, traffic, m = cell["cfg"], cell["traffic"], cell["cfg"]["model"]
    reference = common.family_module("reference", cfg["family"])
    control = cfg["correct"]["stream_ratings"]["control_precision"]
    B, K = int(m["mini_batch"]), job_stream.steps_per_dispatch(cfg)
    law = ratings.RatingLaw(traffic["ratings"], int(m["users"]),
                            int(m["items"]))
    shards = os.path.join(common.RUN_DIR, "read_limits_mf", "shards")
    print(json.dumps({"workload": CELL, "toy": toy,
                      "device": str(jax.devices()[0])}), flush=True)
    for n, big_seed in enumerate(int(s) for s in args.seeds.split(",")):
        seed = common.seed31(big_seed)
        gen = ratings.make_ratings(law, K * B, big_seed)
        ratings.write_shards(*gen, shards, B)
        first = list(itertools.islice(job.open_stream(shards).batches(
            B, epochs=1, max_len=2, **traffic.get("batches", {})), K))
        trainer = job.build_trainer(cfg, seed)
        prog = job.first_dispatch(trainer, cfg, reference, first)
        trainer.params = trainer.opt_state = None
        del trainer, first
        numbers = job.compare_first_dispatch(cfg, seed, reference, prog, gen)
        line = {"seed": big_seed, "seconds": numbers.pop("_seconds"),
                "program": numbers}      # "_step_loss_gaps" rides along
        if n < args.control_seeds:
            ids, labels = prog["ids"], prog["labels"]
            init = reference.initial_rows(cfg, seed, prog["keys"])
            ref = reference.run(cfg, seed, ids, labels, init=init)
            for name, kw in (("control", {"precision": control}),
                             ("half_ratings", {"fault": "half_ratings"})):
                bad = reference.run(cfg, seed, ids, labels, init=init, **kw)
                line[name] = check.train_numbers(bad, ref)
            same = dict(ref, after=ref["before"],
                        gg={k: np.zeros_like(v) for k, v in ref["gg"].items()})
            line["state_unchanged"] = check.train_numbers(same, ref)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
