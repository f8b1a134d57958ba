#!/usr/bin/env python
"""BASELINE config #2 (north star): FFM on Criteo-like CTR data.

Usage: python examples/criteo_ffm.py [--rows N] [--fields F]
Synthetic categorical rows run through the real pipeline: ffm_features
builds "field:index:value" strings (SURVEY.md §3.12), train_ffm consumes
them with hashed (feature, field) latent tables, and the report carries
logloss + examples/sec (BASELINE metric).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=20000)
    ap.add_argument("--fields", type=int, default=13)
    ap.add_argument("--factors", type=int, default=4)
    ap.add_argument("--data", default=None,
                    help="tsv of 'label\\tfield:idx:val ...' rows, e.g. "
                         "tests/resources/criteo_ffm.frag.tsv")
    ap.add_argument("--mesh", default=None,
                    help="GSPMD-shard the trainer, e.g. 'dp=2,tp=4' "
                         "(CPU demo: JAX_PLATFORMS=cpu XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    args = ap.parse_args()
    mesh_opt = f" -mesh {args.mesh}" if args.mesh else ""

    from hivemall_tpu.catalog.registry import lookup
    from hivemall_tpu.frame.evaluation import auc, logloss

    ffm_features = lookup("ffm_features").resolve()
    Trainer = lookup("train_ffm").resolve()

    if args.data:
        rows, labels = [], []
        for line in open(args.data):
            yv, _, feats = line.rstrip("\n").partition("\t")
            labels.append(float(yv))
            rows.append(feats.split())
        F = 1 + max(int(f.split(":")[0]) for r in rows for f in r)
        tr = Trainer(f"-dims 16384 -factors {args.factors} -fields {F} "
                     f"-opt adagrad -eta0 0.2 -lambda_v 0 -lambda_w 0 "
                     f"-sigma 0.05 -classification -mini_batch 64 -iters 10")
        t0 = time.time()
        for r, lab in zip(rows, labels):
            tr.process(r, lab)
        list(tr.close())
        dt = time.time() - t0
        from hivemall_tpu.io.sparse import SparseDataset
        parsed = [tr._parse_row(r) for r in rows]
        ds = SparseDataset.from_rows([(i, v) for i, v, f in parsed], labels,
                                     [f for i, v, f in parsed])
        p = tr.predict(ds)
        print(json.dumps({
            "config": "criteo_ffm",
            "cumulative_logloss": round(tr.cumulative_loss, 5),
            "train_auc": round(auc(np.asarray(labels), p), 5),
            "wall_examples_per_sec": round(
                len(rows) * 10 / max(dt, 1e-9), 1),
            "synthetic": False,
        }))
        return 0

    rng = np.random.default_rng(3)
    F = args.fields
    cards = rng.integers(10, 1000, F)          # per-field cardinalities
    cols = [f"c{f}" for f in range(F)]
    # a planted low-rank signal: label depends on two field interactions
    from hivemall_tpu.utils.hashing import murmurhash3_x86_32
    rows_cat = [[f"v{rng.integers(cards[f])}" for f in range(F)]
                for _ in range(args.rows)]
    # murmur3, not builtin hash(): labels must be process-independent
    y = np.asarray([1 if murmurhash3_x86_32(r[0] + r[1]) % 100 < 55 else -1
                    for r in rows_cat])

    tr = Trainer(f"-dims 262144 -factors {args.factors} -fields {F} "
                 f"-opt adagrad -classification -mini_batch 1024" + mesh_opt)
    t0 = time.time()
    for r, lab in zip(rows_cat, y):
        tr.process(ffm_features(cols, *r), int(lab))
    list(tr.close())
    dt = time.time() - t0
    print(json.dumps({
        "config": "criteo_ffm",
        "cumulative_logloss": round(tr.cumulative_loss, 5),
        # wall time includes jit compile + host row parse; speed is
        # measured by the benchmark under benchmark/ (PERF.md)
        "wall_examples_per_sec": round(args.rows / max(dt, 1e-9), 1),
        "synthetic": True,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
