# Ran in round 5 on one TPU v5e chip reached over the relay link that PR 21
# retired; its readings have not been re-run on this chip (PERF.md holds those).
"""Phase breakdown of the WARM RandomForest fit (round 5: StagedMatrix +
-bootstrap poisson made the bench repeat-path 1.65 s at 1M x 28 x 16
trees — where does that go now that quantize/h2d/bootstrap-h2d are off
the clock?"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from hivemall_tpu.models.trees import RandomForestClassifier, StagedMatrix
from hivemall_tpu.ops.trees import build_tree_classifier, predict_bins_device

n, d, depth, E = 1_000_000, 28, 8, 16
rng = np.random.default_rng(0)
X = rng.normal(0, 1, (n, d)).astype(np.float32)
y = (X[:, :4].sum(1) + 0.5 * rng.normal(0, 1, n) > 0).astype(np.int32)

t0 = time.perf_counter()
Xs = StagedMatrix.stage(X, 64)
float(np.asarray(Xs.binsj[0, 0]))
print(f"stage (quantize + h2d): {time.perf_counter()-t0:6.2f} s", flush=True)

# warm compiles
RandomForestClassifier(f"-trees {E} -depth {depth} -seed 7 "
                       f"-bootstrap poisson").fit(Xs, y)

def timed(label, fn, reps=3):
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    print(f"{label:34s} {best:6.3f} s", flush=True)
    return out

# full warm fit
timed("full warm fit", lambda: RandomForestClassifier(
    f"-trees {E} -depth {depth} -seed 31 -bootstrap poisson").fit(Xs, y))

# build only (device bootstrap + builder, one value-synced fetch)
yj = np.searchsorted(np.unique(y), y)
key = jax.random.PRNGKey(38)
w = jax.random.poisson(key, 1.0, (E, n)).astype(jnp.int8)
w.block_until_ready()

def build_only():
    tree = build_tree_classifier(Xs.binsj, yj, w, Xs.edges, 2, depth=depth,
                                 n_bins=64, mtry=5, min_split=2.0,
                                 min_leaf=1.0, seed=31, n_trees=E)
    return tree

tree = timed("build_tree_classifier (synced)", build_only)

# OOB pass only
def oob_only():
    preds = predict_bins_device(tree, Xs.binsj)
    pe = preds.argmax(-1)
    oob = jnp.asarray(w) == 0
    n_oob = jnp.maximum(oob.sum(1), 1)
    err = ((pe != jnp.asarray(yj)[None, :]) & oob).sum(1) / n_oob
    return float(np.asarray(err.sum()))

timed("OOB predict+error (synced)", oob_only)

# poisson bootstrap generation alone
def boot_only():
    ww = jax.random.poisson(jax.random.PRNGKey(39), 1.0,
                            (E, n)).astype(jnp.int8)
    return float(np.asarray(ww.sum(), np.float64))

timed("poisson bootstrap (synced)", boot_only)
