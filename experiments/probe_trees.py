# Ran in round 4 on one TPU v5e chip reached over the relay link that PR 21
# retired; its readings have not been re-run on this chip (PERF.md holds those).
"""Round-4 tree-kernel probe (VERDICT r3 weak #5 / next #6).

Questions:
 1. Of the RF build's ~8.5 s at 1M x 28 x 16 trees, how much is the
    dense-channel histogram kernel vs routing/gains/bookkeeping?
 2. Does fusing the per-feature [n_bins, CHUNK] x [CHUNK, cs] matmuls into
    ONE [d*n_bins, CHUNK] x [CHUNK, cs] matmul per chunk-step (bigger
    M-axis, one VMEM accumulate instead of d slices) beat the shipped
    kernel?
"""
import sys, time
import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np, jax, jax.numpy as jnp
from functools import partial
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from hivemall_tpu.ops.pallas_hist import level_histogram_dense

def sync(x):
    return float(np.asarray(jnp.asarray(x).astype(jnp.float32).sum()))

n, d, E, Bn = 1_000_000, 28, 16, 64
depth = 8
rng = np.random.default_rng(0)
bins = rng.integers(0, Bn, (n, d)).astype(np.int32)
np_ = -(-n // 1024) * 1024
dp = -(-d // 8) * 8
bins_t = jnp.asarray(np.pad(bins, ((0, np_ - n), (0, dp - d)),
                            constant_values=-1).T)
S = 2
ws = jnp.asarray(rng.random((np_, S)).astype(np.float32))

# --- 1. hist-only cost across the level schedule, vmapped over E trees ---
LEVELS = (0, 4, 6, 8)   # probe the MAC-light and MAC-heavy ends
locs = {}
for t in LEVELS:
    M = 2 ** t
    locs[t] = jnp.asarray(rng.integers(0, M, (E, np_)).astype(np.int32))

times = {}
for t in LEVELS:
    M = 2 ** t
    f = jax.jit(jax.vmap(lambda l: level_histogram_dense(
        bins_t, l, ws, M, Bn, fast=True)))
    r = f(locs[t]); sync(r[..., 0].sum())           # warm
    t0 = time.perf_counter()
    r = f(locs[t]); sync(r[..., 0].sum())
    times[t] = time.perf_counter() - t0
tot = sum(times.values())
print("hist-only per level:",
      {t: round(v, 3) for t, v in times.items()})
print(f"hist-only total over probed levels: {tot:.2f}s")

# --- 2. fused-feature variant ---
_CHUNK = 512

def _fused_kernel(bins_ref, loc_ref, ws_ref, out_ref, *, d, n_bins, S, cs):
    g = pl.program_id(0)
    first = pl.program_id(1) == 0
    loc = loc_ref[0, :]
    col = jax.lax.broadcasted_iota(jnp.int32, (cs, _CHUNK), 0)
    node_col = col // S + g * (cs // S)
    s_col = col % S
    w2t = jnp.zeros((cs, _CHUNK), jnp.float32)
    for s in range(S):
        w2t = jnp.where(s_col == s, ws_ref[s, :][None, :], w2t)
    w2t = jnp.where(node_col == loc[None, :], w2t, 0.0)
    # fused one-hot over ALL features: [(f,b), CHUNK]
    fb = jax.lax.broadcasted_iota(jnp.int32, (d * n_bins, _CHUNK), 0)
    frow = fb // n_bins
    brow = fb % n_bins
    bv = jnp.zeros((d * n_bins, _CHUNK), jnp.int32)
    for f in range(d):
        bv = jnp.where(frow == f, bins_ref[f, :][None, :], bv)
    oh = (brow == bv).astype(jnp.bfloat16)
    acc = jax.lax.dot_general(
        oh, w2t.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)

    @pl.when(first)
    def _():
        out_ref[0] = acc

    @pl.when(jnp.logical_not(first))
    def _():
        out_ref[0] += acc

def fused_hist(bins_t, loc, ws, n_nodes, n_bins):
    import math as _math
    dp, np_ = bins_t.shape
    S = ws.shape[1]
    cs_need = n_nodes * S
    cs0 = (S * 128) // _math.gcd(S, 128)
    cs = min(max(512 // cs0, 1) * cs0, -(-cs_need // cs0) * cs0)
    n_groups = -(-cs_need // cs)
    locp = loc.reshape(1, np_)
    wsp = ws.T
    out = pl.pallas_call(
        partial(_fused_kernel, d=dp, n_bins=n_bins, S=S, cs=cs),
        grid=(n_groups, np_ // _CHUNK),
        in_specs=[
            pl.BlockSpec((dp, _CHUNK), lambda g, r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _CHUNK), lambda g, r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((S, _CHUNK), lambda g, r: (0, r),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, dp * n_bins, cs), lambda g, r: (g, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_groups, dp * n_bins, cs),
                                       jnp.float32),
    )(bins_t.astype(jnp.int32), locp, wsp)
    npg = cs // S
    out = out.reshape(n_groups, dp, n_bins, npg, S)
    return out.transpose(0, 3, 1, 2, 4).reshape(
        n_groups * npg, dp, n_bins, S)[:n_nodes]

ftimes = {}
for t in LEVELS:
    M = 2 ** t
    f = jax.jit(jax.vmap(lambda l: fused_hist(bins_t, l, ws, M, Bn)))
    try:
        r = f(locs[t]); sync(r[..., 0].sum())
        t0 = time.perf_counter()
        r = f(locs[t]); sync(r[..., 0].sum())
        ftimes[t] = time.perf_counter() - t0
    except Exception as e:
        print(f"level {t}: fused FAILED: {type(e).__name__} {str(e)[:120]}")
        ftimes[t] = float("nan")
ftot = sum(v for v in ftimes.values() if v == v)
print("fused per level:", {t: round(v, 3) for t, v in ftimes.items()})
print(f"fused total: {ftot:.2f}s")

# numeric agreement at one level
ra = jax.vmap(lambda l: level_histogram_dense(bins_t, l, ws, 16, Bn,
                                              fast=True))(locs[4])
rb = jax.vmap(lambda l: fused_hist(bins_t, l, ws, 16, Bn))(locs[4])
print("agree:", bool(np.allclose(np.asarray(ra), np.asarray(rb),
                                 atol=0.5, rtol=1e-2)))

# --- 3. full-build phase breakdown (run as main part 2) -------------------
def phase_breakdown():
    import time
    from hivemall_tpu.ops.trees import quantize_bins, build_tree_classifier
    from hivemall_tpu.ops.trees import predict_bins_device
    y = (np.asarray(bins[:, :4]).sum(1) > 2 * Bn).astype(np.int32)
    X = rng.normal(0, 1, (n, d)).astype(np.float32)
    t0 = time.perf_counter()
    codes, edges = quantize_bins(X, Bn)
    t1 = time.perf_counter()
    print(f"quantize_bins host: {t1-t0:.2f}s")
    w = np.empty((E, n), np.int8)
    t0 = time.perf_counter()
    r2 = np.random.default_rng(1)
    for e in range(E):
        w[e] = np.bincount(r2.integers(0, n, n), minlength=n).astype(np.int8)
    t1 = time.perf_counter()
    print(f"bootstrap host: {t1-t0:.2f}s")
    t0 = time.perf_counter()
    cj = jnp.asarray(codes); sync(cj[:4, :4].astype(jnp.float32))
    t1 = time.perf_counter()
    print(f"h2d bins ({codes.nbytes/1e6:.0f} MB): {t1-t0:.2f}s")
    t0 = time.perf_counter()
    wj = jnp.asarray(w); sync(wj[:, :4].astype(jnp.float32))
    t1 = time.perf_counter()
    print(f"h2d w ({w.nbytes/1e6:.0f} MB): {t1-t0:.2f}s")
    # full build (includes everything again, warm compile from bench maybe)
    t0 = time.perf_counter()
    tree = build_tree_classifier(cj, y, w, edges, 2, depth=8, n_bins=Bn,
                                 mtry=5, seed=31, n_trees=E)
    jax.block_until_ready(tree.feat)
    sync(jnp.asarray(tree.value).sum())
    t1 = time.perf_counter()
    print(f"build_tree_classifier (given staged bins): {t1-t0:.2f}s "
          f"(first call INCLUDES compile)")
    t0 = time.perf_counter()
    tree = build_tree_classifier(cj, y, w, edges, 2, depth=8, n_bins=Bn,
                                 mtry=5, seed=32, n_trees=E)
    sync(jnp.asarray(tree.value).sum())
    t1 = time.perf_counter()
    print(f"build (warm): {t1-t0:.2f}s")
    t0 = time.perf_counter()
    preds = predict_bins_device(tree, cj)
    sync(preds.sum())
    t1 = time.perf_counter()
    print(f"OOB-style predict sweep: {t1-t0:.2f}s")

if __name__ == "__main__":
    phase_breakdown()

# --- round-5 A/B: is the fused kernel's one-hot CONSTRUCTION the lever?
# Variants of the [d*n_bins, CHUNK] bin one-hot build, measured at the RF
# bench shape (1M rows, d=28, 64 bins, S=2, chunk 512, 256 nodes):
#   d-loop of jnp.where (current)     : 25.26 ms   bit-identical
#   broadcast_to + reshape            : 33.99 ms   (sublane-collapse
#       reshape lowers WORSE than the where-chain)
#   pltpu.repeat(bins, n_bins, 0)     : 25.39 ms   (speed-neutral; row
#       order is b-major so out rows would need the inverse permute)
# Conclusion: the construction is NOT separable overhead — Mosaic already
# overlaps it; the ~27-31% MXU ceiling is the intrinsic compare+accumulate
# mix of this layout, and ROADMAP gap #3 ("a radically different binning
# layout for the next factor") stands confirmed.
