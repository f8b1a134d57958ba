# Ran in round 5 on one TPU v5e chip reached over the relay link that PR 21
# retired; its readings have not been re-run on this chip (PERF.md holds those).
"""Probe: would per-field duplicate pre-aggregation beat the Pallas RMW?

VERDICT r4 #1: at flagship shapes (B=32768 slots into MRF=8192-row field
partitions) the mean slot duplication is >=4x by pigeonhole, and the RMW
pass (~22 ms of the 44 ms step) pays ~17 ns per SLOT.  A sort-by-row-id +
segment-sum could reduce the RMW to unique rows only (~0.25x the slots).

The question this probe answers with numbers: does the pre-aggregation
pipeline (sort keys, permute the [B, 2, 128] bf16 gradient slab into
sorted order, segment-sum runs, RMW unique rows) cost LESS than the
17 ns/slot x duplicated-fraction it saves?

Cost model going in (docs/PERFORMANCE.md "cost model" table): every
per-row index op — gather, scatter, RMW — costs 10.7-26 ns/row nearly
independent of row width, and pre-aggregation ADDS one permutation
gather per slot before it REMOVES any RMW.  Sort measured ~120 ms / 13M
int32 keys (~9 ns/key).  So the pipeline's floor is
  sort (~9) + permute-gather (~10.7-17) + segsum + boundary ops
per slot, against a maximum saving of 17 x (1 - unique/slots) ns/slot
(= ~12.8 ns at uniform 4.07x duplication, ~17 ns at infinite
duplication).  If permute-gather alone costs ~>= the RMW it replaces,
the design can NEVER win, on any duplication (Zipf included).

What this probe did and did not price (PR 28). It priced ONE design, for
the FFM parts kernel's field partitions: sort, then an EXPLICIT gather that
permutes the whole gradient slab into sorted order, then a segment-sum and
an RMW of the unique rows, each phase timed alone, on uniform ids (4x
duplication) and hashed Zipf ids into 8,192-row partitions. It did not
price: a scatter-add handed sorted indices and the permutation (XLA reads
the updates through the permutation inside the scatter it would have written
for itself: no extra pass, and its own sort saved); the zero-fill and dense
optimizer pass over a 2 GiB table that a compact gradient makes unnecessary;
or the whole step. `ops/fm.py` `rows_update` is that other design; its
readings are `experiments/probe_distinct_tail.py`'s (PERF.md section 6).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

B, F, K, MRF = 32768, 40, 4, 8192
HP, W = 2, 256
N = B * F

rng = np.random.default_rng(0)


def sync(x):
    return float(np.asarray(jnp.asarray(x).astype(jnp.float32).sum(),
                            np.float64))


def timeit(fn, iters=5, repeats=3):
    out = fn()
    sync(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        sync(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def report(name, secs, n=N):
    print(f"{name:52s} {secs*1e3:9.3f} ms  {secs/n*1e9:6.2f} ns/slot",
          flush=True)


def dup_stats(rows, label):
    u = np.unique(rows.reshape(F, B), axis=1)  # not meaningful per-axis; do per field
    uniq = sum(len(np.unique(rows[g])) for g in range(F))
    print(f"{label}: unique {uniq} / {N} slots = {uniq/N:.3f} "
          f"(dup factor {N/uniq:.2f}x); RMW saving ceiling "
          f"{17*(1-uniq/N):.1f} ns/slot", flush=True)
    return uniq


# --- batch row ids: uniform (bench synthetic) and Zipf (Criteo-like) ----
rows_u = rng.integers(0, MRF, (F, B)).astype(np.int32)
zipf_ids = rng.zipf(1.25, (F, B)).astype(np.int64)
h = (zipf_ids * 0x9E3779B1) & 0xFFFFFFFF
h ^= h >> 15
h = (h * 0xC2B2AE35) & 0xFFFFFFFF
rows_z = (h & (MRF - 1)).astype(np.int32)

uniq_u = dup_stats(rows_u, "uniform")
uniq_z = dup_stats(rows_z, "zipf(1.25)")

grad = jnp.asarray(rng.standard_normal((F, B, HP * 128)),
                   jnp.bfloat16)
keys_u = jnp.asarray(rows_u)
keys_z = jnp.asarray(rows_z)
iota = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32), (F, B))

# --- 1. sort keys + slot-id payload, per field (batched axis 1) ---------
sortf = jax.jit(lambda k: jax.lax.sort_key_val(k, iota, dimension=1))
t = timeit(lambda: sortf(keys_u)[0])
report("sort [F,B] int32 keys + slot payload", t)

# --- 2. permute-gather the gradient slab into sorted order --------------
perm_u = jax.jit(lambda k: jax.lax.sort_key_val(k, iota, dimension=1)[1]
                 )(keys_u)
permf = jax.jit(lambda g, p: jnp.take_along_axis(
    g, p[:, :, None], axis=1))
t_perm = timeit(lambda: permf(grad, perm_u))
report("permute [F,B,256] bf16 grad by sorted order", t_perm)

# --- 3. segment-sum of sorted runs via cumsum + boundary gather ---------
sorted_keys = jax.jit(lambda k: jax.lax.sort_key_val(k, iota, dimension=1)[0]
                      )(keys_u)


@jax.jit
def segsum(gs, ks):
    cs = jnp.cumsum(gs.astype(jnp.float32), axis=1)          # [F, B, 256]
    last = jnp.concatenate([ks[:, 1:] != ks[:, :-1],
                            jnp.ones((F, 1), bool)], axis=1)  # run ends
    # per-field compaction of run-end positions costs another B index ops;
    # for the probe, charge only the cumsum + mask (lower bound).
    return cs * last[:, :, None]


gsorted = permf(grad, perm_u)
t_seg = timeit(lambda: segsum(gsorted, sorted_keys))
report("cumsum segment-sum [F,B,256] f32 (lower bound)", t_seg)

# --- 4. reference: XLA scatter-add of ALL slots vs UNIQUE rows ----------
g32 = grad.astype(jnp.float32)


@jax.jit
def scat_all(g, k):
    out = jnp.zeros((F, MRF, HP * 128), jnp.float32)
    return jax.vmap(lambda o, gg, kk: o.at[kk].add(gg))(out, g, k)


t_scat = timeit(lambda: scat_all(g32, keys_u))
report("XLA scatter-add ALL slots (baseline analog)", t_scat)

# RMW-only production cost: cite the measured kernel share
print("\nmeasured production RMW share: ~22 ms for 1.31M slots = "
      "~17 ns/slot (docs/PERFORMANCE.md)", flush=True)

tot = t + t_perm + t_seg
print(f"\npre-agg pipeline total (sort + permute + segsum lower bound): "
      f"{tot*1e3:.1f} ms = {tot/N*1e9:.1f} ns/slot")
print(f"RMW saving at uniform dup ({N/uniq_u:.2f}x): "
      f"{17*(1-uniq_u/N):.1f} ns/slot -> net "
      f"{tot/N*1e9 - 17*(1-uniq_u/N):+.1f} ns/slot")
print(f"RMW saving ceiling (infinite dup): 17.0 ns/slot -> net "
      f"{tot/N*1e9 - 17.0:+.1f} ns/slot")
