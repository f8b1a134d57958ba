# Ran in round 3 on one TPU v5e chip reached over the relay link that PR 21
# retired; its readings have not been re-run on this chip (PERF.md holds those).
"""Does XLA gather/scatter per-row cost depend on the table size?

If a VMEM-resident table gathers/scatters faster per row, the FFM table can
be partitioned by field (40 partitions of Mr/F rows) and each partition
processed with a small-table op.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

W = 168
N = 1310720  # total row-ops, matched to the flagship step

rng = np.random.default_rng(0)


def sync(x):
    return float(np.asarray(jnp.asarray(x).astype(jnp.float32).sum(), np.float64))


def timeit(fn, iters=20, repeats=3):
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


def report(name, secs, nrows):
    print(f"{name:44s} {secs*1e3:9.3f} ms  {nrows/secs/1e6:8.1f} Mrows/s  "
          f"{secs/nrows*1e9:6.2f} ns/row", flush=True)


def main():
    for mrows in (1 << 12, 1 << 14, 1 << 16, 1 << 18, 1 << 21):
        T = jnp.asarray(rng.standard_normal((mrows, W)), jnp.bfloat16)
        rows = jnp.asarray(rng.integers(0, mrows, (N,)).astype(np.int32))
        g = jnp.asarray(rng.standard_normal((N, W)).astype(np.float32))

        gather_sum = jax.jit(lambda T, r: T[r].astype(jnp.float32).sum())
        report(f"gather  Mr=2^{int(np.log2(mrows))}",
               timeit(lambda: gather_sum(T, rows)), N)

        scat = jax.jit(lambda G, r, g: G.at[r].add(g))
        G = jnp.zeros((mrows, W), jnp.float32)
        report(f"scatter Mr=2^{int(np.log2(mrows))}",
               timeit(lambda: scat(G, rows, g)), N)

    # batched variant: L separate scatters of B rows each into one table
    # (the field-partitioned shape: one scatter per field partition)
    mrows, B, L = 1 << 13, 32768, 40
    T = jnp.asarray(rng.standard_normal((L, mrows, W)), jnp.bfloat16)
    rows2 = jnp.asarray(rng.integers(0, mrows, (L, B)).astype(np.int32))
    g2 = jnp.asarray(rng.standard_normal((L, B, W)).astype(np.float32))

    @jax.jit
    def scat_part(T, rows2, g2):
        G = jnp.zeros(T.shape, jnp.float32)
        # one scatter per partition, vmapped over the leading axis
        return jax.vmap(lambda Gp, r, g: Gp.at[r].add(g))(G, rows2, g2)
    report("scatter 40x(32k into 2^13) vmapped",
           timeit(lambda: scat_part(T, rows2, g2)), N)

    @jax.jit
    def gath_part(T, rows2):
        return jax.vmap(lambda Tp, r: Tp[r])(T, rows2).astype(
            jnp.float32).sum()
    report("gather  40x(32k from 2^13) vmapped",
           timeit(lambda: gath_part(T, rows2)), N)

    # one-hot matmul accumulation into a 2^13 partition (MXU scatter analog)
    @jax.jit
    def scat_onehot(rows2, g2):
        iota = jnp.arange(mrows, dtype=jnp.int32)
        def one(r, g):
            E = (r[:, None] == iota[None, :]).astype(jnp.bfloat16)
            return jax.lax.dot_general(
                E, g.astype(jnp.bfloat16),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return jax.vmap(one)(rows2, g2).sum()
    report("scatter 40x onehot-matmul 2^13",
           timeit(lambda: scat_onehot(rows2, g2), iters=5), N)


if __name__ == "__main__":
    print(jax.devices(), flush=True)
    main()
