# Ran in round 3 on one TPU v5e chip reached over the relay link that PR 21
# retired; its readings have not been re-run on this chip (PERF.md holds those).
"""Round-3 probes: where do the flagship step's 71.7 ms actually go, and
can a Pallas per-row DMA pipeline beat XLA's ~26 ns/row gather/scatter?

Flagship shapes (chip_smoke.py FULL): B=32768, L=40, F=40, K=4, dims=2^24
=> T [Mr=262144, W=168] bf16, rows [B*L=1310720] int32.

Run:  python experiments/probe_idx.py [probe ...]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

B, L, F, K = 32768, 40, 40, 4
Mr, W = 262144, F * K + 8
N = B * L

rng = np.random.default_rng(0)
rows_np = rng.integers(0, Mr, (N,)).astype(np.int32)


def sync(x):
    return float(np.asarray(jnp.asarray(x).astype(jnp.float32).sum(), np.float64))


def timeit(fn, *args, iters=20, repeats=3):
    """fn(*args) -> array; returns best seconds/iter with true value sync."""
    out = fn(*args)
    sync(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        sync(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def report(name, secs, nrows=None):
    extra = ""
    if nrows:
        extra = f"  {nrows/secs/1e6:8.1f} Mrows/s  {secs/nrows*1e9:6.2f} ns/row"
    print(f"{name:42s} {secs*1e3:9.3f} ms{extra}", flush=True)


# ---------------------------------------------------------------- XLA probes

def probe_xla():
    T = jnp.asarray(rng.standard_normal((Mr, W)), jnp.bfloat16)
    rows = jnp.asarray(rows_np)
    g = jnp.asarray(rng.standard_normal((N, W)).astype(np.float32))

    gather_sum = jax.jit(lambda T, r: T[r].astype(jnp.float32).sum())
    report("xla gather+fusedsum", timeit(gather_sum, T, rows), N)

    gather_mat = jax.jit(lambda T, r: T[r])
    report("xla gather materialize bf16", timeit(gather_mat, T, rows), N)

    @jax.jit
    def scat(G, r, g):
        return G.at[r].add(g)
    G = jnp.zeros((Mr, W), jnp.float32)
    report("xla scatter-add f32", timeit(lambda: scat(G, rows, g)), N)

    # scatter of bf16 payload
    @jax.jit
    def scat16(G, r, g):
        return G.at[r].add(g)
    G16 = jnp.zeros((Mr, W), jnp.bfloat16)
    report("xla scatter-add bf16", timeit(lambda: scat16(G16, rows, g.astype(jnp.bfloat16))), N)

    # unique-ish: sorted rows
    rs = jnp.asarray(np.sort(rows_np))
    report("xla gather sorted rows", timeit(gather_sum, T, rs), N)

    # half the rows (index-count scaling check)
    half = jnp.asarray(rows_np[: N // 2])
    report("xla gather half rows", timeit(gather_sum, T, half), N // 2)


# ------------------------------------------------------- step decomposition

def probe_step():
    from hivemall_tpu.ops.losses import get_loss
    from hivemall_tpu.ops import fm as fmops

    T = jnp.asarray(rng.standard_normal((Mr, W)), jnp.bfloat16)
    w0 = jnp.zeros((), jnp.float32)
    rows2 = jnp.asarray(rows_np.reshape(B, L))
    val = jnp.ones((B, L), jnp.float32)
    lab = jnp.asarray((rng.integers(0, 2, B) * 2 - 1).astype(np.float32))
    mask = jnp.ones((B,), jnp.float32)
    loss = get_loss("logloss")

    @jax.jit
    def fwd_only(T, rows2):
        slab = T[rows2.reshape(-1)].reshape(B, L, W)
        phi = fmops._fused_phi_fieldmajor(w0, slab, val, F, K)
        return (loss.loss(phi, lab) * mask).sum()
    report("step: gather+fwd", timeit(fwd_only, T, rows2))

    @jax.jit
    def fwd_bwd(T, rows2):
        slab = T[rows2.reshape(-1)].reshape(B, L, W)

        def f(s):
            phi = fmops._fused_phi_fieldmajor(w0, s, val, F, K)
            return (loss.loss(phi, lab) * mask).sum()
        l, gs = jax.value_and_grad(f)(slab)
        return l + gs.astype(jnp.float32).sum()
    report("step: gather+fwd+bwd(slab)", timeit(fwd_bwd, T, rows2))

    @jax.jit
    def fwd_bwd_scat(T, rows2):
        slab = T[rows2.reshape(-1)].reshape(B, L, W)

        def f(s):
            phi = fmops._fused_phi_fieldmajor(w0, s, val, F, K)
            return (loss.loss(phi, lab) * mask).sum()
        l, gs = jax.value_and_grad(f)(slab)
        G = jnp.zeros((Mr, W), jnp.float32).at[rows2.reshape(-1)].add(
            gs.reshape(-1, W).astype(jnp.float32))
        return l + G.sum()
    report("step: +scatter G", timeit(fwd_bwd_scat, T, rows2))

    # the true full-table grad via autodiff on T (what the real step does)
    @jax.jit
    def full_grad(T, rows2):
        def f(Tf):
            slab = Tf[rows2.reshape(-1)].reshape(B, L, W)
            phi = fmops._fused_phi_fieldmajor(w0, slab, val, F, K)
            return (loss.loss(phi, lab) * mask).sum()
        l, gT = jax.value_and_grad(f)(T.astype(jnp.float32))
        return l + gT.sum()
    report("step: autodiff-through-table", timeit(full_grad, T, rows2))

    # dense adagrad pass alone
    @jax.jit
    def dense_opt(T, G, S):
        S2 = S + G * G
        Tn = T.astype(jnp.float32) - 0.1 * G * jax.lax.rsqrt(S2 + 1e-6)
        return Tn.astype(jnp.bfloat16), S2
    G = jnp.asarray(rng.standard_normal((Mr, W)).astype(np.float32))
    S = jnp.ones((Mr, W), jnp.float32)

    def run_opt():
        Tn, S2 = dense_opt(T, G, S)
        return Tn.astype(jnp.float32).sum() + S2.sum()
    report("step: dense adagrad pass", timeit(run_opt))


# -------------------------------------------------------------- pallas DMA

def make_pallas_gather(tile: int, nq: int, width: int, unroll: int = 1,
                       sequential: bool = False):
    """Gather rows of a [Mr, width] bf16 HBM table into VMEM slabs tile rows
    at a time with an nq-deep DMA pipeline. HBM slices must be 8-row
    aligned, so each slot copies the aligned [8, width] block containing its
    row (8x bytes; bandwidth floor ~4 ms -- issue rate is the question).
    sequential=True copies block i instead (randomness control)."""
    n_tiles = N // tile

    def kernel(rows_ref, T_ref, out_ref, slab, sems):
        t = pl.program_id(0)

        def copy(i, slot):
            if sequential:
                r8 = ((t * tile + i) * 8) % Mr
            else:
                r8 = (rows_ref[i] // 8) * 8
            return pltpu.make_async_copy(
                T_ref.at[pl.ds(r8, 8), :], slab.at[i], sems.at[slot])

        for q in range(nq):
            copy(q, q).start()

        def body(i, _):
            for u in range(unroll):
                j = i * unroll + u
                copy(j, (j % nq)).wait()

                @pl.when(j + nq < tile)
                def _():
                    copy(j + nq, (j % nq)).start()
            return 0

        jax.lax.fori_loop(0, tile // unroll, body, 0)
        s = slab[...].astype(jnp.float32).sum(axis=(0, 1),
                                              keepdims=True)[0, :, :128]
        out_ref[...] = jnp.broadcast_to(s, (8, 128))

    grid_spec = pl.GridSpec(
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile,), lambda t: (t,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((8, 128), lambda t: (t, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((tile, 8, width), jnp.bfloat16),
            pltpu.SemaphoreType.DMA((nq,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles * 8, 128), jnp.float32),
    )


def probe_pallas():
    for width in (256,):
        T = jnp.asarray(rng.standard_normal((Mr, width)), jnp.bfloat16)
        rows = jnp.asarray(rows_np)
        for tile, nq, seq in ((512, 4, False), (512, 8, False),
                              (512, 16, False), (2048, 16, False),
                              (512, 8, True)):
            try:
                fn = jax.jit(make_pallas_gather(tile, nq, width,
                                                sequential=seq))
                secs = timeit(fn, rows, T, iters=5)
                report(f"pallas g8 tile={tile} nq={nq} seq={int(seq)}",
                       secs, N)
            except Exception as e:  # noqa
                print(f"pallas tile={tile} nq={nq}: FAIL "
                      f"{type(e).__name__}: {e}", flush=True)


def probe_pallas_unroll():
    T = jnp.asarray(rng.standard_normal((Mr, W)), jnp.bfloat16)
    rows = jnp.asarray(rows_np)
    for tile, nq, un in ((2048, 8, 4), (2048, 16, 4), (2048, 16, 8)):
        try:
            fn = jax.jit(make_pallas_gather(tile, nq, W, un))
            secs = timeit(fn, rows, T, iters=5)
            report(f"pallas gather t={tile} nq={nq} unroll={un}", secs, N)
        except Exception as e:  # noqa
            print(f"pallas unroll {tile}/{nq}/{un}: FAIL {type(e).__name__}: {e}",
                  flush=True)


PROBES = {"xla": probe_xla, "step": probe_step, "pallas": probe_pallas,
          "unroll": probe_pallas_unroll}

if __name__ == "__main__":
    names = sys.argv[1:] or list(PROBES)
    print(jax.devices(), flush=True)
    for n in names:
        print(f"--- {n}", flush=True)
        PROBES[n]()
