"""Phase breakdown of the round-5 train_fm minibatch step (411k ex/s =
79.7 ms at B=32k, L=32, K=8, dims=2^24): where do the ~34 ms above the
gather+scatter floor go?

The numbers quoted here and in the comments below are round-5 probe readings
(uniform ids, a 2^24-entry bf16 table, each phase alone in its own jit). The
benchmark's cell reads the whole step at Criteo shape from a profiler trace
and disagrees with the "structural floor" drawn from them: PERF.md section 5.
The fwd/bwd phase runs today's helper (ops.fm._fm_packed_phi, PR 25), not
the masked-sum unpack these readings were taken with."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

B, L, K = 32768, 32, 8
dims = 1 << 24
P, Wf = 8, 16
Np = dims // P
rng = np.random.default_rng(0)
idx = jnp.asarray(rng.integers(1, dims, (B, L)).astype(np.int32))
T = jnp.asarray(rng.standard_normal((Np, 128)) * 0.01, jnp.bfloat16)
S = jnp.zeros((Np, 128), jnp.float32)
lab = jnp.asarray((rng.integers(0, 2, B) * 2 - 1).astype(np.float32))


def sync(x):
    return float(np.asarray(jnp.asarray(x).astype(jnp.float32).sum()))


def timeit(fn, iters=10):
    sync(fn())
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        sync(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


rows = idx // P


@jax.jit
def gather(T, idx):
    return T[idx // P].astype(jnp.float32).sum()


print(f"gather:         {timeit(lambda: gather(T, idx))*1e3:7.2f} ms",
      flush=True)

g128 = jnp.asarray(rng.standard_normal((B, L, 128)) * 1e-3, jnp.float32)


@jax.jit
def scat(g, rows):
    return jnp.zeros((Np, 128), jnp.float32).at[rows.reshape(-1)].add(
        g.reshape(-1, 128)).sum()


print(f"scatter-add:    {timeit(lambda: scat(g128, rows))*1e3:7.2f} ms",
      flush=True)


@jax.jit
def dense(T, S, G):
    gg = S + G * G
    Tn = T.astype(jnp.float32) - 0.1 * G / (jnp.sqrt(gg) + 1e-6)
    return Tn.astype(jnp.bfloat16).sum()


G = jnp.zeros((Np, 128), jnp.float32)
print(f"dense adagrad:  {timeit(lambda: dense(T, S, G))*1e3:7.2f} ms",
      flush=True)

from hivemall_tpu.ops.fm import _fm_packed_phi  # noqa: E402
from hivemall_tpu.ops.losses import get_loss  # noqa: E402

loss = get_loss("logloss")


@jax.jit
def fwdbwd(T, idx, lab):
    rows, sub = idx.T // P, idx.T % P               # slot-major [L, B]

    def bl(s128):
        phi, _ = _fm_packed_phi(0.0, s128, sub, jnp.ones((L, B)), K, Wf, P)
        return (loss.loss(phi, lab)).sum()

    return jax.grad(bl)(T[rows]).astype(jnp.float32).sum()


print(f"gather+fwd/bwd: {timeit(lambda: fwdbwd(T, idx, lab))*1e3:7.2f} ms",
      flush=True)

gslab = jnp.asarray(rng.standard_normal((B, L, Wf)), jnp.float32)


@jax.jit
def onehot_expand(gslab, sub):
    oh = jax.nn.one_hot(sub, P, dtype=jnp.float32)
    return (oh[..., None] * gslab[..., None, :]).reshape(B, L, P * Wf).sum()


print(f"one-hot expand: "
      f"{timeit(lambda: onehot_expand(gslab, idx % P))*1e3:7.2f} ms",
      flush=True)


# --- round-5 follow-up: is there a cheap win left in the scatter+dense
# tail? Measured (same shapes, one jit per variant, value-synced):
#   zeros+scatter+dense fused in ONE jit : 28.28 ms   <- the step's actual
#       tail (better than the 23.6 + 9.8 sum of the isolated phases above:
#       XLA fuses the zero-init and the elementwise update around the
#       scatter when they share a jit)
#   donated pre-zeroed G (re-zeroed by the dense pass, no memset): 32.21 ms
#       — WORSE: donation pins the buffer and defeats the fusion
#   f32 table (no bf16<->f32 astype copies in the dense pass): 30.51 ms
#       — WORSE: the wider gather/update traffic costs more than the
#       conversions saved
# Conclusion: the minibatch step is at its structural floor —
# gather+fwd/bwd ~28 ms + fused tail ~28 ms = ~56-61 ms measured e2e
# (535k ex/s clean). The remaining alternatives all price out at net <= 0
# by the cost model (docs/PERFORMANCE.md "table-row operations are the
# scarce resource"):
#   - sort + segment-sum pre-aggregation: 1.05M slots into 2M rows is
#     mostly UNIQUE (uniform hashing, <=30% collisions) — nothing to
#     pre-aggregate, and the sorted-order permutation is itself a 1.05M
#     row gather (~18 ms).
#   - sorted-range Pallas VMEM accumulate + fused AdaGrad (the FFM parts
#     treatment): FM has no field structure, so slots hit the whole 2M-row
#     table; bucketing needs a device sort (~8-10 ms) AND the kernel's
#     random g128 reads pay the same ~17 ns/row the XLA scatter pays —
#     net ~0. The FFM kernel wins only because canonical field-major
#     batches arrive PRE-GROUPED by partition.
