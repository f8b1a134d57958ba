"""Which programs a change left alone: sha256 (16 hex digits) and length
of the LOWERED text (StableHLO, before any compiler) of the steps and
megasteps the benchmark's cells run, at the cells' shapes:
`fm_criteo.stream` (packed FM, -dims 2^26 -factors 5), `ffm_criteo_joint
.stream` (the joint fused step's three variants, each with and without the
distinct-row tail, -dims 2^28 -halffloat), `logreg_criteo.stream`
(AdaGrad-RDA, -dims 2^28), `skipgram_enwiki.stream_text` (-dim 300 on
2,519,370 words) and the four-chip cell `ffm_criteo_joint_tp4.stream_mesh`'s
megastep (-dims 2^30 -halffloat -mesh dp=1,tp=4, lowered over four host CPU
devices; the text does not depend on which devices), and
`mf_hugewiki.stream_ratings`'s megastep (-factors 100 on 6,260,326 users
and 39,780 items). Fifteen lines; nothing is compiled or run, so it says
nothing about time.

A perf PR that must not move a cell compares two trees (PRs 34, 36, 38):

    git archive <parent> | tar -x -C /root/scratch/parent
    python experiments/lowered_sums.py /root/scratch/parent > a.txt
    python experiments/lowered_sums.py . > b.txt && diff a.txt b.txt

The tree named on the command line is the one imported (default: this
file's own).
"""
from __future__ import annotations

import hashlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from hivemall_tpu.models import mf
from hivemall_tpu.models import word2vec as w2v
from hivemall_tpu.ops import fm
from hivemall_tpu.ops.linear import make_linear_step
from hivemall_tpu.ops.losses import get_loss
from hivemall_tpu.ops.optimizers import make_optimizer, make_optimizer_cached
from hivemall_tpu.ops.scan import make_megastep
from hivemall_tpu.parallel.mesh import make_mesh

S = jax.ShapeDtypeStruct
KS, B, L = 2, 32768, 39


def show(name, lowered):
    text = lowered.as_text()
    print(name, hashlib.sha256(text.encode()).hexdigest()[:16], len(text))


def main():
    loss = get_loss("logloss")
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1, reg="no")
    f0 = S((), jnp.float32)
    idx, idxs = S((B, L), jnp.int32), S((KS, B, L), jnp.int32)
    row, rows = S((B,), jnp.float32), S((KS, B), jnp.float32)
    ts = S((KS,), jnp.int32)

    K = 5
    Wf, Pk = fm.fm_pack_geometry(K)
    T = S(((1 << 26) // Pk, Pk * Wf), jnp.float32)
    st = ({"T": T, "w0": f0}, {"T": {"gg": T}, "w0": {"gg": f0}})
    step = fm.make_fm_step_minibatch(loss, opt, (0.01,) * 3, K)
    show("fm_step", step.lower(*st, f0, idx, None, row, row))
    show("fm_mega", make_megastep(step.core, none_val=True).lower(
        *st, f0, ts, idxs, None, rows, None, None))

    F, Kf, Mr = 39, 4, 1 << 22
    W = F * Kf + 8
    st = ({"T": S((Mr, W), jnp.bfloat16), "w0": f0},
          {"T": {"gg": S((Mr, W), jnp.float32)}, "w0": {"gg": f0}})
    vals, fields = S((KS, B, L), jnp.float32), idxs
    for name, kw, val, field in (
            ("unit", dict(fieldmajor=True, unit_val=True), None, None),
            ("valued", dict(fieldmajor=True), vals, None),
            ("pairs", {}, vals, fields)):
        for tail in (True, False):
            step = fm.make_ffm_step_fused(loss, opt, (0.01,) * 3, F, Kf,
                                          distinct_tail=tail, **kw)
            show(f"ffm_{name}_mega_tail{int(tail)}",
                 make_megastep(step.core).lower(
                     *st, f0, ts, idxs, val, rows, field, None))
        one = [idx] + [S((B, L), jnp.float32)] * (val is not None) \
            + [row, row] + [idx] * (field is not None)
        show(f"ffm_{name}_step", step.lower(*st, f0, *one))

    rda = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         power_t=0.1, reg="rda", lam=1e-6)
    w = S((1 << 28,), jnp.float32)
    step = make_linear_step(loss, rda)
    # the distinct-slot step rebuilds `w` after the scan (`finish`)
    after = {"finish": step.finish} if hasattr(step, "finish") else {}
    show("linear_mega", make_megastep(step.core, none_val=True, **after
                                      ).lower(
        w, {"u": w, "gg": w}, f0, ts, idxs, None, rows, None, None))

    V, Bp, lanes = 2_519_370, 16384, w2v.row_lanes(300)
    table = S((V, lanes), jnp.float32)
    show("skipgram_mega", make_megastep(
        w2v._step_cached(5, 27, 0.025, 1.7e9 * 5, False).core,
        none_val=True).lower(
            {"in": table, "out": table}, {"words": S((2,), jnp.int32)}, f0,
            ts, S((KS, Bp, 5), jnp.int32), None, S((KS, Bp), jnp.float32),
            None, S((1 << 27,), jnp.int32)))

    # the four-chip cell: -dims 2^30 is 2^24 rows dealt over tp=4
    mesh = make_mesh(dp=1, tp=4)
    blocks, everywhere = NamedSharding(mesh, P("tp", None)), \
        NamedSharding(mesh, P())
    Mr, W = 1 << 24, F * Kf + 8
    step = fm.make_ffm_step_fused(loss, opt, (0.01,) * 3, F, Kf,
                                  fieldmajor=True, unit_val=True, mesh=mesh)
    w0 = S((), jnp.float32, sharding=everywhere)
    show("ffm_unit_mega_tp4", make_megastep(step.core).lower(
        {"T": S((Mr, W), jnp.bfloat16, sharding=blocks), "w0": w0},
        {"T": {"gg": S((Mr, W), jnp.float32, sharding=blocks)},
         "w0": {"gg": w0}},
        w0, S((KS,), jnp.int32, sharding=everywhere),
        S((KS, B, L), jnp.int32, sharding=everywhere), None,
        S((KS, B), jnp.float32, sharding=everywhere), None, None))

    U, I, Bm = 6_260_326, 39_780, 131072
    opt = make_optimizer_cached("adagrad", "fixed", 0.01, 10_000, 0.1)
    Ut, It = S((U, mf.row_lanes(100)), jnp.float32), \
        S((I, mf.row_lanes(100)), jnp.float32)
    show("mf_mega", make_megastep(
        mf._step_cached(100, 3.0, 0.01, True, opt, True).core,
        none_val=True).lower(
            {"U": Ut, "I": It}, {"U": {"gg": Ut}, "I": {"gg": It}}, f0, ts,
            S((KS, Bm, 2), jnp.int32), None, S((KS, Bm), jnp.float32), None,
            None))


if __name__ == "__main__":
    main()
