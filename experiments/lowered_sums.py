"""Which one-chip programs a change left alone: sha256 (16 hex digits) and
length of the LOWERED text (StableHLO, before any compiler) of the steps
and megasteps the benchmark's one-chip cells run, at the cells' shapes:
`fm_criteo.stream` (packed FM, -dims 2^26 -factors 5), `ffm_criteo_joint
.stream` (the joint fused step's three variants, each with and without the
distinct-row tail, -dims 2^28 -halffloat) and `logreg_criteo.stream`
(AdaGrad-RDA, -dims 2^28). Twelve lines; nothing is compiled or run, so it
says nothing about time.

A perf PR that must not move a cell compares two trees (PRs 34 and 36):

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
sys.path.insert(0, os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from hivemall_tpu.ops import fm
from hivemall_tpu.ops.linear import make_linear_step
from hivemall_tpu.ops.losses import get_loss
from hivemall_tpu.ops.optimizers import make_optimizer
from hivemall_tpu.ops.scan import make_megastep

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
    show("linear_mega", make_megastep(
        make_linear_step(loss, rda).core, none_val=True).lower(
            w, {"u": w, "gg": w}, f0, ts, idxs, None, rows, None, None))


if __name__ == "__main__":
    main()
