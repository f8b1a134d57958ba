"""Optimizers — dense-table functional updates (the Optimizer family).

Reference: hivemall.optimizer.{Optimizer,DenseOptimizerFactory,Regularization}
(SURVEY.md §3.2): SGD, Momentum/Nesterov, AdaGrad, AdaDelta, Adam, AdaGrad-RDA,
FTRL, with none/L1/L2/ElasticNet regularization composed into the gradient
(RDA/FTRL fold L1 in closed form instead).

TPU shape: the reference updates one hash-table cell per feature per row; here
the model is a dense ``[N]`` (or ``[N, K]``) table in HBM and one jitted call
updates the whole table elementwise after a scatter-add of the minibatch
gradient — O(N) HBM traffic per step, fully fused by XLA, no per-row scalar
loops. Per-coordinate adaptive state (gg, m/v, z/n) lives in co-shaped arrays,
the analog of WeightValueParamsF1/F2 cells.

API: ``opt.init(shape) -> state``; ``opt.update(w, g, state, t) -> (w, state)``
with t the 0-based global step; ``opt.finalize(w, state) -> w`` materializes
lazy weights (RDA/FTRL). All pieces are pytrees, safe under jit/shard_map —
and under ``lax.scan``: every update is a pure function of (w, g, state, t)
with no step-count side state of its own (t arrives as an argument), which
is what lets the fused-dispatch path (ops.scan) thread K optimizer steps
through one donated scan carry without touching this module.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Tuple

import jax.numpy as jnp

from .schedules import make_eta

__all__ = ["Optimizer", "OPTIMIZERS", "make_optimizer"]

EPS = 1e-6


@dataclass(frozen=True)
class Optimizer:
    name: str
    init: Callable[..., Dict[str, Any]]
    update: Callable[..., Tuple[Any, Dict[str, Any]]]
    finalize: Callable[..., Any] = None  # type: ignore[assignment]
    # Sparse/embedding-style update over a dense table: touch only the rows a
    # minibatch gathered, O(batch) instead of O(table) HBM traffic. This is
    # the TPU analog of the reference's per-cell hash-table updates (it only
    # ever touched features present in the row). Signature:
    #   sparse_update(w_table, g_slab, state, flat_idx, t) -> (w_table, state)
    # with flat_idx [M] row ids into axis 0 of w_table and g_slab [M, ...]
    # the f32 gradients at those rows. Duplicate ids accumulate by scatter-add
    # (grad/accumulator sums match whole-batch accumulation; the weight step
    # then uses the batch-final accumulators). None = no sparse form
    # (momentum/adam/adadelta decay untouched state; use the dense update).
    sparse_update: Callable[..., Tuple[Any, Dict[str, Any]]] = None  # type: ignore[assignment]
    # update(w, 0, s, t) == (w, s) for every entry: an entry whose gradient
    # is zero need not be visited, so a minibatch step may update the rows
    # its batch touched and no others (ops/fm.py rows_update). SGD and
    # AdaGrad without a regularizer; RDA and FTRL rebuild w from their
    # sums at every t, the others decay their state.
    zero_grad_noop: bool = False

    def __post_init__(self):
        if self.finalize is None:
            object.__setattr__(self, "finalize", lambda w, state: w)


def _regularize(g, w, reg: str, lam: float, l1_ratio: float):
    """Compose the regularizer gradient (reference: Regularization.regularize)."""
    if reg in ("no", "none", "rda", None):
        return g
    if reg == "l1":
        return g + lam * jnp.sign(w)
    if reg == "l2":
        return g + lam * w
    if reg == "elasticnet":
        return g + lam * (l1_ratio * jnp.sign(w) + (1.0 - l1_ratio) * w)
    raise ValueError(f"unknown regularization {reg!r}")


def make_optimizer(name: str = "adagrad", *, eta_scheme: str = "fixed",
                   eta0: float = 0.1, total_steps: int = 10_000,
                   power_t: float = 0.1, reg: str = "rda",
                   lam: float = 1e-6, l1_ratio: float = 0.5,
                   rho: float = 0.95, beta1: float = 0.9, beta2: float = 0.999,
                   adam_eps: float = 1e-8, momentum: float = 0.9,
                   ftrl_alpha: float = 0.5, ftrl_beta: float = 1.0,
                   ftrl_l1: float = 1e-6, ftrl_l2: float = 1e-6,
                   ) -> Optimizer:
    """Build an Optimizer from option values (the -opt/-reg/-eta* grammar)."""
    eta = make_eta(eta_scheme, eta0, total_steps, power_t)
    key = str(name).lower().replace("-", "").replace("_", "")
    # '-reg rda' upgrades plain adagrad to the RDA variant, as the reference's
    # optimizer factory does.
    if key == "adagrad" and reg == "rda":
        key = "adagradrda"

    def regz(g, w):
        return _regularize(g, w, reg, lam, l1_ratio)
    unregularized = reg in ("no", "none", "rda", None)

    if key == "sgd":
        def sgd_sparse(w, g, s, ix, t):
            ge = regz(g, w[ix].astype(jnp.float32))
            return w.at[ix].add((-eta(t) * ge).astype(w.dtype)), s

        return Optimizer(
            "sgd",
            init=lambda shape, dtype=jnp.float32: {},
            update=lambda w, g, s, t: (w - eta(t) * regz(g, w), s),
            sparse_update=sgd_sparse, zero_grad_noop=unregularized)

    if key in ("momentum", "nesterov"):
        nesterov = key == "nesterov"

        def m_init(shape, dtype=jnp.float32):
            return {"v": jnp.zeros(shape, dtype)}

        def m_update(w, g, s, t):
            ge = regz(g, w)
            v = momentum * s["v"] - eta(t) * ge
            step = momentum * v - eta(t) * ge if nesterov else v
            return w + step, {"v": v}

        return Optimizer(key, m_init, m_update)

    if key == "adagrad":
        def ag_init(shape, dtype=jnp.float32):
            return {"gg": jnp.zeros(shape, jnp.float32)}

        def ag_update(w, g, s, t):
            ge = regz(g, w)
            gg = s["gg"] + ge * ge
            return w - eta(t) * ge / (jnp.sqrt(gg) + EPS), {"gg": gg}

        def ag_sparse(w, g, s, ix, t):
            ge = regz(g, w[ix].astype(jnp.float32))
            gg = s["gg"].at[ix].add(ge * ge)
            step = -eta(t) * ge / (jnp.sqrt(gg[ix]) + EPS)
            return w.at[ix].add(step.astype(w.dtype)), {"gg": gg}

        return Optimizer("adagrad", ag_init, ag_update,
                         sparse_update=ag_sparse,
                         zero_grad_noop=unregularized)

    if key == "adadelta":
        def ad_init(shape, dtype=jnp.float32):
            return {"gg": jnp.zeros(shape, jnp.float32),
                    "dx": jnp.zeros(shape, jnp.float32)}

        def ad_update(w, g, s, t):
            ge = regz(g, w)
            gg = rho * s["gg"] + (1 - rho) * ge * ge
            step = jnp.sqrt((s["dx"] + EPS) / (gg + EPS)) * ge
            dx = rho * s["dx"] + (1 - rho) * step * step
            return w - step, {"gg": gg, "dx": dx}

        return Optimizer("adadelta", ad_init, ad_update)

    if key == "adam":
        def am_init(shape, dtype=jnp.float32):
            return {"m": jnp.zeros(shape, jnp.float32),
                    "v": jnp.zeros(shape, jnp.float32)}

        def am_update(w, g, s, t):
            ge = regz(g, w)
            m = beta1 * s["m"] + (1 - beta1) * ge
            v = beta2 * s["v"] + (1 - beta2) * ge * ge
            tt = t + 1.0
            mhat = m / (1 - beta1 ** tt)
            vhat = v / (1 - beta2 ** tt)
            return (w - eta(t) * mhat / (jnp.sqrt(vhat) + adam_eps),
                    {"m": m, "v": v})

        return Optimizer("adam", am_init, am_update)

    if key in ("adagradrda", "rda"):
        # Xiao's l1-RDA with AdaGrad scaling (reference: AdaGradRDAUDTF /
        # Optimizer.RDA): weights are re-materialized from the running
        # gradient sum each step; lam is the l1 truncation threshold.
        def rda_init(shape, dtype=jnp.float32):
            return {"u": jnp.zeros(shape, jnp.float32),
                    "gg": jnp.zeros(shape, jnp.float32)}

        def rda_update(w, g, s, t):
            u = s["u"] + g
            gg = s["gg"] + g * g
            tt = t + 1.0
            thresh = jnp.maximum(0.0, jnp.abs(u) / tt - lam)
            w_new = -jnp.sign(u) * eta(t) * tt * thresh / (jnp.sqrt(gg) + EPS)
            return w_new, {"u": u, "gg": gg}

        def rda_sparse(w, g, s, ix, t):
            u = s["u"].at[ix].add(g)
            gg = s["gg"].at[ix].add(g * g)
            ug, gf = u[ix], gg[ix]
            tt = t + 1.0
            thresh = jnp.maximum(0.0, jnp.abs(ug) / tt - lam)
            w_new = -jnp.sign(ug) * eta(t) * tt * thresh / (jnp.sqrt(gf) + EPS)
            return w.at[ix].set(w_new.astype(w.dtype)), {"u": u, "gg": gg}

        return Optimizer("adagrad_rda", rda_init, rda_update,
                         sparse_update=rda_sparse)

    if key == "ftrl":
        # FTRL-Proximal (McMahan et al.) — the update family BASELINE names
        # for the FFM/CTR path; weights live implicitly in (z, n).
        def f_init(shape, dtype=jnp.float32):
            return {"z": jnp.zeros(shape, jnp.float32),
                    "n": jnp.zeros(shape, jnp.float32)}

        def f_materialize(z, n):
            inv = (ftrl_beta + jnp.sqrt(n)) / ftrl_alpha + ftrl_l2
            return jnp.where(jnp.abs(z) > ftrl_l1,
                             -(z - jnp.sign(z) * ftrl_l1) / inv, 0.0)

        def f_update(w, g, s, t):
            n_new = s["n"] + g * g
            sigma = (jnp.sqrt(n_new) - jnp.sqrt(s["n"])) / ftrl_alpha
            z = s["z"] + g - sigma * w
            return f_materialize(z, n_new), {"z": z, "n": n_new}

        def f_sparse(w, g, s, ix, t):
            n_old = s["n"][ix]
            n_new = s["n"].at[ix].add(g * g)
            # sigma is an ENTRY-level quantity (pre-batch -> batch-final n),
            # identical across duplicate occurrences of an id. Scatter-ADDing
            # -sigma*w would subtract it once per duplicate; instead add the
            # grad sums, then .set the batch-final z (duplicates write
            # identical values, so the .set is deterministic).
            sigma = (jnp.sqrt(n_new[ix]) - jnp.sqrt(n_old)) / ftrl_alpha
            z_g = s["z"].at[ix].add(g)
            z_final = z_g[ix] - sigma * w[ix].astype(jnp.float32)
            z = z_g.at[ix].set(z_final)
            w_new = f_materialize(z[ix], n_new[ix])
            return w.at[ix].set(w_new.astype(w.dtype)), {"z": z, "n": n_new}

        return Optimizer("ftrl", f_init, f_update, sparse_update=f_sparse)

    raise ValueError(f"unknown optimizer {name!r}; one of {sorted(OPTIMIZERS)}")


OPTIMIZERS = ("sgd", "momentum", "nesterov", "adagrad", "adadelta", "adam",
              "adagrad_rda", "rda", "ftrl")


from functools import lru_cache as _lru_cache


@_lru_cache(maxsize=256)
def _make_optimizer_cached_impl(opt_name, eta_scheme, eta0, total_steps,
                                power_t, reg, lam, l1_ratio):
    return make_optimizer(opt_name, eta_scheme=eta_scheme, eta0=eta0,
                          total_steps=total_steps, power_t=power_t,
                          reg=reg, lam=lam, l1_ratio=l1_ratio)


def make_optimizer_cached(opt_name, eta_scheme, eta0, total_steps, power_t,
                          reg="no", lam=0.0, l1_ratio=0.5):
    """Config-keyed cache over make_optimizer (round 4): Optimizer objects
    are immutable bundles of pure closures, so identical configs can share
    one — and more importantly, the jitted STEPS built around them become
    shareable across trainer instances (a fresh closure per instance
    re-traces/compiles for every identical config; measured costing
    word2vec 4x and LDA 10x before the same fix). The key is normalized
    HERE — types coerced, defaults applied — so call sites that spell the
    same config differently (int vs float eta0, omitted vs explicit
    reg defaults) converge on one cache entry instead of duplicate
    compiles."""
    return _make_optimizer_cached_impl(
        str(opt_name), str(eta_scheme), float(eta0), int(total_steps),
        float(power_t), str(reg), float(lam), float(l1_ratio))
