"""Online linear classifiers — Perceptron, Passive-Aggressive, and the
covariance family (CW / AROW / SCW), plus AdaGrad-RDA and kernelized PA.

Reference (SURVEY.md §3.3): hivemall.classifier.{PerceptronUDTF,
PassiveAggressiveUDTF (+PA1/PA2), ConfidenceWeightedUDTF,
AROWClassifierUDTF (+arowh), SoftConfideceWeightedUDTF (SCW1/SCW2 — upstream
class name carries that historical spelling), AdaGradRDAUDTF,
KernelExpansionPassiveAggressiveUDTF}.

Batching semantics (SURVEY.md §8 "hard parts"): these algorithms are
per-row sequential in the reference. Here each minibatch computes every row's
closed-form step size against the BATCH-START weights and aggregates the
deltas by scatter-add — with ``-mini_batch 1`` this is exactly the reference's
sequential update (the unit tests pin that equivalence against numpy
oracles); larger batches trade per-row adaptivity for TPU throughput, the
documented delta. Measured guidance (tests/test_covariance_batching.py, a9a
fragment, 1 epoch AUC): ``-mini_batch 16`` matches the sequential oracle
within 0.002; 64 loses 0.03-0.27 AUC in one epoch but recovers with ~4
epochs; 256 is not recommended (CW can diverge). Use 1 for exactness,
16 for throughput at parity, 64 only with extra -iters. Covariance
trainers keep a diagonal sigma table (the WeightValueWithCovar analog) and
emit (feature, weight, covar) rows so argmin-KLD mixing/merging stays
available.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.sparse import SparseBatch, SparseDataset
from ..utils.options import OptionSpec
from .base import LearnerBase, learner_option_spec, weight_table_view
from .linear import _sigmoid

__all__ = ["PerceptronTrainer", "PassiveAggressiveTrainer", "PA1Trainer",
           "PA2Trainer", "ConfidenceWeightedTrainer", "AROWTrainer",
           "AROWhTrainer", "SCW1Trainer", "SCW2Trainer", "AdaGradRDATrainer",
           "KernelizedPATrainer", "PARegressionTrainer", "PA1aRegressionTrainer",
           "PA2RegressionTrainer", "PA2aRegressionTrainer",
           "AROWRegressionTrainer", "AROWeRegressionTrainer",
           "AROWe2RegressionTrainer"]


def _online_spec(name: str) -> OptionSpec:
    s = OptionSpec(name)
    s.add("c", "aggressiveness", type=float, default=1.0,
          help="aggressiveness parameter C (PA1/PA2/SCW)")
    s.add("phi", "confidence", type=float, default=1.0,
          help="confidence parameter phi = Phi^-1(eta) (CW/SCW)")
    s.add("eta", "hyper_eta", type=float, default=0.85,
          help="CW confidence level eta in (0.5, 1]; phi derived when set")
    s.add("r", "regularization", type=float, default=0.1,
          help="AROW regularization r")
    s.add("epsilon", type=float, default=0.1,
          help="epsilon-insensitive band (regression variants)")
    s.add("dims", "feature_dimensions", type=int, default=1 << 24,
          help="model table size")
    s.add("mini_batch", "mini_batch_size", type=int, default=1,
          help="rows per step (1 = exact reference semantics)")
    s.add("batch_mode", default="aggregate",
          help="how a >1-row minibatch updates the model: aggregate "
               "(one closed-form step over the batch — fast, documented "
               "semantic delta) | sequential (lax.scan row-by-row inside "
               "ONE device dispatch — bit-equivalent to -mini_batch 1 "
               "reference semantics at minibatch dispatch rate)")
    s.add("iters", "iterations", type=int, default=1, help="epochs")
    s.flag("int_feature", help="features are integer indices")
    s.add("seed", type=int, default=42,
          help="as the shared grammar's (these tables start at zero and "
               "one: nothing is drawn)")
    s.add("mix", default=None, help="mix cohort spec")
    s.add("mix_threshold", type=int, default=16)
    s.add("mix_session", default=None)
    from .base import add_mix_reliability_options
    add_mix_reliability_options(s)
    s.add("loadmodel", default=None)
    s.flag("dense", "densemodel", help="compat flag (always dense table)")
    s.flag("halffloat", help="bf16 weights")
    s.flag("disable_halffloat", help="compat flag")
    s.add("loss", default=None, help="compat (loss fixed per algorithm)")
    s.add("opt", default=None, help="compat (update rule fixed)")
    s.add("reg", default=None, help="compat")
    s.add("lambda", type=float, default=1e-6, help="RDA l1 (AdaGrad-RDA)")
    s.add("eta0", type=float, default=0.1, help="eta0 (AdaGrad-RDA)")
    s.add("total_steps", type=int, default=10_000)
    s.add("power_t", type=float, default=0.1)
    s.add("l1_ratio", type=float, default=0.5)
    s.flag("cv")
    return s


class _OnlineBase(LearnerBase):
    """Shared scaffolding: dense w (+ optional sigma) tables and a jitted
    closed-form aggregated step built by `_rates`. The weight table is
    ``params``; what the update rule keeps beside it is ``opt_state``
    (``{"sigma": ...}`` for the covariance learners, else empty)."""

    HAS_COVAR = False
    CLASSIFICATION = True
    w = weight_table_view

    @property
    def sigma(self):
        return (self.opt_state or {}).get("sigma")

    @sigma.setter
    def sigma(self, table) -> None:
        self.opt_state = {} if table is None else {"sigma": table}

    @classmethod
    def spec(cls) -> OptionSpec:
        return _online_spec(cls.NAME)

    def _init_state(self) -> None:
        dtype = jnp.bfloat16 if self.opts.halffloat else jnp.float32
        self.params = jnp.zeros(self.dims, dtype)
        self.sigma = jnp.ones(self.dims, jnp.float32) if self.HAS_COVAR \
            else None
        mode = str(getattr(self.opts, "batch_mode", "aggregate"))
        if mode not in ("aggregate", "sequential"):
            raise ValueError(f"-batch_mode must be aggregate|sequential, "
                             f"got {mode!r}")
        self._step = self._shared_step(
            mode, self._make_step_sequential if mode == "sequential"
            else self._make_step)

    # subclass: (margin_y, v, xx, y, params) -> (alpha_like, beta_like)
    #   margin_y = y * (w.x); v = sigma-weighted or plain ||x||^2
    def _rates(self):
        raise NotImplementedError

    def _make_step(self):
        rates = self._rates()
        has_covar = self.HAS_COVAR

        @jax.jit
        def step(w, sigma, idx, val, label, row_mask):
            wf = w.astype(jnp.float32)
            wg = wf[idx]
            m = (wg * val).sum(-1) * label                   # y * margin
            if has_covar:
                sg = sigma[idx]
                v = (sg * val * val).sum(-1)
            else:
                sg = jnp.ones_like(val)
                v = (val * val).sum(-1)
            alpha, beta = rates(m, v)
            alpha = alpha * row_mask
            beta = beta * row_mask
            dw = jnp.zeros_like(wf).at[idx.ravel()].add(
                ((alpha * label)[:, None] * sg * val).ravel())
            w2 = (wf + dw).astype(w.dtype)
            if has_covar:
                ds = jnp.zeros_like(sigma).at[idx.ravel()].add(
                    (beta[:, None] * (sg * val) ** 2).ravel())
                sigma2 = jnp.maximum(sigma - ds, 1e-8)
            else:
                sigma2 = sigma
            # cumulative hinge-ish loss for -cv reporting
            loss_sum = (jnp.maximum(0.0, 1.0 - m) * row_mask).sum()
            return w2, sigma2, loss_sum

        return step

    def _make_step_sequential(self):
        """Reference-exact row-by-row updates at minibatch dispatch rate.

        Round-2 shape (a lax.scan carrying the full [dims] tables through
        every row) measured ~1.8k rows/s: each scan iteration moved
        whole-table state. Round 3 processes SLABS of G=128 rows: gather
        the slab's touched entries once, run the exact per-row loop on the
        small [G, L] in-register slab — cross-row feature sharing inside
        the slab is propagated through an idx-match mask, so every row
        sees exactly the f32 values true row-by-row dispatch would — and
        scatter the final values back once per slab. Bit-equivalent to
        -mini_batch 1 for rows with distinct features (the covariance
        batching tests pin it); a feature repeated WITHIN one row keeps
        add-semantics for w (same as the reference's accumulating update)
        and delta-semantics for sigma. This is the SURVEY §8
        'online-learner semantics under batching' hard part solved
        exactly rather than approximated."""
        rates = self._rates()
        has_covar = self.HAS_COVAR
        G = 128

        @jax.jit
        def step(w, sigma, idx, val, label, row_mask):
            B, L = idx.shape
            pad = (-B) % G
            if pad:
                idx = jnp.pad(idx, ((0, pad), (0, 0)))
                val = jnp.pad(val, ((0, pad), (0, 0)))
                label = jnp.pad(label, (0, pad))
                row_mask = jnp.pad(row_mask, (0, pad))
            nS = (B + pad) // G
            wf = w.astype(jnp.float32)
            sig0 = sigma if has_covar else jnp.zeros((1,), jnp.float32)

            def slab(carry, rows):
                cw, cs = carry
                sidx, sval, sy, smsk = rows
                Ws = cw[sidx]                               # [G, L]
                Ss = cs[sidx] if has_covar else jnp.ones_like(sval)

                def row_body(j, st):
                    Ws, Ss, acc = st
                    rv, y, msk = sval[j], sy[j], smsk[j]
                    wg, sg = Ws[j], Ss[j]
                    m = (wg * rv).sum() * y
                    v = ((sg * rv * rv).sum() if has_covar
                         else (rv * rv).sum())
                    alpha, beta = rates(m, v)
                    alpha = alpha * msk
                    beta = beta * msk
                    dw = alpha * y * sg * rv                # [L]
                    match = sidx[:, :, None] == sidx[j][None, None, :]
                    Ws = Ws + jnp.where(match, dw[None, None, :],
                                        0.0).sum(-1)
                    if has_covar:
                        new_s = jnp.maximum(sg - beta * (sg * rv) ** 2,
                                            1e-8)
                        dsg = jnp.where(msk > 0, new_s - sg, 0.0)
                        Ss = Ss + jnp.where(match, dsg[None, None, :],
                                            0.0).sum(-1)
                    return Ws, Ss, acc + jnp.maximum(0.0, 1.0 - m) * msk

                Ws, Ss, acc = jax.lax.fori_loop(
                    0, G, row_body, (Ws, Ss, jnp.float32(0.0)))
                # every slab entry of a shared feature tracked the same
                # value, so duplicate-index .set is well-defined
                cw = cw.at[sidx].set(Ws)
                if has_covar:
                    cs = cs.at[sidx].set(Ss)
                return (cw, cs), acc

            (wf, sig), losses = jax.lax.scan(
                slab, (wf, sig0),
                (idx.reshape(nS, G, L), val.reshape(nS, G, L),
                 label.reshape(nS, G), row_mask.reshape(nS, G)))
            return (wf.astype(w.dtype),
                    sig if has_covar else sigma, losses.sum())

        return step

    def _train_batch(self, batch: SparseBatch) -> float:
        self.params, self.sigma, loss = self._step(
            self.params, self.sigma, batch.idx, batch.val, batch.label,
            batch.row_mask)
        return loss

    def _finalized_weights(self) -> np.ndarray:
        return np.asarray(self.params.astype(jnp.float32))

    def _load_weights(self, w: np.ndarray) -> None:
        self.params = jnp.asarray(w, self.params.dtype)

    def covar_table(self) -> Optional[np.ndarray]:
        return None if self.sigma is None else np.asarray(self.sigma)

    def model_rows(self):
        w = self._finalized_weights()
        nz = np.nonzero(w)[0]
        if self.sigma is None:
            for i in nz:
                yield self._names.get(int(i), str(int(i))), float(w[i])
        else:
            sig = np.asarray(self.sigma)
            for i in nz:
                yield (self._names.get(int(i), str(int(i))), float(w[i]),
                       float(sig[i]))

    def _make_margin_fn(self):
        from .linear import _linear_predict_cached
        w = jnp.asarray(self._finalized_weights())
        predict = _linear_predict_cached()   # shared jitted gather+sum
        return lambda b: predict(w, b.idx, b.val)

    def decision_function(self, ds: SparseDataset) -> np.ndarray:
        return self._score_dataset(ds, max(int(self.opts.mini_batch), 256))

    def predict_proba(self, ds: SparseDataset) -> np.ndarray:
        return _sigmoid(self.decision_function(ds))


class PerceptronTrainer(_OnlineBase):
    """SQL: train_perceptron — mistake-driven, unit step."""
    NAME = "train_perceptron"

    def _rates(self):
        def rates(m, v):
            return (m <= 0).astype(jnp.float32), jnp.zeros_like(m)
        return rates


class PassiveAggressiveTrainer(_OnlineBase):
    """SQL: train_pa — tau = hinge/||x||^2 (Crammer et al. PA-0)."""
    NAME = "train_pa"

    def _tau_factory(self):
        # returns a closure over SCALARS only — capturing a bound method
        # here pinned the first trainer instance (and its dims-sized
        # tables) inside the global step cache forever
        return lambda loss, xx: loss / jnp.maximum(xx, 1e-12)

    def _rates(self):
        tau_fn = self._tau_factory()

        def rates(m, v):
            loss = jnp.maximum(0.0, 1.0 - m)
            return jnp.where(loss > 0, tau_fn(loss, v), 0.0), \
                jnp.zeros_like(m)
        return rates


class PA1Trainer(PassiveAggressiveTrainer):
    """SQL: train_pa1 — tau capped at C."""
    NAME = "train_pa1"

    def _tau_factory(self):
        c = float(self.opts.c)
        return lambda loss, xx: jnp.minimum(
            c, loss / jnp.maximum(xx, 1e-12))


class PA2Trainer(PassiveAggressiveTrainer):
    """SQL: train_pa2 — tau = loss / (||x||^2 + 1/(2C))."""
    NAME = "train_pa2"

    def _tau_factory(self):
        c = float(self.opts.c)
        return lambda loss, xx: loss / (xx + 1.0 / (2.0 * c))


def _phi_of(opts) -> float:
    """phi = Phi^-1(eta) when -eta given, else the explicit -phi."""
    eta = float(opts.eta)
    if eta and eta != 0.85:
        # inverse normal CDF via erfinv
        return float(math.sqrt(2.0) * _erfinv(2.0 * eta - 1.0))
    return float(opts.phi)


def _erfinv(x: float) -> float:
    # Winitzki's approximation — adequate for confidence params
    a = 0.147
    ln1mx2 = math.log(max(1e-12, 1.0 - x * x))
    t1 = 2.0 / (math.pi * a) + ln1mx2 / 2.0
    return math.copysign(math.sqrt(math.sqrt(t1 * t1 - ln1mx2 / a) - t1), x)


def _cw_beta(alpha, v, phi):
    u = 0.25 * (-alpha * v * phi
                + jnp.sqrt(alpha ** 2 * v ** 2 * phi ** 2 + 4.0 * v)) ** 2
    return alpha * phi / (jnp.sqrt(u) + v * alpha * phi + 1e-12)


class ConfidenceWeightedTrainer(_OnlineBase):
    """SQL: train_cw — Dredze/Crammer confidence-weighted (diagonal)."""
    NAME = "train_cw"
    HAS_COVAR = True

    def _rates(self):
        phi = _phi_of(self.opts)
        zeta = 1.0 + phi * phi
        psi = 1.0 + phi * phi / 2.0

        def rates(m, v):
            alpha = jnp.maximum(0.0, (-m * psi + jnp.sqrt(
                m * m * phi ** 4 / 4.0 + v * phi * phi * zeta))
                / jnp.maximum(v * zeta, 1e-12))
            return alpha, _cw_beta(alpha, v, phi)
        return rates


class AROWTrainer(_OnlineBase):
    """SQL: train_arow — adaptive regularization of weight vectors."""
    NAME = "train_arow"
    HAS_COVAR = True

    def _rates(self):
        r = float(self.opts.r)

        def rates(m, v):
            beta = 1.0 / (v + r)
            alpha = jnp.maximum(0.0, 1.0 - m) * beta
            update = (m < 1.0).astype(jnp.float32)
            return alpha * update, beta * update
        return rates


class AROWhTrainer(AROWTrainer):
    """SQL: train_arowh — AROW with hinge threshold (same closed form;
    the reference variant differs only in its loss bookkeeping)."""
    NAME = "train_arowh"


class SCW1Trainer(_OnlineBase):
    """SQL: train_scw — soft confidence-weighted I (Wang et al. 2012)."""
    NAME = "train_scw"
    HAS_COVAR = True

    def _rates(self):
        phi = _phi_of(self.opts)
        zeta = 1.0 + phi * phi
        psi = 1.0 + phi * phi / 2.0
        C = float(self.opts.c)

        def rates(m, v):
            alpha = jnp.maximum(0.0, (-m * psi + jnp.sqrt(
                m * m * phi ** 4 / 4.0 + v * phi * phi * zeta))
                / jnp.maximum(v * zeta, 1e-12))
            alpha = jnp.minimum(alpha, C)
            return alpha, _cw_beta(alpha, v, phi)
        return rates


class SCW2Trainer(_OnlineBase):
    """SQL: train_scw2 — soft confidence-weighted II."""
    NAME = "train_scw2"
    HAS_COVAR = True

    def _rates(self):
        phi = _phi_of(self.opts)
        C = float(self.opts.c)

        def rates(m, v):
            n = v + 1.0 / (2.0 * C)
            gamma = phi * jnp.sqrt(
                phi * phi * m * m * v * v + 4.0 * n * v * (n + v * phi * phi))
            alpha = jnp.maximum(0.0, (-(2.0 * m * n + phi * phi * m * v)
                                      + gamma)
                                / (2.0 * (n * n + n * v * phi * phi) + 1e-12))
            return alpha, _cw_beta(alpha, v, phi)
        return rates


class AdaGradRDATrainer(_OnlineBase):
    """SQL: train_adagrad_rda — AdaGrad + L1 regularized dual averaging
    (reference AdaGradRDAUDTF: hinge loss)."""
    NAME = "train_adagrad_rda"

    def _init_state(self) -> None:
        self.params = jnp.zeros(self.dims, jnp.float32)
        self.opt_state = {"u": jnp.zeros(self.dims, jnp.float32),
                          "gg": jnp.zeros(self.dims, jnp.float32)}
        self._step = self._shared_step("rda", self._make_rda_step)

    def _make_rda_step(self):
        lam = float(self.opts["lambda"])
        eta0 = float(self.opts.eta0)

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def step(w, u, gg, t, idx, val, label, row_mask):
            m = (w[idx] * val).sum(-1) * label
            active = ((m < 1.0).astype(jnp.float32)) * row_mask
            g = jnp.zeros_like(w).at[idx.ravel()].add(
                ((-label * active)[:, None] * val).ravel())
            u2 = u + g
            gg2 = gg + g * g
            tt = t + 1.0
            thresh = jnp.maximum(0.0, jnp.abs(u2) / tt - lam)
            w2 = -jnp.sign(u2) * eta0 * tt * thresh / (jnp.sqrt(gg2) + 1e-6)
            loss = (jnp.maximum(0.0, 1.0 - m) * row_mask).sum()
            return w2, u2, gg2, loss

        return step

    def _train_batch(self, batch: SparseBatch) -> float:
        s = self.opt_state
        self.params, u, gg, loss = self._step(
            self.params, s["u"], s["gg"], float(self._t), batch.idx,
            batch.val, batch.label, batch.row_mask)
        self.opt_state = {"u": u, "gg": gg}
        return loss


class KernelizedPATrainer(PA1Trainer):
    """SQL: train_kpa — polynomial-kernel PA via explicit degree-2 expansion
    (reference KernelExpansionPassiveAggressiveUDTF expands
    (1 + x.z)^2 into bias + linear + pairwise-cross feature space)."""
    NAME = "train_kpa"

    def _parse_row(self, features):
        idx, val = super()._parse_row(features)
        from ..utils.hashing import mhash
        n = len(idx)
        ei: list = list(idx)
        ev: list = list(val)
        for a in range(n):
            for b in range(a, n):
                key = (f"{min(idx[a], idx[b])}^{max(idx[a], idx[b])}"
                       .encode())
                h = mhash(key, self.dims - 1)
                ei.append(h)
                ev.append(float(val[a]) * float(val[b]))
        return np.asarray(ei, np.int32), np.asarray(ev, np.float32)


# --- regression variants (SURVEY.md §3.5 rows 4-5) -------------------------

class _PARegressionBase(_OnlineBase):
    """Epsilon-insensitive PA regression: rows (features, float target)."""
    CLASSIFICATION = False
    CAP_C = False       # PA1-style cap
    SQUARED = False     # PA2-style denominator

    def _make_step(self):
        eps = float(self.opts.epsilon)
        C = float(self.opts.c)
        cap = self.CAP_C
        sq = self.SQUARED

        @jax.jit
        def step(w, sigma, idx, val, label, row_mask):
            wf = w.astype(jnp.float32)
            pred = (wf[idx] * val).sum(-1)
            err = label - pred
            loss = jnp.maximum(0.0, jnp.abs(err) - eps)
            xx = (val * val).sum(-1)
            if sq:
                tau = loss / (xx + 1.0 / (2.0 * C))
            else:
                tau = loss / jnp.maximum(xx, 1e-12)
                if cap:
                    tau = jnp.minimum(tau, C)
            tau = tau * jnp.sign(err) * row_mask
            dw = jnp.zeros_like(wf).at[idx.ravel()].add(
                (tau[:, None] * val).ravel())
            return (wf + dw).astype(w.dtype), sigma, (loss * row_mask).sum()

        return step


class PARegressionTrainer(_PARegressionBase):
    """SQL: train_pa1_regr — reference PassiveAggressiveRegressionUDTF."""
    NAME = "train_pa1_regr"
    CAP_C = True


class PA1aRegressionTrainer(_PARegressionBase):
    """SQL: train_pa1a_regr — uncapped variant."""
    NAME = "train_pa1a_regr"


class PA2RegressionTrainer(_PARegressionBase):
    """SQL: train_pa2_regr."""
    NAME = "train_pa2_regr"
    SQUARED = True


class PA2aRegressionTrainer(_PARegressionBase):
    """SQL: train_pa2a_regr."""
    NAME = "train_pa2a_regr"
    SQUARED = True


class _AROWRegressionBase(_OnlineBase):
    """AROW regression with epsilon-insensitive loss and diagonal covar."""
    CLASSIFICATION = False
    HAS_COVAR = True

    def _make_step(self):
        eps = float(self.opts.epsilon)
        r = float(self.opts.r)

        @jax.jit
        def step(w, sigma, idx, val, label, row_mask):
            wf = w.astype(jnp.float32)
            sg = sigma[idx]
            pred = (wf[idx] * val).sum(-1)
            err = label - pred
            loss = jnp.maximum(0.0, jnp.abs(err) - eps)
            v = (sg * val * val).sum(-1)
            beta = 1.0 / (v + r)
            alpha = loss * beta * jnp.sign(err)
            active = (loss > 0).astype(jnp.float32) * row_mask
            dw = jnp.zeros_like(wf).at[idx.ravel()].add(
                ((alpha * active)[:, None] * sg * val).ravel())
            ds = jnp.zeros_like(sigma).at[idx.ravel()].add(
                ((beta * active)[:, None] * (sg * val) ** 2).ravel())
            return ((wf + dw).astype(w.dtype),
                    jnp.maximum(sigma - ds, 1e-8),
                    (loss * row_mask).sum())

        return step


class AROWRegressionTrainer(_AROWRegressionBase):
    """SQL: train_arow_regr — reference AROWRegressionUDTF."""
    NAME = "train_arow_regr"


class AROWeRegressionTrainer(_AROWRegressionBase):
    """SQL: train_arowe_regr — epsilon variant (same closed form, eps set
    by -epsilon)."""
    NAME = "train_arowe_regr"


class AROWe2RegressionTrainer(_AROWRegressionBase):
    """SQL: train_arowe2_regr — squared-step variant; beta uses v + 1/(2C)."""
    NAME = "train_arowe2_regr"

    def _make_step(self):
        eps = float(self.opts.epsilon)
        C = float(self.opts.c)

        @jax.jit
        def step(w, sigma, idx, val, label, row_mask):
            wf = w.astype(jnp.float32)
            sg = sigma[idx]
            pred = (wf[idx] * val).sum(-1)
            err = label - pred
            loss = jnp.maximum(0.0, jnp.abs(err) - eps)
            v = (sg * val * val).sum(-1)
            beta = 1.0 / (v + 1.0 / (2.0 * C))
            alpha = loss * beta * jnp.sign(err)
            active = (loss > 0).astype(jnp.float32) * row_mask
            dw = jnp.zeros_like(wf).at[idx.ravel()].add(
                ((alpha * active)[:, None] * sg * val).ravel())
            ds = jnp.zeros_like(sigma).at[idx.ravel()].add(
                ((beta * active)[:, None] * (sg * val) ** 2).ravel())
            return ((wf + dw).astype(w.dtype),
                    jnp.maximum(sigma - ds, 1e-8),
                    (loss * row_mask).sum())

        return step
