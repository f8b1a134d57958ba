"""train_fm / train_ffm — factorization-machine trainers (BASELINE config #2).

Reference (SURVEY.md §3.6): hivemall.fm.FactorizationMachineUDTF (train_fm,
options -factors/-iters/-eta*/-lambda*/-sigma/-classification/-int_feature),
FieldAwareFactorizationMachineUDTF (train_ffm, "field:index:value" features,
per-(feature,field) latent vectors, AdaGrad/FTRL), FMPredictGenericUDAF /
FFMPredictUDF for scoring.

TPU design: dense hashed tables w[N], V[N,K] (FM) / V[N,F,K] (FFM) in HBM,
bf16-able; one jitted value_and_grad step per minibatch (ops.fm). The FFM
(feature,field) table is the TP-sharding target for multi-chip (SURVEY.md §8
M3); see parallel.mesh / __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.sparse import (PackedBatch, SparseBatch, SparseDataset,
                         canonicalize_fieldmajor, pack_unit_fieldmajor)
from ..ops.fm import (ffm_row_hash, ffm_score, fm_pack_geometry, fm_score,
                      make_ffm_score_fieldmajor, make_ffm_score_fused,
                      make_ffm_step, make_ffm_step_fused,
                      make_fm_score_fused, make_fm_step, make_fm_step_fused)
from ..ops.losses import get_loss
from ..ops.optimizers import (make_optimizer,
                              make_optimizer_cached)
from ..utils.hashing import mhash
from ..utils.options import OptionSpec
from .base import LearnerBase, learner_option_spec
from .ffm_pairs import ObservedPairs

__all__ = ["FMTrainer", "FFMTrainer", "fm_predict", "ffm_predict"]


# --- config-cached step builders (round 4) ---------------------------------
# A fresh jitted closure per TRAINER instance re-traces/compiles for every
# identical config (the disease that cost word2vec 4x and LDA 10x e2e —
# each bench/CV iteration constructing a new trainer paid seconds of XLA
# compile). Steps/scorers are pure functions of the OPTION subset below, so
# module-level lru_caches keyed on it let instances share one compile;
# sharing jitted fns is safe (donation applies per CALL to that call's
# buffers, and all trainer state is passed in, never closed over).

from functools import lru_cache as _lru_cache
from functools import partial as _partial

from ..obs.devprof import instrument_factory as _instrument


@_instrument("fm", "step_fused")
@_lru_cache(maxsize=64)
def _fm_step_fused_cached(loss_name, opt, eta_scheme, eta0, total_steps,
                          power_t, lambdas, k):
    return make_fm_step_fused(
        get_loss(loss_name),
        make_optimizer_cached(opt, eta_scheme, eta0, total_steps,
                              power_t),
        lambdas, k)


@_instrument("fm", "step_minibatch")
@_lru_cache(maxsize=64)
def _fm_step_minibatch_cached(loss_name, opt, eta_scheme, eta0, total_steps,
                              power_t, lambdas, k, distinct_tail):
    from ..ops.fm import make_fm_step_minibatch
    return make_fm_step_minibatch(
        get_loss(loss_name),
        make_optimizer_cached(opt, eta_scheme, eta0, total_steps,
                              power_t),
        lambdas, k, distinct_tail)


@_instrument("fm", "step")
@_lru_cache(maxsize=64)
def _fm_step_cached(loss_name, opt, eta_scheme, eta0, total_steps,
                    power_t, lambdas):
    return make_fm_step(
        get_loss(loss_name),
        make_optimizer_cached(opt, eta_scheme, eta0, total_steps,
                              power_t),
        lambdas)


@_instrument("ffm", "step_fused")
@_lru_cache(maxsize=64)
def _ffm_step_fused_cached(loss_name, opt, eta_scheme, eta0, total_steps,
                           power_t, lambdas, F, k, fieldmajor, unit_val,
                           distinct_tail=True, mesh=None):
    return make_ffm_step_fused(
        get_loss(loss_name),
        make_optimizer_cached(opt, eta_scheme, eta0, total_steps,
                              power_t),
        lambdas, F, k, fieldmajor=fieldmajor, unit_val=unit_val,
        distinct_tail=distinct_tail, mesh=mesh)


@_instrument("ffm", "step")
@_lru_cache(maxsize=64)
def _ffm_step_cached(loss_name, opt, eta_scheme, eta0, total_steps,
                     power_t, lambdas):
    return make_ffm_step(
        get_loss(loss_name),
        make_optimizer_cached(opt, eta_scheme, eta0, total_steps,
                              power_t),
        lambdas)


@_instrument("ffm", "parts_step")
@_lru_cache(maxsize=64)
def _parts_step_cached(loss_name, eta_scheme, eta0, total_steps, power_t,
                       lambdas, F, k, MRF, unit_val, interpret):
    from ..ops.fm_pallas import make_parts_step
    from ..ops.schedules import make_eta
    return make_parts_step(get_loss(loss_name),
                           make_eta(eta_scheme, eta0, total_steps, power_t),
                           lambdas, F, k, MRF, unit_val=unit_val,
                           interpret=interpret)


@_instrument("ffm", "parts_score")
@_lru_cache(maxsize=64)
def _parts_score_cached(F, k, MRF):
    from ..ops.fm_pallas import make_parts_score
    return make_parts_score(F, k, MRF)


@_instrument("fm", "score_fused")
@_lru_cache(maxsize=64)
def _fm_score_fused_cached(k):
    return make_fm_score_fused(k)


@_instrument("ffm", "score_fused")
@_lru_cache(maxsize=64)
def _ffm_score_fused_cached(F, k):
    return make_ffm_score_fused(F, k)


@_instrument("ffm", "score_fieldmajor")
@_lru_cache(maxsize=64)
def _ffm_score_fieldmajor_cached(F, k):
    return make_ffm_score_fieldmajor(F, k)


def _unpack_on_device(buf, nv, B: int, L: int):
    """Device-side decode of ONE io.sparse.PackedBatch wire buffer:
    3-byte little-endian idx lanes reassembled via shifts, f32 labels via
    bitcast, valid-row mask from the nv scalar. The single source of the
    packed wire format on the consume side — the K=1 wrapper and the
    K-step scan body below both call it, so a layout change can never
    reach one dispatch path and not the other. Elementwise, fuses into
    the step; the win is on the h2d link (see io.sparse.PackedBatch)."""
    ni = B * L * 3
    b3 = buf[:ni].reshape(B, L, 3).astype(jnp.int32)
    idx = b3[..., 0] | (b3[..., 1] << 8) | (b3[..., 2] << 16)
    label = jax.lax.bitcast_convert_type(
        buf[ni:].reshape(B, 4), jnp.float32)
    mask = (jnp.arange(B) < nv).astype(jnp.float32)
    return idx, label, mask


@_instrument("ffm", "packed_megastep", shape_args=(1, 2))
@_lru_cache(maxsize=128)
def _packed_megawrap_cached(base_step, B: int, L: int):
    """K-step fused dispatch for the PACKED flagship path
    (-steps_per_dispatch > 1 + pack_input): one jitted lax.scan over a
    [K, nbytes] stacked uint8 buffer, each step unpacking its window
    (_unpack_on_device) and running the SAME unit-val field-major step
    core the K=1 path compiled. Model/optimizer state is donated through
    the scan carry — XLA updates the tables in place across all K
    steps."""
    core = getattr(base_step, "core", base_step)
    from ..ops.scan import _profiled_megastep, scopes_in_name

    @_partial(jax.jit, donate_argnums=(0, 1))
    @scopes_in_name
    def packed_megastep(params, opt_state, t0, bufs, nvs):
        def body(carry, x):
            p, s, t = carry
            idx, label, mask = _unpack_on_device(x["buf"], x["nv"], B, L)
            p, s, *out = core(p, s, t, idx, label, mask)
            return (p, s, t + 1.0), tuple(out)

        # the phase scopes are the core's own (ops/fm.py); the unpack and
        # the scan's slicing stay under hm.scan alone
        with jax.named_scope("hm.scan"):
            (p, s, _), out = jax.lax.scan(
                body, (params, opt_state, t0), {"buf": bufs, "nv": nvs})
        return (p, s, *out)     # losses [K] and, from a joint step, stats

    # same devprof dispatch boundary as ops.scan.megastep_for: the packed
    # flagship path must not be the one fused dispatch whose peak-bytes
    # tracking silently reads zero
    return _profiled_megastep(packed_megastep)


@_instrument("ffm", "packed_step", shape_args=(1, 2))
@_lru_cache(maxsize=128)
def _packed_wrap_cached(base_step, B: int, L: int):
    """Jitted wrapper (cached per (shared base step, batch shape)) that
    unpacks a PackedBatch buffer on device (_unpack_on_device) then runs
    the regular unit-val field-major step."""
    @jax.jit
    def fn(params, opt_state, t, buf, nv):
        idx, label, mask = _unpack_on_device(buf, nv, B, L)
        return base_step(params, opt_state, t, idx, label, mask)

    return fn


@_lru_cache(maxsize=256)
def _fused_state_init(optimizer, rows: int, pack: int, k: int, width: int,
                      dtype):
    """Initialiser of a fused table's whole state, for
    ``LearnerBase._make_state``: ``init(key, sigma) -> (params,
    opt_state)`` with ``T`` of ``[rows, pack * width]`` — ``pack`` features
    a row, each ``normal * sigma`` in its first ``k`` lanes and zeros in
    the rest — ``w0``, and the optimizer's state for both. One function
    object per configuration, so that its compile is shared."""
    def init(key, sigma):
        T = jnp.concatenate([
            jax.random.normal(key, (rows * pack, k)) * sigma,
            jnp.zeros((rows * pack, width - k)),
        ], axis=1).astype(dtype).reshape(rows, pack * width)
        params = {"w0": jnp.zeros((), dtype), "T": T}
        opt_state = {"w0": optimizer.init(()),
                     "T": optimizer.init((rows, pack * width))}
        return params, opt_state
    return init


def _factor_spec(name: str, default_factors: int, default_opt: str
                 ) -> OptionSpec:
    s = learner_option_spec(name, classification=True,
                            default_loss="squaredloss")
    s.add("factors", "factor", type=int, default=default_factors,
          help="latent dimension k")
    s.add("sigma", type=float, default=0.1, help="init stddev for V")
    s.flag("classification", help="optimize logloss on +-1 labels "
                                  "(default: regression, squared loss)")
    s.add("lambda0", type=float, default=0.01, help="L2 for w0")
    s.add("lambda_w", type=float, default=0.01, help="L2 for linear weights")
    s.add("lambda_v", type=float, default=0.01, help="L2 for latent factors")
    s.add("min_target", type=float, default=None, help="clip regression target")
    s.add("max_target", type=float, default=None, help="clip regression target")
    s.add("fm_table", default="auto",
          help="train_fm table layout: fused (one [N, K+pad] row per "
               "feature holding V and w — half the gather/scatter index "
               "ops, see docs/PERFORMANCE.md) | split (separate w/V) | "
               "auto (fused when the optimizer has a sparse form)")
    for o in s.options:
        if o.name == "opt":
            o.default = default_opt
        if o.name == "reg":
            o.default = "no"       # factor models carry their own L2 lambdas
    return s


class FMTrainer(LearnerBase):
    """SQL: train_fm — reference hivemall.fm.FactorizationMachineUDTF."""

    NAME = "train_fm"
    CLASSIFICATION = False     # label handling driven by -classification
    _adareg = False            # class default: FFMTrainer inherits the
    # _batch_args/_fit_epochs hooks without running FM's _init_state

    @classmethod
    def spec(cls) -> OptionSpec:
        s = _factor_spec(cls.NAME, default_factors=5, default_opt="sgd")
        # reference train_fm options (SURVEY.md §3.6 FM row): adaptive
        # regularization against a held-out validation fraction
        s.flag("adareg", "adaptive_regularization",
               help="adapt -lambda_w/-lambda_v per epoch against a "
                    "held-out validation split (see -va_ratio)")
        s.add("va_ratio", "validation_ratio", type=float, default=0.05,
              help="fraction of rows held out for -adareg validation")
        s.add("fm_update", default="auto",
              help="fused-layout update shape: minibatch (one scatter-add "
                   "into a dense G + dense AdaGrad — accumulators see the "
                   "summed batch gradient, 2 index ops/slot) | occurrence "
                   "(per-occurrence sparse AdaGrad chain, 5 index "
                   "ops/slot) | auto (minibatch for -opt adagrad)")
        return s

    def _init_state(self) -> None:
        o = self.opts
        self.classification = bool(o.classification)
        self._loss_name = ("logloss" if self.classification
                           else (o.loss or "squaredloss"))
        self.loss = get_loss(self._loss_name)
        self._opt_key = (str(o.opt), str(o.eta), float(o.eta0),
                         o.total_steps, o.power_t)
        self.optimizer = make_optimizer_cached(*self._opt_key)
        self.k = int(o.factors)
        dtype = jnp.bfloat16 if o.halffloat else jnp.float32
        key = jax.random.PRNGKey(int(o.seed))
        self.fm_layout = str(getattr(o, "fm_table", "auto"))
        if self.fm_layout not in ("fused", "split", "auto"):
            raise ValueError(f"-fm_table must be fused|split|auto, "
                             f"got {self.fm_layout!r}")
        # fused needs zero-grad sparse updates to be exact no-ops on the
        # sibling features packed into the same 128-lane row; FTRL/RDA
        # re-materialize every scattered element (they'd wipe siblings'
        # lazy init), so only the elementwise .add families qualify
        fusable = self.optimizer.name in ("sgd", "adagrad")
        self._adareg = False
        upd = str(getattr(o, "fm_update", "auto"))
        if upd not in ("auto", "minibatch", "occurrence"):
            raise ValueError(f"-fm_update must be auto|minibatch|"
                             f"occurrence, got {upd!r}")
        if self.fm_layout == "auto":
            self.fm_layout = "fused" if fusable else "split"
        if self.fm_layout == "fused" and not fusable:
            raise ValueError(f"-fm_table fused needs -opt sgd|adagrad "
                             f"(-opt {self.optimizer.name} re-materializes "
                             f"packed sibling rows); use -fm_table split")
        if self.fm_layout == "fused":
            # packed fused rows: [V(K) | w | pad] x P features per 128-lane
            # physical row — one gather + one sparse update per step
            # instead of two tables' worth of narrow-row chains
            self.W, self.P = fm_pack_geometry(self.k)
            self.Np = -(-self.dims // self.P)
            self._tp_sizes.add(self.Np)    # mesh: shard packed rows over tp
            self.params, self.opt_state = self._make_state(
                _fused_state_init(self.optimizer, self.Np, self.P, self.k,
                                  self.W, dtype), key, float(o.sigma))
            self._adareg = bool(getattr(o, "adareg", False))
            self._va_ratio = float(getattr(o, "va_ratio", 0.05))
            if self._adareg:
                if not 0.0 < self._va_ratio < 0.5:
                    raise ValueError(
                        f"-va_ratio must be in (0, 0.5), got "
                        f"{self._va_ratio}")
                # runtime lambdas (adapted per epoch) -> dynamic-lambda
                # step variants (lambdas=None builders)
                self._lams = np.asarray(
                    [o.lambda0, o.lambda_w, o.lambda_v], np.float32)
            # minibatch: ONE scatter-add into a dense G + dense optimizer
            # pass (2 table-row index ops/slot) instead of the
            # per-occurrence sparse chain's 5 — the update shape the FFM
            # fused/parts paths already use. AdaGrad only: SGD's sparse
            # form is already 2 index ops, and the dense pass would be
            # pure overhead there.
            if upd == "minibatch" and self.optimizer.name != "adagrad":
                raise ValueError("-fm_update minibatch needs -opt adagrad")
            if upd == "auto":
                upd = ("minibatch" if self.optimizer.name == "adagrad"
                       else "occurrence")
            # -adareg: lambdas become a runtime step argument (the None
            # sentinel below) so per-epoch adaptation re-uses one compile
            lam_key = (None if self._adareg
                       else (o.lambda0, o.lambda_w, o.lambda_v))
            if upd == "minibatch":
                # under -mesh the dense tail stays: a sort and a cond over
                # a row-sharded table are another question (PERF.md §7)
                self._step = _fm_step_minibatch_cached(
                    self._loss_name, *self._opt_key, lam_key, self.k,
                    not o.get("mesh"))
            else:
                self._step = _fm_step_fused_cached(
                    self._loss_name, *self._opt_key, lam_key, self.k)
            self._fused_score = _fm_score_fused_cached(self.k)
            self.UNIT_VAL_ELISION = True   # fused step accepts val=None
        else:
            if bool(getattr(o, "adareg", False)):
                raise ValueError("-adareg needs the fused table layout "
                                 "(-fm_table fused, i.e. -opt sgd|adagrad)")
            if upd != "auto":
                raise ValueError("-fm_update applies to the fused table "
                                 "layout only (-fm_table fused)")
            self.params = {
                "w0": jnp.zeros((), dtype),
                "w": jnp.zeros(self.dims, dtype),
                "V": (jax.random.normal(key, (self.dims, self.k)) *
                      float(o.sigma)).astype(dtype),
            }
            self.opt_state = {k: self.optimizer.init(v.shape)
                              for k, v in self.params.items()}
            self._step = _fm_step_cached(
                self._loss_name, *self._opt_key,
                (o.lambda0, o.lambda_w, o.lambda_v))

    def _convert_label(self, label: float) -> float:
        if self.classification:
            return 1.0 if float(label) > 0 else -1.0
        y = float(label)
        if self.opts.min_target is not None:
            y = max(y, self.opts.min_target)
        if self.opts.max_target is not None:
            y = min(y, self.opts.max_target)
        return y

    def _convert_labels(self, labels: np.ndarray) -> np.ndarray:
        if self.classification:
            return np.where(labels > 0, 1.0, -1.0).astype(np.float32)
        y = labels.astype(np.float32)
        if self.opts.min_target is not None:
            y = np.maximum(y, self.opts.min_target)
        if self.opts.max_target is not None:
            y = np.minimum(y, self.opts.max_target)
        return y

    def _batch_args(self, batch: SparseBatch) -> tuple:
        if self._adareg:
            return (jnp.asarray(self._lams),)
        return ()

    def _mega_lams(self):
        # -adareg runtime lambdas ride the megastep as a BROADCAST extra
        # (not scanned): all K steps in a window see the same lambdas,
        # exactly as K consecutive K=1 steps within one epoch do
        # (adaptation happens per epoch, between fits)
        if self._adareg:
            return jnp.asarray(self._lams)
        return None

    def _train_batch(self, batch: SparseBatch) -> float:
        self.params, self.opt_state, loss_sum, *stats = self._step(
            self.params, self.opt_state, float(self._t), batch.idx, batch.val,
            batch.label, batch.row_mask, *self._batch_args(batch))
        self._stats_pending += stats
        return loss_sum

    # -- adaptive regularization (-adareg, SURVEY.md §3.6 train_fm row) -----
    _ADAREG_UP, _ADAREG_DOWN = 2.0, 0.9

    def _fit_epochs(self, ds, epochs, bs, shuffle, prefetch, ckdir,
                    seed0: int = 42) -> None:
        """-adareg: hold out -va_ratio of the rows, train each epoch on
        the rest, and adapt lambda_w/lambda_v against the held-out loss —
        validation got WORSE since the last epoch -> multiply lambdas by
        2 (regularize harder), got better -> decay by 0.9 (the reference's
        SGDA-style per-update lambda gradient becomes this per-epoch
        multiplicative trust region; direction is pinned by test). The
        step reads lambdas at RUNTIME (dynamic-lambda variant), so
        adaptation never recompiles."""
        if not self._adareg or len(ds) < 20:
            return super()._fit_epochs(ds, epochs, bs, shuffle, prefetch,
                                       ckdir, seed0)
        rng = np.random.default_rng(int(self.opts.seed))
        n = len(ds)
        n_va = max(1, int(round(n * self._va_ratio)))
        perm = rng.permutation(n)
        ds_va = ds.take(perm[:n_va])
        ds_tr = ds.take(perm[n_va:])
        prev = None
        for ep in range(epochs):
            # ckdir handled HERE so bundle names carry the REAL epoch
            # number (the inner call's local epoch is always 1)
            super()._fit_epochs(ds_tr, 1, bs, shuffle, prefetch, None,
                                seed0=seed0 + ep)
            if ckdir:
                self._save_epoch_bundle(ckdir, ep + 1)
            # per-EPOCH validation eval, not per step: one sync per
            # epoch is the adaptive-regularization design
            # graftcheck: disable=GC07
            va = self._mean_loss(ds_va)
            if prev is not None:
                scale = (self._ADAREG_UP if va > prev * (1 + 1e-9)
                         else self._ADAREG_DOWN)
                self._lams[1:] *= scale
            prev = va

    def _mean_loss(self, ds: SparseDataset) -> float:
        phi = self.decision_function(ds)
        return float(np.mean(np.asarray(self.loss.loss(
            jnp.asarray(phi), jnp.asarray(ds.labels)))))

    # -- scoring -------------------------------------------------------------
    def _score_batch(self, batch: SparseBatch) -> np.ndarray:
        p = self.params
        if getattr(self, "fm_layout", "split") == "fused":
            return np.asarray(self._fused_score(
                p["w0"], p["T"], jnp.asarray(batch.idx),
                jnp.asarray(batch.val)))
        return np.asarray(fm_score(p["w0"], p["w"], p["V"],
                                   batch.idx, batch.val))

    def _make_margin_fn(self):
        # _score_batch reads self.params at call time (no finalization
        # pass to freeze); the serve engine still swaps trainer + scorer
        # as one ref, so a hot-reload can never mix versions mid-batch
        return self._score_batch

    def decision_function(self, ds: SparseDataset) -> np.ndarray:
        return self._score_dataset(ds)

    def predict(self, ds: SparseDataset) -> np.ndarray:
        phi = self.decision_function(ds)
        if self.classification:
            return 1.0 / (1.0 + np.exp(-phi))
        return phi

    def make_scorer(self):
        # mirror predict()'s historical sigmoid form exactly so online
        # scores bit-match the offline FM predict path
        margin = self._make_margin_fn()
        if self.classification:
            return lambda b: np.asarray(
                1.0 / (1.0 + np.exp(-np.asarray(margin(b), np.float32))),
                np.float32)
        return lambda b: np.asarray(margin(b), np.float32)

    def serving_tables(self):
        """Arena extraction (io.weight_arena): the canonical (w, V)
        split-layout f32 tables — _wv_tables already normalizes the
        fused packed layout, so one arena family serves both."""
        w, V = self._wv_tables()
        meta = {"family": "fm", "k": self.k,
                "w0": float(np.asarray(self.params["w0"],
                                       np.float32)),
                "classification": bool(self.classification)}
        return meta, {"w": np.ascontiguousarray(w, np.float32),
                      "V": np.ascontiguousarray(V, np.float32)}

    def _fused_rows(self):
        """Per-feature [>=dims, Wf] view of the packed fused table (device).
        Row i = feature i's [V(K) | w | pad] block — the [Np, P*Wf]
        physical layout unpacks with one reshape."""
        return self.params["T"].reshape(self.Np * self.P, self.W)

    def _wv_tables(self):
        """(w [N], V [N, K]) float32 views for emission, either layout."""
        if getattr(self, "fm_layout", "split") == "fused":
            R = np.asarray(self._fused_rows().astype(jnp.float32))
            return R[:self.dims, self.k], R[:self.dims, :self.k]
        return (np.asarray(self.params["w"].astype(jnp.float32)),
                np.asarray(self.params["V"].astype(jnp.float32)))

    # -- model emission: (feature, Wi, Vi[]) rows ---------------------------
    def model_rows(self):
        w, V = self._wv_tables()
        touched = np.nonzero((np.abs(V).sum(-1) > 0) | (w != 0))[0]
        yield ("0", float(np.asarray(self.params["w0"])), None)
        for i in touched:
            if i == 0:
                continue
            yield (self._names.get(int(i), str(int(i))), float(w[i]),
                   V[i].tolist())

    def model_table(self):
        return {row[0]: row[1:] for row in self.model_rows()}

    def save_model(self, path: str) -> None:
        """Binary model bundle (params + optimizer state), orbax-style npz."""
        # save path: one fetch per param tensor (a handful), not per step
        np.savez(path, **{k: np.asarray(v.astype(jnp.float32))  # graftcheck: disable=GC07
                          for k, v in self.params.items()})

    def _warm_start(self, path: str) -> None:
        z = np.load(path if path.endswith(".npz") else path + ".npz")
        missing = [k for k in self.params if k not in z.files]
        if missing:
            raise ValueError(
                f"-loadmodel {path}: saved model has keys "
                f"{sorted(z.files)} but this trainer expects "
                f"{sorted(self.params)} — table-layout mismatch "
                f"(-fm_table/-ffm_table changed since the save?)")
        for k in self.params:
            if tuple(z[k].shape) != tuple(self.params[k].shape):
                raise ValueError(
                    f"-loadmodel {path}: saved {k!r} has shape "
                    f"{tuple(z[k].shape)}, trainer expects "
                    f"{tuple(self.params[k].shape)} — options mismatch "
                    f"(-dims/-factors/-fields/-fm_table/-ffm_table)?")
            self.params[k] = jnp.asarray(z[k], self.params[k].dtype)

    # -- sparse weight access for the mix client (fused layout: w is col k) --
    def _weight_table(self):
        if getattr(self, "fm_layout", "split") == "fused":
            return None                # w lives inside T; use overrides
        return super()._weight_table()

    def _get_weights_at(self, keys: np.ndarray) -> np.ndarray:
        if getattr(self, "fm_layout", "split") != "fused":
            return super()._get_weights_at(keys)
        rr = jnp.asarray(np.asarray(keys))
        return np.asarray(self._fused_rows()[rr, self.k], np.float32)

    def _set_weights_at(self, keys: np.ndarray, vals: np.ndarray) -> None:
        if getattr(self, "fm_layout", "split") != "fused":
            return super()._set_weights_at(keys, vals)
        R = self._fused_rows()
        rr = jnp.asarray(np.asarray(keys))
        R = R.at[rr, self.k].set(jnp.asarray(vals, R.dtype))
        self.params["T"] = R.reshape(self.Np, self.P * self.W)

    def _finalized_weights(self) -> np.ndarray:
        if getattr(self, "fm_layout", "split") == "fused":
            return np.asarray(
                self._fused_rows()[:self.dims, self.k].astype(jnp.float32))
        return np.asarray(self.params["w"].astype(jnp.float32))

    def _load_weights(self, w: np.ndarray) -> None:
        if getattr(self, "fm_layout", "split") == "fused":
            R = self._fused_rows()
            R = R.at[:self.dims, self.k].set(jnp.asarray(w, R.dtype))
            self.params["T"] = R.reshape(self.Np, self.P * self.W)
            return
        self.params["w"] = jnp.asarray(w, self.params["w"].dtype)


# --- FFM host prep as pure config-parameterized functions -------------------
# The parallel prep leg (canonicalize -> parts row pad -> pack) must exist
# in TWO callable forms with identical semantics: the bound trainer methods
# (thread pools, sequential fallback) and a PICKLABLE config-built callable
# for -ingest_pool process — a bound method would drag the whole trainer
# (device tables included) through pickle per task and cannot cross the
# process boundary. Both forms call the module functions below, so they can never
# drift; tests/test_pipeline.py pins process == thread == sequential
# bit-exact.

from dataclasses import dataclass as _dataclass


def _ffm_canonicalize(batch: SparseBatch, F: int, canon_on: bool,
                      forced: bool) -> SparseBatch:
    """Canonicalize one host batch into field-major slots (slot s holds a
    feature of field s % F) so the jitted step can run the static
    field-grouped interaction — no L^2 intermediate, no per-slot field
    array. Skipped (general pair path) when the trainer/layout doesn't use
    it (``canon_on``), when a row has > 4 same-field features, or when the
    canonical width m*F would more than double the batch (rows sparse
    relative to the field space — the pair kernel is cheaper there).
    ``forced`` (-ffm_interaction fieldmajor) disables the width bail and
    raises on overflow instead of falling back."""
    if not canon_on or batch.fieldmajor or batch.field is None:
        return batch
    L = int(batch.idx.shape[1])
    if not forced and F > 2 * L:            # even m=1 inflates > 2x
        return batch
    res = canonicalize_fieldmajor(
        np.asarray(batch.idx), np.asarray(batch.val),
        np.asarray(batch.field), F)
    if res is None or (not forced and res[2] * F > 2 * L):
        if forced and res is None:
            raise ValueError(
                "-ffm_interaction fieldmajor: a row has more than 4 "
                "features in one field; use -ffm_interaction auto")
        return batch
    idx2, val2, _ = res
    if np.array_equal(val2, (idx2 != 0).astype(np.float32)):
        # unit-value elision: skip the val array entirely (a third of
        # the h2d bytes; the step rebuilds it from idx on device)
        val2 = None
    return SparseBatch(idx2, val2, batch.label, None,
                       n_valid=batch.n_valid, fieldmajor=True)


def _parts_row_target(B: int, dp: int = 1) -> int:
    """The parts kernel's allocated row count for ``B`` logical rows:
    whole 128-row tiles (the SMEM row-id packing) up to 2048, then whole
    2048-row chunks, scaled by the dp axis. The ONE copy of the grid rule
    — the streamed pad (_ffm_pad_parts) and the shard cache's batch
    assembly (_cache_row_pad) must agree or cached batches stop matching
    the compiled buckets."""
    mult = 128 * dp if B <= 2048 * dp else 2048 * dp
    return -(-B // mult) * mult


def _ffm_pad_parts(batch: SparseBatch, dp: int = 1) -> SparseBatch:
    """Pad the batch's row count to the Pallas parts kernel's grid
    multiple (_parts_row_target); padded rows carry idx 0 and are masked
    out of the loss by n_valid. Under -mesh each dp rank must receive
    whole tiles, so the multiple scales by dp on both branches."""
    B = batch.batch_size
    target = _parts_row_target(B, dp)
    if target == B:
        return batch
    pad = target - B
    idx = np.pad(np.asarray(batch.idx), ((0, pad), (0, 0)))
    val = None if batch.val is None else np.pad(
        np.asarray(batch.val), ((0, pad), (0, 0)))
    lab = np.pad(np.asarray(batch.label), (0, pad))
    nv = batch.n_valid if batch.n_valid is not None else B
    return SparseBatch(idx, val, lab, None, n_valid=nv, fieldmajor=True)


@_dataclass(frozen=True)
class FFMPrep:
    """Picklable FFM train-prep: a plain dataclass of the option-derived
    booleans the bound prep reads off the trainer, so a process-pool
    worker rebuilds the exact same function from ~5 scalars instead of a
    pickled trainer. ``__call__`` IS ``_preprocess_train_parallel``."""

    F: int
    canon: bool          # a field-major step exists (joint/parts layouts)
    forced: bool         # -ffm_interaction fieldmajor
    parts: bool          # parts layout: kernel-grid row padding
    pack: bool           # packed uint8 wire format conditions all hold
    parts_dp: int = 1

    def __call__(self, batch: SparseBatch):
        batch = _ffm_canonicalize(batch, self.F, self.canon, self.forced)
        if self.parts and batch.fieldmajor:
            batch = _ffm_pad_parts(batch, self.parts_dp)
        if (self.pack and batch.fieldmajor and batch.val is None
                and isinstance(batch.idx, np.ndarray)):
            return pack_unit_fieldmajor(batch)
        return batch


def _tee_into_writer(src, writer, order, bs: int):
    """Yield prepared batches unchanged while scattering each one's rows
    into a shard-cache writer (batch i covers order[i*bs:(i+1)*bs] — the
    same chunking ds.batches applied to the same permutation). Runs on
    whatever single thread consumes the prep pipeline."""
    i = 0
    for b in src:
        writer.add(b, order[i * bs:(i + 1) * bs])
        i += 1
        yield b


class FFMTrainer(FMTrainer):
    """SQL: train_ffm — reference hivemall.fm.FieldAwareFactorizationMachineUDTF.

    Features are "field:index:value" triples (ftvec.trans.ffm_features).
    Two latent-table layouts (-ffm_table):

      joint (default) — the fused feature-row layout: one table
        T[Mr, F*K + 8] where row ffm_row_hash(feature) holds ALL F of that
        feature's per-field latent vectors plus its linear weight, and
        Mr * F_pow2 = -dims total (feature, field) capacity. The TPU analog
        of the reference's packed-long keys, laid out so one train step
        costs exactly one row-gather + one row-scatter (TPU scatter cost is
        per-row, not per-byte — see ops.fm.make_ffm_step_fused; measured
        95x over a flat per-pair table). Criteo-scale ``-dims 2^24
        -fields 64 -halffloat`` is ~140 MB of weights + ~280 MB f32
        AdaGrad state, single-chip friendly; shards over 'tp'.
      dense — V[N, F, K] field cube, exact (feature, field) cells, for
        small field counts.
    """

    NAME = "train_ffm"

    @classmethod
    def spec(cls) -> OptionSpec:
        s = _factor_spec(cls.NAME, default_factors=4, default_opt="adagrad")
        s.add("fields", "num_fields", type=int, default=64,
              help="field-space size F")
        s.add("ffm_table", default="auto",
              help="latent-table layout: joint (hashed flat [M,K], "
                   "Criteo-scale) | parts (field-partitioned fused rows "
                   "with the Pallas VMEM scatter+AdaGrad kernel — fastest "
                   "on TPU for adagrad/-halffloat/fieldmajor configs) | "
                   "dense ([N,F,K] field cube) | auto (joint when -dims "
                   "is a power of two, else dense)")
        s.add("ffm_interaction", default="auto",
              help="pair-interaction kernel for the joint layout: "
                   "fieldmajor (canonical field-major batches, no L^2 "
                   "intermediate — fastest when rows are near field-dense, "
                   "e.g. Criteo) | pairs (general one-hot einsum) | auto "
                   "(fieldmajor per batch when it fits, else pairs)")
        s.flag("no_w0", help="drop the global bias term")
        s.flag("no_wi", help="drop the linear terms (libffm-style)")
        s.add("pack_input", default="auto",
              help="pack canonical unit-value batches into one 3-byte-lane "
                   "uint8 buffer per h2d transfer (idx exact for dims <= "
                   "2^24; ~27%% fewer input bytes and one transfer instead "
                   "of three): auto (accelerators only) | on | off")
        return s

    def _init_state(self) -> None:
        o = self.opts
        self.classification = bool(o.classification)
        self._loss_name = ("logloss" if self.classification
                           else (o.loss or "squaredloss"))
        self.loss = get_loss(self._loss_name)
        self._opt_key = (str(o.opt), str(o.eta), float(o.eta0),
                         o.total_steps, o.power_t)
        self.optimizer = make_optimizer_cached(*self._opt_key)
        self.k = int(o.factors)
        self.F = int(o.fields)
        self.layout = str(o.ffm_table)
        if self.layout not in ("joint", "dense", "auto", "parts"):
            raise ValueError(f"-ffm_table must be joint|parts|dense|auto, "
                             f"got {self.layout!r}")
        self.interaction = str(getattr(o, "ffm_interaction", "auto"))
        if self.interaction not in ("auto", "pairs", "fieldmajor"):
            raise ValueError("-ffm_interaction must be auto|pairs|fieldmajor,"
                             f" got {self.interaction!r}")
        pow2 = (self.dims & (self.dims - 1)) == 0
        if self.layout == "auto":
            self.layout = "joint" if pow2 else "dense"
        if self.layout in ("joint", "parts") and not pow2:
            raise ValueError(f"-ffm_table {self.layout} needs a "
                             f"power-of-two -dims (got {self.dims})")
        dtype = jnp.bfloat16 if o.halffloat else jnp.float32
        key = jax.random.PRNGKey(int(o.seed))
        self._pairs = ObservedPairs(self.F)   # seen on the stream path
        self._fit_ds = None            # dataset ref, columnar path
        if self.layout == "parts":
            from ..ops.fm_pallas import parts_geometry, parts_supported
            if not parts_supported(self.F, self.k, self.optimizer.name,
                                   dtype):
                raise ValueError(
                    "-ffm_table parts requires -opt adagrad, -halffloat, "
                    f"and F*K+8 <= 248 (got opt={self.optimizer.name}, "
                    f"dtype={dtype.__name__}, F={self.F}, K={self.k}); "
                    "use -ffm_table joint")
            self.MRF, self.Wp, self.HP = parts_geometry(self.dims, self.F,
                                                        self.k)
            FK = self.F * self.k
            Tl = jnp.concatenate([
                jax.random.normal(key, (self.F * self.MRF, FK))
                * float(o.sigma),
                jnp.zeros((self.F * self.MRF, self.Wp - FK)),
            ], axis=1)
            self.params = {
                "w0": jnp.zeros((), jnp.float32),
                "T2": Tl.reshape(self.F * self.MRF * self.HP,
                                 128).astype(dtype)}
            self.opt_state = {
                "w0": self.optimizer.init(()),
                "T2": {"gg": jnp.zeros((self.F * self.MRF * self.HP, 128),
                                       jnp.float32)}}
            from ..utils.device import pallas_interpret
            interp = pallas_interpret()
            lamt = (o.lambda0, o.lambda_w, o.lambda_v)
            eta_key = (str(o.eta), float(o.eta0), o.total_steps, o.power_t)
            self._step = None
            self._step_fm = _parts_step_cached(
                self._loss_name, *eta_key, lamt, self.F, self.k, self.MRF,
                False, interp)
            self._step_fm_unit = _parts_step_cached(
                self._loss_name, *eta_key, lamt, self.F, self.k, self.MRF,
                True, interp)
            self._fused_score = None
            self._fused_score_fm = _parts_score_cached(self.F, self.k,
                                                       self.MRF)
            self.interaction = "fieldmajor"   # parts is fieldmajor-only
            return
        if self.layout == "joint":
            f_pow2 = 1
            while f_pow2 < self.F:
                f_pow2 <<= 1
            self.Mr = max(1 << 10, self.dims // f_pow2)
            FK = self.F * self.k
            self.W = FK + 8            # [V(F*K) | w | pad] fused row
            self._tp_sizes.add(self.Mr)     # mesh: shard T rows over tp
            self.params, self.opt_state = self._make_state(
                _fused_state_init(self.optimizer, self.Mr, 1, FK, self.W,
                                  dtype), key, float(o.sigma))
            head = (self._loss_name, *self._opt_key,
                    (o.lambda0, o.lambda_w, o.lambda_v), self.F, self.k)
            # what the mesh shows decides which step a chip runs. Rows
            # alone dealt out (dp == 1): the one-chip step on each chip's
            # block (shard_map over tp), whose tail rank_rows picks from
            # the block and the optimizer as it does on one chip. A dp
            # axis (a gradient summed over replicas whose distinct rows
            # differ): GSPMD's cut of the dense step
            blocks = self.mesh if (
                self.mesh is not None and self.mesh.shape["dp"] == 1
                and self.mesh.shape["tp"] > 1) else None
            tail = (self.mesh is None or blocks is not None, blocks)
            self._step = _ffm_step_fused_cached(*head, False, False, *tail)
            self._step_fm = None if self.interaction == "pairs" else \
                _ffm_step_fused_cached(*head, True, False, *tail)
            self._step_fm_unit = None if self.interaction == "pairs" else \
                _ffm_step_fused_cached(*head, True, True, *tail)
            self._fused_score = _ffm_score_fused_cached(self.F, self.k)
            self._fused_score_fm = _ffm_score_fieldmajor_cached(self.F,
                                                                self.k)
        else:
            self.params = {
                "w0": jnp.zeros((), dtype),
                "w": jnp.zeros(self.dims, dtype),
                "V": (jax.random.normal(key, (self.dims, self.F, self.k)) *
                      float(o.sigma)).astype(dtype),
            }
            self.opt_state = {k: self.optimizer.init(v.shape)
                              for k, v in self.params.items()}
            if self.interaction == "fieldmajor":
                raise ValueError("-ffm_interaction fieldmajor needs the "
                                 "joint layout (-ffm_table joint, "
                                 "power-of-two -dims)")
            self._step = _ffm_step_cached(
                self._loss_name, *self._opt_key,
                (o.lambda0, o.lambda_w, o.lambda_v))
            self._step_fm = None
            self._step_fm_unit = None
            self.interaction = "pairs"

    def _apply_mesh(self, spec: str) -> None:
        if getattr(self, "layout", None) == "parts":
            self._apply_mesh_parts(spec)
            return
        super()._apply_mesh(spec)

    def _apply_mesh_parts(self, spec: str) -> None:
        """Shard the parts layout over a (dp, tp) mesh: field partitions
        over 'tp' (the shard boundary is a partition boundary, so slab
        gathers stay rank-local), batch over 'dp' with a G psum before the
        optimizer tail (ops.fm_pallas.make_parts_step_sharded). The fused
        single-chip kernel remains the mesh=None path."""
        from ..ops.fm_pallas import make_parts_step_sharded
        from ..ops.schedules import make_eta
        from ..parallel.mesh import make_mesh, parse_mesh_spec
        from ..utils.device import pallas_interpret
        o = self.opts
        dp, tp = parse_mesh_spec(spec)
        if self.F % tp:
            raise ValueError(f"-ffm_table parts: -fields {self.F} must be "
                             f"divisible by the tp axis ({tp})")
        B = int(o.mini_batch)
        Bd = B // dp
        if B % (dp * 128) or (Bd > 2048 and Bd % 2048):
            raise ValueError(f"-ffm_table parts: -mini_batch "
                             f"{o.mini_batch} must be a multiple of "
                             f"128*dp ({128 * dp}) and, when the per-rank "
                             f"batch exceeds 2048, of 2048*dp — each dp "
                             "rank feeds the kernel whole chunk tiles")
        self.mesh = make_mesh(dp=dp, tp=tp)
        eta_fn = make_eta(o.eta, o.eta0, o.total_steps, o.power_t)
        lamt = (o.lambda0, o.lambda_w, o.lambda_v)
        interp = pallas_interpret()
        self._step_fm = make_parts_step_sharded(
            self.loss, eta_fn, lamt, self.F, self.k, self.MRF, self.mesh,
            interpret=interp)
        self._step_fm_unit = make_parts_step_sharded(
            self.loss, eta_fn, lamt, self.F, self.k, self.MRF, self.mesh,
            unit_val=True, interpret=interp)
        self._tp_sizes.add(self.F * self.MRF * self.HP)
        self._reshard_state()

    def _batch_args(self, batch: SparseBatch) -> tuple:
        if batch.field is None:
            raise ValueError("train_ffm needs field ids; use "
                             "'field:index:value' features (ffm_features)")
        return (batch.field,)

    def _preprocess_batch(self, batch: SparseBatch) -> SparseBatch:
        batch = self._canonicalize_batch(batch)
        if self.layout == "parts" and batch.fieldmajor:
            batch = self._pad_parts_rows(batch)
        return batch

    def _preprocess_train_serial(self, batch: SparseBatch):
        # FFM prep has no cross-batch state (no elision latch: unit-ness
        # is decided per batch inside _canonicalize_batch) — everything
        # runs on the parallel leg, nothing on the serial one
        return batch

    def _preprocess_train_parallel(self, batch: SparseBatch):
        # packing lives on the TRAIN hook only: scoring shares
        # _preprocess_batch and consumes .idx/.val, which a PackedBatch
        # deliberately doesn't carry. Canonicalize + pack are pure
        # per-batch NumPy (GIL-releasing) — the heavy leg the
        # -ingest_workers pool shards.
        batch = self._preprocess_batch(batch)
        if (batch.fieldmajor and batch.val is None
                and self._pack_input_on() and self._step_fm_unit is not None
                and isinstance(batch.idx, np.ndarray)
                and self.dims <= (1 << 24)):
            return pack_unit_fieldmajor(batch)
        return batch

    def _picklable_prep(self):
        # the process-pool form of the leg above: same module functions,
        # parameterized by a plain dataclass instead of bound state
        return FFMPrep(
            F=self.F, canon=self._step_fm is not None,
            forced=self.interaction == "fieldmajor",
            parts=self.layout == "parts",
            parts_dp=(self.mesh.shape["dp"] if self.mesh is not None
                      else 1),
            pack=(self._pack_input_on() and self._step_fm_unit is not None
                  and self.dims <= (1 << 24)))

    _DEVICE_CACHE_MB = 2048      # HBM budget for the -iters replay cache

    # -- the on-disk packed shard cache (-shard_cache_dir, io.shard_cache) --
    def _prep_cache_config(self) -> dict:
        """The prep-config identity the shard cache keys on: everything
        that changes the canonical packed bytes a source row preps into —
        layout geometry AND label conversion. Batch size is deliberately
        absent (the cache is row-level; any bs re-slices the same
        records)."""
        o = self.opts
        return {"trainer": self.NAME, "record": 1, "dims": self.dims,
                "fields": self.F, "layout": self.layout,
                "interaction": self.interaction,
                "classification": bool(self.classification),
                "min_target": o.min_target, "max_target": o.max_target}

    def _packed_cache(self):
        """PackedShardCache when -shard_cache_dir is set AND this config's
        prep lands on the packed wire format (the cache stores exactly
        those bytes); None otherwise — dense layout, pairs-only
        interaction, mesh/mix (pack off), or dims past the 3-byte lane
        range all decline."""
        ckdir = self.opts.get("shard_cache_dir")
        if not ckdir or self.layout == "dense":
            return None
        if self._step_fm is None or self._step_fm_unit is None:
            return None
        if not self._pack_input_on() or self.dims > (1 << 24):
            return None
        from ..io.shard_cache import PackedShardCache
        return PackedShardCache(ckdir, self._prep_cache_config(),
                                F=self.F, name=self.NAME)

    def _cache_row_pad(self, B: int) -> int:
        """Allocated row count for a cached batch of ``B`` logical rows —
        the parts layout's kernel-grid padding (single-chip rule; the
        cache is off under -mesh), identity for joint."""
        return _parts_row_target(B) if self.layout == "parts" else B

    def _streamed_epoch(self, ds, bs, shuffle, seed, prefetch, writer,
                        order) -> None:
        """One base-loop epoch (prep pipeline -> megabatch stacking ->
        prefetch -> dispatch), optionally teeing every prepared
        PackedBatch into a shard-cache writer."""
        closers: list = []
        it = self._ingest_iter(ds.batches(bs, shuffle=shuffle, seed=seed),
                               closers)
        if writer is not None:
            it = _tee_into_writer(it, writer, order, bs)
        it = self._wrap_megabatch(it, prefetch=prefetch)
        if prefetch:
            it = self._wrap_prefetch(it, closers)
        try:
            for b in self._inputs(it):
                self._dispatch(b)
        finally:
            for c in reversed(closers):
                c()

    def _cached_epoch(self, shard, bs, order, prefetch) -> None:
        """One epoch served from the mmap'd shard cache: parse,
        canonicalize and pack never run — record gather + h2d + step is
        the whole host leg."""
        closers: list = []
        it = shard.batches(bs, order, stats=self.pipeline_stats,
                           pad_rows=self._cache_row_pad)
        it = self._wrap_megabatch(it, prefetch=prefetch)
        if prefetch:
            it = self._wrap_prefetch(it, closers)
        try:
            for b in self._inputs(it):
                self._dispatch(b)
        finally:
            for c in reversed(closers):
                c()

    def _fit_epochs(self, ds, epochs, bs, shuffle, prefetch, ckdir,
                    seed0: int = 42) -> None:
        """Multi-epoch fit with TWO replay caches.

        DEVICE-RESIDENT replay (round 4): the reference's -iters pattern
        re-reads the corpus every epoch; the round-3 disk replay did too —
        and every epoch re-paid the full h2d transfer. When
        the packed input path is active and the dataset fits the HBM
        budget, epoch 1 streams normally but RETAINS its staged device
        buffers; epochs >= 2 reshuffle with ONE on-device row gather
        (~26 ns/row — thousands of times cheaper than re-transferring) and
        run at near-kernel rate. Padded tail rows stay at the END of the
        replay matrix so per-batch validity remains a prefix (the packed
        step's nv-scalar contract).

        ON-DISK packed shard cache (round 6, -shard_cache_dir): the cold
        epoch additionally tees its prepared PackedBatches into a
        digest-keyed cache file; RESTARTS, repeat fits, and any epoch the
        HBM replay can't cover (over budget, -checkpoint_dir runs, CPU
        hosts) then mmap the prepared records and skip parse/canonicalize/
        pack entirely — shuffled or not, bit-exact vs the streamed path
        (warm epoch ep reuses the exact seed0+ep permutation). Both caches
        compose: a warm shard-cache epoch 1 still feeds the HBM retention
        for on-device epochs >= 2."""
        cache = self._packed_cache()
        if cache is None and (epochs <= 1 or ckdir or self.mesh is not None
                              or not self._pack_input_on()):
            return super()._fit_epochs(ds, epochs, bs, shuffle, prefetch,
                                       ckdir, seed0)
        if prefetch is None:
            prefetch = jax.default_backend() != "cpu"
        shard = writer = None
        if cache is not None:
            shard = cache.load(ds)
            if shard is None:
                writer = cache.writer(ds)   # None: uncacheable rows

        def order_for(ep):
            return (np.random.default_rng(seed0 + ep).permutation(len(ds))
                    if shuffle else np.arange(len(ds)))

        device_replay = (epochs > 1 and not ckdir and self.mesh is None
                         and self._pack_input_on())
        if not device_replay:
            # shard-cache orchestration for the configs HBM replay
            # excludes (single epoch, -checkpoint_dir): warm epochs serve
            # from the cache, the first cold epoch tees into the writer
            for ep in range(epochs):
                if shard is not None:
                    self._cached_epoch(shard, bs, order_for(ep), prefetch)
                else:
                    self._streamed_epoch(ds, bs, shuffle, seed0 + ep,
                                         prefetch,
                                         writer if ep == 0 else None,
                                         order_for(ep))
                    if ep == 0 and writer is not None:
                        shard = writer.commit()   # None: build fell open
                        writer = None
                if ckdir:
                    self._save_epoch_bundle(ckdir, ep + 1)
            return

        # ---- epoch 1: streamed (or shard-cache-served) epoch, retaining
        # staged buffers for the on-device replay of epochs >= 2 ----
        closers: list = []
        if shard is not None:
            it = shard.batches(bs, order_for(0), stats=self.pipeline_stats,
                               pad_rows=self._cache_row_pad)
        else:
            it = self._ingest_iter(
                ds.batches(bs, shuffle=shuffle, seed=seed0), closers)
            if writer is not None:
                it = _tee_into_writer(it, writer, order_for(0), bs)
        if prefetch:
            it = self._wrap_prefetch(it, closers)
        try:
            staged = self._dispatch_retaining(it)
        finally:
            for c in reversed(closers):
                c()
        if writer is not None:
            shard = writer.commit()
        mat = self._staged_matrix(staged)
        del staged           # free the per-batch buffers BEFORE replay:
        # peak device memory stays ~M (+Mp), not M + the staged copies
        if mat is None:
            # HBM replay unsafe or over budget: warm shard-cache epochs
            # when available (exactly the -iters-over-budget case the disk
            # cache exists for), else re-stream on the uninterrupted
            # seed schedule
            for ep in range(1, epochs):
                if shard is not None:
                    self._cached_epoch(shard, bs, order_for(ep), prefetch)
                else:
                    super()._fit_epochs(ds, 1, bs, shuffle, prefetch, None,
                                        seed0=seed0 + ep)
            return
        if mat == ():
            return                       # empty dataset, nothing to replay
        self._replay_epochs(mat, epochs - 1, shuffle)

    def _dispatch_retaining(self, it) -> Optional[list]:
        """Dispatch every batch from `it`, retaining PackedBatches for
        on-device replay. Returns the staged list, or None when replay is
        unsafe: an unpacked batch appeared, or the cumulative staged
        bytes exceeded the admission budget (budget/3 of
        _DEVICE_CACHE_MB: construction transiently holds the staged
        buffers + the rows_m copies + M, and shuffled epochs hold M + Mp
        — the cap bounds the PEAK, not just M)."""
        budget = (self._DEVICE_CACHE_MB << 20) // 3
        staged: list = []
        cache_on = True
        cached_bytes = 0
        for b in self._inputs(it):
            if cache_on and isinstance(b, PackedBatch):
                cached_bytes += int(b.buf.size)
                if cached_bytes > budget:
                    # over budget mid-epoch: free the cache NOW (the
                    # streamed path never retains buffers) and finish
                    # the epoch + remaining epochs streamed
                    staged.clear()
                    cache_on = False
                else:
                    staged.append(b)
            elif cache_on:
                # a batch failed the pack conditions: replay unsafe
                staged.clear()
                cache_on = False
            self._dispatch(b)
        return staged if cache_on else None

    def _staged_matrix(self, staged):
        """Collapse retained PackedBatches into the replay matrix.
        Returns (M, n_real, B, L), () for an empty epoch, or None when
        replay is unsafe (mixed shapes / staged is None).

        Rows matrix has REAL rows first, padding rows last (prefix
        validity per tail batch); idx bytes and label bytes re-packed
        row-major so a row gather moves one contiguous 3L+4 record."""
        if staged is None:
            return None
        if not staged:
            return ()
        B, L = staged[0].B, staged[0].L
        if any(s.B != B or s.L != L for s in staged):
            return None
        mats = []
        n_real = 0
        pad_rows = []
        for s in staged:
            nv = s.B if s.n_valid is None else s.n_valid
            ni = s.B * L * 3
            rows_m = jnp.concatenate(
                [s.buf[:ni].reshape(s.B, L * 3),
                 s.buf[ni:].reshape(s.B, 4)], axis=1)     # [B, rb]
            mats.append(rows_m[:nv])
            n_real += nv
            if nv < s.B:
                pad_rows.append(rows_m[nv:])
        M = jnp.concatenate(mats + pad_rows)              # [N_total, rb]
        return (M, n_real, B, L)

    def _replay_epochs(self, mat, n_epochs: int, shuffle: bool,
                       seed: int = 43) -> None:
        """Run `n_epochs` epochs from the device-resident replay matrix:
        per epoch ONE on-device row gather (~26 ns/row) reshuffles; no
        bytes re-cross the link."""
        M, n_real, B, L = mat
        n_total = M.shape[0]
        rng = np.random.default_rng(seed)
        for ep in range(n_epochs):
            if shuffle:
                perm = rng.permutation(n_real)
                if n_total > n_real:
                    perm = np.concatenate(
                        [perm, np.arange(n_real, n_total)])
                Mp = M[jnp.asarray(perm.astype(np.int32))]
            else:
                Mp = M
            for s0 in range(0, n_total, B):
                rows_b = Mp[s0:s0 + B]
                buf = jnp.concatenate(
                    [rows_b[:, :L * 3].reshape(-1),
                     rows_b[:, L * 3:].reshape(-1)])
                nv = min(B, max(0, n_real - s0))
                if nv == 0:
                    break
                self._dispatch(PackedBatch(buf, B, L, n_valid=nv))

    def fit_stream(self, batches, *, convert_labels: bool = True,
                   epochs: int = 1, replay_shuffle: bool = True,
                   resume: bool = False, on_dispatch=None) -> "FFMTrainer":
        """Out-of-core epochs with the device replay cache (VERDICT r4
        weak #5: -iters over Parquet re-paid the link every epoch).

        `batches` may be an iterable (single epoch, base behavior) or a
        zero-arg FACTORY returning one epoch's stream — with epochs > 1
        the factory form lets failed replay fall open to re-streaming.
        When the packed input path is active and the epoch fits the HBM
        budget, epoch 1 streams normally while RETAINING its staged
        device buffers; epochs >= 2 replay on device exactly like
        fit(-iters) does (same admission, same fail-open).

        ``resume`` (docs/RELIABILITY.md) is the base single-stream
        contract; the multi-epoch replay form has no checkpointed stream
        position to skip into, so the combination is rejected. So is
        ``on_dispatch`` there: a replayed epoch has no staged input to
        number.

        Observed-pair tracking (``_note_batch``) runs on a tracker thread
        for the call's duration; it is drained and joined before this
        returns or raises, and a fault it met is raised from here."""
        with self._pairs.streaming(self._tracer):
            return self._fit_stream(
                batches, convert_labels=convert_labels, epochs=epochs,
                replay_shuffle=replay_shuffle, resume=resume,
                on_dispatch=on_dispatch)

    def _fit_stream(self, batches, *, convert_labels, epochs, replay_shuffle,
                    resume, on_dispatch) -> "FFMTrainer":
        if epochs <= 1:
            it = batches() if callable(batches) else batches
            return super().fit_stream(it, convert_labels=convert_labels,
                                      resume=resume, on_dispatch=on_dispatch)
        if resume or on_dispatch is not None:
            raise ValueError(
                "fit_stream(resume=True) and fit_stream(on_dispatch=...) "
                "need the single-stream form (epochs=1); the epochs>1 "
                "replay path has no stream position to resume into and "
                "no staged inputs to number")
        if not callable(batches):
            raise ValueError(
                "fit_stream(epochs>1) needs a zero-arg factory returning "
                "one epoch's batch stream, e.g. "
                "lambda: stream.batches(B, epochs=1)")
        if self.mesh is not None or not self._pack_input_on():
            for _ in range(epochs):
                super().fit_stream(batches(),
                                   convert_labels=convert_labels,
                                   _emit_done=False)
            self._emit_train_done()    # ONE record for the whole run
            return self

        from ..io.pipeline import PipelineStats
        self.pipeline_stats = PipelineStats()
        closers: list = []
        it = self._ingest_iter(self._source_side(batches(), convert_labels),
                               closers)
        prefetch = jax.default_backend() != "cpu"
        if prefetch:
            it = self._wrap_prefetch(it, closers)
        try:
            staged = self._dispatch_retaining(it)
        finally:
            for c in reversed(closers):
                c()
        mat = self._staged_matrix(staged)
        del staged           # peak device memory ~M (+Mp), not M + copies
        if mat == ():
            self._emit_train_done()
            return self
        if mat is None:                      # fail-open: re-stream
            for _ in range(epochs - 1):
                super().fit_stream(batches(),
                                   convert_labels=convert_labels,
                                   _emit_done=False)
            self._emit_train_done()
            return self
        self._replay_epochs(mat, epochs - 1, replay_shuffle)
        # the packed replay path never re-enters base fit_stream after
        # epoch 1, so the run's single train_done is emitted here
        self._emit_train_done()
        return self

    def _pack_input_on(self) -> bool:
        # the mesh/mixer exclusions outrank an explicit "on": _shard_batch
        # and MixClient.touch consume .idx, which packed buffers don't have
        if self.mesh is not None or self._mixer is not None:
            return False
        mode = str(self.opts.pack_input)
        if mode == "on":
            return True
        if mode == "off":
            return False
        import jax
        return jax.default_backend() != "cpu"

    def _packed_step(self, B: int, L: int):
        # module-cached on (base step, B, L): the base steps are
        # themselves config-cached, so same-config trainers share the
        # packed wrapper's compile too (an instance-keyed dict here undid
        # the cross-instance sharing on the flagship packed path)
        return _packed_wrap_cached(self._step_fm_unit, B, L)

    def _pad_parts_rows(self, batch: SparseBatch) -> SparseBatch:
        """Parts-layout kernel-grid row padding (see _ffm_pad_parts — the
        module function is the single implementation, shared with the
        picklable process-pool prep)."""
        return _ffm_pad_parts(
            batch, self.mesh.shape["dp"] if self.mesh is not None else 1)

    def _canonicalize_batch(self, batch: SparseBatch) -> SparseBatch:
        """Field-major canonicalization (see _ffm_canonicalize — the
        module function is the single implementation, shared with the
        picklable process-pool prep)."""
        return _ffm_canonicalize(batch, self.F, self._step_fm is not None,
                                 self.interaction == "fieldmajor")

    # -- fused multi-step dispatch (-steps_per_dispatch) ---------------------
    def _supports_megastep(self) -> bool:
        # the FFM dispatch picks among THREE steps per batch kind (pairs /
        # fieldmajor / fieldmajor-unit+packed); fusion is on when any of
        # them is scannable — a window of a non-scannable kind (only
        # possible under the mesh-sharded parts steps, which also null
        # self._step) simply never forms. parts layout keeps
        # self._step = None, so the base check alone would disable the
        # flagship path.
        return any(
            getattr(s, "core", None) is not None
            for s in (self._step, self._step_fm, self._step_fm_unit))

    def _mega_field(self, mb):
        # pairs-path megabatches carry stacked per-step field arrays; the
        # pairs core takes them as its trailing batch argument
        return mb.field

    def _train_megabatch(self, mb):
        """Route one stacked window to the megastep of the SAME step the
        K=1 dispatch would pick for its kind: PackedMegaBatch -> the
        packed scan wrapper over the unit-val field-major core (one uint8
        buffer, per-step unpack on device); field-major MegaBatch -> the
        field-major (unit or real-valued) core; anything else -> the base
        generic megastep over the pairs core."""
        from ..io.sparse import PackedMegaBatch
        from ..ops.scan import megastep_for
        if isinstance(mb, PackedMegaBatch):
            nv = (mb.nv_dev if mb.nv_dev is not None
                  else jnp.asarray(mb.nv))
            mega = _packed_megawrap_cached(self._step_fm_unit, mb.B, mb.L)
            self.params, self.opt_state, losses, *stats = mega(
                self.params, self.opt_state, float(self._t), mb.buf, nv)
            self._stats_pending += stats
            return losses
        if mb.fieldmajor and self._step_fm is not None:
            step = self._step_fm_unit if mb.val is None else self._step_fm
            mega = megastep_for(step)
            nv = (mb.nv_dev if mb.nv_dev is not None
                  else jnp.asarray(mb.nv))
            self.params, self.opt_state, losses, *stats = mega(
                self.params, self.opt_state, float(self._t), nv, mb.idx,
                mb.val, mb.label, None, None)
            self._stats_pending += stats
            return losses
        return super()._train_megabatch(mb)

    def _train_batch(self, batch: SparseBatch) -> float:
        # a joint step returns its tail's stats beside the loss, a parts
        # step the loss alone
        if isinstance(batch, PackedBatch):
            nv = batch.B if batch.n_valid is None else batch.n_valid
            out = self._packed_step(batch.B, batch.L)(
                self.params, self.opt_state, float(self._t), batch.buf,
                np.int32(nv))
        elif batch.fieldmajor and self._step_fm is not None:
            if batch.val is None:
                out = self._step_fm_unit(
                    self.params, self.opt_state, float(self._t), batch.idx,
                    batch.label, batch.row_mask)
            else:
                out = self._step_fm(
                    self.params, self.opt_state, float(self._t), batch.idx,
                    batch.val, batch.label, batch.row_mask)
        else:
            return super()._train_batch(batch)
        self.params, self.opt_state, loss_sum, *stats = out
        self._stats_pending += stats
        return loss_sum

    def _parse_row(self, features):
        """Parse "field:index:value" (value defaults to 1)."""
        if (isinstance(features, tuple) and len(features) == 3):
            return features           # (idx, val, field) pre-parsed
        idx: List[int] = []
        val: List[float] = []
        fld: List[int] = []
        for f in features:
            if f is None or f == "":
                continue
            parts = str(f).split(":")
            if len(parts) == 2:
                fstr, istr, vstr = parts[0], parts[1], "1"
            elif len(parts) >= 3:
                fstr, istr, vstr = parts[0], parts[1], ":".join(parts[2:])
            else:
                raise ValueError(f"FFM feature needs field:index[:value]: {f!r}")
            try:
                fi = int(fstr)
            except ValueError:
                fi = mhash(fstr, self.F) - 1
            try:
                ii = int(istr)
            except ValueError:
                ii = mhash(istr, self.dims - 1)
                self._names.setdefault(ii, istr)
            idx.append(ii)
            val.append(float(vstr))
            fld.append(fi % self.F)
        return (np.asarray(idx, np.int32), np.asarray(val, np.float32),
                np.asarray(fld, np.int32))

    def process(self, features, label) -> None:
        idx, val, fld = self._parse_row(features)
        self._buf_rows.append((idx, val, fld))
        self._buf_labels.append(self._convert_label(label))
        if len(self._buf_rows) >= int(self.opts.mini_batch):
            self._flush()

    def _flush_chunk(self, rows, labels) -> None:
        B = int(self.opts.mini_batch)
        L = self._pow2_len(max(1, max(len(r[0]) for r in rows)))
        idx = np.zeros((B, L), np.int32)
        val = np.zeros((B, L), np.float32)
        fld = np.zeros((B, L), np.int32)
        lab = np.zeros(B, np.float32)
        for b, (i, v, f) in enumerate(rows):
            idx[b, :len(i)] = i
            val[b, :len(v)] = v
            fld[b, :len(f)] = f
            lab[b] = labels[b]
        if self.layout == "joint":         # joint emission needs seen pairs
            self._pairs.add(np.concatenate(
                [i.astype(np.int64) * self.F + f for i, _, f in rows]))
        nv = len(rows)
        self._dispatch(self._preprocess_batch(
            SparseBatch(idx, val, lab, fld, n_valid=nv if nv < B else None)))

    def _score_batch(self, batch: SparseBatch) -> np.ndarray:
        p = self.params
        if self.layout == "parts":
            B0 = batch.batch_size
            if not batch.fieldmajor:
                batch = self._preprocess_batch(batch)   # forced; may raise
            out = np.asarray(self._fused_score_fm(
                p["w0"], p["T2"], jnp.asarray(batch.idx),
                None if batch.val is None else jnp.asarray(batch.val)))
            return out[:B0]            # drop kernel-grid padding rows
        if self.layout == "joint":
            if not batch.fieldmajor and self._step_fm is not None:
                # scoring fast path; unlike training, a row canonicalization
                # cannot handle (forced mode raises) just keeps the general
                # pairs scorer — prediction must accept any row
                try:
                    batch = self._preprocess_batch(batch)
                except ValueError:
                    pass
            if batch.fieldmajor:
                return np.asarray(self._fused_score_fm(
                    p["w0"], p["T"], jnp.asarray(batch.idx),
                    None if batch.val is None else jnp.asarray(batch.val)))
            return np.asarray(self._fused_score(
                p["w0"], p["T"], jnp.asarray(batch.idx),
                jnp.asarray(batch.val), jnp.asarray(batch.field)))
        return np.asarray(ffm_score(p["w0"], p["w"], p["V"],
                                    batch.idx, batch.val, batch.field))

    def _init_parser(self) -> None:
        # make_parser support: FFM's _parse_row hashes field names mod F
        self.F = int(self.opts.fields)

    def serving_tables(self):
        """Arena extraction (io.weight_arena): joint keeps the fused
        row-hashed table (V block + the linear-weight column, pad lanes
        dropped); dense flattens the field cube to the pair-flat [N*F, K]
        the general scorer gathers. The ``parts`` layout's kernel-grid
        geometry has no host-gather mapping — unsupported (the engine
        keeps the bundle path; docs/PERFORMANCE.md "when NOT to
        quantize")."""
        from ..io.weight_arena import ArenaUnsupported
        p = self.params
        cls = bool(self.classification)
        w0 = float(np.asarray(p["w0"], np.float32))
        if self.layout == "joint":
            T = np.asarray(p["T"].astype(jnp.float32))
            return ({"family": "ffm_joint", "F": self.F, "k": self.k,
                     "Mr": int(T.shape[0]), "w0": w0,
                     "classification": cls},
                    {"T": np.ascontiguousarray(
                        T[:, :self.F * self.k + 1])})
        if self.layout == "dense":
            V = np.asarray(p["V"].astype(jnp.float32))
            return ({"family": "ffm_dense", "F": self.F, "k": self.k,
                     "w0": w0, "classification": cls},
                    {"w": np.asarray(p["w"].astype(jnp.float32)),
                     "V2": np.ascontiguousarray(
                         V.reshape(-1, self.k))})
        raise ArenaUnsupported(
            f"-ffm_table {self.layout} has no weight-arena mapping")

    def _wants_fit_ds(self) -> bool:
        # emission needs observed pairs
        return self.layout in ("joint", "parts")

    def _note_batch(self, batch) -> None:
        """Streaming path (fit_stream): record observed (feature, field)
        pairs so joint-layout model emission keeps names/fields. Inside a
        fit_stream this only hands the batch's host arrays to the tracker
        thread (models/ffm_pairs.py); the unique and the merge run there."""
        if self.layout not in ("joint", "parts") or batch.field is None:
            return
        self._pairs.note(batch.idx, batch.field, batch.val)

    def _observed_pairs(self):
        """Unique (feature_id, field) pairs seen in training as two sorted
        arrays (ii, ff), merged from the streaming path's tracked keys
        (joined first: every batch handed over is in) and the columnar
        dataset — all vectorized (no per-pair Python)."""
        uniq = self._pairs.keys()          # sorted and unique already
        seen = len(uniq) > 0
        ds = self._fit_ds
        if ds is not None and ds.fields is not None:
            seen = True
            uniq = np.unique(np.concatenate(
                [uniq, ds.indices.astype(np.int64) * self.F
                 + ds.fields.astype(np.int64)]))
        if not seen:
            return None
        ii, ff = np.divmod(uniq, self.F)
        return ii.astype(np.int32), ff.astype(np.int32)

    def _rows_for(self, keys: np.ndarray, fields: np.ndarray = None
                  ) -> np.ndarray:
        """Host-side fused-table row ids for feature ids (joint layout) or
        (feature, own-field) pairs (parts layout)."""
        if self.layout == "parts":
            from ..ops.fm_pallas import parts_row_hash
            return np.asarray(parts_row_hash(
                jnp.asarray(keys, jnp.int32),
                jnp.asarray(fields, jnp.int32), self.MRF))
        return np.asarray(ffm_row_hash(jnp.asarray(keys, jnp.int32),
                                       self.Mr))

    def model_rows(self):
        """(feature, field, Wi, Vi[k]) rows — the FFMPredictionModel surface.

        Joint layout: rows are enumerated from the observed (feature, field)
        pairs; each feature's weight and per-field vectors are read from its
        hashed fused row. Colliding features intentionally report the same
        shared state (hashing-trick semantics). If no pairs were observed
        (e.g. a bundle-restored trainer that never saw data), falls back to
        row-keyed "vrow:<id>:<field>" rows."""
        yield ("0", -1, float(np.asarray(self.params["w0"])), None)
        if self.layout == "dense":
            w = np.asarray(self.params["w"].astype(jnp.float32))
            V = np.asarray(self.params["V"].astype(jnp.float32))
            touched = np.nonzero(np.abs(V).sum((1, 2)) > 0)[0]
            for i in touched:
                if i == 0:
                    continue
                name = self._names.get(int(i), str(int(i)))
                for f in range(self.F):
                    if np.abs(V[i, f]).sum() > 0:
                        yield (name, f, float(w[i]), V[i, f].tolist())
            return
        FK = self.F * self.k
        if self.layout == "parts":
            T = np.asarray(self.params["T2"].astype(jnp.float32)).reshape(
                self.F * self.MRF, self.Wp)
        else:
            T = np.asarray(self.params["T"].astype(jnp.float32))
        pairs = self._observed_pairs()
        if pairs is None:
            live = np.nonzero(np.abs(T[:, :FK]).sum(-1) > 0)[0]
            for r in live:
                for f in range(self.F):
                    vec = T[r, f * self.k:(f + 1) * self.k]
                    if np.abs(vec).sum() > 0:
                        yield (f"vrow:{int(r)}", f, float(T[r, FK]),
                               vec.tolist())
            return
        ii, ff = pairs
        rr = self._rows_for(ii, ff)
        for i, f, r in zip(ii.tolist(), ff.tolist(), rr.tolist()):
            if i == 0:
                continue
            name = self._names.get(i, str(i))
            yield (name, f, float(T[r, FK]),
                   T[r, f * self.k:(f + 1) * self.k].tolist())

    # -- sparse weight access for the mix client (joint layout) -------------
    def _weight_table(self):
        if self.layout in ("joint", "parts"):
            return None                # w lives inside T; use overrides
        return super()._weight_table()

    def _get_weights_at(self, keys: np.ndarray) -> np.ndarray:
        if self.layout == "parts":
            raise ValueError("MIX weight exchange is not supported with "
                             "-ffm_table parts; use -ffm_table joint")
        if self.layout != "joint":
            return super()._get_weights_at(keys)
        FK = self.F * self.k
        rr = jnp.asarray(self._rows_for(np.asarray(keys)))
        return np.asarray(self.params["T"][rr, FK], np.float32)

    def _set_weights_at(self, keys: np.ndarray, vals: np.ndarray) -> None:
        if self.layout == "parts":
            raise ValueError("MIX weight exchange is not supported with "
                             "-ffm_table parts; use -ffm_table joint")
        if self.layout != "joint":
            return super()._set_weights_at(keys, vals)
        FK = self.F * self.k
        rr = jnp.asarray(self._rows_for(np.asarray(keys)))
        T = self.params["T"]
        self.params["T"] = T.at[rr, FK].set(jnp.asarray(vals, T.dtype))

    def _finalized_weights(self) -> np.ndarray:
        if self.layout == "parts":
            FK = self.F * self.k
            Tl = self.params["T2"].reshape(self.F * self.MRF, self.Wp)
            return np.asarray(Tl[:, FK].astype(jnp.float32))
        if self.layout != "joint":
            return super()._finalized_weights()
        FK = self.F * self.k
        return np.asarray(self.params["T"][:, FK].astype(jnp.float32))

    def _load_weights(self, w: np.ndarray) -> None:
        if self.layout == "parts":
            FK = self.F * self.k
            T2 = self.params["T2"]
            Tl = T2.reshape(self.F * self.MRF, self.Wp)
            Tl = Tl.at[:, FK].set(jnp.asarray(w, T2.dtype))
            self.params["T2"] = Tl.reshape(T2.shape)
            return
        if self.layout != "joint":
            return super()._load_weights(w)
        FK = self.F * self.k
        T = self.params["T"]
        self.params["T"] = T.at[:, FK].set(jnp.asarray(w, T.dtype))


# --- standalone predict kernels (the UDAF/UDF reassembly path) -------------

def fm_predict(w0, w, V, idx, val) -> np.ndarray:
    """SQL: fm_predict — reference hivemall.fm.FMPredictGenericUDAF."""
    return np.asarray(fm_score(jnp.asarray(w0), jnp.asarray(w),
                               jnp.asarray(V), jnp.asarray(idx),
                               jnp.asarray(val)))


def ffm_predict(w0, w, V, idx, val, field) -> np.ndarray:
    """SQL: ffm_predict — reference hivemall.fm.FFMPredictUDF."""
    return np.asarray(ffm_score(jnp.asarray(w0), jnp.asarray(w),
                                jnp.asarray(V), jnp.asarray(idx),
                                jnp.asarray(val), jnp.asarray(field)))
