"""General linear trainers — train_classifier / train_regressor and the
historical logistic-regression family.

Reference classes (SURVEY.md §3.3, §3.5):
  - hivemall.classifier.GeneralClassifierUDTF  (train_classifier) [B]
  - hivemall.regression.GeneralRegressorUDTF   (train_regressor)  [B]
  - hivemall.regression.LogressUDTF            (logress / train_logregr)
  - hivemall.regression.AdaGradUDTF            (train_adagrad_regr)
  - hivemall.regression.AdaDeltaUDTF           (train_adadelta_regr)

Pluggable loss x optimizer x regularization over a dense hashed weight table;
one jitted step per minibatch (ops.linear). bf16 storage via -halffloat is the
HalfFloat analog (SURVEY.md §3.20).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..io.sparse import SparseBatch, SparseDataset
from ..ops.linear import make_linear_predict, make_linear_step
from ..ops.losses import get_loss
from ..ops.optimizers import make_optimizer_cached
from .base import (LearnerBase, sigmoid_np as _sigmoid,
                   weight_table_view)

__all__ = ["GeneralClassifier", "GeneralRegressor", "LogressTrainer",
           "AdaGradLogisticTrainer", "AdaDeltaLogisticTrainer"]



# config-cached step/optimizer builders (round 4 — see models/fm.py: a
# fresh jitted closure per trainer instance re-traces/compiles for every
# identical config; these are pure functions of the keyed options).
# instrument_factory records every cache MISS (a fresh closure actually
# built) into the obs devprof ledger — docs/OBSERVABILITY.md "Training
# profiling"
from functools import lru_cache as _lru_cache

from ..obs.devprof import instrument_factory as _instrument


@_instrument("linear", "step")
@_lru_cache(maxsize=128)
def _linear_step_cached(loss_name, opt_name, eta_scheme, eta0, total_steps,
                        power_t, reg, lam, l1_ratio, distinct=True):
    return make_linear_step(
        get_loss(loss_name),
        make_optimizer_cached(opt_name, eta_scheme, eta0,
                              total_steps, power_t, reg, lam, l1_ratio),
        distinct)


@_lru_cache(maxsize=128)
def _linear_state_init(optimizer, dims, dtype):
    """The zero table and the optimizer's zero state as ONE function of no
    arguments, the same object for the same configuration:
    `LearnerBase._make_state` jits it, so a table of 2^28 entries is born
    on the chip (or on the mesh, in its shards) and never on the host."""
    def init():
        return jnp.zeros(dims, dtype), optimizer.init(dims)
    return init


@_instrument("linear", "predict")
@_lru_cache(maxsize=1)
def _linear_predict_cached():
    return make_linear_predict()

class _LinearLearner(LearnerBase):
    """Shared machinery for dense-table linear trainers. The [dims] weight
    table is ``params``, the optimizer's co-shaped arrays ``opt_state``."""
    UNIT_VAL_ELISION = True      # ops.linear.make_linear_step takes val=None
    # the table under its older name; a write is one from outside
    w = property(weight_table_view.fget,
                 lambda self, table: self._store_weight_table(table))

    FIXED_LOSS: Optional[str] = None       # set by historical subclasses
    FIXED_OPT: Optional[str] = None
    ZERO_ONE_LABELS = False                # logress-style 0/1 labels

    def _init_state(self) -> None:
        o = self.opts
        self.loss = get_loss(self.FIXED_LOSS or o.loss)
        if self.CLASSIFICATION and not self.loss.for_classification:
            raise ValueError(f"loss {self.loss.name} is regression-only")
        opt_name = str(self.FIXED_OPT or o.opt)
        loss_name = str(self.FIXED_LOSS or o.loss)
        opt_key = (opt_name, str(o.eta), float(o.eta0), o.total_steps,
                   o.power_t, str(o.reg), o["lambda"], o.l1_ratio)
        self.optimizer = make_optimizer_cached(*opt_key)
        dtype = jnp.bfloat16 if o.halffloat else jnp.float32
        self.params, self.opt_state = self._make_state(
            _linear_state_init(self.optimizer, self.dims, dtype))
        # the distinct-slot step where the optimizer builds w from its
        # state (ops/linear.py), on one chip: a -mesh keeps GSPMD's cut of
        # the dense step. The dense step also takes the one dispatch after
        # w was written from outside (_note_w_written): the distinct one
        # holds w to be the optimizer's weight of the state, or the zeros
        # the table is born with before the first step
        self._step = _linear_step_cached(
            loss_name, *opt_key,
            self.mesh is None and self.optimizer.weight_of is not None)
        self._dense_step = _linear_step_cached(loss_name, *opt_key, False)
        self._w_written = False
        self._predict = _linear_predict_cached()

    def _note_w_written(self) -> None:
        """w was written from outside (a warm start, MIX, a restore): the
        next dispatch's margin reads it as stored, through the dense step,
        after which w is the optimizer's weight of the state again."""
        self._w_written = self._step is not self._dense_step

    def _restore_arrays(self, tree) -> None:
        super()._restore_arrays(tree)
        self._note_w_written()

    def _scan_step(self):
        return self._dense_step if self._w_written else self._step

    def _convert_label(self, label: float) -> float:
        if self.ZERO_ONE_LABELS:
            # logress semantics: float target in [0,1]; map to ±1 margin space
            return 1.0 if float(label) > 0.5 else -1.0
        return super()._convert_label(label)

    def _train_batch(self, batch: SparseBatch) -> float:
        # the distinct-slot step returns its counts beside the loss
        self.params, self.opt_state, loss_sum, *stats = self._scan_step()(
            self.params, self.opt_state, float(self._t),
            batch.idx, batch.val, batch.label, batch.row_mask)
        self._stats_pending += stats
        self._w_written = False
        return loss_sum

    def _train_megabatch(self, mb):
        losses = super()._train_megabatch(mb)
        self._w_written = False
        return losses

    def _finalize_device(self):
        """Optimizer-finalized weights as a DEVICE array — the one
        finalization expression; _finalized_weights and the sharded
        margin fn must never diverge (the online/offline bit-match
        hangs on it)."""
        return self.optimizer.finalize(self.params.astype(jnp.float32),
                                       self.opt_state)

    def _finalized_weights(self) -> np.ndarray:
        return np.asarray(self._finalize_device())

    def _load_weights(self, w: np.ndarray) -> None:
        self.params = jnp.asarray(w, self.params.dtype)
        self._note_w_written()

    def _store_weight_table(self, t) -> None:
        self.params = t
        self._note_w_written()

    # -- scoring (the predict-is-a-join path, SURVEY.md §4.2) ---------------
    def _make_margin_fn(self):
        # optimizer finalization (RDA truncation etc.) captured ONCE per
        # scorer — the serve engine swaps scorers per model version, the
        # offline path builds one per decision_function call
        if self.mesh is not None:
            # GSPMD-sharded scorer (serving tables too big for one chip):
            # finalize on device and keep the weight table tp-sharded —
            # np round-tripping here would gather the whole dims-sized
            # table onto one device and un-shard every predict
            import jax
            w = self._finalize_device()
            w = jax.device_put(w, self._state_sharding(w))
        else:
            w = jnp.asarray(self._finalized_weights())
        predict = self._predict
        return lambda b: predict(w, b.idx, b.val)

    def decision_function(self, ds: SparseDataset) -> np.ndarray:
        return self._score_dataset(ds)

    def predict_proba(self, ds: SparseDataset) -> np.ndarray:
        return _sigmoid(self.decision_function(ds))

    def serving_tables(self):
        """Arena extraction (io.weight_arena): the ONE finalized f32
        inference table — optimizer finalization (RDA truncation etc.)
        baked in, exactly what _make_margin_fn captures."""
        meta = {"family": "linear", "w0": 0.0,
                "classification": bool(self.CLASSIFICATION)}
        return meta, {"w": np.asarray(self._finalized_weights(),
                                      np.float32)}


class GeneralClassifier(_LinearLearner):
    """SQL: train_classifier — reference hivemall.classifier.GeneralClassifierUDTF."""
    NAME = "train_classifier"
    CLASSIFICATION = True
    DEFAULT_LOSS = "hingeloss"


class GeneralRegressor(_LinearLearner):
    """SQL: train_regressor — reference hivemall.regression.GeneralRegressorUDTF."""
    NAME = "train_regressor"
    CLASSIFICATION = False
    DEFAULT_LOSS = "squaredloss"


class LogressTrainer(_LinearLearner):
    """SQL: logress / train_logregr — reference hivemall.regression.LogressUDTF.
    Logistic regression by SGD, the historically canonical Hivemall example."""
    NAME = "train_logregr"
    CLASSIFICATION = True
    DEFAULT_LOSS = "logloss"
    FIXED_LOSS = "logloss"
    FIXED_OPT = "sgd"
    ZERO_ONE_LABELS = True

    @classmethod
    def spec(cls):
        s = super().spec()
        for o in s.options:        # logress default regularization is none
            if o.name == "reg":
                o.default = "no"
        return s


class AdaGradLogisticTrainer(LogressTrainer):
    """SQL: train_adagrad_regr — reference hivemall.regression.AdaGradUDTF."""
    NAME = "train_adagrad_regr"
    FIXED_OPT = "adagrad"


class AdaDeltaLogisticTrainer(LogressTrainer):
    """SQL: train_adadelta_regr — reference hivemall.regression.AdaDeltaUDTF."""
    NAME = "train_adadelta_regr"
    FIXED_OPT = "adadelta"
