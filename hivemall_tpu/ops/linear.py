"""Linear-model minibatch kernels: sparse dot forward + scatter-add update.

This is the TPU replacement for the reference's per-row hot path
(SURVEY.md §4.1: parse -> sparse dot -> dloss -> per-feature Optimizer.update):
one jitted call takes a padded (idx, val) minibatch, computes margins with a
gather, scatter-adds the per-row gradients into a dense [N] gradient, and runs
the optimizer's elementwise table update. Gradients accumulate by SUM within
the batch (gradient accumulation of the reference's per-row steps, one
optimizer-state advance per batch — the semantic delta vs strict per-row
sequential updates is documented in SURVEY.md §8 "hard parts").

Padding convention: (idx=0, val=0) slots contribute zero to margin and
gradient. Slot 0 doubles as the ``add_bias`` feature ("0:1.0") — a real bias
row has val=1 there, so it trains; padding has val=0, so it doesn't.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .losses import Loss
from .optimizers import Optimizer
from .scan import scannable

__all__ = ["make_linear_step", "linear_margin", "make_linear_predict"]


def linear_margin(w: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray
                  ) -> jnp.ndarray:
    """margin[b] = sum_l w[idx[b,l]] * val[b,l] — batched sparse dot."""
    return (w[idx].astype(jnp.float32) * val).sum(axis=-1)


def make_linear_step(loss: Loss, optimizer: Optimizer) -> Callable:
    """Build the jitted train step: (w, opt_state, t, batch) -> updated."""

    # the pure scannable core: the K=1 path jits it directly (donation
    # lets XLA update the weight/accumulator tables in place instead of
    # copying them every minibatch — O(dims) tables; the copy, not the
    # math, dominates at -dims 2^24) and -steps_per_dispatch > 1 runs the
    # SAME function as a lax.scan body (ops.scan.make_megastep) with the
    # state threaded through the donated scan carry. The phases carry the
    # scopes ops/fm.py's steps carry (ops/scan.py SCOPES): hm.gather the
    # margin's gather, hm.grad loss and dloss, hm.scatter the dense
    # gradient's zeroing and scatter-add, hm.update the optimizer's pass
    def core(w, opt_state, t, idx, val, label, row_mask):
        wf = w.astype(jnp.float32)
        with jax.named_scope("hm.gather"):
            if val is None:
                # unit-value elision (io.sparse.SparseBatch): categorical
                # rows never transfer the val array; rebuild it from idx on
                # device. None is static under jit, so this is a separate
                # compiled variant, not a runtime branch.
                val = (idx != 0).astype(jnp.float32)
            margin = linear_margin(wf, idx, val)
        with jax.named_scope("hm.grad"):
            d = loss.dloss(margin, label) * row_mask            # [B]
            loss_sum = (loss.loss(margin, label) * row_mask).sum()
        with jax.named_scope("hm.scatter"):
            g = jnp.zeros_like(wf).at[idx.ravel()].add(
                (d[:, None] * val).ravel())                     # dense [N]
        with jax.named_scope("hm.update"):
            w_new, opt_state = optimizer.update(wf, g, opt_state, t)
        return w_new.astype(w.dtype), opt_state, loss_sum

    return scannable(partial(jax.jit, donate_argnums=(0, 1))(core), core)


def make_linear_predict() -> Callable:
    """Jitted scoring kernel: gather + segment-sum (+ sigmoid handled by
    caller). This is the rebuild of the reference's predict-is-a-join query
    (SURVEY.md §4.2) as an embedding-style lookup."""

    @jax.jit
    def predict(w, idx, val):
        return linear_margin(w.astype(jnp.float32), idx, val)

    return predict
