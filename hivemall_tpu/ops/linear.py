"""Linear-model minibatch kernels: sparse dot forward + scatter-add update.

This is the TPU replacement for the reference's per-row hot path
(SURVEY.md §4.1: parse -> sparse dot -> dloss -> per-feature Optimizer.update):
one jitted call takes a padded (idx, val) minibatch, computes margins with a
gather, sums the per-slot gradients by feature and runs the optimizer's
update. Gradients accumulate by SUM within the batch (gradient accumulation
of the reference's per-row steps, one optimizer-state advance per batch —
the semantic delta vs strict per-row sequential updates is documented in
SURVEY.md §8 "hard parts").

Two steps, chosen by the optimizer and the trainer, never by an option:

- the DENSE step, for every optimizer: a dense [N] gradient and the
  optimizer's elementwise pass over the whole table;
- the DISTINCT-SLOT step, for an optimizer whose weight is a function of
  its state and t (``Optimizer.weight_of``: AdaGrad-RDA) on one chip: the
  batch's slots are ranked once, the margin builds w from the state at the
  batch's distinct slots, the gradient is summed into a compact array by
  rank and the state is updated at those slots alone. The stored w is left
  stale inside a dispatch and rebuilt over the table once, after it
  (``step.finish``, which ops/scan.py's megastep runs after its scan).

Padding convention: (idx=0, val=0) slots contribute zero to margin and
gradient. Slot 0 doubles as the ``add_bias`` feature ("0:1.0") — a real bias
row has val=1 there, so it trains; padding has val=0, so it doesn't.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .losses import Loss
from .optimizers import Optimizer
from .rows_pallas import fold_runs
from .scan import scannable

__all__ = ["make_linear_step", "linear_margin", "make_linear_predict",
           "distinct_cap", "LINEAR_STATS"]

#: what the distinct-slot step counts about itself (ops/fm.py TAIL_STATS'
#: names): which step ran, and the batch's distinct slots
LINEAR_STATS = ("tail_distinct_steps", "tail_dense_steps", "distinct_rows")

#: distinct slots a trip of the compact reads (a gather costs by its
#: indices: ~13 ns a scalar out of HBM on a v5e, so in blocks it costs by
#: the batch)
SLOT_BLOCK = 8192
#: distinct slots a trip of the state's writes: a sorted scatter into a
#: [2^28] float32 array costs a pass over it (3.2 ms on a v5e) and 4.9 ns
#: an entry, padding included, so a trip is one pass for many slots
WRITE_BLOCK = 131072


class _StepCost(NamedTuple):
    """ns a unit of what each step costs on a TPU v5e, read at the cell
    logreg_criteo.stream's 1,277,952 slots into 2^28 float32 slots (the
    megastep of 8; experiments/probe_linear_distinct.py, PERF.md section
    6)."""
    #: the dense branch, a TABLE slot: the zero fill, the optimizer's pass
    #: and the pass that builds the stale w first (1.6 + 9.8 + 6.1 ms)
    dense_table: float
    #: the dense branch, a SLOT: the margin's gather out of the table, the
    #: scatter-add into it and its sort (48.1 ms at uniform ids, less the
    #: table's passes)
    dense_slot: float
    #: the distinct-slot step, a SLOT: its four sorts, the fill of the
    #: runs and their ordered sums
    distinct_slot: float
    #: the distinct-slot step, a DISTINCT slot: the state read there, the
    #: runs' starts placed, their sums read
    distinct: float
    #: the distinct-slot step, a TABLE slot a trip of WRITE_BLOCK: the
    #: writes' pass over the state's two arrays
    write_pass: float
    #: the distinct-slot step, an entry of a write trip, padding included,
    #: both arrays
    write_entry: float


# (the dense branch 48.16 ms a step at uniform ids, 17.5 of it the
# table's passes; the distinct step 16.86 / 18.55 / 29.59 ms at 27.8k /
# 73.8k / 164.8k distinct slots, one write trip 2 x 3.81 ms)
_COST = _StepCost(0.065, 24.0, 6.4, 36.7, 0.0236, 9.8)


def distinct_cap(n: int, N: int) -> int:
    """Most distinct slots the distinct-slot step takes on for n slots into
    a table of N: the largest count, in whole trips of WRITE_BLOCK (whole
    blocks of SLOT_BLOCK, or 128s, under one trip), at which it costs no
    more than the dense branch; 0 where none does (a table small against
    the batch) or where N + n overflows the int32 ids that pad the list.
    The costs grow with the batch's count, not the capacity, so a large
    capacity costs a batch within it nothing."""
    if N + n >= 2 ** 31:
        return 0
    c = _COST
    dense = N * c.dense_table + n * c.dense_slot

    def distinct(d, trips):
        return n * c.distinct_slot + d * c.distinct \
            + trips * (N * c.write_pass + WRITE_BLOCK * c.write_entry)
    trips = 0
    while (trips + 1) * WRITE_BLOCK <= n \
            and distinct((trips + 1) * WRITE_BLOCK, trips + 1) <= dense:
        trips += 1
    if trips:
        return trips * WRITE_BLOCK
    even = (dense - n * c.distinct_slot - N * c.write_pass) \
        / (c.distinct + c.write_entry)
    cap = max(0, min(n, int(even)))
    return cap // SLOT_BLOCK * SLOT_BLOCK or cap // 128 * 128


def linear_margin(w: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray
                  ) -> jnp.ndarray:
    """margin[b] = sum_l w[idx[b,l]] * val[b,l] — batched sparse dot."""
    return (w[idx].astype(jnp.float32) * val).sum(axis=-1)


def make_linear_step(loss: Loss, optimizer: Optimizer, distinct: bool = True
                     ) -> Callable:
    """Build the jitted train step: (w, opt_state, t, batch) -> updated.

    The distinct-slot step where the optimizer has ``weight_of`` and
    ``distinct`` (the trainer's choice: False under -mesh, and for a
    dispatch after w was written from outside), else the dense one. A
    batch with more distinct slots than `distinct_cap` of the shapes takes
    the dense step inside the same program (lax.cond)."""

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

    if optimizer.weight_of is None or not distinct:
        return scannable(partial(jax.jit, donate_argnums=(0, 1))(core), core)

    weight_of = optimizer.weight_of

    def distinct_core(w, opt_state, t, idx, val, label, row_mask):
        B, L = idx.shape
        N = w.shape[0]
        rows = idx.reshape(-1)
        n = rows.shape[0]
        c = distinct_cap(n, N)
        if not c:
            # no capacity at these shapes: every step dense, w kept fresh
            return (*core(w, opt_state, t, idx, val, label, row_mask), dict(
                zip(LINEAR_STATS, jnp.asarray([0, 1, 0], jnp.int32))))
        tb = min(c, SLOT_BLOCK)
        leaves, tree = jax.tree_util.tree_flatten(opt_state)
        with jax.named_scope("hm.scatter"):
            slot = jnp.arange(n, dtype=jnp.int32)
            srows, perm = jax.lax.sort_key_val(rows, slot)
            first = jnp.concatenate(
                [jnp.ones((1,), bool), srows[1:] != srows[:-1]])
            n_distinct = first.sum(dtype=jnp.int32)

        def compact(w, leaves):
            k = jnp.arange(c, dtype=jnp.int32)
            live = k < n_distinct
            with jax.named_scope("hm.scatter"):
                # the distinct slots in order, then ids past the table, and
                # where each one's run of slots starts in table order
                urows, ustart = jax.lax.sort_key_val(
                    jnp.where(first, srows, N + slot), slot, is_stable=False)
                urows, ustart = urows[:c], jnp.where(live, ustart[:c], n)
                # the longest run bounds the fill's passes
                longest = jnp.max(jnp.where(
                    live, jnp.concatenate([ustart[1:], jnp.full((1,), n)])
                    - ustart, 0))
            with jax.named_scope("hm.gather"):
                sc = _read(leaves, urows, n_distinct, c, tb)
                # at t == 0 no step has built w: it is the zeros the table
                # was born with (a write from outside, a warm start's
                # included, sends the dispatch through the dense step), so
                # this branch never reads the stored w and passes it on
                # untouched (read here, the compiler copied the whole
                # table twice a step)
                wc = jnp.where(t > 0, weight_of(tree.unflatten(sc), t - 1.0)
                               .astype(w.dtype).astype(jnp.float32), 0.0)
                # w of every slot: a run's value at its start, carried
                # along the run, then brought to slot order by a sort (a
                # gather of 1,277,952 scalars costs 7 ns each, a sort ~1)
                at_start = _write(
                    (jnp.zeros(n, jnp.float32),),
                    jnp.where(live, ustart, n + k), (wc,), n_distinct, tb)[0]
                w_sorted = _fill_runs(first, at_start, longest)
                _, w_slot = jax.lax.sort_key_val(perm, w_sorted,
                                                 is_stable=False)
                wv = val if val is not None \
                    else (idx != 0).astype(jnp.float32)
                margin = (w_slot.reshape(B, L) * wv).sum(axis=-1)
            with jax.named_scope("hm.grad"):
                d = loss.dloss(margin, label) * row_mask        # [B]
                loss_sum = (loss.loss(margin, label) * row_mask).sum()
            with jax.named_scope("hm.scatter"):
                # the slots' gradients in table order (a stable sort by the
                # same keys is the first sort's order), each run summed in
                # that order, one addend after another, as the dense step's
                # scatter-add and the float32 reference sum them. A run of
                # thousands of near-equal addends rounds the same way at
                # each add, so another order (a tree's, more exact) moves
                # a hot slot's sum by 1e-4 of it, and AdaGrad's sums by
                # 1.8e-5 in norm: past what the benchmark's check allows.
                # rows_pallas.fold_runs keeps the order in 0.12 ms a step
                # on a v5e, where XLA's scatter-add takes 11
                _, g_sorted = jax.lax.sort_key_val(
                    rows, (d[:, None] * wv).reshape(-1))
                sums = fold_runs(g_sorted, first, longest)
                if sums is None:            # off a TPU: XLA's, same order
                    Gc = jax.ops.segment_sum(
                        g_sorted, jnp.cumsum(first.astype(jnp.int32)) - 1,
                        c, indices_are_sorted=True)
                else:                       # a run's sum at its end
                    ends = jnp.concatenate([ustart[1:], jnp.full((1,), n)])
                    Gc = _read((sums,), jnp.where(live, ends - 1, n),
                               n_distinct, c, tb)[0]
            with jax.named_scope("hm.update"):
                _, sn = optimizer.update(wc, Gc, tree.unflatten(sc), t)
                leaves = _write(leaves, urows, jax.tree_util.tree_leaves(sn),
                                n_distinct, min(c, WRITE_BLOCK))
            return w, leaves, loss_sum

        def dense(w, leaves):
            # inside a dispatch the stored w is stale: built first, for
            # the margin of a batch past the capacity (the stored w at
            # t == 0, which nothing has built yet)
            state = tree.unflatten(leaves)
            built = weight_of(state, t - 1.0).astype(w.dtype)
            w_new, state, loss_sum = core(
                jnp.where(t > 0, built, w), state, t, idx, val, label,
                row_mask)
            return w_new, tuple(jax.tree_util.tree_leaves(state)), loss_sum

        took = n_distinct <= c
        w, leaves, loss_sum = jax.lax.cond(took, compact, dense, w,
                                           tuple(leaves))
        took = took.astype(jnp.int32)
        return w, tree.unflatten(leaves), loss_sum, {
            "tail_distinct_steps": took, "tail_dense_steps": 1 - took,
            "distinct_rows": n_distinct}

    def finish(w, opt_state, t):
        """After step t (a dispatch's last): w over the whole table, as the
        dense step leaves it, for everything that reads the state."""
        with jax.named_scope("hm.update"):
            return weight_of(opt_state, t).astype(w.dtype), opt_state

    def step(w, opt_state, t, idx, val, label, row_mask):
        w, opt_state, loss_sum, stats = distinct_core(
            w, opt_state, t, idx, val, label, row_mask)
        return (*finish(w, opt_state, t), loss_sum, stats)

    jitted = scannable(partial(jax.jit, donate_argnums=(0, 1))(step),
                       distinct_core)
    jitted.finish = finish
    return jitted


def _read(tables, ids, count, c, tb):
    """The [N] tables at ``ids`` [c], float32 [c] each, a block of tb a
    trip up to ``count`` (entries past it are never read; a last block of
    a capacity that is not whole blocks ends at the capacity and reads
    some entries a second time). A gather costs by its indices, ~13 ns a
    scalar out of HBM on a v5e: in blocks it costs by the batch."""
    def block(i, cs):
        at = jnp.minimum(i * tb, c - tb)
        sub = jax.lax.dynamic_slice_in_dim(ids, at, tb)
        return tuple(jax.lax.dynamic_update_slice_in_dim(
            cc, T.at[sub].get(mode="fill", fill_value=0).astype(jnp.float32),
            at, 0) for cc, T in zip(cs, tables))
    return jax.lax.fori_loop(0, (count + tb - 1) // tb, block, tuple(
        jnp.zeros((c,), jnp.float32) for _ in tables))


def _write(tables, ids, vals, count, tb):
    """``tables`` with ``vals`` [c] set at the sorted, distinct ``ids`` [c]
    (past a table's end: dropped), a block of tb a trip up to ``count``
    (a last block that ends at the capacity sets some entries a second
    time, to the same values). Each trip is one sorted scatter, in place:
    on a v5e a pass over the table and 4.9 ns an entry."""
    c = ids.shape[0]

    def block(i, tables):
        at = jnp.minimum(i * tb, c - tb)
        sub = jax.lax.dynamic_slice_in_dim(ids, at, tb)
        return tuple(T.at[sub].set(
            jax.lax.dynamic_slice_in_dim(v, at, tb).astype(T.dtype),
            mode="drop", indices_are_sorted=True, unique_indices=True)
            for T, v in zip(tables, vals))
    return jax.lax.fori_loop(0, (count + tb - 1) // tb, block, tuple(tables))


def _fill_runs(first, vals, longest):
    """Each run of equal keys in table order (``first`` marks its start)
    takes the value ``vals`` holds at its start. Hillis-Steele doubling:
    pass p hands a value 2^p slots on to a slot whose range holds no start
    yet, ceil(log2(longest)) passes of elementwise work over [n] (a gather
    of each slot's value would cost ~7 ns an index on a v5e, 9 ms here)."""
    n = vals.shape[0]

    def back(x, d, fill):
        """x[j - d], ``fill`` before the start."""
        return jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([jnp.full((n,), fill, x.dtype), x]), n - d, n)

    def one(carry):
        d, f, v = carry
        d = jnp.minimum(d, n - 1)
        return 2 * d, f | back(f, d, True), jnp.where(f, v, back(v, d, 0))

    _, _, out = jax.lax.while_loop(
        lambda carry: carry[0] < longest, one,
        (jnp.ones((), jnp.int32), first, vals))
    return out


def make_linear_predict() -> Callable:
    """Jitted scoring kernel: gather + segment-sum (+ sigmoid handled by
    caller). This is the rebuild of the reference's predict-is-a-join query
    (SURVEY.md §4.2) as an embedding-style lookup."""

    @jax.jit
    def predict(w, idx, val):
        return linear_margin(w.astype(jnp.float32), idx, val)

    return predict
