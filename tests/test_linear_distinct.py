"""The linear family's distinct-slot step (ops/linear.py): for an optimizer
whose weight is a function of its state and t (AdaGrad-RDA's
``weight_of``), the step ranks a batch's slots, builds w from u and gg at
the distinct slots, sums the gradient into a compact array and writes u and
gg there alone; w over the whole table is rebuilt once a dispatch. Held
here, at toy size on the CPU, to the dense step it replaces: w, u and gg at
every slot after every dispatch, within float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hivemall_tpu.io.sparse import SparseDataset
from hivemall_tpu.models import linear as linear_models
from hivemall_tpu.models.linear import GeneralClassifier
from hivemall_tpu.ops import linear as linear_ops
from hivemall_tpu.ops.linear import make_linear_step
from hivemall_tpu.ops.losses import get_loss
from hivemall_tpu.ops.optimizers import make_optimizer
from hivemall_tpu.ops.scan import make_megastep, megastep_for

N, B, L, K = 4096, 64, 8, 4
LOSS = get_loss("logloss")
RDA = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1, power_t=0.1,
                     reg="rda", lam=1e-6)


def _batches(seed, k=K, zipf=1.3, n=N):
    """[k, B, L] ids with duplicates (Zipf), padding in the last column,
    and +-1 labels."""
    rng = np.random.default_rng(seed)
    idx = (rng.zipf(zipf, (k, B, L)) % (n - 1) + 1).astype(np.int32)
    idx[:, :, -1] = 0
    lab = np.where(rng.random((k, B)) < 0.3, 1.0, -1.0).astype(np.float32)
    return idx, lab


def _state(dtype=jnp.float32):
    return jnp.zeros(N, dtype), RDA.init(N)


def _close(a, b):
    """Equal within float32 rounding: the two steps add a slot's gradients
    in the same order, and XLA may fuse the rest of their arithmetic
    differently; a sum of mixed signs then moves by a few ulps of its
    terms (of the leaf's largest entry), not of its result."""
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        np.testing.assert_allclose(x, y, rtol=1e-5,
                                   atol=1e-6 * max(1e-6, np.abs(y).max()))


def _n_distinct(idx):
    return [len(np.unique(b)) for b in idx]


def _capacity(monkeypatch, cap):
    """The distinct-slot step's capacity at the toy shapes (its cost model
    gives the chip's break-even: none here)."""
    monkeypatch.setattr(linear_ops, "distinct_cap", lambda n, N: cap)


@pytest.mark.parametrize("dtype,explicit_val", [
    (jnp.float32, False), (jnp.bfloat16, False), (jnp.float32, True)],
    ids=["f32", "halffloat", "explicit_val"])
def test_megastep_matches_the_dense_step_after_every_dispatch(
        dtype, explicit_val, monkeypatch):
    """K=8 megasteps, three dispatches: the state at every slot is the
    dense step's; every step took the distinct path and counted its
    batch's distinct slots."""
    k = 8
    _capacity(monkeypatch, 512)
    distinct = make_linear_step(LOSS, RDA)
    dense = make_linear_step(LOSS, RDA, distinct=False)
    m1 = make_megastep(distinct.core, none_val=True, finish=distinct.finish)
    m0 = make_megastep(dense.core, none_val=True)
    a, b = _state(dtype), _state(dtype)
    nv = jnp.full((k,), B, jnp.int32)
    for d in range(3):
        idx, lab = _batches(d, k)
        val = (idx != 0).astype(np.float32) if explicit_val else None
        *a, la, stats = m1(*a, float(d * k), nv, idx, val, lab, None, None)
        *b, lb = m0(*b, float(d * k), nv, idx, val, lab, None, None)
        _close(a, b)
        _close(la, lb)
        assert list(stats["tail_distinct_steps"]) == [1] * k
        assert list(stats["tail_dense_steps"]) == [0] * k
        assert list(stats["distinct_rows"]) == _n_distinct(idx)
    assert a[0].dtype == dtype


def test_single_steps_match_the_dense_step(monkeypatch):
    """K=1: the jitted step rebuilds w after itself."""
    _capacity(monkeypatch, 512)
    distinct = make_linear_step(LOSS, RDA)
    dense = make_linear_step(LOSS, RDA, distinct=False)
    a, b = _state(), _state()
    idx, lab = _batches(7, 6)
    for s in range(6):
        *a, la, stats = distinct(*a, float(s), idx[s], None, lab[s],
                                 jnp.ones(B))
        *b, lb = dense(*b, float(s), idx[s], None, lab[s], jnp.ones(B))
        _close(a, b)
        assert int(stats["tail_distinct_steps"]) == 1
        assert int(stats["distinct_rows"]) == _n_distinct(idx[s:s + 1])[0]


def test_a_batch_over_the_capacity_takes_the_dense_branch_mid_dispatch(
        monkeypatch):
    """Steps within the capacity and past it alternate inside one
    dispatch: the dense branch builds the stale w first, so the state is
    still the dense step's; a padded step (nv < B) goes through too."""
    idx, lab = _batches(3, K)
    idx[1] = np.random.default_rng(9).integers(1, N, (B, L))   # ~all distinct
    idx[3] = idx[1]
    counts = _n_distinct(idx)
    cap = 384
    assert counts[0] <= cap < counts[1] and counts[2] <= cap
    _capacity(monkeypatch, cap)
    distinct = make_linear_step(LOSS, RDA)
    dense = make_linear_step(LOSS, RDA, distinct=False)
    m1 = make_megastep(distinct.core, none_val=True, finish=distinct.finish)
    m0 = make_megastep(dense.core, none_val=True)
    nv = jnp.asarray([B, B, B, B // 2], jnp.int32)
    a, b = _state(), _state()
    for d in range(2):
        *a, _, stats = m1(*a, float(d * K), nv, idx, None, lab, None, None)
        *b, _ = m0(*b, float(d * K), nv, idx, None, lab, None, None)
        _close(a, b)
    assert list(stats["tail_distinct_steps"]) == [1, 0, 1, 0]
    assert list(stats["tail_dense_steps"]) == [0, 1, 0, 1]


def test_no_capacity_keeps_every_step_dense(monkeypatch):
    """A capacity of 0 (the int32 ids would overflow) runs the dense step
    in the same program and counts it."""
    assert linear_ops.distinct_cap(1000, 2 ** 31 - 10) == 0
    _capacity(monkeypatch, 0)
    distinct = make_linear_step(LOSS, RDA)
    dense = make_linear_step(LOSS, RDA, distinct=False)
    idx, lab = _batches(4, 2)
    a, b = _state(), _state()
    for s in range(2):
        *a, _, stats = distinct(*a, float(s), idx[s], None, lab[s],
                                jnp.ones(B))
        *b, _ = dense(*b, float(s), idx[s], None, lab[s], jnp.ones(B))
    _close(a, b)
    assert int(stats["tail_dense_steps"]) == 1


def test_capacity_follows_the_shapes():
    """Whole trips of the writes at the cell's shapes, above its batches'
    ~74k distinct slots and under the slots (uniformly hashed ids, every
    slot distinct, are past it); no more for a larger table, whose writes
    pass over more; whole 128s where one trip is too many."""
    n = 32768 * 39
    cap = linear_ops.distinct_cap(n, 1 << 28)
    assert 74_000 < cap < n and cap % linear_ops.WRITE_BLOCK == 0
    assert linear_ops.distinct_cap(n, 1 << 30) <= cap
    small = linear_ops.distinct_cap(512, 4096)
    assert small < 512 and small % 128 == 0


# --- the runs' sums, in order ---------------------------------------------------


@pytest.mark.parametrize("n,zipf", [(512, 1.5), (5000, 1.2), (20000, 1.05)])
def test_fold_runs_adds_each_run_in_order(n, zipf):
    """rows_pallas.fold_runs (the kernel, interpreted) at each run's end is
    XLA's sorted scatter-add bit for bit: the same addends in the same
    order, runs that cross many of the kernel's stretches included."""
    from hivemall_tpu.ops.rows_pallas import fold_runs
    rng = np.random.default_rng(n)
    keys = np.sort(rng.zipf(zipf, n) % 500)
    vals = ((rng.random(n) - 0.3) * 0.37).astype(np.float32)
    first = np.concatenate([[True], keys[1:] != keys[:-1]])
    starts = np.nonzero(first)[0]
    longest = int(np.diff(np.concatenate([starts, [n]])).max())
    assert longest > 2 * -(-n // 8192) * 8       # crosses several stretches
    out = np.asarray(fold_runs(jnp.asarray(vals), jnp.asarray(first),
                               longest, interpret=True))
    rank = np.cumsum(first) - 1
    want = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(rank),
                               len(starts), indices_are_sorted=True)
    ends = np.concatenate([starts[1:], [n]]) - 1
    np.testing.assert_array_equal(out[ends], np.asarray(want))
    assert fold_runs(jnp.asarray(vals), jnp.asarray(first), longest) is None


def test_the_step_through_the_fold_kernel_is_the_dense_step(monkeypatch):
    """The step as a TPU runs it (the runs' sums by the kernel, here
    interpreted, read at the runs' ends) against the dense step."""
    from functools import partial
    from hivemall_tpu.ops import rows_pallas
    monkeypatch.setattr(linear_ops, "fold_runs",
                        partial(rows_pallas.fold_runs, interpret=True))
    _capacity(monkeypatch, 512)
    distinct = make_linear_step(LOSS, RDA)
    dense = make_linear_step(LOSS, RDA, distinct=False)
    m1 = make_megastep(distinct.core, none_val=True, finish=distinct.finish)
    m0 = make_megastep(dense.core, none_val=True)
    idx, lab = _batches(11, 2)
    nv = jnp.full((2,), B, jnp.int32)
    a, b = _state(), _state()
    for d in range(2):
        *a, _, stats = m1(*a, float(2 * d), nv, idx, None, lab, None, None)
        *b, _ = m0(*b, float(2 * d), nv, idx, None, lab, None, None)
        _close(a, b)
    assert list(stats["tail_distinct_steps"]) == [1, 1]


# --- the trainer: which step a dispatch takes ---------------------------------

OPTS = (f"-dims {N} -loss logloss -opt adagrad -mini_batch {B} "
        f"-steps_per_dispatch {K}")


def _dataset(seed, n=B * K * 2):
    rng = np.random.default_rng(seed)
    idx = (rng.zipf(1.3, (n, L)) % (N - 1) + 1).astype(np.int32)
    lab = np.where(rng.random(n) < 0.3, 1.0, -1.0).astype(np.float32)
    return SparseDataset(idx.ravel(), np.arange(0, n * L + 1, L,
                                                dtype=np.int64),
                         np.ones(n * L, np.float32), lab)


@pytest.fixture
def toy_capacity(monkeypatch):
    """The toy table offers the distinct-slot step (its cost model gives
    the chip's break-even, 0 at this size); fresh step objects for it."""
    _capacity(monkeypatch, 512)
    linear_models._linear_step_cached.cache_clear()
    yield
    linear_models._linear_step_cached.cache_clear()


def _pair(opts=OPTS):
    """A trainer on the distinct-slot step, and one held to the dense
    step (the program every other optimizer runs)."""
    t, ref = GeneralClassifier(opts), GeneralClassifier(opts)
    assert t._step is not t._dense_step
    ref._step = ref._dense_step
    return t, ref


def _same_state(t, ref):
    _close((t.params, t.opt_state), (ref.params, ref.opt_state))


def test_trainer_takes_the_distinct_step_and_counts_it(toy_capacity):
    t, ref = _pair()
    ds = _dataset(1)
    t.fit(ds, shuffle=False)
    ref.fit(ds, shuffle=False)
    _same_state(t, ref)
    t._fold_loss()
    steps = t._t
    assert t._step_counts["tail_distinct_steps"] == steps
    assert t._step_counts["tail_dense_steps"] == 0
    idx = ds.indices.reshape(-1, B, L)
    assert t._step_counts["distinct_rows"] == sum(_n_distinct(idx))
    _close(t._finalized_weights(), ref._finalized_weights())


def test_a_write_between_dispatches_is_read_as_the_dense_step_reads_it(
        toy_capacity):
    """MIX's `_set_weights_at` after a dispatch: the next margin reads the
    written w (the dispatch runs the dense step), and the one after is the
    distinct-slot step again."""
    t, ref = _pair()
    first, second = _dataset(2, B * K), _dataset(3, B * K)
    for tr in (t, ref):
        tr.fit(first, shuffle=False)
    keys = np.unique(second.indices)[:40].astype(np.int64)
    vals = np.linspace(-0.5, 0.5, len(keys)).astype(np.float32)
    for tr in (t, ref):
        tr._set_weights_at(keys, vals)
    assert t._w_written and t._scan_step() is t._dense_step
    for tr in (t, ref):
        tr.fit(second, shuffle=False)
    _same_state(t, ref)
    assert not t._w_written and t._scan_step() is t._step
    for tr in (t, ref):
        tr.fit(first, shuffle=False)
    _same_state(t, ref)


def test_a_warm_start_at_t0_is_read_as_stored(toy_capacity, tmp_path):
    """-loadmodel before the first step: the first dispatch's margin reads
    the loaded weights, as the dense step does."""
    model = tmp_path / "m.tsv"
    rng = np.random.default_rng(5)
    model.write_text("".join(f"{i}\t{w:.6f}\n" for i, w in zip(
        rng.choice(np.arange(1, 64), 30, replace=False),
        rng.normal(0, 0.3, 30))))
    t, ref = _pair(f"{OPTS} -loadmodel {model}")
    assert t._w_written
    ds = _dataset(6, B * K)
    t.fit(ds, shuffle=False)
    ref.fit(ds, shuffle=False)
    _same_state(t, ref)
    assert not t._w_written


def test_single_dispatches_and_halffloat_through_the_trainer(toy_capacity):
    """K=1 dispatches, a bfloat16 table."""
    opts = OPTS.replace(f"-steps_per_dispatch {K}",
                        "-steps_per_dispatch 1") + " -halffloat"
    t, ref = _pair(opts)
    ds = _dataset(8, B * 3)
    t.fit(ds, shuffle=False)
    ref.fit(ds, shuffle=False)
    assert t.params.dtype == jnp.bfloat16
    _same_state(t, ref)
    t._fold_loss()
    assert t._step_counts["tail_distinct_steps"] == 3


# --- every other program is the parent's ------------------------------------


def _dense_core_as_it_was(loss, optimizer):
    """The dense step's core as it stood before the distinct-slot step."""
    def core(w, opt_state, t, idx, val, label, row_mask):
        wf = w.astype(jnp.float32)
        with jax.named_scope("hm.gather"):
            if val is None:
                val = (idx != 0).astype(jnp.float32)
            margin = (wf[idx].astype(jnp.float32) * val).sum(axis=-1)
        with jax.named_scope("hm.grad"):
            d = loss.dloss(margin, label) * row_mask
            loss_sum = (loss.loss(margin, label) * row_mask).sum()
        with jax.named_scope("hm.scatter"):
            g = jnp.zeros_like(wf).at[idx.ravel()].add(
                (d[:, None] * val).ravel())
        with jax.named_scope("hm.update"):
            w_new, opt_state = optimizer.update(wf, g, opt_state, t)
        return w_new.astype(w.dtype), opt_state, loss_sum
    return core


def _lowered(core, optimizer, mega=False, finish=None):
    w = jax.ShapeDtypeStruct((N,), jnp.float32)
    state = jax.eval_shape(lambda: optimizer.init(N))
    if mega:
        return make_megastep(core, none_val=True, finish=finish).lower(
            w, state, 0.0, jnp.zeros(K, jnp.int32),
            jax.ShapeDtypeStruct((K, B, L), jnp.int32), None,
            jax.ShapeDtypeStruct((K, B), jnp.float32), None, None).as_text()
    return jax.jit(core, donate_argnums=(0, 1)).lower(
        w, state, 0.0, jax.ShapeDtypeStruct((B, L), jnp.int32), None,
        jax.ShapeDtypeStruct((B,), jnp.float32),
        jax.ShapeDtypeStruct((B,), jnp.float32)).as_text()


@pytest.mark.parametrize("name,reg", [
    ("sgd", "no"), ("adagrad", "no"), ("adagrad", "l2"), ("adadelta", "no"),
    ("adam", "no"), ("ftrl", "no"), ("momentum", "no"), ("rda", "rda")])
def test_every_optimizer_without_weight_of_runs_the_parents_program(name,
                                                                    reg):
    """The step and its megastep lower to the text the parent's did; RDA's
    dense step (a -mesh trainer's, the dispatch after a write) too."""
    opt = make_optimizer(name, eta_scheme="inverse", eta0=0.1, reg=reg)
    step = make_linear_step(LOSS, opt, distinct=opt.weight_of is None)
    assert (opt.weight_of is not None) == (name == "rda")
    assert not hasattr(step, "finish")
    parent = _dense_core_as_it_was(LOSS, opt)
    assert _lowered(step.core, opt) == _lowered(parent, opt)
    assert _lowered(step.core, opt, mega=True) \
        == _lowered(parent, opt, mega=True)
    assert megastep_for(step, none_val=True) is not None


def test_a_mesh_trainer_keeps_the_dense_step():
    """-mesh: GSPMD's cut of the dense step, whatever the optimizer."""
    t = GeneralClassifier(f"-dims {N} -loss logloss -opt adagrad "
                          f"-mini_batch {B} -mesh dp=1,tp=2")
    assert t.optimizer.weight_of is not None
    assert t._step is t._dense_step and not hasattr(t._step, "finish")
    t._set_weights_at(np.arange(1, 5), np.ones(4, np.float32))
    assert not t._w_written


def test_rda_update_and_weight_of_are_one_expression():
    """rda_update's w is weight_of of the state it leaves."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=64), jnp.float32)
    g = jnp.asarray(rng.normal(size=64), jnp.float32)
    s = {"u": jnp.asarray(rng.normal(size=64), jnp.float32),
         "gg": jnp.asarray(rng.random(64), jnp.float32)}
    for t in (0.0, 5.0):
        w_new, s_new = RDA.update(w, g, s, t)
        np.testing.assert_array_equal(w_new, RDA.weight_of(s_new, t))
