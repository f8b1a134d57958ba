"""The FFM joint step under `-mesh dp=1,tp>1`: `make_ffm_step_fused(mesh=)`
runs the one-chip step on every chip's own block of rows (`shard_map` over
tp), against the one-device step on the same batches.

Same mathematics, so: the table, its AdaGrad state, w0 and the loss agree
to float32 rounding where a batch touched a row (a duplicate's addends may
meet in another order, a row is rounded to the table's dtype once on
either side) and are BIT-equal everywhere else; the step's stats are the
one-device step's where every chip took the branch the one device took.
Shapes whose LOCAL `tail_cap` is not 0: the table of `_FFM_OPTS` in
tests/test_fm_distinct_tail.py (131,072 rows of 40 lanes), 1,024 slots.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from hivemall_tpu.ops import fm
from hivemall_tpu.ops.losses import get_loss
from hivemall_tpu.ops.optimizers import make_optimizer
from hivemall_tpu.ops.scan import make_megastep
from hivemall_tpu.parallel.mesh import make_mesh

F, K, B, L, MR = 8, 4, 64, 16, 131072
W, N = F * K + 8, B * L
LAMS = (0.01, 0.02, 0.03)
OPT = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1, reg="no")
#: feature ids by the block of four their row falls in
_IDS = np.arange(1, 1 << 18, dtype=np.int32)
_ROWS = np.asarray(fm.ffm_row_hash(jnp.asarray(_IDS), MR))


def _mesh(tp):
    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} devices")
    return make_mesh(dp=1, tp=tp)


def _state(dtype=jnp.bfloat16, mesh=None, seed=2):
    rng = np.random.default_rng(seed)
    T = jnp.asarray(0.1 * rng.normal(size=(MR, W)), dtype)
    gg = jnp.asarray(rng.random((MR, W)).astype(np.float32))
    state = ({"T": T, "w0": jnp.asarray(0.05, jnp.float32)},
             {"T": {"gg": gg}, "w0": {"gg": jnp.asarray(0.5, jnp.float32)}})
    if mesh is None:
        return state
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, NamedSharding(
        mesh, P("tp", None) if a.ndim == 2 else P())), state)


def _ids(per_block, seed=4, tp=4):
    """[B, L] feature ids over `per_block[b]` distinct rows of block b of
    `tp` (no two ids of a batch share a row)."""
    rng = np.random.default_rng(seed)
    pool = []
    for b, n in enumerate(per_block):
        at = np.flatnonzero(_ROWS // (MR // tp) == b)
        _, first = np.unique(_ROWS[at], return_index=True)
        pool.append(_IDS[at[rng.choice(first, n, replace=False)]])
    pool = np.concatenate(pool)
    idx = rng.choice(pool, N)
    idx[rng.choice(N, len(pool), replace=False)] = pool
    return idx.reshape(B, L).astype(np.int32)


def _batch(kind, idx, seed=5):
    """(step kwargs, batch args after t): field-major with unit values
    elided, field-major with values, the pairs path with its field array,
    or the unit batch with its second half padding (id 0, masked out)."""
    rng = np.random.default_rng(seed)
    idx, mask = idx.copy(), np.ones(B, np.float32)
    if kind == "padded":
        idx[B // 2:] = 0
        mask[B // 2:] = 0.0
    val = jnp.asarray((0.5 + rng.random((B, L))).astype(np.float32))
    field = jnp.asarray(np.tile(np.arange(L, dtype=np.int32) % F, (B, 1)))
    label = jnp.asarray(np.where(rng.random(B) < 0.4, 1.0, -1.0)
                        .astype(np.float32))
    idx, mask = jnp.asarray(idx), jnp.asarray(mask)
    if kind in ("unit", "padded"):
        return dict(fieldmajor=True, unit_val=True), (idx, label, mask)
    if kind == "valued":
        return dict(fieldmajor=True), (idx, val, label, mask)
    return {}, (idx, val, label, mask, field)


def _step(mesh=None, **kw):
    return fm.make_ffm_step_fused(get_loss("logloss"), OPT, LAMS, F, K,
                                  mesh=mesh, **kw)


def _both(kind, idx, tp, dtype=jnp.bfloat16):
    """One step from the same state on one device and over tp chips."""
    mesh = _mesh(tp)
    kw, args = _batch(kind, idx)
    ref = _step(**kw)(*_state(dtype), 3.0, *args)
    new = _step(mesh, **kw)(*_state(dtype, mesh), 3.0, *args)
    assert new[0]["T"].sharding.spec == P("tp", None)
    assert new[1]["T"]["gg"].sharding.spec == P("tp", None)
    return new, ref, np.unique(np.asarray(fm.ffm_row_hash(args[0], MR)))


def _stats(out):
    return {k: int(v) for k, v in out[3].items()}


def _assert_same_to_rounding(new, ref, rows):
    touched = np.zeros(MR, bool)
    touched[rows] = True
    for a, b in ((new[0]["T"], ref[0]["T"]),
                 (new[1]["T"]["gg"], ref[1]["T"]["gg"])):
        assert a.dtype == b.dtype
        bf16 = a.dtype == jnp.bfloat16
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_array_equal(a[~touched], b[~touched])
        if bf16:               # rounded once on either side: an ulp, rarely
            np.testing.assert_allclose(a[touched], b[touched],
                                       rtol=2.0 ** -7)
            assert (a[touched] == b[touched]).mean() > 0.99
        else:
            np.testing.assert_allclose(a[touched], b[touched], rtol=2e-6,
                                       atol=1e-7)
    # the slab every chip differentiates is T[rows] bit for bit
    for a, b in ((new[0]["w0"], ref[0]["w0"]), (new[2], ref[2]),
                 (new[1]["w0"]["gg"], ref[1]["w0"]["gg"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_capacities_of_the_test_shape_are_the_blocks():
    assert fm.tail_cap(N, MR, W, 2) == 1024
    assert fm.tail_cap(N, MR // 2, W, 2) == 1024
    assert fm.tail_cap(N, MR // 4, W, 2) == 640      # a bfloat16 table
    assert fm.tail_cap(N, MR // 4, W, 4) == 768
    assert fm.gather_cap(640, W, 2) == 640


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["halffloat", "float32"])
@pytest.mark.parametrize("kind", ["unit", "valued", "pairs"])
@pytest.mark.parametrize("tp", [2, 4], ids=["tp2", "tp4"])
def test_step_over_tp_is_the_one_device_step(tp, kind, dtype):
    new, ref, rows = _both(kind, _ids([30] * tp, tp=tp), tp, dtype)
    assert _stats(new) == _stats(ref) == {
        "tail_distinct_steps": 1, "tail_dense_steps": 0,
        "distinct_rows": 30 * tp, "gather_compact_steps": 1}
    _assert_same_to_rounding(new, ref, rows)


@pytest.mark.parametrize("tp", [2, 4], ids=["tp2", "tp4"])
def test_padded_batch_over_tp(tp):
    """Padding is feature id 0, row 0: block 0's, with a zero gradient."""
    new, ref, rows = _both("padded", _ids([40] * tp, tp=tp), tp)
    assert 0 in rows and _stats(new) == _stats(ref)
    assert _stats(new)["tail_distinct_steps"] == 1
    _assert_same_to_rounding(new, ref, rows)


def test_chips_with_no_slot_of_their_own():
    """Every slot is block 1's: three chips rank nothing, read nothing
    (their slabs are zeros), and leave their blocks as they were."""
    new, ref, rows = _both("unit", _ids([0, 90, 0, 0]), 4)
    assert (rows // (MR // 4) == 1).all()
    assert _stats(new) == _stats(ref) and _stats(new)["distinct_rows"] == 90
    _assert_same_to_rounding(new, ref, rows)
    before = np.asarray(_state()[0]["T"], np.float32)
    after = np.asarray(new[0]["T"], np.float32)
    mine = slice(MR // 4, MR // 2)
    assert not np.array_equal(after[mine], before[mine])
    after[mine] = before[mine]
    np.testing.assert_array_equal(after, before)


def test_one_chip_over_its_capacity_takes_its_own_dense_tail():
    """Block 2 holds 700 distinct rows of a capacity of 640, the others
    20 each: chip 2 reads its table directly and runs the dense tail on
    ITS block, the others the distinct-row tail, and the step counts as
    dense; the one device, with room for 1,024, took the distinct tail."""
    new, ref, rows = _both("unit", _ids([20, 20, 700, 20]), 4)
    assert _stats(ref) == {
        "tail_distinct_steps": 1, "tail_dense_steps": 0,
        "distinct_rows": 760, "gather_compact_steps": 1}
    assert _stats(new) == {
        "tail_distinct_steps": 0, "tail_dense_steps": 1,
        "distinct_rows": 760, "gather_compact_steps": 0}
    _assert_same_to_rounding(new, ref, rows)


def test_megastep_of_four_over_tp_equals_four_single_steps():
    """The K-step scan runs the sharded step's own core, stats included;
    the third batch puts one chip over its capacity."""
    mesh = _mesh(4)
    step = _step(mesh, fieldmajor=True, unit_val=True)
    per_block = ([30] * 4, [10, 600, 5, 80], [20, 20, 700, 20], [0, 0, 0, 50])
    batches = [_batch("unit", _ids(pb, seed=30 + i), seed=40 + i)[1]
               for i, pb in enumerate(per_block)]
    nv = np.asarray([B, B, B - 5, B], np.int32)
    params, state = _state(mesh=mesh)
    losses, stats = [], []
    for i, (idx, label, _) in enumerate(batches):
        mask = (jnp.arange(B) < nv[i]).astype(jnp.float32)
        params, state, ls, st = step(params, state, 7.0 + i, idx, label,
                                     mask)
        losses.append(float(ls))
        stats.append({k: int(v) for k, v in st.items()})
    p2, s2 = _state(mesh=mesh)
    p2, s2, ls2, st2 = make_megastep(step.core)(
        p2, s2, 7.0, jnp.asarray(nv), jnp.stack([b[0] for b in batches]),
        None, jnp.stack([b[1] for b in batches]), None, None)
    np.testing.assert_array_equal(np.asarray(ls2),
                                  np.asarray(losses, np.float32))
    for a, b in ((p2["T"], params["T"]), (s2["T"]["gg"], state["T"]["gg"]),
                 (p2["w0"], params["w0"])):
        assert a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    for name in fm.TAIL_STATS:
        assert [int(v) for v in st2[name]] == [s[name] for s in stats]
    assert [s["tail_distinct_steps"] for s in stats] == [1, 1, 0, 1]
    assert [s["distinct_rows"] for s in stats] == [
        len(np.unique(np.asarray(fm.ffm_row_hash(b[0], MR))))
        for b in batches]


def test_small_blocks_keep_the_dense_tail_on_each_chip():
    """A table small against the batch (local `tail_cap` 0) has no ranking
    in its program: every chip scatters its own slots into a dense G of
    its block (another block's slot carries the id R: dropped)."""
    mesh, rows_n = _mesh(4), 4096
    kw, args = _batch("unit", _ids([30] * 4))
    assert fm.tail_cap(N, rows_n // 4, W, 2) == 0
    rng = np.random.default_rng(7)
    T = jnp.asarray(0.1 * rng.normal(size=(rows_n, W)), jnp.float32)
    gg = jnp.asarray(rng.random((rows_n, W)).astype(np.float32))

    def state():
        return ({"T": T + 0, "w0": jnp.asarray(0.05, jnp.float32)},
                {"T": {"gg": gg + 0},
                 "w0": {"gg": jnp.asarray(0.5, jnp.float32)}})
    step = _step(mesh, **kw)
    text = step.lower(*state(), 3.0, *args).as_text()
    assert "stablehlo.sort" not in text and "stablehlo.case" not in text
    new = step(*state(), 3.0, *args)
    ref = _step(**kw)(*state(), 3.0, *args)
    assert _stats(new) == _stats(ref) == {
        "tail_distinct_steps": 0, "tail_dense_steps": 1, "distinct_rows": 0,
        "gather_compact_steps": 0}
    for a, b in ((new[0]["T"], ref[0]["T"]),
                 (new[1]["T"]["gg"], ref[1]["T"]["gg"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6,
                                   atol=1e-7)
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(ref[2]))


def test_the_factory_refuses_what_it_cannot_deal():
    """A dp axis is refused where the step is built, rows that do not
    deal into tp equal blocks where it is traced: neither reaches the
    shapes inside `shard_map`."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    with pytest.raises(ValueError, match="dp axis"):
        _step(make_mesh(dp=2, tp=2))
    kw, args = _batch("unit", _ids([30] * 4))
    params, opt_state = _state()
    odd = jax.tree_util.tree_map(
        lambda a: a[:MR - 2] if a.ndim == 2 else a, (params, opt_state))
    with pytest.raises(ValueError, match="4 equal blocks"):
        _step(_mesh(4), **kw).lower(*odd, 3.0, *args)


def test_the_steps_collectives_are_the_partitioners():
    """No collective is written inside the two `shard_map`s: the blocks'
    slabs and stats leave them stacked over tp, and the sums between are
    plain reductions over that axis, which the partitioner turns into the
    all-reduce its cut of the dense step has (and names as it names that
    one: the benchmark's `mesh.collective_share` reads ops by name)."""
    kw, args = _batch("unit", _ids([30] * 4))
    mesh = _mesh(4)
    text = _step(mesh, **kw).lower(*_state(mesh=mesh), 3.0, *args).as_text()
    assert text.count("sdy.manual_computation") == 2
    for op in ("all_reduce", "all_gather", "reduce_scatter",
               "collective_permute", "all_to_all"):
        assert f"stablehlo.{op}" not in text, op
    assert re.search(r"stablehlo\.reduce\(.*\(tensor<4x%dx%dx%dxbf16>"
                     % (B, L, W), text)


def test_rank_rows_sets_another_blocks_slots_aside():
    """The ranking alone, of a block of 4,096 rows: slots that carry the
    id R sort behind the block's, are not counted, rank at the capacity,
    and `gather_rows` reads zeros for them."""
    R, n, cap = 4096, 1024, 256
    rng = np.random.default_rng(11)
    own = rng.random(n) < 0.3
    pool = rng.choice(R, 100, replace=False)
    rows = np.where(own, rng.choice(pool, n), R).astype(np.int32)
    T = jnp.arange(R * 128, dtype=jnp.float32).reshape(R, 128) + 1.0
    ranks = jax.jit(lambda r: fm.rank_rows(r, T, {"gg": T}, OPT, cap, True))(
        jnp.asarray(rows))
    uniq, inverse = np.unique(rows[own], return_inverse=True)
    assert int(ranks.n_distinct) == len(uniq)
    urows = np.asarray(ranks.urows)
    np.testing.assert_array_equal(urows[:len(uniq)], uniq)
    assert (urows[len(uniq):] >= R).all()
    np.testing.assert_array_equal(np.asarray(ranks.srows), np.sort(rows))
    rank_of_slot = np.empty(n, np.int64)
    rank_of_slot[np.asarray(ranks.perm)] = np.asarray(ranks.rank)
    np.testing.assert_array_equal(rank_of_slot[own], inverse)
    assert (rank_of_slot[~own] == cap).all()
    got, compact = fm.gather_rows(T, jnp.asarray(rows), ranks, True)
    np.testing.assert_array_equal(
        np.asarray(got)[:, 0], np.where(own, rows * 128.0 + 1.0, 0.0))
    assert int(compact) == 1
