"""The distinct-row tail of the packed FM minibatch step and of the FFM joint
step (ops/fm.py `rows_update`) against the dense tail it replaces where a
batch touches few table rows.

Same mathematics, so: table and AdaGrad state agree at float32 rounding on
the rows a batch touched (a duplicate's addends meet in another order) and
are BIT-equal on every other row; which tail ran is what the step says it
ran. The counters the tail feeds reach the obs registry's `train` section
at the loss fold, and the dispatch path fetches nothing in between.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hivemall_tpu.ops import fm
from hivemall_tpu.ops.losses import get_loss
from hivemall_tpu.ops.optimizers import make_optimizer
from hivemall_tpu.ops.scan import make_megastep

K = 5
WF, P = fm.fm_pack_geometry(K)
B, L, R = 64, 32, 4096             # 2,048 slots into 4,096 packed rows
N = B * L
LAMS = (0.01, 0.02, 0.03)


def _opt():
    return make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                          reg="no")


def _state(rows=R, seed=1):
    rng = np.random.default_rng(seed)
    T = jnp.asarray(0.1 * rng.normal(size=(rows, P * WF)).astype(np.float32))
    gg = jnp.asarray(rng.random((rows, P * WF)).astype(np.float32))
    return ({"T": T, "w0": jnp.asarray(0.05, jnp.float32)},
            {"T": {"gg": gg}, "w0": {"gg": jnp.asarray(0.5, jnp.float32)}})


def _ids(n_distinct, seed=0, b=B, l=L, rows=R):
    """[b, l] feature ids over exactly `n_distinct` packed table rows."""
    rng = np.random.default_rng(seed)
    pool = rng.choice(np.arange(1, rows), n_distinct, replace=False)
    r = rng.choice(pool, b * l)
    r[rng.choice(b * l, n_distinct, replace=False)] = pool
    return (r * P + rng.integers(0, P, b * l)).reshape(b, l).astype(np.int32)


def _label(b=B, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.where(rng.random(b) < 0.4, 1.0, -1.0)
                       .astype(np.float32))


def _pair(idx, *, lambdas=LAMS, mask=None, extra=(), rows=R):
    """One step from the same state through both tails."""
    out = []
    for distinct in (True, False):
        step = fm.make_fm_step_minibatch(get_loss("logloss"), _opt(),
                                         lambdas, K, distinct)
        params, state = _state(rows)
        b = idx.shape[0]
        out.append(step(params, state, 3.0, jnp.asarray(idx), None,
                        _label(b), jnp.ones(b) if mask is None else mask,
                        *extra))
    return out


def _assert_same(new, ref, idx):
    touched = np.zeros(new[0]["T"].shape[0], bool)
    touched[np.unique(idx // P)] = True
    for a, b in ((new[0]["T"], ref[0]["T"]),
                 (new[1]["T"]["gg"], ref[1]["T"]["gg"])):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(a[~touched], b[~touched])
        np.testing.assert_allclose(a[touched], b[touched], rtol=2e-6,
                                   atol=1e-7)
    for a, b in ((new[0]["w0"], ref[0]["w0"]), (new[2], ref[2]),
                 (new[1]["w0"]["gg"], ref[1]["w0"]["gg"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert {k: int(v) for k, v in ref[3].items()} == {
        "tail_distinct_steps": 0, "tail_dense_steps": 1, "distinct_rows": 0,
        "gather_compact_steps": 0, "scatter_compact_steps": 0}


CAP = fm.tail_cap(N, R)


def test_capacity_of_the_test_shape():
    assert 0 < CAP < N // 2 and CAP % 128 == 0


@pytest.mark.parametrize("case,n_distinct,distinct", [
    ("heavy_duplication", 5, True),
    ("half_the_capacity", CAP // 2, True),
    ("one_under_the_capacity", CAP - 1, True),
    ("at_the_capacity", CAP, True),
    ("one_over_falls_through", CAP + 1, False),
    ("far_over_falls_through", 3 * CAP, False),
])
def test_distinct_tail_matches_dense_tail(case, n_distinct, distinct):
    idx = _ids(n_distinct)
    new, ref = _pair(idx)
    _assert_same(new, ref, idx)
    assert {k: int(v) for k, v in new[3].items()} == {
        "tail_distinct_steps": int(distinct),
        "tail_dense_steps": int(not distinct), "distinct_rows": n_distinct,
        "gather_compact_steps": int(distinct),
        "scatter_compact_steps": int(distinct)}


def test_every_slot_distinct_within_the_capacity(monkeypatch):
    """No duplicate to sum: the compact gradient is the slab, permuted."""
    monkeypatch.setattr(fm, "tail_cap", lambda n, *shape: n)
    idx = _ids(N)
    new, ref = _pair(idx)
    _assert_same(new, ref, idx)
    assert int(new[3]["tail_distinct_steps"]) == 1
    assert int(new[3]["distinct_rows"]) == N


def test_padded_batch_through_the_distinct_tail():
    """Rows past n_valid are masked out and hold id 0: packed row 0 is
    one more distinct row, with a zero gradient."""
    idx = _ids(9)
    idx[B // 2:] = 0
    mask = (jnp.arange(B) < B // 2).astype(jnp.float32)
    new, ref = _pair(idx, mask=mask)
    _assert_same(new, ref, idx)
    assert int(new[3]["tail_distinct_steps"]) == 1
    assert int(new[3]["distinct_rows"]) == len(np.unique(idx // P))
    row0 = np.asarray(new[0]["T"])[0]
    np.testing.assert_array_equal(row0, np.asarray(_state()[0]["T"])[0])


def test_dynamic_lambdas_through_the_distinct_tail():
    """-adareg's variant: lambdas arrive as a step argument."""
    idx = _ids(12)
    lams = jnp.asarray(LAMS, jnp.float32)
    new, ref = _pair(idx, lambdas=None, extra=(lams,))
    _assert_same(new, ref, idx)
    assert int(new[3]["tail_distinct_steps"]) == 1
    fixed, _ = _pair(idx)
    np.testing.assert_allclose(np.asarray(new[0]["T"]),
                               np.asarray(fixed[0]["T"]), rtol=2e-6,
                               atol=1e-7)


def test_small_table_picks_the_dense_tail_statically():
    """The toy config: 4,096 packed rows against 256 x 39 = 9,984 slots.
    The step holds no ranking at all: no sort in its program."""
    b, l = 256, 39
    assert fm.tail_cap(b * l, R) == 0
    idx = _ids(300, b=b, l=l)
    new, ref = _pair(idx)
    _assert_same(new, ref, idx)
    assert {k: int(v) for k, v in new[3].items()} == {
        "tail_distinct_steps": 0, "tail_dense_steps": 1, "distinct_rows": 0,
        "gather_compact_steps": 0, "scatter_compact_steps": 0}
    step = fm.make_fm_step_minibatch(get_loss("logloss"), _opt(), LAMS, K)
    params, state = _state()
    text = step.lower(params, state, 3.0, jnp.asarray(idx), None, _label(b),
                      jnp.ones(b)).as_text()
    assert "stablehlo.sort" not in text and "stablehlo.case" not in text


def test_capacity_follows_the_shapes():
    n = 32768 * 39                                   # the benchmark's cells
    cell = fm.tail_cap(n, 1 << 22)
    # [4194304,128] float32: one pass of the compact sum (the two tails
    # cost the same at ~284k, but a second pass costs more than the dense
    # tail, PR 38)
    assert cell == 196_608 == fm.scatter_cap(n, 128)
    assert 161_600 < cell < n // 2                   # holds Zipf 1.05's batch
    flagship = fm.tail_cap(n, 1 << 22, 164, 2)       # [4194304,164] bfloat16
    assert 73_300 < flagship < cell and flagship % 2048 == 0
    assert flagship > fm.scatter_cap(n, 164)         # two passes past 98,304
    # a shape nobody read: the flagship's readings by the bytes of a row
    assert 0 < fm.tail_cap(n, 1 << 22, 128, 2) < flagship
    assert fm.tail_cap(n, n // 4) == 0               # table under the batch
    assert fm.tail_cap(n, 2 ** 31 - n) == 0          # pad ids would overflow
    assert fm.tail_cap(4096, 1 << 30) == 4096        # never over n
    assert fm.tail_cap(n, 1 << 30) == 196_608        # nor over the passes
    assert fm.tail_cap(n, 1 << 30, 164, 2) == 2 * 98_304
    caps = [fm.tail_cap(n, r) for r in (1 << 20, 1 << 21, 1 << 22, 1 << 23)]
    assert caps == sorted(caps) and all(c % 128 == 0 for c in caps)


@pytest.mark.parametrize("rows", ["xla", "kernel"])
def test_megastep_of_four_equals_four_single_steps(rows, monkeypatch):
    """The K-step scan runs the step's own core, stats included; with the
    row kernel in it (interpreted), as a TPU's megastep has."""
    if rows == "kernel":
        from hivemall_tpu.ops import rows_pallas
        monkeypatch.setattr(fm, "update_rows",
                            partial(rows_pallas.update_rows, interpret=True))
    step = fm.make_fm_step_minibatch(get_loss("logloss"), _opt(), LAMS, K)
    ks = 4
    nds = (6, CAP - 3, CAP + 5, CAP)
    idx = np.stack([_ids(nd, seed=10 + i) for i, nd in enumerate(nds)])
    label = jnp.stack([_label(seed=20 + i) for i in range(ks)])
    nv = np.asarray([B, B, B - 5, B], np.int32)

    params, state = _state()
    losses, stats = [], []
    for i in range(ks):
        mask = (jnp.arange(B) < nv[i]).astype(jnp.float32)
        params, state, ls, st = step(params, state, 7.0 + i,
                                     jnp.asarray(idx[i]), None, label[i],
                                     mask)
        losses.append(float(ls))
        stats.append({k: int(v) for k, v in st.items()})

    mega = make_megastep(step.core, none_val=True)
    p2, s2 = _state()
    p2, s2, ls2, st2 = mega(p2, s2, 7.0, jnp.asarray(nv), jnp.asarray(idx),
                            None, label, None, None)
    np.testing.assert_array_equal(np.asarray(ls2), np.asarray(losses,
                                                              np.float32))
    for a, b in ((p2["T"], params["T"]), (s2["T"]["gg"], state["T"]["gg"]),
                 (p2["w0"], params["w0"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in fm.TAIL_STATS:
        assert [int(v) for v in st2[name]] == [s[name] for s in stats]
    assert [s["tail_distinct_steps"] for s in stats] == [1, 1, 0, 1]


def _assert_bf16_close(new, ref, touched):
    """A bfloat16 table through both tails: bit-equal where no slot
    pointed, and where one did the float32 result, rounded once on either
    side, a bfloat16 ulp apart at most (and rarely)."""
    a, b = np.asarray(new, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_array_equal(a[~touched], b[~touched])
    np.testing.assert_allclose(a[touched], b[touched], rtol=2.0 ** -7)
    assert (a[touched] == b[touched]).mean() > 0.99


@pytest.mark.parametrize("scatter", ["xla", "kernel"])
@pytest.mark.parametrize("width", [128, 164])
def test_bfloat16_table_takes_the_distinct_tail(width, scatter, monkeypatch):
    """-halffloat: a bfloat16 table beside its float32 accumulators. The
    rows the list names are read, widened to float32, updated and rounded
    ONCE on the way back, where the dense pass rounds: same table on the
    rows no slot points to, bit for bit, and on the touched ones to the
    order of a duplicate's float32 sum; the compact gradient summed by
    XLA's scatter-add or by the kernel (interpreted) alike."""
    if scatter == "kernel":
        from hivemall_tpu.ops import rows_pallas
        monkeypatch.setattr(fm, "scatter_rows", partial(
            rows_pallas.scatter_rows, interpret=True))
    rows_n, n = 16384, 2048
    rng = np.random.default_rng(width)
    T = jnp.asarray(0.1 * rng.normal(size=(rows_n, width)), jnp.bfloat16)
    gg = jnp.asarray(rng.random((rows_n, width)).astype(np.float32))
    pool = rng.choice(rows_n, 150, replace=False)
    rows = jnp.asarray(rng.choice(pool, n).astype(np.int32))
    g = jnp.asarray(rng.normal(size=(n, width)).astype(np.float32))
    cap = fm.tail_cap(n, rows_n, width, 2)
    assert cap > 150
    out = [jax.jit(lambda T, s, c=c: fm.rows_update(
        T, s, rows, g, _opt(), 3.0, fm.rank_rows(rows, T, s, _opt(), c)))(
            T, {"gg": gg}) for c in (None, 0)]
    (Tn, sn, stats), (Td, sd, dense_stats) = out
    assert Tn.dtype == jnp.bfloat16 and sn["gg"].dtype == jnp.float32
    assert {k: int(v) for k, v in stats.items()} == {
        "tail_distinct_steps": 1, "tail_dense_steps": 0,
        "distinct_rows": len(np.unique(np.asarray(rows))),
        "scatter_compact_steps": 1}
    assert int(dense_stats["tail_dense_steps"]) == 1
    touched = np.zeros(rows_n, bool)
    touched[np.asarray(rows)] = True
    _assert_bf16_close(Tn, Td, touched)
    assert not np.array_equal(np.asarray(Tn, np.float32)[touched],
                              np.asarray(T, np.float32)[touched])
    a, b = np.asarray(sn["gg"]), np.asarray(sd["gg"])
    np.testing.assert_array_equal(a[~touched], b[~touched])
    np.testing.assert_allclose(a[touched], b[touched], rtol=2e-6)


# -- the row kernel (interpret mode here; compiled for a v5e by
# tests/test_tpu_aot_compile.py) -----------------------------------------------

def _optimizer_fn(opt, names):
    """What rows_update hands update_rows: the optimizer's own update over
    (w, *state leaves), the leaves in `names` order."""
    def fn(blocks, g, t):
        w, s = opt.update(blocks[0], g, dict(zip(names, blocks[1:])), t)
        return (w, *(s[k] for k in names))
    return fn


def _assert_rows_updated(opt, names, cap, n_live, rows_total=8192):
    """The kernel against XLA's gather -> optimizer.update -> scatter: the
    list's live rows agree to float32 rounding, every other row of every
    table is bit-equal to what went in."""
    from hivemall_tpu.ops.rows_pallas import update_rows
    rng = np.random.default_rng(cap + n_live)
    tables = tuple(
        jnp.asarray((0.1 + rng.random((rows_total, 128))).astype(np.float32))
        for _ in range(1 + len(names)))
    live = np.sort(rng.choice(rows_total, n_live, replace=False))
    ids = jnp.asarray(np.concatenate(
        [live, rows_total + np.arange(cap - n_live)]).astype(np.int32))
    g = np.zeros((cap, 128), np.float32)
    g[:n_live] = rng.normal(size=(n_live, 128))
    fn = _optimizer_fn(opt, names)
    got = jax.jit(lambda *a: update_rows(*a, fn, interpret=True))(
        tables, ids, jnp.asarray(n_live, jnp.int32), jnp.asarray(g),
        jnp.asarray(3.0, jnp.float32))
    want = [np.asarray(a).copy() for a in tables]
    new = fn(tuple(a[live] for a in want), g[:n_live], 3.0)
    for a, u in zip(want, new):
        a[live] = np.asarray(u)
    assert len(got) == len(tables)
    untouched = np.ones(rows_total, bool)
    untouched[live] = False
    for a, b, before in zip(got, want, tables):
        a = np.asarray(a)
        np.testing.assert_array_equal(a[untouched],
                                      np.asarray(before)[untouched])
        np.testing.assert_allclose(a[live], b[live], rtol=2e-6, atol=1e-7)
        assert not n_live or not np.array_equal(a[live],
                                                np.asarray(before)[live])


@pytest.mark.parametrize("cap,n_live", [(128, 0), (128, 1), (128, 128),
                                        (384, 200), (2048, 2047),
                                        (4096, 2049)])
def test_row_kernel_updates_the_live_rows_and_no_others(cap, n_live):
    """0 live, 1, a full list, a partial block after full ones (three
    blocks of 128: both slots reused), 2047 of 2048, one row into a second
    block."""
    _assert_rows_updated(_opt(), ("gg",), cap, n_live)


@pytest.mark.parametrize("name,reg,names", [
    ("sgd", "no", ()),                               # a state with no leaf
    ("adagrad", "rda", ("gg", "u")),                 # and one with two
])
def test_row_kernel_is_generic_over_the_state_leaves(name, reg, names):
    opt = make_optimizer(name, eta_scheme="inverse", eta0=0.1, reg=reg)
    assert sorted(opt.init((1,))) == sorted(names)
    _assert_rows_updated(opt, names, 384, 300)


@pytest.mark.parametrize("case,rows,dtype,n_ids,match", [
    ("list_not_whole_id_tiles", 256, jnp.float32, 100, "multiple of 128"),
    ("list_longer_than_the_table", 128, jnp.float32, 256, "into a table"),
    # what Mosaic refuses (tests/tpu_aot_worker.py ffm_joint_megastep), the
    # kernel refuses by name; update_rows takes such tables through XLA
    ("rows_of_half_words", 256, jnp.bfloat16, 128, "32-bit"),
    ("rows_of_164_lanes", 256, jnp.float32, 128, "128 lanes"),
])
def test_row_kernel_refuses(case, rows, dtype, n_ids, match):
    from hivemall_tpu.ops.rows_pallas import update_rows
    width = 164 if "164" in case else 128
    with pytest.raises(ValueError, match=match):
        update_rows((jnp.zeros((rows, width), dtype),),
                    jnp.zeros((n_ids,), jnp.int32), jnp.asarray(3),
                    jnp.zeros((n_ids, width)), 0.0, lambda b, g, t: b,
                    interpret=True)


@pytest.mark.parametrize("cap,n_live", [(384, 0), (384, 1), (384, 200),
                                        (384, 384), (8192, 5000)])
def test_xla_rows_update_half_word_rows_of_164_lanes(cap, n_live,
                                                     monkeypatch):
    """The flagship's tables, a [R, 164] bfloat16 table beside a float32
    leaf, through update_rows' XLA blocks (three blocks of 128 here; the
    shipped 8192 in the last case): the live rows equal gather -> update ->
    scatter to rounding, and every other row, a live row's SUBLANE PARTNER (the row
    that shares its 32-bit words, r ^ 1) first of all, is bit-identical to
    what went in. No trip runs past the count."""
    from hivemall_tpu.ops import rows_pallas
    if cap < 8192:
        monkeypatch.setattr(rows_pallas, "XLA_BLOCK_ROWS", 128)
    rows_total, width = 16384, 164
    rng = np.random.default_rng(cap + n_live)
    T = jnp.asarray(0.1 + rng.random((rows_total, width)), jnp.bfloat16)
    gg = jnp.asarray((0.1 + rng.random((rows_total, width)))
                     .astype(np.float32))
    # even rows only, so that every live row's partner is a dead row
    live = np.sort(rng.choice(rows_total // 2, n_live, replace=False)) * 2
    ids = jnp.asarray(np.concatenate(
        [live, rows_total + np.arange(cap - n_live)]).astype(np.int32))
    g = np.zeros((cap, width), np.float32)
    g[:n_live] = rng.normal(size=(n_live, width))
    opt = _opt()

    def fn(blocks, g, t):
        w, s = opt.update(blocks[0].astype(jnp.float32), g,
                          {"gg": blocks[1]}, t)
        return w, s["gg"]
    trips = []
    real = jax.lax.fori_loop
    monkeypatch.setattr(jax.lax, "fori_loop", lambda lo, hi, body, init:
                        trips.append(hi) or real(lo, hi, body, init))
    Tn, ggn = rows_pallas.update_rows(
        (T, gg), ids, jnp.asarray(n_live, jnp.int32), jnp.asarray(g), 3.0,
        fn)
    block = 128 if cap < 8192 else 8192
    assert [int(h) for h in trips] == [-(-n_live // block)]
    want_T, want_gg = fn((T[live], gg[live]), g[:n_live],
                         jnp.full((1, width), 3.0))
    assert Tn.dtype == jnp.bfloat16 and ggn.dtype == jnp.float32
    # (to rounding: the loop's fused update contracts a multiply-add)
    np.testing.assert_allclose(
        np.asarray(Tn[live], np.float32),
        np.asarray(want_T.astype(jnp.bfloat16), np.float32), rtol=2.0 ** -7)
    np.testing.assert_allclose(np.asarray(ggn[live]), np.asarray(want_gg),
                               rtol=2e-6)
    dead = np.ones(rows_total, bool)
    dead[live] = False
    for new, old in ((Tn, T), (ggn, gg)):
        new, old = np.asarray(new, np.float32), np.asarray(old, np.float32)
        np.testing.assert_array_equal(new[live + 1], old[live + 1])
        np.testing.assert_array_equal(new[dead], old[dead])
        assert not n_live or not np.array_equal(new[live], old[live])


def test_step_with_the_row_kernel_matches_the_xla_rows(monkeypatch):
    """What a TPU runs: the distinct rows through the kernel, the rest
    unchanged."""
    idx = _ids(CAP - 7)
    ref, _ = _pair(idx)
    from hivemall_tpu.ops import rows_pallas
    monkeypatch.setattr(fm, "update_rows",
                        partial(rows_pallas.update_rows, interpret=True))
    step = fm.make_fm_step_minibatch(get_loss("logloss"), _opt(), LAMS, K)
    params, state = _state()
    new = step(params, state, 3.0, jnp.asarray(idx), None, _label(),
               jnp.ones(B))
    assert int(new[3]["tail_distinct_steps"]) == 1
    for a, b in ((new[0]["T"], ref[0]["T"]),
                 (new[1]["T"]["gg"], ref[1]["T"]["gg"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6,
                                   atol=1e-7)


# -- the FFM joint step through the same tail ---------------------------------

FF, FK_, FB, FL, FR = 8, 4, 64, 16, 16384    # 1,024 slots, rows of 40 lanes


def _ffm_state(dtype=jnp.bfloat16, seed=2):
    rng = np.random.default_rng(seed)
    W = FF * FK_ + 8
    T = jnp.asarray(0.1 * rng.normal(size=(FR, W)), dtype)
    gg = jnp.asarray(rng.random((FR, W)).astype(np.float32))
    return ({"T": T, "w0": jnp.asarray(0.05, jnp.float32)},
            {"T": {"gg": gg}, "w0": {"gg": jnp.asarray(0.5, jnp.float32)}})


def _ffm_batch(kind, n_ids=120, seed=4):
    """(step kwargs, batch args after t) of one [FB, FL] batch over
    `n_ids` feature ids: field-major with unit values elided, field-major
    with values, the pairs path with its field array, or the unit batch
    with its second half padding (id 0, masked out)."""
    rng = np.random.default_rng(seed)
    idx = rng.choice(rng.choice(np.arange(1, 1 << 20), n_ids, replace=False),
                     (FB, FL)).astype(np.int32)
    mask = np.ones(FB, np.float32)
    if kind == "padded":
        idx[FB // 2:] = 0
        mask[FB // 2:] = 0.0
    val = (0.5 + rng.random((FB, FL))).astype(np.float32)
    field = np.tile(np.arange(FL, dtype=np.int32) % FF, (FB, 1))
    label, mask = _label(FB), jnp.asarray(mask)
    idx = jnp.asarray(idx)
    if kind in ("unit", "padded"):
        return dict(fieldmajor=True, unit_val=True), (idx, label, mask)
    if kind == "valued":
        return dict(fieldmajor=True), (idx, jnp.asarray(val), label, mask)
    return {}, (idx, jnp.asarray(val), label, mask, jnp.asarray(field))


def _ffm_step(opt=None, **kw):
    return fm.make_ffm_step_fused(get_loss("logloss"), opt or _opt(), LAMS,
                                  FF, FK_, **kw)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["halffloat", "float32"])
@pytest.mark.parametrize("kind", ["unit", "valued", "pairs", "padded"])
def test_ffm_joint_step_distinct_tail_matches_its_dense_tail(kind, dtype):
    kw, args = _ffm_batch(kind)
    new, ref = (_ffm_step(distinct_tail=d, **kw)(*_ffm_state(dtype), 3.0,
                                                 *args)
                for d in (True, False))
    rows = np.unique(np.asarray(fm.ffm_row_hash(args[0], FR)))
    assert {k: int(v) for k, v in new[3].items()} == {
        "tail_distinct_steps": 1, "tail_dense_steps": 0,
        "distinct_rows": len(rows), "gather_compact_steps": 1,
        "scatter_compact_steps": 1}
    assert {k: int(v) for k, v in ref[3].items()} == {
        "tail_distinct_steps": 0, "tail_dense_steps": 1, "distinct_rows": 0,
        "gather_compact_steps": 0, "scatter_compact_steps": 0}
    touched = np.zeros(FR, bool)
    touched[rows] = True
    assert new[0]["T"].dtype == dtype
    if dtype == jnp.bfloat16:
        _assert_bf16_close(new[0]["T"], ref[0]["T"], touched)
    else:
        a, b = np.asarray(new[0]["T"]), np.asarray(ref[0]["T"])
        np.testing.assert_array_equal(a[~touched], b[~touched])
        np.testing.assert_allclose(a[touched], b[touched], rtol=2e-6,
                                   atol=1e-7)
    a, b = np.asarray(new[1]["T"]["gg"]), np.asarray(ref[1]["T"]["gg"])
    np.testing.assert_array_equal(a[~touched], b[~touched])
    np.testing.assert_allclose(a[touched], b[touched], rtol=2e-6, atol=1e-7)
    for a, b in ((new[0]["w0"], ref[0]["w0"]), (new[2], ref[2]),
                 (new[1]["w0"]["gg"], ref[1]["w0"]["gg"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name,reg", [("adam", "no"), ("ftrl", "no"),
                                      ("adagrad", "rda"), ("adagrad", "l2")])
def test_an_optimizer_that_moves_a_zero_gradient_row_keeps_the_dense_tail(
        name, reg):
    """`train_ffm` takes any -opt: Adam decays its moments, FTRL and RDA
    rebuild w from their sums at every t, a regularizer pulls on every
    weight. Their dense pass visits every row, and still does: the step's
    program holds no ranking."""
    opt = make_optimizer(name, eta_scheme="inverse", eta0=0.1, reg=reg,
                         lam=1e-3)
    assert not opt.zero_grad_noop and _opt().zero_grad_noop
    assert make_optimizer("sgd", reg="no").zero_grad_noop
    kw, args = _ffm_batch("unit")
    step = _ffm_step(opt, **kw)
    params, _ = _ffm_state(jnp.float32)
    state = {k: opt.init(v.shape) for k, v in params.items()}
    text = step.lower(params, state, 3.0, *args).as_text()
    assert "stablehlo.sort" not in text and "stablehlo.case" not in text
    before = np.asarray(params["T"]).copy()
    new = step(params, state, 3.0, *args)
    assert {k: int(v) for k, v in new[3].items()} == {
        "tail_distinct_steps": 0, "tail_dense_steps": 1, "distinct_rows": 0,
        "gather_compact_steps": 0, "scatter_compact_steps": 0}
    assert np.isfinite(np.asarray(new[0]["T"])).all()
    assert not np.array_equal(np.asarray(new[0]["T"]), before)


def test_ffm_megastep_of_four_equals_four_single_steps():
    """The K-step scan runs the joint step's own core, stats included: one
    step of the four falls through to the dense branch."""
    kw = dict(fieldmajor=True, unit_val=True)
    step = _ffm_step(**kw)
    cap = fm.tail_cap(FB * FL, FR, FF * FK_ + 8, 2)
    nds = (30, cap - 40, cap + 200, 90)
    batches = [_ffm_batch("unit", nd, seed=30 + i)[1]
               for i, nd in enumerate(nds)]
    nv = np.asarray([FB, FB, FB - 5, FB], np.int32)
    params, state = _ffm_state()
    losses, stats = [], []
    for i, (idx, label, _) in enumerate(batches):
        mask = (jnp.arange(FB) < nv[i]).astype(jnp.float32)
        params, state, ls, st = step(params, state, 7.0 + i, idx, label,
                                     mask)
        losses.append(float(ls))
        stats.append({k: int(v) for k, v in st.items()})
    mega = make_megastep(step.core)
    p2, s2 = _ffm_state()
    p2, s2, ls2, st2 = mega(
        p2, s2, 7.0, jnp.asarray(nv), jnp.stack([b[0] for b in batches]),
        None, jnp.stack([b[1] for b in batches]), None, None)
    np.testing.assert_array_equal(np.asarray(ls2),
                                  np.asarray(losses, np.float32))
    for a, b in ((p2["T"], params["T"]), (s2["T"]["gg"], state["T"]["gg"]),
                 (p2["w0"], params["w0"])):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    for name in fm.TAIL_STATS:
        assert [int(v) for v in st2[name]] == [s[name] for s in stats]
    assert [s["tail_distinct_steps"] for s in stats] == [1, 1, 0, 1]


# -- the gather through the distinct rows --------------------------------------

def _direct_gather(monkeypatch):
    """The steps built under it gather `T[rows]` whatever was ranked: no
    room for a compact table."""
    monkeypatch.setattr(fm, "COMPACT_TABLE_BYTES", 0)


def _assert_bit_equal(new, ref):
    """Two steps' (params, state, loss, stats), leaf by leaf, bit for bit:
    a gather is exact, and so is the tail's sum in one pass or two (a
    row's slots meet in slot order either way), so nothing may differ but
    the counts of how they read."""
    new, ref = ([*out[:3], {k: v for k, v in out[3].items() if k not in (
        "gather_compact_steps", "scatter_compact_steps")}]
                for out in (new, ref))
    a, b = jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(ref)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def _fm_both_gathers(monkeypatch, idx, mask=None, block=None):
    """One packed FM step from the same state, reading through the
    distinct rows and reading the table directly; (compact, direct)."""
    def run():
        step = fm.make_fm_step_minibatch(get_loss("logloss"), _opt(), LAMS, K)
        b = idx.shape[0]
        return step(*_state(), 3.0, jnp.asarray(idx), None, _label(b),
                    jnp.ones(b) if mask is None else mask)
    if block:
        monkeypatch.setattr(fm, "FILL_BLOCK_ROWS", block)
    new = run()
    _direct_gather(monkeypatch)
    return new, run()


@pytest.mark.parametrize("case,n_distinct,block", [
    ("heavy_duplication", 5, None),
    ("three_blocks_and_a_part", 3 * 128 + 17, 128),
    ("whole_blocks", 4 * 128, 128),
    ("last_block_overlaps", CAP - 1, CAP // 2 + 128),
    ("at_the_capacity", CAP, None),
    ("one_over_reads_the_table", CAP + 1, None),
    ("far_over_reads_the_table", 3 * CAP, None),
])
def test_fm_step_through_the_distinct_rows_is_the_direct_gather(
        case, n_distinct, block, monkeypatch):
    """The compact gather re-addresses reads: table, state, loss and the
    counters are those of `T[rows]` to the bit, in whole blocks of the fill
    and in parts, and over the capacity, where the step reads the table
    directly and takes the dense tail."""
    idx = _ids(n_distinct)
    new, ref = _fm_both_gathers(monkeypatch, idx, block=block)
    _assert_bit_equal(new, ref)
    fits = int(n_distinct <= CAP)
    assert {k: int(v) for k, v in new[3].items()} == {
        "tail_distinct_steps": fits, "tail_dense_steps": 1 - fits,
        "distinct_rows": n_distinct, "gather_compact_steps": fits,
        "scatter_compact_steps": fits}
    assert int(ref[3]["gather_compact_steps"]) == 0


def test_gather_capacity_follows_the_compact_tables_bytes(monkeypatch):
    """The compact table is sized to stay in the chip's fast memory: the
    benchmark's cells get 196,608 rows of it, the packed FM table all its
    ranking lists (since PR 38 as many) and the flagship's all of them; a
    ranking never lists fewer than the gather reads through."""
    n = 32768 * 39
    fm_cap, ffm_cap = fm.tail_cap(n, 1 << 22), fm.tail_cap(n, 1 << 22, 164, 2)
    assert fm.gather_cap(fm_cap, 128, 4) == 196_608 == fm_cap
    assert fm.gather_cap(282_624, 128, 4) == 196_608
    assert fm.gather_cap(ffm_cap, 164, 2) == ffm_cap < 196_608
    assert 161_600 < fm.gather_cap(fm_cap, 128, 4)   # Zipf 1.05's batch
    assert fm.gather_cap(4096, 128, 4) == 4096
    assert fm.gather_cap(fm_cap, 640, 4) % 128 == 0
    monkeypatch.setattr(fm, "COMPACT_TABLE_BYTES", 0)
    assert fm.gather_cap(fm_cap, 128, 4) == 0


@pytest.mark.parametrize("case,n_distinct,compact", [
    ("within_both", 120, 1), ("at_the_gathers", 128, 1),
    ("over_the_gathers_alone", 129, 0), ("at_the_tails", CAP, 0)])
def test_a_batch_between_the_capacities_keeps_the_distinct_tail(
        case, n_distinct, compact, monkeypatch):
    """A compact table smaller than the ranking (the packed FM table's in
    the benchmark): a batch it cannot hold reads the table directly and
    still updates its distinct rows alone, and is counted as both; the
    tail's sum, sized by the scatter kernel's own budget, is one pass."""
    monkeypatch.setattr(fm, "COMPACT_TABLE_BYTES", 128 * 128 * 4)
    assert 128 < CAP
    new, ref = _fm_both_gathers(monkeypatch, _ids(n_distinct))
    _assert_bit_equal(new, ref)
    assert {k: int(v) for k, v in new[3].items()} == {
        "tail_distinct_steps": 1, "tail_dense_steps": 0,
        "distinct_rows": n_distinct, "gather_compact_steps": compact,
        "scatter_compact_steps": 1}


def test_fm_every_slot_distinct_reads_through_the_distinct_rows(monkeypatch):
    monkeypatch.setattr(fm, "tail_cap", lambda n, *shape: n)
    new, ref = _fm_both_gathers(monkeypatch, _ids(N), block=512)
    _assert_bit_equal(new, ref)
    assert int(new[3]["tail_distinct_steps"]) == 1
    assert int(new[3]["distinct_rows"]) == N


def test_fm_padded_slots_read_row_zero_through_the_distinct_rows(monkeypatch):
    """Padded rows hold id 0: packed row 0 is the batch's first distinct
    row, rank 0, and every padded slot reads it from there."""
    idx = _ids(9)
    idx[B // 2:] = 0
    idx[:, L - 3:] = 0                               # short rows too
    mask = (jnp.arange(B) < B // 2).astype(jnp.float32)
    new, ref = _fm_both_gathers(monkeypatch, idx, mask=mask)
    _assert_bit_equal(new, ref)
    np.testing.assert_array_equal(np.asarray(new[0]["T"])[0],
                                  np.asarray(_state()[0]["T"])[0])


def test_the_gathers_the_program_holds(monkeypatch):
    """A step with the ranking has three sorts (ranking, distinct ids, the
    ranks carried back to slot order) and three `cond`s (the list of
    distinct rows, the gather, the tail); with no room for a compact table
    three sorts still (the tail's scatter-add reads the slot-order ranks
    too) and two `cond`s; without a ranking (`-mesh`), none."""
    def text(**kw):
        step = fm.make_fm_step_minibatch(get_loss("logloss"), _opt(), LAMS, K,
                                         **kw)
        return step.lower(*_state(), 3.0, jnp.asarray(_ids(9)), None,
                          _label(), jnp.ones(B)).as_text()
    compact, dense = text(), text(distinct_tail=False)
    _direct_gather(monkeypatch)
    direct = text()
    assert [t.count("stablehlo.sort") for t in (compact, direct, dense)] \
        == [3, 3, 0]
    assert [t.count("stablehlo.case") for t in (compact, direct, dense)] \
        == [3, 2, 0]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["halffloat", "float32"])
@pytest.mark.parametrize("kind", ["unit", "valued", "pairs", "padded"])
def test_ffm_joint_step_through_the_distinct_rows_is_the_direct_gather(
        kind, dtype, monkeypatch):
    kw, args = _ffm_batch(kind)
    monkeypatch.setattr(fm, "FILL_BLOCK_ROWS", 128)  # two blocks of C
    new = _ffm_step(**kw)(*_ffm_state(dtype), 3.0, *args)
    _direct_gather(monkeypatch)
    ref = _ffm_step(**kw)(*_ffm_state(dtype), 3.0, *args)
    _assert_bit_equal(new, ref)
    assert int(new[3]["tail_distinct_steps"]) == 1
    assert new[0]["T"].dtype == dtype


def test_ffm_batch_over_the_capacity_reads_the_table(monkeypatch):
    kw, args = _ffm_batch("unit", n_ids=600)
    cap = fm.tail_cap(FB * FL, FR, FF * FK_ + 8, 2)
    new = _ffm_step(**kw)(*_ffm_state(), 3.0, *args)
    assert int(new[3]["distinct_rows"]) > cap
    assert int(new[3]["tail_dense_steps"]) == 1
    _direct_gather(monkeypatch)
    _assert_bit_equal(new, _ffm_step(**kw)(*_ffm_state(), 3.0, *args))


@pytest.mark.parametrize("family", ["fm", "ffm"])
def test_megastep_through_the_distinct_rows_is_the_direct_gather(
        family, monkeypatch):
    """Four steps in one scan, the third over the capacity: the carry a
    step hands the next is the direct gather's, bit for bit."""
    ks = 4
    if family == "fm":
        make = lambda: fm.make_fm_step_minibatch(      # noqa: E731
            get_loss("logloss"), _opt(), LAMS, K)
        nds, state, none_val = (6, CAP - 3, CAP + 5, CAP), _state, True
        idx = np.stack([_ids(nd, seed=10 + i) for i, nd in enumerate(nds)])
        label = jnp.stack([_label(seed=20 + i) for i in range(ks)])
        nv = np.asarray([B, B, B - 5, B], np.int32)
    else:
        make = lambda: _ffm_step(fieldmajor=True, unit_val=True)  # noqa: E731
        cap = fm.tail_cap(FB * FL, FR, FF * FK_ + 8, 2)
        nds, state, none_val = (30, cap - 40, cap + 200, 90), _ffm_state, \
            False
        batches = [_ffm_batch("unit", nd, seed=30 + i)[1]
                   for i, nd in enumerate(nds)]
        idx = np.stack([np.asarray(b[0]) for b in batches])
        label = jnp.stack([b[1] for b in batches])
        nv = np.asarray([FB, FB, FB - 5, FB], np.int32)

    def run():
        return make_megastep(make().core, none_val=none_val)(
            *state(), 7.0, jnp.asarray(nv), jnp.asarray(idx), None, label,
            None, None)
    new = run()
    _direct_gather(monkeypatch)
    _assert_bit_equal(new, run())
    assert [int(v) for v in new[3]["tail_distinct_steps"]] == [1, 1, 0, 1]


@pytest.mark.parametrize("case,n,rows_n,cap,n_distinct", [
    ("duplicates", 2048, 4096, 256, 150),
    ("one_row", 512, 4096, 128, 1),
    ("at_the_capacity", 1024, 4096, 384, 384),
    ("over_the_capacity", 1024, 4096, 128, 300),
    ("every_slot_distinct", 1024, 4096, 1024, 1024),
])
def test_rank_rows_against_numpy(case, n, rows_n, cap, n_distinct):
    """The ranking alone: `urows[:n]` are numpy's sorted unique rows and
    the rest out of range, a slot's rank in slot order (the sorted slots'
    rank carried back through `perm`) is numpy's inverse, `fits` compares
    the count with the capacity."""
    rng = np.random.default_rng(n_distinct)
    pool = rng.choice(rows_n, n_distinct, replace=False)
    rows = rng.choice(pool, n)
    rows[rng.choice(n, n_distinct, replace=False)] = pool
    T = jnp.zeros((rows_n, 128), jnp.float32)
    ranks = jax.jit(lambda r: fm.rank_rows(r, T, {"gg": T}, _opt(), cap))(
        jnp.asarray(rows.astype(np.int32)))
    uniq, inverse = np.unique(rows, return_inverse=True)
    assert int(ranks.n_distinct) == len(uniq) == n_distinct
    urows = np.asarray(ranks.urows)
    assert urows.shape == (cap,)
    np.testing.assert_array_equal(np.asarray(ranks.srows), np.sort(rows))
    np.testing.assert_array_equal(rows[np.asarray(ranks.perm)],
                                  np.sort(rows))
    if n_distinct <= cap:              # over it nothing reads the list
        np.testing.assert_array_equal(urows[:n_distinct], uniq)
        assert (urows[n_distinct:] >= rows_n).all()
        np.testing.assert_array_equal(np.asarray(ranks.slot_rank), inverse)
    got, compact = fm.gather_rows(
        jnp.arange(rows_n * 128, dtype=jnp.float32).reshape(rows_n, 128),
        jnp.asarray(rows), ranks)
    np.testing.assert_array_equal(np.asarray(got)[:, 0], rows * 128.0)
    assert int(compact) == (n_distinct <= cap)


def test_no_ranking_where_the_dense_tail_is_static():
    T = jnp.zeros((4096, 128), jnp.float32)
    rows = jnp.zeros((9984,), jnp.int32)
    assert fm.rank_rows(rows, T, {"gg": T}, _opt()) is None   # small table
    assert fm.rank_rows(rows[:2048], T, {"gg": T}, _opt(), 0) is None
    adam = make_optimizer("adam", eta_scheme="inverse", eta0=0.1, reg="no")
    assert fm.rank_rows(rows[:2048], T, adam.init(T.shape), adam) is None
    got, compact = fm.gather_rows(T + 1.0, rows[:8].reshape(2, 4), None)
    assert got.shape == (2, 4, 128) and float(got.min()) == 1.0
    assert int(compact) == 0


# -- the counters -------------------------------------------------------------

def _trainer_stream(n_batches, bsz, l, dims, n_distinct):
    from hivemall_tpu.io.sparse import SparseDataset
    rng = np.random.default_rng(5)
    pool = rng.choice(np.arange(P, dims), n_distinct, replace=False)
    n = n_batches * bsz
    idx = rng.choice(pool, (n, l)).astype(np.int32)
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    indptr = np.arange(0, n * l + 1, l, dtype=np.int64)
    return SparseDataset(idx.ravel(), indptr, np.ones(n * l, np.float32), lab)


@pytest.mark.parametrize("k", [1, 4])
def test_tail_counters_fold_with_the_loss_and_nothing_fetches_between(
        k, monkeypatch):
    """512 steps over duplicated ids: every step is counted as one tail or
    the other, the counts reach the registry's `train` section, and the
    dispatch path converts a device value to a host one only inside the
    loss fold (every 256 steps, and once when the count is asked for)."""
    from hivemall_tpu.models import base
    from hivemall_tpu.models.fm import FMTrainer
    from hivemall_tpu.obs.registry import registry

    steps, bsz, l, dims = 512, 32, 8, R * P
    ds = _trainer_stream(steps, bsz, l, dims, n_distinct=12)
    t = FMTrainer(f"-dims {dims} -factors {K} -opt adagrad -classification "
                  f"-mini_batch {bsz} -steps_per_dispatch {k}")

    fetches, in_fold = [], []
    real_fetch, real_fold = base._fetch, t._fold_loss

    def fetch(tree):
        fetches.append(bool(in_fold))
        return real_fetch(tree)

    def fold():
        in_fold.append(1)
        try:
            real_fold()
        finally:
            in_fold.pop()

    monkeypatch.setattr(base, "_fetch", fetch)
    monkeypatch.setattr(t, "_fold_loss", fold)
    t.fit_stream(ds.batches(bsz, shuffle=False))
    assert t._t == steps
    assert len(fetches) == 2 and all(fetches)      # folds at 256 and 512
    counts = dict(t._step_counts)
    assert counts["tail_distinct_steps"] + counts["tail_dense_steps"] == steps
    assert counts["tail_distinct_steps"] == steps  # 12 rows fit the rung
    assert counts["gather_compact_steps"] == steps
    assert counts["scatter_compact_steps"] == steps
    assert 0 < counts["distinct_rows"] <= 12 * steps
    snap = registry.snapshot()
    assert {n: snap["train"][n] for n in fm.TAIL_STATS} == counts
    assert np.isfinite(t.cumulative_loss)
    # where an operator looks: /metrics and `obs report`
    from hivemall_tpu.obs.http import to_prometheus
    from hivemall_tpu.obs.report import summarize
    for name in ("tail_distinct_steps", "scatter_compact_steps"):
        assert f"hivemall_tpu_train_{name} {steps}" in to_prometheus(snap)
    report = summarize([{"event": "train_done", "ts": 1.0, "telemetry": snap}])
    assert f"tail:   distinct-row x{steps}  dense x0" in report
    assert f"gather on the distinct rows x{steps}" in report
    assert f"scatter in fast memory x{steps}" in report


def test_mesh_trainer_keeps_the_dense_tail():
    from hivemall_tpu.models.fm import FMTrainer
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    t = FMTrainer(f"-dims {R * P} -factors {K} -opt adagrad -mini_batch 32 "
                  "-mesh dp=1,tp=2")
    ds = _trainer_stream(4, 32, 8, R * P, n_distinct=12)
    t.fit(ds)
    assert t.cumulative_loss == t.cumulative_loss
    assert t._step_counts["tail_distinct_steps"] == 0
    assert t._step_counts["gather_compact_steps"] == 0
    assert t._step_counts["scatter_compact_steps"] == 0
    assert t._step_counts["tail_dense_steps"] == t._t


def _ffm_trainer_stream(n_batches, bsz, n_ids, dims, fields=8):
    """Canonical field-major unit-valued rows (one feature a field, in
    field order) over `n_ids` feature ids."""
    from hivemall_tpu.io.sparse import SparseDataset
    rng = np.random.default_rng(6)
    n = n_batches * bsz
    idx = rng.choice(rng.choice(np.arange(1, dims), n_ids, replace=False),
                     (n, fields)).astype(np.int32)
    fld = np.tile(np.arange(fields, dtype=np.int32), (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    return SparseDataset(idx.ravel(),
                         np.arange(0, n * fields + 1, fields, dtype=np.int64),
                         np.ones(n * fields, np.float32), lab, fld.ravel())


_FFM_OPTS = ("-dims 1048576 -factors 4 -fields 8 -opt adagrad "
             "-classification -halffloat -mini_batch 32")


@pytest.mark.parametrize("k,pack", [(1, "off"), (4, "off"), (1, "on"),
                                    (4, "on")])
def test_tail_counters_fold_for_train_ffm(k, pack):
    """`train_ffm`'s joint step feeds the counters `train_fm`'s does, on
    each of its dispatch paths: the unit field-major step alone and under
    the megastep, and the packed wire format's two wrappers (what a TPU
    runs)."""
    from hivemall_tpu.models.fm import FFMTrainer
    from hivemall_tpu.obs.registry import registry
    steps = 264                                    # one fold at 256, one asked
    t = FFMTrainer(f"{_FFM_OPTS} -steps_per_dispatch {k} -pack_input {pack}")
    assert fm.tail_cap(32 * 8, t.Mr, t.W, 2) == 256
    t.fit_stream(_ffm_trainer_stream(steps, 32, 20, 1 << 20).batches(
        32, shuffle=False))
    assert t._t == steps and np.isfinite(t.cumulative_loss)
    counts = dict(t._step_counts)
    assert counts["tail_distinct_steps"] == steps
    assert counts["gather_compact_steps"] == steps
    assert counts["scatter_compact_steps"] == steps
    assert counts["tail_dense_steps"] == 0
    assert steps < counts["distinct_rows"] <= 20 * steps
    snap = registry.snapshot()
    assert {n: snap["train"][n] for n in fm.TAIL_STATS} == counts
    from hivemall_tpu.obs.report import summarize
    report = summarize([{"event": "train_done", "ts": 1.0, "telemetry": snap}])
    assert f"tail:   distinct-row x{steps}  dense x0" in report
    assert f"gather on the distinct rows x{steps}" in report


@pytest.mark.parametrize("tp,k", [(2, 1), (4, 1), (4, 4)],
                         ids=["tp2", "tp4", "tp4_megastep"])
def test_ffm_mesh_trainer_takes_the_distinct_tail(tp, k):
    """`train_ffm -mesh dp=1,tp>1` (AdaGrad): the trainer hands its mesh
    to the step's factory, every chip runs the one-chip step on its own
    block of rows, and the counters say so: every step the distinct-row
    tail and the gather through the distinct rows on EVERY chip,
    `distinct_rows` the one-device trainer's count (the chips' summed)."""
    from hivemall_tpu.models.fm import FFMTrainer
    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} devices")
    from hivemall_tpu.obs.registry import registry
    from hivemall_tpu.obs.report import summarize
    opts = f"{_FFM_OPTS} -steps_per_dispatch {k}"
    one = FFMTrainer(opts)
    t = FFMTrainer(f"{opts} -mesh dp=1,tp={tp}")   # the registry's, last
    assert fm.tail_cap(32 * 8, t.Mr // tp, t.W, 2) == 256
    for trainer in (t, one):
        trainer.fit_stream(_ffm_trainer_stream(8, 32, 20, 1 << 20).batches(
            32, shuffle=False))
    assert t._t == one._t == 8 and np.isfinite(t.cumulative_loss)
    assert t.cumulative_loss == pytest.approx(one.cumulative_loss, rel=1e-5)
    # folded above; beside them, how the slab crossed the mesh: as the
    # blocks' compact tables on every step (20 ids, a few rows a block)
    counts = dict(t._step_counts)
    assert counts.pop("exchange_compact_steps") == t._t
    assert counts == dict(one._step_counts)
    snap = registry.snapshot()
    assert snap["train"]["exchange_compact_steps"] == t._t
    report = summarize([{"event": "train_done", "ts": 1.0, "telemetry": snap}])
    assert f"exchange of compact tables x{t._t}" in report
    assert t._step_counts["tail_distinct_steps"] == t._t
    assert t._step_counts["gather_compact_steps"] == t._t
    assert t._step_counts["scatter_compact_steps"] == t._t
    assert t._step_counts["tail_dense_steps"] == 0
    assert 8 < t._step_counts["distinct_rows"] <= 8 * 20
    assert t.params["T"].sharding.spec == jax.sharding.PartitionSpec(
        "tp", None)
    np.testing.assert_allclose(np.asarray(t.params["T"], np.float32),
                               np.asarray(one.params["T"], np.float32),
                               rtol=2.0 ** -6, atol=1e-6)


@pytest.mark.parametrize("opt,mesh", [("", "dp=2,tp=2"),
                                      ("-opt ftrl", "dp=1,tp=2"),
                                      ("", "dp=2,tp=1")],
                         ids=["dp2_tp2", "ftrl", "dp2"])
def test_ffm_mesh_trainer_keeps_the_dense_tail(opt, mesh):
    """A dp axis sums a gradient over replicas whose distinct rows differ:
    GSPMD's cut of the dense step, as before PR 36. FTRL rebuilds every
    row at every t: under dp=1,tp=2 it runs the step every such mesh runs,
    each chip on its block, and `rank_rows` keeps the dense tail there as
    it does on one chip. Either way the one-device trainer's loss."""
    from hivemall_tpu.models.fm import FFMTrainer
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    t = FFMTrainer(f"{_FFM_OPTS} {opt} -mesh {mesh}")
    one = FFMTrainer(f"{_FFM_OPTS} {opt}")
    for trainer in (t, one):
        trainer.fit(_ffm_trainer_stream(4, 32, 20, 1 << 20))
    assert t.cumulative_loss == pytest.approx(one.cumulative_loss, rel=1e-5)
    assert t._step_counts["tail_distinct_steps"] == 0
    assert t._step_counts["gather_compact_steps"] == 0
    assert t._step_counts["distinct_rows"] == 0
    assert t._step_counts["tail_dense_steps"] == t._t == 4


# -- the compact scatter-add (PR 38) -------------------------------------------

def _sorted_scatter(g, slot_rank, cap):
    """The parent's spelling: the slots sorted by rank (stable), the slab
    read through the permutation, a sorted scatter into [cap, W]."""
    W = g.shape[-1]
    perm = jnp.argsort(slot_rank, stable=True)
    return jnp.zeros((cap, W), jnp.float32).at[slot_rank[perm]].add(
        g.reshape(-1, W)[perm], mode="drop", indices_are_sorted=True)


@pytest.mark.parametrize("way", ["kernel_u1", "kernel_u8", "xla"])
@pytest.mark.parametrize("case,shape,n_distinct,own", [
    ("fm_128_lanes", (4096, 128), 700, 1.0),
    ("flagship_164_lanes", (128, 39, 164), 900, 1.0),
    ("three_blocks_of_four_dropped", (128, 39, 164), 300, 0.25),
    ("every_slot_distinct", (1024, 40), 1024, 1.0),
])
def test_scatter_rows_is_the_sorted_scatter(case, shape, n_distinct, own,
                                            way, monkeypatch):
    """`rows_pallas.scatter_rows` (the kernel interpreted, a slot a trip of
    its loop and a tile a trip, and XLA's scatter-add it falls back to off
    a TPU) sums what the sorted scatter through the permutation sums, row
    by row, the slots meeting in slot order on both sides; a slot ranked
    at the capacity (another block's, under a mesh) adds nothing, and so
    does the padding a [B, 39, W] slab is given to whole tiles."""
    from hivemall_tpu.ops import rows_pallas
    if way != "xla":
        monkeypatch.setattr(rows_pallas, "SCATTER_UNROLL",
                            int(way.split("_u")[1]))
    rng = np.random.default_rng(n_distinct)
    n = int(np.prod(shape[:-1]))
    cap = -(-n_distinct // 128) * 128
    rank = (rng.zipf(1.25, n) - 1) % n_distinct
    rank[rng.choice(n, n_distinct, replace=False)] = np.arange(n_distinct)
    rank[rng.random(n) >= own] = cap
    g = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    rank = jnp.asarray(rank.astype(np.int32))
    assert rows_pallas.scatter_rows_takes(shape, cap)
    got = jax.jit(lambda g, r: rows_pallas.scatter_rows(
        g, r, cap, interpret=True if way != "xla" else None))(g, rank)
    want = jax.jit(_sorted_scatter, static_argnums=2)(g, rank, cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    assert got.shape == (cap, shape[-1])


@pytest.mark.parametrize("shape,cap,takes", [
    ((1277952, 128), 196608, True),       # FM's slab, 96 MiB of sum
    ((32768, 39, 164), 98304, True),      # the flagship's, 164 lanes -> 256
    ((32768, 39, 164), 174080, False),    # its ranking's capacity: 170 MiB
    ((1277952, 128), 196612, False),      # not whole 8-row tiles
    ((1000, 128), 128, False),            # slab rows not whole id tiles
    ((10, 39, 40), 128, False),           # 10 x 40 padded rows not whole
])
def test_scatter_rows_takes_what_fits_its_fast_memory(shape, cap, takes):
    from hivemall_tpu.ops import rows_pallas
    assert rows_pallas.scatter_rows_takes(shape, cap) == takes


def test_scatter_capacity_follows_the_kernels_bytes_and_the_width(
        monkeypatch):
    """The compact gradient is sized to stay in fast memory by the scatter
    kernel's own budget (`rows_pallas.SCATTER_VMEM_BYTES`, which the
    kernel gates itself on too: one constant, so that no budget of the
    gather's can leave the tail's passes sized for a kernel that refuses
    them). The cells: FM 196,608 rows of 128 lanes, the flagship 98,304
    (164 lanes pad to 256), each a whole number of id tiles, never over
    the ranking's capacity, and 0 with no bytes (then one pass of the
    whole capacity, by XLA's scatter-add)."""
    from hivemall_tpu.ops import rows_pallas
    n = 32768 * 39
    fm_cap, ffm_cap = fm.tail_cap(n, 1 << 22), fm.tail_cap(n, 1 << 22, 164, 2)
    assert fm.scatter_cap(fm_cap, 128) == 196_608
    assert fm.scatter_cap(ffm_cap, 164) == 98_304 < ffm_cap
    assert fm.scatter_cap(ffm_cap, 164) <= fm.gather_cap(ffm_cap, 164, 2)
    assert 73_300 < fm.scatter_cap(ffm_cap, 164)     # the cells' batches
    assert fm.scatter_cap(4096, 128) == 4096
    assert fm.scatter_cap(fm_cap, 640) % 128 == 0
    for W in (128, 164, 640):
        assert rows_pallas.scatter_rows_takes(
            (n, W) if W != 164 else (32768, 39, W), fm.scatter_cap(fm_cap, W))
    monkeypatch.setattr(fm, "COMPACT_TABLE_BYTES", 128 * 256 * 4)
    assert fm.scatter_cap(fm_cap, 128) == 196_608    # the gather's alone
    monkeypatch.setattr(rows_pallas, "SCATTER_VMEM_BYTES", 128 * 256 * 4)
    assert fm.scatter_cap(fm_cap, 128) == 256
    assert fm.scatter_cap(ffm_cap, 164) == 128
    monkeypatch.setattr(rows_pallas, "SCATTER_VMEM_BYTES", 0)
    assert fm.scatter_cap(fm_cap, 128) == 0


@pytest.mark.parametrize("way", ["kernel", "xla"])
@pytest.mark.parametrize("case,n_distinct,passes", [
    ("within_the_scatters", 100, 1), ("at_the_scatters", 128, 1),
    ("one_past_a_second_pass", 129, 2), ("into_the_second_pass", 200, 2),
    ("at_the_rankings", CAP, -(-CAP // 128)),
    ("over_the_rankings_dense", CAP + 1, 0)])
def test_the_tails_passes_follow_the_batch(case, n_distinct, passes, way,
                                           monkeypatch):
    """A compact gradient of 128 rows: a batch within it is summed in one
    pass, one past it in as many passes as it needs (each the rows ranked
    in its 128), one over the ranking's capacity takes the dense tail; the
    same table and state as the dense tail to rounding, and
    `scatter_compact_steps` 1 only for the one pass."""
    from hivemall_tpu.ops import rows_pallas
    monkeypatch.setattr(rows_pallas, "SCATTER_VMEM_BYTES", 128 * 128 * 4)
    # as many passes as the ranking's capacity needs (FM's own readings
    # allow one: the next test)
    monkeypatch.setitem(fm._TAIL_COSTS, (128, 4),
                        fm._TAIL_COSTS[128, 4]._replace(passes=CAP))
    if way == "kernel":
        monkeypatch.setattr(fm, "scatter_rows", partial(
            rows_pallas.scatter_rows, interpret=True))
    calls = []
    real = fm.scatter_rows
    monkeypatch.setattr(fm, "scatter_rows", lambda g, r, c: calls.append(c)
                        or real(g, r, c))
    idx = _ids(n_distinct)
    new, ref = _pair(idx)
    _assert_same(new, ref, idx)
    assert int(new[3]["tail_distinct_steps"]) == int(passes > 0)
    assert int(new[3]["scatter_compact_steps"]) == int(passes == 1)
    # one pass is traced, a trip of a loop: a batch runs as many as it needs
    assert calls == [128]


@pytest.mark.parametrize("case,n_distinct,tail", [
    ("at_one_pass", 128, 1), ("one_past_it_dense", 129, 0)])
def test_fm_ranks_no_further_than_one_pass(case, n_distinct, tail,
                                           monkeypatch):
    """FM's readings allow one pass of the compact sum (a second costs more
    than the dense tail, PR 38): with a compact gradient of 128 rows the
    ranking stops there, and a batch one row past it takes the dense
    tail, the same table and state to rounding."""
    from hivemall_tpu.ops import rows_pallas
    monkeypatch.setattr(rows_pallas, "SCATTER_VMEM_BYTES", 128 * 128 * 4)
    assert fm.tail_cap(N, R) == 128 < CAP
    idx = _ids(n_distinct)
    new, ref = _pair(idx)
    _assert_same(new, ref, idx)
    assert int(new[3]["tail_distinct_steps"]) == tail
    assert int(new[3]["scatter_compact_steps"]) == tail
