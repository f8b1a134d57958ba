"""FM/FFM trainers: score-formula correctness vs a naive oracle + convergence
on synthetic interaction data (SURVEY.md §5 golden-convergence style)."""

import numpy as np
import pytest

from hivemall_tpu.frame.evaluation import auc
from hivemall_tpu.io.sparse import SparseDataset
from hivemall_tpu.models.fm import FFMTrainer, FMTrainer


def naive_fm_score(w0, w, V, idx, val):
    """Direct per-row double loop oracle of the FM formula."""
    out = []
    for b in range(idx.shape[0]):
        s = w0 + sum(w[idx[b, l]] * val[b, l] for l in range(idx.shape[1]))
        for i in range(idx.shape[1]):
            for j in range(i + 1, idx.shape[1]):
                s += float(V[idx[b, i]] @ V[idx[b, j]]) * val[b, i] * val[b, j]
        out.append(s)
    return np.asarray(out)


def naive_ffm_score(w0, w, V, idx, val, fld):
    out = []
    for b in range(idx.shape[0]):
        s = w0 + sum(w[idx[b, l]] * val[b, l] for l in range(idx.shape[1]))
        for i in range(idx.shape[1]):
            for j in range(i + 1, idx.shape[1]):
                s += float(V[idx[b, i], fld[b, j]] @ V[idx[b, j], fld[b, i]]
                           ) * val[b, i] * val[b, j]
        out.append(s)
    return np.asarray(out)


def test_fm_score_matches_oracle():
    from hivemall_tpu.ops.fm import fm_score
    rng = np.random.default_rng(0)
    N, K, B, L = 20, 3, 7, 4
    w0 = 0.3
    w = rng.normal(0, 1, N).astype(np.float32)
    V = rng.normal(0, 1, (N, K)).astype(np.float32)
    idx = rng.integers(1, N, (B, L)).astype(np.int32)
    val = rng.uniform(0.5, 1.5, (B, L)).astype(np.float32)
    got = np.asarray(fm_score(np.float32(w0), w, V, idx, val))
    want = naive_fm_score(w0, w, V, idx, val)
    np.testing.assert_allclose(got, want, rtol=2e-4)


def test_ffm_score_matches_oracle():
    from hivemall_tpu.ops.fm import ffm_score
    rng = np.random.default_rng(1)
    N, F, K, B, L = 15, 5, 2, 6, 4
    w0 = -0.2
    w = rng.normal(0, 1, N).astype(np.float32)
    V = rng.normal(0, 1, (N, F, K)).astype(np.float32)
    idx = rng.integers(1, N, (B, L)).astype(np.int32)
    val = rng.uniform(0.5, 1.5, (B, L)).astype(np.float32)
    fld = rng.integers(0, F, (B, L)).astype(np.int32)
    got = np.asarray(ffm_score(np.float32(w0), w, V, idx, val, fld))
    want = naive_ffm_score(w0, w, V, idx, val, fld)
    np.testing.assert_allclose(got, want, rtol=2e-4)


def _xor_dataset(n=2000, seed=0):
    """Pure interaction task: y = +1 iff exactly one of (f1, f2) present —
    linear terms can't solve it, factors must."""
    rng = np.random.default_rng(seed)
    rows, fields, labels = [], [], []
    for _ in range(n):
        a, b = rng.integers(0, 2), rng.integers(0, 2)
        idx = [1 if a else 2, 3 if b else 4]
        rows.append((np.asarray(idx, np.int32), np.ones(2, np.float32)))
        fields.append(np.asarray([0, 1], np.int32))
        labels.append(1.0 if a != b else -1.0)
    return rows, fields, labels


def test_fm_learns_interactions():
    rows, _, labels = _xor_dataset()
    ds = SparseDataset.from_rows(rows, labels)
    t = FMTrainer("-dims 16 -factors 4 -classification -opt adagrad "
                  "-eta fixed -eta0 0.1 -mini_batch 64 -iters 8 -sigma 0.3 "
                  "-lambda0 0 -lambda_w 0 -lambda_v 0")
    t.fit(ds)
    assert auc(np.asarray(labels), t.predict(ds)) > 0.95


def test_ffm_learns_interactions():
    rows, fields, labels = _xor_dataset()
    ds = SparseDataset.from_rows(rows, labels, fields=fields)
    t = FFMTrainer("-dims 16 -factors 4 -fields 4 -classification "
                   "-opt adagrad -eta fixed -eta0 0.1 -mini_batch 64 "
                   "-iters 8 -sigma 0.3 -lambda0 0 -lambda_w 0 -lambda_v 0")
    t.fit(ds)
    assert auc(np.asarray(labels), t.predict(ds)) > 0.95


def test_ffm_udtf_lifecycle_with_string_features():
    t = FFMTrainer("-dims 4096 -factors 2 -fields 8 -classification "
                   "-mini_batch 8 -eta fixed -eta0 0.2 -sigma 0.2")
    rng = np.random.default_rng(3)
    for _ in range(200):
        a, b = rng.integers(0, 2), rng.integers(0, 2)
        feats = [f"0:u{a}:1", f"1:i{b}:1"]     # field:index:value strings
        t.process(feats, 1 if a != b else -1)
    rows = list(t.close())
    assert rows[0][0] == "0"                   # w0 row first
    names = {r[0] for r in rows}
    assert any(n.startswith("u") for n in names)
    assert any(n.startswith("i") for n in names)


def test_fm_regression_targets():
    rng = np.random.default_rng(5)
    rows, labels = [], []
    for _ in range(800):
        i = int(rng.integers(1, 5))
        rows.append((np.asarray([i], np.int32), np.ones(1, np.float32)))
        labels.append(float(i))                # target = feature id
    ds = SparseDataset.from_rows(rows, labels)
    t = FMTrainer("-dims 8 -factors 2 -opt adagrad -eta fixed -eta0 0.5 "
                  "-mini_batch 32 -iters 6 -lambda0 0 -lambda_w 0 -lambda_v 0")
    t.fit(ds)
    pred = t.predict(ds)
    assert np.corrcoef(pred, np.asarray(labels))[0, 1] > 0.98


def test_fm_save_warm_start(tmp_path):
    rows, _, labels = _xor_dataset(300)
    ds = SparseDataset.from_rows(rows, labels)
    a = FMTrainer("-dims 16 -factors 2 -classification -mini_batch 64")
    a.fit(ds)
    p = str(tmp_path / "fm_model.npz")
    a.save_model(p)
    b = FMTrainer(f"-dims 16 -factors 2 -classification -loadmodel {p}")
    np.testing.assert_allclose(a.predict(ds), b.predict(ds), atol=1e-5)


# --- sparse (gather/scatter) step vs dense step ----------------------------

def _factor_step_fixture(kind, opt_name, seed=3):
    import jax.numpy as jnp
    from hivemall_tpu.ops.fm import (_make_factor_step_dense,
                                     _make_factor_step_sparse,
                                     fm_score, ffm_score)
    from hivemall_tpu.ops.losses import get_loss
    from hivemall_tpu.ops.optimizers import make_optimizer

    rng = np.random.default_rng(seed)
    N, F, K, B = 64, 4, 3, 8
    L = 4  # == F so per-row distinct fields keep (idx,field) pairs unique
    loss = get_loss("logloss")
    opt = make_optimizer(opt_name, eta_scheme="fixed", eta0=0.1, reg="no")
    if kind == "ffm":
        V = rng.normal(0, 0.1, (N, F, K)).astype(np.float32)
        score = ffm_score
    else:
        V = rng.normal(0, 0.1, (N, K)).astype(np.float32)
        score = fm_score
    params = {"w0": jnp.zeros(()), "w": jnp.zeros(N), "V": jnp.asarray(V)}
    state = {k: opt.init(np.asarray(v).shape) for k, v in params.items()}
    # duplicate-free indices BATCH-wide (per-occurrence sparse updates only
    # match one dense accumulated update when no id repeats anywhere in the
    # batch), and per-row distinct fields so FFM (idx,field) pairs are unique
    idx = rng.permutation(np.arange(1, N))[:B * L].reshape(B, L).astype(
        np.int32)
    val = rng.uniform(0.5, 1.5, (B, L)).astype(np.float32)
    fld = np.tile(rng.permutation(np.arange(F, dtype=np.int32))[:L], (B, 1))
    lab = (rng.integers(0, 2, B) * 2 - 1).astype(np.float32)
    mask = np.ones(B, np.float32)
    extra = (fld,) if kind == "ffm" else ()
    dense = _make_factor_step_dense(score, loss, opt, (0.0, 0.0, 0.0))
    sparse = _make_factor_step_sparse(kind, loss, opt, (0.0, 0.0, 0.0))
    return params, state, (idx, val, lab, mask), extra, dense, sparse


@pytest.mark.parametrize("kind", ["fm", "ffm"])
@pytest.mark.parametrize("opt_name", ["sgd", "adagrad", "ftrl"])
def test_sparse_step_matches_dense(kind, opt_name):
    """With duplicate-free indices and no L2, the O(batch) gather/scatter step
    must reproduce the O(table) dense step exactly (same math, different
    memory traffic)."""
    import jax
    params, state, (idx, val, lab, mask), extra, dense, sparse = \
        _factor_step_fixture(kind, opt_name)
    copy = jax.tree.map(lambda x: x.copy() if hasattr(x, "copy") else x,
                        (params, state))
    pd, sd, ld = dense(params, state, 0.0, idx, val, lab, mask, *extra)
    ps, ss, ls = sparse(copy[0], copy[1], 0.0, idx, val, lab, mask, *extra)
    np.testing.assert_allclose(float(ld), float(ls), rtol=1e-5)
    if opt_name == "ftrl":
        # FTRL weights live implicitly in (z, n): the dense step eagerly
        # re-materializes the WHOLE table (zeroing untouched random inits),
        # the sparse step is lazy (untouched cells keep their init until
        # first touched — the reference's per-cell behavior). Compare only
        # the entries this batch touched.
        np.testing.assert_allclose(np.asarray(pd["w0"]), np.asarray(ps["w0"]),
                                   rtol=1e-4, atol=1e-6)
        ix = np.asarray(idx).ravel()
        np.testing.assert_allclose(np.asarray(pd["w"])[ix],
                                   np.asarray(ps["w"])[ix],
                                   rtol=1e-4, atol=1e-6)
        if kind == "ffm":
            N, F, K = np.asarray(pd["V"]).shape
            # off-diagonal pairs only: diagonal self-pair cells are
            # deliberately untouched by the sparse step (they never enter
            # the score), while dense FTRL eagerly re-materializes them
            L = np.asarray(idx).shape[1]
            offdiag = ~np.eye(L, dtype=bool)[None].repeat(len(idx), 0)
            flat = (np.asarray(idx)[:, :, None] * F +
                    np.asarray(extra[0])[:, None, :])[offdiag].ravel()
            np.testing.assert_allclose(
                np.asarray(pd["V"]).reshape(N * F, K)[flat],
                np.asarray(ps["V"]).reshape(N * F, K)[flat],
                rtol=1e-4, atol=1e-6)
        else:
            np.testing.assert_allclose(np.asarray(pd["V"])[ix],
                                       np.asarray(ps["V"])[ix],
                                       rtol=1e-4, atol=1e-6)
    else:
        for k in ("w0", "w", "V"):
            np.testing.assert_allclose(np.asarray(pd[k]), np.asarray(ps[k]),
                                       rtol=1e-4, atol=1e-6)


def test_sparse_step_duplicate_indices_accumulate():
    """Duplicate feature ids within a batch must accumulate their gradients
    (scatter-add), not race (last-write-wins)."""
    import jax.numpy as jnp
    from hivemall_tpu.ops.fm import _make_factor_step_sparse
    from hivemall_tpu.ops.losses import get_loss
    from hivemall_tpu.ops.optimizers import make_optimizer

    loss = get_loss("squaredloss")
    opt = make_optimizer("sgd", eta_scheme="fixed", eta0=1.0, reg="no")
    step = _make_factor_step_sparse("fm", loss, opt, (0.0, 0.0, 0.0))
    N, K = 8, 2
    params = {"w0": jnp.zeros(()), "w": jnp.zeros(N),
              "V": jnp.zeros((N, K))}
    state = {k: opt.init(np.asarray(v).shape) for k, v in params.items()}
    # two rows, both touching feature 3 with val 1 → dloss = phi - y = -1 each
    idx = np.array([[3, 0], [3, 0]], np.int32)
    val = np.array([[1.0, 0.0], [1.0, 0.0]], np.float32)
    lab = np.ones(2, np.float32)
    mask = np.ones(2, np.float32)
    p2, _, _ = step(params, state, 0.0, idx, val, lab, mask)
    # squaredloss dloss = (phi - y) = -1 per row; w[3] += eta * 1 * 2 rows
    np.testing.assert_allclose(float(p2["w"][3]), 2.0, rtol=1e-6)


def test_ffm_sparse_convergence_adagrad():
    """FFM with the sparse AdaGrad path learns field-crossed interactions."""
    rng = np.random.default_rng(11)
    n, L, F = 600, 3, 3
    idx = rng.integers(1, 40, (n, L)).astype(np.int32)
    val = np.ones((n, L), np.float32)
    fld = np.tile(np.arange(L, dtype=np.int32), (n, 1))
    y = np.where((idx[:, 0] % 2) == (idx[:, 1] % 2), 1.0, -1.0
                 ).astype(np.float32)
    ds = SparseDataset.from_rows(
        [(idx[i], val[i]) for i in range(n)], y,
        fields=[fld[i] for i in range(n)])
    t = FFMTrainer("-dims 64 -factors 4 -fields 3 -classification "
                   "-mini_batch 64 -iters 30 -opt adagrad -eta0 0.2 -seed 7")
    t.fit(ds)
    assert t.optimizer.sparse_update is not None   # sparse path in use
    scores = t.predict(ds)
    assert auc((y > 0).astype(int), scores) > 0.9


def test_ffm_sparse_no_diagonal_state_pollution():
    """Self-pair cells V[idx_i, field_i] never enter the score (i<j mask);
    the sparse step must not decay them or inflate their AdaGrad state."""
    import jax.numpy as jnp
    from hivemall_tpu.ops.fm import _make_factor_step_sparse
    from hivemall_tpu.ops.losses import get_loss
    from hivemall_tpu.ops.optimizers import make_optimizer

    loss = get_loss("logloss")
    opt = make_optimizer("adagrad", eta_scheme="fixed", eta0=0.1, reg="no")
    step = _make_factor_step_sparse("ffm", loss, opt, (0.01, 0.01, 0.01))
    N, F, K = 16, 2, 2
    rng = np.random.default_rng(0)
    V = jnp.asarray(rng.normal(0, 0.5, (N, F, K)), jnp.float32)
    params = {"w0": jnp.zeros(()), "w": jnp.zeros(N), "V": V.copy()}
    state = {k: opt.init(np.asarray(v).shape) for k, v in params.items()}
    # one row: feature 3 (field 0), feature 7 (field 1); cross pairs touch
    # (3,1) and (7,0); diagonals (3,0)/(7,1) must stay untouched
    idx = np.array([[3, 7]], np.int32)
    val = np.ones((1, 2), np.float32)
    fld = np.array([[0, 1]], np.int32)
    lab = np.ones(1, np.float32)
    mask = np.ones(1, np.float32)
    V0 = np.asarray(V).copy()
    p2, s2, _ = step(params, state, 0.0, idx, val, lab, mask, fld)
    gg = np.asarray(s2["V"]["gg"])
    np.testing.assert_allclose(np.asarray(p2["V"])[3, 0], V0[3, 0])
    np.testing.assert_allclose(np.asarray(p2["V"])[7, 1], V0[7, 1])
    assert gg[3, 0].sum() == 0 and gg[7, 1].sum() == 0
    # the cross cells DID move
    assert np.abs(np.asarray(p2["V"])[3, 1] - V0[3, 1]).sum() > 0
    assert np.abs(np.asarray(p2["V"])[7, 0] - V0[7, 0]).sum() > 0


def test_ffm_sparse_padding_pairs_keep_lazy_init_under_ftrl():
    """Pairs where one side is a padding slot (idx=0/val=0) must not be
    scattered into real (feature, field-0) cells: FTRL's re-materializing
    .set would wipe their lazy init to 0 and freeze the interaction."""
    import jax.numpy as jnp
    from hivemall_tpu.ops.fm import _make_factor_step_sparse
    from hivemall_tpu.ops.losses import get_loss
    from hivemall_tpu.ops.optimizers import make_optimizer

    loss = get_loss("logloss")
    opt = make_optimizer("ftrl")
    step = _make_factor_step_sparse("ffm", loss, opt, (0.0, 0.0, 0.0))
    N, F, K = 16, 3, 2
    rng = np.random.default_rng(2)
    V = jnp.asarray(rng.normal(0, 0.5, (N, F, K)), jnp.float32)
    params = {"w0": jnp.zeros(()), "w": jnp.zeros(N), "V": V.copy()}
    state = {k: opt.init(np.asarray(v).shape) for k, v in params.items()}
    # row: feature 5 (field 1), feature 9 (field 2), one padding slot
    idx = np.array([[5, 9, 0]], np.int32)
    val = np.array([[1.0, 1.0, 0.0]], np.float32)
    fld = np.array([[1, 2, 0]], np.int32)
    V0 = np.asarray(V).copy()
    p2, _, _ = step(params, state, 0.0, idx, val,
                    np.ones(1, np.float32), np.ones(1, np.float32), fld)
    V1 = np.asarray(p2["V"])
    # pair with the padding slot (field 0) must keep its lazy random init
    np.testing.assert_allclose(V1[5, 0], V0[5, 0])
    np.testing.assert_allclose(V1[9, 0], V0[9, 0])
    # the real cross pair (5,f2) x (9,f1) was touched (FTRL materializes)
    assert np.abs(V1[5, 2] - V0[5, 2]).sum() > 0
    assert np.abs(V1[9, 1] - V0[9, 1]).sum() > 0


# --- field-major canonical layout (ops.fm._fused_phi_fieldmajor) -----------

def test_canonicalize_fieldmajor_invariants():
    from hivemall_tpu.io.sparse import canonicalize_fieldmajor
    rng = np.random.default_rng(7)
    F = 5
    for _ in range(10):
        B, L = 4, 11
        idx = rng.integers(1, 999, (B, L)).astype(np.int32)
        val = rng.uniform(0.1, 1, (B, L)).astype(np.float32)
        fld = rng.integers(0, F, (B, L)).astype(np.int32)
        dead = rng.uniform(size=(B, L)) < 0.4
        val[dead] = 0
        idx[dead] = 0
        res = canonicalize_fieldmajor(idx, val, fld, F, max_m=8)
        assert res is not None
        idx2, val2, m = res
        assert idx2.shape == (B, m * F) and (m & (m - 1)) == 0
        for b in range(B):
            orig = sorted((int(i), float(v), int(f)) for i, v, f in
                          zip(idx[b], val[b], fld[b]) if v != 0)
            got = sorted((int(idx2[b, s]), float(val2[b, s]), s % F)
                         for s in range(m * F) if val2[b, s] != 0)
            assert orig == got          # same (feature, value, field) multiset


def test_canonicalize_fieldmajor_overflow_returns_none():
    from hivemall_tpu.io.sparse import canonicalize_fieldmajor
    idx = np.ones((2, 6), np.int32)
    val = np.ones((2, 6), np.float32)
    fld = np.zeros((2, 6), np.int32)       # six features all in field 0
    assert canonicalize_fieldmajor(idx, val, fld, 5, max_m=4) is None
    out = canonicalize_fieldmajor(idx, val, fld, 5, max_m=8)
    assert out is not None and out[2] == 8  # pow2 bucket of m_needed=6


def test_fieldmajor_phi_matches_pairs_phi():
    import jax.numpy as jnp
    from hivemall_tpu.io.sparse import canonicalize_fieldmajor
    from hivemall_tpu.ops.fm import (_fused_phi, _fused_phi_fieldmajor,
                                     ffm_row_hash)
    rng = np.random.default_rng(3)
    F, K, Mr = 5, 3, 1 << 8
    W = F * K + 2
    T = rng.normal(0, 1, (Mr, W)).astype(np.float32)
    for _ in range(5):
        B, L = 6, 9
        idx = rng.integers(1, 1000, (B, L)).astype(np.int32)
        val = rng.uniform(0.1, 1, (B, L)).astype(np.float32)
        fld = rng.integers(0, F, (B, L)).astype(np.int32)
        dead = rng.uniform(size=(B, L)) < 0.3
        val[dead] = 0
        idx[dead] = 0
        idx2, val2, m = canonicalize_fieldmajor(idx, val, fld, F, max_m=8)
        r1 = np.asarray(ffm_row_hash(jnp.asarray(idx), Mr))
        r2 = np.asarray(ffm_row_hash(jnp.asarray(idx2), Mr))
        p1 = np.asarray(_fused_phi(0.3, jnp.asarray(T[r1]), jnp.asarray(val),
                                   jnp.asarray(fld), F, K))
        p2 = np.asarray(_fused_phi_fieldmajor(
            0.3, jnp.asarray(T[r2]), jnp.asarray(val2), F, K))
        # same math, different summation order: f32-noise tolerance
        np.testing.assert_allclose(p1, p2, rtol=2e-3, atol=2e-2)


def test_ffm_fieldmajor_trains_like_pairs():
    """End-to-end: the canonical-batch step and the general pair step are the
    same optimization — same data, same seed => near-identical tables."""
    rows, fields, labels = _xor_dataset(600)
    ds = SparseDataset.from_rows(rows, labels, fields=fields)
    opts = ("-dims 64 -factors 4 -fields 4 -classification -opt adagrad "
            "-eta fixed -eta0 0.1 -mini_batch 64 -iters 4 -sigma 0.3")
    tp = FFMTrainer(opts + " -ffm_interaction pairs")
    tf = FFMTrainer(opts + " -ffm_interaction fieldmajor")
    tp.fit(ds)
    tf.fit(ds)
    assert tf._step_fm is not None and tp._step_fm is None
    Tp = np.asarray(tp.params["T"], np.float32)
    Tf = np.asarray(tf.params["T"], np.float32)
    np.testing.assert_allclose(Tp, Tf, rtol=5e-2, atol=5e-3)
    assert auc(np.asarray(labels), tf.predict(ds)) > 0.95


def test_ffm_auto_interaction_skips_sparse_rows():
    """auto mode must fall back to the pair kernel when rows are sparse
    relative to the field space (canonical width would inflate > 2x)."""
    rng = np.random.default_rng(5)
    rows, fields, labels = [], [], []
    for _ in range(64):
        idx = rng.integers(1, 200, 3).astype(np.int32)
        rows.append((idx, np.ones(3, np.float32)))
        fields.append(rng.integers(0, 64, 3).astype(np.int32))
        labels.append(1.0 if rng.uniform() > 0.5 else -1.0)
    ds = SparseDataset.from_rows(rows, labels, fields=fields)
    t = FFMTrainer("-dims 256 -factors 2 -fields 64 -classification "
                   "-opt adagrad -mini_batch 32")
    b = next(ds.batches(32))
    out = t._preprocess_batch(t._convert_batch(b) if hasattr(
        t, "_convert_batch") else b)
    assert not out.fieldmajor            # 64 fields >> 3-feature rows
    t.fit(ds)                            # trains through the pair path
    assert np.isfinite(t.cumulative_loss)


def test_out_of_range_fields_fold_consistently():
    """Field ids >= F fold mod F in BOTH interaction kernels (parse-path
    normalization) — the fieldmajor and pairs paths must agree on the same
    data (review r2: fieldmajor silently dropped such features)."""
    import jax.numpy as jnp
    from hivemall_tpu.io.sparse import canonicalize_fieldmajor
    from hivemall_tpu.ops.fm import (_fused_phi, _fused_phi_fieldmajor,
                                     ffm_row_hash)
    F, K, Mr = 4, 3, 1 << 8
    W = F * K + 2
    rng = np.random.default_rng(11)
    T = rng.normal(0, 1, (Mr, W)).astype(np.float32)
    idx = np.asarray([[3, 8, 12, 5]], np.int32)
    val = np.ones((1, 4), np.float32)
    fld = np.asarray([[0, 1, 2, 5]], np.int32)       # 5 >= F
    idx2, val2, m = canonicalize_fieldmajor(idx, val, fld, F)
    assert (val2 != 0).sum() == 4                    # nothing dropped
    r1 = np.asarray(ffm_row_hash(jnp.asarray(idx), Mr))
    r2 = np.asarray(ffm_row_hash(jnp.asarray(idx2), Mr))
    p1 = np.asarray(_fused_phi(0.0, jnp.asarray(T[r1]), jnp.asarray(val),
                               jnp.asarray(fld), F, K))
    p2 = np.asarray(_fused_phi_fieldmajor(
        0.0, jnp.asarray(T[r2]), jnp.asarray(val2), F, K))
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-4)


def test_ffm_interaction_option_validated_any_layout():
    with pytest.raises(ValueError):
        FFMTrainer("-dims 1000 -fields 4 -ffm_interaction fieldmajro")
    with pytest.raises(ValueError):                 # dense layout, forced fm
        FFMTrainer("-dims 1000 -fields 4 -ffm_interaction fieldmajor")


@pytest.mark.parametrize("table", ["parts", "jiont"])
def test_ffm_table_option_takes_joint_dense_or_auto(table):
    with pytest.raises(ValueError, match=r"joint\|dense\|auto"):
        FFMTrainer(f"-dims 4096 -factors 4 -fields 8 -opt adagrad "
                   f"-halffloat -ffm_table {table}")


@pytest.mark.parametrize("distinct_tail", [True, False],
                         ids=["distinct", "dense"])
@pytest.mark.parametrize("fieldmajor", [False, True],
                         ids=["pairs", "fieldmajor"])
def test_ffm_joint_step_l2_matches_autodiff_oracle(fieldmajor,
                                                    distinct_tail):
    """One SGD step of the joint fused step with -lambda0, -lambda_w and
    -lambda_v non-zero moves T by eta times the gradient jax.grad takes of
    an independent objective: the dense-cube scorer (ffm_score) over
    features read out of the hashed rows, plus 0.5 * lambda * x^2 for
    every PRESENT occurrence (val != 0, row not masked) of a feature's
    weight and latent vectors, and 0.5 * lambda0 * w0^2 once. A repeated
    feature, a padded slot and a masked row are in the batch."""
    import jax
    import jax.numpy as jnp
    from hivemall_tpu.ops.fm import (ffm_row_hash, ffm_score,
                                     make_ffm_step_fused)
    from hivemall_tpu.ops.losses import get_loss
    from hivemall_tpu.ops.optimizers import make_optimizer

    F, K, Mr, N, eta = 4, 3, 16384, 40, 0.1   # tail_cap offers 128 rows
    FK, W = F * K, F * K + 8
    lam0, lam_w, lam_v = 0.03, 0.02, 0.05
    rng = np.random.default_rng(11)
    T = np.zeros((Mr, W), np.float32)          # pad lanes stay zero
    T[:, :FK + 1] = rng.normal(0, 0.3, (Mr, FK + 1))
    w0 = np.float32(0.2)
    B, L = 16, 2 * F                           # two occurrences a field
    idx = rng.integers(1, N, (B, L)).astype(np.int32)
    idx[0, 5] = idx[0, 1]                      # a feature twice in a row
    idx[2, 3] = 0                              # a padded slot
    val = np.where(idx != 0, rng.uniform(0.5, 1.5, (B, L)), 0.0
                   ).astype(np.float32)
    field = np.tile(np.arange(L, dtype=np.int32) % F, (B, 1))
    label = np.where(np.arange(B) % 3, 1.0, -1.0).astype(np.float32)
    row_mask = np.ones(B, np.float32)
    row_mask[3] = 0.0                          # a masked row
    loss = get_loss("logloss")

    def objective(w0f, Tf):
        rows_n = ffm_row_hash(jnp.arange(N, dtype=jnp.int32), Mr)
        phi = ffm_score(w0f, Tf[rows_n, FK],
                        Tf[rows_n, :FK].reshape(N, F, K), idx, val, field)
        data = (loss.loss(phi, label) * row_mask).sum()
        slab = Tf[ffm_row_hash(jnp.asarray(idx), Mr)]
        pm = (val != 0) * row_mask[:, None]
        l2 = (pm * (lam_v * (slab[..., :FK] ** 2).sum(-1)
                    + lam_w * slab[..., FK] ** 2)).sum()
        return data + 0.5 * l2 + 0.5 * lam0 * w0f ** 2

    g0, gT = jax.grad(objective, argnums=(0, 1))(jnp.float32(w0),
                                                 jnp.asarray(T))
    assert np.abs(np.asarray(gT)[:, FK + 1:]).max() == 0.0

    step = make_ffm_step_fused(
        loss, make_optimizer("sgd", eta_scheme="fixed", eta0=eta, reg="no"),
        (lam0, lam_w, lam_v), F, K, fieldmajor=fieldmajor,
        distinct_tail=distinct_tail)
    params = {"T": jnp.asarray(T), "w0": jnp.asarray(w0)}
    state = {"T": {}, "w0": {}}
    batch = (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(label),
             jnp.asarray(row_mask))
    if not fieldmajor:
        batch += (jnp.asarray(field),)
    new, _, loss_sum, stats = step(params, state, 1.0, *batch)
    assert int(stats["tail_distinct_steps"]) == int(distinct_tail)
    np.testing.assert_allclose((T - np.asarray(new["T"])) / eta,
                               np.asarray(gT), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose((w0 - float(new["w0"])) / eta, float(g0),
                               rtol=1e-5)


def test_fm_fused_layout_matches_split():
    """-fm_table fused (one [N,K+pad] row: V|w) is the same optimization as
    the split w/V layout — same data, same seed => matching tables."""
    rows, _, labels = _xor_dataset(600)
    ds = SparseDataset.from_rows(rows, labels)
    opts = ("-dims 64 -factors 4 -classification -opt adagrad -eta fixed "
            "-eta0 0.1 -mini_batch 64 -iters 4 -sigma 0.3")
    # -fm_update occurrence: the split layout's sparse chain is
    # per-occurrence AdaGrad, so the exact-match claim needs the fused
    # layout on the same update shape (minibatch is the throughput default)
    tf = FMTrainer(opts + " -fm_table fused -fm_update occurrence")
    tsp = FMTrainer(opts + " -fm_table split")
    tf.fit(ds)
    tsp.fit(ds)
    assert tf.fm_layout == "fused" and tsp.fm_layout == "split"
    wf, Vf = tf._wv_tables()
    ws, Vs = tsp._wv_tables()
    np.testing.assert_allclose(Vf, Vs, rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(wf, ws, rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(tf.predict(ds), tsp.predict(ds),
                               rtol=2e-2, atol=2e-3)
    assert auc(np.asarray(labels), tf.predict(ds)) > 0.95


def test_fm_fused_rejects_dense_only_optimizer():
    with pytest.raises(ValueError):
        FMTrainer("-dims 64 -opt adam -fm_table fused")
    t = FMTrainer("-dims 64 -opt adam")          # auto falls back to split
    assert t.fm_layout == "split"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("K", [5, 8, 20, 31, 127])
def test_fm_packed_phi_matches_split_score_and_grad(K, dtype):
    """The packed helper (ops.fm._fm_packed_phi: lane mask, sums over L on
    whole rows, fold of the P blocks last) against the split-layout
    fm_score and its jax.grad: loss, g0 and the packed gradient scattered
    back to per-feature rows, for every pack geometry — (8,16), (16,8),
    (24,8) a 192-lane row, (32,4), and P = 1 — with a repeated id inside a
    row, two ids of one packed row with different sub, id-0 padding, a
    partial row_mask and per-occurrence L2. The occurrence step (SGD) and
    the fused scorer run the same helper and are held to the same
    reference."""
    import jax
    import jax.numpy as jnp
    from hivemall_tpu.ops.fm import (_fm_packed_phi, fm_pack_geometry,
                                     fm_score, make_fm_score_fused,
                                     make_fm_step_fused)
    from hivemall_tpu.ops.losses import get_loss
    from hivemall_tpu.ops.optimizers import make_optimizer

    Wf, P = fm_pack_geometry(K)
    assert (Wf, P) == {5: (8, 16), 8: (16, 8), 20: (24, 8), 31: (32, 4),
                       127: (128, 1)}[K]
    rng = np.random.default_rng(K)
    Np = 5
    N = Np * P
    dt = jnp.dtype(dtype)
    # the tables as the trainer holds them: rounded to the table dtype;
    # the reference reads the same values, widened
    w = np.asarray(jnp.asarray(rng.normal(0, .3, N), dt).astype(jnp.float32))
    V = np.asarray(jnp.asarray(rng.normal(0, .3 / np.sqrt(K), (N, K)),
                               dt).astype(jnp.float32))
    R = np.zeros((N, Wf), np.float32)
    R[:, :K], R[:, K] = V, w
    T = jnp.asarray(R.reshape(Np, P * Wf), dt)
    w0 = jnp.asarray(0.25, dt)
    a, b = (1, 2) if P > 1 else (1, 3)     # P > 1: one packed row, two subs
    idx = np.array([[a, b, N - 1, a, 0],   # repeated id, shared row, padding
                    [N - 2, 0, 0, 0, 0],
                    [b, P + 1 if P > 1 else 2, 2 * P, b, b],
                    [3, 4, N - 1, 0, 0],
                    [0, 0, 0, 0, 0],
                    [a, N - 3, 1, 2, 0]], np.int32) % N
    val = np.where(idx != 0, rng.uniform(.5, 1.5, idx.shape), 0.).astype(
        np.float32)
    label = np.array([1, -1, 1, -1, 1, 1], np.float32)
    row_mask = np.array([1, 1, 1, 0, 1, 1], np.float32)
    lam0, lam_w, lam_v = 0.02, 0.03, 0.05
    loss = get_loss("logloss")
    pm = (val != 0) * row_mask[:, None]

    def ref_loss(w0f, wf, Vf, with_l2):
        phi = fm_score(w0f, wf, Vf, idx, val)
        data = (loss.loss(phi, label) * row_mask).sum()
        l2 = 0.5 * (pm * (lam_w * wf[idx] ** 2
                          + lam_v * (Vf[idx] ** 2).sum(-1))).sum()
        return data + with_l2 * l2

    w0f = jnp.float32(w0)
    want_loss = ref_loss(w0f, jnp.asarray(w), jnp.asarray(V), 0.)
    g0, gw, gV = jax.grad(ref_loss, argnums=(0, 1, 2))(
        w0f, jnp.asarray(w), jnp.asarray(V), 1.)

    idxT, valT = jnp.asarray(idx.T), jnp.asarray(val.T)
    rows, sub = idxT // P, idxT % P

    def packed_loss(w0f, s128):
        phi, reg = _fm_packed_phi(w0f, s128, sub, valT, K, Wf, P,
                                  (lam_w, lam_v, jnp.asarray(pm.T)))
        return (loss.loss(phi, label) * row_mask).sum() + reg

    got_loss, (h0, g128) = jax.value_and_grad(packed_loss, argnums=(0, 1))(
        w0f, T[rows])
    assert g128.shape == (idx.shape[1], idx.shape[0], P * Wf)
    assert g128.dtype == dt
    G = np.asarray(jnp.zeros(T.shape, jnp.float32).at[rows.reshape(-1)].add(
        g128.astype(jnp.float32).reshape(-1, P * Wf))).reshape(N, Wf)
    # a bfloat16 table hands back a gradient rounded per occurrence
    tol = dict(rtol=1e-5, atol=1e-6) if dtype == "float32" else \
        dict(rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    np.testing.assert_allclose(h0, g0, rtol=1e-5)
    np.testing.assert_allclose(G[:, :K], gV, **tol)
    np.testing.assert_allclose(G[:, K], gw, **tol)
    assert not G[:, K + 1:].any()           # pad lanes: exact zeros
    assert not G[0].any()                   # id 0 is padding (val 0)

    # the fused scorer: the same helper, no gradient
    np.testing.assert_allclose(
        make_fm_score_fused(K)(w0, T, jnp.asarray(idx), jnp.asarray(val)),
        fm_score(w0f, jnp.asarray(w), jnp.asarray(V), idx, val),
        rtol=1e-5, atol=1e-6)

    # -fm_update occurrence with SGD: one step is T - eta * gradient
    # (the step donates its state: T and w0 are dead after it)
    eta, w0_was = 0.1, np.float32(w0)
    step = make_fm_step_fused(
        loss, make_optimizer("sgd", eta_scheme="fixed", eta0=eta, reg="no"),
        (lam0, lam_w, lam_v), K)
    params, _, loss_sum = step(
        {"T": T, "w0": w0}, {"T": {}, "w0": {}}, jnp.float32(0),
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(label),
        jnp.asarray(row_mask))
    want = R.copy()
    want[:, :K] -= eta * np.asarray(gV)
    want[:, K] -= eta * np.asarray(gw)
    np.testing.assert_allclose(loss_sum, want_loss, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(params["T"].astype(jnp.float32)).reshape(N, Wf), want,
        **(tol if dtype == "float32" else dict(rtol=2e-2, atol=4e-3)))
    np.testing.assert_allclose(
        np.float32(params["w0"]),
        w0_was - eta * (np.float32(g0) + lam0 * w0_was),
        rtol=1e-2 if dtype == "bfloat16" else 1e-5)


def test_fm_adareg_increases_lambda_on_overfit():
    """-adareg (SURVEY §3.6 train_fm row): on an overfittable task (tiny
    sample, label noise, ample capacity) held-out loss degrades as the fit
    memorizes -> lambda_w/lambda_v must be adapted UP from their start."""
    rng = np.random.default_rng(0)
    n, d = 120, 512
    rows = [(np.sort(rng.choice(np.arange(1, d), 6, replace=False)).astype(
        np.int32), np.ones(6, np.float32)) for _ in range(n)]
    labels = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)  # pure noise
    ds = SparseDataset.from_rows(rows, labels)
    t = FMTrainer(f"-dims {d} -factors 8 -classification -opt adagrad "
                  "-eta fixed -eta0 0.5 -mini_batch 32 -iters 8 "
                  "-sigma 0.3 -adareg -va_ratio 0.2 "
                  "-lambda_w 0.001 -lambda_v 0.001")
    assert t._adareg
    t.fit(ds)
    # noise labels: validation loss trends worse as training memorizes
    assert t._lams[1] > 0.001 and t._lams[2] > 0.001, t._lams

    # option validation
    with pytest.raises(ValueError, match="va_ratio"):
        FMTrainer("-dims 64 -adareg -va_ratio 0.9")
    with pytest.raises(ValueError, match="adareg"):
        FMTrainer("-dims 64 -opt ftrl -adareg")


def test_fm_adareg_matches_static_when_never_adapted():
    """Epoch 1 runs on the initial lambdas; with -iters 1 the adareg path
    (dynamic-lambda step + holdout) must train the same model the static
    step trains on the same rows."""
    rows, _, labels = _xor_dataset(200)
    ds = SparseDataset.from_rows(rows, labels)
    opts = ("-dims 64 -factors 4 -classification -opt adagrad -eta fixed "
            "-eta0 0.1 -mini_batch 64 -iters 1 -sigma 0.3")
    ta = FMTrainer(opts + " -adareg -va_ratio 0.1")
    ta.fit(ds)
    # same split, same seed: rebuild the training subset and fit static
    rng = np.random.default_rng(42)
    perm = rng.permutation(len(ds))
    n_va = max(1, int(round(len(ds) * 0.1)))
    labels_conv = np.where(np.asarray(labels) > 0, 1.0, -1.0)
    ds_conv = SparseDataset(ds.indices, ds.indptr, ds.values,
                            labels_conv, ds.fields)
    ds_tr = ds_conv.take(perm[n_va:])
    ts = FMTrainer(opts)
    ts._fit_epochs(ds_tr, 1, 64, True, None, None, seed0=42)
    np.testing.assert_allclose(np.asarray(ta.params["T"], np.float32),
                               np.asarray(ts.params["T"], np.float32),
                               rtol=1e-5, atol=1e-6)


def test_fm_minibatch_update_converges_like_occurrence():
    """-fm_update minibatch (one scatter into dense G + dense AdaGrad, the
    FFM fused paths' accumulator semantics) is the adagrad default; it
    must reach the same solution quality as the per-occurrence chain and
    stay close in function space."""
    rows, _, labels = _xor_dataset(600)
    ds = SparseDataset.from_rows(rows, labels)
    opts = ("-dims 64 -factors 4 -classification -opt adagrad -eta fixed "
            "-eta0 0.1 -mini_batch 64 -iters 4 -sigma 0.3")
    tm = FMTrainer(opts)
    assert tm.fm_layout == "fused"
    to = FMTrainer(opts + " -fm_update occurrence")
    tm.fit(ds)
    to.fit(ds)
    y = np.asarray(labels)
    assert auc(y, tm.predict(ds)) > 0.95
    # same optimization problem, mildly different adaptive scaling:
    # predictions agree in rank almost everywhere
    am, ao = tm.predict(ds), to.predict(ds)
    assert np.corrcoef(am, ao)[0, 1] > 0.98
    with pytest.raises(ValueError, match="minibatch"):
        FMTrainer("-dims 64 -opt sgd -fm_update minibatch")


def test_fm_fused_unit_val_elision():
    """Categorical FM batches drop the val array; the fused step rebuilds
    it from idx on device — same model as the explicit-val path."""
    rows, _, labels = _xor_dataset(400)
    ds = SparseDataset.from_rows(rows, labels)
    opts = ("-dims 64 -factors 4 -classification -opt adagrad -eta fixed "
            "-eta0 0.1 -mini_batch 64 -iters 3 -sigma 0.3")
    t1 = FMTrainer(opts)
    b = t1._preprocess_batch(next(ds.batches(64)))
    assert b.val is None                   # elision engaged (all-unit vals)
    t1.fit(ds)
    t2 = FMTrainer(opts)
    t2.UNIT_VAL_ELISION = False
    t2.fit(ds)
    np.testing.assert_allclose(np.asarray(t1.params["T"], np.float32),
                               np.asarray(t2.params["T"], np.float32),
                               rtol=1e-5, atol=1e-6)


def test_fm_warm_start_layout_mismatch_is_friendly(tmp_path):
    """Loading a split-layout save into a fused-layout trainer (or vice
    versa) must raise the diagnostic ValueError, not a raw npz KeyError."""
    t = FMTrainer("-dims 64 -factors 4 -fm_table split -opt adagrad")
    p = str(tmp_path / "m.npz")
    t.save_model(p)
    with pytest.raises(ValueError, match="fm_table"):
        FMTrainer(f"-dims 64 -factors 4 -opt adagrad -loadmodel {p}")


def test_ffm_scoring_fieldmajor_matches_pairs_scorer():
    """decision_function routes canonical batches through the field-major
    scorer; predictions must match the general pairs scorer exactly."""
    rows, fields, labels = _xor_dataset(300)
    ds = SparseDataset.from_rows(rows, labels, fields=fields)
    t = FFMTrainer("-dims 64 -factors 4 -fields 4 -classification "
                   "-opt adagrad -mini_batch 64 -iters 3 -sigma 0.3")
    t.fit(ds)
    fast = t.predict(ds)
    t2 = FFMTrainer("-dims 64 -factors 4 -fields 4 -classification "
                    "-opt adagrad -mini_batch 64 -iters 3 -sigma 0.3 "
                    "-ffm_interaction pairs")
    t2.fit(ds)
    slow = t2.predict(ds)
    np.testing.assert_allclose(fast, slow, rtol=2e-2, atol=2e-3)


def test_ffm_forced_fieldmajor_scoring_falls_back_on_overflow():
    """Forced -ffm_interaction fieldmajor: a scoring row with too many
    same-field features must score through the pairs kernel, not raise."""
    rows, fields, labels = _xor_dataset(100)
    ds = SparseDataset.from_rows(rows, labels, fields=fields)
    t = FFMTrainer("-dims 64 -factors 4 -fields 4 -classification "
                   "-opt adagrad -mini_batch 32 -iters 2 "
                   "-ffm_interaction fieldmajor")
    t.fit(ds)
    # 6 features all in field 0: canonicalization overflows max_m=4
    odd = SparseDataset.from_rows(
        [(np.arange(1, 7, dtype=np.int32), np.ones(6, np.float32))],
        [1.0], fields=[np.zeros(6, np.int32)])
    out = t.predict(odd)
    assert np.isfinite(out).all()


def test_ffm_pack_input_bit_exact():
    """-pack_input on (3-byte idx lanes + f32 label bytes in ONE uint8
    buffer, unpacked on device) must be bit-identical to the unpacked
    path — same params after an epoch, joint layout."""
    import numpy as np
    from hivemall_tpu.io.sparse import SparseDataset
    from hivemall_tpu.models.fm import FFMTrainer

    B, L, F, K, dims, n = 256, 8, 8, 4, 1 << 20, 1024
    rng = np.random.default_rng(1)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32), (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L, dtype=np.int64)
    ds = SparseDataset(idx.ravel(), indptr, np.ones(n * L, np.float32),
                       lab, fld.ravel())
    cfg = (f"-dims {dims} -factors {K} -fields {F} -mini_batch {B} "
           f"-opt adagrad -classification -halffloat -seed 5")
    a = FFMTrainer(cfg + " -pack_input off")
    a.fit(ds, epochs=1, shuffle=False, prefetch=False)
    b = FFMTrainer(cfg + " -pack_input on")
    b.fit(ds, epochs=1, shuffle=False, prefetch=False)
    for k2 in a.params:
        pa = np.asarray(a.params[k2], np.float32)
        pb = np.asarray(b.params[k2], np.float32)
        np.testing.assert_array_equal(pa, pb, err_msg=k2)
    assert a.cumulative_loss == b.cumulative_loss


def test_ffm_pack_input_partial_batch_mask():
    """A short tail batch (n_valid < B) must keep its padded rows out of
    the loss on the packed path, matching the unpacked path exactly."""
    import numpy as np
    from hivemall_tpu.io.sparse import SparseDataset
    from hivemall_tpu.models.fm import FFMTrainer

    B, L, F, K, dims, n = 256, 8, 8, 4, 1 << 20, 300   # 300 = 256 + 44
    rng = np.random.default_rng(3)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32), (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L, dtype=np.int64)
    ds = SparseDataset(idx.ravel(), indptr, np.ones(n * L, np.float32),
                       lab, fld.ravel())
    cfg = (f"-dims {dims} -factors {K} -fields {F} -mini_batch {B} "
           f"-opt adagrad -classification -halffloat -seed 5")
    a = FFMTrainer(cfg + " -pack_input off")
    a.fit(ds, epochs=1, shuffle=False, prefetch=False)
    b = FFMTrainer(cfg + " -pack_input on")
    b.fit(ds, epochs=1, shuffle=False, prefetch=False)
    for k2 in a.params:
        np.testing.assert_array_equal(np.asarray(a.params[k2], np.float32),
                                      np.asarray(b.params[k2], np.float32),
                                      err_msg=k2)


def test_ffm_device_replay_cache_multi_epoch():
    """-iters/epochs >= 2 with the packed path: epoch 1 streams, later
    epochs replay DEVICE-resident rows. shuffle=False replays the exact
    batch composition, so params must be bit-equal to the uncached path;
    shuffle=True must still converge with the same example count."""
    import numpy as np
    from hivemall_tpu.io.sparse import SparseDataset
    from hivemall_tpu.models.fm import FFMTrainer

    B, L, F, K, dims, n = 256, 8, 8, 4, 1 << 20, 900   # 900 = 3*256 + 132
    rng = np.random.default_rng(11)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32), (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L, dtype=np.int64)
    ds = SparseDataset(idx.ravel(), indptr, np.ones(n * L, np.float32),
                       lab, fld.ravel())
    cfg = (f"-dims {dims} -factors {K} -fields {F} -mini_batch {B} "
           "-opt adagrad -classification -halffloat -seed 5 "
           "-pack_input on")
    a = FFMTrainer(cfg)
    a.fit(ds, epochs=3, shuffle=False, prefetch=False)
    b = FFMTrainer(cfg.replace("-pack_input on", "-pack_input off"))
    b.fit(ds, epochs=3, shuffle=False, prefetch=False)
    for k2 in a.params:
        np.testing.assert_array_equal(
            np.asarray(a.params[k2], np.float32),
            np.asarray(b.params[k2], np.float32), err_msg=k2)
    assert a._examples == b._examples == 3 * n
    c = FFMTrainer(cfg)
    c.fit(ds, epochs=3, shuffle=True, prefetch=False)
    assert c._examples == 3 * n
    assert np.isfinite(c.cumulative_loss)


def test_ffm_fit_stream_replay_cache_multi_epoch():
    """fit_stream with an epoch factory: epoch 1 streams + retains the
    staged device buffers, epochs >= 2 replay on device — bit-equal to
    re-streaming the same epochs when replay_shuffle=False (VERDICT r4
    weak #5: the out-of-core path re-paid the link every epoch)."""
    import numpy as np
    from hivemall_tpu.io.sparse import SparseDataset
    from hivemall_tpu.models.fm import FFMTrainer

    B, L, F, K, dims, n = 128, 8, 8, 4, 1 << 20, 520
    rng = np.random.default_rng(12)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32), (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L, dtype=np.int64)
    ds = SparseDataset(idx.ravel(), indptr, np.ones(n * L, np.float32),
                       lab, fld.ravel())
    cfg = (f"-dims {dims} -factors {K} -fields {F} -mini_batch {B} "
           "-opt adagrad -classification -halffloat -seed 5 "
           "-pack_input on")

    def factory():
        return ds.batches(B, shuffle=False)

    a = FFMTrainer(cfg)
    a.fit_stream(factory, epochs=3, replay_shuffle=False)
    # uncached reference: identical epochs, streamed each time
    b = FFMTrainer(cfg.replace("-pack_input on", "-pack_input off"))
    for _ in range(3):
        b.fit_stream(factory())
    for k2 in a.params:
        np.testing.assert_array_equal(
            np.asarray(a.params[k2], np.float32),
            np.asarray(b.params[k2], np.float32), err_msg=k2)
    assert a._examples == b._examples == 3 * n

    # iterable + epochs>1 is a usage error; factory with epochs=1 works
    with pytest.raises(ValueError, match="factory"):
        FFMTrainer(cfg).fit_stream(factory(), epochs=2)
    c = FFMTrainer(cfg)
    c.fit_stream(factory, epochs=1)
    assert c._examples == n


def test_step_builders_shared_across_instances():
    """Round 4: jitted steps/scorers are config-cached at module level —
    two same-config trainers share ONE compiled step (the per-instance
    re-jit cost word2vec 4x and LDA 10x before the same fix), while their
    training state stays independent."""
    import numpy as np
    from hivemall_tpu.models.fm import FFMTrainer, FMTrainer

    cfg = ("-dims 4096 -factors 4 -fields 8 -mini_batch 64 -opt adagrad "
           "-classification -halffloat")
    a, b = FFMTrainer(cfg), FFMTrainer(cfg)
    assert a._step_fm_unit is b._step_fm_unit
    assert a._fused_score_fm is b._fused_score_fm
    c = FFMTrainer(cfg + " -lambda_v 0.5")      # different config: distinct
    assert c._step_fm_unit is not a._step_fm_unit
    f1, f2 = FMTrainer("-dims 1024 -factors 4"), FMTrainer("-dims 1024 "
                                                           "-factors 4")
    assert f1._step is f2._step
    # shared step, separate state: training a must not move b
    rng = np.random.default_rng(0)
    rows = [([f"{f}:{int(i)}:1" for f, i in
              zip(range(8), rng.integers(1, 4000, 8))], 1 if k % 2 else -1)
            for k in range(128)]
    for feats, lab in rows:
        a.process(feats, lab)
    list(a.close())
    assert not np.array_equal(np.asarray(a.params["T"], np.float32),
                              np.asarray(b.params["T"], np.float32))


def test_fm_adareg_regression_objective():
    """-adareg with the squared-loss (regression) objective: the holdout
    loss path must work for non-classification FM too."""
    rng = np.random.default_rng(0)
    rows = [(np.sort(rng.choice(np.arange(1, 50), 4,
                                replace=False)).astype(np.int32),
             np.ones(4, np.float32)) for _ in range(120)]
    y = rng.normal(size=120).astype(np.float32)
    t = FMTrainer("-dims 64 -factors 4 -opt adagrad -mini_batch 32 "
                  "-iters 3 -adareg -va_ratio 0.2")
    t.fit(SparseDataset.from_rows(rows, y))
    assert np.isfinite(t._lams).all() and (t._lams > 0).all()


def test_ffm_fit_stream_fail_open_over_budget():
    """fit_stream(epochs>1) with a cache budget the epoch cannot fit:
    replay falls open to re-streaming the factory — same model, same
    example count (no silent data loss)."""
    import numpy as np
    from hivemall_tpu.models.fm import FFMTrainer

    B, L, F, K, dims, n = 128, 8, 8, 4, 1 << 20, 384
    rng = np.random.default_rng(9)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32), (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L, dtype=np.int64)
    ds = SparseDataset(idx.ravel(), indptr, np.ones(n * L, np.float32),
                       lab, fld.ravel())
    cfg = (f"-dims {dims} -factors {K} -fields {F} -mini_batch {B} "
           "-opt adagrad -classification -halffloat -seed 5 "
           "-pack_input on")
    a = FFMTrainer(cfg)
    a._DEVICE_CACHE_MB = 0          # force over-budget -> fail-open
    a.fit_stream(lambda: ds.batches(B, shuffle=False), epochs=3,
                 replay_shuffle=False)
    b = FFMTrainer(cfg)
    for _ in range(3):
        b.fit_stream(ds.batches(B, shuffle=False))
    assert a._examples == b._examples == 3 * n
    np.testing.assert_array_equal(
        np.asarray(a.params["T"], np.float32),
        np.asarray(b.params["T"], np.float32))
