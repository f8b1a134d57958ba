"""MF/BPR/SLIM/samplers: convergence + semantics (SURVEY.md §5 style)."""

import numpy as np
import pytest

from hivemall_tpu.io.sparse import SparseBatch
from hivemall_tpu.models.mf import (BPRMFTrainer, MFAdaGradTrainer, MFTrainer,
                                    bprmf_predict, mf_predict)


def synthetic_ratings(U=50, I=40, K=3, n=3000, seed=0):
    rng = np.random.default_rng(seed)
    P = rng.normal(0, 1, (U, K))
    Q = rng.normal(0, 1, (I, K))
    users = rng.integers(0, U, n)
    items = rng.integers(0, I, n)
    ratings = (P[users] * Q[items]).sum(-1) + rng.normal(0, 0.1, n)
    return users, items, ratings.astype(np.float32)


def test_mf_sgd_fits():
    users, items, ratings = synthetic_ratings()
    t = MFTrainer("-factors 3 -eta0 0.05 -lambda 0.001 -iters 30 "
                  "-users 64 -items 64 -mini_batch 256 -sigma 0.3")
    t.fit(users, items, ratings)
    pred = t.predict(users, items)
    rmse = float(np.sqrt(np.mean((pred - ratings) ** 2)))
    assert rmse < 0.6, rmse


def test_mf_adagrad_fits():
    users, items, ratings = synthetic_ratings(seed=2)
    t = MFAdaGradTrainer("-factors 3 -eta0 0.3 -lambda 0.001 -iters 25 "
                         "-users 64 -items 64 -mini_batch 256 -sigma 0.3")
    t.fit(users, items, ratings)
    rmse = float(np.sqrt(np.mean((t.predict(users, items) - ratings) ** 2)))
    assert rmse < 0.6, rmse


def test_mf_udtf_lifecycle_and_rows():
    t = MFTrainer("-factors 2 -users 8 -items 8 -mini_batch 4 -eta0 0.1")
    for _ in range(5):
        t.process(1, 2, 4.0)
        t.process(0, 3, 1.0)
    rows = list(t.close())
    # user rows carry Pu (slot 1), item rows carry Qi (slot 2)
    assert any(r[1] is not None and r[0] == 1 for r in rows)
    assert any(r[2] is not None and r[0] == 2 for r in rows)


def test_bprmf_ranks_pos_above_neg():
    rng = np.random.default_rng(1)
    U, I = 20, 30
    # users prefer even items
    t = BPRMFTrainer("-factors 4 -eta0 0.05 -lambda 0.001 -users 32 "
                     "-items 32 -mini_batch 128 -iters 3 -sigma 0.2")
    for _ in range(4000):
        u = int(rng.integers(0, U))
        pos = int(rng.integers(0, I // 2)) * 2
        neg = int(rng.integers(0, I // 2)) * 2 + 1
        t.process(u, pos, neg)
    list(t.close())
    users = np.repeat(np.arange(U), I // 2)
    even = t.predict(users, np.tile(np.arange(0, I, 2), U))
    odd = t.predict(users, np.tile(np.arange(1, I, 2), U))
    assert (even > odd).mean() > 0.9


def test_predict_udfs_cold_start():
    assert mf_predict([1.0, 2.0], [3.0, 4.0], 0.5, 0.25, 3.0) == \
        pytest.approx(3.0 + 0.5 + 0.25 + 11.0)
    assert mf_predict(None, [1.0], None, 0.5, 3.0) == pytest.approx(3.5)
    assert bprmf_predict([1.0], [2.0], 0.5) == pytest.approx(2.5)
    assert bprmf_predict(None, None, None) == 0.0


def test_slim_recovers_structure():
    from hivemall_tpu.models.slim import SlimTrainer
    rng = np.random.default_rng(3)
    # item 1 == copy of item 0; item 2 independent
    U = 40
    base = rng.uniform(1, 5, U)
    t = SlimTrainer("-l1 0.01 -l2 0.01 -iters 20")
    for u in range(U):
        t.process(u, 0, float(base[u]))
        t.process(u, 1, float(base[u]))
        t.process(u, 2, float(rng.uniform(1, 5)))
    W = {(j, i): w for j, i, w in t.close()}
    # W[0 -> 1] strong (item 0 explains item 1), both >> any weight into 2
    assert W.get((0, 1), 0.0) > 0.5
    assert W.get((0, 1), 0.0) > abs(W.get((0, 2), 0.0))
    assert (0, 0) not in W     # diag forced to zero


def test_samplers():
    from hivemall_tpu.ftvec.ranking import (bpr_sampling, item_pairs_sampling,
                                            populate_not_in)
    trips = list(bpr_sampling(7, [1, 2, 3], 10, 2.0, seed=0))
    assert len(trips) == 6
    for u, p, n in trips:
        assert u == 7 and p in (1, 2, 3) and n not in (1, 2, 3)
        assert 0 <= n <= 10
    pairs = list(item_pairs_sampling([4], 6, 3.0, seed=1))
    assert all(p == 4 and q != 4 for p, q in pairs)
    assert list(populate_not_in([0, 2], 4)) == [1, 3, 4]


# -- the normal path: fit_stream, the two tables' distinct-row tail ----------

# what the trainers gave before they were LearnerBases (dense step over
# [U,k] / [I,k] / [U] / [I] tables), on 8 full batches of 64 seeded ratings
# (-factors 6 -users 40 -items 24 -mini_batch 64 -eta0 0.05 -mu 3.0
# -lambda 0.02 -seed 9): sum(P), sum|P|, sum(Q), sum(bu), sum(bi), the
# summed loss after process(); after fit(epochs=2): sum(P), sum|P|, sum(Q),
# sum(bu), the summed loss, and predict() of the first four rows
GOLDEN = {
    "train_mf_sgd": {
        "process": [0.290136181, 16.8936583, -0.708146062, 1.30102527,
                    1.29578781, 271.403412],
        "fit": [0.230287008, 17.0326339, -0.707863876, 1.04401195,
                528.073608],
        "predict": [2.9271102, 3.32939601, 3.25658488, 2.62429905]},
    "train_mf_adagrad": {
        "process": [-0.353237859, 19.3059059, -0.275059545, 0.560498476,
                    0.480643809, 260.859802],
        "fit": [-0.283871167, 24.9100101, -0.805645122, 1.08139467,
                506.380676],
        "predict": [2.965729, 3.10857654, 3.16995096, 2.77384996]},
    # sum(P), sum(Q), sum(bi), the summed loss after process()
    "train_bprmf": {"process": [0.4891058, -0.681992166, 0.00165486336,
                                355.632172]},
}
GOLDEN_OPTS = "-factors 6 -users 40 -items 24 -mini_batch 64 -eta0 0.05 " \
    "-seed 9 -lambda 0.02"


def _golden_rows():
    rng = np.random.default_rng(17)
    u = rng.integers(0, 40, 512).astype(np.int32)
    i = rng.integers(0, 24, 512).astype(np.int32)
    r = (3.0 + rng.normal(0, 1, 512)).astype(np.float32)
    return u, i, r, rng.integers(0, 24, 512).astype(np.int32)


def _sums(t):
    k = t.k
    P = np.asarray(t.params["U"][:, :k], np.float64)
    Q = np.asarray(t.params["I"][:, :k], np.float64)
    return P, Q, np.asarray(t.params["U"][:, k]), \
        np.asarray(t.params["I"][:, k])


@pytest.mark.parametrize("cls", [MFTrainer, MFAdaGradTrainer],
                         ids=lambda c: c.NAME)
def test_legacy_process_and_fit_give_what_they_gave(cls):
    u, i, r, _ = _golden_rows()
    want = GOLDEN[cls.NAME]
    t = cls(GOLDEN_OPTS + " -mu 3.0")
    for a, b, c in zip(u, i, r):
        t.process(int(a), int(b), float(c))
    t._flush()
    P, Q, bu, bi = _sums(t)
    got = [P.sum(), np.abs(P).sum(), Q.sum(), bu.sum(), bi.sum(), t.cum_loss]
    np.testing.assert_allclose(got, want["process"], rtol=2e-5, atol=1e-6)
    t = cls(GOLDEN_OPTS + " -mu 3.0").fit(u, i, r, epochs=2)
    P, Q, bu, _ = _sums(t)
    got = [P.sum(), np.abs(P).sum(), Q.sum(), bu.sum(), t.cum_loss]
    np.testing.assert_allclose(got, want["fit"], rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(t.predict(u[:4], i[:4]), want["predict"],
                               rtol=1e-6)


def test_legacy_bprmf_gives_what_it_gave():
    u, i, _, j = _golden_rows()
    t = BPRMFTrainer(GOLDEN_OPTS)
    for a, b, c in zip(u, i, j):
        t.process(int(a), int(b), int(c))
    t._flush()
    P, Q, _, bi = _sums(t)
    np.testing.assert_allclose([P.sum(), Q.sum(), bi.sum(), t.cum_loss],
                               GOLDEN["train_bprmf"]["process"],
                               rtol=2e-5, atol=1e-7)


def _plain_adagrad_steps(P, bu, Q, bi, steps, k, mu, lam, eta0):
    """The published equations in float64, whole tables: squared error of
    mu + p_u.q_i + b_u + b_i, L2 on the rows each rating reads, the
    gradients of a row's ratings summed, per-element AdaGrad. Also which
    elements ever had a summed gradient under 1e-4 but not zero: AdaGrad
    moves such an element by up to eta0 g / (|g| + 1e-6), so float32's
    rounding of g (~1e-7) moves it by a share of eta0, not to rounding."""
    P, bu, Q, bi = (np.asarray(a, np.float64).copy() for a in (P, bu, Q, bi))
    gg = [np.zeros_like(a) for a in (P, bu, Q, bi)]
    tiny = [np.zeros(a.shape, bool) for a in (P, bu, Q, bi)]
    for u, i, r in steps:
        err = r - (mu + (P[u] * Q[i]).sum(1) + bu[u] + bi[i])
        G = [np.zeros_like(a) for a in (P, bu, Q, bi)]
        np.add.at(G[0], u, -err[:, None] * Q[i] + 2 * lam * P[u])
        np.add.at(G[1], u, -err + 2 * lam * bu[u])
        np.add.at(G[2], i, -err[:, None] * P[u] + 2 * lam * Q[i])
        np.add.at(G[3], i, -err + 2 * lam * bi[i])
        for n, (a, g) in enumerate(zip((P, bu, Q, bi), G)):
            gg[n] += g * g
            tiny[n] |= (g != 0) & (np.abs(g) < 1e-4)
            a -= eta0 * g / (np.sqrt(gg[n]) + 1e-6)
    return P, bu, Q, bi, gg, tiny


def test_fit_stream_dispatch_matches_the_plain_equations():
    """One fused dispatch of four steps through fit_stream, the tables
    of the two id spaces each through the distinct-row tail where the
    batch fits its capacity (users: tail_cap 128 of 2,048 rows; items:
    whole_table_cap 128 of 200), the dense tail where it does not. Step 1
    within both; step 2 one hot user and one hot item repeated 200 times;
    step 3 past the users' capacity; step 4 past the items'."""
    from hivemall_tpu.ops import fm
    B, k = 256, 6
    assert fm.tail_cap(B, 2048) == 128 and fm.tail_cap(B, 200) == 0 \
        and fm.whole_table_cap(B, 200) == 128
    t = MFAdaGradTrainer(f"-factors {k} -users 2048 -items 200 "
                         f"-mini_batch {B} -eta0 0.05 -mu 3.0 -lambda 0.02 "
                         "-steps_per_dispatch 4 -sigma 0.3")
    rng = np.random.default_rng(5)
    steps = [(rng.integers(0, 100, B), rng.integers(0, 60, B)),
             (np.where(np.arange(B) < 200, 7, rng.integers(0, 50, B)),
              np.where(np.arange(B) < 200, 3, rng.integers(0, 50, B))),
             (rng.permutation(2048)[:B], rng.integers(0, 60, B)),
             (rng.integers(0, 100, B),
              np.concatenate([rng.permutation(200)[:150],
                              rng.permutation(200)[:B - 150]]))]
    steps = [(u.astype(np.int32), i.astype(np.int32),
              (3.0 + rng.normal(0, 1, B)).astype(np.float32))
             for u, i in steps]
    assert len(np.unique(steps[2][0])) > 128 \
        and len(np.unique(steps[3][1])) > 128
    U0, I0 = (np.asarray(t.params[n], np.float64) for n in ("U", "I"))
    batches = [SparseBatch(np.stack([u, i], 1), None, r)
               for u, i, r in steps]
    t.fit_stream(iter(batches), convert_labels=False)
    assert t.pipeline_stats.megabatches_staged == 1
    t.cumulative_loss                           # folds the step's counters
    counts = t._step_counts
    assert (counts["tail_distinct_steps"], counts["tail_dense_steps"]) \
        == (2, 2), counts
    assert counts["mf_user_rows"] == sum(len(np.unique(u))
                                         for u, _, _ in steps)
    assert counts["mf_item_rows"] == sum(len(np.unique(i))
                                         for _, i, _ in steps)
    before = (U0[:, :k], U0[:, k], I0[:, :k], I0[:, k])
    *want, gg, tiny = _plain_adagrad_steps(*before, steps, k, 3.0, 0.02,
                                           0.05)
    Ut, It = (np.asarray(t.params[n], np.float64) for n in ("U", "I"))
    got = (Ut[:, :k], Ut[:, k], It[:, :k], It[:, k])
    for g, w, b, few in zip(got, want, before, tiny):
        assert few.mean() < 1e-3
        np.testing.assert_allclose(g[~few], w[~few], rtol=2e-5, atol=2e-6)
        # every element, the tiny gradients' too: the change's norm
        ref = np.linalg.norm(w - b)
        assert abs(np.linalg.norm(g - b) - ref) < 1e-5 * ref
    G = [np.asarray(t.opt_state[n]["gg"], np.float64) for n in ("U", "I")]
    for got, want in ((G[0][:, :k], gg[0]), (G[0][:, k], gg[1]),
                      (G[1][:, :k], gg[2]), (G[1][:, k], gg[3])):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-8)
    # the lanes past the bias stay zero, value and sums
    assert not Ut[:, k + 1:].any() and not It[:, k + 1:].any()
    assert not G[0][:, k + 1:].any() and not G[1][:, k + 1:].any()


def test_rows_round_trip_serving_tables_and_bundle(tmp_path):
    """The 128-lane rows as the model's [U,k] / [I,k] / [U] / [I] tables,
    and the whole state through a checkpoint bundle."""
    users, items, ratings = synthetic_ratings(U=50, I=40, n=600, seed=4)
    opts = "-factors 5 -users 64 -items 48 -mini_batch 128 -eta0 0.1 -seed 3"
    t = MFAdaGradTrainer(opts).fit(users, items, ratings, epochs=2)
    meta, tabs = t.serving_tables()
    assert meta["k"] == 5 and meta["user_bias"] and meta["item_bias"]
    U, I = np.asarray(t.params["U"]), np.asarray(t.params["I"])
    assert U.shape == (64, 128) and I.shape == (48, 128)
    assert tabs["P"].shape == (64, 5) and tabs["bu"].shape == (64,)
    assert tabs["Q"].shape == (48, 5) and tabs["bi"].shape == (48,)
    np.testing.assert_array_equal(tabs["P"], U[:, :5])
    np.testing.assert_array_equal(tabs["bi"], I[:, 5])
    assert np.abs(tabs["bu"]).sum() > 0 and not U[:, 6:].any()
    want = (tabs["P"][users] * tabs["Q"][items]).sum(1) \
        + tabs["bu"][users] + tabs["bi"][items]
    np.testing.assert_allclose(t.predict(users, items), want, rtol=1e-6)
    path = str(tmp_path / "mf.npz")
    t.save_bundle(path)
    back = MFAdaGradTrainer(opts)
    back.load_bundle(path)
    assert back._t == t._t and back._examples == t._examples
    for name in ("U", "I"):
        np.testing.assert_array_equal(np.asarray(back.params[name]),
                                      np.asarray(t.params[name]))
        np.testing.assert_array_equal(
            np.asarray(back.opt_state[name]["gg"]),
            np.asarray(t.opt_state[name]["gg"]))
    for name, table in back.serving_tables()[1].items():
        np.testing.assert_array_equal(table, tabs[name])


def test_a_ratings_table_streams_as_fit_does(tmp_path):
    """Parquet shards of three scalar columns (user, item, rating), read
    by ParquetStream's `id_cols`, train the model `fit` trains on the same
    rows in the same order."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from hivemall_tpu.io.arrow import ParquetStream
    users, items, ratings = synthetic_ratings(U=50, I=40, n=640, seed=8)
    d = tmp_path / "ratings"
    d.mkdir()
    for s in range(0, 640, 320):
        pq.write_table(pa.table({
            "user": pa.array(users[s:s + 320], pa.int32()),
            "item": pa.array(items[s:s + 320], pa.int32()),
            "rating": pa.array(ratings[s:s + 320], pa.float32())}),
            str(d / f"part-{s:05d}.parquet"))
    stream = ParquetStream(str(d), id_cols=("user", "item"),
                           label_col="rating")
    assert stream.max_row_len == 2 and len(stream) == 640
    opts = "-factors 4 -users 64 -items 48 -mini_batch 128 -eta0 0.1"
    a = MFAdaGradTrainer(opts)
    a.fit_stream(stream.batches(128, shuffle=False))
    b = MFAdaGradTrainer(opts).fit(users, items, ratings, epochs=1,
                                   shuffle=False)
    assert a._examples == b._examples == 640
    for name in ("U", "I"):
        np.testing.assert_allclose(np.asarray(a.params[name]),
                                   np.asarray(b.params[name]), rtol=1e-6,
                                   atol=1e-7)


def test_the_cells_capacities_come_from_each_tables_shapes():
    """mf_hugewiki's step (131,072 ratings): the cold user table's list
    from tail_cap (every batch fits), the hot item table's from
    whole_table_cap (tail_cap prices it at 0); a table larger than one
    pass of the compact sum keeps tail_cap's rule."""
    from hivemall_tpu.ops import fm
    from hivemall_tpu.ops.rows_pallas import scatter_rows_cap
    n = 131072
    assert fm.tail_cap(n, 6_260_326) == n
    assert fm.tail_cap(n, 39_780) == 0
    assert fm.whole_table_cap(n, 39_780) == 38912
    assert fm.whole_table_cap(n, scatter_rows_cap(128) + 1) == 0
    assert fm.whole_table_cap(256, 100) == 0      # under one id tile


def test_cli_trains_from_a_ratings_table(tmp_path, capsys):
    """`hivemall-tpu train --algo train_mf_adagrad --input <dir>` over
    Parquet shards of (user, item, rating): the trainer's id columns, the
    rating as the label column, fit_stream, and the model table written."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from hivemall_tpu.cli.main import main
    users, items, ratings = synthetic_ratings(U=50, I=40, n=600, seed=9)
    d = tmp_path / "ratings"
    d.mkdir()
    for s in range(0, 600, 200):
        pq.write_table(pa.table({
            "user": pa.array(users[s:s + 200], pa.int32()),
            "item": pa.array(items[s:s + 200], pa.int32()),
            "rating": pa.array(ratings[s:s + 200], pa.float32())}),
            str(d / f"part-{s:05d}.parquet"))
    model = str(tmp_path / "mf.tsv")
    rc = main(["train", "--algo", "train_mf_adagrad", "--input", str(d),
               "--label-col", "rating", "--options",
               "-factors 4 -users 64 -items 48 -mini_batch 128 -iters 2",
               "--model", model])
    assert rc == 0
    assert '"examples": 1200' in capsys.readouterr().out
    rows = [line.split("\t") for line in open(model)]
    # every row of both tables: the initial factors are not zero
    assert len(rows) == 64 + 48 and all(len(r) == 5 for r in rows)
