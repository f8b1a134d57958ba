"""Online top-k retrieval plane (ISSUE 20): knn/ann two-stage ANN,
serve/retrieve.RetrievalEngine, the HMR1 response frame, the /retrieve
route on both serving planes, and the promotion gate's recall guardrail.

The seconds-scale concurrent/hot-reload acceptance surface lives in the
run_tests.sh smoke (``python -m hivemall_tpu.serve.retrieve_smoke``
under tsan+leaktrack on both planes); these tests pin the semantics at
suite-friendly shapes."""

import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

from hivemall_tpu.frame.tools import each_top_k
from hivemall_tpu.knn.ann import (SrpIndex, exact_top_ids, mips_augment,
                                  mips_query, recall_at_k)
from hivemall_tpu.serve.retrieve import (KIND_ITEM_NEIGHBORS,
                                         KIND_USER_ITEMS, RetrievalEngine)

OPTS = "-factors 4 -users 8 -items 16 -mini_batch 64 -iters 1"
N_USERS, N_ITEMS = 8, 16


def _train_mf(ckdir, seed=7, epochs=2):
    from hivemall_tpu.models.mf import MFTrainer
    t = MFTrainer(OPTS)
    rng = np.random.default_rng(seed)
    t.fit(rng.integers(0, N_USERS, 512), rng.integers(0, N_ITEMS, 512),
          rng.normal(3.0, 1.0, 512).astype(np.float32), epochs=epochs)
    os.makedirs(ckdir, exist_ok=True)
    path = os.path.join(ckdir, f"train_mf_sgd-step{int(t._t):010d}.npz")
    t.save_bundle(path)
    return t, path


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    ck = str(tmp_path_factory.mktemp("retrieve_ck"))
    t, path = _train_mf(ck)
    return {"trainer": t, "bundle": path, "ckdir": ck}


def _engine(trained, **kw):
    kw.setdefault("rescore", "numpy")
    return RetrievalEngine("train_mf_sgd", OPTS,
                           bundle=trained["bundle"], **kw)


def _oracle_ids(eng, kind, qid, k):
    s = eng.exact_scores(kind, qid)
    return [int(v) for _rank, _s, v in
            each_top_k(k, [qid] * len(s), [float(x) for x in s],
                       list(range(len(s))))]


# --- knn/ann primitives ------------------------------------------------------

def test_exact_top_ids_matches_each_top_k():
    """exact_top_ids == the reference UDTF's ranking, including the tie
    rule (descending score, ties by arrival order)."""
    rng = np.random.default_rng(1)
    s = np.round(rng.standard_normal(200), 1).astype(np.float32)  # ties
    for k in (1, 5, 17, 200):
        want = [int(v) for _r, _s, v in
                each_top_k(k, [0] * len(s), [float(x) for x in s],
                           list(range(len(s))))]
        assert exact_top_ids(s, k).tolist() == want, k
    assert exact_top_ids(s, 0).tolist() == []


def test_mips_reduction_preserves_dot_order():
    """Neyshabur–Srebro: cosine order in the augmented space == inner
    product (+bias) order in the raw space, and every augmented row has
    norm M."""
    rng = np.random.default_rng(2)
    Q = rng.standard_normal((64, 6)).astype(np.float32) \
        * rng.uniform(0.2, 3.0, (64, 1)).astype(np.float32)  # mixed norms
    bi = rng.standard_normal(64).astype(np.float32)
    for bias in (None, bi):
        aug, M = mips_augment(Q, bias)
        assert aug.shape == (64, Q.shape[1] + (2 if bias is not None
                                               else 1))
        norms = np.sqrt((aug * aug).sum(-1))
        assert np.allclose(norms, M, rtol=1e-5)
        for _ in range(5):
            p = rng.standard_normal(6).astype(np.float32)
            dots = Q @ p + (bias if bias is not None else 0.0)
            qa = mips_query(p, has_bias=bias is not None)
            # equal norms => cosine order == augmented-dot order; the
            # augmented dot IS the raw dot (+bias): fill slot is 0
            assert np.allclose(aug @ qa, dots, atol=1e-4)
            assert exact_top_ids(aug @ qa, 10).tolist() \
                == exact_top_ids(dots, 10).tolist()


def test_srp_index_clamp_determinism_and_stats():
    rng = np.random.default_rng(3)
    V = rng.standard_normal((200, 8)).astype(np.float32)
    idx = SrpIndex(V, n_tables=6, n_bits=10)
    # catalog clamp: 2^b ~ N/4 (200 rows -> 5 bits), never raised
    assert idx.n_bits == 5
    assert SrpIndex(V[:3], n_bits=10).n_bits == 2
    assert SrpIndex(V, n_bits=3).n_bits == 3
    with pytest.raises(ValueError):
        SrpIndex(V, n_bits=0)
    with pytest.raises(ValueError):
        SrpIndex(V[0])
    st = idx.stats()
    assert st["rows"] == 200 and st["tables"] == 6 and st["bits"] == 5
    assert st["buckets"] > 0 and st["max_bucket"] >= st["mean_bucket"] > 0
    # same seed -> identical candidate sets; ascending unique ids; and
    # every probe finds at least its own bucket-mates
    twin = SrpIndex(V, n_tables=6, n_bits=10)
    for i in (0, 7, 199):
        c = idx.candidates(V[i])
        assert np.array_equal(c, twin.candidates(V[i]))
        assert np.array_equal(c, np.unique(c))
        assert i in c


def test_lsh_recall_rises_with_tables_and_candidates_stay_sublinear():
    """Seeded factors with planted rank-8 structure, seeded hyperplanes,
    the MIPS-augmented geometry the serving tier hashes: more tables can
    only widen the candidate union, so recall@10 against exact search
    rises table over table while the union stays a small share of the
    catalog."""
    rng = np.random.default_rng(11)
    users, items = 64, 8192
    P = rng.standard_normal((users, 8)).astype(np.float32)
    Q = rng.standard_normal((items, 8)).astype(np.float32)
    bi = (0.1 * rng.standard_normal(items)).astype(np.float32)
    aug, _m = mips_augment(Q, bi)
    recall, frac = {}, {}
    for n_tables in (2, 12):
        idx = SrpIndex(aug, n_tables=n_tables)
        recs, fracs = [], []
        for u in range(users):
            scores = Q @ P[u] + bi
            cands = idx.candidates(mips_query(P[u], has_bias=True))
            fracs.append(len(cands) / items)
            recs.append(recall_at_k(
                cands[exact_top_ids(scores[cands], 10)] if len(cands)
                else [], exact_top_ids(scores, 10)))
        recall[n_tables] = float(np.mean(recs))
        frac[n_tables] = float(np.mean(fracs))
    assert recall[12] >= recall[2] > 0.0, recall
    assert frac[2] <= frac[12] < 0.25, frac


def test_recall_at_k():
    assert recall_at_k([1, 2, 3], [1, 2, 3]) == 1.0
    assert recall_at_k([1, 9, 8], [1, 2, 3]) == pytest.approx(1 / 3)
    assert recall_at_k([], [1, 2]) == 0.0
    assert recall_at_k([1], []) == 1.0          # nothing to find
    assert recall_at_k([1, 2, 9], [1, 9, 5], k=2) == 0.5


# --- RetrievalEngine ---------------------------------------------------------

def test_engine_exact_tier_matches_each_top_k_oracle(trained):
    """Both query kinds through the plane surface
    (retrieve_rows_versioned) bit-match the each_top_k oracle replayed
    over exact_scores; padding is -1 past each query's k."""
    eng = _engine(trained, max_k=20, k_default=5)
    try:
        rows = [eng.parse_query({"user": 3}),
                eng.parse_query({"user": 0, "k": 7}),
                eng.parse_query({"item": 2, "k": 3})]
        packed, step = eng.retrieve_rows_versioned(rows)
        assert packed.shape == (3, 20, 2)
        assert step == int(trained["trainer"]._t)
        for r, (kind, qid, k, _tier) in enumerate(rows):
            ids = packed[r, :, 0]
            got = ids[ids >= 0].astype(int).tolist()
            assert got == _oracle_ids(eng, kind, qid, k), (r, kind, qid)
            assert (ids[k:] == -1).all()
            s = eng.exact_scores(kind, qid)
            assert np.allclose(packed[r, :len(got), 1], s[got], atol=1e-6)
        # item neighbors never include the probe item itself
        nb = _oracle_ids(eng, KIND_ITEM_NEIGHBORS, 2, N_ITEMS - 1)
        assert 2 not in nb and len(nb) == N_ITEMS - 1
        assert eng.queries_user == 2 and eng.queries_item == 1
    finally:
        eng.close()


def test_engine_lsh_tier_recall_and_fallback_counters(trained):
    """At a 16-item catalog the clamped index keeps the candidate union
    dense: the LSH tier's recall vs the exact tier stays high and empty
    unions fall back to exact (counted, never failed)."""
    eng = _engine(trained)
    try:
        recs = []
        for u in range(N_USERS):
            packed, _ = eng.retrieve_rows_versioned(
                [eng.parse_query({"user": u, "k": 5, "tier": "lsh"})])
            ids = packed[0, :, 0]
            got = ids[ids >= 0].astype(int).tolist()
            recs.append(recall_at_k(got, _oracle_ids(
                eng, KIND_USER_ITEMS, u, 5)))
        assert float(np.mean(recs)) >= 0.9, recs
        assert eng.queries_lsh == N_USERS and eng.queries_exact \
            == eng.empty_candidates
    finally:
        eng.close()


def test_engine_parse_query_validation(trained):
    eng = _engine(trained, max_k=10)
    try:
        for bad in ("nope", 7, {}, {"k": 3}, {"user": -1},
                    {"user": 0, "k": 0}, {"user": 0, "k": 11},
                    {"user": 0, "tier": "annoy"}, {"item": "x"}):
            with pytest.raises(ValueError):
                eng.parse_query(bad)
        assert eng.parse_query({"user": 2}) == (KIND_USER_ITEMS, 2,
                                                eng.k_default, 0)
        assert eng.parse_query({"item": 1, "k": 4, "tier": "lsh"}) \
            == (KIND_ITEM_NEIGHBORS, 1, 4, 1)
    finally:
        eng.close()


def test_engine_kernel_rescore_matches_numpy(trained):
    """The jitted kernel dot backend ranks identically to the numpy
    arena twin (same ids; scores to f32 tolerance)."""
    a = _engine(trained, rescore="numpy")
    b = _engine(trained, rescore="kernel")
    try:
        assert b._model.backend == "kernel"
        for q in ({"user": 1, "k": 6}, {"user": 5, "k": 6},
                  {"item": 3, "k": 6}):
            pa, _ = a.retrieve_rows_versioned([a.parse_query(q)])
            pb, _ = b.retrieve_rows_versioned([b.parse_query(q)])
            assert pa[0, :, 0].astype(int).tolist() \
                == pb[0, :, 0].astype(int).tolist(), q
            assert np.allclose(pa[0, :, 1], pb[0, :, 1],
                               rtol=1e-5, atol=1e-5), q
    finally:
        a.close()
        b.close()


def test_engine_int8_scores_within_factor_bound(trained):
    """The int8 tier's exact scores stay inside the arena's published
    per-pair dot-product error bound vs the f32 tier — the ranking can
    only reorder items whose f32 gap is below the summed bounds."""
    from hivemall_tpu.io.weight_arena import factor_score_error_bound
    f32 = _engine(trained, precision="f32")
    i8 = _engine(trained, precision="int8")
    try:
        items = np.arange(N_ITEMS)
        for u in range(N_USERS):
            ref = f32.exact_scores(KIND_USER_ITEMS, u)
            got = i8.exact_scores(KIND_USER_ITEMS, u)
            bound = factor_score_error_bound(
                i8._model.arena, "int8", np.int64(u), items)
            assert (np.abs(got - ref) <= bound + 1e-5).all(), u
        assert (factor_score_error_bound(
            f32._model.arena, "f32", np.int64(0), items) == 0).all()
    finally:
        f32.close()
        i8.close()


def test_engine_follows_promoted_pointer(tmp_path):
    """follow="promoted": poll() swaps on pointer flips (even to an
    OLDER step) and ignores newer unpromoted bundles."""
    from hivemall_tpu.io.checkpoint import promote_bundle
    ck = str(tmp_path)
    t1, p1 = _train_mf(ck, epochs=2)
    promote_bundle(ck, p1)
    eng = RetrievalEngine("train_mf_sgd", OPTS, checkpoint_dir=ck,
                          follow="promoted", rescore="numpy")
    try:
        s1 = eng.model_step
        assert s1 == int(t1._t)
        t2, p2 = _train_mf(ck, epochs=4)           # newer, NOT promoted
        eng.poll()
        assert eng.model_step == s1
        promote_bundle(ck, p2)
        eng.poll()
        assert eng.model_step == int(t2._t) > s1
        assert eng.reloads == 1
        promote_bundle(ck, p1)                     # rollback: older step
        eng.poll()
        assert eng.model_step == s1 and eng.reloads == 2
    finally:
        eng.close()


def test_engine_labels_vocab(trained):
    """labels(): None without a vocab (MF), id->word translation with
    one (the word2vec arena header's vocab list)."""
    eng = _engine(trained)
    try:
        assert eng.labels([0, 1]) is None
        eng._model.vocab = ["a", "b", "c"]
        assert eng.labels([2, 0, 99, -1]) == ["c", "a", None, None]
    finally:
        eng.close()


# --- HMR1 response frame -----------------------------------------------------

def test_response_frame_roundtrip():
    from hivemall_tpu.serve.wire import (decode_response_frame,
                                         encode_response_frame)
    scores = [[0.5, -1.25, 3.0], [], [7.0]]
    ids = [[4, 0, 9], [], [1]]
    for step in (None, 0, 1 << 40):
        for use_ids in (False, True):
            body = encode_response_frame(
                scores, ids if use_ids else None, model_step=step)
            s2, i2, st2 = decode_response_frame(body)
            assert [r.tolist() for r in s2] \
                == [list(map(float, r)) for r in scores]
            if use_ids:
                assert [r.tolist() for r in i2] == ids
            else:
                assert i2 is None
            assert st2 == step


def test_response_frame_malformed():
    from hivemall_tpu.serve.wire import (WireError, decode_response_frame,
                                         encode_response_frame)
    good = encode_response_frame([[1.0, 2.0]], [[3, 4]], model_step=5)
    for bad in (b"", b"HMF1" + good[4:],          # wrong magic
                good[:-3],                        # truncated payload
                good + b"\x00",                   # trailing bytes
                bytes([good[0], good[1], good[2], good[3], 0xFF])
                + good[5:]):                      # unknown flags
        with pytest.raises(WireError):
            decode_response_frame(bad)
    with pytest.raises(WireError):
        encode_response_frame([[1.0]], [[1, 2]])  # ids/scores mismatch
    with pytest.raises(WireError):
        encode_response_frame([[1.0], [2.0]], [[1]])


# --- /retrieve on both serving planes ---------------------------------------

def _post_raw(url, obj, headers=None, timeout=15.0):
    req = urllib.request.Request(
        url, json.dumps(obj).encode(),
        {"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


@pytest.mark.parametrize("plane", ["threaded", "evloop"])
def test_http_retrieve_route(trained, plane):
    """Retrieval-only serving on each plane: /retrieve 200 matches the
    oracle, Accept negotiation returns an HMR1 frame with the model
    step, malformed queries 400 with JSON errors, /predict 404s, and
    the obs snapshot carries the retrieval section."""
    from hivemall_tpu.serve.wire import (CONTENT_TYPE_FRAME,
                                         decode_response_frame)
    if plane == "evloop":
        from hivemall_tpu.serve.evloop import \
            EvloopPredictServer as ServerCls
    else:
        from hivemall_tpu.serve.http import PredictServer as ServerCls
    eng = _engine(trained, k_default=5)
    srv = ServerCls(None, port=0, max_delay_ms=1.0, retrieval=eng).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        code, ctype, body = _post_raw(
            base + "/retrieve",
            {"queries": [{"user": 1, "k": 4}, {"item": 0, "k": 2}]})
        assert code == 200 and "json" in ctype
        r = json.loads(body)
        assert r["results"][0]["ids"] \
            == _oracle_ids(eng, KIND_USER_ITEMS, 1, 4)
        assert r["results"][1]["ids"] \
            == _oracle_ids(eng, KIND_ITEM_NEIGHBORS, 0, 2)
        assert r["model_step"] == eng.model_step

        # bare single-query shorthand + frame negotiation
        code, ctype, body = _post_raw(
            base + "/retrieve", {"user": 1, "k": 4},
            headers={"Accept": CONTENT_TYPE_FRAME})
        assert code == 200 and CONTENT_TYPE_FRAME in ctype
        srows, irows, step = decode_response_frame(body)
        assert irows[0].tolist() \
            == _oracle_ids(eng, KIND_USER_ITEMS, 1, 4)
        assert np.allclose(
            srows[0], eng.exact_scores(KIND_USER_ITEMS, 1)[irows[0]],
            atol=1e-6)
        assert step == eng.model_step

        for bad in ({"k": 3}, {"user": -2}, {"user": 0, "k": 0},
                    {"queries": "x"}, {"user": 0, "tier": "faiss"}):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_raw(base + "/retrieve", bad)
            assert ei.value.code == 400, bad
            assert "error" in json.loads(ei.value.read()), bad
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_raw(base + "/predict", {"features": ["1:1"]})
        assert ei.value.code == 404

        with urllib.request.urlopen(base + "/snapshot", timeout=15) as rr:
            snap = json.loads(rr.read())
        assert snap["retrieval"]["queries_user"] >= 2
        assert snap["retrieval"]["model_step"] == eng.model_step
    finally:
        srv.stop()


# --- promotion gate recall guardrail ----------------------------------------

def test_promotion_gate_recall_guardrail(tmp_path):
    """Factor candidates are recall-checked: a healthy small-catalog MF
    bundle passes end-to-end (recall ~1 under the clamped index) and a
    geometry whose LSH buckets collapse fails with a recall reason."""
    from hivemall_tpu.serve.promote import PromotionGate
    _t, bundle = _train_mf(str(tmp_path))
    gate = PromotionGate("train_mf_sgd", OPTS)
    report = gate.evaluate(bundle)
    assert report["verdict"] == "pass", report
    assert report["checks"]["recall_at_k"] >= 0.95
    assert report["checks"]["recall_k"] == 10

    class _Collapsed:
        """Big iid-noise catalog: no angular structure, 10-bit codes
        scatter the true top-k across buckets and recall craters."""

        def serving_tables(self):
            rng = np.random.default_rng(13)
            return ({"family": "factor", "item_bias": False},
                    {"P": rng.standard_normal((64, 16)).astype(np.float32),
                     "Q": rng.standard_normal((4096, 16)
                                              ).astype(np.float32)})

    checks, reasons = {}, []
    gate._check_retrieval(_Collapsed(), checks, reasons)
    assert checks["recall_at_k"] < 0.95
    assert any("recall@10" in r for r in reasons), reasons

    class _NonFactor:
        def serving_tables(self):
            return {"family": "linear"}, {}

    checks, reasons = {}, []
    gate._check_retrieval(_NonFactor(), checks, reasons)
    assert not checks and not reasons
