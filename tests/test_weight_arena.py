"""Weight arena + quantized scoring + router result cache (ISSUE 15,
docs/PERFORMANCE.md "Weight arena + quantized scoring"): the mmap'd
multi-precision serving sidecar, its numpy scorer twins and error
bounds, the engine's zero-copy load path (quantization OFF bit-matches
the pre-arena path), the promotion gate's quantized-candidate
guardrail, and the router's invalidate-on-reload result cache."""

import json
import os
import threading

import numpy as np
import pytest

from hivemall_tpu.io import weight_arena as wa
from hivemall_tpu.io.libsvm import synthetic_classification
from hivemall_tpu.io.shard_cache import CacheInvalid
from hivemall_tpu.io.sparse import SparseBatch, SparseDataset

OPTS = "-dims 4096 -loss logloss -opt adagrad -mini_batch 64"


def _bundle_path(tmp, trainer):
    return os.path.join(str(tmp),
                        f"{trainer.NAME}-step{trainer._t:010d}.npz")


def _save(tmp, trainer):
    p = _bundle_path(tmp, trainer)
    trainer.save_bundle(p)
    return p


@pytest.fixture(scope="module")
def linear_setup(tmp_path_factory):
    from hivemall_tpu.models.linear import GeneralClassifier
    tmp = tmp_path_factory.mktemp("arena_linear")
    ds, _ = synthetic_classification(256, 80, seed=5)
    t = GeneralClassifier(OPTS)
    t.fit(ds)
    path = _save(tmp, t)
    arena = wa.open_arena(wa.publish_arena(path, t))
    return {"tmp": tmp, "ds": ds, "trainer": t, "path": path,
            "arena": arena}


def _ffm_dataset(n=256, L=8, F=8, dims=4000, seed=9):
    rng = np.random.default_rng(seed)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32) % F, (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    return SparseDataset(idx.ravel(),
                         np.arange(0, n * L + 1, L, dtype=np.int64),
                         rng.uniform(0.5, 1.5, n * L).astype(np.float32),
                         lab, fld.ravel())


def _rand_batch(rng, B, L, dims=4000, F=None):
    idx = rng.integers(1, dims, (B, L)).astype(np.int32)
    val = rng.uniform(0.2, 1.5, (B, L)).astype(np.float32)
    fld = (rng.integers(0, F, (B, L)).astype(np.int32)
           if F is not None else None)
    return SparseBatch(idx, val, np.zeros(B, np.float32), fld)


# --- container / quantization ------------------------------------------------

def test_publish_open_roundtrip(linear_setup):
    a = linear_setup["arena"]
    assert a.family == "linear" and a.classification
    assert a.trainer_name == "train_classifier"
    assert a.step == linear_setup["trainer"]._t
    assert set(a.precisions) == {"f32", "bf16", "int8"}
    assert a.mapped_bytes > 0
    assert a.matches_bundle(linear_setup["path"])


def test_stale_arena_detected(tmp_path):
    from hivemall_tpu.models.linear import GeneralClassifier
    ds, _ = synthetic_classification(128, 60, seed=6)
    t = GeneralClassifier(OPTS)
    t.fit(ds)
    p = os.path.join(str(tmp_path), f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(p)
    ap = wa.publish_arena(p, t)
    # bundle rewritten in place (newer training state, same path):
    # the arena's recorded source digest no longer matches
    t.fit(ds)
    t._t -= 1   # keep the filename/step identical
    t.save_bundle(p)
    assert not wa.open_arena(ap).matches_bundle(p)


def test_corrupt_arena_refused(linear_setup, tmp_path):
    import shutil
    src = wa.arena_path(linear_setup["path"])
    bad = str(tmp_path / "bad.arena")
    shutil.copy(src, bad)
    with open(bad, "r+b") as f:
        f.seek(-16, os.SEEK_END)
        f.write(b"\xff" * 8)
    with pytest.raises(CacheInvalid):
        wa.open_arena(bad)


def test_quantize_int8_contract():
    rng = np.random.default_rng(0)
    a = rng.normal(size=1000).astype(np.float32) * 3.0
    q, scale = wa.quantize_int8(a)
    assert q.dtype == np.int8
    assert np.isclose(scale, np.abs(a).max() / 127.0)
    # round-to-nearest: per-weight error <= scale / 2
    assert np.abs(q.astype(np.float32) * scale - a).max() <= scale / 2 + 1e-7
    qz, sz = wa.quantize_int8(np.zeros(4, np.float32))
    assert sz == 1.0 and not qz.any()


def test_bf16_shift_matches_mldtypes():
    import ml_dtypes
    rng = np.random.default_rng(1)
    a = (rng.normal(size=512).astype(np.float32) *
         10.0 ** rng.integers(-6, 6, 512))
    bits = wa._to_bf16_bits(a)
    via_shift = wa._bf16_bits_to_f32(bits)
    via_lib = bits.view(ml_dtypes.bfloat16).astype(np.float32)
    assert np.array_equal(via_shift, via_lib)


def test_row_hash_matches_jitted():
    import jax.numpy as jnp
    from hivemall_tpu.ops.fm import ffm_row_hash
    rng = np.random.default_rng(2)
    idx = rng.integers(0, 1 << 31, (16, 8)).astype(np.int32)
    for Mr in (256, 4096):
        ref = np.asarray(ffm_row_hash(jnp.asarray(idx), Mr))
        assert np.array_equal(wa._row_hash_np(idx, Mr), ref)


# --- error-bound property tests: every family, every (B, L) bucket ----------

def _family_cases(tmp_path_factory):
    from hivemall_tpu.models.fm import FFMTrainer, FMTrainer
    from hivemall_tpu.models.linear import GeneralClassifier
    tmp = tmp_path_factory.mktemp("arena_families")
    ds, _ = synthetic_classification(256, 80, seed=5)
    dsf = _ffm_dataset()
    out = []
    for name, cls, opts, data, F in (
            ("linear", GeneralClassifier, OPTS, ds, None),
            ("fm_fused", FMTrainer,
             "-dims 4000 -factors 4 -classification -opt adagrad",
             ds, None),
            ("ffm_joint", FFMTrainer,
             "-dims 4096 -factors 2 -fields 8 -classification",
             dsf, 8),
            ("ffm_dense", FFMTrainer,
             "-dims 500 -factors 2 -fields 8 -classification "
             "-ffm_table dense", dsf, 8)):
        t = cls(opts)
        t.fit(data)
        p = os.path.join(str(tmp), f"{name}-{t.NAME}.npz")
        t.save_bundle(p)
        a = wa.open_arena(wa.publish_arena(p, t))
        dims = 500 if name == "ffm_dense" else 4000
        out.append((name, t, a, F, dims))
    return out


@pytest.fixture(scope="module")
def family_cases(tmp_path_factory):
    return _family_cases(tmp_path_factory)


def test_quant_error_within_documented_bound(family_cases):
    """int8/bf16 margins within score_error_bound of f32, and the f32
    arena tier numerically equal to the trainer's own margin — across
    every (B, L) serve bucket shape and every scorer family."""
    rng = np.random.default_rng(3)
    for name, t, a, F, dims in family_cases:
        margin_ref = t._make_margin_fn()
        # FFM's pairwise [B,L,L,K] reference cube is the expensive leg;
        # the L=64 column only needs one B to pin the wide bucket
        shapes = ([(1, 8), (8, 16), (64, 16), (8, 64)]
                  if name.startswith("ffm")
                  else [(B, L) for B in (1, 8, 64) for L in (8, 16, 64)])
        for B, L in shapes:
                b = _rand_batch(rng, B, L, dims=dims, F=F)
                ref = np.asarray(margin_ref(b), np.float32)
                for prec in ("f32", "bf16", "int8"):
                    m = a.margin_fn(prec)(b)
                    bound = wa.score_error_bound(a, prec, b) \
                        + 1e-4 + 1e-5 * np.abs(ref)
                    err = np.abs(m - ref)
                    assert (err <= bound).all(), \
                        (name, prec, B, L, float(err.max()),
                         float(bound.min()))
                    if prec == "f32":
                        assert np.allclose(m, ref, rtol=1e-5,
                                           atol=2e-5), (name, B, L)


def test_f32_bound_is_zero_quant_bounds_positive(linear_setup):
    rng = np.random.default_rng(4)
    b = _rand_batch(rng, 8, 16)
    a = linear_setup["arena"]
    assert not wa.score_error_bound(a, "f32", b).any()
    assert (wa.score_error_bound(a, "int8", b) > 0).all()
    assert (wa.score_error_bound(a, "bf16", b) >= 0).all()


def test_scorer_probability_space(linear_setup):
    """Classification arenas emit probabilities through the family's
    own sigmoid form — f32 tier matches make_scorer exactly-ish."""
    rng = np.random.default_rng(5)
    b = _rand_batch(rng, 8, 16)
    ref = np.asarray(linear_setup["trainer"].make_scorer()(b))
    got = linear_setup["arena"].scorer("f32")(b)
    assert got.dtype == np.float32
    assert ((got >= 0) & (got <= 1)).all()
    assert np.allclose(got, ref, atol=2e-6)


def test_oob_feature_id_clamps_like_xla(linear_setup):
    """A raw integer feature id past dims must degrade like the jitted
    gather (clamp), never crash the replica."""
    b = SparseBatch(np.array([[999_999_999, 3]], np.int32),
                    np.ones((1, 2), np.float32), np.zeros(1, np.float32))
    for prec in ("f32", "bf16", "int8"):
        assert np.isfinite(linear_setup["arena"].margin_fn(prec)(b)).all()


# --- parse-only facade -------------------------------------------------------

def test_make_parser_hashes_identically():
    from hivemall_tpu.models.fm import FFMTrainer
    from hivemall_tpu.models.linear import GeneralClassifier
    full = GeneralClassifier(OPTS)
    parser = GeneralClassifier.make_parser(OPTS)
    row = ["cat:1.5", "7:2.0", "other:1"]
    for a, b in zip(full._parse_row(row), parser._parse_row(row)):
        assert np.array_equal(a, b)
    assert not hasattr(parser, "w"), "parser must not allocate tables"
    fopts = "-dims 4096 -factors 2 -fields 8"
    ffull = FFMTrainer(fopts)
    fparser = FFMTrainer.make_parser(fopts)
    frow = ["3:12:1.5", "f7:abc:2.0"]
    for a, b in zip(ffull._parse_row(frow), fparser._parse_row(frow)):
        assert np.array_equal(a, b)
    assert not hasattr(fparser, "params")


# --- engine integration ------------------------------------------------------

def _rows(ds, n=8):
    out = []
    for i in range(n):
        idx, val = ds.row(i)
        out.append([f"{int(a)}:{float(v)!r}" for a, v in zip(idx, val)])
    return out


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """One fitted trainer shared by the engine tests (each test saves
    its own bundle copy into its own tmp dir — fitting dominates)."""
    from hivemall_tpu.models.linear import GeneralClassifier
    ds, _ = synthetic_classification(128, 60, seed=8)
    t = GeneralClassifier(OPTS)
    t.fit(ds)
    return t, ds


def test_engine_default_bitmatches_prearena_path(tmp_path, fitted):
    """Quantization OFF == today's path: bit-identical scores to
    predict_proba, no arena file created, no arena mapped."""
    from hivemall_tpu.serve.engine import PredictEngine
    t, ds = fitted
    p = os.path.join(str(tmp_path), f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(p)
    e = PredictEngine("train_classifier", OPTS, bundle=p,
                      max_batch=16, warmup_len=ds.max_row_len)
    try:
        got = e.predict_rows([e.parse(r) for r in _rows(ds)])
        ref = np.asarray(t.predict_proba(ds)[:8], np.float32)
        assert np.array_equal(got, ref)
        assert not os.path.exists(wa.arena_path(p))
        sec = e.obs_section()
        assert sec["arena"] == {"active": False, "mode": "auto",
                                "mapped_bytes": 0, "loads": 0,
                                "publishes": 0, "fallbacks": 0}
        assert sec["precision"] == "f32"
        assert sec["host_rss_bytes"] is None or sec["host_rss_bytes"] > 0
    finally:
        e.close()


def test_engine_quantized_serves_from_arena(tmp_path, fitted):
    from hivemall_tpu.serve.engine import PredictEngine
    t, ds = fitted
    p = os.path.join(str(tmp_path), f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(p)
    e = PredictEngine("train_classifier", OPTS, bundle=p,
                      precision="int8", max_batch=16,
                      warmup_len=ds.max_row_len)
    try:
        # no sidecar existed: the engine published one, then mapped it
        assert e.arena_publishes == 1 and e.arena_loads == 1
        assert os.path.exists(wa.arena_path(p))
        assert e.arena_mapped_bytes > 0
        got = e.predict_rows([e.parse(r) for r in _rows(ds)])
        ref = np.asarray(t.predict_proba(ds)[:8], np.float64)
        assert np.abs(got - ref).max() < 0.05
        # the serving trainer is the parse-only facade, not a full model
        assert not hasattr(e._model.trainer, "w")
        assert e._model.arena is not None
    finally:
        e.close()
    # close released the mapping and the obs surface stays sane
    assert e._model is None
    assert e.obs_section()["arena"]["active"] is False


def test_engine_second_replica_maps_without_publishing(tmp_path, fitted):
    from hivemall_tpu.serve.engine import PredictEngine
    t, ds = fitted
    p = os.path.join(str(tmp_path), f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(p)
    wa.publish_arena(p, t)
    e = PredictEngine("train_classifier", OPTS, bundle=p,
                      precision="bf16", max_batch=16,
                      warmup_len=ds.max_row_len)
    try:
        assert e.arena_publishes == 0 and e.arena_loads == 1
    finally:
        e.close()


def test_engine_partial_precision_arena_republished(tmp_path, fitted):
    """A digest-valid sidecar MISSING the requested tier must read as a
    miss (republish with every tier), not wedge reloads on KeyError."""
    from hivemall_tpu.serve.engine import PredictEngine
    t, ds = fitted
    p = os.path.join(str(tmp_path), f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(p)
    wa.publish_arena(p, t, precisions=("f32", "bf16"))
    e = PredictEngine("train_classifier", OPTS, bundle=p,
                      precision="int8", max_batch=16,
                      warmup_len=ds.max_row_len)
    try:
        assert e.arena_publishes == 1      # republished with all tiers
        assert "int8" in e._model.arena.precisions
        assert np.isfinite(
            e.predict_rows([e.parse(r) for r in _rows(ds, 2)])).all()
    finally:
        e.close()


def test_engine_force_f32_degrades_on_publish_failure(tmp_path, fitted,
                                                      monkeypatch):
    """--serve-arena force against a read-only model dir (no sidecar):
    the replica holds a servable trainer — it must degrade to the
    bundle path, never die on the publish error. (Simulated by patching
    publish_arena: chmod can't make a dir read-only for root.)"""
    import hivemall_tpu.io.weight_arena as wam
    from hivemall_tpu.serve.engine import PredictEngine
    t, ds = fitted
    p = os.path.join(str(tmp_path), f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(p)

    def boom(*a, **kw):
        raise OSError("read-only file system (simulated)")

    monkeypatch.setattr(wam, "publish_arena", boom)
    e = PredictEngine("train_classifier", OPTS, bundle=p,
                      arena="force", max_batch=16,
                      warmup_len=ds.max_row_len)
    try:
        assert e.arena_fallbacks == 1 and e.arena_loads == 0
        assert "publish" in (e.last_reload_error or "")
        got = e.predict_rows([e.parse(r) for r in _rows(ds)])
        assert np.array_equal(
            got, np.asarray(t.predict_proba(ds)[:8], np.float32))
    finally:
        e.close()
    # quantized precision has no bundle fallback: it must raise
    with pytest.raises(OSError):
        PredictEngine("train_classifier", OPTS, bundle=p,
                      precision="int8", max_batch=16,
                      warmup_len=ds.max_row_len)


def test_engine_hot_reload_through_arena(tmp_path):
    from hivemall_tpu.models.linear import GeneralClassifier
    from hivemall_tpu.serve.engine import PredictEngine
    ds, _ = synthetic_classification(128, 60, seed=8)
    t = GeneralClassifier(OPTS)
    t.fit(ds)
    p1 = os.path.join(str(tmp_path), f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(p1)
    e = PredictEngine("train_classifier", OPTS,
                      checkpoint_dir=str(tmp_path), precision="int8",
                      max_batch=16, warmup_len=ds.max_row_len)
    try:
        step1 = e.model_step
        t.fit(ds)
        p2 = os.path.join(str(tmp_path), f"{t.NAME}-step{t._t:010d}.npz")
        t.save_bundle(p2)
        wa.publish_arena(p2, t)
        assert e.poll() is True
        assert e.model_step == t._t != step1
        assert e.arena_loads == 2 and e.arena_publishes == 1
        ref = np.asarray(t.predict_proba(ds)[:8], np.float64)
        got = e.predict_rows([e.parse(r) for r in _rows(ds)])
        assert np.abs(got - ref).max() < 0.05
    finally:
        e.close()


def test_engine_option_validation():
    from hivemall_tpu.serve.engine import PredictEngine
    with pytest.raises(ValueError, match="precision"):
        PredictEngine("train_classifier", OPTS, bundle="x.npz",
                      precision="fp4")
    with pytest.raises(ValueError, match="arena"):
        PredictEngine("train_classifier", OPTS, bundle="x.npz",
                      arena="maybe")
    with pytest.raises(ValueError, match="needs the weight"):
        PredictEngine("train_classifier", OPTS, bundle="x.npz",
                      precision="int8", arena="off")


# --- promotion gate: the quantized-candidate guardrail -----------------------

def _outlier_candidate(tmp, ds, bump=10):
    """A candidate whose f32 scores are FINE but whose symmetric int8
    quantization collapses: one giant weight on an index the holdout
    never uses makes the per-table scale so coarse that every real
    weight rounds to zero."""
    import jax.numpy as jnp
    from hivemall_tpu.models.linear import GeneralClassifier
    t = GeneralClassifier(OPTS)
    t.fit(ds)
    w = np.array(t.w, np.float32)        # writable copy
    w[4095] = 1e6                        # holdout ids stay < 4000
    t.w = jnp.asarray(w)
    t._t += bump
    path = _save(tmp, t)
    return t, path


@pytest.fixture()
def gated_dir(tmp_path):
    from hivemall_tpu.models.linear import GeneralClassifier
    ds, _ = synthetic_classification(256, 80, seed=12,)
    t = GeneralClassifier(OPTS)
    t.fit(ds)
    base = _save(tmp_path, t)
    return tmp_path, ds, t, base


def test_gate_scores_quantized_and_publishes(gated_dir):
    from hivemall_tpu.serve.promote import PromotionController, PromotionGate
    tmp, ds, t, base = gated_dir
    gate = PromotionGate("train_classifier", OPTS, holdout=ds,
                         precision="int8")
    report = PromotionController(str(tmp), gate).check_once()
    assert report["verdict"] == "pass", report
    assert report["checks"]["precision"] == "int8"
    assert gate.arena_published >= 1
    assert os.path.exists(wa.arena_path(base))
    assert "arena_published" in gate.counters()


def test_gate_quantized_fails_unsupported_family_without_holdout(tmp_path):
    """A quantized gate with NO validation data at all must still fail
    a candidate whose family has no arena mapping — passing it would
    wedge every quantized replica on reload (review-caught edge). The
    AROW family keeps a covariance beside its weights and has no
    serving_tables() surface."""
    from hivemall_tpu.models.classifier import AROWTrainer
    from hivemall_tpu.serve.promote import PromotionGate
    t = AROWTrainer("-dims 4096")
    assert not hasattr(t, "serving_tables")
    t._t = 1
    p = _save(tmp_path, t)
    report = PromotionGate("train_arow", "-dims 4096",
                           precision="int8").evaluate(p)
    assert report["verdict"] == "fail"
    assert any("unusable" in r and "ArenaUnsupported" in r
               for r in report["reasons"]), report


def test_gate_rejects_over_error_quantized_candidate(gated_dir):
    """The same candidate passes at f32 and FAILS at int8 — proof the
    gate catches quantization error specifically — and the controller
    quarantines it (.rejected marker)."""
    from hivemall_tpu.io.checkpoint import is_rejected, rejected_reason
    from hivemall_tpu.serve.promote import PromotionController, PromotionGate
    tmp, ds, t, base = gated_dir
    # bootstrap-promote the good baseline at int8
    g0 = PromotionGate("train_classifier", OPTS, holdout=ds,
                       precision="int8")
    assert PromotionController(str(tmp), g0).check_once()["verdict"] \
        == "pass"
    _, bad = _outlier_candidate(tmp, ds)
    f32_report = PromotionGate(
        "train_classifier", OPTS, holdout=ds,
        precision="f32").evaluate(bad, base)
    assert f32_report["verdict"] == "pass", f32_report
    gate = PromotionGate("train_classifier", OPTS, holdout=ds,
                         precision="int8")
    report = PromotionController(str(tmp), gate).check_once()
    assert report is not None and report["verdict"] == "fail", report
    assert is_rejected(bad)
    assert rejected_reason(bad)


# --- router result cache -----------------------------------------------------

def test_result_cache_lru_and_invalidate():
    from hivemall_tpu.serve.router import ResultCache
    c = ResultCache(max_entries=2, max_bytes=1 << 20)
    assert c.get(b"a") is None           # miss
    c.put(b"a", b"HTTP/1.1 200 OK\r\n", b"pa")
    c.put(b"b", b"HTTP/1.1 200 OK\r\n", b"pb")
    hit = c.get(b"a")
    assert hit is not None and hit.endswith(b"pa")
    assert b"x-hivemall-cache: hit" in hit
    c.put(b"c", b"HTTP/1.1 200 OK\r\n", b"pc")   # evicts LRU (b)
    assert c.get(b"b") is None
    assert c.get(b"a") is not None and c.get(b"c") is not None
    st = c.stats()
    assert st["entries"] == 2 and st["hits"] == 3 and st["misses"] == 2
    c.invalidate()
    assert c.get(b"a") is None
    assert c.stats()["invalidations"] == 1 and c.stats()["version"] == 1
    c.bypass = True
    c.put(b"d", b"H", b"p")
    assert c.stats()["entries"] == 0     # bypass: nothing cached


def test_result_cache_version_guard_drops_stale_put():
    """A forward in flight across invalidate() carries the PRE-reload
    model's scores — put() must drop it (the review-caught race)."""
    from hivemall_tpu.serve.router import ResultCache
    c = ResultCache(max_entries=8)
    v = c.version                        # snapshot before "forwarding"
    c.invalidate()                       # model changed mid-flight
    c.put(b"a", b"HTTP/1.1 200 OK\r\n", b"stale", version=v)
    assert c.get(b"a") is None and c.stats()["entries"] == 0
    c.put(b"a", b"HTTP/1.1 200 OK\r\n", b"fresh", version=c.version)
    assert c.get(b"a") is not None


def test_result_cache_strips_per_request_headers():
    """A hit must not replay another request's trace id or the original
    forward's hop timing breakdown."""
    from hivemall_tpu.serve.router import ResultCache
    c = ResultCache(max_entries=8)
    head = (b"HTTP/1.1 200 OK\r\n"
            b"Content-Type: application/json\r\n"
            b"x-hivemall-trace: someone-elses-id\r\n"
            b"x-hivemall-hop: parse=1,total=2\r\n"
            b"x-hivemall-hop-router: relay=1,total=3\r\n")
    c.put(b"a", head, b"p")
    hit = c.get(b"a")
    assert b"x-hivemall-trace" not in hit
    assert b"x-hivemall-hop" not in hit
    assert b"Content-Type: application/json" in hit
    assert b"x-hivemall-cache: hit" in hit


def test_result_cache_byte_bound():
    from hivemall_tpu.serve.router import ResultCache
    c = ResultCache(max_entries=100, max_bytes=64)
    c.put(b"a", b"h" * 30, b"p" * 30)
    c.put(b"b", b"h" * 30, b"p" * 30)
    assert c.stats()["bytes"] <= 64 and c.stats()["entries"] == 1


@pytest.fixture()
def router_with_replica(tmp_path, fitted):
    """A real PredictServer registered directly as a router replica —
    the cache integration surface without spawning a fleet."""
    from hivemall_tpu.serve.engine import PredictEngine
    from hivemall_tpu.serve.http import PredictServer
    from hivemall_tpu.serve.router import RouterServer
    t, ds = fitted
    p = os.path.join(str(tmp_path), f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(p)
    engine = PredictEngine("train_classifier", OPTS, bundle=p,
                           max_batch=16, warmup_len=ds.max_row_len)
    srv = PredictServer(engine, watch=False, slo=False).start()
    router = RouterServer(result_cache_entries=64).start()
    router.add_replica("r0", "127.0.0.1", srv.port, ready=True)
    yield router, srv, ds
    router.stop()
    srv.stop()


def test_router_cache_end_to_end(router_with_replica):
    from hivemall_tpu.serve.http import KeepAliveClient
    router, srv, ds = router_with_replica
    cli = KeepAliveClient("127.0.0.1", router.port)
    try:
        body = {"rows": _rows(ds, 2)}
        code1, r1 = cli.post_json("/predict", body)
        assert code1 == 200
        assert "x-hivemall-cache" not in cli.last_headers
        code2, r2 = cli.post_json("/predict", body)
        assert code2 == 200 and r2["scores"] == r1["scores"]
        assert cli.last_headers.get("x-hivemall-cache") == "hit"
        st = router.result_cache.stats()
        assert st["hits"] == 1 and st["entries"] >= 1
        # a model change invalidates: the next identical body forwards
        router.invalidate_result_cache()
        code3, _ = cli.post_json("/predict", body)
        assert code3 == 200
        assert "x-hivemall-cache" not in cli.last_headers
        # router stats + fleet snapshot carry the cache counters and
        # the memory gauges
        assert router.stats()["result_cache"]["invalidations"] == 1
        snap = router.fleet_snapshot()["fleet"]
        agg = snap["aggregate"]
        assert agg["host_rss_bytes"] > 0
        assert "arena_mapped_bytes" in agg \
            and "arena_mapped_bytes_unique" in agg
        sec = snap["replicas"]["r0"]
        assert sec["host_rss_bytes"] > 0 and "arena" in sec
    finally:
        cli.close()


def test_fleet_aggregate_counts_a_shared_arena_once(tmp_path, fitted):
    """Two int8 replicas on one bundle map ONE published arena: the
    fleet aggregate sums what each reports and counts the arena of one
    model step once, with no request failed, shed or expired on the way.
    (In-process replicas share one obs registry, so the two reports are
    one server's; the two-process proof by inode is arena_smoke's.)"""
    from hivemall_tpu.serve.engine import PredictEngine
    from hivemall_tpu.serve.http import KeepAliveClient, PredictServer
    from hivemall_tpu.serve.router import RouterServer
    t, ds = fitted
    p = os.path.join(str(tmp_path), f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(p)
    srvs = [PredictServer(
        PredictEngine("train_classifier", OPTS, bundle=p, precision="int8",
                      max_batch=16, warmup_len=ds.max_row_len),
        watch=False, slo=False).start() for _ in range(2)]
    router = RouterServer().start()
    cli = KeepAliveClient("127.0.0.1", router.port)
    try:
        for i, srv in enumerate(srvs):
            router.add_replica(f"r{i}", "127.0.0.1", srv.port, ready=True)
        for _ in range(6):
            code, _r = cli.post_json("/predict", {"rows": _rows(ds, 2)})
            assert code == 200
        agg = router.fleet_snapshot()["fleet"]["aggregate"]
        one = srvs[0].engine.arena_mapped_bytes
        assert one > 0 and srvs[1].engine.arena_mapped_bytes == one
        assert agg["arena_mapped_bytes_unique"] == one
        assert agg["arena_mapped_bytes"] == 2 * one
        assert agg["requests"] > 0 and agg["errors"] == 0
        assert agg["shed"] == 0 and agg["expired"] == 0
        # the first replica published, the second only mapped
        assert sorted(s.engine.arena_publishes for s in srvs) == [0, 1]
    finally:
        cli.close()
        router.stop()
        for srv in srvs:
            srv.stop()


def test_router_cache_disabled_stub():
    from hivemall_tpu.serve.router import RouterServer, _CACHE_STUB
    r = RouterServer()
    try:
        st = r.stats()["result_cache"]
        assert st == _CACHE_STUB
        r.invalidate_result_cache()      # no-op, must not raise
        r.set_result_cache_bypass(True)
    finally:
        r.stop()


# --- retention ---------------------------------------------------------------

def test_prune_removes_arena_sidecar_keeps_pinned(tmp_path):
    from hivemall_tpu.io.checkpoint import (CheckpointManager,
                                            promote_bundle)
    from hivemall_tpu.models.linear import GeneralClassifier
    ds, _ = synthetic_classification(128, 60, seed=8)
    t = GeneralClassifier(OPTS)
    mgr = CheckpointManager(str(tmp_path), t.NAME, keep=2)
    paths = []
    for _ in range(4):
        t.fit(ds)
        paths.append(mgr.save(t))
        wa.publish_arena(paths[-1], t)
    # keep=2: the two oldest bundles were pruned WITH their arenas
    assert not os.path.exists(paths[0])
    assert not os.path.exists(wa.arena_path(paths[0]))
    assert os.path.exists(wa.arena_path(paths[-1]))
    # a pointer-pinned bundle keeps its arena through further churn
    promote_bundle(str(tmp_path), paths[2])
    for _ in range(3):
        t.fit(ds)
        p = mgr.save(t)
        wa.publish_arena(p, t)
    assert os.path.exists(paths[2])
    assert os.path.exists(wa.arena_path(paths[2]))


def test_host_rss_bytes_reads():
    rss = wa.host_rss_bytes()
    if os.path.exists("/proc/self/statm"):
        assert rss is not None and rss > (1 << 20)
