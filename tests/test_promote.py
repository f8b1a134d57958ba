"""Gated model promotion (docs/RELIABILITY.md "Promotion and rollback"):
the PROMOTED pointer protocol in io.checkpoint, the PromotionGate /
CanaryBake / PromotionController math in serve.promote, the engine's
pointer-follow mode + corrupt-bundle skip-cache regression fix, and the
fleet canary/rollback/recovery lifecycle — against real in-process
PredictServers as replicas (cheap: no worker processes; the full
multi-process canary under live traffic is pinned by the promotion smoke
in run_tests.sh, and the SIGKILL-the-manager scenario by the `slow` test
at the bottom)."""

import json
import os
import shutil
import urllib.request

import numpy as np
import pytest

from hivemall_tpu.io import checkpoint as ck

OPTS = "-dims 1024 -loss logloss -opt adagrad -mini_batch 32"


@pytest.fixture()
def trained(tmp_path):
    from hivemall_tpu.io.libsvm import synthetic_classification
    from hivemall_tpu.models.linear import GeneralClassifier
    ds, _ = synthetic_classification(200, 64, seed=11)
    t = GeneralClassifier(OPTS)
    t.fit(ds)
    path = os.path.join(tmp_path, f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(path)
    return t, ds, str(tmp_path), path


def _save_next(trainer, ckdir, ds=None, bump=0):
    """Save the trainer's state as the next candidate bundle (optionally
    after more training / a step bump)."""
    if ds is not None:
        trainer.fit(ds)
    if bump:
        trainer._t += bump
    path = os.path.join(ckdir, f"{trainer.NAME}-step{trainer._t:010d}.npz")
    trainer.save_bundle(path)
    return path


def _poisoned(ckdir, base_path, bump=5):
    """A deliberately-poisoned candidate: the promoted weights scaled and
    shifted (diverged-learning-rate shape) at a higher step."""
    import jax.numpy as jnp
    from hivemall_tpu.models.linear import GeneralClassifier
    bad = GeneralClassifier(OPTS)
    bad.load_bundle(base_path)
    bad.w = jnp.asarray(np.asarray(bad.w) * 25.0 + 3.0)
    bad._t += bump
    path = os.path.join(ckdir, f"{bad.NAME}-step{bad._t:010d}.npz")
    bad.save_bundle(path)
    return path


def _rows_of(ds, n):
    out = []
    for i in range(n):
        idx, val = ds.row(i)
        out.append([f"{int(a)}:{float(v)!r}" for a, v in zip(idx, val)])
    return out


# --- pointer protocol --------------------------------------------------------

def test_pointer_promote_finalize_rollback(trained):
    t, ds, ckdir, pA = trained
    stepA = t._t
    m = ck.promote_bundle(ckdir, pA)
    assert m["current"]["step"] == stepA and m["state"] == "serving"
    assert m["current"]["digest"] and m["current"]["trainer"] == t.NAME
    assert ck.promoted_bundle(ckdir, t.NAME) == (stepA, pA)
    pB = _save_next(t, ckdir, ds)
    m = ck.promote_bundle(ckdir, pB, state="canary",
                          gate={"verdict": "pass"})
    assert m["state"] == "canary"
    assert m["history"][0]["step"] == stepA     # rollback target
    assert ck.read_promoted(ckdir)["current"]["gate"]["verdict"] == "pass"
    assert ck.finalize_promotion(ckdir)["state"] == "serving"
    m = ck.rollback_promoted(ckdir, "injected burn")
    assert m["current"]["step"] == stepA and m["rollbacks"] == 1
    assert m["last_rollback"]["from"]["step"] == t._t
    assert m["last_rollback"]["reason"] == "injected burn"
    assert ck.promoted_bundle(ckdir, t.NAME) == (stepA, pA)
    # nothing older: a second rollback refuses
    assert ck.rollback_promoted(ckdir, "again") is None
    # wrong trainer name never resolves
    assert ck.promoted_bundle(ckdir, "train_ffm") is None


def test_reject_marker_roundtrip(trained):
    _, _, ckdir, pA = trained
    assert not ck.is_rejected(pA)
    marker = ck.reject_bundle(pA, "poisoned shard")
    assert os.path.exists(marker) and ck.is_rejected(pA)
    assert ck.rejected_reason(pA) == "poisoned shard"


def test_retention_never_deletes_promoted_or_rollback_target(tmp_path):
    """Satellite: keep=2 across 10 saves while the pointer pins save 3
    (and later a rollback target) — pinned bundles survive GC."""
    from hivemall_tpu.io.libsvm import synthetic_classification
    from hivemall_tpu.models.linear import GeneralClassifier
    ds, _ = synthetic_classification(64, 16, seed=3)
    t = GeneralClassifier("-dims 256 -loss logloss -mini_batch 16")
    mgr = ck.CheckpointManager(str(tmp_path), t.NAME, keep=2, every=1)
    saved = []
    for i in range(10):
        t.fit(ds)
        saved.append(mgr.save(t))
        if i == 2:                       # promote save 3
            ck.promote_bundle(str(tmp_path), saved[2])
        if i == 5:                       # save 6 promoted: save 3 becomes
            ck.promote_bundle(str(tmp_path), saved[5])   # rollback target
    live = set(ck.list_bundles(str(tmp_path), t.NAME))
    assert saved[2] in live, "rollback target was GC'd"
    assert saved[5] in live, "promoted bundle was GC'd"
    assert saved[8] in live and saved[9] in live      # the k=2 window
    for gone in (saved[0], saved[1], saved[3], saved[4], saved[6],
                 saved[7]):
        assert gone not in live
    # rollback: the pinned save 3 must still load bit-exact
    m = ck.rollback_promoted(str(tmp_path), "bad save 6")
    assert m["current"]["bundle"] == os.path.basename(saved[2])
    fresh = GeneralClassifier("-dims 256 -loss logloss -mini_batch 16")
    fresh.load_bundle(saved[2])          # digest-validated


def test_prune_removes_orphaned_reject_markers(tmp_path):
    from hivemall_tpu.io.libsvm import synthetic_classification
    from hivemall_tpu.models.linear import GeneralClassifier
    ds, _ = synthetic_classification(64, 16, seed=3)
    t = GeneralClassifier("-dims 256 -loss logloss -mini_batch 16")
    mgr = ck.CheckpointManager(str(tmp_path), t.NAME, keep=1, every=1)
    t.fit(ds)
    first = mgr.save(t)
    ck.reject_bundle(first, "bad")
    t.fit(ds)
    mgr.save(t)
    assert not os.path.exists(first)
    assert not os.path.exists(first + ".rejected")


# --- engine: follow the pointer, skip-cache regression -----------------------

def _engine(ckdir, **kw):
    from hivemall_tpu.serve.engine import PredictEngine
    kw.setdefault("warmup", False)
    return PredictEngine("train_classifier", OPTS, checkpoint_dir=ckdir,
                         **kw)


def test_engine_follows_pointer_not_newest(trained):
    from hivemall_tpu.io.sparse import SparseDataset
    t, ds, ckdir, pA = trained
    stepA = t._t
    refA = np.asarray(t.predict_proba(ds), np.float32)
    pB = _save_next(t, ckdir, ds)
    ck.promote_bundle(ckdir, pA)         # pointer at the OLDER bundle
    eng = _engine(ckdir, follow="promoted")
    assert eng.model_step == stepA, "promoted-follow served the newest"
    assert eng.poll() is False           # pointer unchanged: no churn
    ck.promote_bundle(ckdir, pB)
    assert eng.poll() is True and eng.model_step == t._t
    # rollback = the pointer moves BACKWARD; the engine must follow and
    # restore bit-identical scores to the pre-canary bundle
    ck.rollback_promoted(ckdir, "bake failed")
    assert eng.poll() is True and eng.model_step == stepA
    rows = _rows_of(ds, 9)
    got = eng.predict_rows([eng.parse(r) for r in rows])
    assert np.array_equal(got, refA[:9])


def test_engine_boots_stable_side_during_canary(trained):
    """While the pointer is in state "canary" its current entry is an
    UNBAKED candidate — an engine booting on its own (a respawned
    replica) must serve the prior stable entry; canary membership is an
    explicit manager /reload, never a side effect of churn."""
    t, ds, ckdir, pA = trained
    stepA = t._t
    ck.promote_bundle(ckdir, pA)
    pB = _save_next(t, ckdir, ds)
    ck.promote_bundle(ckdir, pB, state="canary")
    eng = _engine(ckdir, follow="promoted")
    assert eng.model_step == stepA       # the stable side, not the canary
    assert eng.poll() is False
    ck.finalize_promotion(ckdir)         # bake completed: candidate is
    assert eng.poll() is True            # now THE promoted model
    assert eng.model_step == t._t


def test_engine_promoted_bootstraps_from_newest_without_pointer(trained):
    t, _, ckdir, _ = trained
    eng = _engine(ckdir, follow="promoted")
    assert eng.model_step == t._t        # no pointer yet: newest usable
    with pytest.raises(ValueError, match="follow mode"):
        _engine(ckdir, follow="nonsense")


def test_engine_skip_cache_reexamines_rewritten_bundle(trained):
    """Regression (ISSUE 10 satellite): the corrupt-bundle skip memo was
    keyed by mtime alone, so a bundle rewritten IN PLACE with a
    preserved mtime was never re-examined. Now keyed by (mtime, size)
    with a head/tail digest fallback on full collision."""
    t, ds, ckdir, pA = trained
    eng = _engine(ckdir)
    bad = os.path.join(ckdir, f"{t.NAME}-step{t._t + 99:010d}.npz")
    with open(bad, "wb") as f:
        f.write(b"not a bundle" * 64)
    st = os.stat(bad)
    assert eng.poll() is False and eng.reload_failures == 1
    assert eng.poll() is False and eng.reload_failures == 1   # memo holds
    # rewrite in place with VALID content, mtime preserved (size differs):
    # the old mtime-only memo would skip this forever
    shutil.copy(pA, bad)
    os.utime(bad, (st.st_atime, st.st_mtime))
    assert eng.poll() is True, "rewritten-in-place bundle never re-read"
    assert eng.reloads == 1              # (its META step is A's: 7)
    # (mtime, size) full collision: different bytes, same size AND mtime
    # — the content-tag fallback must still re-examine
    bad2 = os.path.join(ckdir, f"{t.NAME}-step{t._t + 200:010d}.npz")
    with open(bad2, "wb") as f:
        f.write(b"A" * 5000)
    st2 = os.stat(bad2)
    eng.poll()
    n = eng.reload_failures
    with open(bad2, "wb") as f:
        f.write(b"B" * 5000)
    os.utime(bad2, (st2.st_atime, st2.st_mtime))
    eng.poll()
    assert eng.reload_failures == n + 1, "collided rewrite not re-examined"
    eng.poll()
    assert eng.reload_failures == n + 1   # unchanged content: memo holds


def test_engine_skips_quarantined_bundles(trained):
    t, ds, ckdir, pA = trained
    stepA = t._t
    pB = _save_next(t, ckdir, ds)
    ck.reject_bundle(pB, "failed the gate")
    eng = _engine(ckdir)                 # newest-wins mode
    assert eng.model_step == stepA, "quarantined bundle was served"
    assert eng.poll() is False and eng.reload_failures == 0


# --- the gate ----------------------------------------------------------------

def test_gate_blocks_injected_logloss_regression(trained):
    from hivemall_tpu.serve.promote import PromotionGate
    t, ds, ckdir, pA = trained
    gate = PromotionGate("train_classifier", OPTS, holdout=ds)
    pBad = _poisoned(ckdir, pA)
    report = gate.evaluate(pBad, pA)
    assert report["verdict"] == "fail"
    assert any("logloss regressed" in r for r in report["reasons"])
    assert report["checks"]["logloss"] > report["checks"][
        "baseline_logloss"] + 0.05
    # a genuinely-better candidate passes the same gate
    pGood = _save_next(t, ckdir, ds)
    report = gate.evaluate(pGood, pA)
    assert report["verdict"] == "pass" and not report["reasons"]
    assert gate.counters() == {"candidates": 2, "gate_passes": 1,
                               "gate_failures": 1, "arena_published": 1,
                               "last_verdict": "pass"}


def test_gate_corrupt_candidate_fails(trained):
    from hivemall_tpu.serve.promote import PromotionGate
    t, ds, ckdir, pA = trained
    gate = PromotionGate("train_classifier", OPTS, holdout=ds)
    bad = os.path.join(ckdir, f"{t.NAME}-step{t._t + 9:010d}.npz")
    with open(bad, "wb") as f:
        f.write(b"torn mid-write")
    report = gate.evaluate(bad, pA)
    assert report["verdict"] == "fail"
    assert any("unusable" in r for r in report["reasons"])


def test_gate_calibration_drift_flagged_by_driftwatch(trained):
    """Satellite: calibration drift is flagged by the shared DriftWatch
    changefinder — a gap individually under the absolute bound still
    fails when it breaks the history of admitted candidates. Every
    OTHER guardrail is disabled here so the changefinder is the only
    judge (it only sees candidates that pass the explicit checks)."""
    from hivemall_tpu.serve.promote import PromotionGate
    t, ds, ckdir, pA = trained
    gate = PromotionGate("train_classifier", OPTS, holdout=ds,
                         max_logloss_increase=None,
                         max_auc_decrease=None,
                         max_score_shift=None,
                         max_calibration_gap=None,   # absolute check off:
                         drift_warmup=4, drift_sigma=1.0)   # drift only
    rng = np.random.default_rng(5)
    for _ in range(24):                  # history of well-calibrated
        ev = gate._calibration_drift(0.02 + rng.uniform(-0.005, 0.005))
        assert ev is None
    pBad = _poisoned(ckdir, pA)          # saturated probs: gap ~0.5
    report = gate.evaluate(pBad, pA)
    assert report["verdict"] == "fail"
    assert any("calibration drift" in r for r in report["reasons"]), \
        report["reasons"]
    assert report["checks"].get("calibration_drift") is not None


def test_gate_drift_baseline_sees_only_admitted_candidates(trained):
    """A candidate rejected on OTHER guardrails must not feed (and so
    pollute) the calibration changefinder's admitted-history baseline."""
    from hivemall_tpu.serve.promote import PromotionGate
    t, ds, ckdir, pA = trained
    gate = PromotionGate("train_classifier", OPTS, holdout=ds)
    pBad = _poisoned(ckdir, pA)          # fails logloss/AUC/shift
    gate.evaluate(pBad, pA)
    assert gate.calibration_watch.n == 0
    gate.evaluate(pA, pA)                # passes: gap joins the history
    assert gate.calibration_watch.n == 1


def test_gate_nonfinite_baseline_degrades_to_absolute_checks(trained):
    """A NaN-scoring BASELINE must not vacuously pass candidates (NaN
    comparisons are all False) — the gate degrades to absolute-only
    checks and records it."""
    import jax.numpy as jnp
    from hivemall_tpu.models.linear import GeneralClassifier
    from hivemall_tpu.serve.promote import PromotionGate
    t, ds, ckdir, pA = trained
    nan = GeneralClassifier(OPTS)
    nan.load_bundle(pA)
    nan.w = jnp.asarray(np.full_like(np.asarray(nan.w), np.nan))
    pNan = os.path.join(ckdir, f"{nan.NAME}-step{nan._t + 1:010d}.npz")
    nan.save_bundle(pNan)
    gate = PromotionGate("train_classifier", OPTS, holdout=ds)
    # a POISONED candidate against the NaN baseline: the absolute
    # calibration check must still catch it
    pBad = _poisoned(ckdir, pA, bump=7)
    report = gate.evaluate(pBad, pNan)
    assert report["checks"].get("baseline_nonfinite") is True
    assert report["verdict"] == "fail", report
    # and a NaN CANDIDATE fails outright
    report = gate.evaluate(pNan, pA)
    assert report["verdict"] == "fail"
    assert any("not finite" in r for r in report["reasons"])


def test_gate_shadow_scores_mirrored_traffic(trained):
    """The batcher tee mirrors live rows into the ShadowBuffer off the
    request path; the gate compares candidate vs baseline score
    distributions on them."""
    from hivemall_tpu.serve.batcher import MicroBatcher
    from hivemall_tpu.serve.promote import PromotionGate, ShadowBuffer
    t, ds, ckdir, pA = trained
    shadow = ShadowBuffer(capacity=64)
    mb = MicroBatcher(lambda rows: np.zeros(len(rows), np.float32),
                      max_delay_ms=0.5)
    mb.set_tee(shadow.add)
    parsed = [t._parse_row(r) for r in _rows_of(ds, 40)]
    futs = [mb.submit([p]) for p in parsed]
    for f in futs:
        f.result(timeout=5)
    mb.close()
    assert shadow.mirrored == 40 and len(shadow.rows()) == 40
    gate = PromotionGate("train_classifier", OPTS, shadow=shadow,
                         min_shadow_rows=16)
    pBad = _poisoned(ckdir, pA)
    report = gate.evaluate(pBad, pA)
    assert report["verdict"] == "fail"
    assert any("shadow score distribution shifted" in r
               for r in report["reasons"]), report["reasons"]
    assert report["checks"]["shadow_rows"] == 40
    # the good twin of the same bundle: no shift on the same traffic
    report = gate.evaluate(pA, pA)
    assert report["verdict"] == "pass"
    # a buffer past capacity drops (counted), never grows
    shadow.add(parsed * 2)
    assert len(shadow.rows()) == 64 and shadow.dropped > 0


# --- canary bake math --------------------------------------------------------

def _totals(req, bad=0, lat_s=0.0, lat_n=0, score=(0.0, 0.0, 0)):
    return {"requests": req, "errors": bad,
            "latency": {"sum": lat_s, "count": lat_n},
            "score_sum": score[0], "score_sumsq": score[1],
            "score_n": score[2]}


def test_canary_bake_pass_and_failures():
    from hivemall_tpu.serve.promote import CanaryBake
    kw = dict(bake_seconds=5.0, min_requests=10,
              max_bad_frac_increase=0.05, max_latency_factor=2.0,
              latency_floor_ms=10.0)
    b = CanaryBake(**kw)
    b.start(_totals(100, 0, 1.0, 100), _totals(300, 0, 3.0, 300), now=0.0)
    # under min_requests: no verdict either way
    assert b.update(_totals(105, 0, 1.05, 105),
                    _totals(330, 0, 3.3, 330), now=1.0) is None
    # healthy canary, window elapsed: pass
    assert b.update(_totals(160, 0, 1.6, 160),
                    _totals(500, 0, 5.0, 500), now=6.0) == "pass"
    # latency regression: fail with the reason
    b = CanaryBake(**kw)
    b.start(_totals(100, 0, 1.0, 100), _totals(300, 0, 3.0, 300), now=0.0)
    v = b.update(_totals(160, 0, 16.0, 160),
                 _totals(500, 0, 5.0, 500), now=1.0)
    assert v.startswith("fail:") and "latency" in v
    # error-rate regression
    b = CanaryBake(**kw)
    b.start(_totals(100), _totals(300), now=0.0)
    v = b.update(_totals(160, 30), _totals(500, 0), now=1.0)
    assert v.startswith("fail:") and "bad-fraction" in v
    # score-mean shift vs the stable cohort
    b = CanaryBake(**kw, max_score_shift=3.0, score_shift_floor=0.05)
    b.start(_totals(100, score=(50.0, 25.5, 100)),
            _totals(300, score=(150.0, 76.0, 300)), now=0.0)
    v = b.update(_totals(200, score=(140.0, 106.0, 200)),
                 _totals(600, score=(300.0, 152.0, 600)), now=1.0)
    assert v.startswith("fail:") and "score mean" in v
    # an idle canary (never reaches min_requests) passes at max_bake
    b = CanaryBake(**kw, max_bake_seconds=30.0)
    b.start(_totals(0), _totals(0), now=0.0)
    assert b.update(_totals(2), _totals(5), now=10.0) is None
    assert b.update(_totals(2), _totals(5), now=31.0) == "pass"
    # a cohort counter RESET (replica respawn mid-bake — possibly killed
    # by the candidate) voids the window: the bake restarts instead of
    # clamping to an "idle" no-evidence pass at max_bake
    b = CanaryBake(**kw, max_bake_seconds=30.0)
    b.start(_totals(500, 0, 5.0, 500), _totals(900, 0, 9.0, 900), now=0.0)
    assert b.update(_totals(30, 0, 0.3, 30),         # canary respawned
                    _totals(950, 0, 9.5, 950), now=31.0) is None
    assert b.resets == 1
    assert b.started_at == 31.0                      # window re-opened
    # the restarted window judges honestly from the new base
    assert b.update(_totals(90, 0, 0.9, 90),
                    _totals(1100, 0, 11.0, 1100), now=37.0) == "pass"


# --- controller --------------------------------------------------------------

def test_controller_gates_quarantines_and_promotes(trained):
    from hivemall_tpu.serve.promote import (PromotionController,
                                            PromotionGate, promotion_stub)
    t, ds, ckdir, pA = trained
    gate = PromotionGate("train_classifier", OPTS, holdout=ds)
    ctrl = PromotionController(ckdir, gate)
    # bootstrap: first candidate promotes on absolute checks
    r = ctrl.check_once()
    assert r["promoted"] is True
    assert ck.promoted_bundle(ckdir, t.NAME) == (t._t, pA)
    assert ctrl.check_once() is None     # nothing new
    pBad = _poisoned(ckdir, pA)
    r = ctrl.check_once()
    assert r["promoted"] is False and ck.is_rejected(pBad)
    assert ck.promoted_bundle(ckdir, t.NAME)[1] == pA   # still serving A
    assert ctrl.check_once() is None     # quarantined: never retried
    pGood = _save_next(t, ckdir, ds, bump=10)   # step past the reject
    r = ctrl.check_once()
    assert r["promoted"] is True
    assert ck.promoted_bundle(ckdir, t.NAME)[1] == pGood
    sec = ctrl.obs_section()
    assert sec["configured"] and sec["promotions"] == 2
    assert sec["quarantined"] == 1 and sec["gate_failures"] == 1
    assert set(sec) == set(promotion_stub())


def test_http_promotion_endpoint(trained):
    from hivemall_tpu.serve.http import PredictServer
    t, ds, ckdir, pA = trained
    ck.promote_bundle(ckdir, pA, gate={"verdict": "pass"})
    srv = PredictServer(_engine(ckdir, follow="promoted"), port=0,
                        watch=False, slo=False).start()
    try:
        out = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/promotion", timeout=10).read())
        assert out["configured"] is True
        assert out["follow"] == "promoted"
        assert out["promoted_step"] == t._t
        assert out["manifest"]["current"]["gate"]["verdict"] == "pass"
    finally:
        srv.stop()


# --- fleet canary lifecycle (in-process replicas) ----------------------------

class _FakeProc:
    def poll(self):
        return None

    def terminate(self):
        pass

    def kill(self):
        pass

    def wait(self, timeout=None):
        return 0


def _replica_server(ckdir):
    from hivemall_tpu.serve.engine import PredictEngine
    from hivemall_tpu.serve.http import PredictServer
    eng = PredictEngine("train_classifier", OPTS, checkpoint_dir=ckdir,
                        warmup=False, follow="promoted")
    return PredictServer(eng, port=0, max_delay_ms=1.0, watch=False,
                         slo=False).start()


def _manager(ckdir, servers, **kw):
    """A promote-mode ReplicaManager over in-process replica servers
    (no worker spawn — check_and_roll is driven by hand)."""
    from hivemall_tpu.serve.fleet import ReplicaManager, _Replica
    kw.setdefault("bake_opts", {"bake_seconds": 0.0, "min_requests": 0,
                                "max_bake_seconds": 0.0})
    mgr = ReplicaManager("train_classifier", OPTS, checkpoint_dir=ckdir,
                         replicas=len(servers), promote=True, **kw)
    for i, srv in enumerate(servers):
        r = _Replica(f"t{i}", _FakeProc(), i)
        r.port = srv.port
        r.model_step = srv.engine.model_step
        mgr._replicas[r.rid] = r
    return mgr


@pytest.fixture()
def fleet2(trained):
    t, ds, ckdir, pA = trained
    ck.promote_bundle(ckdir, pA)
    servers = [_replica_server(ckdir) for _ in range(2)]
    yield t, ds, ckdir, pA, servers
    for srv in servers:
        srv.stop()


def test_fleet_gate_canary_promote_and_injected_rollback(fleet2):
    from hivemall_tpu.serve.promote import PromotionGate
    from hivemall_tpu.testing.faults import inject_canary_regression
    t, ds, ckdir, pA, servers = fleet2
    stepA = t._t
    gate = PromotionGate("train_classifier", OPTS, holdout=ds)
    mgr = _manager(ckdir, servers, gate=gate, canary_fraction=0.5)
    assert mgr.check_and_roll() is False          # nothing new
    # poisoned candidate: blocked at the gate, fleet untouched
    pBad = _poisoned(ckdir, pA)
    assert mgr.check_and_roll() is False
    assert ck.is_rejected(pBad) and mgr.quarantined == 1
    assert all(r.model_step == stepA for r in mgr.replicas())
    # good candidate: pass -> one-replica canary -> clean bake -> roll
    pC = _save_next(t, ckdir, ds, bump=10)
    stepC = t._t
    assert mgr.check_and_roll() is False          # canary started
    assert ck.read_promoted(ckdir)["state"] == "canary"
    assert sorted(r.model_step for r in mgr.replicas()) == [stepA, stepC]
    assert mgr.check_and_roll() is True           # bake pass: completed
    assert ck.read_promoted(ckdir)["state"] == "serving"
    assert all(r.model_step == stepC for r in mgr.replicas())
    assert mgr.promotions == 1 and mgr.fleet_step == stepC
    # next candidate: injected latency regression -> auto-rollback
    pD = _save_next(t, ckdir, ds, bump=10)
    mgr.bake_opts = {"bake_seconds": 60.0, "min_requests": 1,
                     "max_bake_seconds": 600.0}
    assert mgr.check_and_roll() is False          # canary for D started
    rows = _rows_of(ds, 20)
    for srv in servers:                           # traffic on both cohorts
        for r_ in rows:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/predict",
                json.dumps({"rows": [r_]}).encode(),
                {"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=10).read()
    inject_canary_regression(mgr, latency_ms=500.0)
    assert mgr.check_and_roll() is False          # bake fail: rolled back
    m = ck.read_promoted(ckdir)
    assert m["current"]["step"] == stepC and m["state"] == "serving"
    assert m["rollbacks"] == 1 and ck.is_rejected(pD)
    assert all(r.model_step == stepC for r in mgr.replicas())
    assert mgr.canary_rollbacks == 1
    # rollback restored bit-identical scores to the pre-canary bundle
    from hivemall_tpu.models.linear import GeneralClassifier
    tc = GeneralClassifier(OPTS)
    tc.load_bundle(pC)
    refC = np.asarray(tc.predict_proba(ds), np.float32)
    eng = servers[0].engine
    got = eng.predict_rows([eng.parse(r_) for r_ in rows[:9]])
    assert np.array_equal(got, refC[:9])
    sec = mgr.promotion_section()
    assert sec["rollbacks"] == 1 and sec["gate_failures"] == 1


def test_fleet_recovers_mid_canary_from_manifest(fleet2):
    """Satellite: a manager killed mid-canary leaves pointer state
    "canary" on disk; a FRESH manager must re-bake and converge — no
    half-rolled fleet, steps converge."""
    t, ds, ckdir, pA, servers = fleet2
    pB = _save_next(t, ckdir, ds)
    stepB = t._t
    ck.promote_bundle(ckdir, pB, state="canary")
    # half-rolled: one replica already on the candidate (as a dying
    # manager would leave it), one still on the old model
    servers[0].engine.reload(pB)
    mgr = _manager(ckdir, servers)
    for r, srv in zip(mgr.replicas(), servers):
        r.model_step = srv.engine.model_step
    assert mgr.check_and_roll() is False          # canary re-baked
    assert mgr._canary is not None and mgr._canary["step"] == stepB
    assert mgr.check_and_roll() is True           # bake(0s) completes
    assert all(r.model_step == stepB for r in mgr.replicas())
    assert ck.read_promoted(ckdir)["state"] == "serving"


def test_fleet_recovers_mid_rollback_from_manifest(fleet2):
    """Satellite: a rollback killed between the quarantine marker and
    the pointer flip recovers as a completed rollback — the quarantined
    bundle never serves again."""
    t, ds, ckdir, pA, servers = fleet2
    stepA = t._t
    pB = _save_next(t, ckdir, ds)
    ck.promote_bundle(ckdir, pB, state="canary")
    servers[1].engine.reload(pB)                  # canary replica on B
    ck.reject_bundle(pB, "injected burn")         # crash right after this
    mgr = _manager(ckdir, servers)
    for r, srv in zip(mgr.replicas(), servers):
        r.model_step = srv.engine.model_step
    assert mgr.check_and_roll() is True           # rollback completed
    m = ck.read_promoted(ckdir)
    assert m["current"]["step"] == stepA and m["state"] == "serving"
    assert m["rollbacks"] == 1
    assert all(r.model_step == stepA for r in mgr.replicas())
    assert mgr.check_and_roll() is False          # B quarantined: no retry


def test_fleet_promoted_reload_rejects_explicit_path(trained):
    """A promotion-gated fleet's /reload must not bypass the gate."""
    from hivemall_tpu.serve.fleet import Fleet
    t, ds, ckdir, pA = trained
    ck.promote_bundle(ckdir, pA)
    fleet = Fleet.__new__(Fleet)          # wiring only — no spawn
    fleet.manager = _manager(ckdir, [])
    out = fleet._on_reload(json.dumps({"path": pA}).encode())
    assert "promotion-gated" in out["error"]


# --- real processes: SIGKILL the manager (slow; smoke covers the rest) -------

@pytest.mark.slow
def test_sigkill_fleet_manager_mid_canary_recovers(trained):
    """SIGKILL the whole fleet process mid-canary; a fresh Fleet on the
    same checkpoint dir must recover a consistent state from the
    PROMOTED manifest: canary re-baked, steps converge, state serving."""
    import signal
    import subprocess
    import sys
    import time
    t, ds, ckdir, pA = trained
    ck.promote_bundle(ckdir, pA)
    pB = _save_next(t, ds=ds, ckdir=ckdir)
    stepB = t._t
    ck.promote_bundle(ckdir, pB, state="canary")   # mid-canary on disk
    driver = (
        "import json,sys,time\n"
        "from hivemall_tpu.serve.fleet import Fleet\n"
        f"f = Fleet('train_classifier', {OPTS!r}, checkpoint_dir="
        f"{ckdir!r}, replicas=2, promote=True, watch_interval=0.5,\n"
        "          bake_opts={'bake_seconds': 3600.0, 'min_requests': 1})\n"
        "f.start(wait_ready=True)\n"
        "print(json.dumps({'pids': [r.proc.pid for r in"
        " f.manager.replicas()]}), flush=True)\n"
        "time.sleep(3600)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", driver],
                            stdout=subprocess.PIPE, text=True, env=env,
                            cwd=os.path.dirname(os.path.dirname(
                                os.path.abspath(__file__))))
    try:
        line = proc.stdout.readline()
        pids = json.loads(line)["pids"]
        os.kill(proc.pid, signal.SIGKILL)      # the manager dies hard
        proc.wait(timeout=10)
        for pid in pids:                        # host death takes the
            try:                                # orphaned workers too
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
    finally:
        if proc.poll() is None:
            proc.kill()
    assert ck.read_promoted(ckdir)["state"] == "canary"   # crash window
    from hivemall_tpu.serve.fleet import Fleet
    fleet = Fleet("train_classifier", OPTS, checkpoint_dir=ckdir,
                  replicas=2, promote=True, watch_interval=0.3,
                  bake_opts={"bake_seconds": 0.5, "min_requests": 0,
                             "max_bake_seconds": 0.5})
    fleet.start(wait_ready=True)
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            steps = {r.model_step for r in fleet.manager.replicas()}
            if steps == {stepB} \
                    and ck.read_promoted(ckdir)["state"] == "serving":
                break
            time.sleep(0.3)
        assert {r.model_step for r in fleet.manager.replicas()} \
            == {stepB}
        assert ck.read_promoted(ckdir)["state"] == "serving"
    finally:
        fleet.stop()
