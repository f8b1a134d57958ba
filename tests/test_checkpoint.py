"""Checkpoint/resume bundles (SURVEY.md §6): resumed == continuous."""

import numpy as np
import pytest

from hivemall_tpu.models.fm import FMTrainer
from hivemall_tpu.models.linear import GeneralClassifier, GeneralRegressor


def _rows(n, d=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = np.where(X[:, 0] - 0.3 * X[:, 1] > 0, 1, -1)
    feats = [[f"f{j}:{X[i, j]:.5f}" for j in range(d)] for i in range(n)]
    return feats, y


OPTS = "-opt adagrad -loss logloss -mini_batch 8 -dims 4096"


def test_resume_equals_continuous(tmp_path):
    feats, y = _rows(96)
    cont = GeneralClassifier(OPTS)
    for f, lab in zip(feats, y):
        cont.process(f, lab)
    cont_rows = dict(cont.close())

    first = GeneralClassifier(OPTS)
    for f, lab in zip(feats[:48], y[:48]):
        first.process(f, lab)
    first._flush()
    p = tmp_path / "ck.npz"
    first.save_bundle(str(p))

    second = GeneralClassifier(OPTS)
    second.load_bundle(str(p))
    assert second._t == first._t and second._examples == 48
    for f, lab in zip(feats[48:], y[48:]):
        second.process(f, lab)
    res_rows = dict(second.close())

    assert set(res_rows) == set(cont_rows)
    for k in cont_rows:
        np.testing.assert_allclose(res_rows[k], cont_rows[k],
                                   rtol=1e-6, atol=1e-7)


def test_bundle_keeps_optimizer_state(tmp_path):
    """AdaGrad accumulators survive the roundtrip (what -loadmodel loses)."""
    feats, y = _rows(32)
    tr = GeneralClassifier(OPTS)
    for f, lab in zip(feats, y):
        tr.process(f, lab)
    tr._flush()
    p = tmp_path / "ck.npz"
    tr.save_bundle(str(p))
    fresh = GeneralClassifier(OPTS)
    fresh.load_bundle(str(p))
    ref = tr._checkpoint_arrays()
    got = fresh._checkpoint_arrays()
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-6)


def test_fm_bundle_roundtrip(tmp_path):
    feats, y = _rows(40)
    tr = FMTrainer("-factors 4 -mini_batch 8 -dims 2048 -classification")
    for f, lab in zip(feats, y):
        tr.process(f, lab)
    tr._flush()
    p = tmp_path / "fm.npz"
    tr.save_bundle(str(p))
    fresh = FMTrainer("-factors 4 -mini_batch 8 -dims 2048 -classification")
    fresh.load_bundle(str(p))
    np.testing.assert_allclose(np.asarray(fresh.params["T"], np.float32),
                               np.asarray(tr.params["T"], np.float32))


def test_rda_resume_keeps_dual_accumulators(tmp_path):
    """RDA recomputes w from u/gg each step — they must survive the bundle."""
    from hivemall_tpu.models.classifier import AdaGradRDATrainer
    feats, y = _rows(96)
    opts = "-mini_batch 8 -dims 4096"
    cont = AdaGradRDATrainer(opts)
    for f, lab in zip(feats, y):
        cont.process(f, lab)
    cont_rows = dict(cont.close())

    first = AdaGradRDATrainer(opts)
    for f, lab in zip(feats[:48], y[:48]):
        first.process(f, lab)
    first._flush()
    p = tmp_path / "rda.npz"
    first.save_bundle(str(p))
    second = AdaGradRDATrainer(opts)
    second.load_bundle(str(p))
    assert float(np.abs(np.asarray(second.opt_state["gg"])).sum()) > 0
    for f, lab in zip(feats[48:], y[48:]):
        second.process(f, lab)
    res_rows = dict(second.close())
    assert set(res_rows) == set(cont_rows)
    for k in cont_rows:
        np.testing.assert_allclose(res_rows[k], cont_rows[k],
                                   rtol=1e-6, atol=1e-7)


def test_save_bundle_atomic_leaves_no_litter(tmp_path):
    """tmp -> fsync -> os.replace: after a save (including overwriting an
    existing bundle) the directory holds exactly the bundle, no tmp files,
    and the result round-trips."""
    import os
    feats, y = _rows(24)
    tr = GeneralClassifier(OPTS)
    for f, lab in zip(feats, y):
        tr.process(f, lab)
    tr._flush()
    p = tmp_path / "ck.npz"
    tr.save_bundle(str(p))
    tr.save_bundle(str(p))              # overwrite path also atomic
    assert os.listdir(tmp_path) == ["ck.npz"]
    fresh = GeneralClassifier(OPTS)
    fresh.load_bundle(str(p))
    assert fresh._t == tr._t


def test_bundle_digest_detects_tamper(tmp_path):
    """The format-2 manifest digest catches a bit-flipped leaf that the
    zip container itself would happily return."""
    import json
    feats, y = _rows(24)
    tr = GeneralClassifier(OPTS)
    for f, lab in zip(feats, y):
        tr.process(f, lab)
    tr._flush()
    p = tmp_path / "ck.npz"
    tr.save_bundle(str(p))
    with np.load(str(p), allow_pickle=False) as z:
        data = {k: z[k] for k in z.files}
    meta = json.loads(str(data["__meta__"]))
    assert meta["format"] == 2 and "digest" in meta
    data["leaf_0"] = data["leaf_0"] + 1          # tamper one leaf
    np.savez(str(p), **data)
    fresh = GeneralClassifier(OPTS)
    with pytest.raises(ValueError, match="digest mismatch"):
        fresh.load_bundle(str(p))


def test_checkpoint_manager_retention(tmp_path):
    """-checkpoint_keep k: only the k newest step bundles survive, and
    resume() restores the newest."""
    from hivemall_tpu.io.checkpoint import list_bundles
    from hivemall_tpu.io.libsvm import synthetic_classification
    ds, _ = synthetic_classification(192, 8, seed=7)
    ckdir = str(tmp_path / "ck")
    opts = (f"{OPTS} -steps_per_dispatch 1 -checkpoint_dir {ckdir} "
            f"-checkpoint_every 2 -checkpoint_keep 2")
    tr = GeneralClassifier(opts)
    tr.fit_stream(ds.batches(16, shuffle=False))     # 12 batches
    bundles = list_bundles(ckdir, tr.NAME)
    assert len(bundles) == 2                         # retention enforced
    r = GeneralClassifier(opts)
    assert r.resume()
    assert r._t == tr._t                             # newest == final state


def test_prune_spares_in_use_bundles(tmp_path):
    """Last-k retention must not GC a bundle a live reader holds open
    (the ``.pin.<pid>`` sidecar a bulk scoring job writes via
    hold_bundle): the held bundle survives pruning past the keep window,
    ages out normally once the hold releases, and a stale pin left by a
    dead holder is swept instead of leaking retention forever."""
    import os
    import subprocess
    import sys
    from hivemall_tpu.io.checkpoint import (CheckpointManager, hold_bundle,
                                            in_use_bundles)

    feats, y = _rows(64)
    tr = GeneralClassifier(OPTS)
    mgr = CheckpointManager(str(tmp_path), tr.NAME, keep=1)

    def advance_and_save(lo, hi):
        for f, lab in zip(feats[lo:hi], y[lo:hi]):
            tr.process(f, lab)
        tr._flush()
        return mgr.save(tr)

    p1 = advance_and_save(0, 16)
    with hold_bundle(p1):
        assert os.path.exists(p1 + f".pin.{os.getpid()}")
        assert in_use_bundles(str(tmp_path)) == {p1}
        p2 = advance_and_save(16, 32)         # prune: p1 pinned, survives
        assert os.path.exists(p1)
        p3 = advance_and_save(32, 48)         # p2 has no pin: pruned
        assert os.path.exists(p1) and os.path.exists(p3)
        assert not os.path.exists(p2)
    assert not os.path.exists(p1 + f".pin.{os.getpid()}")
    p4 = advance_and_save(48, 64)             # hold released: p1 ages out
    assert os.path.exists(p4) and not os.path.exists(p1)

    # a pin whose holder died must be swept, not honored forever
    child = subprocess.Popen([sys.executable, "-c", "pass"])
    child.wait()
    stale = p4 + f".pin.{child.pid}"
    with open(stale, "w") as f:
        f.write('{"pid": %d}' % child.pid)
    assert in_use_bundles(str(tmp_path)) == set()
    assert not os.path.exists(stale)


def test_bundle_rejects_mismatch(tmp_path):
    feats, y = _rows(16)
    tr = GeneralClassifier(OPTS)
    for f, lab in zip(feats, y):
        tr.process(f, lab)
    p = tmp_path / "ck.npz"
    tr.save_bundle(str(p))
    with pytest.raises(ValueError, match="cannot resume"):
        GeneralRegressor(OPTS.replace("logloss", "squaredloss")) \
            .load_bundle(str(p))
    with pytest.raises(ValueError, match="mismatch"):
        GeneralClassifier("-opt adagrad -loss logloss -dims 1024") \
            .load_bundle(str(p))


def test_mf_resume_equals_continuous(tmp_path):
    """Non-LearnerBase trainer (MF AdaGrad) bundles via the same protocol."""
    from hivemall_tpu.models.mf import MFAdaGradTrainer
    rng = np.random.default_rng(5)
    opts = "-factors 4 -users 30 -items 20 -mini_batch 8 -seed 2"
    trips = [(int(rng.integers(30)), int(rng.integers(20)),
              float(rng.normal())) for _ in range(80)]

    cont = MFAdaGradTrainer(opts)
    for u, i, r in trips:
        cont.process(u, i, r)
    cont._flush()
    ref = np.asarray(cont.params["P"], np.float32)

    first = MFAdaGradTrainer(opts)
    for u, i, r in trips[:40]:
        first.process(u, i, r)
    first._flush()
    p = tmp_path / "mf.npz"
    first.save_bundle(str(p))
    second = MFAdaGradTrainer(opts)
    second.load_bundle(str(p))
    assert second._t == first._t
    for u, i, r in trips[40:]:
        second.process(u, i, r)
    second._flush()
    np.testing.assert_allclose(np.asarray(second.params["P"], np.float32),
                               ref, rtol=1e-6, atol=1e-7)


def test_per_epoch_auto_checkpoint(tmp_path, monkeypatch):
    """HIVEMALL_TPU_CHECKPOINT_DIR => one bundle per fit() epoch (§6)."""
    import os
    from hivemall_tpu.io.libsvm import synthetic_classification
    monkeypatch.setenv("HIVEMALL_TPU_CHECKPOINT_DIR", str(tmp_path))
    ds, _ = synthetic_classification(64, 16, seed=9)
    tr = GeneralClassifier("-dims 128 -mini_batch 16 -iters 3")
    tr.fit(ds)
    files = sorted(os.listdir(tmp_path))
    assert files == [f"train_classifier-ep{i}.npz" for i in (1, 2, 3)]
    resumed = GeneralClassifier("-dims 128 -mini_batch 16 -iters 3")
    resumed.load_bundle(str(tmp_path / files[-1]))
    assert resumed._t == tr._t


def test_lda_bundle_resume(tmp_path):
    """Topic-model bundles: lambda matrix + hashed vocab names survive."""
    from hivemall_tpu.models.topicmodel import LDATrainer
    docs_a = [["apple", "banana", "fruit"] * 4 for _ in range(10)]
    docs_b = [["stock", "market", "trade"] * 4 for _ in range(10)]
    opts = "-topics 2 -vocab 1024 -mini_batch 4"
    tr = LDATrainer(opts)
    for d in docs_a + docs_b:
        tr.process(d)
    tr._flush()
    p = tmp_path / "lda.npz"
    tr.save_bundle(str(p))
    fresh = LDATrainer(opts)
    fresh.load_bundle(str(p))
    np.testing.assert_allclose(np.asarray(fresh.lam), np.asarray(tr.lam))
    assert fresh._vocab_names == tr._vocab_names
    assert fresh._t == tr._t
    # restored model assigns the same topics
    np.testing.assert_allclose(fresh.transform(["apple", "banana"]),
                               tr.transform(["apple", "banana"]), rtol=1e-6)


def test_multiclass_bundle_resume(tmp_path):
    """Multiclass bundles keep the class-row map with label types intact."""
    from hivemall_tpu.models.multiclass import MulticlassPerceptronTrainer
    rng = np.random.default_rng(8)
    opts = "-classes 3 -dims 1024 -mini_batch 8"
    tr = MulticlassPerceptronTrainer(opts)
    for _ in range(60):
        x = rng.normal(size=3)
        cls = int(np.argmax(x))
        tr.process([f"f{j}:{x[j]:.4f}" for j in range(3)], cls)
    tr._flush()
    p = tmp_path / "mc.npz"
    tr.save_bundle(str(p))
    fresh = MulticlassPerceptronTrainer(opts)
    fresh.load_bundle(str(p))
    assert fresh._labels == tr._labels
    assert all(isinstance(k, int) for k in fresh._labels)
    np.testing.assert_allclose(np.asarray(fresh.W), np.asarray(tr.W))


def test_multiclass_bundle_bool_labels(tmp_path):
    from hivemall_tpu.models.multiclass import MulticlassPerceptronTrainer
    opts = "-classes 2 -dims 256 -mini_batch 4"
    tr = MulticlassPerceptronTrainer(opts)
    for i in range(8):
        tr.process([f"f{i % 3}:1.0"], bool(i % 2))
    tr._flush()
    p = tmp_path / "b.npz"
    tr.save_bundle(str(p))
    fresh = MulticlassPerceptronTrainer(opts)
    fresh.load_bundle(str(p))
    assert fresh._labels == tr._labels
    assert all(isinstance(k, bool) for k in fresh._labels)
