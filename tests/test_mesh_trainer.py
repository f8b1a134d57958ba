"""Sharded training as a product feature (SURVEY.md §3.17 TP row, §8 M3).

The trainers' OWN sparse steps run GSPMD-partitioned via the ``-mesh`` option
— batch over dp, dims-sized state axes over tp — and must match the
single-device model to float tolerance on identical batch streams. This is
the multi-chip path the driver's dryrun exercises; here it runs on the
8-virtual-device CPU mesh (conftest).
"""

import numpy as np
import pytest
from conftest import tracer_spans as _spans

from hivemall_tpu.io.sparse import SparseDataset
from hivemall_tpu.models.fm import FFMTrainer
from hivemall_tpu.models.linear import GeneralClassifier
from hivemall_tpu.parallel.mesh import parse_mesh_spec


def _ffm_ds(n=384, L=6, F=8, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(1, 200, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32) % F, (n, 1))
    val = np.ones((n, L), np.float32)
    w_true = rng.normal(0, 1, 201)
    y = np.sign(w_true[idx].sum(1) + rng.normal(0, 0.1, n)).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L)
    return SparseDataset(idx.ravel(), indptr, val.ravel(), y, fld.ravel())


def _linear_ds(n=512, L=8, seed=1):
    rng = np.random.default_rng(seed)
    idx = rng.integers(1, 300, (n, L)).astype(np.int32)
    val = rng.uniform(0.5, 1.5, (n, L)).astype(np.float32)
    w_true = rng.normal(0, 1, 301)
    y = np.sign((w_true[idx] * val).sum(1)).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L)
    return SparseDataset(idx.ravel(), indptr, val.ravel(), y)


def test_parse_mesh_spec():
    assert parse_mesh_spec("dp=2,tp=4") == (2, 4)
    assert parse_mesh_spec("dp=8") == (8, 1)
    assert parse_mesh_spec("tp=8") == (1, 8)
    assert parse_mesh_spec("auto", n_devices=8) == (8, 1)
    with pytest.raises(ValueError):
        parse_mesh_spec("pp=2")
    with pytest.raises(ValueError):
        parse_mesh_spec("dp=0")


def test_mesh_requires_divisible_batch():
    with pytest.raises(ValueError, match="divisible"):
        FFMTrainer("-dims 1024 -fields 8 -mini_batch 100 -mesh dp=8")


def test_ffm_joint_mesh_matches_single_device():
    ds = _ffm_ds()
    opts = "-dims 4096 -factors 4 -fields 8 -mini_batch 128 -opt adagrad " \
           "-classification"
    single = FFMTrainer(opts).fit(ds, epochs=2)
    sharded = FFMTrainer(opts + " -mesh dp=2,tp=4").fit(ds, epochs=2)
    assert sharded.params["T"].shape == (sharded.Mr, sharded.W)
    np.testing.assert_allclose(np.asarray(single.params["T"]),
                               np.asarray(sharded.params["T"]), atol=1e-4)


def test_fm_minibatch_mesh_matches_single_device():
    """train_fm's round-5 default (minibatch scatter + dense AdaGrad over
    the packed fused table) under GSPMD: the -mesh model must match the
    single-device model on identical batch streams — the scatter into G
    and the dense optimizer pass both partition over (dp, tp)."""
    from hivemall_tpu.models.fm import FMTrainer

    ds = _linear_ds(n=384)
    opts = ("-dims 4096 -factors 4 -mini_batch 128 -opt adagrad "
            "-classification")
    single = FMTrainer(opts).fit(ds, epochs=2)
    sharded = FMTrainer(opts + " -mesh dp=2,tp=4").fit(ds, epochs=2)
    assert single._step is not None
    np.testing.assert_allclose(np.asarray(single.params["T"]),
                               np.asarray(sharded.params["T"]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(single.params["w0"]),
                               np.asarray(sharded.params["w0"]), atol=1e-5)


def test_ffm_ftrl_mesh_matches_single_device():
    ds = _ffm_ds(seed=3)
    opts = "-dims 4096 -factors 4 -fields 8 -mini_batch 128 -opt ftrl " \
           "-classification"
    single = FFMTrainer(opts).fit(ds, epochs=1)
    sharded = FFMTrainer(opts + " -mesh dp=4,tp=2").fit(ds, epochs=1)
    np.testing.assert_allclose(np.asarray(single.params["T"]),
                               np.asarray(sharded.params["T"]), atol=1e-4)


def test_linear_mesh_matches_single_device():
    ds = _linear_ds()
    opts = "-dims 2048 -loss logloss -opt adagrad -reg no -mini_batch 128"
    single = GeneralClassifier(opts).fit(ds, epochs=2)
    sharded = GeneralClassifier(opts + " -mesh dp=2,tp=4").fit(ds, epochs=2)
    np.testing.assert_allclose(single._finalized_weights(),
                               sharded._finalized_weights(), atol=1e-4)
    # scoring works off the sharded state
    p1 = single.predict_proba(ds)
    p2 = sharded.predict_proba(ds)
    np.testing.assert_allclose(p1, p2, atol=1e-4)


def test_sharded_bundle_roundtrip(tmp_path):
    ds = _ffm_ds(seed=5)
    opts = "-dims 4096 -factors 4 -fields 8 -mini_batch 128 -opt adagrad " \
           "-classification -mesh dp=2,tp=4"
    t = FFMTrainer(opts).fit(ds, epochs=1)
    path = str(tmp_path / "ffm_mesh.npz")
    t.save_bundle(path)
    t2 = FFMTrainer(opts)
    t2.load_bundle(path)
    np.testing.assert_allclose(np.asarray(t.params["T"]),
                               np.asarray(t2.params["T"]), atol=0)
    # restored state is re-sharded onto the mesh and trainable
    t2.fit(ds, epochs=1)
    assert np.isfinite(t2.cumulative_loss)


def test_mesh_dp_only_auto():
    ds = _linear_ds(seed=7)
    opts = "-dims 2048 -loss logloss -opt sgd -reg no -mini_batch 128"
    single = GeneralClassifier(opts).fit(ds, epochs=1)
    sharded = GeneralClassifier(opts + " -mesh auto").fit(ds, epochs=1)
    np.testing.assert_allclose(single._finalized_weights(),
                               sharded._finalized_weights(), atol=1e-4)


def test_mesh_with_parquet_stream(tmp_path):
    """Out-of-core streaming composes with GSPMD sharding: the same
    ParquetStream batches train a -mesh FFM trainer and match the
    single-device in-RAM result."""
    pytest.importorskip("pyarrow")
    from hivemall_tpu.io.arrow import ParquetStream, write_parquet_shards

    ds = _ffm_ds(seed=11)
    write_parquet_shards(ds, str(tmp_path / "s"), rows_per_shard=100)
    opts = "-dims 4096 -factors 4 -fields 8 -mini_batch 64 -opt adagrad " \
           "-classification"
    ram = FFMTrainer(opts).fit(ds, epochs=1, shuffle=False)
    stream = ParquetStream(str(tmp_path / "s"))
    sharded = FFMTrainer(opts + " -mesh dp=2,tp=4")
    sharded.fit_stream(stream.batches(64, epochs=1, shuffle=False))
    # same rows, same shard order when unshuffled with one pass
    np.testing.assert_allclose(np.asarray(ram.params["T"]),
                               np.asarray(sharded.params["T"]), atol=1e-3)


def test_mf_mesh_matches_single_device():
    """-mesh on the MF family: dp-sharded batches + tp-sharded P/Q tables
    train to the same model as the unsharded trainer."""
    import numpy as np
    from hivemall_tpu.models.mf import MFAdaGradTrainer
    rng = np.random.default_rng(3)
    n, U, I = 512, 64, 32
    u = rng.integers(0, U, n).astype(np.int32)
    i = rng.integers(0, I, n).astype(np.int32)
    r = (3.0 + 0.5 * rng.normal(0, 1, n)).astype(np.float32)
    opts = (f"-factors 8 -users {U} -items {I} -mini_batch 128 "
            f"-eta0 0.05 -iters 2")
    t0 = MFAdaGradTrainer(opts)
    t0.fit(u, i, r, shuffle=False)
    t1 = MFAdaGradTrainer(opts + " -mesh dp=2,tp=4")
    assert t1.mesh is not None
    t1.fit(u, i, r, shuffle=False)
    P1 = np.asarray(t1.params["P"], np.float32)
    shard = t1.params["P"].sharding.shard_shape(t1.params["P"].shape)
    assert shard[0] == U // 4        # tp=4 row sharding
    np.testing.assert_allclose(np.asarray(t0.params["P"], np.float32), P1,
                               rtol=1e-4, atol=1e-5)
    preds0 = t0.predict(u[:32], i[:32])
    preds1 = t1.predict(u[:32], i[:32])
    np.testing.assert_allclose(preds0, preds1, rtol=1e-4, atol=1e-5)


def test_parts_layout_shards_over_mesh():
    """-ffm_table parts -mesh dp=2,tp=4 (VERDICT r3 next #2): fields shard
    over tp (rank-local slab gathers), batch over dp with a G psum before
    the XLA optimizer tail. Equivalence to the single-chip fused kernel is
    asserted in FUNCTION SPACE (epoch loss + scores): raw T2 entries can
    differ by O(eta) where bf16 gradient rounding flips near-zero grads
    through AdaGrad's G/(|G|+eps)."""
    import numpy as np
    from hivemall_tpu.io.sparse import SparseDataset
    from hivemall_tpu.models.fm import FFMTrainer

    B, L, F, K, dims, n = 256, 8, 8, 16, 1 << 12, 512
    rng = np.random.default_rng(2)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32), (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    indptr = np.arange(0, n * L + 1, L, dtype=np.int64)
    ds = SparseDataset(idx.ravel(), indptr, np.ones(n * L, np.float32),
                       lab, fld.ravel())
    cfg = (f"-dims {dims} -factors {K} -fields {F} -mini_batch {B} "
           "-opt adagrad -classification -halffloat -ffm_table parts "
           "-seed 5")
    a = FFMTrainer(cfg)
    a.fit(ds, epochs=1, shuffle=False, prefetch=False)
    b = FFMTrainer(cfg + " -mesh dp=2,tp=4")
    b.fit(ds, epochs=1, shuffle=False, prefetch=False)
    ss = b.params["T2"].sharding.shard_shape(b.params["T2"].shape)
    assert ss[0] == (F * b.MRF * 2) // 4, ss     # tp=4 field partitions
    la, lb = a.cumulative_loss, b.cumulative_loss
    assert abs(la - lb) / max(abs(la), 1e-9) < 1e-3, (la, lb)
    pa = np.asarray(a.predict(ds))
    pb = np.asarray(b.predict(ds))
    assert np.abs(pa - pb).max() < 0.02, np.abs(pa - pb).max()
    # gradient SCALE parity: shard_map transposes psum to psum, so an
    # unowned (replicated) data loss would make every slab cotangent tp-x
    # and the AdaGrad accumulators tp^2-x (~16 here). The S2 ratio is the
    # sharp detector AdaGrad's scale-invariance hides from loss/scores.
    Sa = np.asarray(a.opt_state["T2"]["gg"], np.float64)
    Sb = np.asarray(b.opt_state["T2"]["gg"], np.float64)
    touched = Sa > 1e-12
    med = float(np.median(Sb[touched] / Sa[touched]))
    assert 0.9 < med < 1.1, med


def test_parts_mesh_option_validation():
    import pytest
    from hivemall_tpu.models.fm import FFMTrainer

    with pytest.raises(ValueError, match="divisible by the tp axis"):
        FFMTrainer("-dims 4096 -factors 16 -fields 8 -mini_batch 256 "
                   "-opt adagrad -classification -halffloat "
                   "-ffm_table parts -mesh dp=2,tp=3")
    with pytest.raises(ValueError, match="128\\*dp"):
        FFMTrainer("-dims 4096 -factors 16 -fields 8 -mini_batch 192 "
                   "-opt adagrad -classification -halffloat "
                   "-ffm_table parts -mesh dp=2,tp=4")


# --- started as a CLI user starts it (PR 31): state born on the mesh, ---
# --- input staged on the mesh ahead of compute ---------------------------

FFM_TP4 = ("-dims 65536 -factors 4 -fields 39 -mini_batch 256 -opt adagrad "
           "-classification -halffloat -seed 7")
FM_TP4 = ("-dims 65536 -factors 5 -mini_batch 256 -opt adagrad "
          "-classification -halffloat -seed 7")


@pytest.mark.parametrize("cls_name,opts", [("FFMTrainer", FFM_TP4),
                                           ("FMTrainer", FM_TP4)])
def test_mesh_state_is_born_in_its_sharding(monkeypatch, tracer, cls_name,
                                            opts):
    """`-mesh dp=1,tp=4`: every table leaf leaves the constructor in
    `_state_sharding`'s sharding, a quarter of its rows on each chip, and
    nothing moved it there afterwards (`_reshard_state` is for -loadmodel
    and load_bundle); its rows are the one-device trainer's to a
    bfloat16 ulp (a fused draw rounds a float32 ulp apart from the eager
    one a CPU trainer makes)."""
    from hivemall_tpu.models import fm
    from hivemall_tpu.obs.registry import registry
    cls = getattr(fm, cls_name)
    moved = []
    monkeypatch.setattr(cls, "_reshard_state",
                        lambda self: moved.append(type(self).__name__))
    t = cls(opts + " -mesh dp=1,tp=4")
    assert not moved
    T, gg = t.params["T"], t.opt_state["T"]["gg"]
    rows = T.shape[0]
    for leaf in (T, gg):
        assert leaf.sharding == t._state_sharding(leaf)
        assert [s.data.shape for s in leaf.addressable_shards] \
            == [(rows // 4, T.shape[1])] * 4
    for small in (t.params["w0"], t.opt_state["w0"]["gg"]):
        assert small.sharding.is_fully_replicated
    made = _spans(tracer, "init.state")
    total = T.nbytes + gg.nbytes + 2 + 4
    assert len(made) == 1 and made[0]["args"]["bytes"] == total
    train = registry.snapshot()["train"]
    assert (train["mesh_dp"], train["mesh_tp"]) == (1, 4)
    assert train["state_bytes_per_chip"] == (T.nbytes + gg.nbytes) // 4 + 6

    one = cls(opts)
    assert registry.snapshot()["train"]["state_bytes_per_chip"] == total
    assert not moved
    a = np.asarray(T.astype(np.float32))
    b = np.asarray(one.params["T"].astype(np.float32))
    ulp = np.exp2(np.floor(np.log2(np.maximum(np.abs(b), 1e-30))) - 7)
    assert np.all(np.abs(a - b) <= ulp) and np.mean(a != b) < 1e-3
    assert np.std(a[:, :4]) > 0.05


def test_loadmodel_and_bundles_still_reshard(tmp_path):
    """What is loaded over a mesh trainer's state arrives whole and is
    moved onto the mesh: `_reshard_state` stays for that."""
    ds = _ffm_ds(seed=5)
    opts = ("-dims 4096 -factors 4 -fields 8 -mini_batch 128 -opt adagrad "
            "-classification")
    t = FFMTrainer(opts).fit(ds, epochs=1)
    path = str(tmp_path / "m")
    t.save_model(path)
    warm = FFMTrainer(opts + f" -mesh dp=2,tp=4 -loadmodel {path}.npz")
    T = warm.params["T"]
    assert T.sharding == warm._state_sharding(T)
    np.testing.assert_array_equal(np.asarray(T), np.asarray(t.params["T"]))
    warm.fit(ds, epochs=1)
    assert np.isfinite(warm.cumulative_loss)


def test_mesh_fit_stream_stages_on_the_prefetch_thread(monkeypatch, tracer,
                                                       tmp_path):
    """Under -mesh the window is placed on the mesh by the `h2d-prefetch`
    thread, as on one chip (an accelerator's path; the CPU is steered to
    it here): every `h2d.stage` carries its dispatch's `seq` and none
    runs on the thread that dispatches, nothing is left for `h2d.shard`,
    the stager's buffer ring follows the one-chip rule, and the model is
    the one the dispatching thread's own placement trains."""
    pytest.importorskip("pyarrow")
    from hivemall_tpu.io import prefetch
    from hivemall_tpu.io.arrow import ParquetStream, write_parquet_shards
    from hivemall_tpu.models.base import LearnerBase

    ds = _ffm_ds(n=1100, seed=11)
    write_parquet_shards(ds, str(tmp_path / "s"), rows_per_shard=300)
    opts = ("-dims 4096 -factors 4 -fields 8 -mini_batch 64 -opt adagrad "
            "-classification -steps_per_dispatch 4 -mesh dp=2,tp=4")

    def fit(trainer):
        stream = ParquetStream(str(tmp_path / "s"))
        return trainer.fit_stream(stream.batches(64, epochs=1,
                                                 shuffle=False))
    plain = fit(FFMTrainer(opts))
    shard_spans = _spans(tracer, "h2d.shard")
    assert len(shard_spans) == 18 // 4 + 18 % 4     # 1100 rows: 18 batches
    assert {e["args"]["thread"] for e in shard_spans} == {"MainThread"}
    assert not _spans(tracer, "h2d.stage")

    tracer.reset()
    reuse = []
    stager_init = prefetch.MegabatchStager.__init__

    def spy(self, *args, **kw):
        reuse.append(kw["reuse"])
        stager_init(self, *args, **kw)
    monkeypatch.setattr(prefetch.MegabatchStager, "__init__", spy)
    monkeypatch.setattr(LearnerBase, "_wants_prefetch",
                        staticmethod(lambda: True))
    ahead = fit(FFMTrainer(opts))
    assert reuse == [True]
    staged = _spans(tracer, "h2d.stage")
    dispatched = _spans(tracer, "dispatch.megastep") \
        + _spans(tracer, "dispatch.step")
    assert {e["args"]["thread"] for e in staged} == {"h2d-prefetch"}
    assert sorted(e["args"]["seq"] for e in staged) \
        == sorted(e["args"]["seq"] for e in dispatched) \
        == list(range(len(shard_spans)))
    assert {e["args"]["thread"] for e in dispatched} == {"MainThread"}
    assert not _spans(tracer, "h2d.shard")
    np.testing.assert_array_equal(np.asarray(plain.params["T"]),
                                  np.asarray(ahead.params["T"]))


def _bench_run():
    import importlib.util
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(root, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _exchange_left_out(trainer):
    """The harness's `break_trainer` fault for a mesh cell: every chip but
    the first contributes nothing (rows of the other shards read zero)."""
    import jax
    import jax.numpy as jnp
    inner = trainer._train_megabatch
    rows = trainer.params["T"].shape[0]

    def alone(mb):
        T = trainer.params["T"]
        mask = (jnp.arange(rows) < rows // 4)[:, None]
        trainer.params["T"] = jax.device_put(
            jnp.where(mask, T, 0).astype(T.dtype), T.sharding)
        return inner(mb)
    trainer._train_megabatch = alone


@pytest.mark.parametrize("fault", [None, _exchange_left_out],
                         ids=["sound", "exchange_left_out"])
def test_tp4_cell_agrees_with_its_reference_at_toy_size(
        capsys, monkeypatch, tmp_path, fault):
    """`benchmark/run.py --toy --workload ffm_criteo_joint_tp4.stream_mesh`:
    the catalog's constructor under `-mesh dp=1,tp=4`, no detour, and its
    first dispatch of 4 steps against benchmark/reference/ffm.py:
    `correct`, and not with a fault planted through the harness's hook.
    The harness is run in this process (the hook is a function), so what
    it sets for a process of its own is put back: the import path and the
    compile cache."""
    import json
    import sys

    import jax
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    cache_keys = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")
    before = {key: getattr(jax.config, key) for key in cache_keys}
    run = _bench_run()
    hooks = {} if fault is None else {"break_trainer": fault}
    try:
        assert run.main(["--workload", "ffm_criteo_joint_tp4.stream_mesh",
                         "--seed", str(2 ** 31 + 31), "--seconds", "1",
                         "--trace", "0", "--toy"], **hooks) == 0
    finally:
        for key, value in before.items():
            jax.config.update(key, value)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is (fault is None)
    assert res["compared"]["decode_missed_rows"]["value"] == 0
    assert res["compared"]["window_lost_examples"]["value"] == 0
    if fault is not None:
        assert any(c["value"] > c["limit"]
                   for c in res["compared"].values())
