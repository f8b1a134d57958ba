"""Packed shard cache (io/shard_cache.py): bit-exact warm epochs,
digest-keyed invalidation, atomic rewrite, obs counters."""

import json
import os

import numpy as np
import pytest

from hivemall_tpu.io import shard_cache as sc
from hivemall_tpu.io.sparse import SparseDataset
from hivemall_tpu.models.fm import FFMTrainer


def _ffm_unit_ds(n=700, L=8, F=8, dims=1 << 11, seed=5):
    """Criteo-shaped unit-value FFM dataset (one feature per field)."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(1, dims, (n, L)).astype(np.int32)
    fld = np.tile(np.arange(L, dtype=np.int32) % F, (n, 1))
    lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
    return SparseDataset(idx.ravel(),
                         np.arange(0, n * L + 1, L, dtype=np.int64),
                         np.ones(n * L, np.float32), lab, fld.ravel())


_CFG = ("-dims 2048 -factors 2 -fields 8 -mini_batch 64 "
        "-classification -pack_input on")


def _traj(cfg, ds, epochs=3, shuffle=True):
    t = FFMTrainer(cfg)
    t._trace_losses = []
    t.fit(ds, epochs=epochs, shuffle=shuffle)
    return np.asarray(t._trace_losses), t


# --- container format -------------------------------------------------------

def test_container_roundtrip_and_validation(tmp_path):
    path = str(tmp_path / "x.pack")
    a = np.arange(999, dtype=np.uint8).reshape(27, 37)
    b = np.linspace(0, 1, 55).astype(np.float32)
    sc.write_cache_file(path, {"kind": "t", "who": "roundtrip"},
                        {"a": a, "b": b})
    header, views = sc.read_cache_file(path)
    assert header["who"] == "roundtrip"
    np.testing.assert_array_equal(np.asarray(views["a"]), a)
    np.testing.assert_array_equal(np.asarray(views["b"]), b)
    # bit flip in the payload -> CacheInvalid
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size - 8)
        f.write(b"\x7f")
    with pytest.raises(sc.CacheInvalid, match="digest"):
        sc.read_cache_file(path)
    # truncation -> CacheInvalid before any digest work
    sc.write_cache_file(path, {"kind": "t"}, {"a": a})
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 5)
    with pytest.raises(sc.CacheInvalid, match="truncated"):
        sc.read_cache_file(path)
    # not a cache file at all
    with open(path, "wb") as f:
        f.write(b"definitely not a cache")
    with pytest.raises(sc.CacheInvalid, match="magic"):
        sc.read_cache_file(path)
    # header-only read degrades to None, never raises
    assert sc.read_cache_header(path) is None


# --- bit-exactness of the cached fit path -----------------------------------

@pytest.mark.parametrize("shuffle", [True, False])
def test_cached_epochs_bit_match_streamed(tmp_path, shuffle):
    """Shuffled and unshuffled epochs through the shard cache reproduce
    the streamed path's loss trajectory EXACTLY — cold (build + tee) and
    warm (fresh trainer, pure mmap replay) both. -checkpoint_dir forces
    the per-epoch streamed loop on the reference so both sides run the
    same seed schedule."""
    ds = _ffm_unit_ds()
    ref, _ = _traj(_CFG + f" -checkpoint_dir {tmp_path}/ck0", ds,
                   shuffle=shuffle)
    cold, _ = _traj(_CFG + f" -checkpoint_dir {tmp_path}/ck1 "
                           f"-shard_cache_dir {tmp_path}/cache", ds,
                    shuffle=shuffle)
    np.testing.assert_array_equal(ref, cold)
    warm, tw = _traj(_CFG + f" -checkpoint_dir {tmp_path}/ck2 "
                            f"-shard_cache_dir {tmp_path}/cache", ds,
                     shuffle=shuffle)
    np.testing.assert_array_equal(ref, warm)
    # the warm run never ran live prep: parse/canonicalize/pack at zero
    d = tw.pipeline_stats.as_dict()
    assert d["batches_prepared"] == 0 and d["prep_seconds"] == 0.0
    assert d["cache_batches"] > 0


def test_cached_device_replay_orchestration_matches_no_cache(tmp_path):
    """Without -checkpoint_dir the epochs>1 path keeps the HBM/device
    replay orchestration; adding -shard_cache_dir must not change the
    trajectory — cold (tee rides along) or warm (epoch 1 served from the
    cache feeds the same retention)."""
    ds = _ffm_unit_ds(seed=7)
    ref, _ = _traj(_CFG, ds)
    cold, _ = _traj(_CFG + f" -shard_cache_dir {tmp_path}/c", ds)
    np.testing.assert_array_equal(ref, cold)
    warm, tw = _traj(_CFG + f" -shard_cache_dir {tmp_path}/c", ds)
    np.testing.assert_array_equal(ref, warm)
    assert tw.pipeline_stats.batches_prepared == 0
    assert tw.pipeline_stats.cache_batches > 0


def test_cached_restart_bit_matches_and_counts(tmp_path):
    """A fresh process-restart-shaped trainer on a warm cache reproduces
    the cold run and the obs counters record the hit/rebuild."""
    ds = _ffm_unit_ds(seed=9)
    sc.counters.reset()
    cfg = _CFG + f" -shard_cache_dir {tmp_path}/c"
    cold, _ = _traj(cfg, ds, epochs=1)
    warm, _ = _traj(cfg, ds, epochs=1)
    np.testing.assert_array_equal(cold, warm)
    d = sc.counters.as_dict()
    assert d["misses"] == 1 and d["rebuilds"] == 1 and d["hits"] == 1
    assert d["bytes_mmapped"] > 0 and d["bytes_written"] > 0


def test_model_tables_equal_through_cache(tmp_path):
    ds = _ffm_unit_ds(seed=11)
    a = FFMTrainer(_CFG).fit(ds, epochs=2)
    b = FFMTrainer(_CFG + f" -shard_cache_dir {tmp_path}/c").fit(ds,
                                                                 epochs=2)
    c = FFMTrainer(_CFG + f" -shard_cache_dir {tmp_path}/c").fit(ds,
                                                                 epochs=2)
    sa = json.dumps(a.model_table(), sort_keys=True, default=str)
    assert sa == json.dumps(b.model_table(), sort_keys=True, default=str)
    assert sa == json.dumps(c.model_table(), sort_keys=True, default=str)


# --- invalidation safety ----------------------------------------------------

def test_corrupt_cache_falls_back_and_rewrites_atomically(tmp_path):
    """A corrupted cache file must read as a MISS (invalid counted), the
    fit must fall back to live prep with an unchanged trajectory, and the
    cache must be rewritten atomically (tmp -> fsync -> os.replace: the
    published file is valid again, no .tmp litter)."""
    ds = _ffm_unit_ds(seed=3)
    cdir = tmp_path / "c"
    cfg = _CFG + f" -shard_cache_dir {cdir}"
    ref, _ = _traj(cfg, ds, epochs=1)
    (path,) = [str(cdir / f) for f in os.listdir(cdir)]
    for corruption in ("flip", "truncate"):
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            if corruption == "flip":
                f.seek(size - 64)
                f.write(b"\xde\xad\xbe\xef")
            else:
                f.truncate(size // 3)
        sc.counters.reset()
        got, _ = _traj(cfg, ds, epochs=1)
        np.testing.assert_array_equal(ref, got)
        d = sc.counters.as_dict()
        assert d["invalid"] == 1 and d["misses"] == 1 and d["rebuilds"] == 1
        sc.read_cache_file(path)            # rewritten file validates
        assert not [f for f in os.listdir(cdir) if ".tmp" in f]


def test_source_mutation_invalidates_file_keyed_cache(tmp_path):
    """A dataset carrying a file identity (source_id) must miss when the
    source's mtime changes, fall back to live prep, and rewrite."""
    ds = _ffm_unit_ds(seed=13)
    src = tmp_path / "src.libsvm"
    src.write_text("synthetic source stand-in\n")
    cdir = tmp_path / "c"
    cfg = _CFG + f" -shard_cache_dir {cdir}"

    def fit_with_sid():
        d2 = SparseDataset(ds.indices, ds.indptr, ds.values, ds.labels,
                           ds.fields)
        d2.source_id = sc.file_source_id(str(src))
        return _traj(cfg, d2, epochs=1)

    ref, _ = fit_with_sid()
    sc.counters.reset()
    same, _ = fit_with_sid()                # unchanged source: pure hit
    d = sc.counters.as_dict()
    assert d["hits"] == 1 and d["misses"] == 0
    np.testing.assert_array_equal(ref, same)
    st = os.stat(src)
    os.utime(src, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))
    sc.counters.reset()
    again, _ = fit_with_sid()               # mutated mtime: miss + rebuild
    d = sc.counters.as_dict()
    assert d["hits"] == 0 and d["misses"] == 1 and d["rebuilds"] == 1
    np.testing.assert_array_equal(ref, again)
    assert len(os.listdir(cdir)) == 1       # stale file REPLACED in place


def test_prep_config_change_never_false_hits(tmp_path):
    ds = _ffm_unit_ds(seed=15)
    cdir = tmp_path / "c"
    _traj(_CFG + f" -shard_cache_dir {cdir}", ds, epochs=1)
    sc.counters.reset()
    _traj(_CFG.replace("-dims 2048", "-dims 1024")
          + f" -shard_cache_dir {cdir}", ds, epochs=1)
    d = sc.counters.as_dict()
    assert d["hits"] == 0 and d["misses"] >= 1
    assert len(os.listdir(cdir)) == 2       # distinct prep-config keys


def test_non_unit_dataset_declines_cache_and_still_trains(tmp_path):
    """Real-valued batches never pack, so the build must fail open: no
    cache file, identical training outcome."""
    ds = _ffm_unit_ds(seed=17)
    ds = SparseDataset(ds.indices, ds.indptr,
                       np.linspace(0.5, 1.5, len(ds.values))
                       .astype(np.float32), ds.labels, ds.fields)
    cdir = tmp_path / "c"
    sc.counters.reset()
    a, _ = _traj(_CFG, ds, epochs=1)
    b, _ = _traj(_CFG + f" -shard_cache_dir {cdir}", ds, epochs=1)
    np.testing.assert_array_equal(a, b)
    assert sc.counters.as_dict()["build_failed"] == 1
    assert not os.path.exists(cdir) or not os.listdir(cdir)


# --- ParquetStream decoded-shard cache --------------------------------------

def test_parquet_decode_cache_bit_exact_and_invalidates(tmp_path):
    pytest.importorskip("pyarrow")
    from hivemall_tpu.io.arrow import ParquetStream, write_parquet_shards

    ds = _ffm_unit_ds(n=300, seed=21)
    pq_dir = str(tmp_path / "pq")
    write_parquet_shards(ds, pq_dir, rows_per_shard=64)
    cdir = str(tmp_path / "cache")
    plain = list(ParquetStream(pq_dir).batches(32, epochs=2, shuffle=True,
                                               seed=9))
    sc.counters.reset()
    cold = list(ParquetStream(pq_dir, cache_dir=cdir)
                .batches(32, epochs=2, shuffle=True, seed=9))
    from conftest import assert_batches_equal
    assert len(plain) == len(cold) > 0
    for x, y in zip(plain, cold):
        assert_batches_equal(x, y)
    n_shards = sc.counters.as_dict()["rebuilds"]
    assert n_shards == len(ParquetStream(pq_dir).files)
    # epoch 2 of the same traversal already hit the cache
    assert sc.counters.as_dict()["hits"] >= n_shards
    sc.counters.reset()
    warm = list(ParquetStream(pq_dir, cache_dir=cdir)
                .batches(32, epochs=2, shuffle=True, seed=9))
    for x, y in zip(plain, warm):
        assert_batches_equal(x, y)
    d = sc.counters.as_dict()
    assert d["misses"] == 0 and d["rebuilds"] == 0 and d["hits"] > 0
    # mutate one shard's mtime: that shard misses + rebuilds, output equal
    shard0 = ParquetStream(pq_dir).files[0]
    st = os.stat(shard0)
    os.utime(shard0, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))
    sc.counters.reset()
    again = list(ParquetStream(pq_dir, cache_dir=cdir)
                 .batches(32, epochs=2, shuffle=True, seed=9))
    for x, y in zip(plain, again):
        assert_batches_equal(x, y)
    d = sc.counters.as_dict()
    assert d["misses"] >= 1 and d["rebuilds"] == 1


def test_fit_stream_with_decode_cache_matches(tmp_path):
    pytest.importorskip("pyarrow")
    from hivemall_tpu.io.arrow import ParquetStream, write_parquet_shards

    ds = _ffm_unit_ds(n=256, seed=23)
    pq_dir = str(tmp_path / "pq")
    write_parquet_shards(ds, pq_dir, rows_per_shard=128)
    cdir = str(tmp_path / "cache")

    def run(cache):
        t = FFMTrainer(_CFG)
        t._trace_losses = []
        stream = ParquetStream(pq_dir, cache_dir=cdir if cache else None)
        t.fit_stream(stream.batches(64, epochs=1, shuffle=False))
        return np.asarray(t._trace_losses)

    a = run(False)
    b = run(True)                           # cold: builds shard caches
    c = run(True)                           # warm: decode skipped
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)


# --- obs surface ------------------------------------------------------------

def test_registry_section_and_prometheus_surface():
    from hivemall_tpu.obs.http import to_prometheus
    from hivemall_tpu.obs.registry import registry

    snap = registry.snapshot()
    assert "ingest_cache" in snap
    for key in ("hits", "misses", "rebuilds", "bytes_mmapped"):
        assert key in snap["ingest_cache"]
    text = to_prometheus(snap)
    assert "hivemall_tpu_ingest_cache_hits" in text
    assert "hivemall_tpu_ingest_cache_bytes_mmapped" in text


def test_source_id_distinguishes_parse_configs(tmp_path):
    """The same file parsed under different reader options is a DIFFERENT
    dataset — its source_id must differ so the packed cache can never
    serve one parse's records for another's key."""
    from hivemall_tpu.io.libsvm import read_libsvm

    p = str(tmp_path / "t.libsvm")
    with open(p, "w") as f:
        f.write("1 1:1 2:1\n-1 3:1\n")
    a = read_libsvm(p)
    b = read_libsvm(p, zero_based=True)
    c = read_libsvm(p)
    assert a.source_id != b.source_id
    assert a.source_id == c.source_id


# --- native canonicalizer default (tentpole leg 3) --------------------------

def test_fit_native_and_python_canonicalizer_bit_equal(tmp_path):
    """The C++ canonicalizer is the default in every prep path; a fit
    with it active must be bit-equal to the numpy fallback (the automatic
    degradation when the native library did not build)."""
    import hivemall_tpu.utils.native as nat

    ds = _ffm_unit_ds(seed=25)
    a = FFMTrainer(_CFG)
    a._trace_losses = []
    a.fit(ds, epochs=1, shuffle=True)
    saved = nat.canonicalize_fieldmajor_native
    try:
        nat.canonicalize_fieldmajor_native = lambda *a_, **k: NotImplemented
        b = FFMTrainer(_CFG)
        b._trace_losses = []
        b.fit(ds, epochs=1, shuffle=True)
    finally:
        nat.canonicalize_fieldmajor_native = saved
    np.testing.assert_array_equal(np.asarray(a._trace_losses),
                                  np.asarray(b._trace_losses))
