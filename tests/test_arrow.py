"""Arrow/Parquet/CSV ingest + out-of-core streaming epochs (SURVEY.md §1,
§8 M0; VERDICT r1 missing #1)."""

import numpy as np
import pytest

pytest.importorskip("pyarrow")

from hivemall_tpu.io.arrow import (ParquetStream, read_csv, read_parquet,
                                   table_to_dataset, write_parquet_shards)
from hivemall_tpu.io.libsvm import synthetic_classification
from hivemall_tpu.io.sparse import SparseDataset
from hivemall_tpu.utils.hashing import mhash


def _ds(n=1000, seed=0):
    ds, _ = synthetic_classification(n, 500, density=0.02, seed=seed)
    return ds


def test_parquet_roundtrip(tmp_path):
    ds = _ds()
    paths = write_parquet_shards(ds, str(tmp_path / "shards"),
                                 rows_per_shard=300)
    assert len(paths) == 4
    back = read_parquet(str(tmp_path / "shards"))
    np.testing.assert_array_equal(ds.indices, back.indices)
    np.testing.assert_array_equal(ds.indptr, back.indptr)
    np.testing.assert_allclose(ds.values, back.values)
    np.testing.assert_allclose(ds.labels, back.labels)


def test_parquet_roundtrip_with_fields(tmp_path):
    n, L = 200, 5
    rng = np.random.default_rng(0)
    ds = SparseDataset(
        rng.integers(1, 100, n * L).astype(np.int32),
        np.arange(0, n * L + 1, L), np.ones(n * L, np.float32),
        rng.normal(0, 1, n).astype(np.float32),
        rng.integers(0, 8, n * L).astype(np.int32))
    write_parquet_shards(ds, str(tmp_path / "s"), rows_per_shard=64)
    back = read_parquet(str(tmp_path / "s"))
    np.testing.assert_array_equal(ds.fields, back.fields)


def test_string_features_table():
    import pyarrow as pa
    table = pa.table({
        "features": [["1:0.5", "7", "height:1.7"], ["2:2.0"]],
        "label": [1.0, -1.0],
    })
    ds = table_to_dataset(table, dims=1 << 16)
    assert len(ds) == 2
    i0, v0 = ds.row(0)
    assert list(i0[:2]) == [1, 7]
    assert i0[2] == mhash("height", (1 << 16) - 1)
    np.testing.assert_allclose(v0, [0.5, 1.0, 1.7])


def test_ffm_string_features_table():
    import pyarrow as pa
    table = pa.table({
        "features": [["2:11:0.5", "3:12"], ["0:1:1.0"]],
        "label": [1.0, -1.0],
    })
    ds = table_to_dataset(table, dims=1 << 16, ffm=True, num_fields=8)
    i0, v0 = ds.row(0)
    assert list(i0) == [11, 12]
    np.testing.assert_allclose(v0, [0.5, 1.0])
    assert list(ds.fields[:2]) == [2, 3]


def test_csv_reader(tmp_path):
    p = tmp_path / "t.csv"
    p.write_text("label,age,income\n1,30,5.5\n-1,40,0\n")
    ds = read_csv(str(p), dims=1 << 16)
    assert len(ds) == 2
    i0, v0 = ds.row(0)
    assert len(i0) == 2
    np.testing.assert_allclose(sorted(v0), [5.5, 30.0])
    i1, v1 = ds.row(1)       # zero income dropped (sparse semantics)
    assert len(i1) == 1 and v1[0] == 40.0


def test_stream_covers_every_row_once_per_epoch(tmp_path):
    ds = _ds(997)            # prime size: exercises the carry-over path
    write_parquet_shards(ds, str(tmp_path / "s"), rows_per_shard=250)
    stream = ParquetStream(str(tmp_path / "s"))
    assert len(stream) == 997
    seen = 0.0
    n_rows = 0
    for b in stream.batches(64, epochs=2, shuffle=True, seed=7):
        nv = b.n_valid or b.batch_size
        n_rows += nv
        seen += b.label[:nv].sum()
    assert n_rows == 2 * 997
    assert abs(seen - 2 * ds.labels.sum()) < 1e-3


def test_fit_stream_matches_in_ram_quality(tmp_path):
    from hivemall_tpu.models.linear import GeneralClassifier
    ds = _ds(2000, seed=3)
    write_parquet_shards(ds, str(tmp_path / "s"), rows_per_shard=512)
    opts = "-dims 1024 -loss logloss -opt adagrad -reg no -mini_batch 128"
    ram = GeneralClassifier(opts).fit(ds, epochs=2)
    stream = ParquetStream(str(tmp_path / "s"))
    oo = GeneralClassifier(opts).fit_stream(stream.batches(128, epochs=2))
    # same corpus, different order: equal quality, not equal bits
    assert abs(ram.cumulative_loss - oo.cumulative_loss) < 0.1
    from hivemall_tpu.frame.evaluation import auc
    assert auc(ds.labels, oo.predict_proba(ds)) > 0.9


def test_cli_trains_from_parquet_dir(tmp_path, capsys):
    from hivemall_tpu.cli.main import main
    ds = _ds(600, seed=5)
    write_parquet_shards(ds, str(tmp_path / "s"), rows_per_shard=200)
    model = str(tmp_path / "m.tsv")
    rc = main(["train", "--algo", "train_classifier",
               "--input", str(tmp_path / "s"),
               "--options", "-dims 1024 -mini_batch 64 -loss logloss "
                            "-opt adagrad -reg no",
               "--model", model])
    assert rc == 0
    out = capsys.readouterr().out
    assert '"examples": 600' in out
    assert sum(1 for _ in open(model)) > 10


def test_frame_arrow_interchange(tmp_path):
    from hivemall_tpu.frame.dataframe import Frame
    f = Frame({"features": [["1:1.0", "2:0.5"], ["3:2.0"]],
               "label": [1.0, -1.0]})
    p = str(tmp_path / "f.parquet")
    f.to_parquet(p)
    back = Frame.from_parquet(p)
    assert len(back) == 2
    assert list(back["label"]) == [1.0, -1.0]
    assert list(back["features"][0]) == ["1:1.0", "2:0.5"]
    # trains straight off the round-tripped frame (HivemallOps-style)
    model = back.train_classifier("features", "label",
                                  "-dims 64 -mini_batch 2 -loss logloss "
                                  "-opt adagrad -reg no")
    assert len(model) > 0


def test_frame_from_csv(tmp_path):
    from hivemall_tpu.frame.dataframe import Frame
    p = tmp_path / "t.csv"
    p.write_text("a,b\n1,x\n2,y\n")
    f = Frame.from_csv(str(p))
    assert list(f["a"]) == [1, 2]
    assert list(f["b"]) == ["x", "y"]


# -- ParquetStream.batches against the parent's double-copy loop --------------
# A frozen copy of what PR 27 replaced: every shard reordered into a whole
# copy (_take_rows) and then padded batch by batch. Nothing below calls the
# program's batch assembly, so it stays the oracle whatever that becomes.

def _frozen_take_rows(ds, rows):
    lens = np.diff(ds.indptr)[rows]
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    starts = ds.indptr[rows].astype(np.int64)
    total = int(indptr[-1])
    flat = (np.arange(total, dtype=np.int64)
            - np.repeat(indptr[:-1], lens)
            + np.repeat(starts, lens)) if total else np.zeros(0, np.int64)
    return SparseDataset(
        ds.indices[flat], indptr, ds.values[flat], ds.labels[rows],
        None if ds.fields is None else ds.fields[flat])


def _frozen_concat(a, b):
    fields = None
    if a.fields is not None and b.fields is not None:
        fields = np.concatenate([a.fields, b.fields])
    return SparseDataset(
        np.concatenate([a.indices, b.indices]),
        np.concatenate([a.indptr, b.indptr[1:] + a.indptr[-1]]),
        np.concatenate([a.values, b.values]),
        np.concatenate([a.labels, b.labels]), fields)


def _frozen_padded(ds, batch_size, L):
    """SparseDataset.batches(shuffle=False) of the parent: rows in order."""
    n = len(ds)
    lens = np.diff(ds.indptr).astype(np.int64)
    for s in range(0, n, batch_size):
        take = np.arange(n)[s: s + batch_size]
        nv = len(take)
        m = np.minimum(lens[take], L)
        pos = np.arange(L, dtype=np.int64)[None, :]
        keep = pos < m[:, None]
        flat = np.where(keep, ds.indptr[take][:, None] + pos, 0)
        idx = np.zeros((batch_size, L), np.int32)
        val = np.zeros((batch_size, L), np.float32)
        if len(ds.indices):
            idx[:nv] = np.where(keep, ds.indices[flat], 0)
            val[:nv] = np.where(keep, ds.values[flat], 0.0)
        fld = None
        if ds.fields is not None:
            fld = np.zeros((batch_size, L), np.int32)
            if len(ds.fields):
                fld[:nv] = np.where(keep, ds.fields[flat], 0)
        lab = np.zeros(batch_size, np.float32)
        lab[:nv] = ds.labels[take]
        yield idx, val, lab, fld, (nv if nv < batch_size else None)


def _frozen_stream_batches(stream, batch_size, *, epochs, shuffle, seed, L,
                           shard_lists=None):
    rng = np.random.default_rng(seed)
    for _ in range(epochs):
        order = rng.permutation(len(stream.files)) if shuffle \
            else np.arange(len(stream.files))
        if shard_lists is not None:
            shard_lists.append([stream.files[fi] for fi in order])
        carry = None
        for fi in order:
            ds = stream._shard(stream.files[fi])
            if carry is not None:
                ds = _frozen_concat(carry, ds)
                carry = None
            n = len(ds)
            n_full = (n // batch_size) * batch_size
            row_order = rng.permutation(n) if shuffle else np.arange(n)
            full = _frozen_take_rows(ds, row_order[:n_full])
            yield from _frozen_padded(full, batch_size, L)
            if n_full < n:
                carry = _frozen_take_rows(ds, row_order[n_full:])
        if carry is not None and len(carry):
            yield from _frozen_padded(carry, batch_size, L)


def _ragged_ds(n=500, with_fields=False, seed=3):
    """Rows 0..12 features long (empty rows too), ids and values distinct
    enough that a misplaced slot shows."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 13, n)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    nnz = int(indptr[-1])
    return SparseDataset(
        rng.integers(1, 1 << 20, nnz).astype(np.int32), indptr,
        rng.normal(0, 1, nnz).astype(np.float32),
        rng.normal(0, 1, n).astype(np.float32),
        rng.integers(0, 8, nnz).astype(np.int32) if with_fields else None)


def _assert_same_batches(got, want):
    assert len(got) == len(want)
    for k, (g, w) in enumerate(zip(got, want)):
        idx, val, lab, fld, nv = w
        for name, a, b in (("idx", g.idx, idx), ("val", g.val, val),
                           ("label", g.label, lab)):
            assert a.dtype == b.dtype and a.shape == b.shape, (k, name)
            assert a.tobytes() == b.tobytes(), (k, name)
        assert (g.field is None) == (fld is None), k
        if fld is not None:
            assert g.field.dtype == fld.dtype, k
            assert g.field.tobytes() == fld.tobytes(), (k, "field")
        assert g.n_valid == nv, (k, "n_valid")


# (rows a shard, batch): shards that are whole batches; shards that are not
# (the remainder rides into the next shard and, at the epoch's end, into one
# short padded batch); shards smaller than a batch (the remainder is carried
# across two shards before a batch fills)
_GEOMETRY = [(128, 64), (150, 64), (150, 400)]


@pytest.mark.parametrize("truncate", [False, True])
@pytest.mark.parametrize("cache", ["off", "warm"])
@pytest.mark.parametrize("decode_ahead", [0, 2])
@pytest.mark.parametrize("with_fields", [False, True])
@pytest.mark.parametrize("rows_per_shard,batch", _GEOMETRY)
@pytest.mark.parametrize("shuffle", [True, False])
def test_stream_batches_bit_identical_to_the_double_copy_loop(
        tmp_path, shuffle, rows_per_shard, batch, with_fields, decode_ahead,
        cache, truncate):
    d = str(tmp_path / "s")
    write_parquet_shards(_ragged_ds(with_fields=with_fields), d,
                         rows_per_shard=rows_per_shard)
    cache_dir = str(tmp_path / "cache") if cache == "warm" else None
    if cache_dir:                   # fill it: the run below reads it warm
        for _ in ParquetStream(d, cache_dir=cache_dir).batches(
                batch, shuffle=False):
            pass
    max_len = 5 if truncate else None
    stream = ParquetStream(d, decode_ahead=decode_ahead, cache_dir=cache_dir)
    got = list(stream.batches(batch, epochs=2, shuffle=shuffle, seed=1234,
                              max_len=max_len, truncate=truncate))
    ref = ParquetStream(d)
    want = list(_frozen_stream_batches(
        ref, batch, epochs=2, shuffle=shuffle, seed=1234,
        L=max_len or ref.max_row_len))
    _assert_same_batches(got, want)
    if rows_per_shard % batch:      # the epoch ends on a short padded batch
        assert got[-1].n_valid == (2 * 500 // 2) % batch


def _uniform_ds(n=500, r=6, with_fields=False, seed=5):
    """Every row exactly r long: the Criteo shape the benchmark writes."""
    rng = np.random.default_rng(seed)
    return SparseDataset(
        rng.integers(1, 1 << 20, n * r).astype(np.int32),
        np.arange(n + 1, dtype=np.int64) * r,
        rng.normal(0, 1, n * r).astype(np.float32),
        rng.normal(0, 1, n).astype(np.float32),
        rng.integers(0, 8, n * r).astype(np.int32) if with_fields else None)


# case: (dataset, rows a shard, batch, max_len, truncate, shard cache, and
# how a batch is gathered: whole rows out of [n, r] views of the shard's
# arrays where its rows are one length, else slot by slot)
_LAYOUT_CASES = {
    "uniform": (_uniform_ds, 128, 64, None, False, False, "view"),
    "uniform_truncate": (_uniform_ds, 128, 64, 4, True, False, "view"),
    "uniform_carry": (_uniform_ds, 150, 64, None, False, False, "view"),
    "uniform_fields": (lambda: _uniform_ds(with_fields=True), 150, 64, None,
                       False, False, "view"),
    "variable": (_ragged_ds, 128, 64, None, False, False, "slots"),
    "truncate": (_ragged_ds, 128, 64, 5, True, False, "slots"),
    "carry": (_ragged_ds, 150, 64, None, False, False, "slots"),
    "fields": (lambda: _ragged_ds(with_fields=True), 150, 64, None, False,
               False, "slots"),
    "cache": (_uniform_ds, 150, 64, 8, False, True, "view"),
    "cache_variable": (_ragged_ds, 150, 64, None, False, True, "slots"),
}

_CSR = {"indices", "indptr", "values", "labels", "fields"}


@pytest.mark.parametrize("case", list(_LAYOUT_CASES))
def test_batches_from_the_row_layout_match_a_per_slot_gather(tmp_path,
                                                            case):
    """Where a shard's rows are one length, a batch is whole rows out of
    [n, r] views of its decoded arrays; it is bit for bit what the per-slot
    gather over the CSR arrays gives (_frozen_padded above), for the stream
    and for SparseDataset.batches_in_order alike, and no shard keeps any
    array beside its CSR ones (a warm shard cache holds its shards for the
    stream's life)."""
    make, rows, batch, max_len, truncate, cache, layout = \
        _LAYOUT_CASES[case]
    ds = make()
    d = str(tmp_path / "s")
    write_parquet_shards(ds, d, rows_per_shard=rows)
    cache_dir = str(tmp_path / "cache") if cache else None
    if cache_dir:                   # fill it: the run below reads it warm
        for _ in ParquetStream(d, cache_dir=cache_dir).batches(
                batch, shuffle=False):
            pass
    stream = ParquetStream(d, cache_dir=cache_dir)
    L = max_len or stream.max_row_len
    got = list(stream.batches(batch, epochs=2, shuffle=True, seed=4321,
                              max_len=max_len, truncate=truncate))
    want = list(_frozen_stream_batches(ParquetStream(d), batch, epochs=2,
                                       shuffle=True, seed=4321, L=L))
    _assert_same_batches(got, want)
    if rows % batch:                # the carry, and a short last batch
        assert got[-1].n_valid == len(ds) % batch

    (shard,) = stream._iter_shards(stream.files[:1])
    grid = shard.rows2d(L)
    if layout == "slots":
        assert grid is None
    else:                           # views: no copy of the decoded arrays
        idx, val, fld = grid
        assert idx.shape == val.shape == (len(shard), min(L, 6))
        assert (fld is None) == (shard.fields is None)
        assert np.shares_memory(idx, shard.indices)
        assert np.shares_memory(val, shard.values)
        assert fld is None or np.shares_memory(fld, shard.fields)
        if cache:                   # a view of the mmapped cache file
            assert isinstance(shard.indices.base, np.memmap)
    if cache:                       # two epochs later, the memo holds
        memo = [m for _, m in stream._cache._memo.values()]  # CSR alone
        assert len(memo) == len(stream.files)
        for m in memo:
            extra = {k: v for k, v in vars(m).items() if k not in _CSR}
            assert not any(isinstance(v, (np.ndarray, tuple, list))
                           for v in extra.values()), sorted(extra)

    # in memory, in a shuffled order, through the same routine
    order = np.random.default_rng(8).permutation(len(ds))
    L = max_len or ds.max_row_len
    got = list(ds.batches_in_order(order, batch, L, truncate=truncate))
    _assert_same_batches(
        got, list(_frozen_padded(_frozen_take_rows(ds, order), batch, L)))
    assert (ds.rows2d(L) is None) == (layout == "slots")
    assert set(vars(ds)) == _CSR


def test_stream_rng_draws_unchanged_in_number_and_order(tmp_path,
                                                        monkeypatch):
    """One permutation of the files an epoch, one of the rows a shard (the
    carry included), nothing else, in that order: so the same seed gives
    the same file order in epoch 2 as the parent's loop drew."""
    d = str(tmp_path / "s")
    write_parquet_shards(_ragged_ds(), d, rows_per_shard=150)
    real = np.random.default_rng

    class Recorded:
        def __init__(self, seed, log):
            self._g, self._log = real(seed), log

        def __getattr__(self, name):
            fn = getattr(self._g, name)

            def call(*a, **k):
                self._log.append((name,) + tuple(int(x) for x in a))
                return fn(*a, **k)
            return call

    logs = []

    def recording_rng(seed):
        logs.append([])
        return Recorded(seed, logs[-1])

    monkeypatch.setattr(np.random, "default_rng", recording_rng)
    stream = ParquetStream(d)
    seen = []
    inner = stream._iter_shards
    monkeypatch.setattr(stream, "_iter_shards",
                        lambda files: seen.append(list(files))
                        or inner(files))
    got = list(stream.batches(64, epochs=2, shuffle=True, seed=77))
    want_lists = []
    want = list(_frozen_stream_batches(
        ParquetStream(d), 64, epochs=2, shuffle=True, seed=77,
        L=stream.max_row_len, shard_lists=want_lists))
    # (SparseDataset.batches makes a generator for the epoch's last short
    # batch and draws nothing from it)
    got_log, want_log = [log for log in logs if log]
    assert got_log == want_log
    # 500 rows in shards of 150,150,150,50: the sizes drawn show the carry
    assert [c[0] for c in got_log] == ["permutation"] * 10
    assert got_log[0] == got_log[5] == ("permutation", 4)
    assert sum(c[1] for c in got_log[1:5]) - 500 == \
        sum(c[1] % 64 for c in got_log[1:4])
    assert seen == want_lists and len(seen) == 2
    _assert_same_batches(got, want)
