"""Arrow-native columnar ingest: Parquet/CSV/Arrow -> SparseDataset, plus
out-of-core streaming epochs over sharded Parquet directories.

Reference analogs (SURVEY.md §1 "Arrow-native columnar runtime", §8 M0
"Arrow ingest + LIBSVM reader", §3.20 NioStatefulSegment -> "Arrow input
pipeline, memory-map shards"): the reference's engine feeds trainer UDTFs
rows from Hive/Spark columnar scans; here pyarrow record batches are the
scan, and a directory of Parquet shards plays the split-per-task input.
Criteo-1TB cannot be an in-RAM LIBSVM parse — ParquetStream re-reads
shards per epoch so the resident set is one shard, not the dataset.

Two supported schemas per table:
  string features — `features: list<string>` of "name:val"/"idx:val"
    ("field:idx:val" with ffm=True) + numeric label column. Names hash
    through the bit-exact murmur3 (utils.hashing.mhash_batch).
  pre-parsed CSR — `indices: list<int32>` + optional `values: list<float>`
    (+ `fields: list<int32>`) + label column: zero parse cost, the Criteo
    fast path.
"""

from __future__ import annotations

import os
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.trace import get_tracer
from .sparse import SparseBatch, SparseDataset

__all__ = ["read_parquet", "read_csv", "read_arrow", "table_to_dataset",
           "ParquetStream", "write_parquet_shards"]


def _parse_string_features(flat: np.ndarray, *, dims: Optional[int],
                           ffm: bool, num_fields: int
                           ) -> Tuple[np.ndarray, np.ndarray,
                                      Optional[np.ndarray]]:
    """Vectorized parse of flat feature strings.

    "name:val" (value defaults to 1) or, with ffm=True, "field:idx[:val]".
    Integer names pass through; non-integer names hash via murmur3 into
    [1, dims-1] (dims defaults to 2^24, the reference's feature_hashing
    default). np.char ops keep this C-speed; the per-string Python loop
    only runs for the non-integer residue."""
    from ..utils.hashing import mhash_batch

    u = flat.astype("U")
    if ffm:
        fld_s, _, rest = np.char.partition(u, ":").T[(0, 1, 2), :]
        name_s, _, val_s = np.char.partition(rest, ":").T[(0, 1, 2), :]
    else:
        # split on the LAST ':' so "ns:name:val" string names still parse
        name_s, _, val_s = np.char.rpartition(u, ":").T[(0, 1, 2), :]
        # bare "name" (no colon): rpartition puts it in the last slot
        bare = name_s == ""
        name_s = np.where(bare, val_s, name_s)
        val_s = np.where(bare, "1", val_s)
        fld_s = None
    val = np.where(val_s == "", "1", val_s).astype(np.float32)

    def ids_from(names: np.ndarray, space: int) -> np.ndarray:
        # only NON-NEGATIVE integer names pass through as direct indices;
        # anything else (including "-3") murmur-hashes into [1, space] —
        # negative gather indices would silently wrap to the table's end
        digits = np.char.isdigit(names)
        out = np.zeros(len(names), np.int64)
        if digits.any():
            out[digits] = names[digits].astype(np.int64)
        rest = ~digits
        if rest.any():
            out[rest] = mhash_batch([str(s) for s in names[rest]], space)
        return out

    idx = ids_from(name_s, (dims or (1 << 24)) - 1).astype(np.int32)
    fld = None
    if ffm:
        fld = (ids_from(fld_s, num_fields) % num_fields).astype(np.int32)
    return idx, val, fld


def table_to_dataset(table, *, feature_col: str = "features",
                     label_col: str = "label",
                     dims: Optional[int] = None, ffm: bool = False,
                     num_fields: int = 64) -> SparseDataset:
    """One pyarrow Table -> SparseDataset (schemas per module docstring)."""
    import pyarrow as pa
    names = set(table.column_names)
    labels = table.column(label_col).to_numpy(
        zero_copy_only=False).astype(np.float32)

    if "indices" in names:              # pre-parsed CSR fast path
        col = table.column("indices").combine_chunks()
        indices = col.flatten().to_numpy().astype(np.int32)
        indptr = col.offsets.to_numpy().astype(np.int64)
        if "values" in names:
            values = table.column("values").combine_chunks().flatten() \
                .to_numpy().astype(np.float32)
        else:
            values = np.ones(len(indices), np.float32)
        fields = None
        if "fields" in names:
            fields = table.column("fields").combine_chunks().flatten() \
                .to_numpy().astype(np.int32)
        return SparseDataset(indices, indptr, values, labels, fields)

    col = table.column(feature_col).combine_chunks()
    indptr = col.offsets.to_numpy().astype(np.int64)
    flat = col.flatten().to_numpy(zero_copy_only=False)
    if len(flat) and not isinstance(flat[0], str):
        # list<int> categorical ids, value 1.0
        indices = flat.astype(np.int32)
        return SparseDataset(indices, indptr,
                             np.ones(len(indices), np.float32), labels)
    idx, val, fld = _parse_string_features(
        np.asarray(flat, object), dims=dims, ffm=ffm, num_fields=num_fields)
    return SparseDataset(idx, indptr, val, labels, fld)


def _parquet_files(path: str) -> List[str]:
    if os.path.isdir(path):
        out = sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if f.endswith((".parquet", ".pq")))
        if not out:
            raise FileNotFoundError(f"no .parquet shards under {path}")
        return out
    if not os.path.exists(path):
        raise FileNotFoundError(f"parquet input not found: {path}")
    return [path]


def read_parquet(path: str, **kw) -> SparseDataset:
    """Read one Parquet file or a shard directory fully into RAM.
    For larger-than-RAM corpora use ParquetStream instead."""
    import pyarrow.parquet as pq
    import pyarrow as pa
    files = _parquet_files(path)
    ds = table_to_dataset(pa.concat_tables([pq.read_table(f)
                                            for f in files]), **kw)
    if len(files) == 1:
        # file identity for the packed shard cache (io.shard_cache):
        # mtime/size staleness discipline + the parse config (the same
        # bytes parsed differently are a different dataset)
        from .shard_cache import file_source_id
        sid = file_source_id(files[0], {"reader": "parquet", **kw})
        if sid:
            ds.source_id = sid
    return ds


def read_csv(path: str, *, feature_cols: Optional[Sequence[str]] = None,
             label_col: str = "label",
             dims: Optional[int] = None) -> SparseDataset:
    """CSV -> SparseDataset. With feature_cols=None every non-label column
    becomes a quantitative feature "col:value" (hashed name); explicit
    feature_cols restricts the set. The ftvec.trans quantitative_features
    analog at ingest level."""
    import pyarrow as pa
    from pyarrow import csv as pacsv
    from ..utils.hashing import mhash_batch
    table = pacsv.read_csv(path)

    def numeric(c):
        t = table.schema.field(c).type
        return (pa.types.is_integer(t) or pa.types.is_floating(t)
                or pa.types.is_boolean(t))     # bool casts cleanly to 0/1
    if feature_cols is not None:
        cols = list(feature_cols)
        bad = [c for c in cols if not numeric(c)]
        if bad:
            raise ValueError(
                f"non-numeric feature columns {bad}; encode them first "
                f"(e.g. ftvec categorical_features) or drop them")
    else:
        # id/name/text columns are common — only numeric columns become
        # quantitative features by default
        cols = [c for c in table.column_names
                if c != label_col and numeric(c)]
        if not cols:
            raise ValueError(
                f"no numeric feature columns in {path}; pass feature_cols")
    labels = table.column(label_col).to_numpy(
        zero_copy_only=False).astype(np.float32)
    n = len(labels)
    space = (dims or (1 << 24)) - 1
    ids = np.asarray(mhash_batch(cols, space), np.int32)
    mat = np.stack([table.column(c).to_numpy(zero_copy_only=False)
                    .astype(np.float32) for c in cols], axis=1)
    indices = np.tile(ids, n)
    values = mat.ravel()
    indptr = np.arange(0, n * len(cols) + 1, len(cols), dtype=np.int64)
    keep = values != 0                  # sparse semantics: drop zeros
    if not keep.all():
        counts = keep.reshape(n, len(cols)).sum(1)
        indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        indices, values = indices[keep], values[keep]
    return SparseDataset(indices, indptr, values, labels)


def read_arrow(path: str, **kw) -> SparseDataset:
    """Arrow IPC/feather file -> SparseDataset."""
    import pyarrow.feather as feather
    return table_to_dataset(feather.read_table(path), **kw)


def write_parquet_shards(ds: SparseDataset, out_dir: str, *,
                         rows_per_shard: int = 1 << 20) -> List[str]:
    """Spill a SparseDataset to a directory of CSR-schema Parquet shards
    (the inverse of ParquetStream; used to stage out-of-core corpora)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    n = len(ds)
    for s0 in range(0, n, rows_per_shard):
        s1 = min(n, s0 + rows_per_shard)
        lo, hi = ds.indptr[s0], ds.indptr[s1]
        off = (ds.indptr[s0:s1 + 1] - lo).astype(np.int32)
        cols = {
            "indices": pa.ListArray.from_arrays(
                off, pa.array(ds.indices[lo:hi], pa.int32())),
            "values": pa.ListArray.from_arrays(
                off, pa.array(ds.values[lo:hi], pa.float32())),
            "label": pa.array(ds.labels[s0:s1], pa.float32()),
        }
        if ds.fields is not None:
            cols["fields"] = pa.ListArray.from_arrays(
                off, pa.array(ds.fields[lo:hi], pa.int32()))
        path = os.path.join(out_dir, f"shard-{s0 // rows_per_shard:05d}"
                                     f".parquet")
        pq.write_table(pa.table(cols), path)
        paths.append(path)
    return paths


class ParquetStream:
    """Out-of-core epochs over a directory of Parquet shards.

    The NioStatefulSegment rebuild at corpus scale: every epoch re-reads
    the shards from disk (shard order shuffled per epoch, rows shuffled
    within each shard) and yields fixed-shape padded SparseBatches; resident
    memory is one shard + one carry-over remainder, never the corpus.
    Feed the result to ``LearnerBase.fit_stream``.
    """

    def __init__(self, path: str, *, feature_col: str = "features",
                 label_col: str = "label", dims: Optional[int] = None,
                 ffm: bool = False, num_fields: int = 64,
                 decode_ahead: int = 1, cache_dir: Optional[str] = None):
        self.files = _parquet_files(path)
        self._kw = dict(feature_col=feature_col, label_col=label_col,
                        dims=dims, ffm=ffm, num_fields=num_fields)
        # decode-ahead: while training consumes the current shard's batches,
        # a reader thread decodes the NEXT decode_ahead shards (Parquet
        # read + string parse + hashing — pyarrow releases the GIL on the
        # IO/decode legs). 0 restores the synchronous per-shard re-read.
        self.decode_ahead = max(0, int(decode_ahead))
        # per-shard decoded-CSR cache (io.shard_cache.ShardDecodeCache):
        # the first decode of each (shard mtime/size, parse config) also
        # persists the parsed columns, so epoch >= 2 and RESTARTS mmap
        # them instead of re-paying Parquet read + string parse + murmur
        # hashing — the string-parse-heavy leg of the streaming wall
        # (docs/PERFORMANCE.md "Shard cache"). None = off.
        self._cache = None
        if cache_dir:
            from .shard_cache import ShardDecodeCache
            self._cache = ShardDecodeCache(cache_dir, self._kw)
        from .pipeline import PipelineStats
        self.stats = PipelineStats(pool="decode-ahead",
                                   workers=self.decode_ahead)

    def _shard(self, path: str) -> SparseDataset:
        import pyarrow.parquet as pq
        if self._cache is not None:
            ds = self._cache.load(path)
            if ds is not None:
                return ds
        ds = table_to_dataset(pq.read_table(path), **self._kw)
        if self._cache is not None:
            self._cache.store(path, ds)
        return ds

    def _iter_shards(self, files: List[str]) -> Iterator[SparseDataset]:
        """Yield decoded shards in order, reading up to ``decode_ahead``
        shards beyond the one being consumed. Row-shuffle rng calls stay in
        the CONSUMING loop, so shuffled epochs are bit-identical to the
        synchronous path — only the disk read/parse moves off it."""
        import time as _time
        if self.decode_ahead <= 0:
            for f in files:
                t0 = _time.perf_counter()
                with get_tracer().span("source.wait_shard"):
                    ds = self._shard(f)     # synchronous: the wait IS the decode
                self.stats.add(prep_seconds=_time.perf_counter() - t0,
                               batches_prepared=1)
                yield ds
            return
        import concurrent.futures as cf
        tracer = get_tracer()
        ex = cf.ThreadPoolExecutor(max_workers=1,
                                   thread_name_prefix="pq-decode")
        try:
            import itertools
            from collections import deque
            pending = deque()
            it = iter(files)

            def timed_shard(f):
                # source.decode and stats.prep_seconds: the same seconds
                with tracer.span("source.decode") as sp:
                    t0 = _time.perf_counter()
                    ds = self._shard(f)
                    dt = _time.perf_counter() - t0
                    if sp is not None:
                        sp.args = {"rows": len(ds)}
                self.stats.add(prep_seconds=dt, batches_prepared=1)
                return ds

            # prime exactly decode_ahead futures: with the shard the
            # consumer holds, at most decode_ahead decoded shards sit in
            # ``pending`` — the memory bound the docs promise
            for f in itertools.islice(it, self.decode_ahead):
                pending.append(ex.submit(timed_shard, f))
            while pending:
                t0 = _time.perf_counter()
                with get_tracer().span("source.wait_shard"):
                    ds = pending.popleft().result()
                self.stats.add(prep_wait_seconds=_time.perf_counter() - t0)
                nxt = next(it, None)
                if nxt is not None:
                    pending.append(ex.submit(timed_shard, nxt))
                yield ds
        finally:
            for p in pending:
                p.cancel()
            ex.shutdown(wait=False)

    def __len__(self) -> int:
        import pyarrow.parquet as pq
        return sum(pq.ParquetFile(f).metadata.num_rows for f in self.files)

    @property
    def max_row_len(self) -> int:
        """Longest row across shards, from the list column's OFFSETS only —
        no string parse, no hashing, one column read per shard. With the
        decode cache on, cached shards answer from a header-only read, so
        a fully warm traversal never opens the source Parquet bytes at
        all."""
        import pyarrow.parquet as pq
        m = 1
        for f in self.files:
            if self._cache is not None:
                hint = self._cache.max_row_len_hint(f)
                if hint is not None:
                    m = max(m, hint)
                    continue
            pf = pq.ParquetFile(f)
            col = "indices" if "indices" in pf.schema_arrow.names \
                else self._kw["feature_col"]
            t = pq.read_table(f, columns=[col])
            arr = t.column(col).combine_chunks()
            m = max(m, int(np.diff(arr.offsets.to_numpy()).max(initial=1)))
        return m

    def batches(self, batch_size: int, *, epochs: int = 1,
                shuffle: bool = True, seed: int = 42,
                max_len: Optional[int] = None,
                truncate: bool = False) -> Iterator[SparseBatch]:
        # fresh decode counters per stream traversal: a caller that fits
        # repeatedly reads a per-call snapshot, not a lifetime
        # accumulation masquerading as one run's decode cost
        from .pipeline import PipelineStats
        self.stats = PipelineStats(pool="decode-ahead",
                                   workers=self.decode_ahead)
        L = max_len or self.max_row_len
        rng = np.random.default_rng(seed)
        # source.assemble: one span per batch yielded, carrying the batch's
        # ordinal in this traversal. It covers the work (the batch's row
        # gather and padding in SparseDataset.batches_in_order; for a
        # shard's FIRST batch also the carry's concat, the shard's
        # permutation and the copy of its remainder, under a batch of rows
        # each), never the time this generator sits suspended at `yield`
        # nor the wait for a decoded shard (source.wait_shard, in
        # _iter_shards). A shard is gathered ONCE: its batches come
        # straight from the decoded CSR arrays in shuffled row order, with
        # no reordered copy of the shard in between. The rng calls keep
        # their number and order, so a seed's batches never change.
        tracer = get_tracer()
        n_out = 0
        for ep in range(epochs):
            order = rng.permutation(len(self.files)) if shuffle \
                else np.arange(len(self.files))
            carry: Optional[SparseDataset] = None
            for ds in self._iter_shards([self.files[fi] for fi in order]):
                with tracer.span("source.assemble", None, n_out):
                    if carry is not None:
                        ds = _concat_datasets(carry, ds)
                        carry = None
                    n = len(ds)
                    n_batches = n // batch_size
                    n_full = n_batches * batch_size
                    row_order = rng.permutation(n) if shuffle \
                        else np.arange(n)
                    if n_full < n:      # remainder rows roll into next shard
                        carry = _take_rows(ds, row_order[n_full:])
                    it = ds.batches_in_order(row_order[:n_full], batch_size,
                                             L, truncate=truncate)
                    b = next(it) if n_batches else None
                for i in range(n_batches):
                    if i:
                        with tracer.span("source.assemble", None, n_out):
                            b = next(it)
                    yield b
                    n_out += 1
            if carry is not None and len(carry):
                # the remainder is always short of a batch: one padded one
                with tracer.span("source.assemble", None, n_out):
                    b = next(carry.batches(batch_size, shuffle=False,
                                           max_len=L, truncate=truncate))
                yield b
                n_out += 1


def _take_rows(ds: SparseDataset, rows: np.ndarray) -> SparseDataset:
    lens = np.diff(ds.indptr)[rows]
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    # gather the CSR payload of the selected rows in one vectorized fancy
    # index: position j of the output maps to start[row(j)] + (j - out_off)
    starts = ds.indptr[rows].astype(np.int64)
    total = int(indptr[-1])
    flat = (np.arange(total, dtype=np.int64)
            - np.repeat(indptr[:-1], lens)
            + np.repeat(starts, lens)) if total else np.zeros(0, np.int64)
    return SparseDataset(
        ds.indices[flat], indptr, ds.values[flat], ds.labels[rows],
        None if ds.fields is None else ds.fields[flat])


def _concat_datasets(a: SparseDataset, b: SparseDataset) -> SparseDataset:
    fields = None
    if a.fields is not None and b.fields is not None:
        fields = np.concatenate([a.fields, b.fields])
    return SparseDataset(
        np.concatenate([a.indices, b.indices]),
        np.concatenate([a.indptr, b.indptr[1:] + a.indptr[-1]]),
        np.concatenate([a.values, b.values]),
        np.concatenate([a.labels, b.labels]), fields)
