"""Ahead-of-time packed shard cache — persist prepared batches on disk.

Why (docs/PERFORMANCE.md "Shard cache"): the host leg — string parse ->
canonicalize -> pack — otherwise re-runs as (mostly) single-parser Python
every epoch and every restart, and the device waits on it. The reference
never met this wall (Hadoop re-ran the scan per
query but amortized it across mappers); the TPU-native analog is a
device-feeding data service where the host leg runs ONCE: after a shard is
parsed/canonicalized/packed the first time, the prepared bytes persist and
every later traversal mmaps them.

Two cache kinds share one container format (digest-keyed header + raw
array payload, written tmp -> fsync -> ``os.replace`` — the
io/checkpoint.py atomicity discipline):

:class:`PackedShardCache` — the fit()-path cache. Stores each dataset
  ROW's canonical unit-value field-major record (3-byte little-endian idx
  lanes at the shard's max canonical width + the 4 f32 label bytes +
  a per-row same-field multiplicity byte), keyed by (source identity,
  prep-config digest). Row-level storage is what makes SHUFFLED warm
  epochs bit-exact: an epoch is one permutation gather over the mmap'd
  record matrix re-sliced into ``io.sparse.PackedBatch`` buffers — the
  same bytes ``pack_unit_fieldmajor`` would have produced, so the loss
  trajectory reproduces the streamed path exactly (tests/test_shard_cache
  pins it at ``-steps_per_dispatch`` 1 and 8). Parse, canonicalize and
  pack never run on a warm epoch.

:class:`ShardDecodeCache` — the ParquetStream cache. Stores one decoded
  shard's CSR arrays (post parse + murmur hash), keyed by (shard file
  mtime/size, parse-config digest), so epoch >= 2 and restarts of the
  out-of-core path mmap the columns instead of re-reading + re-parsing
  the Parquet bytes.

Invalidation: the header carries the source identity (file mtime_ns/size,
or the dataset content sha256 when the source is RAM-only), the
prep-config digest, and a sha256 over the payload. A mutated source, a
changed prep config, or a corrupted/truncated cache file all read as a
MISS — the caller falls back to live prep and rewrites the cache
atomically. Counters (hits/misses/invalid/rebuilds/bytes) are one obs
registry section (``ingest_cache``), visible via ``/snapshot`` and
``/metrics``.

``python -m hivemall_tpu.io.shard_cache --smoke`` runs the seconds-scale
end-to-end check run_tests.sh wires in.
"""

from __future__ import annotations

import hashlib
import json
import os
import struct
import threading
import time
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from ..obs.trace import get_tracer
from .sparse import PackedBatch, SparseDataset, pow2_len

__all__ = ["PackedShardCache", "CachedPackedShard", "ShardDecodeCache",
           "CacheInvalid", "write_cache_file", "read_cache_file",
           "counters", "file_source_id"]

_MAGIC = b"HMTSC001"
_FORMAT = 1


class CacheInvalid(ValueError):
    """A cache file failed validation (magic/truncation/digest)."""


# --- obs counters (registry section `ingest_cache`) -------------------------

class _Counters:
    """Process-wide cache counters; provider contract: cheap, JSON-ready."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with getattr(self, "_lock", threading.Lock()):
            self.hits = 0
            self.misses = 0
            self.invalid = 0          # digest/magic/truncation failures
            self.rebuilds = 0         # cache files (re)written
            self.build_failed = 0     # builds aborted (uncacheable stream)
            self.bytes_mmapped = 0    # payload bytes opened for mmap reads
            self.bytes_written = 0

    def add(self, **kw: int) -> None:
        with self._lock:
            for k, v in kw.items():
                setattr(self, k, getattr(self, k) + v)

    def as_dict(self) -> dict:
        # canonicalizer status rides here (the ingest-path native surface):
        # report ONLY already-resolved state — a registry provider must
        # never trigger the first-use g++ build from a scrape thread
        from ..utils import native as _n
        lib = _n._LIB
        canon = ("native" if lib is not None
                 else ("python" if _n._TRIED else "unresolved"))
        with self._lock:
            return {
                "configured": True,
                "hits": self.hits,
                "misses": self.misses,
                "invalid": self.invalid,
                "rebuilds": self.rebuilds,
                "build_failed": self.build_failed,
                "bytes_mmapped": self.bytes_mmapped,
                "bytes_written": self.bytes_written,
                "canonicalizer": canon,
            }


counters = _Counters()

from ..obs.registry import registry as _registry  # noqa: E402

_registry.register("ingest_cache", counters.as_dict)


# --- container format -------------------------------------------------------

def _cfg_hash(cfg: dict) -> str:
    """Digest of a prep/parse config dict (sorted-key JSON, sha256)."""
    return hashlib.sha256(
        json.dumps(cfg, sort_keys=True, default=str).encode()).hexdigest()


def file_source_id(path: str, parse_cfg: Optional[dict] = None
                   ) -> Optional[str]:
    """mtime/size identity of a source file — the same staleness contract
    make uses; None when the file cannot be stat'ed. ``parse_cfg`` (the
    reader's own options: feature/label columns, zero_based, ffm, ...)
    folds into the identity, because the same bytes parsed differently
    yield a DIFFERENT dataset — without it the packed cache would serve
    one parse's records for another's key."""
    try:
        st = os.stat(path)
    except OSError:
        return None
    base = os.path.abspath(path)
    if parse_cfg:
        # parse hash rides BEFORE the volatile mtime/size fields so the
        # stable filename key (everything but the last two fields) keeps
        # one cache file per (path, parse config) that a mutation
        # invalidates IN PLACE
        base += f":parse={_cfg_hash(parse_cfg)[:16]}"
    return f"{base}:{st.st_mtime_ns}:{st.st_size}"


def write_cache_file(path: str, header: dict,
                     arrays: Dict[str, np.ndarray]) -> int:
    """Write one cache file atomically: magic | header-len | JSON header |
    raw array payload. The header carries per-array dtype/shape/offset and
    a sha256 over the payload; the write is tmp -> fsync -> ``os.replace``
    (+ best-effort directory fsync), the io/checkpoint.py idiom — a crash
    mid-write can never publish a torn cache. Returns payload bytes."""
    specs = {}
    blobs = []
    off = 0
    digest = hashlib.sha256()
    for name, a in arrays.items():
        a = np.ascontiguousarray(a)
        specs[name] = {"dtype": a.dtype.str, "shape": list(a.shape),
                      "offset": off}
        off += int(a.nbytes)
        blobs.append(a)
        if a.nbytes:        # memoryview.cast rejects zero-size shapes
            digest.update(memoryview(a).cast("B"))
    header = dict(header, format=_FORMAT, arrays=specs,
                  payload_bytes=off, payload_sha256=digest.hexdigest())
    hb = json.dumps(header, sort_keys=True, default=str).encode()
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "wb") as f:
            f.write(_MAGIC)
            f.write(struct.pack("<Q", len(hb)))
            f.write(hb)
            for a in blobs:
                if a.nbytes:
                    f.write(memoryview(a).cast("B"))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    try:
        dfd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass
    counters.add(rebuilds=1, bytes_written=off)
    return off


def read_cache_file(path: str) -> Tuple[dict, Dict[str, np.ndarray]]:
    """Open + validate one cache file; returns (header, name -> mmap view).

    Validation before any view is handed out: magic, header parse, exact
    file length (quick truncation check), then a streaming sha256 over the
    payload region against the header digest — a bit-flipped or torn cache
    can never silently feed the trainer. Raises :class:`CacheInvalid`."""
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        if f.read(8) != _MAGIC:
            raise CacheInvalid(f"{path}: bad magic")
        raw = f.read(8)
        if len(raw) != 8:
            raise CacheInvalid(f"{path}: truncated header length")
        (hlen,) = struct.unpack("<Q", raw)
        if hlen > (1 << 26):
            raise CacheInvalid(f"{path}: implausible header length {hlen}")
        hb = f.read(hlen)
        if len(hb) != hlen:
            raise CacheInvalid(f"{path}: truncated header")
        try:
            header = json.loads(hb)
        except ValueError as e:
            raise CacheInvalid(f"{path}: header parse failed: {e}") from e
        base = 16 + hlen
        if size != base + int(header.get("payload_bytes", -1)):
            raise CacheInvalid(
                f"{path}: payload truncated ({size} bytes, expected "
                f"{base + int(header.get('payload_bytes', -1))})")
        digest = hashlib.sha256()
        f.seek(base)
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            digest.update(chunk)
        if digest.hexdigest() != header.get("payload_sha256"):
            raise CacheInvalid(f"{path}: payload digest mismatch — file "
                               f"corrupted; falling back to live prep")
        # map from the SAME open file object the digest pass validated —
        # re-opening by name would race a concurrent atomic rewrite
        # (os.replace swaps the inode) and serve unvalidated bytes at this
        # header's stale offsets; the mapping outlives the handle
        views = {}
        for name, s in header["arrays"].items():
            shape = tuple(s["shape"])
            dtype = np.dtype(s["dtype"])
            if int(np.prod(shape)) == 0:    # mmap rejects empty mappings
                views[name] = np.empty(shape, dtype)
            else:
                views[name] = np.memmap(f, mode="r", dtype=dtype,
                                        shape=shape,
                                        offset=base + s["offset"])
    counters.add(bytes_mmapped=int(header["payload_bytes"]))
    return header, views


def read_cache_header(path: str) -> Optional[dict]:
    """Header-only read (no payload digest pass) for cheap METADATA hints
    (e.g. a shard's max row length). Returns None on any failure. Never
    use this to admit payload bytes — that is :func:`read_cache_file`'s
    job."""
    try:
        with open(path, "rb") as f:
            if f.read(8) != _MAGIC:
                return None
            raw = f.read(8)
            if len(raw) != 8:
                return None
            (hlen,) = struct.unpack("<Q", raw)
            if hlen > (1 << 26):
                return None
            hb = f.read(hlen)
            if len(hb) != hlen:
                return None
            return json.loads(hb)
    except (OSError, ValueError):
        return None


# --- the fit()-path packed row-record cache ---------------------------------

def _dataset_source(ds: SparseDataset) -> Tuple[dict, str]:
    """(identity dict for the header, stable key for the filename).

    A file-backed dataset (readers attach ``source_id`` = path:mtime:size)
    keys on the PATH and validates mtime/size from the header, so a
    mutated source invalidates in place and the rewrite replaces the stale
    file; a RAM-only dataset keys on its content sha256 (identity and
    validity coincide)."""
    sid = getattr(ds, "source_id", None)
    if sid:
        return {"source_id": sid}, sid.rsplit(":", 2)[0]
    ck = ds.content_key()
    return {"content_sha256": ck}, ck


def _row_field_mults(ds: SparseDataset, F: int) -> Optional[np.ndarray]:
    """Per-row max same-field multiplicity over LIVE (val != 0) features —
    the m each row needs in the canonical field-major layout. int64 [n];
    None when the dataset has no field ids."""
    if ds.fields is None:
        return None
    n = len(ds)
    m_row = np.zeros(n, np.int64)
    live = ds.values != 0
    if live.any():
        rows = np.repeat(np.arange(n, dtype=np.int64),
                         np.diff(ds.indptr).astype(np.int64))
        keys = rows[live] * F + (ds.fields[live].astype(np.int64) % F)
        uniq, cnt = np.unique(keys, return_counts=True)
        np.maximum.at(m_row, uniq // F, cnt)
    return m_row


class CachedPackedShard:
    """One validated, mmap-opened packed shard: the record matrix
    [n, m_cap*F*3 + 4] (3-byte idx lanes + f32 label bytes per row) plus
    the per-row multiplicity vector. :meth:`batches` re-slices any row
    permutation into the exact ``PackedBatch`` buffers the streamed path
    would have packed."""

    def __init__(self, header: dict, records: np.ndarray,
                 m_row: np.ndarray):
        self.header = header
        self.records = records
        self.m_row = np.asarray(m_row)        # small; pull off the mmap
        self.F = int(header["F"])
        self.m_cap = int(header["m_cap"])
        self.n_rows = int(header["n_rows"])

    def batches(self, batch_size: int, order: np.ndarray, *, stats=None,
                pad_rows=None) -> Iterator[PackedBatch]:
        """Yield the epoch's PackedBatches for ``order`` (a permutation or
        arange over the dataset rows). Per batch: gather the records, pick
        the batch's canonical width from the rows' multiplicities (exactly
        how ``canonicalize_fieldmajor`` sizes the streamed batch), and lay
        the lanes/labels out as ``pack_unit_fieldmajor`` does. ``pad_rows``
        maps the logical batch size to the allocated row count (the parts
        layout's kernel-grid row padding); identity otherwise."""
        F, Lcap3 = self.F, self.m_cap * self.F * 3
        bs = int(batch_size)
        B = int(pad_rows(bs)) if pad_rows is not None else bs
        tracer = get_tracer()
        for s in range(0, len(order), bs):
            t0 = time.perf_counter()
            with tracer.span("ingest.cache"):
                take = order[s:s + bs]
                nv = len(take)
                m_b = pow2_len(max(1, int(self.m_row[take].max(initial=0))))
                Lb = m_b * F
                recs = self.records[take]             # mmap gather -> RAM
                idxp = np.zeros((B, Lb * 3), np.uint8)
                idxp[:nv] = recs[:, :Lb * 3]
                labp = np.zeros((B, 4), np.uint8)
                labp[:nv] = recs[:, Lcap3:]
                buf = np.concatenate([idxp.reshape(-1), labp.reshape(-1)])
            if stats is not None:
                stats.add(cache_assemble_seconds=time.perf_counter() - t0,
                          cache_batches=1)
            yield PackedBatch(buf, B, Lb,
                              n_valid=nv if nv < B else None)


class PackedShardWriter:
    """Collects one cold epoch's prepared PackedBatches into the row-record
    matrix (scattered to DATASET row positions via each batch's ``take``
    indices, so the build epoch may be shuffled) and publishes atomically
    on :meth:`commit`. Any batch that is not a PackedBatch, or whose
    canonical width disagrees with the per-row multiplicities, aborts the
    build — the cache only ever admits streams it can replay bit-exactly
    (fail-open: the caller just keeps streaming live)."""

    def __init__(self, cache: "PackedShardCache", ds: SparseDataset,
                 m_row: np.ndarray):
        self._cache = cache
        self._source, self._key = _dataset_source(ds)
        self.F = cache.F
        self.m_row = m_row
        self.m_cap = pow2_len(max(1, int(m_row.max(initial=0))))
        self.n = len(ds)
        self._rec = np.zeros((self.n, self.m_cap * self.F * 3 + 4), np.uint8)
        self._filled = 0
        self.ok = True

    def add(self, batch, take: np.ndarray) -> None:
        if not self.ok:
            return
        if not isinstance(batch, PackedBatch) \
                or not isinstance(batch.buf, np.ndarray):
            self.ok = False
            return
        nv = len(take)
        expect_L = pow2_len(max(1, int(self.m_row[take].max(initial=0)))) \
            * self.F
        if batch.L != expect_L or batch.L * 3 > self._rec.shape[1] - 4 \
                or (batch.n_valid or batch.B) < nv:
            self.ok = False               # prep drifted from the row model
            return
        lanes = batch.buf[:batch.B * batch.L * 3].reshape(batch.B,
                                                          batch.L * 3)
        labs = batch.buf[batch.B * batch.L * 3:].reshape(batch.B, 4)
        self._rec[take, :batch.L * 3] = lanes[:nv]
        self._rec[take, self.m_cap * self.F * 3:] = labs[:nv]
        self._filled += nv

    def commit(self) -> Optional[CachedPackedShard]:
        """Publish the cache file (tmp -> fsync -> replace) and reopen it
        mmap'd; None when the build aborted or did not cover every row."""
        if not self.ok or self._filled != self.n:
            counters.add(build_failed=1)
            return None
        path = self._cache._path_for(self._key)
        header = {"kind": "packed_rows", "prep_hash": self._cache.prep_hash,
                  "prep_config": self._cache.prep_cfg,
                  "source": self._source, "n_rows": self.n, "F": self.F,
                  "m_cap": self.m_cap}
        write_cache_file(path, header,
                         {"records": self._rec,
                          "m_row": np.minimum(self.m_row, 255)
                          .astype(np.uint8)})
        self._rec = None                  # free the RAM copy; serve mmap'd
        try:
            hdr, views = read_cache_file(path)
        except (CacheInvalid, OSError):
            return None
        return CachedPackedShard(hdr, views["records"], views["m_row"])


class PackedShardCache:
    """The fit()-path cache front end for one (cache dir, prep config)."""

    MAX_M = 4      # canonicalize_fieldmajor's max_m — rows past it never pack

    def __init__(self, cache_dir: str, prep_cfg: dict, *, F: int,
                 name: str = "shard"):
        self.dir = cache_dir
        self.prep_cfg = dict(prep_cfg)
        self.prep_hash = _cfg_hash(self.prep_cfg)
        self.F = int(F)
        self.name = name

    def _path_for(self, source_key: str) -> str:
        key = hashlib.sha256(
            (self.prep_hash + "\0" + source_key).encode()).hexdigest()
        return os.path.join(self.dir, f"{self.name}-{key[:20]}.pack")

    def load(self, ds: SparseDataset) -> Optional[CachedPackedShard]:
        """Open the cached shard for ``ds``, or None (miss). Stale identity
        (source mutated), prep-config drift, wrong row count, and corrupt
        files all miss; corrupt additionally counts ``invalid``."""
        source, key = _dataset_source(ds)
        path = self._path_for(key)
        if not os.path.exists(path):
            counters.add(misses=1)
            return None
        try:
            header, views = read_cache_file(path)
        except (CacheInvalid, OSError):
            counters.add(invalid=1, misses=1)
            return None
        if (header.get("kind") != "packed_rows"
                or header.get("prep_hash") != self.prep_hash
                or header.get("source") != source
                or int(header.get("n_rows", -1)) != len(ds)
                or int(header.get("F", -1)) != self.F):
            counters.add(misses=1)
            return None
        counters.add(hits=1)
        return CachedPackedShard(header, views["records"], views["m_row"])

    def writer(self, ds: SparseDataset) -> Optional[PackedShardWriter]:
        """A build-epoch writer, or None when the dataset can never cache
        (no field ids, or a row's same-field multiplicity exceeds the
        canonicalizer's max_m — such rows fall back to the pairs path)."""
        m_row = _row_field_mults(ds, self.F)
        if m_row is None or (len(m_row)
                             and int(m_row.max(initial=0)) > self.MAX_M):
            return None
        return PackedShardWriter(self, ds, m_row)


# --- the ParquetStream decoded-shard cache ----------------------------------

class ShardDecodeCache:
    """Per-shard decoded CSR cache for the out-of-core Parquet path.

    Keyed by (shard file path, parse config digest) and validated against
    the shard's mtime_ns/size + the payload sha256: epoch >= 2 and
    restarts skip the Parquet read + string parse + murmur hashing and
    mmap the columns instead (``SparseDataset`` over memmap views — the
    downstream pad/canonicalize/pack consumers are unchanged)."""

    def __init__(self, cache_dir: str, parse_cfg: dict):
        self.dir = cache_dir
        self.parse_cfg = dict(parse_cfg)
        self.hash = _cfg_hash({"kind": "csr_shard", **self.parse_cfg})
        # validated shards memoized per (path -> (source_id, dataset)):
        # the digest pass streams the whole payload, so re-validating
        # every epoch would re-read all cached bytes — exactly the I/O
        # warm epochs exist to skip. A source mutation changes the
        # source_id and drops the memo entry.
        self._memo: Dict[str, Tuple[str, SparseDataset]] = {}

    def _path_for(self, shard_path: str) -> str:
        key = hashlib.sha256(
            (self.hash + "\0" + os.path.abspath(shard_path)).encode()
        ).hexdigest()
        return os.path.join(self.dir, f"pq-{key[:20]}.csr")

    def load(self, shard_path: str) -> Optional[SparseDataset]:
        sid = file_source_id(shard_path)
        memo = self._memo.get(shard_path)
        if memo is not None and sid is not None and memo[0] == sid:
            counters.add(hits=1)
            return memo[1]
        path = self._path_for(shard_path)
        if sid is None or not os.path.exists(path):
            counters.add(misses=1)
            return None
        try:
            header, views = read_cache_file(path)
        except (CacheInvalid, OSError):
            counters.add(invalid=1, misses=1)
            return None
        if header.get("kind") != "csr_shard" \
                or header.get("source", {}).get("source_id") != sid:
            counters.add(misses=1)
            return None
        counters.add(hits=1)
        ds = SparseDataset(views["indices"], views["indptr"],
                           views["values"], views["labels"],
                           views.get("fields"))
        ds.source_id = sid
        self._memo[shard_path] = (sid, ds)
        return ds

    def max_row_len_hint(self, shard_path: str) -> Optional[int]:
        """Cached shard's max row length from a header-only read, or None.
        Lets ParquetStream size its padded batches without touching the
        source Parquet bytes on warm traversals; validated against the
        shard's current mtime/size (the metadata is right whenever the
        source is unchanged, independent of payload health)."""
        sid = file_source_id(shard_path)
        header = read_cache_header(self._path_for(shard_path))
        if (sid is None or header is None
                or header.get("kind") != "csr_shard"
                or header.get("source", {}).get("source_id") != sid):
            return None
        mrl = header.get("max_row_len")
        return int(mrl) if mrl is not None else None

    def store(self, shard_path: str, ds: SparseDataset) -> None:
        sid = file_source_id(shard_path)
        if sid is None:
            return
        arrays = {"indices": ds.indices, "indptr": ds.indptr,
                  "values": ds.values, "labels": ds.labels}
        if ds.fields is not None:
            arrays["fields"] = ds.fields
        write_cache_file(self._path_for(shard_path),
                         {"kind": "csr_shard", "parse_config": self.parse_cfg,
                          "source": {"source_id": sid},
                          "max_row_len": ds.max_row_len}, arrays)


# --- run_tests.sh smoke -----------------------------------------------------

def _smoke() -> int:                      # pragma: no cover - exercised by sh
    """Seconds-scale end-to-end check (run_tests.sh): build the packed
    cache cold, bit-match a warm restart's loss trajectory, prove the warm
    epoch never re-reads the source (serve after source-content mutation
    with preserved mtime/size), and exercise the Parquet decode cache."""
    import shutil
    import sys
    import tempfile

    from ..models.fm import FFMTrainer

    tmp = tempfile.mkdtemp(prefix="hmt_shard_cache_smoke_")
    failures = 0

    def check(name, cond):
        nonlocal failures
        print(f"shard-cache smoke {name}: {'OK' if cond else 'FAILED'}",
              file=sys.stderr)
        if not cond:
            failures += 1

    try:
        rng = np.random.default_rng(5)
        n, L, F, dims = 1024, 8, 8, 1 << 12
        idx = rng.integers(1, dims, (n, L)).astype(np.int32)
        fld = np.tile(np.arange(L, dtype=np.int32) % F, (n, 1))
        lab = (rng.integers(0, 2, n) * 2 - 1).astype(np.float32)
        ds = SparseDataset(idx.ravel(),
                           np.arange(0, n * L + 1, L, dtype=np.int64),
                           np.ones(n * L, np.float32), lab, fld.ravel())
        cfg = (f"-dims {dims} -factors 2 -fields {F} -mini_batch 128 "
               f"-classification -pack_input on "
               f"-shard_cache_dir {tmp}/cache")
        cold = FFMTrainer(cfg)
        cold._trace_losses = []
        cold.fit(ds, epochs=2, shuffle=True)
        packs = [f for f in os.listdir(f"{tmp}/cache")
                 if f.endswith(".pack")]
        check("cold build wrote a cache file", len(packs) == 1)
        warm = FFMTrainer(cfg)
        warm._trace_losses = []
        warm.fit(ds, epochs=2, shuffle=True)
        check("warm restart bit-matches cold trajectory",
              np.array_equal(np.asarray(cold._trace_losses),
                             np.asarray(warm._trace_losses)))
        check("warm epochs ran zero live prep",
              warm.pipeline_stats.batches_prepared == 0
              and warm.pipeline_stats.cache_batches > 0)
        snap = counters.as_dict()
        check("obs counters populated",
              snap["hits"] >= 1 and snap["rebuilds"] >= 1
              and snap["bytes_mmapped"] > 0)

        # Parquet decode cache: build, then corrupt the SOURCE content
        # while preserving mtime/size — a warm traversal must still serve
        # the original bytes (proof the mmap'd cache, not the source, is
        # what epoch >= 2 reads).
        from .arrow import ParquetStream, write_parquet_shards
        pq_dir = f"{tmp}/pq"
        write_parquet_shards(ds, pq_dir, rows_per_shard=256)
        stream = ParquetStream(pq_dir, cache_dir=f"{tmp}/cache")
        ref = [b.idx.copy() for b in stream.batches(128, shuffle=False)]
        shard0 = sorted(os.path.join(pq_dir, f) for f in os.listdir(pq_dir)
                        if f.endswith(".parquet"))[0]
        st = os.stat(shard0)
        with open(shard0, "r+b") as f:      # same size, same mtime after
            f.seek(0)
            f.write(b"\0" * 64)
        os.utime(shard0, ns=(st.st_atime_ns, st.st_mtime_ns))
        warm_b = [b.idx.copy() for b in
                  ParquetStream(pq_dir, cache_dir=f"{tmp}/cache")
                  .batches(128, shuffle=False)]
        check("decode cache serves without re-reading the source",
              len(ref) == len(warm_b)
              and all(np.array_equal(a, b) for a, b in zip(ref, warm_b)))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return failures


if __name__ == "__main__":                # pragma: no cover
    # run the CANONICAL module's smoke, not __main__'s copy: `python -m`
    # executes this file as __main__, but the trainers it drives import
    # hivemall_tpu.io.shard_cache — two module instances would split the
    # counters and the smoke would assert against the empty half
    import sys

    from hivemall_tpu.io.shard_cache import _smoke as _canonical_smoke
    sys.exit(_canonical_smoke())
