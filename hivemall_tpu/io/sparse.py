"""Columnar sparse-example substrate: padded (idx, val) batches for TPU.

This replaces the reference's per-row ``FeatureValue[]`` parse inside
GenericUDTF.process() (SURVEY.md §4.1 hot path): variable-length feature lists
become fixed-shape ``int32[B, L]`` index / ``float32[B, L]`` value arrays padded
with (idx=0, val=0). Index 0 is reserved — feature ids start at 1 (mhash range
[1, N]) and ``add_bias`` uses a dedicated bias slot — and every kernel scales by
``val``, so zero-valued padding is arithmetically inert in forward and update.

Static shapes are what XLA needs: every batch from one dataset is padded to a
single fixed row length L (the dataset max, or an explicit ``max_len``), so jit
traces exactly one shape per (B, L) configuration.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["SparseBatch", "SparseDataset", "MegaBatch", "PackedMegaBatch",
           "canonicalize_fieldmajor", "pad_examples",
           "parse_feature_strings", "split_feature", "pow2_len",
           "bucket_size", "score_batches"]


def pow2_len(n: int) -> int:
    """Smallest power of two >= max(n, 1) — the shared shape bucket."""
    p = 1
    while p < n:
        p <<= 1
    return p


def bucket_size(n: int, lo: int = 1, hi: Optional[int] = None) -> int:
    """Power-of-two shape bucket for ``n``, clamped to ``[lo, hi]``.

    The shared batch-dimension bucketing of the scoring paths (online
    serve engine and offline ``score_batches``): padding every batch up to
    a power-of-two bucket bounds the number of distinct jit shapes at
    log2(hi/lo) + 1 instead of one compile per request/dataset size.
    ``lo`` floors tiny batches into one bucket; ``hi`` caps the bucket at
    the configured batch size (a tail can never out-shape the body: past
    ``hi`` the bucket IS ``hi`` itself — for a non-power-of-two batch
    size that is the body shape, already compiled)."""
    b = pow2_len(max(int(n), int(lo)))
    if hi is not None and b > int(hi):
        b = int(hi)
    return b


def score_batches(ds: "SparseDataset", batch_size: int, *,
                  min_rows: int = 8
                  ) -> Iterator[Tuple[int, "SparseBatch"]]:
    """Shape-BUCKETED scoring batches over ``ds``: ``(start_row, batch)``.

    The offline peer of the serve engine's bucketed predict (both sides
    share :func:`bucket_size`): row length is padded to the power-of-two
    bucket of the dataset max — datasets of nearby widths score through
    ONE compiled kernel instead of recompiling per max_row_len — and the
    ragged tail is padded to its own power-of-two row bucket (>=
    ``min_rows``, <= ``batch_size``) rather than the full batch size, so
    large offline scoring reuses a bounded set of (B, L) compiles and
    never burns a full-batch pad on a short tail. Padding is
    arithmetically inert (idx 0 / val 0), so per-row scores are unchanged;
    ``n_valid`` marks the real rows."""
    n = len(ds)
    if n == 0:
        return
    # shape-bucket telemetry (obs.devprof): first use of a (B, L) bucket
    # is the moment the scoring kernel compiles for it — recorded so the
    # devprof section shows how many distinct compiles bucketing allowed
    from ..obs.devprof import get_devprof
    devprof = get_devprof()
    bs = int(batch_size)
    L = pow2_len(ds.max_row_len)
    full_end = (n // bs) * bs
    if full_end:
        devprof.note_bucket("score_batches", bs, L)
        it = ds.batches(bs, shuffle=False, max_len=L, drop_remainder=True)
        for s, b in zip(range(0, full_end, bs), it):
            yield s, b
    if full_end < n:
        tail = n - full_end
        Bt = bucket_size(tail, lo=min(int(min_rows), bs), hi=bs)
        devprof.note_bucket("score_batches", Bt, L)
        tb = ds.take(np.arange(full_end, n, dtype=np.int64))
        yield full_end, next(tb.batches(Bt, shuffle=False, max_len=L))


def split_feature(f) -> Tuple[str, str]:
    """Split one feature string into (name, value-string).

    Reference semantics (hivemall.model.FeatureValue.parse): a bare
    ``"name"`` means value 1.0; ``"name:val"`` splits on the LAST ':' so
    names containing ':' still parse."""
    name, sep, v = str(f).rpartition(":")
    if not sep:
        return str(f), "1.0"
    return name, v


@dataclass
class SparseBatch:
    """One padded minibatch. ``field`` is present only for FFM-style features.

    ``fieldmajor=True`` marks the canonical FFM layout built by
    :func:`canonicalize_fieldmajor`: slot s holds a feature of field
    ``s % F`` (so no ``field`` array is needed; the jitted step derives the
    pattern statically).

    ``val=None`` is UNIT-VALUE ELISION: every present feature has value
    1.0, i.e. val == (idx != 0) exactly (categorical/CTR data — the Criteo
    case). Consumers that support it rebuild val from idx inside the
    jitted step; the h2d transfer of the val array (a third of batch
    bytes) is skipped entirely."""

    idx: np.ndarray                  # int32 [B, L], 0 = padding
    val: Optional[np.ndarray]        # float32 [B, L]; None = unit values
    label: np.ndarray                # float32 [B]
    field: Optional[np.ndarray] = None  # int32 [B, L], FFM only
    n_valid: Optional[int] = None    # rows < n_valid are real; rest are padding
    fieldmajor: bool = False         # canonical slot->field layout (FFM)
    seq: Optional[int] = None        # dispatch ordinal (see MegaBatch.seq)

    @property
    def batch_size(self) -> int:
        return int(self.idx.shape[0])

    @property
    def row_mask(self) -> "jnp.ndarray":
        """Valid-row mask as a cached jax DEVICE array (not host numpy —
        callers that need host-side in-place numpy must np.asarray a copy).
        Building it fresh per access made every jitted-step call
        re-transfer 4*B bytes h2d when the same batch is stepped
        repeatedly. The cache also lets jax reuse the device buffer; the
        value is frozen at first access, which is correct because
        SparseBatch is write-once."""
        m = self.__dict__.get("_row_mask")
        if m is None:
            import jax.numpy as jnp
            b = self.batch_size
            n = b if self.n_valid is None else self.n_valid
            m = jnp.asarray((np.arange(b) < n).astype(np.float32))
            object.__setattr__(self, "_row_mask", m)
        return m


@dataclass
class PackedBatch:
    """One canonical unit-value field-major batch packed into a SINGLE
    uint8 buffer for ONE host->device transfer.

    The e2e flagship wall is the h2d link, which charges per TRANSFER
    (latency) and per BYTE (bandwidth): a SparseBatch moves 2-3 arrays
    (idx int32 + label f32 + row mask) = 2-3 latency hits and 4 bytes per
    index lane. Here idx packs to 3 little-endian bytes per lane (exact
    for dims <= 2^24 — every table size the trainers accept), the f32
    labels ride as raw bytes in the same buffer, and the row mask is
    rebuilt on device from the n_valid scalar. The jitted step unpacks
    with shifts/bitcasts (free against the link). Layout:
    ``buf[:B*L*3]`` = idx lanes, ``buf[B*L*3:]`` = label bytes."""

    buf: np.ndarray                  # uint8 [B*L*3 + B*4]
    B: int
    L: int
    n_valid: Optional[int] = None
    fieldmajor: bool = True
    seq: Optional[int] = None        # dispatch ordinal (see MegaBatch.seq)

    @property
    def batch_size(self) -> int:
        return self.B


@dataclass
class MegaBatch:
    """K same-shape minibatches stacked on the leading axis for ONE
    host->device transfer and ONE jitted ``lax.scan`` dispatch of all K
    optimizer steps (``-steps_per_dispatch``, ops.scan.make_megastep).

    Built by io.prefetch.MegabatchStager from consecutive same-kind
    SparseBatches: a window never mixes unit-valued (``val=None``) and
    real-valued batches, so unit-value elision survives stacking — an
    idx-only window transfers no val array at all.

    ``nv`` is the per-step valid-row count as a HOST int32 [K] vector
    (the accounting side reads it without a device sync); ``nv_dev`` is
    its staged device copy, set by ``io.prefetch.stage_batch`` so the
    scan body can rebuild each step's row mask on device (4*B fewer
    bytes per step on the link than shipping the float masks).

    ``seq`` is the dispatch's ordinal in its stream, given by the stager
    as it emits (stacked windows and flushed singles alike, from 0) and
    kept by ``stage_batch``: the id the ``stager.stack``, ``h2d.stage``
    and ``dispatch.*`` spans of one dispatch share (obs.trace)."""

    idx: np.ndarray                  # int32 [K, B, L]
    val: Optional[np.ndarray]        # float32 [K, B, L]; None = unit values
    label: np.ndarray                # float32 [K, B]
    field: Optional[np.ndarray] = None  # int32 [K, B, L], FFM pairs path
    nv: Optional[np.ndarray] = None  # int32 [K] valid rows per step (host)
    nv_dev: Optional[object] = None  # staged device copy of nv
    fieldmajor: bool = False
    seq: Optional[int] = None        # dispatch ordinal in the stream

    @property
    def n_steps(self) -> int:
        return int(self.label.shape[0])

    @property
    def batch_size(self) -> int:
        return int(self.label.shape[1])

    @property
    def n_examples(self) -> int:
        return int(self.nv.sum())


@dataclass
class PackedMegaBatch:
    """K packed unit-value field-major batches (io.sparse.PackedBatch)
    stacked into one uint8 [K, nbytes] buffer — one transfer for K whole
    steps of the flagship packed FFM path."""

    buf: np.ndarray                  # uint8 [K, B*L*3 + B*4]
    B: int
    L: int
    nv: np.ndarray = None            # int32 [K] (host)
    nv_dev: Optional[object] = None
    seq: Optional[int] = None        # dispatch ordinal (see MegaBatch.seq)

    @property
    def n_steps(self) -> int:
        return int(self.buf.shape[0])

    @property
    def batch_size(self) -> int:
        return self.B

    @property
    def n_examples(self) -> int:
        return int(self.nv.sum())


def pack_unit_fieldmajor(batch: SparseBatch) -> PackedBatch:
    """Pack a canonical unit-value field-major SparseBatch (host arrays)
    into a PackedBatch. Caller guarantees val is None (unit-value elision)
    and idx < 2^24."""
    idx = np.ascontiguousarray(np.asarray(batch.idx, np.int32))
    B, L = idx.shape
    lanes = idx.view(np.uint8).reshape(B, L, 4)[:, :, :3]   # little-endian
    lab = np.ascontiguousarray(np.asarray(batch.label, np.float32))
    buf = np.concatenate([np.ascontiguousarray(lanes).reshape(-1),
                          lab.view(np.uint8)])
    return PackedBatch(buf, B, L, n_valid=batch.n_valid)


def canonicalize_fieldmajor(idx: np.ndarray, val: np.ndarray,
                            fld: np.ndarray, F: int, *,
                            max_m: int = 4):
    """Reorder each row's features into FIELD-MAJOR slots.

    Output slot ``s = rank * F + field`` holds the rank-th feature of that
    field in the row (FFM is order-invariant, so reordering within a row is
    free). The jitted FFM step then derives every slot's field statically
    (``s % F``) — ops.fm._fused_phi_fieldmajor computes the pair
    interaction with no gather/scatter/matmul at all. Criteo-shaped rows
    (exactly one feature per field) canonicalize with m = 1, i.e. to a
    [B, F] batch.

    Fully vectorized (one argsort + cumulative ops — this runs on the e2e
    input path; the C++ twin in native/hivemall_native.cpp takes over when
    built, ~10x, rows OpenMP-parallel). Returns ``(idx2, val2, m)`` with
    arrays [B, m*F] and m a power of two, or ``None`` if some row has more
    than ``max_m`` features in one field (caller falls back to the general
    pair path).

    Field ids fold modulo F — the same normalization FFMTrainer._parse_row
    and every FFM kernel apply, so out-of-range ids keep their features
    instead of silently vanishing."""
    from ..utils.native import canonicalize_fieldmajor_native
    native = canonicalize_fieldmajor_native(idx, val, fld, F, max_m)
    if native is not NotImplemented:
        return native
    B, L = idx.shape
    live = val != 0
    fld = fld % F
    fkey = np.where(live, fld, F)                   # dead slots sort last
    order = np.argsort(fkey, axis=1, kind="stable")
    sf = np.take_along_axis(fkey, order, 1)
    pos = np.arange(L, dtype=np.int64)[None, :]
    # occurrence rank within each row's run of equal fields
    first = np.where((sf != np.roll(sf, 1, axis=1)) | (pos == 0), pos, 0)
    first = np.maximum.accumulate(first, 1)
    rank = pos - first
    alive = sf < F
    if not alive.any():
        return (np.zeros((B, F), np.int32), np.zeros((B, F), np.float32), 1)
    m_needed = int(rank[alive].max()) + 1
    if m_needed > max_m:
        return None
    m = pow2_len(m_needed)
    si = np.take_along_axis(idx, order, 1)
    sv = np.take_along_axis(val, order, 1)
    out_idx = np.zeros((B, m * F), np.int32)
    out_val = np.zeros((B, m * F), np.float32)
    slot = rank * F + sf                            # block-major: field s % F
    rowi = np.broadcast_to(np.arange(B)[:, None], (B, L))
    out_idx[rowi[alive], slot[alive]] = si[alive]
    out_val[rowi[alive], slot[alive]] = sv[alive]
    return out_idx, out_val, int(m)


def parse_feature_strings(features: Sequence[str],
                          *, int_feature: bool = False,
                          num_features: Optional[int] = None,
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """Parse one row of ``"idx:val"`` / ``"idx"`` feature strings.

    Reference semantics: hivemall.model.FeatureValue.parse — a bare ``"idx"``
    means value 1.0 (categorical); ``"idx:val"`` splits on the LAST ':' so that
    string feature names containing ':' still parse (SURVEY.md §3.1).
    """
    idx: List[int] = []
    val: List[float] = []
    from ..utils.hashing import mhash
    for f in features:
        if f is None or f == "":
            continue
        name, v = split_feature(f)
        try:
            i = int(name)
        except ValueError:
            if int_feature:
                raise ValueError(
                    f"-int_feature is set but feature name {name!r} is not an "
                    f"integer index")
            # num_features means the weight-array SIZE (ids < num_features),
            # matching every other call site in the repo that passes dims;
            # mhash's range is [1, n] inclusive, hence the -1
            i = mhash(name) if num_features is None \
                else mhash(name, num_features - 1)
        idx.append(i)
        val.append(float(v))
    return np.asarray(idx, np.int32), np.asarray(val, np.float32)


def pad_examples(rows: Sequence[Tuple[np.ndarray, np.ndarray]],
                 labels: Sequence[float],
                 max_len: Optional[int] = None,
                 fields: Optional[Sequence[np.ndarray]] = None,
                 truncate: bool = False) -> SparseBatch:
    """Pad a list of (idx, val) rows to a rectangular SparseBatch.

    Rows longer than ``max_len`` raise unless ``truncate=True`` is explicit —
    silent feature loss is never the default.
    """
    B = len(rows)
    L = max_len or max((len(r[0]) for r in rows), default=1)
    L = max(L, 1)
    idx = np.zeros((B, L), np.int32)
    val = np.zeros((B, L), np.float32)
    fld = np.zeros((B, L), np.int32) if fields is not None else None
    for b, (i, v) in enumerate(rows):
        if len(i) > L and not truncate:
            raise ValueError(
                f"row {b} has {len(i)} features > max_len={L}; pass "
                f"truncate=True to drop the excess explicitly")
        n = min(len(i), L)
        idx[b, :n] = i[:n]
        val[b, :n] = v[:n]
        if fld is not None:
            fld[b, :n] = fields[b][:n]
    return SparseBatch(idx, val, np.asarray(labels, np.float32), fld, n_valid=B)


class SparseDataset:
    """In-memory sparse dataset with epoch/shuffle/minibatch iteration.

    Plays the role of the engine feeding rows into the UDTF plus the
    NioStatefulSegment replay buffer for ``-iters > 1`` (SURVEY.md §3.20):
    holding the parsed CSR arrays in host RAM, re-shuffling per epoch, and
    emitting fixed-shape padded batches (short final batch is padded up and
    carries ``n_valid`` so loss masks it out).
    """

    def __init__(self, indices: np.ndarray, indptr: np.ndarray,
                 values: np.ndarray, labels: np.ndarray,
                 fields: Optional[np.ndarray] = None):
        self.indices = np.asarray(indices, np.int32)    # flat feature ids
        self.indptr = np.asarray(indptr, np.int64)      # row offsets, len = n+1
        self.values = np.asarray(values, np.float32)
        self.labels = np.asarray(labels, np.float32)
        self.fields = None if fields is None else np.asarray(fields, np.int32)

    @classmethod
    def from_rows(cls, rows: Sequence[Tuple[np.ndarray, np.ndarray]],
                  labels: Sequence[float],
                  fields: Optional[Sequence[np.ndarray]] = None
                  ) -> "SparseDataset":
        indptr = np.zeros(len(rows) + 1, np.int64)
        for i, (ix, _) in enumerate(rows):
            indptr[i + 1] = indptr[i] + len(ix)
        indices = np.concatenate([np.asarray(r[0], np.int32) for r in rows]) \
            if rows else np.zeros(0, np.int32)
        values = np.concatenate([np.asarray(r[1], np.float32) for r in rows]) \
            if rows else np.zeros(0, np.float32)
        flds = None
        if fields is not None:
            flds = np.concatenate([np.asarray(f, np.int32) for f in fields]) \
                if len(fields) else np.zeros(0, np.int32)
        return cls(indices, indptr, values, np.asarray(labels, np.float32), flds)

    def __len__(self) -> int:
        return len(self.labels)

    def content_key(self) -> str:
        """sha256 over the CSR payload — the identity the shard cache keys
        RAM-only datasets by (io.shard_cache; file-backed datasets carry a
        ``source_id`` mtime/size identity from their reader instead).
        Cached after the first call; a SparseDataset is write-once."""
        ck = self.__dict__.get("_content_key")
        if ck is None:
            import hashlib
            h = hashlib.sha256()
            for a in (self.indices, self.indptr, self.values, self.labels,
                      self.fields):
                if a is not None:
                    a = np.ascontiguousarray(a)
                    h.update(f"{a.dtype.str}:{a.shape};".encode())
                    h.update(memoryview(a).cast("B"))
            ck = h.hexdigest()
            self.__dict__["_content_key"] = ck
        return ck

    @property
    def max_row_len(self) -> int:
        if len(self) == 0:
            return 1
        return int(np.max(np.diff(self.indptr)))

    def row(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        s, e = self.indptr[i], self.indptr[i + 1]
        return self.indices[s:e], self.values[s:e]

    def take(self, rows) -> "SparseDataset":
        """Row-subset view materialized as a new dataset (vectorized CSR
        range gather — no per-row Python). Used by train_fm's -adareg
        validation holdout; generally useful for CV splits."""
        rows = np.asarray(rows, np.int64)
        lens = (self.indptr[rows + 1] - self.indptr[rows])
        indptr = np.zeros(len(rows) + 1, np.int64)
        np.cumsum(lens, out=indptr[1:])
        total = int(indptr[-1])
        if total:
            starts = np.repeat(self.indptr[rows], lens)
            offs = np.arange(total, dtype=np.int64) - np.repeat(
                indptr[:-1], lens)
            src = starts + offs
            indices, values = self.indices[src], self.values[src]
            fields = None if self.fields is None else self.fields[src]
        else:
            indices = np.zeros(0, np.int32)
            values = np.zeros(0, np.float32)
            fields = None if self.fields is None else np.zeros(0, np.int32)
        return SparseDataset(indices, indptr, values, self.labels[rows],
                             fields)

    def batches(self, batch_size: int, *, epochs: int = 1, shuffle: bool = False,
                seed: int = 42, max_len: Optional[int] = None,
                drop_remainder: bool = False,
                truncate: bool = False) -> Iterator[SparseBatch]:
        n = len(self)
        L = max(1, max_len or self.max_row_len)
        rng = np.random.default_rng(seed)
        for ep in range(epochs):
            order = rng.permutation(n) if shuffle else np.arange(n)
            yield from self.batches_in_order(
                order, batch_size, L, truncate=truncate,
                drop_remainder=drop_remainder)

    def batches_in_order(self, order: np.ndarray, batch_size: int, L: int,
                         *, truncate: bool = False,
                         drop_remainder: bool = False
                         ) -> Iterator[SparseBatch]:
        """Padded ``[batch_size, L]`` batches of rows ``order``, in that
        order, gathered straight from this dataset's CSR arrays: THE batch
        assembly, shared by :meth:`batches` and ``ParquetStream.batches``
        (which passes a shard's shuffled row order, so no reordered copy
        of the shard is ever made). The last batch is short
        (``n_valid``) when ``order`` is no multiple of ``batch_size``."""
        lens = np.diff(self.indptr).astype(np.int64)
        if not truncate and len(lens) and int(lens.max()) > L:
            raise ValueError(
                f"max_len={L} would drop features from rows up to "
                f"{int(lens.max())} long; pass truncate=True to allow")
        pos = np.arange(L, dtype=np.int64)[None, :]           # [1, L]
        for s in range(0, len(order), batch_size):
            take = order[s: s + batch_size]
            nv = len(take)
            if nv < batch_size and drop_remainder:
                break
            # vectorized padding: flat CSR positions of every kept slot
            # in one fancy index (no per-row Python — the host batch
            # assembly is on the e2e critical path, SURVEY.md §8)
            m = np.minimum(lens[take], L)                 # [nv]
            keep = pos < m[:, None]                       # [nv, L]
            flat = np.where(keep, self.indptr[take][:, None] + pos, 0)
            idx = np.zeros((batch_size, L), np.int32)
            val = np.zeros((batch_size, L), np.float32)
            if len(self.indices):        # all-empty-rows dataset guard
                idx[:nv] = np.where(keep, self.indices[flat], 0)
                val[:nv] = np.where(keep, self.values[flat], 0.0)
            fld = None
            if self.fields is not None:
                fld = np.zeros((batch_size, L), np.int32)
                if len(self.fields):
                    fld[:nv] = np.where(keep, self.fields[flat], 0)
            lab = np.zeros(batch_size, np.float32)
            lab[:nv] = self.labels[take]
            yield SparseBatch(idx, val, lab, fld,
                              n_valid=nv if nv < batch_size else None)
