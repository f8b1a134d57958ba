"""Criteo-shaped rows from a seed: 39 unit-valued features a row, one per
field (13 numeric fields log-binned, 26 categorical fields with the
Criteo-1TB vocabulary sizes the configuration lists under `assumed`), ids
drawn Zipf(s) within each field and folded into [1, dims-1] the way
Hivemall's feature_hashing folds names, labels from a planted FM so the
loss can fall. Vectorised numpy only; no JAX (the load generator's child
process imports this file too)."""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

_M1, _M2 = np.uint64(0xFF51AFD7ED558CCD), np.uint64(0xC4CEB9FE1A85EC53)


def _mix64(x: np.ndarray) -> np.ndarray:
    """murmur3's 64-bit finaliser over a uint64 array (wraps on purpose)."""
    x = x ^ (x >> np.uint64(33))
    x = x * _M1
    x = x ^ (x >> np.uint64(33))
    x = x * _M2
    return x ^ (x >> np.uint64(33))


class RowSpec:
    """The shape of a row, read from a configuration file's `data` group."""

    def __init__(self, data: dict, dims: int):
        self.numeric_fields = int(data["numeric_fields"])
        self.numeric_bins = int(data["numeric_bins"])
        self.vocab = [int(v) for v in data["categorical_vocab"]]
        self.zipf_s = float(data["zipf_exponent"])
        self.dims = int(dims)
        self.fields = self.numeric_fields + len(self.vocab)
        self.sizes = np.asarray([self.numeric_bins] * self.numeric_fields
                                + self.vocab, np.float64)


def draw_ids(spec: RowSpec, n: int, rng: np.random.Generator) -> np.ndarray:
    """[n, fields] int32 feature ids in [1, dims-1]. The rank within a field
    is a bounded continuous power law (density ~ r^-s on [1, V+1)), floored:
    Zipf's shape, one uniform draw and one power per id."""
    s = spec.zipf_s
    u = rng.random((n, spec.fields), dtype=np.float32).astype(np.float64)
    top = np.power(spec.sizes + 1.0, 1.0 - s)            # [fields]
    rank = np.power(1.0 + u * (top - 1.0)[None, :], 1.0 / (1.0 - s))
    rank = np.minimum(np.floor(rank), spec.sizes[None, :]).astype(np.uint64)
    field = np.arange(spec.fields, dtype=np.uint64)[None, :]
    h = _mix64((field << np.uint64(40)) ^ rank)
    return (np.uint64(1) + h % np.uint64(spec.dims - 1)).astype(np.int32)


def planted_margin(ids: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """The planted FM's noisy margin (k = 2): its weights are three bit
    ranges of one hash of the id."""
    h = _mix64(ids.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15))
    scale = np.float32(1.0 / (1 << 20))
    w = ((h >> np.uint64(43)).astype(np.float32) * scale - 1.0) * 0.5
    v1 = (((h >> np.uint64(22)) & np.uint64(0x1FFFFF)).astype(np.float32)
          * scale - 1.0) * 0.3
    v2 = ((h & np.uint64(0x1FFFFF)).astype(np.float32) * scale - 1.0) * 0.3
    phi = w.sum(1)
    for v in (v1, v2):
        phi += 0.5 * (v.sum(1) ** 2 - (v * v).sum(1))
    return phi + rng.normal(0.0, 0.5, len(phi)).astype(np.float32)


_CHUNK = 32768


def make_rows(spec: RowSpec, n: int, seed: int, positive_share: float = 0.25,
              threads: int = 8):
    """(ids [n, fields] int32, labels [n] float32 in +-1) from the seed
    alone. Chunks of rows draw from generators keyed by (seed, chunk), so
    the rows do not depend on how many threads made them."""
    from concurrent.futures import ThreadPoolExecutor
    ids = np.empty((n, spec.fields), np.int32)
    phi = np.empty(n, np.float32)

    def chunk(k: int) -> None:
        s0, s1 = k * _CHUNK, min(n, (k + 1) * _CHUNK)
        rng = np.random.default_rng([int(seed), k, 0x5EED])
        ids[s0:s1] = draw_ids(spec, s1 - s0, rng)
        phi[s0:s1] = planted_margin(ids[s0:s1], rng)

    with ThreadPoolExecutor(max_workers=threads) as ex:
        list(ex.map(chunk, range(-(-n // _CHUNK))))
    cut = np.quantile(phi, 1.0 - positive_share)
    return ids, np.where(phi > cut, 1.0, -1.0).astype(np.float32)


def write_shards(ids: np.ndarray, labels: np.ndarray, out_dir: str,
                 rows_per_shard: int, with_fields: bool,
                 threads: int = 8) -> int:
    """Write the CSR-schema Parquet shards the program's ParquetStream
    reads (`indices: list<int32>`, `label`, and `fields: list<int32>` for
    field-aware families; unit values are the schema's default, so no
    `values` column). Returns the bytes written. The directory is emptied
    first so every run reads the same files from the same place."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    os.makedirs(out_dir, exist_ok=True)
    for f in os.listdir(out_dir):
        os.remove(os.path.join(out_dir, f))
    n, F = ids.shape
    fld_row = np.arange(F, dtype=np.int32)

    def shard(k: int) -> int:
        s0 = k * rows_per_shard
        s1 = min(n, s0 + rows_per_shard)
        m = s1 - s0
        off = np.arange(0, (m + 1) * F, F, dtype=np.int32)
        cols = {"indices": pa.ListArray.from_arrays(
                    off, pa.array(ids[s0:s1].reshape(-1), pa.int32())),
                "label": pa.array(labels[s0:s1], pa.float32())}
        if with_fields:
            cols["fields"] = pa.ListArray.from_arrays(
                off, pa.array(np.tile(fld_row, m), pa.int32()))
        path = os.path.join(out_dir, f"shard-{k:05d}.parquet")
        pq.write_table(pa.table(cols), path)
        return os.path.getsize(path)

    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=threads) as ex:
        return sum(ex.map(shard, range(-(-n // rows_per_shard))))


def match_rows(gen_ids, gen_labels, ids, labels):
    """Which generated row each decoded row is: (row numbers into the
    generator's arrays, how many decoded rows match none)."""
    fp_gen = fingerprints(gen_ids, gen_labels)
    order = np.argsort(fp_gen)
    fp_got = fingerprints(ids, labels)
    rowno = order[np.clip(np.searchsorted(fp_gen[order], fp_got), 0,
                          len(order) - 1)]
    return rowno, int(np.sum(fp_gen[rowno] != fp_got))


def fingerprints(ids: np.ndarray, labels: Optional[np.ndarray]) -> np.ndarray:
    """One uint64 per row over its ids (and its label's sign): how the
    check finds which generated row a decoded row is."""
    F = ids.shape[1]
    mult = _mix64(np.arange(1, F + 1, dtype=np.uint64)) | np.uint64(1)
    h = np.zeros(len(ids), np.uint64)
    for f in range(F):                   # column by column: no [n, F] uint64
        h += ids[:, f].astype(np.uint64) * mult[f]
    if labels is not None:
        h = h ^ np.where(labels > 0, np.uint64(0xA5A5A5A5), np.uint64(0))
    return _mix64(h)
