"""Warehouse-scale offline scoring: the HivemallOps batch path.

Reference analog (SURVEY.md §4.6 Spark HivemallOps [B], §3.15
``each_top_k``): Hivemall's other half is offline — score an entire
warehouse table overnight, not one request at a time. This module is that
path as a library call plus the ``hivemall_tpu predict --input <parquet
dir>`` CLI plumbing:

- **Input** is a directory of Parquet shards (the PR 6 out-of-core
  layout) or a single LIBSVM/Parquet file. Shards decode through the
  SAME :class:`~.shard_cache.ShardDecodeCache` the training stream uses
  (same parse-config key), so a table that was ever trained on scores
  warm with zero Parquet read + parse cost.
- **Model source** defaults to the promotion pointer
  (:func:`~.checkpoint.read_promoted`): nightly jobs score with exactly
  the serving model. The resolved bundle is pinned
  (:func:`~.checkpoint.hold_bundle`) for the whole run so checkpoint
  retention can never GC it mid-job.
- **Backends**: ``kernel`` scores through the jitted shape-bucketed
  kernels (:func:`~.sparse.score_batches` — bit-identical to the offline
  ``predict_proba`` path); ``arena`` scores through the PR 15 mmap'd
  numpy/int8 twins (:mod:`~.weight_arena`) — no device at all, the
  pure-CPU scoring-fleet shape (docs/RELIABILITY.md). ``auto`` probes
  both on a sample of the first shard and picks the measured-fastest,
  per host (docs/PERFORMANCE.md "Bulk scoring").
- **Fan-out** mirrors ``-ingest_pool``: shards are scored by a pool of
  worker processes (spawn — JAX is fork-unsafe once initialized), each
  building its scorer once and streaming its shards; ``workers=1`` runs
  inline. Memory is bounded by (workers × one shard), never the table.
  Pool workers score on the host CPU on purpose: a chip takes one
  process and the master holds it, so on an accelerator host the
  ``kernel`` backend runs with ``workers=1`` only and a process pool
  scores through the arena twins (utils/device.py).
- **One pass** writes scored Parquet (one output shard per input shard,
  same basenames so sorted order is row order), folds the evaluation
  UDAFs (logloss/AUC/rmse via :mod:`~..frame.evaluation` — AUC exact up
  to a row cap, histogram-merged beyond), and optionally composes with
  ``frame.tools.each_top_k`` through the streaming
  :class:`~..frame.tools.TopKAccumulator` for the canonical "score then
  top-k per user" job.

Progress is a live ``bulk`` obs-registry section (stub parity with
``obs.registry.BULK_STUB``) plus ``bulk`` events on the metrics stream;
``hivemall_tpu obs`` renders a progress line from either.
"""

from __future__ import annotations

import json
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..obs.flight import FS, get_flight
from .sparse import SparseDataset, score_batches

__all__ = ["bulk_predict", "BulkProgress", "resolve_model_bundle",
           "AUC_EXACT_CAP"]

#: rows of (label, score) retained for EXACT AUC before degrading to the
#: histogram merge (still via the same rank statistic, binned) — bounds
#: master-side memory on billion-row tables
AUC_EXACT_CAP = 8 << 20
_AUC_BINS = 4096
_PROBE_ROWS = 4096


# --------------------------------------------------------------------------
# model resolution

def resolve_model_bundle(algo: str, *, bundle: Optional[str] = None,
                         checkpoint_dir: Optional[str] = None
                         ) -> Tuple[str, str]:
    """``(bundle_path, source)`` for a bulk job: an explicit bundle wins;
    else the checkpoint dir's PROMOTED pointer (the serving model — the
    default nightly-job contract), falling back to the newest step bundle
    when nothing was ever promoted."""
    from ..catalog import lookup
    from .checkpoint import newest_bundle, promoted_bundle
    if bundle:
        return bundle, "explicit"
    if not checkpoint_dir:
        raise ValueError("bulk predict needs --bundle or --checkpoint-dir")
    name = lookup(algo).resolve().NAME
    hit = promoted_bundle(checkpoint_dir, name)
    if hit is not None:
        return hit[1], "promoted"
    hit = newest_bundle(checkpoint_dir, name)
    if hit is not None:
        return hit[1], "newest"
    raise FileNotFoundError(
        f"no {name} bundles under {checkpoint_dir}")


# --------------------------------------------------------------------------
# per-process scorer state (workers build this once, then stream shards)

_state_lock = threading.Lock()
_states: Dict[str, "_BackendState"] = {}


def _trainer_scores(trainer, ds: SparseDataset,
                    batch_size: Optional[int]) -> np.ndarray:
    """Output-space scores through the trainer's OWN offline path when no
    batch size is forced — ``predict_proba``/``decision_function`` choose
    their own bucket sizes, and riding them is what makes the kernel
    backend bit-identical to offline scoring by construction."""
    if batch_size:
        return np.asarray(trainer.score_dataset(ds, batch_size), np.float32)
    classification = getattr(trainer, "classification",
                             getattr(trainer, "CLASSIFICATION", False))
    if classification and hasattr(trainer, "predict_proba"):
        return np.asarray(trainer.predict_proba(ds), np.float32)
    if not classification and hasattr(trainer, "decision_function"):
        return np.asarray(trainer.decision_function(ds), np.float32)
    return np.asarray(trainer.score_dataset(ds), np.float32)


class _BackendState:
    """One process's scorer: jitted trainer (``kernel``) or mmap'd arena
    tier (``arena``), plus the shard decode cache. Built lazily per
    worker process, reused across that worker's shards."""

    def __init__(self, cfg: Dict[str, Any]):
        from ..catalog import lookup
        self.cfg = cfg
        self.backend = cfg["backend"]
        self.precision = cfg["precision"]
        self.batch_size = cfg.get("batch_size") or None
        self._cls = lookup(cfg["algo"]).resolve()
        self.trainer = None
        self.arena = None
        self._arena_fn = None
        if self.backend == "kernel":
            t = self._cls(cfg["options"] or "")
            t.load_bundle(cfg["bundle"])
            self.trainer = t
        else:
            from .weight_arena import try_open_arena
            a = try_open_arena(cfg["bundle"], trainer_name=self._cls.NAME,
                               precision=self.precision)
            if a is None:
                # the master publishes before fanning out; a worker can
                # only get here when the sidecar was deleted mid-run
                raise FileNotFoundError(
                    f"no usable arena sidecar for {cfg['bundle']}")
            self.arena = a
            self._arena_fn = a.scorer(self.precision)
        self.cache = None
        if cfg.get("cache_dir"):
            from .shard_cache import ShardDecodeCache
            self.cache = ShardDecodeCache(cfg["cache_dir"], cfg["parse_kw"])

    def decode(self, kind: str, path: str) -> SparseDataset:
        if kind == "libsvm":
            from .libsvm import read_libsvm
            kw = self.cfg["parse_kw"]
            if kw.get("ffm"):
                return read_libsvm(path, ffm=True,
                                   num_fields=kw["num_fields"],
                                   dims=kw.get("dims"))
            return read_libsvm(path)
        if self.cache is not None:
            ds = self.cache.load(path)
            if ds is not None:
                return ds
        import pyarrow.parquet as pq
        from .arrow import table_to_dataset
        ds = table_to_dataset(pq.read_table(path), **self.cfg["parse_kw"])
        if self.cache is not None:
            self.cache.store(path, ds)
        return ds

    def score(self, ds: SparseDataset) -> np.ndarray:
        if self.backend == "kernel":
            return _trainer_scores(self.trainer, ds, self.batch_size)
        bs = int(self.batch_size or 1024)
        out = np.empty(len(ds), np.float32)
        for s, b in score_batches(ds, bs):
            nv = b.n_valid or b.batch_size
            # output path: the per-batch score fetch IS the product
            # graftcheck: disable=GC07
            out[s:s + nv] = np.asarray(self._arena_fn(b), np.float32)[:nv]
        return out

    def release(self) -> None:
        if self.arena is not None:
            self.arena.release()
            self.arena = None
        self.trainer = None
        self._arena_fn = None


def _get_state(cfg: Dict[str, Any]) -> _BackendState:
    key = cfg["digest"]
    with _state_lock:
        st = _states.get(key)
        if st is None:
            st = _BackendState(cfg)
            _states[key] = st
        return st


def _release_states() -> None:
    """Drop every cached scorer state in THIS process — the inline/thread
    pools run workers in the master, and a cached arena mmap outliving
    the job would fail the leak census that gates the bulk smoke."""
    with _state_lock:
        states = list(_states.values())
        _states.clear()
    for st in states:
        st.release()


def _score_shard_task(cfg: Dict[str, Any], kind: str, path: str,
                      index: int) -> Dict[str, Any]:
    """Score ONE shard: decode (through the shared cache), score through
    the configured backend, write the scored output shard, and return the
    master's aggregation payload (labels+scores ride back for the exact
    evaluation UDAFs; top-k returns only the per-group k best — a row
    outside its shard's per-group k best can never rank globally)."""
    t0 = time.perf_counter()
    # shard lifecycle to the flight ring: a pool worker SIGKILLed (OOM)
    # mid-shard leaves a start with no done — the post-mortem names the
    # exact shard that killed it. Workers inherit $HIVEMALL_TPU_FLIGHT
    # through the spawn env; unset, this is one attribute check.
    fl = get_flight()
    if fl.enabled:
        fl.record("bulk.shard.start",
                  f"i={index}{FS}file={os.path.basename(path)[:48]}")
    st = _get_state(cfg)
    ds = st.decode(kind, path)
    t1 = time.perf_counter()
    scores = st.score(ds)
    t2 = time.perf_counter()
    if fl.enabled:
        fl.record("bulk.shard.done",
                  f"i={index}{FS}rows={len(ds)}{FS}"
                  f"d={(t1 - t0) * 1e3:.1f}{FS}s={(t2 - t1) * 1e3:.1f}")

    out_path = None
    group = None
    if cfg.get("group_col"):
        import pyarrow.parquet as pq
        if kind != "parquet":
            raise ValueError("--group-col needs Parquet input")
        group = pq.read_table(path, columns=[cfg["group_col"]]) \
            .column(cfg["group_col"]).to_numpy(zero_copy_only=False)
    if cfg.get("output_dir"):
        import pyarrow as pa
        import pyarrow.parquet as pq
        name = os.path.basename(path) if kind == "parquet" \
            else f"scores-{index:05d}.parquet"
        if not name.endswith((".parquet", ".pq")):
            name += ".parquet"
        cols = {"label": pa.array(ds.labels, pa.float32()),
                "score": pa.array(scores, pa.float32())}
        if group is not None:
            cols[cfg["group_col"]] = pa.array(group)
        out_path = os.path.join(cfg["output_dir"], name)
        pq.write_table(pa.table(cols), out_path)

    topk = None
    if cfg.get("top_k") and group is not None:
        from ..frame.tools import TopKAccumulator
        acc = TopKAccumulator(cfg["top_k"])
        acc.add_many(group.tolist(), scores,
                     [f"{index}:{r}" for r in range(len(scores))])
        # per-shard survivors only — (group, score, rowref), unranked;
        # the master re-accumulates globally and ranks via each_top_k
        topk = [(g, s, v) for g, _rank, s, v in acc.result()]

    return {"index": index, "rows": int(len(ds)),
            "decode_seconds": t1 - t0, "score_seconds": t2 - t1,
            "busy_seconds": time.perf_counter() - t0,
            "out_path": out_path, "topk": topk,
            "labels": np.asarray(ds.labels, np.float32),
            "scores": scores}


def _group_components(files: List[str], group_col: str) -> List[List[int]]:
    """Union-find shards into group-closed components: two shards land
    in one component iff they share >=1 ``group_col`` value (directly or
    transitively). Fused per-group top-k then routes ONE pooled task per
    component, so no group's candidate set is ever split across workers
    — each worker returns final per-group k-bests and the master merge
    degenerates to concatenation of disjoint group sets. Reads only the
    group column of each shard. Components come back as ascending shard
    indices, ordered by first member."""
    import pyarrow.parquet as pq
    parent = list(range(len(files)))

    def find(i: int) -> int:
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    owner: Dict[Any, int] = {}
    for i, f in enumerate(files):
        vals = pq.read_table(f, columns=[group_col]) \
            .column(group_col).to_numpy(zero_copy_only=False)
        for v in np.unique(vals).tolist():
            j = owner.setdefault(v, i)
            if j != i:
                ra, rb = find(i), find(j)
                if ra != rb:
                    parent[max(ra, rb)] = min(ra, rb)
    comps: Dict[int, List[int]] = {}
    for i in range(len(files)):
        comps.setdefault(find(i), []).append(i)
    return [sorted(m) for _, m in
            sorted(comps.items(), key=lambda kv: min(kv[1]))]


def _score_component_task(cfg: Dict[str, Any], kind: str,
                          paths: List[str],
                          indices: List[int]) -> List[Dict[str, Any]]:
    """Score one group-closed shard component in a single worker: each
    member shard scores exactly as `_score_shard_task` would, but the
    members' per-group top-k survivors merge HERE (ascending shard
    order, preserving the arrival-order tie semantics the master merge
    would have applied) and ride back once on the first member."""
    from ..frame.tools import TopKAccumulator
    results = []
    acc = None
    for path, idx in zip(paths, indices):
        res = _score_shard_task(cfg, kind, path, idx)
        if res["topk"] is not None:
            if acc is None:
                acc = TopKAccumulator(cfg["top_k"])
            for g, s, v in res["topk"]:
                acc.add(g, s, v)
            res["topk"] = None
        results.append(res)
    if acc is not None:
        results[0]["topk"] = [(g, s, v)
                              for g, _rank, s, v in acc.result()]
    return results


# --------------------------------------------------------------------------
# streaming evaluation UDAFs

class _EvalAccum:
    """Exactly-decomposable logloss/rmse sums + AUC that is EXACT (the
    frame/evaluation rank statistic over retained rows) up to
    ``AUC_EXACT_CAP`` rows and a binned midrank merge beyond it."""

    def __init__(self, classification: bool):
        self.classification = classification
        self.n = 0
        self._ll_sum = 0.0
        self._se_sum = 0.0
        self._rows: Optional[List[Tuple[np.ndarray, np.ndarray]]] = []
        self._pos_hist = np.zeros(_AUC_BINS, np.int64)
        self._neg_hist = np.zeros(_AUC_BINS, np.int64)

    def add(self, labels: np.ndarray, scores: np.ndarray) -> None:
        n = len(labels)
        if n == 0:
            return
        from ..frame.evaluation import logloss
        self.n += n
        if self.classification:
            self._ll_sum += float(logloss(labels, scores)) * n
            if self._rows is not None and self.n <= AUC_EXACT_CAP:
                self._rows.append((labels, scores))
            else:
                if self._rows is not None:       # degrade: bin the backlog
                    for lab, sc in self._rows:
                        self._bin(lab, sc)
                    self._rows = None
                self._bin(labels, scores)
        else:
            d = np.asarray(labels, np.float64) - np.asarray(scores,
                                                            np.float64)
            self._se_sum += float(np.dot(d, d))

    def _bin(self, labels: np.ndarray, scores: np.ndarray) -> None:
        b = np.clip((np.asarray(scores, np.float64) * _AUC_BINS).astype(
            np.int64), 0, _AUC_BINS - 1)
        pos = np.asarray(labels) > 0
        self._pos_hist += np.bincount(b[pos], minlength=_AUC_BINS)
        self._neg_hist += np.bincount(b[~pos], minlength=_AUC_BINS)

    def result(self) -> Dict[str, Any]:
        if self.n == 0:
            return {}
        if not self.classification:
            return {"rmse": round(float(np.sqrt(self._se_sum / self.n)), 6)}
        out: Dict[str, Any] = {"logloss": round(self._ll_sum / self.n, 6)}
        if self._rows is not None:
            from ..frame.evaluation import auc
            labels = np.concatenate([r[0] for r in self._rows])
            scores = np.concatenate([r[1] for r in self._rows])
            out["auc"] = round(float(auc(labels, scores)), 6)
            out["auc_method"] = "exact"
            return out
        P, N = int(self._pos_hist.sum()), int(self._neg_hist.sum())
        if P and N:
            # binned midrank: negatives strictly below each bin count
            # fully, same-bin negatives count half (ties at bin width)
            neg_below = np.concatenate(
                [[0], np.cumsum(self._neg_hist)[:-1]])
            wins = float((self._pos_hist * neg_below).sum()) \
                + 0.5 * float((self._pos_hist * self._neg_hist).sum())
            out["auc"] = round(wins / (P * N), 6)
            out["auc_method"] = "histogram"
        return out


# --------------------------------------------------------------------------
# live obs section

class BulkProgress:
    """The ``bulk`` obs-registry section of a running job — key-for-key
    the shape of ``obs.registry.BULK_STUB`` (GC05 stub parity)."""

    def __init__(self):
        self.active = False
        self.input = None
        self.output = None
        self.backend = None
        self.precision = None
        self.workers = 0
        self.shards_total = 0
        self.shards_done = 0
        self.rows_scored = 0
        self.busy_seconds = 0.0
        self.model_step = None
        self.bundle = None
        self._t0 = time.monotonic()
        self._elapsed = 0.0

    def elapsed(self) -> float:
        return time.monotonic() - self._t0 if self.active else self._elapsed

    def finish(self) -> None:
        self._elapsed = time.monotonic() - self._t0
        self.active = False

    def obs_section(self) -> dict:
        el = self.elapsed()
        util = self.busy_seconds / (el * self.workers) \
            if el > 0 and self.workers else 0.0
        return {"active": self.active, "input": self.input,
                "output": self.output, "backend": self.backend,
                "precision": self.precision, "workers": self.workers,
                "shards_total": self.shards_total,
                "shards_done": self.shards_done,
                "rows_scored": self.rows_scored,
                "rows_per_sec": round(self.rows_scored / el, 1)
                if el > 0 else 0.0,
                "worker_utilization": round(min(util, 1.0), 4),
                "elapsed_seconds": round(el, 3),
                "model_step": self.model_step, "bundle": self.bundle}


def _register_progress(prog: BulkProgress) -> None:
    from ..obs.registry import BULK_STUB, registry
    ref = weakref.ref(prog)

    def _obs() -> dict:
        p = ref()
        return p.obs_section() if p is not None else dict(BULK_STUB)

    registry.register("bulk", _obs)


def _pool_worker_init() -> None:
    """Process-pool initializer (before anything imports jax): the host
    CPU by name, and the shared compile cache for kernel scorers."""
    from ..utils.compile_cache import enable_compile_cache
    from ..utils.device import host_only_worker
    host_only_worker()
    enable_compile_cache()


# --------------------------------------------------------------------------
# backend probe

def _probe_backends(cfg: Dict[str, Any], kind: str,
                    first_shard: str) -> Tuple[str, Dict[str, Any]]:
    """Measure kernel vs arena rows/s on a sample of the first shard and
    pick the faster — the per-host heuristic of docs/PERFORMANCE.md
    "Bulk scoring". Probe states are built and released HERE (master);
    workers rebuild only the winning backend."""
    info: Dict[str, Any] = {"rows": 0}
    sample = None
    best, best_rate = "kernel", -1.0
    try:
        for backend in ("kernel", "arena"):
            c = dict(cfg, backend=backend,
                     digest=f"probe:{backend}:{cfg['digest']}")
            try:
                if backend == "arena":
                    # first bulk run against a bundle may predate any
                    # arena sidecar — publish one so the race is real
                    # (persists for every later nightly run); trainer
                    # families without arena support degrade to kernel
                    from ..catalog import lookup
                    from .weight_arena import ArenaUnsupported
                    try:
                        _ensure_arena_published(
                            lookup(cfg["algo"]).resolve(), c)
                    except ArenaUnsupported:
                        continue
                st = _BackendState(c)
            except (FileNotFoundError, ValueError, KeyError, OSError):
                continue
            try:
                if sample is None:
                    ds = st.decode(kind, first_shard)
                    sample = ds.take(np.arange(min(len(ds), _PROBE_ROWS)))
                    info["rows"] = int(len(sample))
                if len(sample) == 0:
                    continue
                st.score(sample)                       # warm (compiles)
                rate = 0.0
                for _ in range(2):                     # best of 2
                    t0 = time.perf_counter()
                    st.score(sample)
                    dt = time.perf_counter() - t0
                    rate = max(rate, len(sample) / max(dt, 1e-9))
            finally:
                st.release()
            info[f"{backend}_rows_per_sec"] = round(rate, 1)
            if rate > best_rate:
                best, best_rate = backend, rate
    finally:
        sample = None
    info["chosen"] = best
    return best, info


# --------------------------------------------------------------------------
# the bulk job

def bulk_predict(algo: str, input_path: str,
                 output_dir: Optional[str] = None, *,
                 options: str = "",
                 bundle: Optional[str] = None,
                 checkpoint_dir: Optional[str] = None,
                 backend: str = "auto", precision: str = "f32",
                 workers: int = 1, pool: str = "process",
                 batch_size: Optional[int] = None,
                 cache_dir: Optional[str] = None,
                 top_k: int = 0, group_col: Optional[str] = None,
                 feature_col: str = "features",
                 label_col: str = "label") -> Dict[str, Any]:
    """Score a Parquet shard directory (or single Parquet/LIBSVM file)
    through the bulk path; returns the job summary dict. See the module
    docstring for the full contract."""
    from ..catalog import lookup
    from .checkpoint import bundle_step, hold_bundle

    if precision != "f32" and backend == "kernel":
        raise ValueError(
            f"backend=kernel scores f32 only (got precision={precision}); "
            f"quantized tiers score through the arena twins")
    cls = lookup(algo).resolve()
    parser = cls.make_parser(options or "")
    # make_parser skips __init__, so option-driven instance flags (FM's
    # -classification) aren't set — fold the parsed option in explicitly
    classification = getattr(parser, "classification",
                             getattr(parser, "CLASSIFICATION", False))
    o = getattr(parser, "opts", None)
    if o is not None and o.get("classification"):
        classification = True
    parse_kw: Dict[str, Any] = dict(
        feature_col=feature_col, label_col=label_col,
        dims=getattr(parser, "dims", None))
    if getattr(parser, "F", None) is not None and cls.NAME == "train_ffm":
        parse_kw.update(ffm=True, num_fields=parser.F)

    bundle_path, source = resolve_model_bundle(
        algo, bundle=bundle, checkpoint_dir=checkpoint_dir)

    if os.path.isdir(input_path) \
            or input_path.endswith((".parquet", ".pq")):
        from .arrow import _parquet_files
        kind, files = "parquet", _parquet_files(input_path)
    else:
        kind, files = "libsvm", [input_path]
    if output_dir:
        os.makedirs(output_dir, exist_ok=True)

    workers = max(1, int(workers))
    if workers == 1:
        pool = "inline"
    from ..utils.device import DevicePolicyError, cpu_requested
    # process workers run on the host CPU; unless this whole job was
    # asked onto the CPU by name, the jitted kernel is a different device
    # there than in the master — so it is not a backend they can run
    host_pool = pool == "process" and not cpu_requested()
    if host_pool and backend == "kernel":
        raise DevicePolicyError(
            f"--backend kernel with {workers} worker processes needs a "
            "chip per process and this process holds it: use --workers 1 "
            "(one process per chip), --backend arena (host scoring), or "
            "JAX_PLATFORMS=cpu for a CPU kernel pool")
    cfg: Dict[str, Any] = {
        "algo": algo, "options": options or "", "bundle": bundle_path,
        "backend": backend, "precision": precision,
        "batch_size": int(batch_size) if batch_size else None,
        "cache_dir": cache_dir, "parse_kw": parse_kw,
        "output_dir": output_dir, "top_k": int(top_k),
        "group_col": group_col,
    }
    cfg["digest"] = json.dumps(
        {k: v for k, v in cfg.items() if k != "digest"},
        sort_keys=True, default=str)

    prog = BulkProgress()
    prog.active = True
    prog.input = input_path
    prog.output = output_dir
    prog.precision = precision
    prog.workers = workers
    prog.shards_total = len(files)
    prog.bundle = bundle_path
    prog.model_step = bundle_step(bundle_path)
    _register_progress(prog)

    from ..utils.metrics import get_stream
    stream = get_stream()

    with hold_bundle(bundle_path):      # retention must not GC it mid-run
        probe_info = None
        if backend == "auto" and precision == "f32" and not host_pool:
            backend, probe_info = _probe_backends(cfg, kind, files[0])
        elif backend == "auto":
            backend = "arena"           # quantized tiers and host pools
        cfg["backend"] = backend
        cfg["digest"] = json.dumps(
            {k: v for k, v in cfg.items() if k != "digest"},
            sort_keys=True, default=str)
        prog.backend = backend

        if backend == "arena":
            _ensure_arena_published(cls, cfg)
        if stream.enabled:
            stream.emit("bulk", phase="start", **prog.obs_section())
        fl = get_flight()
        if fl.enabled:
            fl.record("bulk.start",
                      f"shards={len(files)}{FS}workers={workers}{FS}"
                      f"backend={backend}{FS}pool={pool}")

        ev = _EvalAccum(classification)
        topk_by_shard: Dict[int, list] = {}
        scored_files: List[Optional[str]] = [None] * len(files)
        busy = 0.0

        # group-aware shard routing (ROADMAP item 5 follow-up): with a
        # fused per-group top-k, shards sharing group values union into
        # one pooled task so no group's candidates split across workers
        components = None
        if top_k and group_col and kind == "parquet" and len(files) > 1:
            components = _group_components(files, group_col)
            if fl.enabled:
                fl.record("bulk.route",
                          f"components={len(components)}{FS}"
                          f"largest={max(len(c) for c in components)}")

        def _fold(res: Dict[str, Any]) -> None:
            nonlocal busy
            ev.add(res.pop("labels"), res.pop("scores"))
            if res["topk"] is not None:
                topk_by_shard[res["index"]] = res["topk"]
            scored_files[res["index"]] = res["out_path"]
            busy += res["busy_seconds"]
            prog.shards_done += 1
            prog.rows_scored += res["rows"]
            prog.busy_seconds = busy
            if stream.enabled:
                stream.emit("bulk", phase="shard", **prog.obs_section())

        try:
            if pool == "inline":
                if components is None:
                    for i, f in enumerate(files):
                        _fold(_score_shard_task(cfg, kind, f, i))
                else:
                    for comp in components:
                        for res in _score_component_task(
                                cfg, kind, [files[i] for i in comp], comp):
                            _fold(res)
            else:
                import concurrent.futures as cf
                if pool == "process":
                    import multiprocessing as mp
                    ex = cf.ProcessPoolExecutor(
                        max_workers=workers,
                        mp_context=mp.get_context("spawn"),
                        initializer=_pool_worker_init)
                else:
                    ex = cf.ThreadPoolExecutor(
                        max_workers=workers, thread_name_prefix="bulk")
                try:
                    if components is None:
                        futs = [ex.submit(_score_shard_task, cfg, kind,
                                          f, i)
                                for i, f in enumerate(files)]
                        for fut in cf.as_completed(futs):
                            _fold(fut.result())
                    else:
                        futs = [ex.submit(_score_component_task, cfg,
                                          kind, [files[i] for i in comp],
                                          comp)
                                for comp in components]
                        for fut in cf.as_completed(futs):
                            for res in fut.result():
                                _fold(res)
                finally:
                    ex.shutdown(wait=True)
        finally:
            # inline/thread pools cache scorer state (and arena mmaps)
            # in THIS process — release on every exit path (GC12)
            if pool != "process":
                _release_states()
            prog.finish()

        topk_file = None
        topk_rows = 0
        if top_k and group_col:
            from ..frame.tools import TopKAccumulator
            acc = TopKAccumulator(top_k)
            for i in sorted(topk_by_shard):     # shard order = arrival
                for g, s, ref in topk_by_shard[i]:
                    acc.add(g, s, ref)
            rows = list(acc.result())
            topk_rows = len(rows)
            if output_dir:
                topk_file = os.path.join(output_dir, "topk.tsv")
                tmp = topk_file + ".tmp"
                with open(tmp, "w") as fh:
                    for g, rank, s, ref in rows:
                        fh.write(f"{g}\t{rank}\t{s:.6g}\t{ref}\n")
                os.replace(tmp, topk_file)

    section = prog.obs_section()
    # keep the finished job's section live after prog is collected (the
    # CLI snapshots AFTER return) — same keys, so stub parity holds
    from ..obs.registry import registry
    registry.register("bulk", lambda s=dict(section): dict(s))
    if stream.enabled:
        stream.emit("bulk", phase="done", **section)
    if fl.enabled:
        fl.record("bulk.done",
                  f"rows={prog.rows_scored}{FS}shards={len(files)}")
    result: Dict[str, Any] = {
        "rows": prog.rows_scored, "shards": len(files),
        "backend": backend, "precision": precision,
        "workers": workers, "pool": pool,
        "bundle": bundle_path, "bundle_source": source,
        "model_step": prog.model_step,
        "elapsed_seconds": section["elapsed_seconds"],
        "rows_per_sec": section["rows_per_sec"],
        "worker_utilization": section["worker_utilization"],
        "metrics": ev.result(),
        "output": output_dir,
        "scored_files": [p for p in scored_files if p],
    }
    if probe_info is not None:
        result["probe"] = probe_info
    if top_k and group_col:
        result["topk_file"] = topk_file
        result["topk_rows"] = topk_rows
        if components is not None:
            result["group_components"] = len(components)
    return result


def _ensure_arena_published(cls, cfg: Dict[str, Any]) -> None:
    """Publish the arena sidecar ONCE in the master before fan-out (N
    workers racing publish_arena would each pay the bundle load)."""
    from .weight_arena import open_arena, publish_arena, try_open_arena
    a = try_open_arena(cfg["bundle"], trainer_name=cls.NAME,
                       precision=cfg["precision"])
    if a is not None:
        a.release()
        return
    t = cls(cfg["options"])
    t.load_bundle(cfg["bundle"])
    open_arena(publish_arena(cfg["bundle"], t)).release()


# --------------------------------------------------------------------------
# smoke: python -m hivemall_tpu.io.bulk --smoke  (run_tests.sh, tsan +
# leaktrack enabled there)

def _synth(n: int, dims: int, max_len: int, seed: int) -> SparseDataset:
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, max_len + 1, n)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    idx = rng.integers(1, dims - 1, int(indptr[-1])).astype(np.int32)
    val = rng.standard_normal(int(indptr[-1])).astype(np.float32)
    w = rng.standard_normal(dims).astype(np.float32)
    margins = np.asarray([w[idx[s:e]] @ val[s:e]
                          for s, e in zip(indptr[:-1], indptr[1:])])
    labels = np.where(margins > 0, 1.0, -1.0).astype(np.float32)
    return SparseDataset(idx, indptr, val, labels)


def _write_empty_shard(path: str) -> None:
    import pyarrow as pa
    import pyarrow.parquet as pq
    off = np.zeros(1, np.int32)
    pq.write_table(pa.table({
        "indices": pa.ListArray.from_arrays(off, pa.array([], pa.int32())),
        "values": pa.ListArray.from_arrays(off, pa.array([], pa.float32())),
        "label": pa.array([], pa.float32())}), path)


def _smoke() -> int:
    import shutil
    import sys
    import tempfile
    from ..catalog import lookup
    from ..frame.evaluation import logloss
    from .weight_arena import score_error_bound, try_open_arena

    from ..testing import leaktrack, tsan
    if tsan.maybe_enable():
        print("bulk smoke: tsan sanitizer ON", file=sys.stderr)
    if leaktrack.maybe_enable():
        print("bulk smoke: leaktrack sanitizer ON", file=sys.stderr)
        leaktrack.snapshot()

    tmp = tempfile.mkdtemp(prefix="hivemall_tpu_bulk_smoke_")
    rc = 0
    try:
        dims = 4096
        opts = f"-dims {dims} -mini_batch 128"
        cls = lookup("train_classifier").resolve()
        trainer = cls(opts)
        trainer.fit(_synth(512, dims, 8, seed=1))
        ckdir = os.path.join(tmp, "ck")
        os.makedirs(ckdir)
        bpath = os.path.join(
            ckdir, f"{cls.NAME}-step{int(trainer._t):010d}.npz")
        trainer.save_bundle(bpath)

        test = _synth(700, dims, 8, seed=2)
        in_dir = os.path.join(tmp, "in")
        from .arrow import write_parquet_shards
        write_parquet_shards(test, in_dir, rows_per_shard=256)
        _write_empty_shard(os.path.join(in_dir, "shard-00003.parquet"))

        def _scores(out_dir):
            import pyarrow.parquet as pq
            from .arrow import _parquet_files
            return np.concatenate([
                pq.read_table(f).column("score").to_numpy(
                    zero_copy_only=False).astype(np.float32)
                for f in _parquet_files(out_dir)])

        # f32 / kernel / 2 worker processes: scored output must
        # BIT-match the offline predict_proba path
        r1 = bulk_predict(
            "train_classifier", in_dir, os.path.join(tmp, "out_f32"),
            options=opts, checkpoint_dir=ckdir, backend="kernel",
            precision="f32", workers=2, pool="process",
            cache_dir=os.path.join(tmp, "cache"))
        want = np.asarray(trainer.predict_proba(test), np.float32)
        got = _scores(os.path.join(tmp, "out_f32"))
        assert r1["rows"] == 700 and r1["shards"] == 4, r1
        assert np.array_equal(got, want), \
            f"f32 bulk != predict_proba (max delta " \
            f"{np.abs(got - want).max()})"
        ll = logloss(test.labels, want)
        assert abs(r1["metrics"]["logloss"] - ll) < 1e-4, r1["metrics"]
        assert r1["bundle_source"] == "newest" and r1["rows_per_sec"] > 0

        # int8 / arena / 2 workers: within the published error bound
        r2 = bulk_predict(
            "train_classifier", in_dir, os.path.join(tmp, "out_int8"),
            options=opts, checkpoint_dir=ckdir, backend="arena",
            precision="int8", workers=2, pool="process",
            cache_dir=os.path.join(tmp, "cache"))
        got8 = _scores(os.path.join(tmp, "out_int8"))
        arena = try_open_arena(bpath, trainer_name=cls.NAME,
                               precision="int8")
        assert arena is not None
        try:
            bound = np.empty(700, np.float32)
            for s, b in score_batches(test, 256):
                nv = b.n_valid or b.batch_size
                bound[s:s + nv] = np.asarray(
                    score_error_bound(arena, "int8", b),
                    np.float32)[:nv] / 4.0      # sigmoid is 1/4-Lipschitz
        finally:
            arena.release()
        over = np.abs(got8 - want) - (bound + 1e-6)
        assert (over <= 0).all(), \
            f"int8 bulk outside bound by {over.max()}"
        assert r2["backend"] == "arena" and r2["rows"] == 700

        print(json.dumps({"f32": {k: r1[k] for k in
                                  ("rows", "rows_per_sec", "backend",
                                   "worker_utilization", "metrics")},
                          "int8": {k: r2[k] for k in
                                   ("rows", "rows_per_sec", "backend")}},
                         default=str))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if leaktrack.enabled():
        n = leaktrack.check_and_report("bulk smoke leaktrack")
        print(f"bulk smoke leak_census: {'OK' if n == 0 else 'FAILED'} "
              f"({n} leaked resource(s) after pool drain)",
              file=sys.stderr)
        rc += 1 if n else 0
    print("bulk smoke: PASS" if rc == 0 else "bulk smoke: FAIL",
          file=sys.stderr)
    return rc


if __name__ == "__main__":
    import argparse
    import sys
    ap = argparse.ArgumentParser(prog="hivemall_tpu.io.bulk")
    ap.add_argument("--smoke", action="store_true")
    a = ap.parse_args()
    if a.smoke:
        sys.exit(_smoke())
    ap.error("only --smoke is supported; use `hivemall_tpu predict` "
             "for real jobs")
