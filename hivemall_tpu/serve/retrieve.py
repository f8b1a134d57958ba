"""RetrievalEngine — online top-k over arena-published factor tables.

ROADMAP item 3's last serving-shaped gap: the recommender family
(models/mf.py MF/BPR, models/word2vec.py) trains millions of examples
per second but had no online consumer.  This engine is the factor
twin of serve/engine.py's PredictEngine — same bundle directory, same
``follow`` modes and ``PROMOTED`` pointer, same atomic model-ref swap
under hot reload — but its request shape is *gather two embedding rows
and rank*, not *score one feature row*:

- ``user → top-k items``: gather ``P[u]``, rank every item by
  ``mu + P[u].Q[i] (+ bu[u] + bi[i])``;
- ``item → k neighbors``: rank every other item by cosine over ``Q``.

Two tiers answer each query (docs/SERVING.md "Retrieval plane"):

- **exact**: one full-table matvec over the mmap'd arena ``Q`` (or the
  jitted kernel — auto-probed like io/bulk.py's backend probe, numpy
  wins on CPU hosts at serve shapes), then top-k under the EXACT
  ``frame.tools.each_top_k`` semantics (descending score, ties to the
  earlier id) — bit-matching the offline oracle;
- **lsh**: knn/ann.py signed-random-projection candidates (dot-product
  queries go through the MIPS augmentation so the angular guarantee
  applies), exact rescore over the candidate set only.  Recall against
  the exact tier is a promotion guardrail (serve/promote.py), not a
  silent best-effort.

Model versions load from the weight arena (io/weight_arena.py "factor"
family — published by promotion or self-published on first use, like
PredictEngine's arena path) and carry their LSH index; a hot reload
builds the NEW index fully before the atomic ref swap, so in-flight
queries always see one coherent (tables, index) pair and a mid-traffic
reload drops zero requests.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..io.checkpoint import (bundle_step, is_rejected, list_bundles,
                             read_promoted)
from ..knn.ann import (SrpIndex, exact_top_ids, mips_augment, mips_query,
                       recall_at_k)
from ..obs.flight import FS, get_flight

__all__ = ["RetrievalEngine", "retrieval_stub"]

#: query tuple layout: (kind, id, k, tier)
KIND_USER_ITEMS = 0
KIND_ITEM_NEIGHBORS = 1
TIER_EXACT = 0
TIER_LSH = 1


def retrieval_stub() -> dict:
    """The obs ``retrieval`` section's inactive form — key-for-key the
    live :meth:`RetrievalEngine.obs_section` shape (GC05 stub parity,
    pinned by tests/test_obs.py). Nested dicts are copied so the stub is
    never shared mutable state."""
    from ..obs.registry import RETRIEVAL_STUB
    return {**RETRIEVAL_STUB, "index": dict(RETRIEVAL_STUB["index"]),
            "arena": dict(RETRIEVAL_STUB["arena"])}


@dataclass
class _RModel:
    """One immutable retrieval model version — swapped as a single
    reference; tables, gathers AND the LSH indexes travel together."""
    arena: Any
    step: int
    path: Optional[str]
    k: int                               # factor rank
    mu: float
    gP: Any                              # user-row gather at precision
    gbu: Optional[Any]                   # user-bias gather or None
    Qd: np.ndarray                       # [I, k] item table (f32 view)
    bi: Optional[np.ndarray]             # [I] item bias or None
    qnorms: np.ndarray                   # [I] item vector norms
    index_mips: SrpIndex                 # dot-product (user) candidates
    index_cos: SrpIndex                  # cosine (neighbor) candidates
    vocab: Optional[list]                # id -> label (word2vec arenas)
    build_seconds: float
    backend: str = "numpy"
    index_recall: float = 0.0            # build-time LSH-vs-exact recall@10
    bundle_mtime: Optional[float] = None
    loaded_at: float = field(default_factory=time.monotonic)
    Qdev: Any = None                     # device-staged Q (kernel backend)


class RetrievalEngine:
    """Hot-reloadable factor retrieval over a watched bundle directory."""

    def __init__(self, algo: str = "train_mf_sgd", options: str = "", *,
                 bundle: Optional[str] = None,
                 checkpoint_dir: Optional[str] = None,
                 follow: str = "newest",
                 precision: str = "f32",
                 max_batch: int = 256,
                 max_k: int = 100,
                 k_default: int = 10,
                 tier: str = "exact",
                 lsh_tables: int = 12,
                 lsh_bits: int = 10,
                 rescore: str = "auto",
                 watch_interval: float = 2.0,
                 seed: int = 0x5EED):
        from ..catalog import lookup
        from ..io.weight_arena import PRECISIONS
        if follow not in ("newest", "promoted"):
            raise ValueError(f"unknown follow mode {follow!r} "
                             f"(newest or promoted)")
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r} "
                             f"(one of {PRECISIONS})")
        if tier not in ("exact", "lsh"):
            raise ValueError(f"unknown tier {tier!r} (exact or lsh)")
        if rescore not in ("auto", "numpy", "kernel"):
            raise ValueError(f"unknown rescore backend {rescore!r} "
                             f"(auto, numpy or kernel)")
        self.algo = algo
        self.options = options
        self.follow = follow
        self.precision = precision
        self.max_batch = int(max_batch)
        self.max_k = int(max_k)
        self.k_default = min(int(k_default), self.max_k)
        self.tier = tier
        self.lsh_tables = int(lsh_tables)
        self.lsh_bits = int(lsh_bits)
        self.rescore = rescore
        self.watch_interval = float(watch_interval)
        self.seed = int(seed)
        self._cls = lookup(algo).resolve()
        self._flight = get_flight()
        self._reload_lock = threading.Lock()
        self._watch_thread: Optional[threading.Thread] = None
        self._watch_stop = threading.Event()
        self._dot_jit = None
        # counters (obs `retrieval` section)
        self.reloads = 0
        self.reload_failures = 0
        self.arena_loads = 0
        self.arena_publishes = 0
        self.queries_user = 0
        self.queries_item = 0
        self.queries_lsh = 0
        self.queries_exact = 0
        self.empty_candidates = 0        # LSH misses that fell back exact
        self.last_reload_error: Optional[str] = None
        # known-bad bundle memo (cheap (mtime, size) identity — the full
        # rewritten-in-place paranoia lives in PredictEngine; retrieval
        # bundles come off the same promotion pipeline)
        self._failed: Dict[str, tuple] = {}
        self._promoted_key: Optional[tuple] = None
        self._batcher = None
        ckdir = checkpoint_dir
        self.checkpoint_dir = ckdir
        if bundle:
            self._model: Optional[_RModel] = self._load_model(bundle)
        elif ckdir:
            m = None
            if self.follow == "promoted":
                m = self._load_promoted()
            if m is None:
                m = self._load_newest(min_step=-1)
            if m is None:
                raise FileNotFoundError(
                    f"no usable {algo} checkpoint bundle in {ckdir!r}")
            self._model = m
        else:
            raise ValueError(
                "RetrievalEngine needs a model source: pass bundle=... "
                "or checkpoint_dir=...")
        self._register_obs()

    # -- model loading -------------------------------------------------------
    def _load_model(self, path: str) -> _RModel:
        """Open (or self-publish) the factor arena for ``path``, map the
        tables and build both LSH indexes — the whole version assembles
        BEFORE any caller sees it (atomic swap in poll/reload)."""
        from ..io.weight_arena import (ArenaUnsupported, open_arena,
                                      publish_arena, try_open_arena)
        t0 = time.monotonic()
        arena = try_open_arena(path, trainer_name=self._cls.NAME,
                               precision=self.precision)
        if arena is None:
            t = self._cls(self.options)
            t.load_bundle(path)
            arena = open_arena(publish_arena(path, t))
            self.arena_publishes += 1
        try:
            if arena.family != "factor":
                raise ArenaUnsupported(
                    f"retrieval needs a factor-family arena, "
                    f"{path!r} publishes {arena.family!r}")
            hdr = arena.header
            Qd = arena.table("Q", self.precision)
            bi = arena.table("bi", self.precision) \
                if hdr.get("item_bias") else None
            gP = arena.gather("P", self.precision)
            gbu = arena.gather("bu", self.precision) \
                if hdr.get("user_bias") else None
            qnorms = np.sqrt((np.asarray(Qd, np.float32) ** 2).sum(-1)
                             ).astype(np.float32)
            aug, _m = mips_augment(Qd, bias=bi)
            index_mips = SrpIndex(aug, n_tables=self.lsh_tables,
                                  n_bits=self.lsh_bits, seed=self.seed)
            index_cos = SrpIndex(np.asarray(Qd, np.float32),
                                 n_tables=self.lsh_tables,
                                 n_bits=self.lsh_bits, seed=self.seed + 1)
        except Exception:
            arena.release()              # GC12: a failed assembly must
            raise                        # not leak the mmap views
        m = _RModel(arena, arena.step, path, int(hdr.get("k") or 0),
                    float(hdr.get("mu") or 0.0), gP, gbu, Qd, bi, qnorms,
                    index_mips, index_cos, hdr.get("vocab"),
                    round(time.monotonic() - t0, 4),
                    bundle_mtime=self._mtime(path),
                    index_recall=self._index_recall(arena, Qd, bi,
                                                    index_mips))
        m.backend = self._pick_backend(m)
        self.arena_loads += 1
        fl = self._flight
        if fl.enabled:
            fl.record("retrieve.index",
                      f"rows={m.Qd.shape[0]}{FS}tables={self.lsh_tables}"
                      f"{FS}bits={self.lsh_bits}{FS}"
                      f"recall={m.index_recall}{FS}"
                      f"build_s={m.build_seconds}{FS}backend={m.backend}")
        return m

    @staticmethod
    def _index_recall(arena, Qd, bi, index_mips: SrpIndex) -> float:
        """Build-time self-check of the fresh candidate tier: recall@10
        of LSH+rescore vs exact search over a deterministic user sample,
        published as the obs gauge ``retrieval.index.recall_at_k`` (the
        promotion gate recomputes its own on the CANDIDATE's tables;
        this one tracks what the live index actually serves). ~16 full
        scans per reload — noise next to the index build matmul."""
        P = np.asarray(arena.table("P", "f32"), np.float32)
        rows = Qd.shape[0]
        if len(P) == 0 or rows == 0:
            return 0.0
        k = min(10, rows)
        Qf = np.asarray(Qd, np.float32)
        has_bias = bi is not None
        rng = np.random.default_rng(0xC0FFEE)
        users = rng.choice(len(P), size=min(16, len(P)), replace=False)
        recs = []
        for u in users:
            s = Qf @ P[u]
            if has_bias:
                s = s + bi
            exact = exact_top_ids(s, k)
            cand = index_mips.candidates(
                mips_query(P[u], has_bias=has_bias))
            if len(cand) == 0:
                recs.append(0.0)
                continue
            recs.append(recall_at_k(cand[exact_top_ids(s[cand], k)],
                                    exact))
        return round(float(np.mean(recs)), 4)

    @staticmethod
    def _mtime(path: str) -> Optional[float]:
        try:
            return os.path.getmtime(path)
        except OSError:
            return None

    def _pick_backend(self, m: _RModel) -> str:
        """Auto-probe the full-table rescore backend like io/bulk.py's
        arena-vs-kernel probe: time one exact matvec each way on the real
        table and keep the faster. At serve shapes the per-call XLA
        dispatch usually loses to the numpy matvec on CPU hosts."""
        if self.rescore != "auto":
            return self.rescore
        pu = np.zeros(max(1, m.k), np.float32)
        t0 = time.monotonic()
        for _ in range(3):
            _ = m.Qd @ pu
        t_np = time.monotonic() - t0
        try:
            self._kernel_dot(m, pu)      # compile + stage outside timing
            t0 = time.monotonic()
            for _ in range(3):
                self._kernel_dot(m, pu)
            t_k = time.monotonic() - t0
        except Exception:                # noqa: BLE001 — a kernel-path
            return "numpy"               # failure degrades to numpy
        return "kernel" if t_k < t_np else "numpy"

    def _kernel_dot(self, m: _RModel, pu: np.ndarray) -> np.ndarray:
        """Jitted full-table matvec, table staged on device once per
        model version. The fetch is the product (the score vector feeds
        host-side top-k)."""
        import jax
        import jax.numpy as jnp
        if self._dot_jit is None:
            self._dot_jit = jax.jit(lambda Q, p: Q @ p)
        if m.Qdev is None:
            m.Qdev = jnp.asarray(np.asarray(m.Qd, np.float32))
        return np.asarray(self._dot_jit(m.Qdev, jnp.asarray(pu)),
                          np.float32)    # graftcheck: disable=GC07

    def _load_newest(self, min_step: int) -> Optional[_RModel]:
        listed = list_bundles(self.checkpoint_dir, self._cls.NAME)
        if self._failed:
            live = set(listed)
            self._failed = {p: i for p, i in self._failed.items()
                            if p in live}
        for path in listed:
            step = bundle_step(path)
            if step is None or step <= min_step:
                break                    # list is newest-first
            if is_rejected(path):
                continue
            try:
                st = os.stat(path)
            except OSError:
                continue
            if self._failed.get(path) == (st.st_mtime, st.st_size):
                continue
            try:
                return self._load_model(path)
            except Exception as e:       # noqa: BLE001 — a bad bundle
                # degrades to "keep serving", never takes retrieval down
                self._note_load_failure(path, e)
        return None

    def _load_promoted(self) -> Optional[_RModel]:
        """Same pointer discipline as PredictEngine._load_promoted: serve
        the pointer's entry, or during a canary bake the prior stable
        entry (history head) — a solo engine never self-joins a canary
        cohort."""
        man = read_promoted(self.checkpoint_dir)
        if man is None:
            return None
        cur = man["current"]
        if man.get("state") == "canary" and man.get("history"):
            cur = man["history"][0]
        key = (str(cur.get("bundle")), cur.get("digest"))
        if key == self._promoted_key:
            return None
        path = os.path.join(self.checkpoint_dir, key[0])
        try:
            st = os.stat(path)
            if self._failed.get(path) == (st.st_mtime, st.st_size):
                return None
        except OSError:
            return None
        try:
            model = self._load_model(path)
        except Exception as e:           # noqa: BLE001 — same degrade
            self._note_load_failure(path, e)
            return None
        self._promoted_key = key
        return model

    def _note_load_failure(self, path: str, e: Exception) -> None:
        self.reload_failures += 1
        self.last_reload_error = f"{path}: {type(e).__name__}: {e}"
        fl = self._flight
        if fl.enabled:
            fl.record("retrieve.reload",
                      f"ok=0{FS}bundle={os.path.basename(path)}{FS}"
                      f"err={type(e).__name__}")
        try:
            st = os.stat(path)
            self._failed[path] = (st.st_mtime, st.st_size)
        except OSError:
            pass

    # -- hot reload ----------------------------------------------------------
    @property
    def ready(self) -> bool:
        """No warmup phase: a retrieval model is servable the moment its
        tables mapped and its index built (nothing jits on the default
        numpy backend)."""
        return self._model is not None

    @property
    def model_step(self) -> int:
        m = self._model
        return m.step if m is not None else -1

    @property
    def model_path(self) -> Optional[str]:
        m = self._model
        return m.path if m is not None else None

    @property
    def model_age_seconds(self) -> Optional[float]:
        m = self._model
        return round(time.monotonic() - m.loaded_at, 3) \
            if m is not None else None

    @property
    def bundle_age_seconds(self) -> Optional[float]:
        m = self._model
        mt = m.bundle_mtime if m is not None else None
        # file mtimes are wall-clock; only wall "now" can age them
        return None if mt is None \
            else round(time.time() - mt, 3)  # graftcheck: disable=GC02

    @property
    def arena_mapped_bytes(self) -> int:
        m = self._model
        return int(m.arena.mapped_bytes) if m is not None else 0

    @property
    def platform(self) -> str:
        """/healthz peer of PredictEngine.platform: the JAX backend when
        the rescore runs the jitted kernel, "host" for the numpy path."""
        m = self._model
        if m is not None and m.backend == "kernel":
            import jax
            return jax.default_backend()
        return "host"

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        return self.ready

    def poll(self) -> bool:
        """One watched-directory check under the follow mode; atomic
        model-ref swap on change. In-flight queries finish on the version
        they grabbed — a mid-traffic factor reload drops zero requests."""
        if not self.checkpoint_dir:
            return False
        with self._reload_lock:
            if self.follow == "promoted":
                m = self._load_promoted()
            else:
                m = self._load_newest(min_step=self._model.step)
            if m is None:
                return False
            self._swap(m)
            return True

    def reload(self, path: Optional[str] = None) -> bool:
        """Force a reload — same trust boundary as PredictEngine.reload:
        an explicit path must live inside the watched directory."""
        if path is None:
            return self.poll()
        if not self.checkpoint_dir:
            raise ValueError(
                "explicit-path reload needs a watched checkpoint dir")
        real = os.path.realpath(path)
        root = os.path.realpath(self.checkpoint_dir)
        if os.path.commonpath([real, root]) != root:
            raise ValueError(
                "reload path is outside the watched checkpoint directory")
        with self._reload_lock:
            try:
                m = self._load_model(path)
            except Exception as e:       # noqa: BLE001 — same degrade
                self._note_load_failure(path, e)
                return False
            self._swap(m)
            return True

    def _swap(self, m: _RModel) -> None:
        old = self._model
        old_step = old.step if old is not None else -1
        self._model = m                  # atomic ref swap
        self.reloads += 1
        if old is not None:
            old.arena.release()          # GC12: retired version unmaps
        fl = self._flight
        if fl.enabled:
            fl.record("retrieve.reload",
                      f"ok=1{FS}from={old_step}{FS}to={m.step}{FS}"
                      f"bundle={os.path.basename(m.path or '')}")

    def start_watch(self) -> None:
        if self._watch_thread is not None or not self.checkpoint_dir:
            return
        self._watch_stop.clear()

        def run():
            while not self._watch_stop.wait(self.watch_interval):
                try:
                    self.poll()
                except Exception as e:   # noqa: BLE001 — watcher survives
                    self.last_reload_error = f"{type(e).__name__}: {e}"

        self._watch_thread = threading.Thread(
            target=run, name="retrieve-watch", daemon=True)
        self._watch_thread.start()

    def close(self) -> None:
        self._watch_stop.set()
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=5)
            self._watch_thread = None
        with self._reload_lock:
            m = self._model
            self._model = None
        if m is not None:
            m.arena.release()

    # -- queries -------------------------------------------------------------
    def parse_query(self, obj) -> Tuple[int, int, int, int]:
        """One request query object → the plane row tuple. ``{"user": id}``
        asks for top-k items, ``{"item": id}`` for k neighbors; optional
        ``"k"`` (1..max_k) and ``"tier"`` ("exact"/"lsh") per query.
        Malformed queries raise ValueError (the front end's 400)."""
        if not isinstance(obj, dict):
            raise ValueError("each query must be a JSON object")
        if "user" in obj:
            kind, qid = KIND_USER_ITEMS, obj["user"]
        elif "item" in obj:
            kind, qid = KIND_ITEM_NEIGHBORS, obj["item"]
        else:
            raise ValueError('query needs "user" or "item"')
        qid = int(qid)
        if qid < 0:
            raise ValueError(f"id {qid} must be >= 0")
        k = int(obj.get("k", self.k_default))
        if not 1 <= k <= self.max_k:
            raise ValueError(f"k {k} out of range 1..{self.max_k}")
        tier = obj.get("tier", self.tier)
        if tier not in ("exact", "lsh"):
            raise ValueError(f"unknown tier {tier!r} (exact or lsh)")
        return (kind, qid, k,
                TIER_EXACT if tier == "exact" else TIER_LSH)

    def exact_scores(self, kind: int, qid: int) -> np.ndarray:
        """The exact tier's full score vector for one query — the public
        oracle surface: the smoke's each_top_k bit-match and the
        promotion gate's recall@k leg both score THROUGH this method, so
        the oracle can never drift from the serving arithmetic."""
        return self._exact_scores(self._model, kind, qid)

    def _exact_scores(self, m: _RModel, kind: int, qid: int) -> np.ndarray:
        rows = m.Qd.shape[0]
        if kind == KIND_USER_ITEMS:
            pu = m.gP(np.int64(qid))
            if m.backend == "kernel":
                s = self._kernel_dot(m, np.asarray(pu, np.float32))
            else:
                s = m.Qd @ pu
            if m.bi is not None:
                s = s + m.bi
            const = m.mu + (float(m.gbu(np.int64(qid)))
                            if m.gbu is not None else 0.0)
            if const != 0.0:
                s = s + np.float32(const)
            return np.asarray(s, np.float32)
        qid = min(qid, rows - 1)
        qi = np.asarray(m.Qd[qid], np.float32)
        s = (m.Qd @ qi) / np.maximum(
            m.qnorms * np.float32(m.qnorms[qid]), np.float32(1e-12))
        s = np.asarray(s, np.float32)
        s[qid] = -np.inf                 # a vector is not its own neighbor
        return s

    def _exact_topk(self, m: _RModel, kind: int, qid: int, k: int):
        s = self._exact_scores(m, kind, qid)
        ids = exact_top_ids(s, k)
        return ids, s[ids]

    def _lsh_topk(self, m: _RModel, kind: int, qid: int, k: int):
        """Candidate generation + exact rescore over the candidates only.
        An empty candidate set (every table missed) falls back to the
        exact tier — availability over speed, counted so the obs section
        shows a mistuned index instead of silently slow queries."""
        rows = m.Qd.shape[0]
        if kind == KIND_USER_ITEMS:
            pu = np.asarray(m.gP(np.int64(qid)), np.float32)
            cand = m.index_mips.candidates(
                mips_query(pu, has_bias=m.bi is not None))
            if len(cand) == 0:
                self.empty_candidates += 1
                return self._exact_topk(m, kind, qid, k)
            s = m.Qd[cand] @ pu
            if m.bi is not None:
                s = s + m.bi[cand]
            const = m.mu + (float(m.gbu(np.int64(qid)))
                            if m.gbu is not None else 0.0)
            if const != 0.0:
                s = s + np.float32(const)
        else:
            qid = min(qid, rows - 1)
            qi = np.asarray(m.Qd[qid], np.float32)
            cand = m.index_cos.candidates(qi)
            cand = cand[cand != qid]
            if len(cand) == 0:
                self.empty_candidates += 1
                return self._exact_topk(m, kind, qid, k)
            s = (m.Qd[cand] @ qi) / np.maximum(
                m.qnorms[cand] * np.float32(m.qnorms[qid]),
                np.float32(1e-12))
        s = np.asarray(s, np.float32)
        top = exact_top_ids(s, k)
        return cand[top], s[top]

    def retrieve_rows(self, rows: List[tuple]) -> np.ndarray:
        """Serve parsed query tuples against the current model version.
        Returns float32 ``[n, max_k, 2]``: ``[..., 0]`` ranked ids
        (−1 padding past each query's k or past the candidate count),
        ``[..., 1]`` their scores — a shape both planes' result slicing
        (``scores[off:off+n]``) handles unchanged."""
        return self._retrieve_with(self._model, rows)

    def retrieve_rows_versioned(self, rows: List[tuple]):
        """Batcher fn for the serving planes: ``(results, step)`` where
        step names the version that actually ranked this batch."""
        m = self._model
        return self._retrieve_with(m, rows), m.step

    def _retrieve_with(self, m: _RModel, rows: List[tuple]) -> np.ndarray:
        n = len(rows)
        out = np.full((n, self.max_k, 2), -1.0, np.float32)
        out[:, :, 1] = 0.0
        for r, (kind, qid, k, tier) in enumerate(rows):
            if tier == TIER_LSH:
                ids, sc = self._lsh_topk(m, kind, qid, k)
                self.queries_lsh += 1
            else:
                ids, sc = self._exact_topk(m, kind, qid, k)
                self.queries_exact += 1
            if kind == KIND_USER_ITEMS:
                self.queries_user += 1
            else:
                self.queries_item += 1
            kk = min(len(ids), k)
            out[r, :kk, 0] = ids[:kk]
            out[r, :kk, 1] = sc[:kk]
        return out

    def labels(self, ids: Sequence[int]) -> Optional[List[Optional[str]]]:
        """id → label translation for vocab-carrying arenas (word2vec);
        None when the serving arena has no vocabulary."""
        m = self._model
        if m is None or not m.vocab:
            return None
        v = m.vocab
        return [v[i] if 0 <= i < len(v) else None for i in ids]

    # -- obs (docs/OBSERVABILITY.md `retrieval` section) ---------------------
    def attach_batcher(self, batcher) -> None:
        """The serving plane's batcher, surfaced under ``plane`` in the
        retrieval section (mirrors PredictEngine.attach_batcher)."""
        self._batcher = batcher

    def obs_section(self) -> dict:
        m = self._model
        b = self._batcher
        idx = dict(retrieval_stub()["index"])
        if m is not None:
            idx.update(m.index_mips.stats())
            idx["build_seconds"] = m.build_seconds
            idx["recall_at_k"] = m.index_recall
        return {
            "configured": True,
            "algo": self.algo,
            "follow": self.follow,
            "ready": self.ready,
            "model_step": self.model_step,
            "model_age_seconds": self.model_age_seconds,
            "bundle_age_seconds": self.bundle_age_seconds,
            "model_path": self.model_path,
            "reloads": self.reloads,
            "reload_failures": self.reload_failures,
            "watching": bool(self._watch_thread is not None),
            "precision": self.precision,
            "tier": self.tier,
            "max_k": self.max_k,
            "rescore_backend": m.backend if m is not None else None,
            "queries_user": self.queries_user,
            "queries_item": self.queries_item,
            "queries_lsh": self.queries_lsh,
            "queries_exact": self.queries_exact,
            "empty_candidates": self.empty_candidates,
            "last_reload_error": self.last_reload_error,
            "index": idx,
            "arena": {
                "active": bool(m is not None),
                "mapped_bytes": self.arena_mapped_bytes,
                "loads": self.arena_loads,
                "publishes": self.arena_publishes,
            },
            "plane": b.stats() if b is not None else None,
        }

    def _register_obs(self) -> None:
        import weakref
        from ..obs.registry import registry
        ref = weakref.ref(self)

        def retrieval() -> dict:
            e = ref()
            return e.obs_section() if e is not None else retrieval_stub()

        registry.register("retrieval", retrieval)
