"""PredictEngine — model lifecycle + compiled bucketed predict.

One engine serves one algorithm config (the trainer options used at
training time). It loads a full-state checkpoint bundle (io.checkpoint:
manifest digest validated on load, so a corrupt or truncated file can
never become the serving model), builds an output-space scorer from the
trainer (``LearnerBase.make_scorer`` — the SAME kernels and sigmoid the
offline ``predict_proba`` path runs, so online scores bit-match offline),
and scores request rows through SHAPE-BUCKETED padded batches:

- batch dimension padded to the power-of-two bucket of the row count
  (io.sparse.bucket_size), row length to the power-of-two bucket of the
  widest row — so jit compiles are bounded at ~log2(max_batch) x
  log2(max_len) shapes instead of one per request shape, and ``warmup()``
  pre-compiles the batch buckets at startup so no request pays XLA
  compile latency;

- hot-reload: ``poll()`` (driven by a watcher thread or the ``/reload``
  endpoint) checks the watched ``-checkpoint_dir`` for an autosaved
  bundle with a HIGHER step than the serving model, loads it into a
  FRESH trainer (never mutating the live one), and swaps the
  ``(trainer, scorer)`` pair behind one atomic reference — in-flight
  predictions keep the ref they grabbed, so a swap never drops or mixes
  versions mid-batch. A bundle that fails validation is skipped (counted,
  remembered by (mtime, size) + a cheap head/tail content tag so a bad
  file isn't re-read every poll but a file REWRITTEN IN PLACE — even
  with its mtime preserved — is re-examined) and the old model keeps
  serving. Bundles quarantined with a ``.rejected`` marker (a failed
  promotion gate, an auto-rollback) are never considered. Atomic
  checkpoint writes + the step-pattern filter mean a live trainer
  autosaving into the same directory is safe.

- ``follow="promoted"`` (docs/RELIABILITY.md "Promotion and rollback"):
  instead of "newest step wins", the engine follows the directory's
  atomic ``PROMOTED`` pointer — ``poll()`` swaps whenever the pointer
  names a DIFFERENT bundle than the one serving, including a LOWER step
  (that is exactly what a rollback is). With no pointer yet (bootstrap,
  before the first gate pass) it falls back to the newest usable bundle.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..io.checkpoint import (bundle_step, is_rejected, list_bundles,
                             read_promoted)
from ..io.sparse import SparseBatch, bucket_size
from ..obs.flight import FS, get_flight
from ..obs.trace import get_tracer

__all__ = ["PredictEngine"]

# serving never emits model rows, so the hashed-id -> name memo a
# trainer's _parse_row keeps is dead weight here; cap it so a stream of
# novel feature names can't grow host memory without bound
_NAMES_CAP = 1 << 20


@dataclass
class _Model:
    """One immutable model version — swapped as a single reference."""
    trainer: Any
    scorer: Any                      # fn(SparseBatch) -> np.float32 [B]
    step: int
    path: Optional[str]
    loaded_at: float = field(default_factory=time.monotonic)
    needs_field: bool = False        # FFM-style rows carry field ids
    bundle_mtime: Optional[float] = None   # source file mtime (bundle age)
    # zero-copy serving (io.weight_arena): the mmap'd arena this version
    # scores from, or None for the classic trainer-scorer path. When set,
    # ``trainer`` is a parse-only facade (LearnerBase.make_parser) — no
    # dims-sized tables were allocated for this version
    arena: Any = None
    precision: str = "f32"


class PredictEngine:
    """Compiled bucketed predict over hot-reloadable checkpoint bundles."""

    def __init__(self, algo: str, options: str = "", *,
                 bundle: Optional[str] = None,
                 checkpoint_dir: Optional[str] = None,
                 max_batch: int = 256,
                 max_row_features: int = 4096,
                 min_len_bucket: int = 8,
                 watch_interval: float = 2.0,
                 warmup=True,
                 warmup_len: int = 16,
                 follow: str = "newest",
                 arena: str = "auto",
                 precision: str = "f32"):
        from ..catalog import lookup
        from ..io.weight_arena import PRECISIONS
        if follow not in ("newest", "promoted"):
            raise ValueError(f"unknown follow mode {follow!r} "
                             f"(newest or promoted)")
        if arena not in ("auto", "off", "force"):
            raise ValueError(f"unknown arena mode {arena!r} "
                             f"(auto, off or force)")
        if precision not in PRECISIONS:
            raise ValueError(f"unknown serve precision {precision!r} "
                             f"(one of {PRECISIONS})")
        if precision != "f32" and arena == "off":
            raise ValueError(f"precision {precision!r} needs the weight "
                             f"arena (arena='off' only serves f32)")
        self.algo = algo
        self.options = options
        self.follow = follow
        # zero-copy serving policy (docs/PERFORMANCE.md "Weight arena +
        # quantized scoring"): quantized precisions ALWAYS score from the
        # mmap'd arena; f32 keeps the trainer's jitted scorer — the
        # numpy arena kernels are numerically equivalent but not
        # bit-identical to XLA, and "quantization off" must bit-match
        # the pre-arena path. arena="force" opts f32 into arena scoring
        # too (zero-copy replicas at ulp-level score deviation).
        self.arena_mode = arena
        self.precision = precision
        self._arena_scoring = (precision != "f32" or arena == "force")
        self._cls = lookup(algo).resolve()
        self.max_batch = int(max_batch)
        self.max_row_features = int(max_row_features)
        self.min_len_bucket = int(min_len_bucket)
        self.watch_interval = float(watch_interval)
        self._tracer = get_tracer()
        # flight recorder: model swaps are exactly the events a
        # post-mortem needs to anchor "which version was serving when it
        # died" — record every reload edge (success AND failure)
        self._flight = get_flight()
        self._reload_lock = threading.Lock()   # serializes poll()/reload()
        self._watch_thread: Optional[threading.Thread] = None
        self._watch_stop = threading.Event()
        # readiness (the /healthz gate external LBs and the fleet router
        # key on): set once warmup completes — or immediately when warmup
        # was explicitly opted out (the operator chose to serve cold).
        # warmup="background" starts the HTTP surface cold and flips ready
        # when a daemon thread finishes pre-compiling — the fleet-replica
        # recipe (router excludes the replica until it reports ready).
        self._ready = threading.Event()
        self._warmed_len: Optional[int] = None  # set once warmup() ran
        # counters (obs `serve` section)
        self.reloads = 0
        self.reload_failures = 0
        self.arena_loads = 0         # versions served straight off an arena
        self.arena_publishes = 0     # arenas this engine had to publish
        self.arena_fallbacks = 0     # arena wanted but bundle path used
        self.last_reload_error: Optional[str] = None
        # known-bad bundle memo: path -> (mtime, size, head/tail sha) —
        # the identity a skip decision is re-validated against (a file
        # rewritten in place is re-examined, see _ident_matches)
        self._failed: Dict[str, tuple] = {}
        # the pointer identity served under follow="promoted":
        # (bundle name, digest) — poll() compares, never re-loads blindly
        self._promoted_key: Optional[tuple] = None
        self._batcher = None
        # initial model: an explicit bundle wins; otherwise the newest
        # usable autosave in the watched directory. The option fallback
        # parses the grammar only — constructing a trainer here would
        # allocate (and discard) a full dims-sized table
        ckdir = checkpoint_dir
        if not ckdir and hasattr(self._cls, "spec"):
            try:
                ckdir = self._cls.spec().parse(options).get(
                    "checkpoint_dir")
            except Exception:          # noqa: BLE001 — bad options fail
                ckdir = None           # properly at trainer construction
        self.checkpoint_dir = ckdir
        if bundle:
            self._model = self._load_model(bundle)
        elif ckdir:
            m = None
            if self.follow == "promoted":
                m = self._load_promoted()
            if m is None:                # no pointer yet: bootstrap from
                m = self._load_newest(min_step=-1)   # the newest usable
            if m is None:
                # a bundle that failed to LOAD is reported with its cause:
                # a replica that lost the chip to another process dies in
                # backend init, which is not a missing bundle
                why = (f" (last load error: {self.last_reload_error})"
                       if self.last_reload_error else "")
                raise FileNotFoundError(
                    f"no usable {algo} checkpoint bundle in {ckdir!r}{why}")
            self._model = m
        else:
            raise ValueError(
                "PredictEngine needs a model source: pass bundle=... or "
                "checkpoint_dir=... (or -checkpoint_dir in options)")
        self._register_obs()
        if warmup == "background":
            t = threading.Thread(target=self._warm_bg, args=(warmup_len,),
                                 name="serve-warmup", daemon=True)
            t.start()
        elif warmup:
            self.warmup(warmup_len)
        else:
            self._ready.set()          # cold serving was the caller's call

    # -- model loading -------------------------------------------------------
    def _fresh_trainer(self):
        return self._cls(self.options)

    def _load_model(self, path: str) -> _Model:
        if self._arena_scoring:
            m = self._load_model_arena(path)
        else:
            m = self._load_model_bundle(path)
        if self._warmed_len is not None:
            # a previously warmed engine never swaps in a cold scorer: the
            # new version pre-compiles its batch buckets BEFORE the atomic
            # ref swap, so a rolling hot reload cannot spike p99 with XLA
            # compiles on the dispatch thread (usually a cache hit — the
            # jitted predict kernels are config-cached across trainers;
            # arena models have nothing to compile, the pass just touches
            # the mapped pages)
            self._warm_model(m, self._warmed_len)
        return m

    def _load_model_bundle(self, path: str) -> _Model:
        """The classic path: deserialize the bundle into a fresh trainer
        and score through its (jitted) scorer."""
        t = self._fresh_trainer()
        t.load_bundle(path)            # validates format/digest/shapes
        step = int(getattr(t, "_t", 0))
        m = _Model(t, self._wrap_scorer(t, t.make_scorer()), step, path,
                   needs_field=self._needs_field(t),
                   bundle_mtime=self._mtime(path))
        return m

    @staticmethod
    def _mtime(path: str) -> Optional[float]:
        try:
            return os.path.getmtime(path)
        except OSError:
            return None

    def _load_model_arena(self, path: str) -> _Model:
        """The zero-copy path: mmap the digest-verified ``<bundle>.arena``
        sidecar (published by promotion, or by this engine on first use)
        and score through the precision tier's numpy kernels. The trainer
        slot holds a parse-only facade — no dims-sized allocation, no
        bundle deserialize; N replicas share ONE set of weight pages
        through the page cache."""
        from ..io.weight_arena import (ArenaUnsupported, open_arena,
                                       publish_arena, try_open_arena)
        # a stale/torn/partial-precision sidecar is a MISS (try_open_arena's
        # contract), self-healed by the republish below — recording it as a
        # reload error would leave a standing false alarm on a healthy
        # replica. The same open-or-miss step backs the bulk scorer's arena
        # backend (io/bulk.py), so both planes validate sidecars identically.
        arena = try_open_arena(path, trainer_name=self._cls.NAME,
                               precision=self.precision)
        if arena is None:
            # no (valid) sidecar: pay the one-time bundle load HERE,
            # publish the arena, and still serve zero-copy — a
            # standalone quantized engine must not need a promotion
            # pipeline to exist first
            t = self._fresh_trainer()
            t.load_bundle(path)
            try:
                arena = open_arena(publish_arena(path, t))
                self.arena_publishes += 1
            except (ArenaUnsupported, OSError, ValueError, KeyError) as e:
                # quantized serving NEEDS the arena — surface the
                # failure; force-mode f32 holds a fully loaded, servable
                # trainer, so an unsupported family OR a publish failure
                # (read-only model dir, disk full) degrades to the
                # bundle path instead of killing the replica
                if self.precision != "f32":
                    raise
                self.arena_fallbacks += 1
                self.last_reload_error = \
                    f"arena publish: {type(e).__name__}: {e}"
                step = int(getattr(t, "_t", 0))
                return _Model(t, self._wrap_scorer(t, t.make_scorer()),
                              step, path, needs_field=self._needs_field(t),
                              bundle_mtime=self._mtime(path))
        # (no bundle-leaf validation on this path on purpose: the arena
        # payload is sha256-verified by open_arena, and matches_bundle
        # ties it to THIS bundle's recorded leaf digest — the bundle's
        # own leaves are never read, which is exactly the reload-I/O win)
        parser = self._cls.make_parser(self.options)
        scorer = arena.scorer(self.precision)
        self.arena_loads += 1
        return _Model(parser,
                      lambda b: np.asarray(scorer(b), np.float32),
                      arena.step, path,
                      needs_field=self._needs_field(parser),
                      bundle_mtime=self._mtime(path),
                      arena=arena, precision=self.precision)

    def _wrap_scorer(self, trainer, scorer):
        """GSPMD seam: when the trainer carries a device mesh (`-mesh
        dp=..,tp=..` in the serve options — dims-sized tables sharded over
        'tp' across chips), place each padded request batch on the mesh
        before scoring: rows over 'dp' when the batch bucket divides, else
        replicated (tiny buckets below dp). Single-device trainers score
        the host batch directly, unchanged."""
        mesh = getattr(trainer, "mesh", None)
        if mesh is None or not hasattr(trainer, "_shard_batch"):
            return scorer
        dp = int(mesh.shape["dp"])

        def sharded(batch):
            if dp > 1 and batch.idx.shape[0] % dp == 0:
                batch = trainer._shard_batch(batch)
            return scorer(batch)

        return sharded

    @staticmethod
    def _needs_field(trainer) -> bool:
        row = trainer._parse_row([])
        return isinstance(row, tuple) and len(row) == 3

    @staticmethod
    def _content_tag(path: str) -> str:
        """Cheap content fingerprint — sha256 over the first and last
        4 KiB. Two 4 KiB reads per KNOWN-BAD bundle per poll (rare, and
        retention prunes them), vs. hashing whole multi-GB bundles."""
        h = hashlib.sha256()
        with open(path, "rb") as f:
            h.update(f.read(4096))
            try:
                f.seek(-4096, os.SEEK_END)
            except OSError:
                f.seek(0)
            h.update(f.read(4096))
        return h.hexdigest()

    def _bad_ident(self, path: str) -> Optional[tuple]:
        try:
            st = os.stat(path)
            return (st.st_mtime, st.st_size, self._content_tag(path))
        except OSError:
            return None                # pruned between listdir and stat

    def _ident_matches(self, path: str, remembered: tuple) -> bool:
        """Is ``path`` still the SAME file the failure memo recorded?
        Keyed by (mtime, size); on a collision — both preserved, e.g. a
        bundle rewritten in place with its timestamp restored — fall
        back to the head/tail content tag. A pure-mtime memo silently
        never re-examined such a rewrite (the regression this fixes)."""
        try:
            st = os.stat(path)
        except OSError:
            return False
        if (st.st_mtime, st.st_size) != remembered[:2]:
            return False
        return self._content_tag(path) == remembered[2]

    def _load_newest(self, min_step: int) -> Optional[_Model]:
        """Newest loadable bundle with step > min_step, skipping
        quarantined (``.rejected``) bundles and remembering ones that
        fail validation."""
        name = self._cls.NAME
        listed = list_bundles(self.checkpoint_dir, name)
        if self._failed:
            # drop memo entries for bundles retention has pruned away —
            # a weeks-long watch must not grow the dict one dead path at
            # a time
            live = set(listed)
            self._failed = {p: m for p, m in self._failed.items()
                            if p in live}
        for path in listed:
            step = bundle_step(path)
            if step is None or step <= min_step:
                break                  # list is newest-first
            if is_rejected(path):
                continue               # quarantined: never retried
            bad = self._failed.get(path)
            if bad is not None and self._ident_matches(path, bad):
                continue               # known-bad, content unchanged
            try:
                return self._load_model(path)
            except Exception as e:     # noqa: BLE001 — a corrupt bundle
                # must degrade to "keep serving the old model", never
                # take the server down
                self.reload_failures += 1
                self.last_reload_error = f"{path}: {type(e).__name__}: {e}"
                fl = self._flight
                if fl.enabled:
                    fl.record("engine.reload",
                              f"ok=0{FS}bundle={os.path.basename(path)}"
                              f"{FS}err={type(e).__name__}")
                ident = self._bad_ident(path)
                if ident is not None:
                    self._failed[path] = ident
        return None

    def _load_promoted(self) -> Optional[_Model]:
        """The bundle the directory's ``PROMOTED`` pointer says THIS
        engine should serve, or None when there is no pointer, the
        pointer is already being served, or the pointed-at bundle fails
        to load (counted; the old model keeps serving and the next poll
        retries).

        During state "canary" the pointer's current entry is an UNBAKED
        candidate — an engine on its own (a fresh boot, a replica the
        fleet monitor just respawned mid-bake) must serve the prior
        stable entry (history head) instead: canary membership is an
        explicit manager-driven /reload, never a side effect of replica
        churn (a respawned stable replica silently joining the canary
        cohort would both widen the blast radius and starve the stable
        cohort the bake compares against)."""
        m = read_promoted(self.checkpoint_dir)
        if m is None:
            return None
        cur = m["current"]
        if m.get("state") == "canary" and m.get("history"):
            cur = m["history"][0]
        key = (str(cur.get("bundle")), cur.get("digest"))
        if key == self._promoted_key:
            return None                # pointer unchanged
        path = os.path.join(self.checkpoint_dir, key[0])
        bad = self._failed.get(path)
        if bad is not None and self._ident_matches(path, bad):
            return None
        try:
            model = self._load_model(path)
        except Exception as e:         # noqa: BLE001 — same degrade as
            self.reload_failures += 1  # the newest-bundle scan
            self.last_reload_error = f"{path}: {type(e).__name__}: {e}"
            fl = self._flight
            if fl.enabled:
                fl.record("engine.reload",
                          f"ok=0{FS}bundle={os.path.basename(path)}"
                          f"{FS}err={type(e).__name__}")
            ident = self._bad_ident(path)
            if ident is not None:
                self._failed[path] = ident
            return None
        self._promoted_key = key
        return model

    # -- hot reload ----------------------------------------------------------
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
        """Age of the serving bundle FILE (now - its mtime at load) — how
        stale the model itself is, as opposed to model_age_seconds (how
        long ago this process loaded it). External LBs and the fleet
        router read this off /healthz to spot a fleet stuck on an old
        bundle while training keeps publishing newer ones."""
        m = self._model
        mt = m.bundle_mtime if m is not None else None
        # file mtimes are wall-clock; only wall "now" can age them
        return None if mt is None \
            else round(time.time() - mt, 3)  # graftcheck: disable=GC02

    @property
    def arena_mapped_bytes(self) -> int:
        """Payload bytes of the mmap'd arena the serving model scores
        from (0 on the bundle path). N replicas of one model report the
        SAME number while sharing one set of physical pages — the
        per-replica gauge behind the fleet's ≥4× memory-headroom claim."""
        m = self._model
        a = m.arena if m is not None else None
        return int(a.mapped_bytes) if a is not None else 0

    @property
    def platform(self) -> str:
        """Where the serving model scores: the JAX backend of the jitted
        scorer, or "host" for the numpy arena twin (which never touches a
        device). /healthz reports it so a replica's device is stated."""
        m = self._model
        if m is not None and m.arena is not None:
            return "host"
        import jax
        return jax.default_backend()

    @property
    def ready(self) -> bool:
        """Warmup complete (or explicitly skipped) — the readiness gate."""
        return self._ready.is_set()

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        return self._ready.wait(timeout)

    def poll(self) -> bool:
        """Check the watched directory once; swap to whatever the follow
        mode says should serve. ``follow="newest"``: the newest usable
        bundle NEWER than the serving model. ``follow="promoted"``: the
        bundle the ``PROMOTED`` pointer names, whenever the pointer
        changed — in EITHER direction (a rollback swaps to a lower
        step). Returns True when a swap happened. Safe from any thread;
        in-flight predictions finish on the model version they started
        with."""
        if not self.checkpoint_dir:
            return False
        with self._reload_lock:
            if self.follow == "promoted":
                m = self._load_promoted()
            else:
                m = self._load_newest(min_step=self._model.step)
            if m is None:
                return False
            old_step = self._model.step
            self._model = m            # atomic ref swap
            self.reloads += 1
            fl = self._flight
            if fl.enabled:
                fl.record("engine.reload",
                          f"ok=1{FS}from={old_step}{FS}to={m.step}{FS}"
                          f"bundle={os.path.basename(m.path or '')}")
            return True

    def reload(self, path: Optional[str] = None) -> bool:
        """Force a reload: from an explicit bundle path, or the watched
        directory (newer-step bundles only, like :meth:`poll`).

        An explicit path must live INSIDE the watched checkpoint
        directory — /reload is reachable over the network, and the model
        directory is the trust boundary (an arbitrary filesystem path
        would let any client probe the disk or swap in a planted file).
        Raises ValueError for an out-of-tree path."""
        if path is None:
            return self.poll()
        if not self.checkpoint_dir:
            raise ValueError(
                "explicit-path reload needs a watched checkpoint dir "
                "(this server was started from a pinned --bundle)")
        real = os.path.realpath(path)
        root = os.path.realpath(self.checkpoint_dir)
        if os.path.commonpath([real, root]) != root:
            raise ValueError(
                "reload path is outside the watched checkpoint directory")
        with self._reload_lock:
            try:
                m = self._load_model(path)
            except Exception as e:     # noqa: BLE001 — same degrade
                self.reload_failures += 1
                self.last_reload_error = f"{path}: {type(e).__name__}: {e}"
                fl = self._flight
                if fl.enabled:
                    fl.record("engine.reload",
                              f"ok=0{FS}bundle={os.path.basename(path)}"
                              f"{FS}err={type(e).__name__}")
                return False
            old_step = self._model.step if self._model is not None else -1
            self._model = m
            self.reloads += 1
            fl = self._flight
            if fl.enabled:
                fl.record("engine.reload",
                          f"ok=1{FS}from={old_step}{FS}to={m.step}{FS}"
                          f"bundle={os.path.basename(m.path or '')}")
            return True

    def start_watch(self) -> None:
        """Poll the checkpoint directory on a daemon thread — the live
        trainer + live server recipe (docs/SERVING.md)."""
        if self._watch_thread is not None or not self.checkpoint_dir:
            return
        self._watch_stop.clear()

        def run():
            while not self._watch_stop.wait(self.watch_interval):
                try:
                    self.poll()
                except Exception as e:   # noqa: BLE001 — watcher survives
                    with self._reload_lock:  # shared with the warm thread
                        self.last_reload_error = \
                            f"{type(e).__name__}: {e}"

        self._watch_thread = threading.Thread(
            target=run, name="serve-watch", daemon=True)
        self._watch_thread.start()

    def close(self) -> None:
        self._watch_stop.set()
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=5)
            self._watch_thread = None
        # release the serving model: an arena version holds mmap views
        # of the shared weight file — a drained replica must unmap them
        # (GC on the dropped refs) so the leaktrack census reads clean.
        # Scoring after close() is a caller bug and raises.
        with self._reload_lock:
            m = self._model
            self._model = None
        if m is not None and m.arena is not None:
            m.arena.release()

    # -- predict -------------------------------------------------------------
    def parse(self, features: Sequence[str]) -> tuple:
        """One request row ("name:value" / "field:index:value" feature
        strings) through the trainer's OWN hashing path (_parse_row /
        ftvec mhash) — serving and training can never hash differently."""
        t = self._model.trainer
        row = t._parse_row(features)
        # bound the row-length shape bucket at the REQUEST boundary: one
        # arbitrarily wide row would otherwise force a fresh XLA compile
        # + a huge allocation on the dispatch thread, stalling every
        # coalesced request behind it (the rejection is a per-request
        # 400, never a batch failure)
        if len(row[0]) > self.max_row_features:
            raise ValueError(
                f"request row has {len(row[0])} features > "
                f"max_row_features {self.max_row_features}")
        names = getattr(t, "_names", None)
        if names is not None and len(names) > _NAMES_CAP:
            names.clear()
        return row

    def predict_rows(self, rows: List[tuple]) -> np.ndarray:
        """Score parsed rows through one bucketed padded batch. Returns
        float32 [len(rows)] output-space scores (probabilities for
        classification). The model ref is grabbed once, so a concurrent
        hot-swap never mixes versions inside a batch."""
        return self._predict_with(self._model, rows)

    def predict_rows_versioned(self, rows: List[tuple]):
        """Batcher predict fn for the HTTP front end: ``(scores, step)``
        where ``step`` is the step of the model version that ACTUALLY
        scored this batch — across a hot swap, the response tag must name
        the version that produced the scores, not whatever is newest by
        response time."""
        m = self._model
        return self._predict_with(m, rows), m.step

    def _predict_with(self, m: _Model, rows: List[tuple]) -> np.ndarray:
        n = len(rows)
        if n == 0:
            return np.zeros(0, np.float32)
        with self._tracer.span("serve.predict"):
            batch = self._pad(rows, m.needs_field)
            return np.asarray(m.scorer(batch), np.float32)[:n]

    def _pad(self, rows: List[tuple], needs_field: bool) -> SparseBatch:
        """Bucketed padding: B = pow2 bucket of the row count, L = pow2
        bucket of the widest row (>= min_len_bucket) — the serve-side
        instance of the shared io.sparse bucketing."""
        n = len(rows)
        B = bucket_size(n)
        L = bucket_size(max(len(r[0]) for r in rows), lo=self.min_len_bucket)
        idx = np.zeros((B, L), np.int32)
        val = np.zeros((B, L), np.float32)
        fld = np.zeros((B, L), np.int32) if needs_field else None
        for b, row in enumerate(rows):
            ln = len(row[0])
            idx[b, :ln] = row[0]
            val[b, :ln] = row[1]
            if fld is not None:
                fld[b, :ln] = row[2]
        lab = np.zeros(B, np.float32)
        return SparseBatch(idx, val, lab, fld,
                           n_valid=n if n < B else None)

    def warmup(self, warmup_len: int = 16) -> int:
        """Pre-compile the scorer at every power-of-two batch bucket up to
        ``max_batch`` (at one representative row-length bucket): startup
        pays the XLA compiles, requests don't. Marks the engine ready (the
        /healthz gate) and arms pre-swap warming for every later hot
        reload. Returns the bucket count."""
        count = self._warm_model(self._model, warmup_len)
        self._warmed_len = int(warmup_len)
        self._ready.set()
        return count

    def _warm_bg(self, warmup_len: int) -> None:
        """warmup="background": serve /healthz as warming while the
        buckets compile, then flip ready. A warmup failure must leave the
        replica NOT ready (the router keeps excluding it) rather than
        crash the process — the manager's health monitor surfaces it."""
        try:
            self.warmup(warmup_len)
        except Exception as e:           # noqa: BLE001 — degrade to cold
            with self._reload_lock:      # shared with the watch thread
                self.last_reload_error = f"warmup: {type(e).__name__}: {e}"

    def _warm_model(self, m: _Model, warmup_len: int) -> int:
        L = bucket_size(warmup_len, lo=self.min_len_bucket)
        count = 0
        B = 1
        while B <= bucket_size(self.max_batch):
            fld = (np.zeros((B, L), np.int32) if m.needs_field else None)
            m.scorer(SparseBatch(np.zeros((B, L), np.int32),
                                 np.zeros((B, L), np.float32),
                                 np.zeros(B, np.float32), fld,
                                 n_valid=None))
            count += 1
            B <<= 1
        return count

    # -- obs (docs/OBSERVABILITY.md `serve` section) -------------------------
    def attach_batcher(self, batcher) -> None:
        """Merge a MicroBatcher's queue/batch counters into this engine's
        ``serve`` registry section (the HTTP front end wires this)."""
        self._batcher = batcher

    def obs_section(self) -> dict:
        from ..io.weight_arena import host_rss_bytes
        m = self._model
        d = {
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
            # zero-copy serving gauges (docs/PERFORMANCE.md "Weight
            # arena + quantized scoring"): host RSS next to the arena
            # bytes is what makes the N-replicas-1x-weights claim
            # measurable instead of asserted
            "host_rss_bytes": host_rss_bytes(),
            "precision": self.precision,
            "arena": {
                "active": bool(m is not None and m.arena is not None),
                "mode": self.arena_mode,
                "mapped_bytes": self.arena_mapped_bytes,
                "loads": self.arena_loads,
                "publishes": self.arena_publishes,
                "fallbacks": self.arena_fallbacks,
            },
        }
        mesh = getattr(m.trainer, "mesh", None) if m is not None else None
        if mesh is not None:
            d["mesh"] = "dp={dp},tp={tp}".format(**dict(mesh.shape))
        if self.last_reload_error:
            d["last_reload_error"] = self.last_reload_error
        b = self._batcher
        if b is not None:
            d.update(b.stats())
        return d

    def _register_obs(self) -> None:
        import weakref
        from ..obs.registry import registry
        ref = weakref.ref(self)

        def serve() -> dict:
            e = ref()
            return e.obs_section() if e is not None else {"active": False}

        registry.register("serve", serve)

