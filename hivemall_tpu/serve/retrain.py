"""Autopilot retraining — the drift-driven controller that closes the
train→validate→promote→rollback loop (docs/RELIABILITY.md "Autonomous
retraining").

Hivemall's essence is the full UDTF-train→predict loop over live
warehouse data (PAPER.md [B]); until now this repo's loop was open at
one seam: the SLO engine's score-drift changefinder emits
``retrain_wanted`` votes (obs/slo.py) and nothing consumed them. This
module is the consumer:

- :class:`ReplayBuffer` — a spill-to-disk ring of recent LABELED
  traffic rows (raw request feature strings + joined labels), teed off
  the serving path (:class:`~hivemall_tpu.serve.promote.ShadowBuffer`
  raw capture in a single server, :class:`RouterTee` in a fleet).
  Segments are written with the checkpoint idiom (tmp → fsync →
  ``os.replace``) so a crash never leaves a torn segment, and the ring
  evicts oldest-first so the buffer always holds the newest regime.
- :class:`RetrainController` — the daemon. It debounces
  ``retrain_wanted`` votes through the shared
  :class:`~hivemall_tpu.obs.devprof.DriftWatch` flap detector plus
  explicit storm controls (per-model cooldown with rejection backoff, a
  max-retrains-per-window cap, a concurrent-retrain budget of exactly
  one), then launches a retrain in a SUPERVISED CHILD PROCESS:
  warm-started from the ``PROMOTED`` bundle via the trainer's bundle
  resume path, fed from the base corpus (whose epochs go through the
  PR 6 shard caches — warm mmap, zero re-parse) concatenated with the
  replay buffer. The candidate bundle lands in the watched checkpoint
  dir, where the EXISTING gate/canary/rollback machinery
  (serve.promote / serve.fleet) finishes the job; the controller
  watches the pointer manifest + ``.rejected`` markers to learn the
  outcome, and a gate rejection quarantines the attempt and BACKS OFF
  (cooldown × backoff^consecutive-rejections) so a bad data regime can
  never retrain-storm.

State machine (the ``retrain`` obs registry section):
``idle → triggered → training → gating → canary → cooldown → idle``.

Every transition is durable: the controller persists a ``RETRAIN_STATE``
stamp (atomic json) next to the ``PROMOTED`` pointer, so a controller
crashed/SIGKILLed at ANY state recovers purely from on-disk facts — the
pointer manifest says whether a candidate is baking or promoted, the
``.rejected`` marker says it was quarantined, the replay segments are
still there, and the cooldown stamp still holds the storm controls
closed. Humans only read the obs report.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Callable, List, Optional, Tuple

from ..io.checkpoint import (_atomic_write_json, bundle_step, is_rejected,
                             promoted_bundle, read_promoted)
from ..utils.metrics import get_stream

__all__ = ["ReplayBuffer", "RouterTee", "RetrainController",
           "build_retrain_stream", "retrain_stub"]

#: the on-disk controller stamp, next to the PROMOTED pointer
_STATE_FILE = "RETRAIN_STATE"
#: replay segments live under <checkpoint_dir>/replay by default
_REPLAY_DIRNAME = "replay"

STATES = ("idle", "triggered", "training", "gating", "canary", "cooldown")


def retrain_stub() -> dict:
    """A fresh copy of the ``retrain`` registry stub (key-for-key mirror
    of :meth:`RetrainController.obs_section`, pinned by
    tests/test_obs.py::test_stub_sections_match_live_providers)."""
    from ..obs.registry import RETRAIN_STUB
    return {**RETRAIN_STUB, "replay": dict(RETRAIN_STUB["replay"])}


# ---------------------------------------------------------------------------
# replay buffer: spill-to-disk ring of labeled traffic
# ---------------------------------------------------------------------------

class ReplayBuffer:
    """Disk ring of recent labeled traffic rows for retrain input.

    ``add(raw_rows, labels)`` buffers rows in memory; every
    ``segment_rows`` rows a segment file (``replay-<seq>.jsonl``: one
    header line + one ``{"f": [...], "y": ...}`` line per row) is
    written atomically (tmp → fsync → ``os.replace`` → dir fsync — the
    checkpoint idiom, so a crash can never leave a torn segment) and the
    ring drops oldest segments beyond ``max_segments``. Readers
    (:meth:`rows` / :meth:`dataset`) see only COMMITTED segments — the
    child retrain process trains on exactly what survives a crash.

    Thread-safe; a tee thread feeds ``add`` while the controller's tick
    thread calls ``flush``/``counters``."""

    def __init__(self, dir: str, *, segment_rows: int = 256,
                 max_segments: int = 8):
        self.dir = dir
        self.segment_rows = int(segment_rows)
        self.max_segments = int(max_segments)
        os.makedirs(dir, exist_ok=True)
        self._lock = threading.Lock()
        self._pending: List[Tuple[list, float]] = []
        self.rows_in = 0
        self.rows_dropped = 0
        self.segments_written = 0
        self.segments_dropped = 0
        # recover the sequence counter from whatever segments survived
        self._seq = 1 + max(
            [self._seq_of(p) for p in self._list()] or [-1])

    @staticmethod
    def _seq_of(path: str) -> int:
        name = os.path.basename(path)
        try:
            return int(name[len("replay-"):-len(".jsonl")])
        except ValueError:
            return -1

    def _list(self) -> List[str]:
        try:
            names = os.listdir(self.dir)
        except OSError:
            return []
        out = [os.path.join(self.dir, n) for n in names
               if n.startswith("replay-") and n.endswith(".jsonl")]
        return sorted(out, key=self._seq_of)

    # -- write side ----------------------------------------------------------
    def add(self, raw_rows: List[list], labels: List[float]) -> int:
        """Append labeled rows; rows whose label is None are skipped
        (an unjoinable row must not train as label 0). Returns rows
        accepted. Full segments are committed inline."""
        accepted = []
        for row, y in zip(raw_rows, labels):
            if y is None or row is None:
                continue
            accepted.append((list(row), float(y)))
        if not accepted:
            return 0
        with self._lock:
            self._pending.extend(accepted)
            self.rows_in += len(accepted)
            while len(self._pending) >= self.segment_rows:
                chunk = self._pending[:self.segment_rows]
                del self._pending[:self.segment_rows]
                self._write_segment(chunk)
        return len(accepted)

    def flush(self) -> None:
        """Commit any buffered partial segment (called before a retrain
        launches so the child sees every mirrored row)."""
        with self._lock:
            if self._pending:
                chunk, self._pending = self._pending, []
                self._write_segment(chunk)

    def _write_segment(self, chunk: List[Tuple[list, float]]) -> None:
        """Atomic segment commit + ring eviction (caller holds _lock)."""
        path = os.path.join(self.dir, f"replay-{self._seq:08d}.jsonl")
        self._seq += 1
        tmp = f"{path}.{os.getpid()}.tmp"
        try:
            with open(tmp, "w") as f:
                f.write(json.dumps({"rows": len(chunk),
                                    "ts": round(time.time(), 3)}) + "\n")
                for row, y in chunk:
                    f.write(json.dumps({"f": row, "y": y}) + "\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                try:
                    os.remove(tmp)
                except OSError:
                    pass
        try:  # rename durability — the checkpoint idiom's dir fsync
            dfd = os.open(self.dir, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass
        self.segments_written += 1
        segs = self._list()
        for old in segs[:max(0, len(segs) - self.max_segments)]:
            dropped = self._segment_rows(old)
            try:
                os.remove(old)
            except OSError:
                continue
            self.segments_dropped += 1
            self.rows_dropped += dropped

    @staticmethod
    def _segment_rows(path: str) -> int:
        try:
            with open(path) as f:
                return int(json.loads(f.readline()).get("rows") or 0)
        except (OSError, ValueError):
            return 0

    # -- read side -----------------------------------------------------------
    def rows(self) -> List[Tuple[list, float]]:
        """Every committed row, oldest segment first. A torn line (only
        possible through external corruption — commits are atomic) is
        skipped, never raised."""
        out: List[Tuple[list, float]] = []
        for path in self._list():
            try:
                with open(path) as f:
                    f.readline()                 # header
                    for line in f:
                        try:
                            rec = json.loads(line)
                            out.append((rec["f"], float(rec["y"])))
                        except (ValueError, KeyError, TypeError):
                            continue
            except OSError:
                continue
        return out

    def dataset(self, trainer):
        """Committed rows parsed through the TRAINER'S OWN row parser
        (the same hashing serving uses) into a SparseDataset — or None
        when the buffer is empty."""
        from ..io.sparse import SparseDataset
        rows = self.rows()
        if not rows:
            return None
        parsed, labels, fields = [], [], []
        has_fields = False
        for feats, y in rows:
            p = trainer._parse_row(feats)
            if len(p) == 3:              # FFM-style (idx, val, field)
                has_fields = True
                parsed.append((p[0], p[1]))
                fields.append(p[2])
            else:
                parsed.append(p)
                fields.append(None)
            labels.append(y)
        return SparseDataset.from_rows(
            parsed, labels, fields=fields if has_fields else None)

    def counters(self) -> dict:
        with self._lock:
            pending = len(self._pending)
        return {"rows": self.rows_in,
                "rows_dropped": self.rows_dropped,
                "segments": len(self._list()),
                "pending_rows": pending}


class RouterTee:
    """Bounded non-blocking intake of raw ``/predict`` bodies on router
    connection threads — the fleet-mode traffic source for the replay
    buffer (the manager process never sees parsed rows; the router sees
    every request body). At capacity the oldest body is evicted
    (counted), so a stalled controller can never backpressure the
    serving path."""

    def __init__(self, capacity: int = 512):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._q: deque = deque(maxlen=self.capacity)
        self.teed = 0
        self.dropped = 0

    def __call__(self, body: bytes) -> None:
        with self._lock:
            if len(self._q) >= self.capacity:
                self.dropped += 1
            self._q.append(bytes(body))
            self.teed += 1

    def drain(self) -> List[bytes]:
        with self._lock:
            out = list(self._q)
            self._q.clear()
        return out

    @staticmethod
    def rows_of(body: bytes) -> List[list]:
        """Feature-string rows out of one ``/predict`` body (the same
        shapes the HTTP handler accepts); malformed bodies yield []."""
        try:
            obj = json.loads(body or b"{}")
            rows = obj.get("rows")
            if rows is None:
                feats = obj.get("features")
                rows = [feats] if feats is not None else []
            return [r for r in rows if isinstance(r, list)]
        except (ValueError, TypeError, AttributeError):
            return []


# ---------------------------------------------------------------------------
# retrain input stream: shard-cache-backed base corpus ∪ replay buffer
# ---------------------------------------------------------------------------

def _load_base(trainer, base):
    """The base corpus as a dataset/stream: a SparseDataset passes
    through; a directory becomes a ParquetStream wired to the trainer's
    ``-shard_cache_dir`` (warm traversals mmap the PR 6 decode cache
    instead of re-reading Parquet); a file reads as LIBSVM."""
    if base is None:
        return None
    if not isinstance(base, str):
        return base                      # dataset-like: has .batches
    kw = dict(dims=getattr(trainer, "dims", None))
    if getattr(trainer, "F", None) is not None \
            and trainer.NAME == "train_ffm":
        kw.update(ffm=True, num_fields=trainer.F)
    if os.path.isdir(base):
        from ..io.arrow import ParquetStream
        opts = getattr(trainer, "opts", None)
        cache_dir = opts.get("shard_cache_dir") if opts is not None else None
        return ParquetStream(base, cache_dir=cache_dir, **kw)
    from ..io.libsvm import read_libsvm
    return read_libsvm(base, **kw)


def build_retrain_stream(trainer, *, base=None, replay_dir: Optional[str]
                         = None, batch_size: int = 64, epochs: int = 1):
    """The retrain input: base-corpus batches (through the shard caches
    when configured) followed by replay-buffer batches, DETERMINISTIC
    (no shuffle) so a retrain over the same on-disk inputs is bit-
    reproducible — the warm-start fidelity contract tests/test_retrain
    pins at ``-steps_per_dispatch`` 1 and 8. Returns (stream, n_rows);
    n_rows == 0 means there is nothing to train on."""
    import itertools
    parts = []
    n_rows = 0
    ds = _load_base(trainer, base)
    if ds is not None:
        n_rows += len(ds) * max(1, int(epochs))
        parts.append(ds.batches(int(batch_size), epochs=max(1, int(epochs)),
                                shuffle=False))
    if replay_dir:
        rds = ReplayBuffer(replay_dir).dataset(trainer)
        if rds is not None:
            n_rows += len(rds) * max(1, int(epochs))
            parts.append(rds.batches(int(batch_size),
                                     epochs=max(1, int(epochs)),
                                     shuffle=False))
    return itertools.chain(*parts), n_rows


# ---------------------------------------------------------------------------
# supervised child: one retrain attempt in its own process
# ---------------------------------------------------------------------------

def _child(spec_json: str) -> int:
    """One retrain attempt: fresh trainer, warm-started from the
    promoted bundle, fit over base ∪ replay, candidate bundle saved
    atomically into the checkpoint dir (where the gate watches). Prints
    ONE json result line. Isolated in a child process so a diverging
    retrain (OOM, wedged compile, poisoned data) can be killed by the
    supervising controller without taking serving down."""
    spec = json.loads(spec_json)
    import jax
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from ..catalog import lookup
    cls = lookup(spec["algo"]).resolve()
    trainer = cls(spec.get("options") or "")
    trainer.load_bundle(spec["warm_bundle"])
    start_step = int(getattr(trainer, "_t", 0))
    t0 = time.monotonic()
    stream, n_rows = build_retrain_stream(
        trainer, base=spec.get("train_input"),
        replay_dir=spec.get("replay_dir"),
        batch_size=int(spec.get("batch_size") or 64),
        epochs=int(spec.get("epochs") or 1))
    if n_rows == 0:
        print(json.dumps({"ok": False, "error": "no training data "
                          "(empty replay buffer and no train_input)"}),
              flush=True)
        return 1
    trainer.fit_stream(stream)
    step = int(getattr(trainer, "_t", 0))
    if step <= start_step:
        print(json.dumps({"ok": False, "error": "no steps advanced"}),
              flush=True)
        return 1
    path = os.path.join(spec["checkpoint_dir"],
                        f"{trainer.NAME}-step{step:010d}.npz")
    trainer.save_bundle(path)            # atomic: the gate never sees a
    print(json.dumps({                   # torn candidate
        "ok": True, "bundle": os.path.basename(path), "step": step,
        "warm_step": start_step, "rows": n_rows,
        "platform": jax.default_backend(),
        "seconds": round(time.monotonic() - t0, 3)}), flush=True)
    return 0


class RetrainController:
    """Drift votes in, gated candidates out — with storm controls.

    The controller is DATA-PLANE-FREE: it never touches a live scorer.
    It consumes cumulative ``retrain_wanted`` vote counts (``slo=``
    in-process, or ``votes_fn=`` for a remote ``/slo`` poller), drains
    traffic tees into the :class:`ReplayBuffer`, launches at most ONE
    supervised child retrain at a time, and then watches the on-disk
    promotion protocol (pointer manifest + ``.rejected`` markers) to
    learn the candidate's fate — which is also exactly what makes a
    controller restart free: every decision input is on disk.

    Debounce + storm controls, all enforced before a trigger:

    - ``min_votes`` fresh votes within ``vote_window_s``;
    - the shared DriftWatch flap detector over the per-tick vote rate —
      a vote STORM (changefinder flapping) extends the holdoff instead
      of feeding it;
    - per-model ``cooldown_s`` after every attempt, multiplied by
      ``backoff_factor`` per CONSECUTIVE gate rejection (capped at
      ``max_backoff_s``) — a bad data regime decays to near-silence;
    - at most ``max_retrains_per_window`` triggers per ``window_s``;
    - a concurrent-retrain budget of exactly 1 (the single child).

    ``tick()`` is re-entrant-free and cheap; the fleet manager calls it
    from its watch loop, a standalone controller runs it on its own
    daemon thread (:meth:`start`)."""

    def __init__(self, algo: str, options: str = "", *,
                 checkpoint_dir: str,
                 slo=None,
                 votes_fn: Optional[Callable[[], int]] = None,
                 shadow=None,
                 router_tee: Optional[RouterTee] = None,
                 label_fn: Optional[Callable] = None,
                 replay_dir: Optional[str] = None,
                 replay_segment_rows: int = 256,
                 replay_max_segments: int = 8,
                 train_input: Optional[str] = None,
                 gate=None,
                 batch_size: int = 64,
                 epochs: int = 1,
                 min_votes: int = 1,
                 vote_window_s: float = 300.0,
                 cooldown_s: float = 60.0,
                 window_s: float = 3600.0,
                 max_retrains_per_window: int = 4,
                 backoff_factor: float = 2.0,
                 max_backoff_s: float = 3600.0,
                 train_timeout_s: float = 900.0,
                 gate_timeout_s: float = 600.0,
                 interval: float = 2.0,
                 flap_sigma: float = 6.0,
                 flap_warmup: int = 16,
                 env: Optional[dict] = None):
        from ..catalog import lookup
        self.algo = algo
        self.options = options
        self.checkpoint_dir = checkpoint_dir
        self._name = lookup(algo).resolve().NAME
        self.slo = slo
        self._votes_fn = votes_fn
        self.shadow = shadow             # ShadowBuffer w/ raw capture
        self.router_tee = router_tee
        self.label_fn = label_fn
        self.train_input = train_input
        self.gate = gate                 # own gate (CLI --once); a fleet
        self.batch_size = int(batch_size)   # manager/controller gates
        self.epochs = int(epochs)           # externally when None
        self.min_votes = max(1, int(min_votes))
        self.vote_window_s = float(vote_window_s)
        self.cooldown_s = float(cooldown_s)
        self.window_s = float(window_s)
        self.max_retrains_per_window = int(max_retrains_per_window)
        self.backoff_factor = float(backoff_factor)
        self.max_backoff_s = float(max_backoff_s)
        self.train_timeout_s = float(train_timeout_s)
        self.gate_timeout_s = float(gate_timeout_s)
        self.interval = float(interval)
        self.env = env
        os.makedirs(checkpoint_dir, exist_ok=True)
        self.replay = ReplayBuffer(
            replay_dir or os.path.join(checkpoint_dir, _REPLAY_DIRNAME),
            segment_rows=replay_segment_rows,
            max_segments=replay_max_segments)
        # vote flap detector: the shared dual-stage changefinder wrapper
        # over the PER-TICK vote arrival rate — a storming changefinder
        # upstream (votes every tick) flags here and HOLDS OFF triggers
        # instead of hammering the trainer
        from ..obs.devprof import DriftWatch
        self.flap_watch = DriftWatch("retrain_votes", "retrain_flap",
                                     sigma=flap_sigma, warmup=flap_warmup)
        self._lock = threading.Lock()
        self.state = "idle"
        self.attempts = 0
        self.successes = 0
        self.rejections = 0
        self.rollbacks = 0
        self.flaps = 0
        self.votes_seen = 0
        self.votes_acked = 0
        self.last_trigger_reason: Optional[str] = None
        self.last_error: Optional[str] = None
        self._consecutive_rejections = 0
        self._candidate: Optional[dict] = None   # {"bundle","step"}
        self._child: Optional[subprocess.Popen] = None
        self._child_reader: Optional[threading.Thread] = None
        self._child_out: List[str] = []
        self._child_since: Optional[float] = None     # monotonic
        self._phase_since = time.monotonic()          # gating watchdog
        self._cooldown_until = 0.0                    # monotonic
        self._flap_until = 0.0                        # monotonic
        self._window: List[float] = []                # monotonic triggers
        self._recent_votes: deque = deque()           # (mono, n)
        self._last_total: Optional[int] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._load_state()
        self._register_obs()

    # -- durable state -------------------------------------------------------
    def _state_path(self) -> str:
        return os.path.join(self.checkpoint_dir, _STATE_FILE)

    def _save_state(self) -> None:
        """Persist the storm-control stamp (atomic json, the checkpoint
        idiom). Timestamps are WALL clock on disk — they must mean the
        same thing to the next process — and are re-anchored onto the
        monotonic clock at load."""
        now_wall = time.time()
        now_mono = time.monotonic()
        rec = {
            "state": self.state,
            "attempts": self.attempts,
            "successes": self.successes,
            "rejections": self.rejections,
            "rollbacks": self.rollbacks,
            "votes_acked": self.votes_acked,
            "consecutive_rejections": self._consecutive_rejections,
            "candidate": self._candidate,
            "last_trigger_reason": self.last_trigger_reason,
            # deliberate wall anchors: on-disk stamps must mean the same
            # thing to the NEXT process (load re-anchors onto monotonic)
            "cooldown_until_ts": round(
                now_wall  # graftcheck: disable=GC02
                + max(0.0, self._cooldown_until - now_mono), 3),
            "window_ts": [round(now_wall - (now_mono - t),  # graftcheck: disable=GC02
                                3) for t in self._window],
            "ts": round(now_wall, 3),
        }
        _atomic_write_json(self._state_path(), rec)

    def _load_state(self) -> None:
        try:
            with open(self._state_path()) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            return
        if not isinstance(rec, dict):
            return
        self.attempts = int(rec.get("attempts") or 0)
        self.successes = int(rec.get("successes") or 0)
        self.rejections = int(rec.get("rejections") or 0)
        self.rollbacks = int(rec.get("rollbacks") or 0)
        self.votes_acked = int(rec.get("votes_acked") or 0)
        self._consecutive_rejections = int(
            rec.get("consecutive_rejections") or 0)
        self.last_trigger_reason = rec.get("last_trigger_reason")
        cand = rec.get("candidate")
        self._candidate = cand if isinstance(cand, dict) else None
        # re-anchor wall stamps onto this process's monotonic clock: the
        # on-disk record must survive restarts (wall), runtime compares
        # must survive NTP steps (monotonic)
        now_mono = time.monotonic()
        now_wall = time.time()  # graftcheck: disable=GC02
        until = float(rec.get("cooldown_until_ts") or 0.0)
        self._cooldown_until = \
            now_mono + max(0.0, until - now_wall)  # graftcheck: disable=GC02
        self._window = [now_mono - max(0.0, now_wall - float(t))  # graftcheck: disable=GC02
                        for t in rec.get("window_ts") or []]
        state = rec.get("state")
        # crash recovery: land in whichever state the DISK supports.
        # "training" cannot survive (the child died with us): if its
        # candidate already landed, resume watching the gate; otherwise
        # the attempt is lost — cooldown (stamp already loaded) or idle.
        if state in ("triggered", "training"):
            cand_path = self._candidate_path()
            if cand_path and os.path.exists(cand_path):
                self.state = "gating"
            else:
                self._candidate = None
                self.state = ("cooldown" if self._cooldown_until > now_mono
                              else "idle")
                self.last_error = "recovered: retrain child lost to a " \
                                  "controller crash"
        elif state in ("gating", "canary"):
            self.state = state if self._candidate else "idle"
        elif state == "cooldown":
            self.state = ("cooldown" if self._cooldown_until > now_mono
                          else "idle")
        self._phase_since = now_mono

    def _candidate_path(self) -> Optional[str]:
        if not self._candidate:
            return None
        return os.path.join(self.checkpoint_dir,
                            str(self._candidate["bundle"]))

    def _set_state(self, state: str, **event) -> None:
        with self._lock:
            prev, self.state = self.state, state
            self._phase_since = time.monotonic()
        self._save_state()
        # retrain state edges go to the flight ring too: "the autopilot
        # was mid-<state> when the manager died" is exactly what a
        # post-mortem of a wedged retrain needs
        from ..obs.flight import get_flight
        fl = get_flight()
        if fl.enabled:
            fl.record("retrain.state", state=state, prev=prev)
        if event.pop("emit", True):
            get_stream().emit("retrain", state=state, prev=prev, **event)

    # -- vote intake ---------------------------------------------------------
    def _votes_total(self) -> int:
        if self._votes_fn is not None:
            try:
                return int(self._votes_fn())
            except Exception as e:       # noqa: BLE001 — a dead /slo
                self.last_error = f"votes: {type(e).__name__}: {e}"
                return self.votes_seen   # source must not kill the loop
        return int(getattr(self.slo, "retrain_wanted", 0) or 0)

    def _observe_votes(self, now: float) -> int:
        """Fold the cumulative vote counter into the recency window and
        the flap detector; returns votes pending (fresh, unacked). The
        DURABLE ``votes_acked`` ledger (in the state stamp) is what
        prevents answered votes from re-firing across controller
        restarts — on first sight everything above it is honestly
        pending drift the autopilot has never answered."""
        total = self._votes_total()
        prev = self._last_total
        if prev is not None and total < prev:
            # the serve process restarted (counter reset): re-baseline —
            # votes already counted must not replay
            self._last_total = total
            self.votes_seen = total
            self._recent_votes.clear()
            if total < self.votes_acked:
                self.votes_acked = total
            return 0
        delta = (total - prev if prev is not None
                 else max(0, total - self.votes_acked))
        self._last_total = total
        self.votes_seen = total
        if delta > 0:
            self._recent_votes.append((now, delta))
        ev = self.flap_watch.update(float(delta))
        if ev is not None:
            with self._lock:
                self.flaps += 1
            self._flap_until = now + self.cooldown_s
        while self._recent_votes and \
                now - self._recent_votes[0][0] > self.vote_window_s:
            self._recent_votes.popleft()
        recent = sum(n for _, n in self._recent_votes)
        return min(recent, max(0, total - self.votes_acked))

    def _ack_votes(self) -> int:
        """Consume every pending vote (they're answered by this
        retrain): bump the SLO engine's ``retrain_acked`` so the obs
        surface distinguishes votes from actions."""
        total = self.votes_seen
        n = max(0, total - self.votes_acked)
        self.votes_acked = total
        self._recent_votes.clear()
        if n and self.slo is not None \
                and hasattr(self.slo, "ack_retrain"):
            self.slo.ack_retrain(n)
        elif n:
            get_stream().emit("retrain_acked", count=n, total=total)
        return n

    # -- traffic tees → replay -----------------------------------------------
    def _drain_tees(self) -> None:
        if self.shadow is not None and hasattr(self.shadow,
                                               "drain_labeled"):
            rows, labels = self.shadow.drain_labeled()
            if rows:
                self.replay.add(rows, labels)
        if self.router_tee is not None:
            bodies = self.router_tee.drain()
            if bodies and self.label_fn is not None:
                rows: List[list] = []
                for b in bodies:
                    rows.extend(RouterTee.rows_of(b))
                if rows:
                    labels = [self._label(r) for r in rows]
                    self.replay.add(rows, labels)

    def _label(self, row: list):
        try:
            return self.label_fn(row)
        except Exception:                # noqa: BLE001 — an unjoinable
            return None                  # row is skipped, never poison

    # -- the tick ------------------------------------------------------------
    def tick(self) -> None:
        """One control step; safe to call from any single loop (the
        fleet manager's watch tick, or this controller's own thread)."""
        now = time.monotonic()
        self._drain_tees()
        self._poll_child(now)
        # votes are observed EVERY tick (the flap detector needs the
        # honest per-tick arrival rate, not a lump when idle resumes);
        # only the idle state may act on them
        pending = self._observe_votes(now)
        state = self.state
        if state == "training":
            return                       # child alive; _poll_child watches
        if state in ("gating", "canary"):
            self._watch_candidate(now)
            return
        if state == "cooldown":
            if now < self._cooldown_until:
                return
            self._set_state("idle", emit=False)   # expired: fall through
        # idle: debounce votes through the storm controls
        if pending < self.min_votes:
            return
        if now < self._cooldown_until:
            return                       # per-model cooldown holds
        if now < self._flap_until:
            return                       # flap detector holds
        self._window = [t for t in self._window
                        if now - t <= self.window_s]
        if len(self._window) >= self.max_retrains_per_window:
            self.last_error = (f"retrain budget exhausted "
                               f"({self.max_retrains_per_window} per "
                               f"{self.window_s:.0f}s window)")
            return
        if self._child is not None:
            return                       # concurrent-retrain budget: 1
        self.trigger(f"{pending} drift vote(s) within "
                     f"{self.vote_window_s:.0f}s")

    def trigger(self, reason: str) -> bool:
        """Launch one supervised retrain now (the debounced path calls
        this; ``retrain --once`` calls it directly). Returns False when
        there is no promoted bundle to warm-start from or no data."""
        pb = promoted_bundle(self.checkpoint_dir, self._name)
        if pb is None:
            self.last_error = "no PROMOTED bundle to warm-start from"
            return False
        self.replay.flush()
        if not self.train_input and not self.replay.rows():
            self.last_error = "no training data (empty replay buffer " \
                              "and no train_input)"
            return False
        self._ack_votes()
        with self._lock:
            self.attempts += 1
            self.last_trigger_reason = reason
        self._window.append(time.monotonic())
        self._set_state("triggered", reason=reason, warm_step=pb[0])
        self._launch(pb[1])
        self._set_state("training", warm_step=pb[0], emit=False)
        # an already-exited child (a failed exec, or a test stand-in)
        # resolves on the triggering tick instead of waiting one interval
        self._poll_child(time.monotonic())
        return True

    # -- child supervision ---------------------------------------------------
    def _spec(self, warm_bundle: str) -> dict:
        return {"algo": self.algo, "options": self.options,
                "checkpoint_dir": self.checkpoint_dir,
                "warm_bundle": warm_bundle,
                "train_input": self.train_input,
                "replay_dir": self.replay.dir,
                "batch_size": self.batch_size, "epochs": self.epochs}

    def _launch(self, warm_bundle: str) -> None:
        # one process per chip (utils/device.py): the serving replicas
        # hold the chips, so the child trains on the host CPU ON PURPOSE
        # unless its launcher names a device (env=chip_env(i) on a host
        # with a spare chip). Its result line reports the platform.
        from ..utils.device import CPU_ENV, child_env, names_device
        env = child_env(None if names_device(self.env) else CPU_ENV,
                        self.env)
        proc = subprocess.Popen(
            [sys.executable, "-m", "hivemall_tpu.serve.retrain",
             "--child", json.dumps(self._spec(warm_bundle))],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=env)
        out: List[str] = []
        # _child is written by the tick thread here AND by stop() on the
        # owner's thread (a stop racing a slow tick is legal), so every
        # write takes the controller lock
        with self._lock:
            self._child = proc
            self._child_out = out
            self._child_since = time.monotonic()

        def read():
            try:
                for line in proc.stdout:
                    out.append(line)
            except Exception:            # noqa: BLE001 — pipe teardown
                pass

        reader = threading.Thread(target=read, name="retrain-child-out",
                                  daemon=True)
        with self._lock:
            self._child_reader = reader
        reader.start()

    def _poll_child(self, now: float) -> None:
        child = self._child
        if child is None:
            return
        rc = child.poll()
        if rc is None:
            if self._child_since is not None \
                    and now - self._child_since > self.train_timeout_s:
                child.terminate()
                try:
                    child.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    child.kill()
                with self._lock:
                    self._child = None
                self._attempt_failed("retrain child timed out after "
                                     f"{self.train_timeout_s:.0f}s")
            return
        with self._lock:
            self._child = None
            reader = self._child_reader
        if reader is not None:
            # the child can exit the instant after printing its result:
            # let the pipe reader drain to EOF before parsing, or a
            # successful retrain could misread as a no-result failure
            reader.join(timeout=5.0)
        result = None
        for line in reversed(self._child_out):
            try:
                result = json.loads(line)
                break
            except ValueError:
                continue
        if rc != 0 or not isinstance(result, dict) \
                or not result.get("ok"):
            err = (result or {}).get("error") or f"child exit rc={rc}"
            self._attempt_failed(f"retrain failed: {err}")
            return
        with self._lock:
            self._candidate = {"bundle": result["bundle"],
                               "step": int(result["step"])}
        self._set_state("gating", bundle=result["bundle"],
                        step=result["step"], rows=result.get("rows"),
                        seconds=result.get("seconds"),
                        platform=result.get("platform"))
        if self.gate is not None:
            self._gate_own()

    def _attempt_failed(self, reason: str) -> None:
        self.last_error = reason
        self._enter_cooldown(self.cooldown_s)
        get_stream().emit("retrain", state="cooldown", outcome="failed",
                          reason=reason)

    # -- candidate fate ------------------------------------------------------
    def _gate_own(self) -> None:
        """CLI standalone mode (``retrain --once`` with a holdout): gate
        the candidate ourselves and flip/quarantine like the promotion
        controller would."""
        from ..io.checkpoint import promote_bundle, reject_bundle
        from .promote import _gate_summary
        path = self._candidate_path()
        pb = promoted_bundle(self.checkpoint_dir, self._name)
        report = self.gate.evaluate(path, pb[1] if pb else None)
        if report["verdict"] == "pass":
            promote_bundle(self.checkpoint_dir, path,
                           gate=_gate_summary(report), state="serving")
            get_stream().emit("promotion",
                              bundle=os.path.basename(path),
                              step=report["step"], state="serving")
            self._candidate_promoted()
        else:
            reject_bundle(path, "; ".join(report["reasons"]))
            self._candidate_rejected("; ".join(report["reasons"]))

    def _watch_candidate(self, now: float) -> None:
        """gating/canary: learn the candidate's fate purely from disk —
        the ``.rejected`` marker and the pointer manifest (which is what
        makes SIGKILL-anywhere recovery free)."""
        path = self._candidate_path()
        if path is None:
            self._set_state("idle", emit=False)
            return
        step = int(self._candidate["step"])
        if is_rejected(path):
            if self.state == "canary":
                with self._lock:
                    self.rollbacks += 1
                self._candidate_rejected("canary rolled back",
                                         rolled_back=True)
            else:
                from ..io.checkpoint import rejected_reason
                self._candidate_rejected(rejected_reason(path)
                                         or "gate rejected")
            return
        m = read_promoted(self.checkpoint_dir)
        cur = (m or {}).get("current") or {}
        cur_step = int(cur.get("step") or -1)
        if cur_step == step:
            if (m or {}).get("state") == "canary":
                if self.state != "canary":
                    self._set_state("canary", step=step)
            else:
                self._candidate_promoted()
            return
        if cur_step > step:
            # a newer promotion superseded our candidate while it waited
            self._candidate_done("superseded", outcome="superseded")
            return
        if now - self._phase_since > self.gate_timeout_s:
            self._candidate_done(
                f"no gate verdict within {self.gate_timeout_s:.0f}s",
                outcome="gate_timeout")

    def _candidate_promoted(self) -> None:
        with self._lock:
            self.successes += 1
            self._consecutive_rejections = 0
        # votes that arrived WHILE this retrain ran were votes against
        # the model it just replaced — answered, so acked; a rejection
        # leaves them pending (they retry once the backoff lapses)
        self._ack_votes()
        self._candidate_done("promoted", outcome="promoted",
                             cooldown=self.cooldown_s)

    def _candidate_rejected(self, reason: str,
                            rolled_back: bool = False) -> None:
        with self._lock:
            self.rejections += 1
            self._consecutive_rejections += 1
            k = self._consecutive_rejections
        cool = min(self.max_backoff_s,
                   self.cooldown_s * (self.backoff_factor ** k))
        self._candidate_done(reason,
                             outcome="rolled_back" if rolled_back
                             else "rejected", cooldown=cool)

    def _candidate_done(self, reason: str, *, outcome: str,
                        cooldown: Optional[float] = None) -> None:
        bundle = (self._candidate or {}).get("bundle")
        with self._lock:
            self._candidate = None
        if outcome not in ("promoted",):
            self.last_error = reason
        self._enter_cooldown(cooldown if cooldown is not None
                             else self.cooldown_s)
        get_stream().emit("retrain", state="cooldown", outcome=outcome,
                          reason=reason, bundle=bundle)

    def _enter_cooldown(self, seconds: float) -> None:
        self._cooldown_until = time.monotonic() + max(0.0, seconds)
        self._set_state("cooldown", emit=False)

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "RetrainController":
        """Self-ticking daemon thread (standalone / single-server mode;
        the fleet manager ticks in its own watch loop instead)."""
        if self._thread is not None:
            return self
        self._stop.clear()

        def run():
            while not self._stop.wait(self.interval):
                try:
                    self.tick()
                except Exception as e:   # noqa: BLE001 — the autopilot
                    self.last_error = f"{type(e).__name__}: {e}"   # must
                    #                    outlive any one bad tick

        self._thread = threading.Thread(target=run, name="retrain-ctl",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        with self._lock:
            child, self._child = self._child, None
        if child is not None and child.poll() is None:
            child.terminate()
            try:
                child.wait(timeout=5)
            except subprocess.TimeoutExpired:
                child.kill()

    def wait_idle(self, timeout: float = 60.0) -> bool:
        """Block until the controller leaves the active states (test /
        --once helper)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.state in ("idle", "cooldown"):
                return True
            self.tick()
            time.sleep(0.1)
        return False

    # -- obs -----------------------------------------------------------------
    def status(self) -> dict:
        """The ``retrain --status`` payload: the live section plus the
        on-disk stamp and pointer context."""
        out = {"section": self.obs_section()}
        try:
            with open(self._state_path()) as f:
                out["stamp"] = json.load(f)
        except (OSError, ValueError):
            out["stamp"] = None
        out["promoted"] = read_promoted(self.checkpoint_dir)
        return out

    def obs_section(self) -> dict:
        with self._lock:
            cand = dict(self._candidate) if self._candidate else None
            state = self.state
        now = time.monotonic()
        d = retrain_stub()
        d.update({
            "configured": True,
            "state": state,
            "attempts": self.attempts,
            "successes": self.successes,
            "rejections": self.rejections,
            "rollbacks": self.rollbacks,
            "flaps": self.flaps,
            "votes_seen": self.votes_seen,
            "votes_acked": self.votes_acked,
            "cooldown_remaining_s": round(
                max(0.0, self._cooldown_until - now), 1),
            "child_alive": self._child is not None,
            "candidate_step": (cand or {}).get("step"),
            "last_trigger_reason": self.last_trigger_reason,
            "last_error": self.last_error,
            "replay": self.replay.counters(),
        })
        return d

    def _register_obs(self) -> None:
        import weakref
        from ..obs.registry import registry
        ref = weakref.ref(self)

        def retrain() -> dict:
            c = ref()
            return c.obs_section() if c is not None else retrain_stub()

        registry.register("retrain", retrain)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="hivemall_tpu.serve.retrain")
    ap.add_argument("--child", metavar="SPEC_JSON",
                    help="run one retrain attempt from a json spec "
                         "(internal: spawned by RetrainController)")
    args = ap.parse_args(argv)
    if args.child:
        return _child(args.child)
    ap.error("only --child mode is runnable directly; use "
             "`hivemall_tpu retrain` for the controller")
    return 2


if __name__ == "__main__":
    sys.exit(main())
