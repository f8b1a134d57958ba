"""LearnerBase — the trainer-UDTF lifecycle over TPU minibatch kernels.

Reference: hivemall.LearnerBaseUDTF + UDTFWithOptions (SURVEY.md §3.1, §4.1):
a trainer is fed rows one at a time (``process``), holds model state, and at
``close()`` emits the model as (feature, weight) rows. The rebuild keeps that
exact lifecycle — tests drive trainers the way the reference's unit tests
drive UDTFs by hand (SURVEY.md §5.1) — and adds a columnar fast path
(``fit(dataset)``) that skips per-row Python entirely.

Streaming semantics: rows buffer into fixed-shape minibatches (power-of-two
padded length so jit traces a few shapes); each full buffer dispatches one
jitted step. ``-iters > 1`` replays the recorded stream for further epochs
with reshuffling, the NioStatefulSegment analog.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..io.pipeline import PipelineStats
from ..io.sparse import (MegaBatch, PackedMegaBatch, SparseBatch,
                         SparseDataset, pow2_len, score_batches,
                         split_feature)
from ..obs.devprof import get_devprof
from ..obs.flight import FS, get_flight
from ..obs.trace import get_tracer
from ..utils.hashing import mhash
from ..utils.metrics import Meter, get_stream
from ..utils.options import OptionSpec, Parsed

__all__ = ["LearnerBase", "learner_option_spec",
           "add_mix_reliability_options", "sigmoid_np"]


def _fetch(tree):
    """Device values to host ones: THE place the train loop's dispatch
    path converts (and so may wait for the device) — the loss fold, and
    the per-step loss hooks when a check has set one. A test counts the
    calls to hold the path between two folds to none."""
    import jax
    return jax.device_get(tree)


def sigmoid_np(x: np.ndarray) -> np.ndarray:
    """Numerically-stable host-side sigmoid — THE margin->probability map
    of every classification scoring path (predict_proba and the serve
    engine share it, so online and offline probabilities bit-match)."""
    return np.where(x >= 0, 1.0 / (1.0 + np.exp(-x)),
                    np.exp(x) / (1.0 + np.exp(x)))


def add_mix_reliability_options(s: OptionSpec) -> OptionSpec:
    """MIX fault-tolerance knobs (docs/RELIABILITY.md): retry + backoff +
    circuit breaker replacing the old first-error permanent kill-switch.
    Shared by the general learner grammar and the bespoke specs of
    trainers that also mix (covariance classifiers etc.)."""
    s.add("mix_timeout", type=float, default=2.0, min=1e-3,
          help="per-socket-op MIX timeout in seconds")
    s.add("mix_retries", type=int, default=2, min=0,
          help="extra attempts per MIX exchange after the first fails "
               "(reconnect + resend with jittered exponential backoff)")
    s.add("mix_backoff", type=float, default=0.05, min=0.0,
          help="base MIX retry backoff seconds (doubled per attempt, "
               "jittered in [0.5x, 1.5x), capped at 2s)")
    s.add("mix_deadline", type=float, default=0.0, min=0.0,
          help="wall-clock budget per MIX exchange incl. retries; "
               "0 = 2x -mix_timeout")
    s.add("mix_breaker_threshold", type=int, default=3, min=1,
          help="consecutive failed exchanges that open the MIX circuit "
               "breaker (exchanges then drop instead of blocking on a "
               "dead server)")
    s.add("mix_breaker_cooldown", type=float, default=1.0, min=0.0,
          help="seconds the breaker stays open before a half-open "
               "reconnect probe")
    s.add("mix_breaker_trips", type=int, default=3, min=1,
          help="consecutive breaker opens (no success between) before "
               "the client degrades permanently to unmixed training")
    return s


def _mix_knob_defaults() -> dict:
    """The single source of truth for mix-knob defaults: derived from the
    option spec above, so bespoke trainer specs that predate a knob fall
    back to exactly the documented default (no second literal to drift)."""
    cached = getattr(_mix_knob_defaults, "_cache", None)
    if cached is None:
        spec = add_mix_reliability_options(OptionSpec("_mix_knobs"))
        cached = {o.name: o.default for o in spec.options}
        _mix_knob_defaults._cache = cached
    return cached


def learner_option_spec(name: str, *, classification: bool,
                        default_loss: str) -> OptionSpec:
    """The shared trainer grammar (reference: LearnerBaseUDTF +
    GeneralLearnerBaseUDTF options)."""
    s = OptionSpec(name)
    s.add("loss", "loss_function", default=default_loss,
          help="loss function")
    s.add("opt", "optimizer", default="adagrad", help="optimizer")
    s.add("reg", "regularization", default="rda",
          help="regularization: no|l1|l2|elasticnet|rda")
    s.add("lambda", type=float, default=1e-6, help="regularization strength")
    s.add("l1_ratio", type=float, default=0.5, help="elasticnet mixing")
    s.add("eta", default="inverse", help="eta scheme: fixed|simple|inverse")
    s.add("eta0", type=float, default=0.1, help="initial learning rate")
    s.add("total_steps", type=int, default=10_000, help="simple-eta horizon")
    s.add("power_t", type=float, default=0.1, help="inverse-eta exponent")
    s.add("iters", "iterations", type=int, default=1, help="epochs")
    s.add("mini_batch", "mini_batch_size", type=int, default=256,
          help="minibatch size dispatched per jitted step")
    s.add("ingest_workers", type=int, default=0,
          help="host batch-prep pool size for fit/fit_stream: 0 = auto "
               "(cores-1 capped at 8 on accelerators, 1 on CPU); 1 = "
               "strict sequential (bit-exact pre-pipeline behavior); "
               "N > 1 = N prep worker threads delivering in order")
    s.add("ingest_pool", default="auto",
          help="prep pool kind for -ingest_workers > 1: thread (default — "
               "the canonicalize/pack prep is GIL-releasing NumPy/C++) | "
               "process (true multi-process prep for string-parse-heavy "
               "Python-bound sources; the trainer's prep must be a "
               "picklable config-built function — FFM and the base "
               "trainers qualify) | auto (thread)")
    s.add("shard_cache_dir", default=None,
          help="ahead-of-time packed shard cache directory "
               "(io.shard_cache): after the first epoch parses/"
               "canonicalizes/packs a source, the prepared buffers "
               "persist keyed by (source identity, prep-config digest); "
               "later epochs, -iters replays and restarts mmap them and "
               "skip host prep entirely. Parquet shard directories also "
               "cache their decoded CSR columns here. See "
               "docs/PERFORMANCE.md 'Shard cache'")
    s.add("steps_per_dispatch", type=int, default=0,
          help="fused multi-step dispatch: stack K prepared minibatches "
               "into ONE h2d transfer and run all K optimizer steps in "
               "one jitted lax.scan (donated state — no per-step table "
               "copies). 0 = auto (8 on accelerators for trainers with "
               "a scannable step, 1 on CPU); 1 = per-batch dispatch "
               "(bit-exact pre-fusion behavior); ragged tails and mixed "
               "batch kinds fall back to 1")
    s.add("dims", "feature_dimensions", type=int, default=1 << 24,
          help="model table size (hashed feature space)")
    s.flag("dense", "densemodel",
           help="accepted for reference compatibility (model is always a "
                "dense TPU table)")
    s.flag("disable_halffloat",
           help="keep float32 weights (default); unset-able via -halffloat")
    s.flag("halffloat", help="store weights as bfloat16 (HalfFloat analog)")
    s.flag("int_feature", help="features are integer indices, no hashing")
    s.add("seed", type=int, default=42,
          help="seed of whatever the trainer draws (initial factors; a "
               "table that starts at zero draws nothing)")
    s.add("mesh", default=None,
          help="device mesh spec ('dp=2,tp=4' or 'auto'): run the train "
               "step GSPMD-sharded — batch over dp, weight tables over tp")
    s.add("mix", default=None, help="mix cohort spec (parallel.mix)")
    s.add("mix_threshold", type=int, default=16,
          help="local updates between mix exchanges")
    s.add("mix_session", default=None, help="mix session/group id")
    add_mix_reliability_options(s)
    s.flag("ssl", help="TLS-wrap the MIX connection (reference LearnerBase "
                       "-ssl); pair with -ssl_cafile to verify the server")
    s.add("ssl_cafile", default=None,
          help="CA / self-signed server certificate to verify against "
               "(omit for encrypted-but-unauthenticated, matching the "
               "reference's in-cluster -ssl)")
    s.add("loadmodel", default=None, help="warm-start from a saved model table")
    # elastic recovery (SURVEY.md §6): autosaved full-state bundles +
    # mid-stream resume — see docs/RELIABILITY.md
    s.add("checkpoint_dir", default=None,
          help="directory for autosaved checkpoint bundles; enables "
               "resume() and per-epoch fit() bundles")
    s.add("checkpoint_every", type=int, default=0, min=0,
          help="autosave a full-state bundle every N optimizer steps "
               "during fit_stream (atomic write, last -checkpoint_keep "
               "retained); 0 = off")
    s.add("checkpoint_keep", type=int, default=3, min=1,
          help="how many autosaved step bundles to retain")
    # unified telemetry (docs/OBSERVABILITY.md): registry snapshots into
    # the jsonl stream at a step cadence, plus the live HTTP surface
    s.add("telemetry_every", type=int, default=0, min=0,
          help="emit the full obs-registry snapshot as a 'telemetry' "
               "jsonl event every N optimizer steps (requires "
               "HIVEMALL_TPU_METRICS); 0 = off")
    s.add("obs_port", type=int, default=0, min=0,
          help="serve the obs registry over HTTP on this port: /snapshot "
               "(JSON) and /metrics (Prometheus text exposition) — the "
               "MixServer-JMX analog for the training runtime; 0 = off")
    s.flag("cv", help="track cumulative loss for convergence check")
    return s


_END = object()          # end-of-stream sentinel for next(it, _END)


def _identity_prep(batch):
    """Module-level identity prep — the picklable stand-in for trainers
    whose parallel prep leg is the base no-op, so ``-ingest_pool process``
    works for every trainer (a bound method would not cross the process boundary)."""
    return batch


def _default_platform() -> str:
    """Platform of the device a new array lands on: ``jax.default_device``'s
    where one is set (the benchmark builds one trainer on the host's CPU
    beside a TPU), else the default backend's."""
    import jax
    dev = jax.config.jax_default_device
    return jax.default_backend() if dev is None \
        else getattr(dev, "platform", dev)


@functools.lru_cache(maxsize=256)
def _state_initialiser(init, sharding_leaves: tuple = (), treedef=None):
    """jit of a state initialiser, cached on the function and on where its
    outputs go (the flattened out_shardings; none: the default device), so
    trainers of one configuration share one compile."""
    import jax
    return jax.jit(init, out_shardings=None if treedef is None else
                   jax.tree_util.tree_unflatten(treedef, sharding_leaves))


#: ``w`` of a flat-table family (models/linear.py, models/classifier.py):
#: the [dims] weight table under the name its older callers use, a view of
#: ``params``, the one name every family keeps its model state under
weight_table_view = property(
    lambda self: self.params,
    lambda self, table: setattr(self, "params", table))

_STEP_BUILDER_CACHE: dict = {}


def shared_step(trainer, tag: str, builder):
    """Config-cached jitted step: same-class trainers with identical
    scalar options share ONE compiled step instead of re-tracing per
    instance (the per-instance re-jit disease — measured costing
    word2vec 4x and LDA 10x before the same fix; fm/ffm/linear use
    module-level lru_caches, this is the generic form for trainers whose
    steps are built from bound-method closures over opts). Safe because
    the steps take all state as arguments and the closures are pure
    functions of the keyed option values (donation applies per CALL)."""
    key = (type(trainer).__name__, tag,
           tuple(sorted((k, v) for k, v in trainer.opts.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None)))
    fn = _STEP_BUILDER_CACHE.get(key)
    if fn is None:
        # bounded like the fm/linear lru_caches: a sweep over many
        # distinct configs must not grow compiled-step memory forever
        if len(_STEP_BUILDER_CACHE) >= 256:
            _STEP_BUILDER_CACHE.pop(next(iter(_STEP_BUILDER_CACHE)))
        t0 = time.perf_counter()
        fn = builder()
        _STEP_BUILDER_CACHE[key] = fn
        # the generic peer of the lru_cache factories' build telemetry
        get_devprof().record_build(type(trainer).__name__, tag,
                                  time.perf_counter() - t0)
    return fn


class LearnerBase:
    """Subclasses set NAME/CLASSIFICATION/DEFAULT_LOSS and _build/_step."""

    NAME = "learner"
    CLASSIFICATION = True
    DEFAULT_LOSS = "hingeloss"

    def _shared_step(self, tag: str, builder):
        return shared_step(self, tag, builder)

    @classmethod
    def spec(cls) -> OptionSpec:
        return learner_option_spec(cls.NAME, classification=cls.CLASSIFICATION,
                                   default_loss=cls.DEFAULT_LOSS)

    def __init__(self, options: str = ""):
        self.opts: Parsed = self.spec().parse(options)
        self.dims = int(self.opts.dims)
        self._names: Dict[int, str] = {}      # hashed id -> original name
        self._buf_rows: List[Tuple[np.ndarray, np.ndarray]] = []
        self._buf_labels: List[float] = []
        # -iters replay buffer: RAM up to a byte budget, then disk
        # segments (the NioStatefulSegment analog — io/replay_segment.py)
        from ..io.replay_segment import RowSegmentStore
        self._replay = RowSegmentStore()
        self._t = 0                           # global step (batches seen)
        self._stream_pos = 0                  # fit_stream batches consumed
        self._loss_sum = 0.0                  # host float64, exact
        self._loss_pending = 0.0              # on-device partial, folded in
        # what the step counts about itself (ops/fm.py TAIL_STATS), where
        # it returns any: each dispatch's device values, kept unread (not
        # even summed: a computation queued behind the step costs the
        # dispatching thread 100 ms on a TPU), and the host totals they
        # fold into with the loss (this process's steps; not checkpointed)
        self._stats_pending: List[dict] = []
        self._step_counts: Dict[str, int] = {}
        self._examples = 0
        self._meter = Meter()                 # rolling examples/sec (§6)
        self._tracer = get_tracer()           # span tracing (obs.trace)
        self._flight = get_flight()           # black box (obs.flight)
        self._devprof = get_devprof()         # compile/memory/drift (obs)
        self.pipeline_stats = PipelineStats()  # last fit's ingest metrics
        self._mixer = None
        self._ck_manager = None               # fit_stream's autosaver (obs)
        self._fit_ds = None                   # columnar dataset ref (fit)
        self.mesh = None                      # jax Mesh when -mesh is set
        self._state_on_mesh = False           # _make_state placed the state
        self._tp_sizes = {self.dims}          # axis sizes sharded over 'tp'
        self._elision_off = False             # set on first non-unit batch
        if self.opts.get("mesh"):
            # the mesh comes first: _init_state's leaves are then born in
            # their sharding (_make_state) and no chip holds a whole table
            self._open_mesh(self.opts.mesh)
        self._init_state()
        if self.opts.get("mix"):
            # covariance trainers (CW/AROW/SCW) mix by argmin-KLD —
            # precision-weighted Gaussian posterior merge (SURVEY.md §3.16)
            from ..parallel.mix_service import (EVENT_ARGMIN_KLD,
                                                EVENT_AVERAGE, MixClient)
            has_covar = getattr(self, "sigma", None) is not None
            sslctx = None
            if self.opts.get("ssl"):
                from ..parallel.mix_service import make_client_ssl_context
                sslctx = make_client_ssl_context(self.opts.ssl_cafile)
            # bespoke trainer specs may predate a knob: fall back to the
            # spec-derived default rather than requiring every spec to
            # carry all of add_mix_reliability_options (None = unset,
            # 0 is a valid setting)
            defaults = _mix_knob_defaults()

            def knob(name):
                v = self.opts.get(name)
                return defaults[name] if v is None else v
            self._mixer = MixClient(
                self.opts.mix,
                group=self.opts.mix_session or self.NAME,
                threshold=int(self.opts.mix_threshold),
                event=EVENT_ARGMIN_KLD if has_covar else EVENT_AVERAGE,
                timeout=float(knob("mix_timeout")),
                ssl_context=sslctx,
                retries=int(knob("mix_retries")),
                backoff=float(knob("mix_backoff")),
                deadline=float(knob("mix_deadline")) or None,
                breaker_threshold=int(knob("mix_breaker_threshold")),
                breaker_cooldown=float(knob("mix_breaker_cooldown")),
                breaker_trips=int(knob("mix_breaker_trips")))
        if self.opts.loadmodel:
            self._warm_start(self.opts.loadmodel)
        if self.opts.get("mesh"):
            self._apply_mesh(self.opts.mesh)
        self._state_bytes_per_chip = self._fullest_chip_bytes()
        self._telemetry_every = int(self.opts.get("telemetry_every") or 0)
        self._register_obs()

    @classmethod
    def make_parser(cls, options: str = "") -> "LearnerBase":
        """A PARSE-ONLY instance: option grammar + feature hashing
        (`_parse_row`), with ``_init_state`` skipped — no device tables,
        no optimizer state. The serve engine's arena path uses this so a
        replica that scores from the mmap'd weight arena never allocates
        a dims-sized trainer just to hash request rows (the whole point
        of zero-copy serving). Only parsing methods are usable on the
        result; training/scoring surfaces raise AttributeError."""
        self = object.__new__(cls)
        self.opts = cls.spec().parse(options)
        self.dims = int(self.opts.dims)
        self._names = {}
        self.mesh = None
        self._init_parser()
        return self

    def _init_parser(self) -> None:
        """Hook for subclasses whose ``_parse_row`` needs extra state
        (FFM's field count). Default: nothing beyond make_parser's."""

    # -- subclass surface ----------------------------------------------------
    def _init_state(self) -> None:
        raise NotImplementedError

    def _train_batch(self, batch: SparseBatch):
        """Run one jitted step; returns the summed loss over valid rows as a
        device array (kept unconverted so async dispatch can pipeline; the
        base loop folds it via _fold_loss at cadence)."""
        raise NotImplementedError

    def _finalized_weights(self) -> np.ndarray:
        raise NotImplementedError

    # -- unified telemetry (obs.registry, docs/OBSERVABILITY.md) -------------
    def _register_obs(self) -> None:
        """Register this trainer's counter surfaces with the central obs
        registry: ``pipeline`` (ingest/stager/h2d stage counters),
        ``train`` (step/examples/rate/loss), and ``mix`` (client breaker +
        exchange counters) when mixing. Providers hold the trainer weakly
        (the registry is process-global, must not pin dead trainers) and
        are non-blocking — avg_loss reads the host-side folded sum only,
        never syncing the device from a scrape thread."""
        import weakref
        from ..obs.registry import CHECKPOINT_STUB, MIX_STUB, registry
        ref = weakref.ref(self)

        def pipeline() -> dict:
            t = ref()
            return t.pipeline_stats.as_dict() if t is not None else {}

        def train() -> dict:
            t = ref()
            if t is None:
                return {}
            shape = {"dp": 1, "tp": 1} if t.mesh is None else t.mesh.shape
            return {"trainer": t.NAME, "step": t._t,
                    "examples": t._examples,
                    "examples_per_sec": round(t._meter.rate, 1),
                    "avg_loss": round(t._loss_sum / max(1, t._examples), 6),
                    "mesh_dp": int(shape["dp"]), "mesh_tp": int(shape["tp"]),
                    "state_bytes_per_chip": t._state_bytes_per_chip,
                    **t._step_counts}

        def mix() -> dict:
            t = ref()
            if t is None or t._mixer is None:
                return dict(MIX_STUB)     # inactive form mirrors live keys
            c = dict(t._mixer.counters())
            c["active"] = True
            return c

        def checkpoint() -> dict:
            t = ref()
            m = getattr(t, "_ck_manager", None) if t is not None else None
            return m.obs_section() if m is not None \
                else dict(CHECKPOINT_STUB)

        # every section registers UNCONDITIONALLY, bound to THIS trainer:
        # a trainer without a mixer/autosaver reports inactive rather than
        # letting a previous trainer's live sections leak into its
        # snapshots (last-wins registration makes construction the reset)
        registry.register("pipeline", pipeline)
        registry.register("train", train)
        registry.register("mix", mix)
        registry.register("checkpoint", checkpoint)
        # a telemetry cadence or live obs surface means someone is
        # watching: turn on the devprof drift watches (per-dispatch step
        # drift, memory-leak drift) for this process. Without either the
        # watches stay off and note_dispatch is one attribute check.
        if self._telemetry_every or int(self.opts.get("obs_port") or 0):
            self._devprof.activate()
        if int(self.opts.get("obs_port") or 0):
            from ..obs.http import ensure_server
            ensure_server(int(self.opts.obs_port))

    def _emit_cadence_events(self, window: int) -> None:
        """The per-dispatch emission ladder. ``window`` is how many
        optimizer steps this dispatch advanced (K for a fused megastep).

        Loss-fold cadence (a 256-step boundary crossed): fold the device
        loss partial into the host float64, then — stream permitting —
        emit ``train_step`` (the reportProgress analog) and, when tracing,
        the per-stage ``span_rollup``. ``-telemetry_every`` boundaries
        additionally emit the full registry snapshot."""
        fold = self._t % 256 < window
        every = self._telemetry_every
        telemetry = bool(every) and self._t % every < window
        if fold:
            self._fold_loss()
        if fold or telemetry:
            with self._tracer.span("loop.cadence"):
                self._emit_cadence(fold, telemetry)

    def _emit_cadence(self, fold: bool, telemetry: bool) -> None:
        if fold:
            fl = self._flight
            if fl.enabled:
                # the trainer's heartbeat in the black box: a fit that
                # dies (OOM'd retrain child, SIGKILLed worker) leaves its
                # last step/loss on disk for the post-mortem
                fl.record("fit.step",
                          f"step={self._t}{FS}ex={self._examples}{FS}"
                          f"loss={self._loss_sum / max(1, self._examples):.6f}")
            stream = get_stream()
            if stream.enabled:
                stream.emit("train_step", trainer=self.NAME, step=self._t,
                            examples=self._examples,
                            examples_per_sec=round(self._meter.rate, 1),
                            avg_loss=round(self._loss_sum
                                           / max(1, self._examples), 6))
                if self._tracer.enabled:
                    stream.emit("span_rollup", trainer=self.NAME,
                                step=self._t, stages=self._tracer.rollup())
        if telemetry:
            # refresh the device-memory gauges FIRST so the snapshot about
            # to be emitted carries this boundary's sample (and the
            # live-bytes stream feeds the mem-drift detector at exactly
            # the telemetry cadence)
            self._devprof.sample_memory()
            stream = get_stream()
            if stream.enabled:
                from ..obs.registry import registry
                stream.emit("telemetry", trainer=self.NAME, step=self._t,
                            snapshot=registry.snapshot())

    def _emit_train_done(self) -> None:
        """``train_done`` carrying the merged registry snapshot — the
        one-record run summary both the jsonl surface and the ``obs`` CLI
        read — plus the Chrome-trace export when configured."""
        stream = get_stream()
        if stream.enabled:
            from ..obs.registry import registry
            stream.emit("train_done", trainer=self.NAME, step=self._t,
                        examples=self._examples,
                        avg_loss=round(self.cumulative_loss, 6),
                        telemetry=registry.snapshot())
        fl = self._flight
        if fl.enabled:
            fl.record("fit.done",
                      f"step={self._t}{FS}ex={self._examples}")
        self._tracer.maybe_export()
        # one completed fit = compile warmup over: arm the no-retrace
        # sentinel so a later same-config trainer that re-compiles (the
        # word2vec disease) flags itself as `retrace` telemetry
        self._devprof.note_train_done()

    def _emit_checkpoint_event(self, path: str, **fields) -> None:
        """The ONE checkpoint-event emitter (epoch bundles here and in
        fm.py's adareg loop, CheckpointManager's cadence saves)."""
        stream = get_stream()
        if stream.enabled:
            stream.emit("checkpoint", trainer=self.NAME, path=path, **fields)

    def _save_epoch_bundle(self, ckdir: str, epoch: int) -> str:
        """Per-epoch full-state bundle + its checkpoint event."""
        os.makedirs(ckdir, exist_ok=True)
        path = os.path.join(ckdir, f"{self.NAME}-ep{epoch}.npz")
        self.save_bundle(path)
        self._emit_checkpoint_event(path, epoch=epoch)
        return path

    # -- UDTF lifecycle ------------------------------------------------------
    def process(self, features: Sequence[str] | Tuple[np.ndarray, np.ndarray],
                label: float) -> None:
        """Feed one row: features as "name:value" strings (or pre-parsed
        (idx, val) arrays), label per trainer convention."""
        idx, val = self._parse_row(features)
        y = self._convert_label(label)
        self._buf_rows.append((idx, val))
        self._buf_labels.append(y)
        if len(self._buf_rows) >= int(self.opts.mini_batch):
            self._flush()

    def close(self) -> Iterator[Tuple[str, float]]:
        """Flush, run extra epochs (-iters), emit model rows."""
        self._flush()
        iters = int(self.opts.iters)
        if iters > 1 and self._replay.n_rows:
            # epoch replay over the recorded stream (NioStatefulSegment
            # analog): exact global shuffle while everything fits the RAM
            # budget; past it, rows live in disk segments and epochs
            # stream them back one segment at a time (segment order and
            # within-segment rows shuffled)
            rng = np.random.default_rng(42)
            bs = int(self.opts.mini_batch)
            for ep in range(1, iters):
                if not self._replay.spilled:
                    rows_all = self._replay.ram_rows
                    labels_all = self._replay.ram_labels
                    order = rng.permutation(len(rows_all))
                    for s in range(0, len(order), bs):
                        take = order[s:s + bs]
                        self._flush_chunk([rows_all[i] for i in take],
                                          [labels_all[i] for i in take])
                else:
                    for rows, labels in self._replay.epoch_rows(rng):
                        for s in range(0, len(rows), bs):
                            self._flush_chunk(rows[s:s + bs],
                                              labels[s:s + bs])
        self._replay.cleanup()
        if self._mixer is not None:
            self._mixer.close_group()
        self._emit_train_done()
        yield from self.model_rows()

    # -- columnar fast path --------------------------------------------------
    def fit(self, ds: SparseDataset, *, epochs: Optional[int] = None,
            shuffle: bool = True,
            prefetch: Optional[bool] = None) -> "LearnerBase":
        epochs = int(self.opts.iters) if epochs is None else epochs
        bs = int(self.opts.mini_batch)
        labels = self._convert_labels(ds.labels)
        sid = getattr(ds, "source_id", None)   # survives the label rebuild:
        ds = SparseDataset(ds.indices, ds.indptr, ds.values, labels, ds.fields)
        if sid:                                # the shard cache keys on it
            ds.source_id = sid
        if self._wants_fit_ds():
            self._fit_ds = ds             # emission-time metadata (FFM pairs)
        # elastic recovery (SURVEY.md §6): per-epoch bundle when requested
        # (-checkpoint_dir option, or the env var the pre-option path used)
        ckdir = self.opts.get("checkpoint_dir") \
            or os.environ.get("HIVEMALL_TPU_CHECKPOINT_DIR")
        # tracing/profiling (SURVEY.md §6): HIVEMALL_TPU_PROF=<dir>
        # captures a jax.profiler trace of the FIRST fit() in the process
        # — open with tensorboard/xprof. Routed through obs.devprof so
        # the capture is discoverable (a `profile.capture` span + a
        # `profile` jsonl event) instead of an invisible side effect.
        prof_dir = self._devprof.start_profile_once()
        self.pipeline_stats = PipelineStats()   # fresh counters per fit
        try:
            self._fit_epochs(ds, epochs, bs, shuffle, prefetch, ckdir)
        finally:
            self._devprof.stop_profile(prof_dir)
        # one train_done per completed fit (the columnar peer of close()/
        # fit_stream), carrying the merged registry snapshot; not emitted
        # on the exception path
        self._emit_train_done()
        return self

    def _fit_epochs(self, ds, epochs, bs, shuffle, prefetch, ckdir,
                    seed0: int = 42) -> None:
        # overlap host batch prep + h2d with compute on accelerators
        # seed0: first epoch's shuffle seed — continuation callers (the
        # FFM replay cache's fallback) pass 42 + epochs_already_run so the
        # schedule matches an uninterrupted fit
        if prefetch is None:
            prefetch = self._wants_prefetch()
        for ep in range(epochs):
            closers: List = []
            it = self._ingest_iter(
                ds.batches(bs, shuffle=shuffle, seed=seed0 + ep), closers)
            it = self._wrap_megabatch(it, prefetch=prefetch)
            if prefetch:
                it = self._wrap_prefetch(it, closers)
            try:
                for b in self._inputs(it):
                    self._dispatch(b)
            finally:
                for c in reversed(closers):
                    c()              # release the workers on early exit too
            if ckdir:
                self._save_epoch_bundle(ckdir, ep + 1)

    def _wants_fit_ds(self) -> bool:
        """Whether fit() should keep a reference to the training dataset for
        emission-time metadata. Default no — pinning a Criteo-scale dataset
        on the trainer for its whole lifetime is not free."""
        return False

    # Trainers whose jitted step accepts val=None (rebuilding it from idx
    # on device) set this True: unit-valued categorical batches then skip
    # the val h2d transfer entirely (a third of batch bytes — the link is
    # the measured e2e bottleneck; see io.sparse.SparseBatch).
    UNIT_VAL_ELISION = False

    def _preprocess_batch(self, batch: SparseBatch) -> SparseBatch:
        """Host-side per-batch hook, applied BEFORE device staging (so the
        prefetcher overlaps it with compute). Default: unit-value elision
        when the trainer's step supports it; FFM's joint layout overrides
        to canonicalize into field-major slots.

        The first non-unit batch disables the scan for the trainer's
        lifetime (real-valued datasets stay non-unit; a unit batch arriving
        later merely misses the optimization, which is always correct) —
        the O(B*L) check must not tax every epoch of data that can never
        elide."""
        if (self.UNIT_VAL_ELISION and not self._elision_off
                and isinstance(batch.val, np.ndarray)
                and isinstance(batch.idx, np.ndarray)):
            if np.array_equal(batch.val,
                              (batch.idx != 0).astype(np.float32)):
                return SparseBatch(batch.idx, None, batch.label, batch.field,
                                   n_valid=batch.n_valid,
                                   fieldmajor=batch.fieldmajor)
            self._elision_off = True
        return batch

    def _preprocess_train_batch(self, batch: SparseBatch):
        """TRAINING-ONLY per-batch hook (fit / fit_stream / process-flush):
        the serial leg then the parallel leg. Subclasses whose training
        dispatch accepts a representation scoring can't consume (e.g.
        FFM's packed uint8 transfer buffers) override the LEGS below,
        keeping _preprocess_batch — which the scoring paths share —
        representation-stable."""
        return self._preprocess_train_parallel(
            self._preprocess_train_serial(batch))

    def _preprocess_train_serial(self, batch: SparseBatch):
        """STREAM-ORDER-DEPENDENT training prep. Runs on ONE thread in
        source order even under -ingest_workers > 1 (the pipeline's
        submitter side), because the base elision latch (_elision_off)
        makes a batch's representation depend on the batches before it —
        fanning it out would make the output order-dependent and break
        the N-worker == sequential bit-exactness the tests pin."""
        return self._preprocess_batch(batch)

    def _preprocess_train_parallel(self, batch):
        """ORDER-INDEPENDENT training prep — the leg that fans out across
        the -ingest_workers pool. Must be a pure function of the batch
        (FFM's canonicalize + pack lives here)."""
        return batch

    def _regroup_prepared(self, it):
        """Between host prep and the stager, on the thread that consumes
        the prepared stream: what a streamed fit dispatches, in order.
        Default: the prepared batches themselves, one step each. A
        trainer whose prep is an EXPANSION overrides it (word2vec: a
        batch of token rows becomes a chunk of (center, context) pairs of
        whatever length, and the chunks are cut here into steps of
        ``-mini_batch`` pair slots, so a stream batch maps to no fixed
        number of steps)."""
        return it

    # -- parallel host ingest (SURVEY.md §8: the input path IS the wall) ----
    def _resolved_ingest_workers(self) -> int:
        """-ingest_workers with 0 = auto: cores-1 (cap 8) on accelerators —
        host prep there runs against a waiting chip — and 1 (strict
        sequential) on CPU, where the train step already owns the cores.
        Auto also collapses to 1 when the trainer never overrode the
        parallel prep leg (base identity): a pool whose workers each run
        ``return batch`` is pure queue overhead. An EXPLICIT N is always
        honored (tests drive the pipeline machinery through it)."""
        n = int(self.opts.get("ingest_workers") or 0)
        if n > 0:
            return n
        if type(self)._preprocess_train_parallel \
                is LearnerBase._preprocess_train_parallel:
            return 1
        import jax
        if jax.default_backend() == "cpu":
            return 1
        from ..io.pipeline import auto_workers
        return auto_workers()

    def _resolved_ingest_pool(self) -> str:
        """-ingest_pool with auto = thread: the in-tree prep profile
        (padding fancy-indexing, canonicalize, pack) is GIL-releasing
        NumPy/C++, so threads win by skipping per-batch pickling; process
        is the explicit opt-in for Python-bound string-parse prep."""
        p = str(self.opts.get("ingest_pool") or "auto")
        if p not in ("auto", "thread", "process"):
            raise ValueError(
                f"-ingest_pool must be auto|thread|process, got {p!r}")
        return "thread" if p == "auto" else p

    def _picklable_prep(self):
        """The parallel prep leg as a PICKLABLE callable for
        ``-ingest_pool process`` (a bound trainer method cannot cross the
        process boundary: it would drag the whole trainer — device arrays included —
        through pickle per task). Base trainers' parallel leg is the
        identity, which is trivially picklable; trainers that override the
        leg must also override this (FFM builds one from a plain prep
        config dataclass) or process pools fall back to threads."""
        if type(self)._preprocess_train_parallel \
                is LearnerBase._preprocess_train_parallel:
            return _identity_prep
        return None

    def _ingest_iter(self, src, closers: List):
        """Route ``_preprocess_train_batch`` over ``src`` through the
        parallel ingest pipeline (io.pipeline). workers <= 1 is a strict
        sequential fallback — a plain ``map``, bit-exact with pre-pipeline
        behavior. An opened pipeline's close lands in ``closers`` for the
        caller's finally; batches arrive in source order either way.
        workers <= 1 uses the pipeline's inline sequential mode — literally
        next(src) then fn(item), no threads — so the stage counters emit
        on both paths.

        The serial leg (_preprocess_train_serial: the elision latch) is
        composed into the SOURCE, so the pipeline's single submitter
        thread runs it in stream order; only the order-independent
        parallel leg fans out. The composition equals
        _preprocess_train_batch exactly on every path.

        ``-ingest_pool process`` swaps the bound parallel leg for the
        trainer's picklable config-built equivalent (same function of the
        batch, pinned bit-exact by tests/test_pipeline.py); trainers
        without one fall back to the thread pool with a warning."""
        from ..io.pipeline import IngestPipeline
        pool = self._resolved_ingest_pool()
        fn = self._preprocess_train_parallel
        if pool == "process":
            pfn = self._picklable_prep()
            if pfn is None:
                import warnings
                warnings.warn(
                    f"{type(self).__name__} has no picklable prep for "
                    f"-ingest_pool process; falling back to threads",
                    RuntimeWarning, stacklevel=2)
                pool = "thread"
            else:
                fn = pfn
        pipe = IngestPipeline(map(self._preprocess_train_serial, src), fn,
                              workers=self._resolved_ingest_workers(),
                              pool=pool, stats=self.pipeline_stats)
        closers.append(pipe.close)
        return pipe

    @staticmethod
    def _wants_prefetch() -> bool:
        """Whether fit / fit_stream stage their input from the
        ``h2d-prefetch`` thread: on every accelerator, with or without a
        mesh; not on the CPU, where a step owns the cores."""
        import jax
        return jax.default_backend() != "cpu"

    def _wrap_prefetch(self, it, closers: List, depth: int = 2):
        """Stage ``it`` onto the device — under -mesh, onto the mesh in
        ``_input_sharding``'s placement — ahead of compute, sharing this
        trainer's PipelineStats so prep/transfer/compute waits land in one
        struct (the registry's ``pipeline`` section)."""
        from ..io.prefetch import DevicePrefetcher
        pf = DevicePrefetcher(
            it, depth=depth, stats=self.pipeline_stats,
            sharding=None if self.mesh is None else self._input_sharding)
        closers.append(pf.close)
        return pf

    # -- fused multi-step dispatch (-steps_per_dispatch, ops.scan) -----------
    def _supports_megastep(self) -> bool:
        """Whether this trainer's step is scannable: the jitted step
        carries its pure ``(state, batch) -> (state, loss)`` core
        (ops.scan.scannable) and the trainer uses the standard
        (params-or-w, opt_state) state pair. Trainers with bespoke state
        (covariance pairs, tree ensembles, ...) fall out here and keep
        per-batch dispatch."""
        return getattr(getattr(self, "_step", None), "core", None) \
            is not None

    def _resolved_steps_per_dispatch(self) -> int:
        """-steps_per_dispatch with 0 = auto: 8 on accelerators — the
        per-batch jit call + h2d latency is the post-PR-1 e2e wall there
        — and 1 (per-batch, bit-exact pre-fusion behavior) on CPU, where
        dispatch overhead is noise and the test suite pins the K=1
        trajectory. Collapses to 1 for trainers without a scannable step
        and under MIX (the mix client touches every batch's idx on host
        at step cadence — fusing K steps would skip exchanges)."""
        k = int(self.opts.get("steps_per_dispatch") or 0)
        if k < 0:
            raise ValueError(f"-steps_per_dispatch must be >= 0, got {k}")
        if not self._supports_megastep() or self._mixer is not None:
            return 1
        if k > 0:
            return k
        import jax
        return 8 if jax.default_backend() != "cpu" else 1

    def _wrap_megabatch(self, it, *, prefetch: bool):
        """Insert the K-step stacking stage between host prep and the
        h2d prefetcher. Staging-buffer reuse is only armed when a
        DevicePrefetcher consumes the stager (its stage_batch provides
        the transfer-complete barrier the buffer ring needs)."""
        k = self._resolved_steps_per_dispatch()
        if k <= 1:
            return it
        from ..io.prefetch import MegabatchStager
        return MegabatchStager(it, k, stats=self.pipeline_stats,
                               reuse=prefetch)

    # -- mesh sharding (SURVEY.md §3.17 / §8 M3) -----------------------------
    def _open_mesh(self, spec: str) -> None:
        """Build the (dp, tp) device mesh of a ``-mesh`` spec."""
        from ..parallel.mesh import make_mesh, parse_mesh_spec
        dp, tp = parse_mesh_spec(spec)
        if int(self.opts.mini_batch) % dp:
            raise ValueError(
                f"-mini_batch {self.opts.mini_batch} must be divisible by "
                f"the dp axis ({dp})")
        self.mesh = make_mesh(dp=dp, tp=tp)

    def _apply_mesh(self, spec: str) -> None:
        """Shard this trainer's state over a (dp, tp) device mesh.

        The PRODUCT multi-chip path (not a demo kernel): the same jitted
        sparse step the single-chip trainer runs is compiled under GSPMD —
        batch arrays sharded over 'dp' (XLA inserts the gradient psum that
        replaces MixServer averaging), every dims-sized state axis sharded
        over 'tp' (feature-dim sharding, the context-parallel analog), the
        rest replicated. fit()/process() are unchanged. A trainer may hand
        the mesh to its step's factory instead where the mesh it sees lets
        every chip run the one-chip step on its own rows: `train_ffm`'s
        joint step under dp == 1, tp > 1 (`ops/fm.py`
        `make_ffm_step_fused(mesh=)`, `shard_map` over 'tp': the
        distinct-row tail and the gather through the distinct rows, which
        GSPMD's cut of the dense step has not); state and inputs are
        placed the same either way.

        State that ``_make_state`` built is on the mesh already; whatever
        was built whole on one device, or loaded over it (-loadmodel), is
        moved there."""
        if self.mesh is None:
            self._open_mesh(spec)
        if not self._state_on_mesh or self.opts.loadmodel:
            self._reshard_state()

    def _make_state(self, init, *args):
        """``init(*args)``, a pytree of state leaves, made where the leaves
        live. Under -mesh: ONE jitted program whose outputs are born in
        ``_state_sharding``'s sharding, each chip drawing and zeroing its
        own rows (the random bits are partitionable: a shard's numbers are
        the whole draw's, to the float32 ulp by which a fused draw rounds
        apart from an eager one), so no chip ever holds more than its part
        of a dims-sized leaf. Without a mesh the same program on the
        default device — except where that device is a CPU: there its
        operations are dispatched one by one, un-awaited, as they always
        were (what the benchmark's one host-built trainer costs is part of
        an accepted cell's ``setup_s``, and XLA's CPU backend is no faster
        for the one program). Give it a function that is the same object
        for the same configuration: the compile is cached on it."""
        import jax
        span_args = None
        if self.mesh is not None or self._tracer.enabled:
            shapes = jax.eval_shape(init, *args)
        if self._tracer.enabled:
            span_args = {"bytes": sum(
                l.size * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(shapes))}
        with self._tracer.span("init.state", None, None, span_args):
            if self.mesh is None and _default_platform() == "cpu":
                return init(*args)
            if self.mesh is None:
                make = _state_initialiser(init)
            else:
                leaves, treedef = jax.tree_util.tree_flatten(
                    jax.tree_util.tree_map(self._state_sharding, shapes))
                make = _state_initialiser(init, tuple(leaves), treedef)
                self._state_on_mesh = True
            return jax.block_until_ready(make(*args))

    def _fullest_chip_bytes(self) -> int:
        """Bytes of training state on the chip that holds most of it, from
        the leaves' addressable shards as the constructor left them (the
        registry's ``train.state_bytes_per_chip``; shapes and shardings do
        not change afterwards). 0 for a trainer without array state."""
        import jax
        try:
            leaves = jax.tree_util.tree_leaves(self._checkpoint_arrays())
        except NotImplementedError:
            return 0
        per_chip: Dict[Any, int] = {}
        for leaf in leaves:
            for shard in getattr(leaf, "addressable_shards", ()):
                per_chip[shard.device] = per_chip.get(shard.device, 0) \
                    + shard.data.nbytes
        return max(per_chip.values(), default=0)

    def _state_sharding(self, leaf):
        """NamedSharding for one state leaf: the first axis whose size is a
        registered table size (_tp_sizes: dims, FFM's Mr, ...) -> 'tp',
        everything else replicated (w0, counters, small tables)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        shape = getattr(leaf, "shape", ())
        for ax, s in enumerate(shape):
            if s in self._tp_sizes:
                return NamedSharding(
                    self.mesh,
                    P(*["tp" if a == ax else None for a in range(len(shape))]))
        return NamedSharding(self.mesh, P())

    def _reshard_state(self) -> None:
        """device_put every checkpointable array with its mesh sharding."""
        import jax
        import jax.numpy as jnp
        tree = self._checkpoint_arrays()
        tree = jax.tree_util.tree_map(
            lambda l: jax.device_put(jnp.asarray(l), self._state_sharding(l)),
            tree)
        self._restore_arrays(tree)

    def _input_sharding(self, ndim: int, row_axis: Optional[int]):
        """NamedSharding of one input array on the mesh: the batch's rows
        (``row_axis``: 0 of a batch's arrays, 1 of a stacked window's,
        whose axis 0 is the scan axis) sharded over 'dp', every other axis
        and an array without rows (nv) replicated. The scan body then
        compiles under GSPMD exactly like the K=1 step."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(*[
            "dp" if ax == row_axis else None for ax in range(ndim)]))

    def _shard_batch(self, batch):
        """Place one host batch or stacked window on the mesh. Where the
        prefetcher staged it this is not called: what remains is input
        dispatched from its own thread (process(), the CPU, a scorer),
        under its own span ``h2d.shard``."""
        from ..io.prefetch import stage_batch
        return stage_batch(batch, self._input_sharding, "h2d.shard")

    def _inputs(self, it) -> Iterator:
        """Iterate a staged input stream with every wait for the next item
        under a ``loop.wait_input`` span: what the train loop's thread does
        between dispatches when it is not emitting. Every dispatch loop
        draws its inputs through here."""
        it = iter(it)
        tracer = self._tracer
        while True:
            with tracer.span("loop.wait_input"):
                b = next(it, _END)
            if b is _END:
                return
            yield b

    def _source_side(self, batches, convert_labels: bool
                     ) -> Iterator[SparseBatch]:
        """The serial source leg of a streamed fit: label conversion and
        the ``_note_batch`` hook see HOST arrays in STREAM ORDER (the
        source side of the pipeline is one thread, the one that feeds the
        chip: a hook does no more here than hand the batch on, as FFM's
        pair tracking does); _preprocess_train_batch then fans out over
        the prep workers. ``source.note_batch`` carries the batch's
        ordinal in this stream."""
        tracer = self._tracer
        for n, b in enumerate(batches):
            if convert_labels:
                b = SparseBatch(b.idx, b.val,
                                self._convert_labels(b.label),
                                b.field, n_valid=b.n_valid,
                                fieldmajor=b.fieldmajor)
            with tracer.span("source.note_batch", None, n):
                # ingest-side stats over HOST arrays (np.asarray of
                # already-host data) — no device sync happens here
                # graftcheck: disable=GC07
                self._note_batch(b)
            yield b

    def fit_stream(self, batches: Iterable[SparseBatch], *,
                   convert_labels: bool = True,
                   resume: bool = False,
                   on_dispatch=None,
                   _emit_done: bool = True) -> "LearnerBase":
        """Out-of-core training over a stream of padded batches (e.g.
        io.arrow.ParquetStream.batches): each batch dispatches one jitted
        step; nothing is buffered, so resident memory is one shard.
        Epoch count is owned by the stream (ParquetStream re-reads shards
        per epoch — the NioStatefulSegment analog at corpus scale). On
        accelerators the shard read/parse overlaps device compute via the
        same DevicePrefetcher fit() uses; -ingest_workers > 1 additionally
        shards the batch prep (canonicalize/pack) across a worker pool.

        Fault tolerance (docs/RELIABILITY.md): with -checkpoint_dir +
        -checkpoint_every, a full-state bundle autosaves atomically every
        N steps plus once at stream end. After a crash, ``resume()`` then
        ``fit_stream(same_stream, resume=True)`` skips the checkpointed
        stream prefix and continues; at -steps_per_dispatch 1 the
        post-restore loss trajectory is bit-exact vs. an uninterrupted
        run (the stream must be deterministic — same shard order and
        shuffle seed).

        ``on_dispatch(seq, steps, examples)`` is called on the train
        loop's thread after each dispatch: the dispatch's ordinal in this
        call (the ``seq`` its spans carry), the optimizer steps it fused
        and the examples it applied. The per-step losses go to
        :attr:`loss_sink`."""
        import jax
        self.pipeline_stats = PipelineStats()
        # HIVEMALL_TPU_PROF covers the streaming path too (the long-running
        # workloads one most wants to profile); the once-per-process latch
        # makes the repeated fit_stream calls of multi-epoch wrappers safe
        prof_dir = self._devprof.start_profile_once()
        if resume and self._stream_pos:
            from ..io.replay_segment import skip_batches
            batches = skip_batches(batches, self._stream_pos)
        elif not resume:
            # a fresh stream starts at position 0 — without this, a second
            # fit_stream on the same trainer (FFM's per-epoch loop, any
            # sequential reuse) would checkpoint positions offset by the
            # previous stream's length and resume would skip wrongly
            self._stream_pos = 0
        # the manager is pinned on the trainer (not a local) so the obs
        # registry's weakly-held `checkpoint` section — last_saved_step,
        # age_seconds, bundle count — outlives the stream and stays
        # readable between runs for as long as the trainer does
        autosaver = self._ck_manager = self._autosaver()

        closers: List = []
        it: Iterable[SparseBatch] = self._regroup_prepared(self._ingest_iter(
            self._source_side(batches, convert_labels), closers))
        prefetch = self._wants_prefetch()
        it = self._wrap_megabatch(it, prefetch=prefetch)
        if prefetch:
            it = self._wrap_prefetch(it, closers)
        try:
            for seq, b in enumerate(self._inputs(it)):
                examples = self._examples
                self._dispatch(b)
                # stream position = SOURCE batches consumed (a fused K-step
                # window is K source batches) — what resume() skips past
                steps = int(getattr(b, "n_steps", 1))
                self._stream_pos += steps
                if on_dispatch is not None:
                    on_dispatch(seq, steps, self._examples - examples)
                if autosaver is not None:
                    autosaver.maybe_save(self)
        finally:
            for c in reversed(closers):
                c()
            self._devprof.stop_profile(prof_dir)
        if autosaver is not None:
            # completed stream: make the final state durable too (cadence
            # saves only land on -checkpoint_every boundaries). No save on
            # the exception path — the last cadence bundle IS the recovery
            # point a crashed run resumes from.
            autosaver.save_final(self)
        # completed stream: one train_done record carrying the merged
        # registry snapshot (pipeline/train/mix/checkpoint/spans) — the
        # jsonl peer of `curl /snapshot`. Not emitted on the exception
        # path (a crashed stream has no "done") nor when this call is one
        # epoch inside a multi-epoch wrapper (_emit_done=False: FFM's
        # replay fit_stream emits ONE record for the whole run).
        if _emit_done:
            self._emit_train_done()
        return self

    def _autosaver(self):
        """CheckpointManager for this fit_stream, or None when autosave is
        not configured (-checkpoint_dir AND -checkpoint_every required)."""
        ckdir = self.opts.get("checkpoint_dir")
        every = int(self.opts.get("checkpoint_every") or 0)
        if not ckdir or every <= 0:
            return None
        from ..io.checkpoint import CheckpointManager
        return CheckpointManager(
            ckdir, self.NAME, keep=int(self.opts.get("checkpoint_keep") or 3),
            every=every, start_step=self._t)

    def resume(self, checkpoint_dir: Optional[str] = None) -> bool:
        """Restore the newest USABLE autosaved bundle from
        ``checkpoint_dir`` (default: the -checkpoint_dir option). Bundles
        failing validation — truncated file, digest mismatch, options
        mismatch — are skipped with a warning, falling back to the next
        newest (the retention window exists exactly for this). Returns
        True when state was restored; follow with
        ``fit_stream(same_stream, resume=True)`` to continue mid-stream."""
        import warnings
        import zipfile
        ckdir = checkpoint_dir or self.opts.get("checkpoint_dir")
        if not ckdir:
            return False
        from ..io.checkpoint import list_bundles
        for path in list_bundles(ckdir, self.NAME):
            try:
                self.load_bundle(path)
                return True
            except (ValueError, KeyError, OSError,
                    zipfile.BadZipFile) as e:
                warnings.warn(f"skipping unusable checkpoint {path}: {e}",
                              RuntimeWarning, stacklevel=2)
        return False

    def _note_batch(self, batch: SparseBatch) -> None:
        """Hook for emission-time metadata on the streaming path, called
        once a batch in stream order on the source thread (FFM's joint
        layout hands the batch to its observed-pair tracker here)."""

    # -- shared plumbing -----------------------------------------------------
    def _parse_row(self, features) -> Tuple[np.ndarray, np.ndarray]:
        if (isinstance(features, tuple) and len(features) == 2
                and isinstance(features[0], np.ndarray)):
            return features
        idx: List[int] = []
        val: List[float] = []
        for f in features:
            if f is None or f == "":
                continue
            name, v = split_feature(f)
            try:
                i = int(name)
            except ValueError:
                if self.opts.int_feature:
                    raise ValueError(
                        f"-int_feature set but feature {name!r} not an int")
                i = mhash(name, self.dims - 1)  # ids in [1, dims-1]
                self._names.setdefault(i, name)
            idx.append(i)
            val.append(float(v))
        return np.asarray(idx, np.int32), np.asarray(val, np.float32)

    def _convert_label(self, label: float) -> float:
        if self.CLASSIFICATION:
            return 1.0 if float(label) > 0 else -1.0
        return float(label)

    def _convert_labels(self, labels: np.ndarray) -> np.ndarray:
        if self.CLASSIFICATION:
            return np.where(labels > 0, 1.0, -1.0).astype(np.float32)
        return labels.astype(np.float32)

    # shared shape bucket (io.sparse.pow2_len); kept as a method alias for
    # subclasses that call self._pow2_len
    _pow2_len = staticmethod(pow2_len)

    def _flush(self) -> None:
        if not self._buf_rows:
            return
        rows, labels = self._buf_rows, self._buf_labels
        self._buf_rows, self._buf_labels = [], []
        if int(self.opts.iters) > 1:
            self._replay.append(rows, labels)
        self._flush_chunk(rows, labels)

    def _flush_chunk(self, rows, labels) -> None:
        """Pad one chunk of buffered rows into a SparseBatch and dispatch."""
        B = int(self.opts.mini_batch)
        L = self._pow2_len(max(1, max(len(r[0]) for r in rows)))
        idx = np.zeros((B, L), np.int32)
        val = np.zeros((B, L), np.float32)
        lab = np.zeros(B, np.float32)
        for b, (i, v) in enumerate(rows):
            idx[b, :len(i)] = i
            val[b, :len(v)] = v
            lab[b] = labels[b]
        nv = len(rows)
        self._dispatch(self._preprocess_train_batch(
            SparseBatch(idx, val, lab, n_valid=nv if nv < B else None)))

    # test/debug hook: when set to a list, every dispatched step appends
    # its per-batch loss sum (host float) — the K>1 == K=1 trajectory
    # tests pin exact batch order through it. None (default) costs one
    # attribute check per dispatch and never syncs the device.
    _trace_losses: Optional[List[float]] = None
    #: public per-step loss sink: a list (every dispatched step appends
    #: its loss sum, a host float) or a callable taking one. Fed where
    #: ``_trace_losses`` is; setting it makes each dispatch fetch its
    #: losses, so it is for checks and short runs, not for production.
    loss_sink = None

    def _feed_losses(self, losses) -> None:
        """``losses``: the device loss sum(s) of the dispatch just made."""
        vals = [float(v) for v in np.atleast_1d(_fetch(losses))]
        if self._trace_losses is not None:
            self._trace_losses.extend(vals)
        sink = self.loss_sink
        if sink is not None:
            for v in vals:
                sink(v) if callable(sink) else sink.append(v)

    def _dispatch(self, batch) -> None:
        if isinstance(batch, (MegaBatch, PackedMegaBatch)):
            return self._dispatch_mega(batch)
        nv = batch.n_valid or batch.batch_size
        examples = getattr(batch, "examples", None)     # a pair block's
        examples = nv if examples is None else int(examples)
        if self.mesh is not None and isinstance(batch.idx, np.ndarray):
            batch = self._shard_batch(batch)
        # the span is the HOST-side dispatch boundary: synchronous compute
        # on CPU, dispatch latency on accelerators (async tails land in
        # the next blocking boundary)
        t0 = time.perf_counter()
        with self._tracer.span("dispatch.step", getattr(batch, "seq", None)):
            loss_sum = self._train_batch(batch)
        self._devprof.note_dispatch(time.perf_counter() - t0, 1)
        self._t += 1
        # keep the per-step loss on device: float() here would block the host
        # on every minibatch and stall the dispatch pipeline. The device
        # partial is f32, so fold it into the exact host float64 sum every
        # 256 batches before the running magnitude can swamp the increments.
        self._loss_pending = self._loss_pending + loss_sum
        self._examples += examples
        self._meter.add(examples)
        if self._trace_losses is not None or self.loss_sink is not None:
            self._feed_losses(loss_sum)
        self._emit_cadence_events(1)        # reportProgress analog (§6)
        if self._mixer is not None:
            self._mixer.touch(batch.idx[:nv])
            self._mixer.maybe_mix(self)

    def _dispatch_mega(self, mb) -> None:
        """Dispatch one K-step megabatch: ONE jitted lax.scan call runs
        all K optimizer steps with the state donated through the scan
        carry (no per-step table copies, no per-step Python). The [K]
        per-step loss vector stays on device; its sum folds into the
        host float64 at the same 256-step cadence as the K=1 path, so no
        step ever blocks the host."""
        K = mb.n_steps
        nv_total = mb.n_examples
        if self.mesh is not None and isinstance(mb.idx, np.ndarray):
            mb = self._shard_batch(mb)
        t0 = time.perf_counter()
        with self._tracer.span("dispatch.megastep", mb.seq):
            losses = self._train_megabatch(mb)      # [K] device array
        self._devprof.note_dispatch(time.perf_counter() - t0, K)
        self._t += K
        self._loss_pending = self._loss_pending + losses.sum()
        self._examples += nv_total
        self._meter.add(nv_total)
        if self._trace_losses is not None or self.loss_sink is not None:
            self._feed_losses(losses)
        # emit when this window crossed a multiple-of-256 step boundary
        # (the K=1 condition `t % 256 == 0` is the K=1 case of this)
        self._emit_cadence_events(K)

    def _scan_step(self):
        """The scannable step this dispatch runs: ``_step``, unless a
        family keeps a second one for some dispatches (models/linear.py)."""
        return self._step

    def _megastep_state(self) -> Tuple[Any, Any]:
        """(model-state, optimizer-state) pair threaded through the scan
        carry: ``params`` and ``opt_state``, the names every scannable
        family keeps its state under; trainers with other state override
        this and `_set_megastep_state` as a pair."""
        return self.params, self.opt_state

    def _set_megastep_state(self, s1, s2) -> None:
        self.params, self.opt_state = s1, s2

    def _mega_field(self, mb):
        """Per-step field arrays for the megastep (FFM pairs path only —
        the base/linear/FM cores take no field argument, so a stacked
        field array, if the dataset carries one, is simply not fed)."""
        return None

    def _mega_lams(self):
        """Broadcast (non-scanned) extra for the megastep — train_fm's
        -adareg runtime lambdas. None for everyone else."""
        return None

    def _train_megabatch(self, mb):
        """Run K steps through the shared megastep built from this
        trainer's scannable step core (ops.scan.megastep_for). Returns
        the [K] per-step loss sums as a device array."""
        import jax.numpy as jnp
        from ..ops.scan import megastep_for
        mega = megastep_for(self._scan_step(), none_val=True)
        nv = mb.nv_dev if mb.nv_dev is not None else jnp.asarray(mb.nv)
        s1, s2 = self._megastep_state()
        s1, s2, losses, *stats = mega(s1, s2, float(self._t), nv, mb.idx,
                                      mb.val, mb.label, self._mega_field(mb),
                                      self._mega_lams())
        self._set_megastep_state(s1, s2)
        self._stats_pending += stats
        return losses

    def _fold_loss(self) -> None:
        # the one place the train loop blocks on the device; the step's
        # stats ride the same fetch
        with self._tracer.span("loop.fold_loss"):
            loss, stats = _fetch((self._loss_pending, self._stats_pending))
        self._loss_sum += float(loss)
        self._loss_pending = 0.0
        for dispatch in stats:
            for name, v in dispatch.items():
                self._step_counts[name] = self._step_counts.get(name, 0) \
                    + int(v.sum())
        self._stats_pending = []

    @property
    def cumulative_loss(self) -> float:
        self._fold_loss()
        return self._loss_sum / max(1, self._examples)

    # -- scoring surface (offline predict + online serve share it) ----------
    def _make_margin_fn(self):
        """Raw-score closure over the trainer's CURRENT weights:
        ``fn(padded SparseBatch) -> [B] margins``. Anything expensive to
        derive from training state (the optimizer finalization of the
        linear family) is captured ONCE here, not per batch — the serve
        engine calls this at model-load/swap time and then scores with the
        frozen closure. Trainers without a row-scoring surface (anomaly,
        topic models, ...) leave this unimplemented."""
        raise NotImplementedError(
            f"{type(self).__name__} has no row-scoring surface")

    def make_scorer(self):
        """Output-space scoring closure: ``fn(padded SparseBatch) ->
        np.float32 [B]`` — probabilities for classification trainers
        (sigmoid_np over the margin, exactly what ``predict_proba``
        computes), raw margins for regression. The serve engine's predict
        core; weights are captured at call time, so a hot-reload builds a
        fresh scorer and swaps it atomically with the model."""
        margin = self._make_margin_fn()
        if getattr(self, "classification",
                   getattr(self, "CLASSIFICATION", False)):
            return lambda b: sigmoid_np(
                np.asarray(margin(b), np.float32))
        return lambda b: np.asarray(margin(b), np.float32)

    def _score_dataset(self, ds: SparseDataset,
                       batch_size: Optional[int] = None) -> np.ndarray:
        """Margin-score a whole dataset through the shared shape-bucketed
        batch iterator (io.sparse.score_batches): one compiled kernel per
        (pow2-B, pow2-L) bucket instead of per dataset shape, ragged tails
        padded to their own power-of-two bucket. The decision_function of
        every scoring trainer routes through here."""
        margin = self._make_margin_fn()
        bs = int(batch_size or self.opts.mini_batch)
        out = np.empty(len(ds), np.float32)
        for s, b in score_batches(ds, bs):
            nv = b.n_valid or b.batch_size
            # output path: the per-batch score fetch IS the product
            # graftcheck: disable=GC07
            out[s:s + nv] = np.asarray(margin(b))[:nv]
        return out

    def score_dataset(self, ds: SparseDataset,
                      batch_size: Optional[int] = None) -> np.ndarray:
        """Output-space scores for a whole dataset — the bulk peer of
        :meth:`make_scorer`: probabilities for classification trainers
        (sigmoid over the margin, exactly the ``predict_proba`` space),
        raw margins otherwise. Same shape-bucketed iterator as
        ``_score_dataset``, so the bulk scoring path's jitted kernel
        backend reuses the offline compile buckets."""
        m = self._score_dataset(ds, batch_size)
        if getattr(self, "classification",
                   getattr(self, "CLASSIFICATION", False)):
            return sigmoid_np(m)
        return m

    # -- model emission (the close()-time forward of (feature, weight)) -----
    def model_rows(self) -> Iterator[Tuple[str, float]]:
        w = np.asarray(self._finalized_weights())
        nz = np.nonzero(w)[0]
        for i in nz:
            yield self._names.get(int(i), str(int(i))), float(w[i])

    def model_table(self) -> Dict[str, float]:
        return dict(self.model_rows())

    def _warm_start(self, path: str) -> None:
        """-loadmodel: read a previously saved model table (feature\tweight)."""
        w = np.asarray(self._finalized_weights()).copy()
        seen = set()
        with open(path) as f:
            for line in f:
                feat, _, weight = line.rstrip("\n").partition("\t")
                try:
                    i = int(feat)
                except ValueError:
                    i = mhash(feat, self.dims - 1)
                    self._names.setdefault(i, feat)
                if 0 <= i < len(w):
                    # first touch replaces the warm base; later touches of the
                    # same slot accumulate — feature-hashing collisions share
                    # additively, matching StreamingScorer's loader
                    if i in seen:
                        w[i] += float(weight)
                    else:
                        w[i] = float(weight)
                        seen.add(i)
        self._load_weights(w)

    def save_model(self, path: str) -> None:
        with open(path, "w") as f:
            for feat, weight in self.model_rows():
                f.write(f"{feat}\t{weight:.9g}\n")

    def _load_weights(self, w: np.ndarray) -> None:
        raise NotImplementedError

    # -- sparse weight access (mix delta exchange, O(touched) not O(dims)) ---
    def _weight_table(self):
        """The [dims] device weight array, or None when the trainer's state
        is not a flat table (then sparse access falls back to O(dims)):
        ``params`` itself for the linear families, its "w" leaf for FM."""
        p = getattr(self, "params", None)
        return p.get("w") if isinstance(p, dict) else p

    def _store_weight_table(self, t) -> None:
        if isinstance(self.params, dict):
            self.params["w"] = t
        else:
            self.params = t

    def _get_weights_at(self, keys: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp
        t = self._weight_table()
        if t is None:
            return np.asarray(self._finalized_weights())[keys]
        return np.asarray(t[jnp.asarray(keys)], np.float32)

    def _set_weights_at(self, keys: np.ndarray, vals: np.ndarray) -> None:
        import jax.numpy as jnp
        t = self._weight_table()
        if t is None:
            w = np.array(self._finalized_weights())
            w[keys] = vals
            self._load_weights(w)
            return
        self._store_weight_table(
            t.at[jnp.asarray(keys)].set(jnp.asarray(vals, t.dtype)))

    def _get_covar_at(self, keys: np.ndarray):
        import jax.numpy as jnp
        sig = getattr(self, "sigma", None)
        if sig is None:
            return None
        return np.asarray(sig[jnp.asarray(keys)], np.float32)

    def _set_covar_at(self, keys: np.ndarray, vals: np.ndarray) -> None:
        import jax.numpy as jnp
        sig = getattr(self, "sigma", None)
        if sig is not None:
            self.sigma = sig.at[jnp.asarray(keys)].set(
                jnp.asarray(vals, sig.dtype))

    # -- full-state checkpointing (io.checkpoint bundles, SURVEY.md §6) ------
    def _checkpoint_arrays(self):
        """Pytree of device arrays forming the resumable training state.
        The default covers the standard attribute names; trainers with other
        state override this and `_restore_arrays` as a pair."""
        tree = {}
        for attr in ("params", "opt_state"):
            if getattr(self, attr, None) is not None:
                tree[attr] = getattr(self, attr)
        if not tree:
            raise NotImplementedError(
                f"{type(self).__name__} has no checkpointable arrays")
        return tree

    def _restore_arrays(self, tree) -> None:
        for k, v in tree.items():
            setattr(self, k, v)

    def save_bundle(self, path: str) -> None:
        from ..io.checkpoint import save_bundle
        save_bundle(self, path)

    def load_bundle(self, path: str) -> None:
        from ..io.checkpoint import load_bundle
        load_bundle(self, path)
