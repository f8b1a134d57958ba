"""Job kind `stream_ratings`: `train_mf_adagrad`'s `fit_stream` over a
Parquet shard directory of a ratings table, from bytes on disk to updated
factor tables.

It follows `job_stream.py`'s window to the letter: set-up writes the
shards, builds ONE trainer through the catalog and drives it through its
first dispatch by the window's own call and feed; the window is a second
`fit_stream` on that trainer over the same directory, passes repeating;
its clock starts when its first dispatch has retired and stops when the
dispatch in flight after `--seconds` and everything staged behind it have
retired. The same `timings`, `window`, `spans` and `trace` keys, so every
reader of the `stream` job reads it. It differs in three things:

- the generator (`ratings.py`: (user, item, rating) rows, the shards three
  scalar columns, read by `ParquetStream(id_cols=("user", "item"),
  label_col="rating")`);
- what `correct` compares: the first step's loss, the accumulated
  gradient and the change of both tables (the factors and the bias of
  each) at the rows the dispatch touched, against the float32 reference;
  a decoded rating is found among the generated ones by its ids and its
  rating's bits;
- the window carries the step's own counters (registry `train`: each
  table's distinct rows, which tail every step took), folded with the
  loss at the window's two ends, as `job_stream_text.py` carries its own.

`run.py` takes a result's `job` from the traffic file and every train
reader asks for "stream"; this job has that job's result, so it says so
(`env["traffic"]["job"]`, below). A program whose `train_mf_adagrad` has
no `fit_stream` cannot run the cell: the job exits at once."""

from __future__ import annotations

import itertools
import os
import sys
import threading

import numpy as np

from . import check, common, data, job_stream, ratings, xplane

TRACE_SECONDS = job_stream.TRACE_SECONDS
ID_COLS, LABEL_COL = ("user", "item"), "rating"
#: the step's own counters the window carries (registry `train` section)
COUNTERS = ("mf_user_rows", "mf_item_rows", "tail_distinct_steps",
            "tail_dense_steps")


def mf_counters() -> dict:
    """What the step counts about itself, from the registry's `train`
    section (folded with the loss; absent in a program without them)."""
    from hivemall_tpu.obs.registry import registry
    train = registry.snapshot()["train"]
    return {k: int(train[k]) for k in COUNTERS if k in train}


def trainer_class(cfg: dict):
    """The catalog's class; a program whose trainer is not on the normal
    path (no `fit_stream`) cannot run this cell: exit at once."""
    from hivemall_tpu.catalog import lookup
    cls = lookup(cfg["catalog"]).resolve()
    if not hasattr(cls, "fit_stream"):
        print(f"benchmark: the program's {cfg['catalog']} has no "
              "fit_stream: it cannot run this cell", file=sys.stderr)
        sys.exit(3)
    return cls


def build_trainer(cfg: dict, seed: int):
    """The catalog's trainer with the configuration's options and the
    seed: as a CLI user starts it."""
    return trainer_class(cfg)(f"{cfg['options']} -seed {seed}")


def open_stream(shard_dir: str):
    from hivemall_tpu.io.arrow import ParquetStream
    return ParquetStream(shard_dir, id_cols=ID_COLS, label_col=LABEL_COL)


def first_dispatch(trainer, cfg, reference, batches) -> dict:
    """Drive the trainer through `batches` (one dispatch's worth) with
    fit_stream and read back what the check compares. The program's
    counters have to say that it took them as ONE fused dispatch of as
    many steps as the configuration states."""
    import jax
    state = common.family_module("state", cfg["family"])
    ids = np.stack([np.asarray(b.idx) for b in batches])
    labels = np.stack([np.asarray(b.label) for b in batches])
    keys = reference.table_keys(cfg, ids)
    t_read = common.now()
    before = state.read_rows(trainer, keys)
    read_s = common.now() - t_read
    if not hasattr(trainer, "_trace_losses"):
        raise RuntimeError("the trainer has no `_trace_losses` hook: the "
                           "per-step losses `correct` compares cannot be "
                           "read")
    c0 = job_stream.train_counters()
    trainer._trace_losses = []
    trainer.fit_stream(iter(batches))
    jax.block_until_ready((trainer.params, trainer.opt_state))
    losses = [float(v) for v in trainer._trace_losses]
    trainer._trace_losses = None
    steps = job_stream.train_counters()["step"] - c0["step"]
    p = trainer.pipeline_stats
    if (steps, p.megabatches_staged, p.singles_flushed) \
            != (len(batches), 1, 0):
        raise RuntimeError(
            f"{len(batches)} batches were to be one dispatch of "
            f"{job_stream.steps_per_dispatch(cfg)} steps, the program took "
            f"{steps} steps in {p.megabatches_staged} fused dispatches and "
            f"{p.singles_flushed} single ones: its steps per dispatch "
            "are not the configuration's")
    t_read = common.now()
    after = state.read_rows(trainer, keys)
    read_s += common.now() - t_read
    return {"ids": ids, "labels": labels, "keys": keys, "losses": losses,
            "before": before["value"], "after": after["value"],
            "gg": after["gg"], "read_rows_s": read_s}


def compare_first_dispatch(cfg, seed, reference, prog, gen) -> dict:
    """The numbers of `correct`. The ratings the program decoded are
    looked up among the generator's own `gen` (user, item, rating) by
    fingerprint, and the reference follows the generator's copy of them:
    a rating the decode altered is a miss."""
    S, B, _ = prog["ids"].shape
    t_start = common.now()
    rowno, missed = data.match_rows(
        ratings.row_keys(*gen), None,
        ratings.row_keys(prog["ids"][..., 0].reshape(-1),
                         prog["ids"][..., 1].reshape(-1),
                         prog["labels"].reshape(-1)), None)
    t_match = common.now()
    ids = np.stack([gen[0][rowno], gen[1][rowno]], 1).reshape(S, B, 2)
    ref = reference.run(cfg, seed, ids, gen[2][rowno].reshape(S, B))
    same = all(np.array_equal(ref["keys"][t], prog["keys"][t])
               for t in ref["keys"])
    if not same:
        numbers = {"loss_gap": float("inf"), "grad_gap": float("inf"),
                   "change_gap": float("inf")}
    else:
        numbers = check.train_numbers(prog, ref)
    numbers["decode_missed_rows"] = float(missed)
    numbers["_seconds"] = dict(ref["seconds"], match=t_match - t_start,
                               total=common.now() - t_start)
    return numbers


def run(env: dict) -> dict:
    import jax
    cfg, traffic, args = env["cfg"], env["traffic"], env["args"]
    # this job's result is the `stream` job's, key for key: its readers
    # (benchmark/layer_metrics/, which ask ctx["job"] == "stream") read it
    traffic["job"] = "stream"
    model = cfg["model"]
    seed = common.seed31(args.seed)
    B, F = int(model["mini_batch"]), int(model["fields"])
    timings = {"start_s": common.now() - env["t_start"]}

    # -- data: ratings from the seed, shards at a fixed place --------------
    t = common.now()
    trainer_class(cfg)                  # before any data is made
    law = ratings.RatingLaw(traffic["ratings"], int(model["users"]),
                            int(model["items"]))
    gen = ratings.make_ratings(law, int(traffic["rows_per_pass"]), args.seed)
    shard_dir = os.path.join(common.RUN_DIR, env["workload"], "shards")
    timings["written_bytes"] = ratings.write_shards(
        *gen, shard_dir, int(traffic["rows_per_shard"]))
    timings["data_s"] = common.now() - t

    # -- the program -------------------------------------------------------
    from hivemall_tpu.obs.devprof import get_devprof
    from hivemall_tpu.obs.trace import get_tracer
    from hivemall_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    reference = common.family_module("reference", cfg["family"])
    dp = get_devprof()
    tracer = get_tracer()
    clock = common.SpanClock()
    compiles0, compile_s0 = dp.compiles, dp.compile_s
    t = common.now()
    trainer = build_trainer(cfg, seed)
    timings["construct_s"] = common.now() - t
    if env.get("break_trainer"):
        env["break_trainer"](trainer)         # tests plant a fault here
    K = job_stream.steps_per_dispatch(cfg)
    stream = open_stream(shard_dir)
    batches_kw = dict(traffic.get("batches", {}))

    # -- first dispatch: compiles, and is what `correct` compares ----------
    first = list(itertools.islice(
        stream.batches(B, epochs=1, max_len=F, **batches_kw), K))
    t = common.now()
    prog = first_dispatch(trainer, cfg, reference, first)
    del first
    timings["first_dispatch_s"] = common.now() - t
    timings["read_rows_s"] = prog.pop("read_rows_s")
    timings["compile_s"] = dp.compile_s - compile_s0
    timings["compiles_setup"] = dp.compiles - compiles0

    # -- the window ----------------------------------------------------------
    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, TRACE_SECONDS)
        tracer.enable()
    stop = threading.Event()
    feed_state = {"source_seconds": 0.0, "batches": 0}
    w = {"t0": None, "dispatches": 0}
    trace_dir = os.path.join(common.RUN_DIR, env["workload"], "trace")

    def feed():
        it = stream.batches(B, epochs=1 << 30, max_len=F, **batches_kw)
        n = 0
        while True:
            if n % K == 0 and stop.is_set():
                return                          # on a dispatch boundary
            t = common.now()
            b = next(it)
            feed_state["source_seconds"] += common.now() - t
            n += 1
            feed_state["batches"] = n
            yield b

    # as in job_stream: the window's two instants need the trainer's own
    # thread, so its `_dispatch` is wrapped
    inner_dispatch = trainer._dispatch

    def dispatch(batch):
        inner_dispatch(batch)
        w["dispatches"] += 1
        if w["t0"] is None:
            # first dispatch retired: stager ring and prefetcher are primed
            jax.block_until_ready((trainer.params, trainer.opt_state))
            trainer.cumulative_loss           # folds the step's counters
            w["stats0"] = job_stream._stats_snapshot(trainer, stream,
                                                     feed_state)
            w["counters0"] = job_stream.train_counters()
            w["mf0"] = mf_counters()
            w["compiles0"] = dp.compiles
            if args.trace:
                w["sync_perf"] = xplane.start(trace_dir)
            w["t0"] = common.now()
        elif common.now() - w["t0"] >= seconds:
            stop.set()

    trainer._dispatch = dispatch
    t_lead = common.now()
    trainer.fit_stream(feed())
    jax.block_until_ready((trainer.params, trainer.opt_state))
    t1 = common.now()
    if args.trace:
        jax.profiler.stop_trace()
        tracer.disable()
    t0 = w["t0"]
    timings["window_lead_s"] = t0 - t_lead
    trainer.cumulative_loss                    # the last fold
    stats1 = job_stream._stats_snapshot(trainer, stream, feed_state)
    counters1 = job_stream.train_counters()
    mf1 = mf_counters()
    examples = counters1["examples"] - w["counters0"]["examples"]
    steps = counters1["step"] - w["counters0"]["step"]
    # every batch the feed handed over, less the first dispatch's (applied
    # before the clock started), has to be in the weights now
    fed_examples = (feed_state["batches"] - K) * B
    window = {"t0": t0, "t1": t1, "seconds": t1 - t0, "examples": examples,
              "fed_examples": fed_examples,
              "dispatches": w["dispatches"] - 1, "steps_per_dispatch": K,
              "steps": steps, "batch": B,
              "compiles": dp.compiles - w["compiles0"],
              "stats": {k: stats1[k] - w["stats0"][k] for k in stats1
                        if k != "workers"},
              "workers": stats1["workers"],
              **{k: mf1[k] - w["mf0"].get(k, 0) for k in mf1}}
    peak = common.memory_peak_bytes()
    spans = clock.spans(tracer, t0, t1) if args.trace else []

    # -- free the program's state, then the reference ------------------------
    trainer.params = trainer.opt_state = None
    del trainer, inner_dispatch, dispatch
    t = common.now()
    numbers = compare_first_dispatch(cfg, seed, reference, prog, gen)
    timings["check_s"] = common.now() - t
    timings["check_parts_s"] = numbers.pop("_seconds")
    timings["step_loss_gaps"] = numbers.pop("_step_loss_gaps", None)
    timings["leaf_gaps"] = numbers.pop("_leaf_gaps", None)
    numbers["window_lost_examples"] = float(abs(fed_examples - examples))
    verdict = check.verdict(numbers,
                            cfg["correct"]["stream_ratings"]["limits"])

    out = {"end_to_end": {"train_rate": examples / (t1 - t0),
                          "setup_s": t0 - env["t_start"]},
           "attempted": examples, "failed": 0,
           "memory_peak_bytes": peak, "verdict": verdict,
           "window": window, "timings": timings, "spans": spans,
           "trace": None}
    if args.trace:
        out["trace"] = xplane.reduce_window(trace_dir, w["sync_perf"],
                                            (t0, t1), spans)
    return out
