"""Job kind `stream`: a catalog trainer's `fit_stream` over a Parquet shard
directory, from bytes on disk to updated weights.

Set-up writes the shards, builds ONE trainer, and drives it through its
first dispatch (the steps `correct` compares) by the window's own call and
feed. The window is a second `fit_stream` on that same trainer over the
same directory, passes repeating. Its clock starts when its first dispatch
has retired (pipeline full) and stops when the dispatch in flight after
`--seconds` and everything staged behind it have retired: examples applied
over seconds elapsed, nothing quantised to whole dispatches."""

from __future__ import annotations

import itertools
import os
import threading

import numpy as np

from . import check, common, data, xplane

TRACE_SECONDS = 10.0          # a traced run measures this long at most


def _stats_snapshot(trainer, stream, feed_state) -> dict:
    p, d = trainer.pipeline_stats, stream.stats
    return {"prep_seconds": p.prep_seconds,
            "prep_backpressure_seconds": p.prep_backpressure_seconds,
            "prep_wait_seconds": p.prep_wait_seconds,
            "consume_wait_seconds": p.consume_wait_seconds,
            "stage_seconds": p.stage_seconds,
            "stack_seconds": p.stack_seconds,
            "megabatches_staged": p.megabatches_staged,
            "singles_flushed": p.singles_flushed,
            "workers": p.workers,
            "decode_seconds": d.prep_seconds,
            "decode_wait_seconds": d.prep_wait_seconds,
            "shards_decoded": d.batches_prepared,
            "source_seconds": feed_state["source_seconds"]}


def train_counters() -> dict:
    """The trainer's own `step` and `examples`, from the program's obs
    registry (the `train` section every trainer registers on
    construction: what `/snapshot` serves)."""
    from hivemall_tpu.obs.registry import registry
    train = registry.snapshot()["train"]
    return {"step": int(train["step"]), "examples": int(train["examples"])}


def steps_per_dispatch(cfg: dict) -> int:
    """The configuration states how many steps one dispatch fuses; the
    first dispatch holds the program to it (`first_dispatch`)."""
    return int(cfg["model"]["steps_per_dispatch"])


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
    c0 = train_counters()
    trainer._trace_losses = []
    trainer.fit_stream(iter(batches))
    jax.block_until_ready((trainer.params, trainer.opt_state))
    losses = [float(v) for v in trainer._trace_losses]
    trainer._trace_losses = None
    steps = train_counters()["step"] - c0["step"]
    p = trainer.pipeline_stats
    if (steps, p.megabatches_staged, p.singles_flushed) \
            != (len(batches), 1, 0):
        raise RuntimeError(
            f"{len(batches)} batches were to be one dispatch of "
            f"{steps_per_dispatch(cfg)} steps, the program took {steps} "
            f"steps in {p.megabatches_staged} fused dispatches and "
            f"{p.singles_flushed} single ones: its steps per dispatch "
            "are not the configuration's")
    t_read = common.now()
    after = state.read_rows(trainer, keys)
    read_s += common.now() - t_read
    return {"ids": ids, "labels": labels, "keys": keys, "losses": losses,
            "before": before["value"], "after": after["value"],
            "gg": after["gg"], "read_rows_s": read_s}


def compare_first_dispatch(cfg, seed, reference, prog, gen_ids,
                           gen_labels) -> dict:
    """The numbers of `correct` for a training cell. The rows the program
    decoded are looked up among the generator's own by fingerprint, and
    the reference follows the generator's copy of them: a row the decode
    altered is a miss."""
    S, B, F = prog["ids"].shape
    t_start = common.now()
    rowno, missed = data.match_rows(gen_ids, gen_labels,
                                    prog["ids"].reshape(-1, F),
                                    prog["labels"].reshape(-1))
    t_match = common.now()
    ref = reference.run(cfg, seed, gen_ids[rowno].reshape(S, B, F),
                        gen_labels[rowno].reshape(S, B))
    if not np.array_equal(ref["keys"], prog["keys"]):
        numbers = {"loss_gap": float("inf"), "grad_gap": float("inf"),
                   "change_gap": float("inf")}
    else:
        numbers = check.train_numbers(prog, ref)
    numbers["decode_missed_rows"] = float(missed)
    numbers["_seconds"] = dict(ref["seconds"], match=t_match - t_start,
                               total=common.now() - t_start)
    return numbers


def build_trainer(cfg: dict, seed: int):
    """The catalog's trainer with the configuration's options and the
    seed. `construct_on: host` builds it on the host's CPU backend and
    moves its state to the chip: the way round a constructor that cannot
    run on the chip at this size (PERF.md, Open questions, first). It is
    not how a CLI user starts; its seconds are reported on their own
    (`setup.construct_s.train`) and the key goes once the program can
    build its table on the chip."""
    import jax
    from hivemall_tpu.catalog import lookup
    cls = lookup(cfg["catalog"]).resolve()
    options = f"{cfg['options']} -seed {seed}"
    if cfg.get("construct_on") != "host" or jax.default_backend() == "cpu":
        return cls(options)
    with jax.default_device(jax.devices("cpu")[0]):
        trainer = cls(options)
    if trainer.mesh is None:        # under -mesh the trainer sharded it
        chip = jax.devices()[0]
        trainer.params = jax.device_put(trainer.params, chip)
        trainer.opt_state = jax.device_put(trainer.opt_state, chip)
    jax.block_until_ready((trainer.params, trainer.opt_state))
    return trainer


def run(env: dict) -> dict:
    import jax
    cfg, traffic, args = env["cfg"], env["traffic"], env["args"]
    model = cfg["model"]
    seed = common.seed31(args.seed)
    B, F = int(model["mini_batch"]), int(model["fields"])
    # where set-up's seconds go, in order: start (imports, the device),
    # data, construct, first dispatch (of which: reading the rows that
    # `correct` compares), and the window's lead-in to its first retired
    # dispatch; compile_s lies inside construct and first dispatch
    timings = {"start_s": common.now() - env["t_start"]}

    # -- data: rows from the seed, shards at a fixed place -----------------
    t = common.now()
    spec = data.RowSpec(cfg["data"], int(model["dims"]))
    n_rows = int(traffic["rows_per_pass"])
    gen_ids, gen_labels = data.make_rows(spec, n_rows, args.seed)
    shard_dir = os.path.join(common.RUN_DIR, env["workload"], "shards")
    timings["written_bytes"] = data.write_shards(
        gen_ids, gen_labels, shard_dir, int(traffic["rows_per_shard"]),
        with_fields=bool(cfg.get("needs_fields")))
    timings["data_s"] = common.now() - t

    # -- the program -------------------------------------------------------
    from hivemall_tpu.io.arrow import ParquetStream
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
    K = steps_per_dispatch(cfg)
    stream = ParquetStream(shard_dir)
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

    # The window's two instants need the trainer's own thread: the clock
    # starts once the first dispatch has RETIRED (a wait that only the
    # thread that dispatches can make without racing a donated buffer)
    # and the feed is told to stop from there. fit_stream offers no
    # callback per dispatch, so its `_dispatch` is wrapped; a program
    # that renames it stops the run here, with no number (PERF.md asks
    # the tracing issue for a public hook). Examples and steps are
    # counted from the program's public counters, not by this wrapper.
    inner_dispatch = trainer._dispatch

    def dispatch(batch):
        inner_dispatch(batch)
        w["dispatches"] += 1
        if w["t0"] is None:
            # first dispatch retired: stager ring and prefetcher are primed
            jax.block_until_ready((trainer.params, trainer.opt_state))
            w["stats0"] = _stats_snapshot(trainer, stream, feed_state)
            w["counters0"] = train_counters()
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
    stats1 = _stats_snapshot(trainer, stream, feed_state)
    counters1 = train_counters()
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
              "workers": stats1["workers"]}
    peak = common.memory_peak_bytes()
    spans = clock.spans(tracer, t0, t1) if args.trace else []

    # -- free the program's state, then the reference ------------------------
    trainer.params = trainer.opt_state = None
    del trainer, inner_dispatch, dispatch
    t = common.now()
    numbers = compare_first_dispatch(cfg, seed, reference, prog, gen_ids,
                                     gen_labels)
    timings["check_s"] = common.now() - t
    timings["check_parts_s"] = numbers.pop("_seconds")
    timings["step_loss_gaps"] = numbers.pop("_step_loss_gaps", None)
    timings["leaf_gaps"] = numbers.pop("_leaf_gaps", None)
    numbers["window_lost_examples"] = float(abs(fed_examples - examples))
    verdict = check.verdict(numbers, cfg["correct"]["stream"]["limits"])

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
