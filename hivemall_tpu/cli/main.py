"""hivemall_tpu CLI — train/predict runners + mixserv packaging.

Reference analogs: the L6/L8 operational surface (SURVEY.md §2, §3.16) —
define-all DDL listing, bin/run_mixserv.sh, and the HiveQL train/predict
queries, here as subcommands:

  python -m hivemall_tpu.cli train   --algo train_classifier \
      --input a9a.libsvm --options '-loss logloss -opt adagrad' \
      --model model.tsv
  python -m hivemall_tpu.cli predict --algo train_classifier \
      --model model.tsv --input a9a.t --output scores.tsv --metric auc
  python -m hivemall_tpu.cli mixserv --port 11212
  python -m hivemall_tpu.cli define-all
  python -m hivemall_tpu.cli help train_ffm
"""

from __future__ import annotations

import argparse
import json
import shlex
import sys
import time


def _is_ffm(trainer) -> bool:
    return getattr(trainer, "F", None) is not None and \
        trainer.NAME == "train_ffm"


def _read_libsvm_for(trainer, path):
    """LIBSVM read with the trainer's parsing needs (FFM triples carry
    field ids; hashed names bound by the trainer's -dims). Shared by the
    train and predict commands so their ingest cannot diverge."""
    from ..io.libsvm import read_libsvm
    if _is_ffm(trainer):
        return read_libsvm(path, ffm=True, num_fields=trainer.F,
                           dims=getattr(trainer, "dims", None))
    return read_libsvm(path)


def _load_input(args, trainer):
    """Route --input by format: LIBSVM file (default), .csv, .parquet file,
    or a DIRECTORY of parquet shards (returns a ParquetStream for
    out-of-core training). FFM trainers get field-aware parsing."""
    import os

    path = args.input
    kw = dict(feature_col=args.feature_col, label_col=args.label_col,
              dims=getattr(trainer, "dims", None))
    if _is_ffm(trainer):
        kw.update(ffm=True, num_fields=trainer.F)
    if os.path.isdir(path):
        from ..io.arrow import ParquetStream
        # the trainer's -shard_cache_dir also caches each shard's decoded
        # CSR columns, so epoch >= 2 / restarts skip Parquet read + parse
        opts = getattr(trainer, "opts", None)
        cache_dir = opts.get("shard_cache_dir") if opts is not None else None
        # a factor trainer reads a table of scalar id columns (MF: user,
        # item; the label column is the rating)
        id_cols = getattr(trainer, "ID_COLUMNS", None)
        return ParquetStream(path, cache_dir=cache_dir, id_cols=id_cols,
                             **kw), True
    if path.endswith((".parquet", ".pq")):
        from ..io.arrow import read_parquet
        return read_parquet(path, **kw), False
    if path.endswith(".csv"):
        from ..io.arrow import read_csv
        return read_csv(path, label_col=args.label_col,
                        dims=getattr(trainer, "dims", None)), False
    return _read_libsvm_for(trainer, path), False


def _cmd_train(args) -> int:
    from ..catalog import lookup

    if getattr(args, "profile", None):
        # --profile DIR is the HIVEMALL_TPU_PROF env var as a flag: the
        # first fit captures a jax.profiler trace into DIR, routed
        # through obs.devprof (a `profile` jsonl event + span record the
        # capture — docs/OBSERVABILITY.md "Training profiling")
        import os
        os.environ["HIVEMALL_TPU_PROF"] = args.profile
    cls = lookup(args.algo).resolve()
    trainer = cls(args.options or "")
    if args.load_bundle or args.save_bundle \
            or getattr(args, "promote", False):   # fail fast, not post-train
        # every LearnerBase inherits load_bundle/save_bundle, so hasattr is
        # vacuous — probe the actual capability (checkpointable state);
        # --promote gates checkpoint bundles, so it needs the same probe
        try:
            trainer._checkpoint_arrays()
        except (NotImplementedError, AttributeError):
            flag = ("load-bundle" if args.load_bundle
                    else "save-bundle" if args.save_bundle else "promote")
            print(f"error: {args.algo} does not support checkpoint bundles "
                  f"(--{flag})", file=sys.stderr)
            return 2
    if args.load_bundle:
        trainer.load_bundle(args.load_bundle)
    resumed = False
    if getattr(args, "resume", False):
        if args.load_bundle:
            # both flags name a state source; silently letting the newer
            # autosave win would train something other than the bundle the
            # user pinned explicitly
            print("error: --resume and --load-bundle both restore trainer "
                  "state; pass one or the other", file=sys.stderr)
            return 2
        if not hasattr(trainer, "resume"):
            print(f"error: {args.algo} does not support --resume",
                  file=sys.stderr)
            return 2
        resumed = trainer.resume()
        if resumed:
            print(json.dumps({"resumed": True, "step": int(trainer._t),
                              "stream_pos": int(getattr(trainer,
                                                        "_stream_pos", 0))}),
                  file=sys.stderr)
        else:
            print("warning: --resume found no usable checkpoint in "
                  "-checkpoint_dir; starting fresh", file=sys.stderr)
    ds, streaming = _load_input(args, trainer)
    n_examples = len(ds)
    t0 = time.monotonic()
    if streaming:
        if not hasattr(trainer, "fit_stream"):
            print(f"error: {args.algo} cannot train from a shard directory "
                  f"(no streaming path); pass a single file instead",
                  file=sys.stderr)
            return 2
        epochs = int(getattr(trainer.opts, "iters", 1))
        bs = int(getattr(trainer.opts, "mini_batch", 256))
        trainer.fit_stream(ds.batches(bs, epochs=epochs), resume=resumed)
        n_examples *= max(1, epochs)   # the stream runs every epoch itself
        rows = None
    elif hasattr(trainer, "fit"):
        trainer.fit(ds)
        rows = None
    else:
        for i in range(len(ds)):
            trainer.process(ds.row(i), float(ds.labels[i]))
        rows = list(trainer.close())
    dt = time.monotonic() - t0
    if args.save_bundle:
        trainer.save_bundle(args.save_bundle)
    promotion = None
    if getattr(args, "promote", False):
        # train → validate → promote in one command: gate the newest
        # autosaved bundle against the currently-promoted one and flip
        # the PROMOTED pointer on pass (docs/RELIABILITY.md "Promotion
        # and rollback"). A failed gate quarantines the candidate; the
        # training run itself still succeeded (rc 0) — the verdict rides
        # in the final summary record.
        ckdir = getattr(trainer, "opts", {}).get("checkpoint_dir") \
            if hasattr(trainer, "opts") else None
        if not ckdir:
            print("error: --promote needs -checkpoint_dir in --options "
                  "(candidates are gated out of the autosave dir)",
                  file=sys.stderr)
            return 2
        import os
        holdout = args.holdout or args.input
        if os.path.isdir(holdout):
            print("error: --promote needs --holdout <libsvm file> when "
                  "--input is a shard directory", file=sys.stderr)
            return 2
        from ..io.checkpoint import newest_bundle
        from ..serve.promote import PromotionController, PromotionGate
        # make sure the FINAL state is a candidate: fit_stream autosaves
        # land one, but file-input fit() never writes bundles on its own
        nb = newest_bundle(ckdir, trainer.NAME)
        if nb is None or nb[0] < int(getattr(trainer, "_t", 0)):
            os.makedirs(ckdir, exist_ok=True)
            trainer.save_bundle(os.path.join(
                ckdir, f"{trainer.NAME}-step{trainer._t:010d}.npz"))
        gate = PromotionGate(args.algo, args.options or "",
                             holdout=holdout)
        # the local reference keeps the controller alive through the
        # final registry snapshot below — its weakly-held `promotion`
        # provider would otherwise revert to the stub mid-record
        controller = PromotionController(ckdir, gate)
        report = controller.check_once()
        promotion = report if report is not None else {"candidate": None}
        print(json.dumps({"promotion": promotion}, default=str),
              file=sys.stderr)
    if args.model:
        if hasattr(trainer, "save_model"):
            trainer.save_model(args.model)
        elif rows is not None:
            with open(args.model, "w") as f:
                for r in rows:
                    f.write("\t".join(str(x) for x in r) + "\n")
    # prefer the trainer's own processed-examples counter (covers -iters
    # epochs on every path); fall back to the input-size estimate
    n_examples = int(getattr(trainer, "_examples", 0)) or n_examples
    metrics = {"examples": n_examples, "seconds": round(dt, 3),
               "examples_per_sec": round(n_examples / max(dt, 1e-9), 1)}
    if hasattr(trainer, "cumulative_loss"):
        metrics["cumulative_loss"] = round(trainer.cumulative_loss, 6)
    if promotion is not None:
        metrics["promotion"] = {"verdict": promotion.get("verdict"),
                                "promoted": promotion.get("promoted"),
                                "bundle": promotion.get("bundle")}
    # the final record IS the obs-registry snapshot (docs/OBSERVABILITY.md):
    # CLI runs and library runs report one schema — the run summary rides
    # in its `run` section next to pipeline/train/mix/checkpoint/spans.
    # default=str mirrors MetricsStream.emit: a stray numpy scalar in a
    # provider must degrade, not crash a completed run at the last print.
    from ..obs.registry import registry
    registry.register("run", lambda: metrics)
    try:
        print(json.dumps(registry.snapshot(), default=str))
    finally:
        # the registry is process-global: a library caller embedding this
        # CLI must not see a stale `run` section in later snapshots
        registry.unregister("run")
    return 0


def _cmd_bulk_predict(args) -> int:
    """The warehouse path: Parquet shard dir (or one file) scored from a
    checkpoint bundle through io.bulk — packed shard caches, process
    fan-out, kernel/arena backend pick, scored Parquet + logloss/AUC in
    one pass, optional fused score→top-k (docs/PERFORMANCE.md "Bulk
    scoring"). The final record embeds the obs snapshot like train runs,
    so the `bulk` section rides next to ingest_cache/devprof."""
    import os
    from ..io.bulk import bulk_predict
    from ..obs.registry import registry
    from ..utils.device import DevicePolicyError

    try:
        result = bulk_predict(
            args.algo, args.input, args.output,
            options=args.options or "",
            bundle=args.bundle, checkpoint_dir=args.checkpoint_dir,
            backend=args.backend, precision=args.precision,
            workers=args.workers, batch_size=args.batch_size or None,
            cache_dir=args.cache_dir, top_k=args.top_k,
            group_col=args.group_col, feature_col=args.feature_col,
            label_col=args.label_col)
    except DevicePolicyError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    result["snapshot"] = registry.snapshot()
    print(json.dumps(result, default=str))
    return 0


def _cmd_predict(args) -> int:
    import os
    from ..catalog import lookup
    from ..frame.evaluation import auc, logloss, rmse

    if args.bundle or args.checkpoint_dir or os.path.isdir(args.input):
        return _cmd_bulk_predict(args)
    if not args.model:
        print("error: --model (model TSV) is required unless bulk "
              "scoring via --bundle/--checkpoint-dir or a Parquet "
              "directory --input", file=sys.stderr)
        return 2
    cls = lookup(args.algo).resolve()
    trainer = cls((args.options or "")
                  + f" -loadmodel {shlex.quote(args.model)}")
    ds = _read_libsvm_for(trainer, args.input)
    # Classifiers score in probability space (auc/logloss need it);
    # regressors must emit raw predictions — sigmoid-squashing them would
    # make rmse/mae against real-valued labels meaningless.
    # Instance-level `classification` wins over the class default: FM/FFM
    # flip it per the -classification option at construction time.
    classification = getattr(trainer, "classification",
                             getattr(trainer, "CLASSIFICATION", True))
    if classification:
        # predict() sigmoids in classification mode for trainers without a
        # dedicated predict_proba (e.g. FM/FFM).
        scores = (trainer.predict_proba(ds)
                  if hasattr(trainer, "predict_proba") else trainer.predict(ds))
    elif hasattr(trainer, "decision_function"):
        scores = trainer.decision_function(ds)
    else:
        scores = trainer.predict(ds)
    if args.output:
        with open(args.output, "w") as f:
            for i, s in enumerate(scores):
                f.write(f"{i}\t{float(s):.6g}\n")
    out = {"rows": len(ds)}
    if args.metric == "auc":
        out["auc"] = round(auc(ds.labels, scores), 6)
    elif args.metric == "logloss":
        out["logloss"] = round(logloss(ds.labels, scores), 6)
    elif args.metric == "rmse":
        out["rmse"] = round(rmse(ds.labels, scores), 6)
    print(json.dumps(out))
    return 0


def _cmd_retrieve(args) -> int:
    """Offline top-k retrieval over a factor bundle (docs/SERVING.md
    "Retrieval plane"): load MF/BPR/word2vec factors through the weight
    arena, answer ``user→top-k items`` / ``item→k neighbors`` queries
    from the command line, and print one JSON object. The serving twin
    is ``serve --retrieval``."""
    from ..serve.retrieve import RetrievalEngine

    try:
        eng = RetrievalEngine(
            args.algo, args.options or "",
            bundle=args.bundle, checkpoint_dir=args.checkpoint_dir,
            precision=args.precision, k_default=args.k,
            tier=args.tier, rescore=args.rescore)
    except (FileNotFoundError, ValueError, NotImplementedError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        queries = []
        for tok in (args.user.split(",") if args.user else []):
            queries.append({"user": int(tok)})
        for tok in (args.item.split(",") if args.item else []):
            queries.append({"item": int(tok)})
        if not queries:
            print("error: give at least one --user or --item id",
                  file=sys.stderr)
            return 2
        rows = [eng.parse_query(q) for q in queries]
        packed, step = eng.retrieve_rows_versioned(rows)
        results = []
        for i, q in enumerate(queries):
            ids = packed[i, :, 0]
            valid = ids >= 0
            ids = ids[valid].astype(int)
            row = {**q, "ids": [int(v) for v in ids],
                   "scores": [round(float(v), 6)
                              for v in packed[i, valid, 1]]}
            words = eng.labels(ids)
            if words is not None:
                row["words"] = words
            results.append(row)
        print(json.dumps({"results": results, "model_step": int(step),
                          "tier": args.tier,
                          "model_path": eng.model_path}, default=str))
        return 0
    finally:
        eng.close()


def _cmd_mixserv(args) -> int:
    """The bin/run_mixserv.sh analog: a standalone mix server.

    --impl native runs the C++ epoll server (native/mix_server.cpp, the
    reference's Netty-runtime analog; same wire protocol); python runs
    the asyncio implementation (required for --ssl-*); auto prefers
    native when a toolchain built it and no TLS was requested."""
    from ..parallel.mix_service import MixServer, make_server_ssl_context

    ctx = None
    if bool(args.ssl_cert) != bool(args.ssl_key):
        print("--ssl-cert and --ssl-key must be given together",
              file=sys.stderr)
        return 2
    if args.ssl_cert:
        ctx = make_server_ssl_context(args.ssl_cert, args.ssl_key)
    def serve(srv, impl_name: str, ssl_on: bool) -> int:
        print(json.dumps({"host": srv.host, "port": srv.port,
                          "ssl": ssl_on, "impl": impl_name}))
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            srv.stop()
        return 0

    impl = args.impl
    if impl == "native" and ctx is not None:
        print("--impl native has no TLS; use --impl python with --ssl-*",
              file=sys.stderr)
        return 2
    if impl in ("auto", "native") and ctx is None:
        from ..parallel.mix_native import NativeMixServer, native_available
        if native_available():
            try:
                # only STARTUP failures fall back; once bound, serve()
                # owns the process (a post-start error must not leave the
                # native child running while python doubles the listener)
                nsrv = NativeMixServer(args.host, args.port).start()
            except (RuntimeError, OSError) as e:
                # e.g. hostname --host (the C++ server wants numeric IPv4)
                # or a bound port: auto falls back to the asyncio server,
                # an explicit --impl native reports the real cause
                nsrv = None
                if impl == "native":
                    print(f"native mix server failed: {e}", file=sys.stderr)
                    return 1
                print(f"native mix server failed ({e}); "
                      f"falling back to --impl python", file=sys.stderr)
            if nsrv is not None:
                return serve(nsrv, "native", False)
        elif impl == "native":
            print("native mix server unavailable (no g++?)",
                  file=sys.stderr)
            return 1
    return serve(MixServer(args.host, args.port, ssl_context=ctx).start(),
                 "python", bool(ctx))


def _cmd_serve(args) -> int:
    """Online prediction server (docs/SERVING.md): load a checkpoint
    bundle, serve /predict with dynamic micro-batching, hot-reload newer
    autosaved bundles from --checkpoint-dir (a live trainer writing into
    the same directory is the intended pairing).

    ``--replicas N`` switches to the fleet topology (docs/SERVING.md
    "Fleet topology"): N engine processes behind a health-gated router,
    with manager-coordinated rolling hot reload and crash respawn."""
    if args.replicas > 0:
        if args.retrieval:
            print("error: --retrieval is a single-server surface "
                  "(fleet retrieval is not wired yet)", file=sys.stderr)
            return 2
        return _cmd_serve_fleet(args)
    from ..serve.engine import PredictEngine
    from ..serve.http import PredictServer

    retrieval = None
    if args.retrieval:
        from ..serve.retrieve import RetrievalEngine
        try:
            retrieval = RetrievalEngine(
                args.algo, args.options or "",
                bundle=args.bundle, checkpoint_dir=args.checkpoint_dir,
                follow="promoted" if args.promote else "newest",
                precision=args.serve_precision,
                max_batch=args.serve_max_batch,
                k_default=args.retrieval_k,
                tier=args.retrieval_tier,
                watch_interval=args.watch_interval)
        except (FileNotFoundError, ValueError, NotImplementedError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    try:
        engine = PredictEngine(
            args.algo, args.options or "",
            bundle=args.bundle, checkpoint_dir=args.checkpoint_dir,
            max_batch=args.serve_max_batch,
            watch_interval=args.watch_interval,
            warmup=not args.no_warmup,
            follow="promoted" if args.promote else "newest",
            arena=args.serve_arena,
            precision=args.serve_precision)
    except (FileNotFoundError, ValueError, NotImplementedError,
            AttributeError) as e:
        # AttributeError = no make_scorer: pure factor families
        # (MF/BPR/word2vec) have no row-predict surface
        if retrieval is None:
            print(f"error: {e}", file=sys.stderr)
            return 2
        # --retrieval serves them retrieval-only (/predict 404s)
        engine = None
    if args.serve_plane == "evloop":
        from ..serve.evloop import EvloopPredictServer as _ServerCls
    else:
        _ServerCls = PredictServer
    srv = _ServerCls(
        engine, host=args.host, port=args.port,
        max_delay_ms=args.serve_max_delay_ms,
        max_queue_rows=args.serve_max_queue,
        deadline_ms=args.serve_deadline_ms,
        slo_p99_ms=args.slo_p99_ms,
        slo_availability=args.slo_availability,
        retrieval=retrieval).start()
    ctrl = None
    retrain_ctl = None
    if args.promote and args.checkpoint_dir:
        # single-server promotion: the engine follows the pointer; an
        # in-process controller gates candidates out of the autosave
        # dir (shadow-scoring mirrored traffic teed off the batcher)
        from ..serve.promote import (PromotionController, PromotionGate,
                                     ShadowBuffer)
        # --retrain additionally captures the RAW request rows the
        # replay buffer trains on (the label join is a feedback-side
        # concern — without one, retrains run over --train-input only)
        shadow = ShadowBuffer(capture_raw=args.retrain) \
            if engine is not None else None
        if engine is not None:
            srv.batcher.set_tee(shadow.add, raw=args.retrain)
        gate = PromotionGate(args.algo, args.options or "",
                             holdout=args.holdout, shadow=shadow,
                             precision=args.serve_precision)
        ctrl = PromotionController(args.checkpoint_dir, gate,
                                   interval=args.watch_interval,
                                   slo=srv.slo).start()
        if args.retrain and engine is None:
            print("error: --retrain needs a predict surface (the replay "
                  "buffer mirrors /predict traffic)", file=sys.stderr)
            srv.stop()
            return 2
        if args.retrain:
            from ..serve.retrain import RetrainController
            retrain_ctl = RetrainController(
                args.algo, args.options or "",
                checkpoint_dir=args.checkpoint_dir,
                slo=srv.slo, shadow=shadow,
                train_input=args.train_input,
                cooldown_s=args.retrain_cooldown_s,
                min_votes=args.retrain_min_votes,
                max_retrains_per_window=args.retrain_max_per_window,
                interval=args.watch_interval).start()
    elif args.retrain:
        print("error: --retrain needs --promote and --checkpoint-dir "
              "(candidates go through the promotion gate)",
              file=sys.stderr)
        srv.stop()
        return 2
    eng = engine if engine is not None else retrieval
    print(json.dumps({"host": srv.host, "port": srv.port,
                      "algo": args.algo,
                      "model_step": eng.model_step,
                      "model_path": eng.model_path,
                      "retrieval": retrieval is not None}), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        if retrain_ctl is not None:
            retrain_ctl.stop()
        if ctrl is not None:
            ctrl.stop()
        srv.stop()
    return 0


def _cmd_serve_fleet(args) -> int:
    """`serve --replicas N`: replica manager + front-end router."""
    from ..serve.fleet import Fleet

    try:
        fleet = Fleet(
            args.algo, args.options or "",
            checkpoint_dir=args.checkpoint_dir, bundle=args.bundle,
            replicas=args.replicas, host=args.host, port=args.port,
            policy=args.router_policy, plane=args.serve_plane,
            watch_interval=args.watch_interval,
            slo_p99_ms=args.slo_p99_ms,
            slo_availability=args.slo_availability,
            trace_sample=args.trace_sample,
            promote=args.promote,
            holdout=args.holdout,
            canary_fraction=args.canary_fraction,
            canary_bake_s=args.canary_bake_s,
            retrain=args.retrain,
            train_input=args.train_input,
            retrain_opts={
                "cooldown_s": args.retrain_cooldown_s,
                "min_votes": args.retrain_min_votes,
                "max_retrains_per_window": args.retrain_max_per_window,
            } if args.retrain else None,
            result_cache_entries=args.router_cache,
            result_cache_bytes=int(args.router_cache_mb * (1 << 20)),
            serve_kwargs={
                "max_batch": args.serve_max_batch,
                "max_delay_ms": args.serve_max_delay_ms,
                "max_queue_rows": args.serve_max_queue,
                "deadline_ms": args.serve_deadline_ms,
                "precision": args.serve_precision,
                "arena": args.serve_arena,
            }).start(wait_ready=True)
    except (FileNotFoundError, ValueError, RuntimeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    ready = sum(1 for h in fleet.router.replicas() if h.ready)
    print(json.dumps({"host": fleet.host, "port": fleet.port,
                      "algo": args.algo, "replicas": args.replicas,
                      "ready_replicas": ready,
                      "policy": args.router_policy,
                      "plane": args.serve_plane,
                      "fleet_step": fleet.manager.fleet_step}), flush=True)
    # SIGTERM (systemd stop, docker stop, kill <pid>) must tear the fleet
    # down like Ctrl-C does — the default handler would kill this process
    # and orphan every replica worker on its ephemeral port
    import signal

    def on_term(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, on_term)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        fleet.stop()
    return 0


def _cmd_promote(args) -> int:
    """Promotion control plane, one dir at a time (docs/RELIABILITY.md
    "Promotion and rollback"): gate the newest candidate bundle against
    the promoted one and flip/quarantine (default), keep watching
    (--watch), print the pointer manifest (--status), or manually revert
    to the previous promotion (--rollback)."""
    from ..io.checkpoint import read_promoted, rollback_promoted

    if args.status:
        m = read_promoted(args.checkpoint_dir)
        print(json.dumps({"configured": m is not None, "manifest": m},
                         default=str))
        return 0
    if args.rollback:
        m = rollback_promoted(args.checkpoint_dir,
                              args.reason or "manual rollback")
        if m is None:
            print("error: no promotion history to roll back to",
                  file=sys.stderr)
            return 1
        print(json.dumps({"rolled_back_to": m["current"],
                          "rollbacks": m["rollbacks"]}, default=str))
        return 0
    from ..serve.promote import PromotionController, PromotionGate
    gate = PromotionGate(
        args.algo, args.options or "", holdout=args.holdout,
        max_logloss_increase=args.max_logloss_increase,
        max_auc_decrease=args.max_auc_decrease,
        max_calibration_gap=args.max_calibration_gap,
        precision=args.precision)
    ctrl = PromotionController(
        args.checkpoint_dir, gate, interval=args.interval,
        promote_state="canary" if args.canary else "serving")
    if not args.watch:
        report = ctrl.check_once()
        if report is None:
            print(json.dumps({"candidate": None,
                              "promoted": read_promoted(
                                  args.checkpoint_dir) is not None}))
            return 0
        print(json.dumps(report, default=str))
        return 0 if report["verdict"] == "pass" else 1
    ctrl.start()
    print(json.dumps({"watching": args.checkpoint_dir,
                      "interval": args.interval}), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        ctrl.stop()
    return 0


def _cmd_arena(args) -> int:
    """Publish (or inspect) a bundle's weight-arena sidecar — the
    operator path for fleets that don't run the promotion gate (which
    publishes automatically on every admitted candidate)."""
    from ..catalog import lookup
    from ..io.weight_arena import (ArenaUnsupported, arena_path,
                                   open_arena, publish_arena)
    ap = arena_path(args.bundle)
    if args.status:
        try:
            a = open_arena(ap)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        h = dict(a.header)
        h.pop("arrays", None)            # per-array offsets: noise here
        print(json.dumps({"arena": ap, "mapped_bytes": a.mapped_bytes,
                          "matches_bundle": a.matches_bundle(args.bundle),
                          "header": h}, default=str, indent=1))
        return 0
    try:
        cls = lookup(args.algo).resolve()
        trainer = cls(args.options or "")
        trainer.load_bundle(args.bundle)
        path = publish_arena(args.bundle, trainer)
    except ArenaUnsupported as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    except (OSError, ValueError, KeyError, FileNotFoundError) as e:
        print(f"error: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    a = open_arena(path)
    print(json.dumps({"published": path, "family": a.family,
                      "precisions": list(a.precisions),
                      "mapped_bytes": a.mapped_bytes,
                      "step": a.step}))
    return 0


def _cmd_retrain(args) -> int:
    """Drift-driven retrain autopilot (docs/RELIABILITY.md "Autonomous
    retraining"): consume ``retrain_wanted`` votes (``--slo-url`` polls
    a serve/router ``/slo``), debounce them through cooldown/budget/flap
    storm controls, and launch supervised warm-start retrains whose
    candidates go through the normal promotion gate. ``--once`` forces
    one retrain now; ``--status`` prints the on-disk state."""
    from ..serve.retrain import RetrainController

    votes_fn = None
    if args.slo_url:
        import urllib.request
        url = args.slo_url.rstrip("/")
        if not url.endswith("/slo"):
            url += "/slo"

        def votes_fn() -> int:
            with urllib.request.urlopen(url, timeout=10) as resp:
                drift = json.loads(resp.read()).get("drift") or {}
            return int(drift.get("retrain_wanted") or 0)

    gate = None
    if args.holdout:
        from ..serve.promote import PromotionGate
        gate = PromotionGate(args.algo, args.options or "",
                             holdout=args.holdout)
    ctl = RetrainController(
        args.algo, args.options or "",
        checkpoint_dir=args.checkpoint_dir,
        votes_fn=votes_fn, gate=gate,
        train_input=args.train_input, replay_dir=args.replay_dir,
        min_votes=args.min_votes, cooldown_s=args.cooldown_s,
        window_s=args.window_s,
        max_retrains_per_window=args.max_retrains,
        backoff_factor=args.backoff_factor,
        train_timeout_s=args.train_timeout_s,
        interval=args.interval, batch_size=args.batch_size,
        epochs=args.epochs)
    if args.status:
        print(json.dumps(ctl.status(), default=str))
        return 0
    if args.once:
        # a manual retrain bypasses the vote debounce but still runs
        # the full train -> gate -> promote/quarantine path
        if not ctl.trigger("manual retrain (--once)"):
            print(f"error: {ctl.last_error}", file=sys.stderr)
            return 2
        ctl.wait_idle(timeout=args.train_timeout_s
                      + ctl.gate_timeout_s + 60.0)
        section = ctl.obs_section()
        print(json.dumps(section, default=str))
        return 0 if section["successes"] > 0 else 1
    if not args.slo_url:
        print("error: --watch needs --slo-url <serve/router base> as "
              "the retrain_wanted vote source (or run the controller "
              "in-process via `serve --retrain`)", file=sys.stderr)
        return 2
    ctl.start()
    print(json.dumps({"watching": args.checkpoint_dir,
                      "slo_url": args.slo_url,
                      "interval": args.interval}), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        ctl.stop()
    return 0


def _cmd_obs(args) -> int:
    """Live-run summary off a metrics jsonl (docs/OBSERVABILITY.md): event
    counts, training rate, span stage breakdown, MIX breaker state,
    checkpoint age. ``--follow`` re-renders as the file grows. ``--slo``
    instead renders a serving SLO report (burn rates, windowed p99,
    drift events) from a serve/router ``/slo`` endpoint or a saved JSON
    file. ``obs postmortem <dir>`` merges every flight ring under
    ``<dir>`` into one wall-clock-ordered timeline (docs/OBSERVABILITY.md
    "Flight recorder"): death gaps flagged per ring, each victim's
    admitted-but-never-completed request ids, the last ``--tail``
    events. ``--since`` (shared with the jsonl summary) filters to
    seconds-ago (< 1e9) or an absolute epoch."""
    from ..obs.report import parse_since
    since = parse_since(args.since)
    if args.file == "postmortem":
        if not args.target:
            print("obs postmortem: needs a flight-ring directory "
                  "(e.g. <checkpoint_dir>/flight)", file=sys.stderr)
            return 2
        from ..obs.flight import merge_dir, render_postmortem
        merged = merge_dir(args.target, since=since)
        print(render_postmortem(merged, tail=args.tail), end="")
        if not merged["rings"]:
            print(f"obs postmortem: no *.ring files under {args.target}",
                  file=sys.stderr)
            return 1
        return 0
    if args.slo:
        from ..obs.report import render_slo_source
        return render_slo_source(args.file, follow=args.follow,
                                 interval=args.interval)
    from ..obs.report import render_file
    return render_file(args.file, follow=args.follow,
                       interval=args.interval, since=since)


def _cmd_define_all(args) -> int:
    from ..catalog import registry
    dialect = getattr(args, "dialect", "hive")
    fn = {"hive": registry.define_all,
          "spark": registry.define_all_spark,
          "pig": registry.define_all_pig,
          "td": registry.define_udfs_td}[dialect]
    print(fn())
    return 0


def _cmd_help(args) -> int:
    from ..catalog import help_for
    print(help_for(args.function))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="hivemall_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser(
        "train",
        help="train a catalog algorithm on LIBSVM/CSV/Parquet input "
             "(a directory of .parquet shards streams out-of-core)")
    t.add_argument("--algo", required=True)
    t.add_argument("--input", required=True)
    t.add_argument("--feature-col", default="features",
                   help="feature column for parquet/arrow input")
    t.add_argument("--label-col", default="label",
                   help="label column for parquet/csv/arrow input")
    t.add_argument("--options", default="")
    t.add_argument("--model", default=None)
    t.add_argument("--load-bundle", default=None,
                   help="resume from a full-state checkpoint bundle (.npz)")
    t.add_argument("--save-bundle", default=None,
                   help="write a full-state checkpoint bundle at the end")
    t.add_argument("--resume", action="store_true",
                   help="restore the newest usable autosaved bundle from "
                        "the trainer's -checkpoint_dir before training "
                        "(shard-directory input resumes mid-stream; file "
                        "input restarts its epoch with restored state)")
    t.add_argument("--promote", action="store_true",
                   help="after training, gate the newest autosaved bundle "
                        "(-checkpoint_dir) against the promoted one and "
                        "flip the PROMOTED pointer on pass; a failed gate "
                        "quarantines it (docs/RELIABILITY.md)")
    t.add_argument("--holdout", default=None,
                   help="LIBSVM holdout file for the --promote gate "
                        "(default: --input when it is a single file)")
    t.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of the first fit "
                        "into DIR (sets HIVEMALL_TPU_PROF; open with "
                        "tensorboard/xprof — the capture is recorded as "
                        "a `profile` event in the metrics stream)")
    t.set_defaults(fn=_cmd_train)

    pr = sub.add_parser(
        "predict",
        help="score a LIBSVM file with a model table, or bulk-score a "
             "Parquet shard dir / file from a checkpoint bundle")
    pr.add_argument("--algo", required=True)
    pr.add_argument("--model", default=None,
                    help="model TSV (-loadmodel) for the single-file path")
    pr.add_argument("--input", required=True)
    pr.add_argument("--output", default=None,
                    help="scores TSV (single-file path) or scored-Parquet "
                         "output dir (bulk path)")
    pr.add_argument("--options", default="")
    pr.add_argument("--metric", default=None,
                    choices=[None, "auc", "logloss", "rmse"])
    # bulk path (docs/PERFORMANCE.md "Bulk scoring"): any of
    # --bundle/--checkpoint-dir, or a directory --input, routes here
    pr.add_argument("--bundle", default=None,
                    help="bulk: score with this checkpoint bundle")
    pr.add_argument("--checkpoint-dir", default=None,
                    help="bulk: resolve the model from this dir's PROMOTED "
                         "pointer (newest bundle if nothing promoted)")
    pr.add_argument("--backend", default="auto",
                    choices=("auto", "kernel", "arena"),
                    help="bulk: jitted kernels, mmap'd arena twins, or "
                         "probe-and-pick (default)")
    pr.add_argument("--precision", default="f32",
                    choices=("f32", "bf16", "int8"),
                    help="bulk: arena scoring tier (non-f32 implies "
                         "--backend arena)")
    pr.add_argument("--workers", type=int, default=1,
                    help="bulk: worker processes (1 = in-process)")
    pr.add_argument("--batch-size", type=int, default=0,
                    help="bulk: override the scoring batch size")
    pr.add_argument("--cache-dir", default=None,
                    help="bulk: shard decode cache dir (share it with "
                         "training's -shard_cache_dir for warm scans)")
    pr.add_argument("--top-k", type=int, default=0,
                    help="bulk: per-group top-k over scored rows "
                         "(each_top_k; negative = bottom-k)")
    pr.add_argument("--group-col", default=None,
                    help="bulk: Parquet group column for --top-k")
    pr.add_argument("--feature-col", default="features")
    pr.add_argument("--label-col", default="label")
    pr.set_defaults(fn=_cmd_predict)

    m = sub.add_parser("mixserv", help="run a standalone mix server")
    m.add_argument("--ssl-cert", default=None,
                   help="TLS certificate file (enables -ssl transport)")
    m.add_argument("--ssl-key", default=None, help="TLS private key file")
    m.add_argument("--host", default="0.0.0.0")
    m.add_argument("--port", type=int, default=11212)
    m.add_argument("--impl", default="auto",
                   choices=("auto", "native", "python"),
                   help="native = C++ epoll server (no TLS), python = "
                        "asyncio, auto = native when available")
    m.set_defaults(fn=_cmd_mixserv)

    sv = sub.add_parser(
        "serve", help="online prediction server over a checkpoint bundle "
                      "(dynamic micro-batching + hot reload; "
                      "docs/SERVING.md)")
    sv.add_argument("--algo", required=True,
                    help="catalog trainer the bundle was written by")
    sv.add_argument("--options", default="",
                    help="trainer options (must match the training config "
                         "— table shapes are validated at load)")
    sv.add_argument("--checkpoint-dir", default=None,
                    help="directory of autosaved step bundles to serve "
                         "and watch for hot reload (may be the live "
                         "trainer's -checkpoint_dir)")
    sv.add_argument("--bundle", default=None,
                    help="explicit bundle (.npz) to serve instead of the "
                         "newest in --checkpoint-dir")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=8080)
    sv.add_argument("--serve-max-batch", type=int, default=256,
                    help="max rows coalesced into one predict dispatch")
    sv.add_argument("--serve-max-delay-ms", type=float, default=2.0,
                    help="max milliseconds a request waits for batch "
                         "coalescing")
    sv.add_argument("--serve-max-queue", type=int, default=None,
                    help="bounded queue size in rows (default "
                         "8x max-batch); submits past it are shed with "
                         "503")
    sv.add_argument("--serve-deadline-ms", type=float, default=0.0,
                    help="default per-request deadline (0 = none); "
                         "expired requests get 504")
    sv.add_argument("--watch-interval", type=float, default=2.0,
                    help="seconds between hot-reload checkpoint-dir polls")
    sv.add_argument("--no-warmup", action="store_true",
                    help="skip pre-compiling the batch-size buckets at "
                         "startup")
    sv.add_argument("--serve-precision", default="f32",
                    choices=("f32", "bf16", "int8"),
                    help="scoring precision tier (docs/PERFORMANCE.md "
                         "'Weight arena + quantized scoring'): f32 = "
                         "the bit-exact jitted path; bf16/int8 score "
                         "from the mmap'd weight arena's quantized "
                         "tables (bounded score error, ~2x+ qps on CPU "
                         "hosts, shared weight pages across replicas)")
    sv.add_argument("--serve-plane", default="threaded",
                    choices=("threaded", "evloop"),
                    help="serving plane (docs/SERVING.md 'Serving "
                         "planes'): threaded = thread-per-connection + "
                         "MicroBatcher (default), evloop = epoll event "
                         "loop with inline batch assembly — same "
                         "contracts, lower per-request overhead; in "
                         "fleet mode evloop replicas also expose a UDS "
                         "fast path the co-located router prefers")
    sv.add_argument("--serve-arena", default="auto",
                    choices=("auto", "off", "force"),
                    help="weight-arena policy: auto (quantized tiers "
                         "map the arena, f32 keeps the jitted scorer), "
                         "off (bundle path only), force (f32 also "
                         "scores zero-copy from the arena — ulp-level "
                         "deviation from the jitted path)")
    sv.add_argument("--router-cache", type=int, default=0,
                    help="fleet mode: router-level LRU result cache "
                         "entries for idempotent hot /predict bodies "
                         "(0 = off); invalidated on every reload/"
                         "promotion/rollback, bypassed during canary "
                         "bakes")
    sv.add_argument("--router-cache-mb", type=float, default=8.0,
                    help="fleet mode: result-cache byte bound in MiB")
    sv.add_argument("--replicas", type=int, default=0,
                    help="fleet mode: spawn N replica processes (one "
                         "engine each) behind a health-gated router with "
                         "rolling hot reload and crash respawn; 0 = "
                         "single in-process server")
    sv.add_argument("--router-policy", default="least_loaded",
                    choices=("least_loaded", "hash"),
                    help="fleet routing: least in-flight with "
                         "consistent-hash tiebreak (default), or strict "
                         "consistent hashing of the request body")
    sv.add_argument("--slo-p99-ms", type=float, default=100.0,
                    help="latency SLO: p99 objective in ms — /slo "
                         "reports the fraction of requests over it and "
                         "the burn rate vs the 1%% allowance")
    sv.add_argument("--slo-availability", type=float, default=0.999,
                    help="availability SLO target in (0,1); errors+shed "
                         "burn the error budget (/slo burn rates over "
                         "5m/1h windows)")
    sv.add_argument("--trace-sample", type=float, default=0.01,
                    help="fleet mode: fraction of routed requests the "
                         "router mints an x-hivemall-trace id for when "
                         "HIVEMALL_TPU_TRACE is enabled (client-supplied "
                         "ids are always honored)")
    sv.add_argument("--promote", action="store_true",
                    help="gated promotion: serve the PROMOTED pointer "
                         "instead of the newest bundle; new candidates "
                         "are gated (holdout + mirrored-traffic shadow "
                         "scoring) and, in fleet mode, canaried onto "
                         "--canary-fraction of replicas with auto-"
                         "rollback (docs/RELIABILITY.md)")
    sv.add_argument("--holdout", default=None,
                    help="LIBSVM holdout file the promotion gate scores "
                         "candidates against (omit to gate on digest + "
                         "mirrored traffic only)")
    sv.add_argument("--canary-fraction", type=float, default=0.25,
                    help="fleet --promote: fraction of replicas a "
                         "passing candidate bakes on before the full "
                         "roll (at least 1, at most replicas-1)")
    sv.add_argument("--canary-bake-s", type=float, default=10.0,
                    help="fleet --promote: seconds the canary cohort's "
                         "SLO totals are watched against the stable "
                         "cohort before completing the roll")
    sv.add_argument("--retrain", action="store_true",
                    help="autonomous drift-driven retraining (needs "
                         "--promote): consume the SLO engine's "
                         "retrain_wanted votes, warm-start retrains "
                         "from the PROMOTED bundle over --train-input "
                         "+ the live replay buffer, and gate the "
                         "candidates (docs/RELIABILITY.md)")
    sv.add_argument("--train-input", default=None,
                    help="base corpus for --retrain (LIBSVM file or a "
                         "directory of parquet shards; epochs go "
                         "through the shard caches when -shard_cache_"
                         "dir is in --options)")
    sv.add_argument("--retrain-cooldown-s", type=float, default=300.0,
                    help="--retrain: per-model cooldown after every "
                         "attempt (rejections back off exponentially)")
    sv.add_argument("--retrain-min-votes", type=int, default=2,
                    help="--retrain: drift votes within the vote "
                         "window needed to trigger")
    sv.add_argument("--retrain-max-per-window", type=int, default=4,
                    help="--retrain: max retrains per hour window")
    sv.add_argument("--retrieval", action="store_true",
                    help="also serve /retrieve top-k over the factor "
                         "tables (MF/BPR/word2vec; docs/SERVING.md "
                         "'Retrieval plane'): user→top-k items and "
                         "item→k neighbors, own batcher, hot reload "
                         "shared with /predict")
    sv.add_argument("--retrieval-tier", default="exact",
                    choices=("exact", "lsh"),
                    help="default candidate tier for /retrieve: exact "
                         "full scan (bit-matches each_top_k) or SRP-LSH "
                         "candidates + exact rescore (per-query "
                         "override via the 'tier' field)")
    sv.add_argument("--retrieval-k", type=int, default=10,
                    help="default k for /retrieve queries that omit it")
    sv.set_defaults(fn=_cmd_serve)

    rv = sub.add_parser(
        "retrieve",
        help="offline top-k retrieval over a factor bundle (user→items "
             "/ item→neighbors; the one-shot twin of serve "
             "--retrieval)")
    rv.add_argument("--algo", required=True,
                    help="factor trainer the bundle was written by "
                         "(train_mf_sgd, train_bprmf, train_word2vec)")
    rv.add_argument("--options", default="",
                    help="trainer options (must match the training "
                         "config — table shapes are validated at load)")
    rv.add_argument("--bundle", default=None,
                    help="explicit bundle (.npz) to query")
    rv.add_argument("--checkpoint-dir", default=None,
                    help="resolve the model from this dir (PROMOTED "
                         "pointer first, else newest bundle)")
    rv.add_argument("--user", default=None,
                    help="comma-separated user ids → top-k items each")
    rv.add_argument("--item", default=None,
                    help="comma-separated item ids → k neighbors each")
    rv.add_argument("-k", type=int, default=10,
                    help="results per query")
    rv.add_argument("--tier", default="exact", choices=("exact", "lsh"),
                    help="exact full scan or LSH candidates + exact "
                         "rescore")
    rv.add_argument("--precision", default="f32",
                    choices=("f32", "bf16", "int8"),
                    help="arena scoring tier for the rescore")
    rv.add_argument("--rescore", default="auto",
                    choices=("auto", "numpy", "kernel"),
                    help="rescore backend: numpy arena twins, jitted "
                         "kernels, or probe-and-pick (default)")
    rv.set_defaults(fn=_cmd_retrieve)

    rt = sub.add_parser(
        "retrain",
        help="drift-driven retrain controller: turn retrain_wanted "
             "votes into gated warm-start retrains "
             "(docs/RELIABILITY.md \"Autonomous retraining\")")
    rt.add_argument("--algo", required=True,
                    help="catalog trainer the bundles were written by")
    rt.add_argument("--options", default="",
                    help="trainer options (must match training)")
    rt.add_argument("--checkpoint-dir", required=True,
                    help="autosave dir holding the PROMOTED pointer, "
                         "candidates, replay segments and the "
                         "RETRAIN_STATE stamp")
    rt.add_argument("--train-input", default=None,
                    help="base corpus (LIBSVM file or parquet shard "
                         "dir) retrains run over, in addition to the "
                         "replay buffer")
    rt.add_argument("--replay-dir", default=None,
                    help="replay segment dir (default: <checkpoint-"
                         "dir>/replay)")
    rt.add_argument("--holdout", default=None,
                    help="LIBSVM holdout: gate candidates HERE instead "
                         "of leaving them to an external promote "
                         "watcher / fleet manager")
    rt.add_argument("--slo-url", default=None,
                    help="serve/router base URL whose /slo drift "
                         "counters are the retrain_wanted vote source")
    rt.add_argument("--watch", action="store_true",
                    help="keep consuming votes until Ctrl-C (default "
                         "when neither --once nor --status)")
    rt.add_argument("--once", action="store_true",
                    help="force one retrain now (bypasses the vote "
                         "debounce, still gated); rc 0 promoted, 1 "
                         "rejected/failed")
    rt.add_argument("--status", action="store_true",
                    help="print the controller state + on-disk stamp "
                         "and exit")
    rt.add_argument("--cooldown-s", type=float, default=300.0,
                    help="per-model cooldown seconds after every "
                         "attempt (storm control)")
    rt.add_argument("--min-votes", type=int, default=2,
                    help="votes within the vote window needed to "
                         "trigger")
    rt.add_argument("--window-s", type=float, default=3600.0,
                    help="storm-control window seconds")
    rt.add_argument("--max-retrains", type=int, default=4,
                    help="max retrains per --window-s (storm control)")
    rt.add_argument("--backoff-factor", type=float, default=2.0,
                    help="cooldown multiplier per consecutive gate "
                         "rejection")
    rt.add_argument("--train-timeout-s", type=float, default=900.0,
                    help="kill a retrain child past this wall time")
    rt.add_argument("--interval", type=float, default=2.0,
                    help="controller tick interval seconds")
    rt.add_argument("--batch-size", type=int, default=64,
                    help="retrain mini-batch rows")
    rt.add_argument("--epochs", type=int, default=1,
                    help="epochs over the retrain input")
    rt.set_defaults(fn=_cmd_retrain)

    pm = sub.add_parser(
        "promote",
        help="gate candidate checkpoint bundles and manage the PROMOTED "
             "pointer (shadow validation, rollback; docs/RELIABILITY.md)")
    pm.add_argument("--algo", required=True,
                    help="catalog trainer the bundles were written by")
    pm.add_argument("--options", default="",
                    help="trainer options (must match training)")
    pm.add_argument("--checkpoint-dir", required=True,
                    help="autosave dir holding candidates + the pointer")
    pm.add_argument("--holdout", default=None,
                    help="LIBSVM holdout the gate scores candidates on")
    pm.add_argument("--watch", action="store_true",
                    help="keep gating new candidates until Ctrl-C")
    pm.add_argument("--interval", type=float, default=2.0,
                    help="--watch poll interval seconds")
    pm.add_argument("--canary", action="store_true",
                    help="promote with state=canary so a promote-mode "
                         "fleet bakes it on a canary cohort first")
    pm.add_argument("--status", action="store_true",
                    help="print the PROMOTED pointer manifest and exit")
    pm.add_argument("--rollback", action="store_true",
                    help="revert the pointer to the previous promotion")
    pm.add_argument("--reason", default=None,
                    help="reason recorded with --rollback")
    pm.add_argument("--max-logloss-increase", type=float, default=0.05,
                    help="gate: max absolute holdout logloss increase vs "
                         "the promoted baseline")
    pm.add_argument("--max-auc-decrease", type=float, default=0.02,
                    help="gate: max holdout AUC decrease vs baseline")
    pm.add_argument("--max-calibration-gap", type=float, default=0.15,
                    help="gate: max |mean predicted prob - positive "
                         "rate| on the holdout")
    pm.add_argument("--precision", default="f32",
                    choices=("f32", "bf16", "int8"),
                    help="gate candidates at this scoring precision — "
                         "quantized fleets must gate on the quantized "
                         "scores they actually serve")
    pm.set_defaults(fn=_cmd_promote)

    ar = sub.add_parser(
        "arena",
        help="publish or inspect a bundle's mmap'd weight arena "
             "(zero-copy multi-precision serving weights; "
             "docs/PERFORMANCE.md 'Weight arena + quantized scoring')")
    ar.add_argument("--algo", required=True,
                    help="catalog trainer the bundle was written by")
    ar.add_argument("--options", default="",
                    help="trainer options (must match training)")
    ar.add_argument("--bundle", required=True,
                    help="checkpoint bundle (.npz) to publish/inspect "
                         "the arena for")
    ar.add_argument("--status", action="store_true",
                    help="print the existing arena's header instead of "
                         "publishing")
    ar.set_defaults(fn=_cmd_arena)

    o = sub.add_parser(
        "obs", help="summarize a HIVEMALL_TPU_METRICS jsonl stream "
                    "(rates, stage breakdown, breaker state, checkpoint "
                    "age); `obs postmortem <dir>` merges flight-recorder "
                    "rings into one post-mortem timeline")
    o.add_argument("file", help="metrics jsonl path (or, with --slo, a "
                                "serve/router base URL or /slo JSON "
                                "file); or the literal word `postmortem`")
    o.add_argument("target", nargs="?", default=None,
                   help="with `postmortem`: the flight-ring directory "
                        "(e.g. <checkpoint_dir>/flight)")
    o.add_argument("--follow", action="store_true",
                   help="keep watching; re-render when the file grows")
    o.add_argument("--interval", type=float, default=2.0,
                   help="--follow poll interval seconds")
    o.add_argument("--since", default=None, metavar="SECS",
                   help="only events in the window: seconds-ago when "
                        "< 1e9 (--since 300 = last 5 minutes) or an "
                        "absolute epoch timestamp; shared by the jsonl "
                        "summary and `obs postmortem`")
    o.add_argument("--tail", type=int, default=200,
                   help="postmortem: show the last N merged events")
    o.add_argument("--slo", action="store_true",
                   help="render a serving SLO report instead: FILE is a "
                        "http(s)://host:port serve/router base (its /slo "
                        "endpoint is fetched) or a saved /slo JSON file")
    o.set_defaults(fn=_cmd_obs)

    d = sub.add_parser("define-all", help="print the function manifest")
    d.add_argument("--dialect", default="hive",
                   choices=("hive", "spark", "pig", "td"),
                   help="registration dialect (define-all.hive/.spark/"
                        ".pig / define-udfs.td.hql analogs)")
    d.set_defaults(fn=_cmd_define_all)

    h = sub.add_parser("help", help="show a function's option grammar")
    h.add_argument("function")
    h.set_defaults(fn=_cmd_help)

    args = p.parse_args(argv)
    if args.cmd not in ("define-all", "help", "obs", "mixserv"):
        # every command that can jit shares the one persistent compile
        # cache (sets config only; the backend stays uninitialised, so
        # `serve --replicas N` still leaves the chips to its replicas)
        from ..utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
