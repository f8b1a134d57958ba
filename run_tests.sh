#!/bin/sh
# Everything here runs on the CPU, asked for by name (tests/conftest.py adds
# the 8 virtual devices); Pallas kernels run in interpret mode because of
# it (hivemall_tpu/utils/device.py). The chip is reached only through the
# chip tool, one process per chip: `python chip_smoke.py` first.
export JAX_PLATFORMS=cpu

# CI artifacts (graftcheck JSON report, tsan race log, leaktrack census
# log) land here; a fresh run starts from a clean slate so stale
# records can't confuse a read of the artifacts.
mkdir -p artifacts
rm -f artifacts/graftcheck_report.json artifacts/tsan_races.jsonl \
      artifacts/leaktrack_census.jsonl artifacts/retrain_smoke.json

# graftcheck gate (docs/STATIC_ANALYSIS.md): project-invariant static
# analysis, run FIRST because it is the cheapest phase (~17 s cold /
# <2 s cached, budget <=30 s — the parse/summary AND rule passes fan
# across cores, 2-CPU container floor; per-rule wall breakdown lands
# in the JSON artifact). --selfcheck proves the gate in four
# directions before the real scan — every rule (incl. the
# interprocedural GC01/GC02/GC04 upgrades, GC07/GC08, and the v3 XLA
# compile-contract + resource-lifecycle rules GC09-GC12) must fire on
# a seeded violation in a scratch tree, the baseline machinery must
# silence fresh findings / flag stale entries, the tsan lockset
# sanitizer must detect the re-seeded PR 11 last_reload_error race,
# and the leaktrack census sanitizer must catch a seeded fd leak —
# then the real scan (package + tests/ + graft entry;
# content-hash cached, whole-scan invalidation on any edit or rule
# bump) fails on ANY finding (the tree's contract since PR 11 is an
# EMPTY baseline; a PR that must land with debt commits
# graftcheck_baseline.json, which the bare run picks up from the repo
# root, and the gate keeps failing once a baselined finding is fixed
# but its entry lingers). The full JSON report is emitted as a CI
# artifact.
python -m hivemall_tpu.tools.graftcheck --selfcheck || exit $?
python -m hivemall_tpu.tools.graftcheck \
    --json-out artifacts/graftcheck_report.json || exit $?

python -m pytest tests/ -q "$@" || exit $?

# fault-injection smoke (docs/RELIABILITY.md): a FlakyProxy'd MIX exchange
# survives a mid-run server kill + restart (reconnect counter > 0), and a
# crash-at-step-N fit_stream resumes from its autosaved bundle with
# bit-identical final weights. Seconds-scale; the long soak variants live
# in tests/ marked `slow`.
python -m hivemall_tpu.testing.faults --smoke || exit $?

# observability smoke (docs/OBSERVABILITY.md): a seconds-scale traced fit
# must produce a parseable jsonl stream with train_step/train_done/
# span_rollup events, a registry snapshot carrying every subsystem
# section, a working `hivemall_tpu obs` render, and per-step tracing
# overhead within 5% of tracing disabled (min over alternating pairs).
python -m hivemall_tpu.obs.smoke || exit $?

# serve smoke (docs/SERVING.md): a checkpoint trained in-process is served
# over HTTP with dynamic micro-batching — concurrent predicts must
# coalesce (mean batch > 1), bit-match offline predict_proba on the same
# rows, stay under the p99 latency budget, and a newer checkpoint written
# mid-traffic must hot-reload without dropping in-flight requests.
# HIVEMALL_TPU_TSAN=1 runs it under the Eraser-style lockset race
# sanitizer (hivemall_tpu.testing.tsan): every registered serve/obs
# class's attribute writes are lockset-checked across the HTTP handler
# / dispatch / watch / warmup threads, and ANY write/write race fails
# the smoke (the latency budget relaxes — a sanitizer build is never a
# perf build).
# HIVEMALL_TPU_LEAKTRACK=1 additionally runs the FD/socket/thread leak
# census (hivemall_tpu.testing.leaktrack): a snapshot at smoke start
# must match the census after the full traffic+reload+drain+shutdown
# cycle — any tracked resource still alive fails the smoke with its
# creation stack appended to the JSONL artifact.
env HIVEMALL_TPU_TSAN=1 HIVEMALL_TPU_TSAN_LOG=artifacts/tsan_races.jsonl \
    HIVEMALL_TPU_LEAKTRACK=1 \
    HIVEMALL_TPU_LEAKTRACK_LOG=artifacts/leaktrack_census.jsonl \
    python -m hivemall_tpu.serve.smoke || exit $?

# evloop serve smoke (docs/SERVING.md "Serving planes"): the SAME
# acceptance surface on the epoll event-loop plane — selectors front
# end + inline batch assembly (serve/evloop.py) must coalesce,
# bit-match, hot-reload with zero drops, and pass the identical tsan
# lockset + leaktrack census gates (the loop thread owns all per-
# connection and assembler state; everything crossing threads goes
# through message queues, so ANY write/write race here is a real bug).
env HIVEMALL_TPU_TSAN=1 HIVEMALL_TPU_TSAN_LOG=artifacts/tsan_races.jsonl \
    HIVEMALL_TPU_LEAKTRACK=1 \
    HIVEMALL_TPU_LEAKTRACK_LOG=artifacts/leaktrack_census.jsonl \
    python -m hivemall_tpu.serve.smoke --plane evloop || exit $?

# retrieval smoke (docs/SERVING.md "Retrieval plane"): an MF factor
# bundle published through the weight arena serves /retrieve on BOTH
# planes — concurrent exact-tier top-k bit-matches the each_top_k
# oracle over the engine's own exact scores, the SRP-LSH candidate
# tier holds recall@10 >= 0.95 vs exact at the smoke catalog shape,
# a newly PROMOTED factor bundle hot-reloads mid-traffic with zero
# failed requests, HMR1 response frames decode to the JSON ids, and
# the retrieval obs section rides /snapshot + /metrics. Same tsan
# lockset + leaktrack census gates as the other serve smokes.
env HIVEMALL_TPU_TSAN=1 HIVEMALL_TPU_TSAN_LOG=artifacts/tsan_races.jsonl \
    HIVEMALL_TPU_LEAKTRACK=1 \
    HIVEMALL_TPU_LEAKTRACK_LOG=artifacts/leaktrack_census.jsonl \
    python -m hivemall_tpu.serve.retrieve_smoke || exit $?
env HIVEMALL_TPU_TSAN=1 HIVEMALL_TPU_TSAN_LOG=artifacts/tsan_races.jsonl \
    HIVEMALL_TPU_LEAKTRACK=1 \
    HIVEMALL_TPU_LEAKTRACK_LOG=artifacts/leaktrack_census.jsonl \
    python -m hivemall_tpu.serve.retrieve_smoke --plane evloop || exit $?

# fleet smoke (docs/SERVING.md "Fleet topology"): 2 replica PROCESSES
# behind the front-end router — concurrent routed predicts bit-match
# predict_proba and fan across both replicas; killing one replica under
# live traffic costs ZERO failed requests (router retry + manager
# respawn); a newer checkpoint rolls across the fleet one replica at a
# time with zero drops, converging every replica to the new step; the
# /slo burn-rate surface reports the traffic; and request tracing
# propagates END TO END — an x-hivemall-trace id is echoed with a
# per-hop latency breakdown that sums to the router-measured wall, and
# appears in spans exported from BOTH the router and the scoring
# replica processes via the router's merged /trace (the tracing-
# overhead floor itself stays pinned by the obs smoke above).
# The lockset sanitizer rides along here too: manager-side threads
# (health monitor, rolling reload, respawn, router accept/handlers,
# SLO sampler) gate on zero races in-process; replica subprocesses
# inherit the env and append any races to the shared artifact log.
env HIVEMALL_TPU_TSAN=1 HIVEMALL_TPU_TSAN_LOG=artifacts/tsan_races.jsonl \
    HIVEMALL_TPU_LEAKTRACK=1 \
    HIVEMALL_TPU_LEAKTRACK_LOG=artifacts/leaktrack_census.jsonl \
    python -m hivemall_tpu.serve.fleet_smoke || exit $?

# evloop fleet smoke: the same fleet acceptance surface with evloop
# replicas behind the evloop router front end — including the
# router->replica UDS fast path (every forward must stay on the unix
# socket; a TCP fallback fails the uds_fast_path check), the kill/
# respawn zero-drop guarantee and the rolling reload, under the same
# tsan + leaktrack gates (replica workers census their own sockets,
# including the UDS listener, on drain).
env HIVEMALL_TPU_TSAN=1 HIVEMALL_TPU_TSAN_LOG=artifacts/tsan_races.jsonl \
    HIVEMALL_TPU_LEAKTRACK=1 \
    HIVEMALL_TPU_LEAKTRACK_LOG=artifacts/leaktrack_census.jsonl \
    python -m hivemall_tpu.serve.fleet_smoke --plane evloop || exit $?

# promotion smoke (docs/RELIABILITY.md "Promotion and rollback"): gated
# model promotion over a 2-replica fleet under live traffic — a
# deliberately-poisoned candidate must be BLOCKED at the gate
# (quarantined with a .rejected marker, fleet untouched); a good
# candidate must promote through a 1-replica canary bake with zero
# failed requests; a synthetic latency regression injected into the
# canary cohort must AUTO-ROLL-BACK (pointer reverted, bundle
# quarantined, replicas restored) with zero failed requests; and the
# `promotion` section must be visible on /snapshot, /metrics,
# /promotion and the `hivemall_tpu obs` render.
python -m hivemall_tpu.serve.promote_smoke || exit $?

# weight-arena smoke (docs/PERFORMANCE.md "Weight arena + quantized
# scoring"): zero-copy quantized serving end to end — the bootstrap
# promotion must PUBLISH the arena sidecar, a 2-replica int8 fleet must
# serve off it with zero per-replica publishes while mapping the SAME
# inode (verified via /proc/<pid>/maps), per-replica host-RSS +
# arena-mapped-bytes gauges must be live on /healthz, /snapshot and the
# fleet section, quantized scores must stay inside the documented int8
# bound of offline f32, the router result cache must hit on a repeated
# body and be invalidated by the promotion-driven rolling reload, and
# the roll must converge both replicas onto the NEW arena with zero
# failed requests. tsan + leaktrack enabled like the other serve smokes
# (the mmap'd arena views must be released on replica drain — a leaked
# mapping fails the census).
env HIVEMALL_TPU_TSAN=1 HIVEMALL_TPU_TSAN_LOG=artifacts/tsan_races.jsonl \
    HIVEMALL_TPU_LEAKTRACK=1 \
    HIVEMALL_TPU_LEAKTRACK_LOG=artifacts/leaktrack_census.jsonl \
    python -m hivemall_tpu.serve.arena_smoke || exit $?

# retrain chaos smoke (docs/RELIABILITY.md "Autonomous retraining"):
# the closed train→validate→promote→rollback loop over a 2-replica
# fleet under live traffic — an injected label/covariate shift
# (testing/faults.LabelShiftSource) must drive retrain_wanted votes, a
# debounced trigger, a warm-start child retrain from the PROMOTED
# bundle over (base corpus ∪ replay buffer), a gate pass, a canary
# bake and a full roll (pointer advances, fleet converges) with ZERO
# failed requests; then a POISONED label join must be quarantined at
# the gate (.rejected marker) with the backoff cooldown holding — no
# retrain storm. tsan-enabled like the serve/fleet smokes; the JSON
# result summary lands in artifacts/.
env HIVEMALL_TPU_TSAN=1 HIVEMALL_TPU_TSAN_LOG=artifacts/tsan_races.jsonl \
    HIVEMALL_TPU_LEAKTRACK=1 \
    HIVEMALL_TPU_LEAKTRACK_LOG=artifacts/leaktrack_census.jsonl \
    python -m hivemall_tpu.serve.retrain_smoke \
    --artifact artifacts/retrain_smoke.json || exit $?

# shard-cache smoke (docs/PERFORMANCE.md "Shard cache"): a cold fit must
# build the packed cache, a fresh-trainer warm fit must bit-match its loss
# trajectory with ZERO live prep, and the Parquet decode cache must keep
# serving the original bytes after the source shard's content is mutated
# in place (mtime/size preserved) — proof warm epochs never re-read the
# source.
python -m hivemall_tpu.io.shard_cache --smoke || exit $?

# bulk-predict smoke (ISSUE 17, docs/PERFORMANCE.md "Bulk scoring"): a
# 2-worker-process bulk job over a multi-shard Parquet dir (ragged tail +
# an empty shard) must BIT-match the offline predict_proba path at f32,
# stay inside score_error_bound()/4 on the int8 arena twin, and — under
# tsan + the leaktrack census — leave ZERO leaked fds/threads/mmaps after
# the worker pool drains.
env HIVEMALL_TPU_TSAN=1 HIVEMALL_TPU_TSAN_LOG=artifacts/tsan_races.jsonl \
    HIVEMALL_TPU_LEAKTRACK=1 \
    HIVEMALL_TPU_LEAKTRACK_LOG=artifacts/leaktrack_census.jsonl \
    python -m hivemall_tpu.io.bulk --smoke || exit $?

# native-canonicalizer CI guard: the C++ canonicalizer is the DEFAULT in
# every prep path (fit / fit_stream / serve-side scoring), with the numpy
# twin as the fallback — g++ is part of the installation, so the
# bit-equality parity test must actually RUN (a silent skip would unpin
# the default path).
python -m pytest \
    tests/test_native.py::test_canonicalize_native_matches_numpy -q \
    2>&1 | grep -q "1 passed" || {
    echo "FAIL: canonicalizer parity test skipped/failed (the native" \
         "library did not build?)"; exit 1; }
