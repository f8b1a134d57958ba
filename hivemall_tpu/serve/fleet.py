"""Replica manager — a fleet of serve processes behind one router.

Scale-out serving (docs/SERVING.md "Fleet topology"): the single
PredictServer tops out at one process's parse+dispatch throughput, so the
fleet runs ONE ENGINE PER PROCESS (its own MicroBatcher, its own warmed
compile caches, its own GIL) — on a multi-device host, one replica per
accelerator via per-replica env overrides. All replicas load from the
same watched checkpoint dir; a front-end RouterServer fans /predict
across them.

Lifecycle, all manager-owned:

- **spawn**: each replica is a fresh interpreter running this module's
  worker entry (``python -m hivemall_tpu.serve.fleet --worker <json>``),
  binding an ephemeral loopback port and printing one ready line; the
  manager registers it with the router as NOT ready and lets the health
  monitor flip it once ``/healthz`` reports warmup complete (engines
  warm in the background, so a replica is probe-able while cold).
- **health monitor**: polls every replica's ``/healthz``; readiness
  drives the router's gate; a dead process is respawned and the dead
  handle removed from the router (which has usually already shed to
  survivors at the first failed forward).
- **rolling hot reload**: the manager — not each replica — watches the
  checkpoint dir. A newer bundle is digest-verified ONCE
  (io.checkpoint.verify_bundle), then rolled across replicas ONE AT A
  TIME via each replica's ``/reload {"path": ...}``: every replica
  loads the SAME verified bundle (no step skew from racing polls), the
  in-replica atomic swap keeps it serving its old model mid-load, and
  sequencing means fleet capacity never drops. A corrupt bundle is
  rejected at the manager: zero replica churn.
- **gated promotion + canary rollout** (``promote=True`` /
  ``serve --promote``, docs/RELIABILITY.md "Promotion and rollback"):
  instead of newest-wins, the fleet follows the checkpoint dir's atomic
  ``PROMOTED`` pointer. The manager gates each new candidate
  (serve.promote.PromotionGate: holdout/shadow guardrails), flips the
  pointer with state "canary" on pass, rolls the candidate onto a
  ``canary_fraction`` cohort of replicas, and BAKES: each watch tick
  diffs the canary cohort's SLO totals (error rate, mean latency,
  score mean — off the same /healthz ``slo`` sections the SLO engine
  sums) against the stable cohort's (serve.promote.CanaryBake). A
  clean bake completes the roll and finalizes the pointer; a
  regression AUTO-ROLLS-BACK — the bundle is quarantined with a
  ``.rejected`` marker (never retried), the pointer reverts to the
  prior entry, and the canary cohort reloads the previous model. A
  manager SIGKILLed mid-canary or mid-rollback recovers a consistent
  fleet from the pointer manifest alone on restart: state "canary"
  re-bakes (or completes the rollback when the candidate is already
  quarantined), state "serving" converges every straggler replica onto
  the pointer bundle.
- **graceful stop**: SIGTERM; workers drain their batcher (accepted
  requests complete) before exiting; SIGKILL only after a timeout.

``Fleet`` bundles manager + router into one start()/stop() — the
``serve --replicas N`` CLI surface and what the fleet smoke drives.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional

from ..obs.flight import (ENV_DIR as _FLIGHT_DIR_ENV,
                          ENV_LABEL as _FLIGHT_LABEL_ENV,
                          FS, configure_flight, emit_postmortem, get_flight)
from ..utils.device import child_device_envs, child_env, names_device
from .router import RouterServer

__all__ = ["ReplicaManager", "Fleet"]


class _Replica:
    """Manager-side record of one worker process."""

    def __init__(self, rid: str, proc: subprocess.Popen, slot: int):
        self.rid = rid
        self.proc = proc
        self.slot = slot               # resource slot (core/device pin) —
        self.port: Optional[int] = None   # a respawn must inherit it
        self.uds: Optional[str] = None    # unix socket (evloop fast path)
        self.model_step: Optional[int] = None
        self.ready = False
        self.last_health: dict = {}

    def base(self) -> str:
        return f"http://127.0.0.1:{self.port}"


class ReplicaManager:
    """Spawn/heal/roll N serve replicas; membership flows to a router."""

    def __init__(self, algo: str, options: str = "", *,
                 checkpoint_dir: Optional[str] = None,
                 bundle: Optional[str] = None,
                 replicas: int = 2,
                 router: Optional[RouterServer] = None,
                 env: Optional[dict] = None,
                 per_replica_env: Optional[List[dict]] = None,
                 serve_kwargs: Optional[dict] = None,
                 pin_cpus: bool = False,
                 plane: str = "threaded",
                 uds: Optional[bool] = None,
                 spawn_timeout: float = 180.0,
                 health_interval: float = 0.5,
                 watch_interval: float = 2.0,
                 slo=None,
                 gate=None,
                 promote: bool = False,
                 canary_fraction: float = 0.25,
                 bake_opts: Optional[dict] = None,
                 retrain=None,
                 flight_dir: Optional[str] = None):
        if not checkpoint_dir and not bundle:
            raise ValueError("fleet needs checkpoint_dir=... or bundle=...")
        self.algo = algo
        self.options = options
        self.checkpoint_dir = checkpoint_dir
        self.bundle = bundle
        self.n_replicas = int(replicas)
        self.router = router
        self.env = env
        # one process per chip (utils/device.py): every replica's device
        # is STATED, never inherited by accident. A caller's overlay that
        # names one wins (env={"JAX_PLATFORMS": "cpu"} = host-CPU replicas
        # on purpose, per_replica_env=[chip_env(i), ...] = explicit
        # chips); otherwise slot i gets chip i, and a host that cannot
        # give every replica a chip makes the launcher refuse here with a
        # message instead of spawning replicas that die in backend init
        if per_replica_env is None and not names_device(env):
            per_replica_env = child_device_envs(self.n_replicas)
        self.per_replica_env = per_replica_env or []
        # one-core-per-replica pinning (the CPU-host analog of
        # one-replica-per-accelerator): replica in slot i is affined to
        # core i%N, so each replica's whole thread set — Python AND the
        # XLA host threadpool — owns exactly one core and N replicas
        # scale across N cores instead of every replica's XLA pool
        # thrashing all of them
        self.pin_cpus = bool(pin_cpus)
        # serving plane (docs/SERVING.md "Serving planes"): threaded =
        # thread-per-connection + MicroBatcher; evloop = epoll front end
        # + inline assembly. Replicas AND router front end must agree.
        if plane not in ("threaded", "evloop"):
            raise ValueError(f"unknown serve plane {plane!r}")
        self.plane = plane
        # UDS fast path: evloop replicas also listen on a unix socket
        # the co-located router prefers over TCP (default on for evloop;
        # explicit uds=False keeps it TCP-only, e.g. a remote router)
        self.uds = (plane == "evloop") if uds is None else bool(uds)
        self._uds_dir: Optional[str] = (
            tempfile.mkdtemp(prefix="hmt-uds-")
            if self.uds and self.plane == "evloop" else None)
        self.serve_kwargs = dict(serve_kwargs or {})
        self.spawn_timeout = float(spawn_timeout)
        self.health_interval = float(health_interval)
        self.watch_interval = float(watch_interval)
        from ..catalog import lookup
        self._name = lookup(algo).resolve().NAME
        self._replicas: Dict[str, _Replica] = {}
        self._lock = threading.Lock()
        self._next_rid = 0
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._respawning: set = set()    # slots with a respawn in flight
        # counters (the cached `fleet` obs registry section)
        self.respawns = 0
        self.rolls = 0
        self.roll_failures = 0
        self.rejected_bundles = 0
        self.fleet_step: Optional[int] = None
        self.last_error: Optional[str] = None
        # fleet SLO engine (obs.slo): every health tick sums the
        # replicas' cumulative /healthz `slo` totals (latency histogram,
        # request/error/shed counters, score moments) into one
        # fleet-wide sample — the manager IS the sampler
        self.slo = slo
        self._slo_seen: Dict[str, int] = {}   # rid -> last requests seen
        # gated promotion (serve.promote): follow the PROMOTED pointer
        # instead of newest-wins; a gate makes the manager evaluate new
        # candidates itself, otherwise an external `hivemall_tpu promote`
        # flips the pointer and this manager only converges/canaries
        self.promote = bool(promote or gate is not None)
        self.gate = gate
        self.canary_fraction = float(canary_fraction)
        self.bake_opts = dict(bake_opts or {})
        self._canary: Optional[dict] = None   # {"step","path","bake"}
        self._bake_inject = None   # test hook: fn(canary_totals)->totals
        # drift-driven retrain autopilot (serve.retrain): the controller
        # rides THIS manager's watch loop (one tick cadence, no second
        # daemon) and produces candidates the promotion lifecycle above
        # gates/canaries/rolls back exactly like any other candidate
        self.retrain = retrain
        self._last_manifest: Optional[dict] = None   # cached for obs
        self.promotions = 0
        self.canary_rollbacks = 0
        self.quarantined = 0
        # black-box flight recorder (obs.flight): ALWAYS on for a
        # checkpoint-dir fleet — the whole point is recording the run
        # nobody expected to crash. Explicit flight_dir wins, then the
        # env (an operator recording a whole pipeline into one dir),
        # then <checkpoint_dir>/flight; a pinned-bundle fleet with no
        # env stays dark. Every replica spawn inherits the dir plus a
        # per-SLOT label, so a respawn writes a fresh ring (pid in the
        # name) and the victim's ring survives for the post-mortem.
        fd = flight_dir
        if fd is None:
            fd = os.environ.get(_FLIGHT_DIR_ENV) or None
            if (fd is None or fd == "0") and checkpoint_dir:
                fd = os.path.join(checkpoint_dir, "flight")
        self.flight_dir = fd if fd and fd != "0" else None
        self._flight = (configure_flight(self.flight_dir, label="router")
                        if self.flight_dir else get_flight())
        self._register_obs()

    # -- spawning ------------------------------------------------------------
    def _spec(self, slot: int) -> dict:
        spec = {"algo": self.algo, "options": self.options,
                "checkpoint_dir": self.checkpoint_dir,
                "bundle": self.bundle, "host": "127.0.0.1", "port": 0}
        if self.promote:
            # replicas BOOT from the pointer too: a respawn mid-rollback
            # must come up on the promoted model, not the quarantined
            # newest step (reload sequencing stays manager-owned)
            spec["follow"] = "promoted"
        if self.pin_cpus:
            n = os.cpu_count() or 1
            spec["cpu_affinity"] = [slot % n]
        if self.plane != "threaded":
            spec["plane"] = self.plane
        if self._uds_dir:
            # per-SLOT socket path: a respawn inherits its predecessor's
            # path (the server unlinks the stale file before bind)
            spec["uds"] = os.path.join(self._uds_dir, f"s{slot}.sock")
        spec.update(self.serve_kwargs)
        return spec

    def _spawn(self, slot: int) -> _Replica:
        with self._lock:                   # concurrent slot respawns
            rid = f"r{self._next_rid}"
            self._next_rid += 1
        env = dict(self.env or {})
        if slot < len(self.per_replica_env):
            env.update(self.per_replica_env[slot])
        if self.flight_dir:
            # per-slot label: a respawned slot records under the same
            # label with a new pid — the dead ring stays readable
            env.setdefault(_FLIGHT_DIR_ENV, self.flight_dir)
            env.setdefault(_FLIGHT_LABEL_ENV, f"replica-s{slot}")
        proc = subprocess.Popen(
            [sys.executable, "-m", "hivemall_tpu.serve.fleet", "--worker",
             json.dumps(self._spec(slot))],
            stdout=subprocess.PIPE, stderr=None, text=True,
            env=child_env(env),
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))
        return _Replica(rid, proc, slot)

    def _wait_ready_line(self, r: _Replica, deadline: float) -> None:
        """Read the worker's single ready line (its bound port) with a
        hard deadline — a worker that hangs before binding (e.g. a wedged
        backend init) must fail the spawn, not block the manager. The
        worker warms up in the background AFTER this, so N replicas
        compile concurrently and the health monitor gates admission."""
        got: list = []

        def read():
            try:
                got.append(r.proc.stdout.readline())
            except Exception:            # noqa: BLE001 — pipe teardown
                pass

        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(timeout=max(0.1, deadline - time.monotonic()))
        if not got or not got[0].strip():
            if r.proc.poll() is not None:
                raise RuntimeError(
                    f"replica {r.rid} exited rc={r.proc.returncode} "
                    f"before binding")
            raise RuntimeError(f"replica {r.rid} never reported its port "
                               f"within the spawn timeout")
        msg = json.loads(got[0])
        r.port = int(msg["port"])
        r.uds = msg.get("uds")
        r.model_step = msg.get("model_step")
        # keep draining worker stdout so a chatty replica can't fill the
        # pipe and wedge itself
        threading.Thread(target=self._drain, args=(r,), daemon=True).start()

    @staticmethod
    def _drain(r: _Replica) -> None:
        try:
            for _ in r.proc.stdout:
                pass
        except Exception:                # noqa: BLE001 — pipe teardown
            pass

    def start(self) -> "ReplicaManager":
        deadline = time.monotonic() + self.spawn_timeout
        rs = [self._spawn(i) for i in range(self.n_replicas)]
        try:
            for r in rs:
                self._wait_ready_line(r, deadline)
        except Exception:  # noqa: BLE001 — cleanup-and-reraise: any boot
            for r in rs:   # failure must kill the PARTIAL fleet before
                if r.proc.poll() is None:   # surfacing (no orphans)
                    r.proc.kill()
            raise
        with self._lock:
            for r in rs:
                self._replicas[r.rid] = r
                if self.router is not None:
                    self.router.add_replica(r.rid, "127.0.0.1", r.port,
                                            uds=r.uds)
        for target, name in ((self._monitor, "fleet-health"),
                             (self._watch, "fleet-watch")):
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def wait_ready(self, n: Optional[int] = None,
                   timeout: float = 180.0) -> bool:
        """Block until ``n`` (default: all) replicas report ready."""
        want = self.n_replicas if n is None else int(n)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if sum(1 for r in self.replicas() if r.ready) >= want:
                return True
            time.sleep(0.05)
        return False

    def replicas(self) -> List[_Replica]:
        with self._lock:
            return list(self._replicas.values())

    # -- health monitor + respawn --------------------------------------------
    def _probe(self, r: _Replica) -> Optional[dict]:
        try:
            with urllib.request.urlopen(r.base() + "/healthz",
                                        timeout=2.0) as resp:
                return json.loads(resp.read())
        except urllib.error.HTTPError as e:
            try:
                return json.loads(e.read())    # 503 while warming: a real
            except Exception:                  # noqa: BLE001 — health body
                return None
            finally:
                e.close()    # the error owns the probe socket — without
                #              this every handled 503 leaks one fd (GC12)
        except Exception:                      # noqa: BLE001 — unreachable
            return None

    def _monitor(self) -> None:
        while not self._stop.wait(self.health_interval):
            for r in self.replicas():
                if r.proc.poll() is not None:
                    # the replacement inherits the DEAD replica's resource
                    # slot (its core/device pin) — dict position would
                    # drift after churn and double-book a live replica's
                    # core/device
                    self._replace(r.slot, r)
                    continue
                h = self._probe(r)
                if h is None:
                    continue               # transient; process still alive
                r.last_health = h
                r.ready = bool(h.get("ready"))
                r.model_step = h.get("model_step", r.model_step)
                if self.router is not None:
                    self.router.set_ready(r.rid, r.ready)
            if self.slo is not None:
                try:
                    self.slo.sample(self._slo_totals())
                except Exception as e:     # noqa: BLE001 — obs must never
                    self.last_error = f"slo: {type(e).__name__}: {e}"

    def _slo_totals(self) -> dict:
        """Sum every live replica's cumulative /healthz ``slo`` section
        into one fleet-wide totals dict (histogram buckets add bucket-
        wise: all replicas share the default bounds). A replica respawn
        resets its share; the engine clamps window diffs at zero, and
        the tick is flagged ``reset`` so the drift detector skips it —
        a PARTIAL reset masked by the other replicas' growth would
        otherwise feed the changefinder a garbage interval mean exactly
        during crash recovery."""
        agg: dict = {"requests": 0, "errors": 0, "shed": 0, "expired": 0,
                     "score_sum": 0.0, "score_sumsq": 0.0, "score_n": 0}
        buckets = None
        lat_sum, lat_count = 0.0, 0
        seen = {}
        for r in self.replicas():
            t = (r.last_health or {}).get("slo")
            if not isinstance(t, dict):
                continue
            for k in ("requests", "errors", "shed", "expired", "score_n"):
                agg[k] += int(t.get(k) or 0)
            for k in ("score_sum", "score_sumsq"):
                agg[k] += float(t.get(k) or 0.0)
            lat = t.get("latency") or {}
            lat_sum += float(lat.get("sum") or 0.0)
            lat_count += int(lat.get("count") or 0)
            bs = lat.get("buckets") or []
            if buckets is None:
                buckets = [[b, int(c)] for b, c in bs]
            elif len(bs) == len(buckets):
                for i, (_, c) in enumerate(bs):
                    buckets[i][1] += int(c)
            seen[r.rid] = int(t.get("requests") or 0)
        agg["latency"] = {"buckets": buckets or [], "sum": lat_sum,
                          "count": lat_count}
        # reset detection: a rid vanished (respawned under a new rid) or
        # went backwards since the last tick — this interval's deltas
        # mix pre- and post-reset history
        prev = self._slo_seen
        agg["reset"] = any(rid not in seen or seen[rid] < n
                           for rid, n in prev.items())
        self._slo_seen = seen
        return agg

    def _replace(self, slot: int, dead: _Replica) -> None:
        """Retire a crashed replica and respawn its slot on a DEDICATED
        thread — the monitor must keep polling the survivors' health
        while the replacement boots (a wedged respawn would otherwise
        freeze readiness updates fleet-wide: a survivor gated out by one
        transient forward error could never be revived). The router has
        already shed to the survivors (first failed forward marks the
        dead replica unready)."""
        with self._lock:
            if self._stop.is_set() or dead.rid not in self._replicas:
                return
            del self._replicas[dead.rid]
            if slot in self._respawning:   # one respawn per slot
                return
            self._respawning.add(slot)
        if self.router is not None:
            self.router.remove_replica(dead.rid)
        self.respawns += 1
        fl = self._flight
        if fl.enabled:
            fl.record("fleet.respawn",
                      f"slot={slot}{FS}rid={dead.rid}{FS}"
                      f"pid={dead.proc.pid}{FS}rc={dead.proc.returncode}")
        if self.flight_dir:
            # the victim's ring (pid in its name) is already durable on
            # disk; merge the fleet's rings into postmortem.txt NOW so
            # the death's timeline exists even if nobody ever runs
            # `hivemall_tpu obs postmortem` — off-thread, the monitor
            # must keep polling survivors while the merge reads files
            threading.Thread(target=emit_postmortem,
                             args=(self.flight_dir,),
                             name="fleet-postmortem", daemon=True).start()
        threading.Thread(target=self._respawn_slot, args=(slot,),
                         name=f"fleet-respawn-{slot}", daemon=True).start()

    def _respawn_slot(self, slot: int) -> None:
        """Respawn ``slot`` until it sticks: a transient spawn failure
        (fork pressure, slow boot past the timeout) retries rather than
        permanently shrinking the fleet. A stop() racing the spawn kills
        the fresh worker instead of orphaning it."""
        try:
            while not self._stop.is_set():
                r = None
                try:
                    r = self._spawn(slot)
                    self._wait_ready_line(
                        r, time.monotonic() + self.spawn_timeout)
                except Exception as e:     # noqa: BLE001 — retry the slot
                    self.last_error = f"respawn: {type(e).__name__}: {e}"
                    if r is not None and r.proc.poll() is None:
                        r.proc.kill()      # half-spawned worker reaped
                    if self._stop.wait(1.0):
                        return
                    continue
                with self._lock:
                    if self._stop.is_set():
                        # stop() already terminated + cleared the fleet;
                        # this late arrival must not become an orphan
                        r.proc.terminate()
                        return
                    self._replicas[r.rid] = r
                if self.router is not None:
                    self.router.add_replica(r.rid, "127.0.0.1", r.port,
                                            uds=r.uds)
                return
        finally:
            self._respawning.discard(slot)

    # -- fleet-wide rolling hot reload ---------------------------------------
    def _watch(self) -> None:
        if not self.checkpoint_dir:
            return
        while not self._stop.wait(self.watch_interval):
            try:
                self.check_and_roll()
            except Exception as e:         # noqa: BLE001 — watcher survives
                self.last_error = f"watch: {type(e).__name__}: {e}"
            if self.retrain is not None:
                try:
                    self.retrain.tick()
                except Exception as e:     # noqa: BLE001 — the autopilot
                    self.last_error = \
                        f"retrain: {type(e).__name__}: {e}"

    def check_and_roll(self) -> bool:
        """One watch tick. Newest-wins mode: is there a newer verified
        bundle? Roll it. Promote mode: drive the gate → canary → bake →
        complete/rollback lifecycle off the ``PROMOTED`` pointer instead
        (:meth:`_promotion_tick`). Returns True when a full fleet roll
        completed this tick."""
        from ..io.checkpoint import newest_bundle, verify_bundle
        if not self.checkpoint_dir:
            return False
        if self.promote:
            return self._promotion_tick()
        nb = newest_bundle(self.checkpoint_dir, self._name)
        if nb is None:
            return False
        step, path = nb
        cur = self.fleet_step
        if cur is None:
            cur = min((r.model_step or 0) for r in self.replicas()) \
                if self.replicas() else 0
            self.fleet_step = cur
        if step <= cur:
            return False
        try:
            verify_bundle(path, self._name)   # ONCE, at the manager
        except (ValueError, KeyError, OSError) as e:
            self.rejected_bundles += 1
            self.last_error = f"bundle {path}: {e}"
            return False
        self.roll(path, step)
        return True

    def _reload_replica(self, r: _Replica, path: str, step: int) -> bool:
        """One replica /reload to an explicit bundle. The in-replica
        atomic swap keeps it serving its old model mid-load. Failure is
        counted and leaves the replica on its old (complete) model."""
        try:
            body = json.dumps({"path": path}).encode()
            req = urllib.request.Request(
                r.base() + "/reload", body,
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120.0) as resp:
                out = json.loads(resp.read())
            if not out.get("reloaded"):
                raise RuntimeError(
                    f"replica {r.rid} refused bundle: {out}")
            r.model_step = out.get("model_step", step)
            # ANY replica model change invalidates the router's result
            # cache — a cached score must never outlive the model that
            # produced it (mid-roll the fleet is intentionally mixed;
            # per-reload invalidation keeps the cache honest throughout)
            if self.router is not None:
                self.router.invalidate_result_cache()
            return True
        except Exception as e:             # noqa: BLE001 — stop the roll,
            # keep serving: every replica still runs a complete model
            # (old or new step); the next watch tick retries — by then
            # the monitor has respawned whatever replica broke it
            self.roll_failures += 1
            self.last_error = f"roll {r.rid}: {type(e).__name__}: {e}"
            return False

    def roll(self, path: str, step: int) -> None:
        """Roll one verified bundle across the fleet, one replica at a
        time. Sequencing is about blast radius — a bundle that loads at
        the manager's verify but fails in a replica stops the roll at
        one replica, not N."""
        for r in self.replicas():
            if self._stop.is_set():
                return
            if not self._reload_replica(r, path, step):
                return
        self.fleet_step = step
        self.rolls += 1
        fl = self._flight
        if fl.enabled:
            fl.record("fleet.roll", f"step={step}{FS}"
                      f"bundle={os.path.basename(path)}")

    # -- gated promotion: canary rollout + auto-rollback ---------------------
    def _promotion_tick(self) -> bool:
        """One promote-mode watch tick, driven ENTIRELY by the pointer
        manifest + replica steps — which is what makes recovery free: a
        manager restarted after SIGKILL lands in whichever branch the
        on-disk state says, with no in-memory carryover needed."""
        from ..io.checkpoint import is_rejected, read_promoted
        if self._canary is not None:
            return self._bake_tick()
        m = self._last_manifest = read_promoted(self.checkpoint_dir)
        if m is not None:
            cur = m["current"]
            path = os.path.join(self.checkpoint_dir, str(cur["bundle"]))
            step = int(cur.get("step") or 0)
            if m.get("state") == "canary":
                if is_rejected(path):
                    # a rollback died between the quarantine marker and
                    # the pointer flip: complete it
                    return self._finish_rollback(
                        "recovered: quarantined candidate still "
                        "pointed at")
                # mid-canary restart (or an external promote --canary):
                # (re)start the bake — a fresh window, never a blind
                # completion of a bake nobody watched
                self._start_canary(path, step)
                return False
            # state "serving": converge stragglers (restart recovery,
            # an external promote, the tail of a completed rollback)
            if os.path.exists(path):
                if self._converge(path, step):
                    return True
                if any(r.model_step != step for r in self.replicas()):
                    # a reload failed mid-converge: finish before gating
                    # anything new — never canary onto a mixed fleet
                    return False
        if self.gate is not None:
            return self._gate_tick()
        return False

    def _gate_tick(self) -> bool:
        """Gate the newest unexamined candidate; on pass flip the pointer
        and start a canary (or promote outright when there is nothing to
        compare against); on fail quarantine it."""
        from ..io.checkpoint import (bundle_step, is_rejected, list_bundles,
                                     promote_bundle, promoted_bundle,
                                     reject_bundle)
        from ..utils.metrics import get_stream
        from .promote import _gate_summary
        pb = promoted_bundle(self.checkpoint_dir, self._name)
        promoted_step = pb[0] if pb else -1
        cand = None
        for path in list_bundles(self.checkpoint_dir, self._name):
            step = bundle_step(path)
            if step is None or step <= promoted_step:
                break                     # newest-first list
            if is_rejected(path):
                continue
            cand = (step, path)
            break
        if cand is None:
            return False
        step, path = cand
        report = self.gate.evaluate(path, pb[1] if pb else None)
        if report["verdict"] != "pass":
            reject_bundle(path, "; ".join(report["reasons"]))
            self.quarantined += 1
            fl = self._flight
            if fl.enabled:
                fl.record("promote.quarantine", f"step={step}")
            return False
        n = len(self.replicas())
        if pb is None or n <= 1:
            # bootstrap (no baseline to canary against) or a one-replica
            # fleet (the canary WOULD BE the whole fleet): the gate is
            # the only protection — promote straight to serving
            self._last_manifest = promote_bundle(
                self.checkpoint_dir, path, gate=_gate_summary(report),
                state="serving")
            get_stream().emit("promotion", bundle=os.path.basename(path),
                              step=step, state="serving")
            self.promotions += 1
            fl = self._flight
            if fl.enabled:
                fl.record("promote.serving", f"step={step}")
            self._converge(path, step)
            return True
        self._last_manifest = promote_bundle(
            self.checkpoint_dir, path, gate=_gate_summary(report),
            state="canary")
        get_stream().emit("promotion", bundle=os.path.basename(path),
                          step=step, state="canary")
        self._start_canary(path, step)
        return False

    def _cohorts(self, step: int):
        """Split replicas by serving step: (canary cohort = on the
        candidate step, stable cohort = everything else). Membership is
        derived, not remembered — a canary replica that crashed and
        respawned from the pointer rejoins its cohort automatically."""
        canary, stable = [], []
        for r in self.replicas():
            (canary if r.model_step == step else stable).append(r)
        return canary, stable

    def _cohort_totals(self, rs: List[_Replica]) -> dict:
        """Sum a cohort's cumulative /healthz ``slo`` totals (the
        CanaryBake input shape)."""
        agg: dict = {"requests": 0, "errors": 0, "shed": 0, "expired": 0,
                     "score_sum": 0.0, "score_sumsq": 0.0, "score_n": 0,
                     "latency": {"sum": 0.0, "count": 0}}
        for r in rs:
            t = (r.last_health or {}).get("slo")
            if not isinstance(t, dict):
                continue
            for k in ("requests", "errors", "shed", "expired", "score_n"):
                agg[k] += int(t.get(k) or 0)
            for k in ("score_sum", "score_sumsq"):
                agg[k] += float(t.get(k) or 0.0)
            lat = t.get("latency") or {}
            agg["latency"]["sum"] += float(lat.get("sum") or 0.0)
            agg["latency"]["count"] += int(lat.get("count") or 0)
        return agg

    def _refresh_cohort_health(self, rs: List[_Replica]) -> None:
        """Fresh /healthz per cohort member — bake verdicts must compare
        NOW vs NOW, not whatever the monitor's last tick cached."""
        for r in rs:
            h = self._probe(r)
            if h is not None:
                r.last_health = h

    def _start_canary(self, path: str, step: int) -> bool:
        """Roll the candidate onto the canary cohort and open the bake
        window. Returns True when the bake started (False = a cohort
        reload failed; the next tick retries from the manifest)."""
        from .promote import CanaryBake
        rs = self.replicas()
        if not rs:
            return False
        k = max(1, int(round(self.canary_fraction * len(rs))))
        if len(rs) > 1:
            k = min(k, len(rs) - 1)       # keep a stable cohort to
        need = k - sum(1 for r in rs      # compare against
                       if r.model_step == step)
        for r in rs:
            if need <= 0:
                break
            if self._stop.is_set() or r.model_step == step:
                continue
            if not self._reload_replica(r, path, step):
                return False
            need -= 1
        canary_rs, stable_rs = self._cohorts(step)
        self._refresh_cohort_health(canary_rs + stable_rs)
        bake = CanaryBake(**self.bake_opts)
        bake.start(self._cohort_totals(canary_rs),
                   self._cohort_totals(stable_rs))
        self._canary = {"step": step, "path": path, "bake": bake}
        fl = self._flight
        if fl.enabled:
            fl.record("promote.canary",
                      f"step={step}{FS}cohort={len(canary_rs)}")
        if self.router is not None:
            # a result-cache hit skips replica placement entirely — it
            # would starve the canary cohort of the comparable traffic
            # the bake diffs, so the cache sits out the bake
            self.router.set_result_cache_bypass(True)
        return True

    def _bake_tick(self) -> bool:
        """One bake observation: diff both cohorts' totals since the
        window opened; complete the roll on pass, auto-rollback on fail."""
        c = self._canary
        canary_rs, stable_rs = self._cohorts(c["step"])
        if not canary_rs:
            # every canary replica died/reverted: restart from manifest
            self._canary = None
            if self.router is not None:
                self.router.set_result_cache_bypass(False)
            return False
        self._refresh_cohort_health(canary_rs + stable_rs)
        ct = self._cohort_totals(canary_rs)
        if self._bake_inject is not None:   # fault injection (testing/
            ct = self._bake_inject(ct)      # faults.py): synthetic canary
        st = self._cohort_totals(stable_rs)  # latency/error regression
        verdict = c["bake"].update(ct, st)
        if verdict is None:
            return False
        if verdict == "pass":
            return self._complete_canary()
        self._rollback(verdict)
        return False

    def _complete_canary(self) -> bool:
        """Clean bake: roll the candidate onto the stable cohort and
        finalize the pointer."""
        from ..io.checkpoint import finalize_promotion
        from ..utils.metrics import get_stream
        c = self._canary
        for r in self.replicas():
            if self._stop.is_set():
                return False
            if r.model_step == c["step"]:
                continue
            if not self._reload_replica(r, c["path"], c["step"]):
                return False              # _canary stays; next tick retries
        self._last_manifest = finalize_promotion(self.checkpoint_dir)
        self.fleet_step = c["step"]
        self.rolls += 1
        self.promotions += 1
        get_stream().emit("promotion", bundle=os.path.basename(c["path"]),
                          step=c["step"], state="serving")
        fl = self._flight
        if fl.enabled:
            fl.record("promote.serving", f"step={c['step']}")
        self._canary = None
        if self.router is not None:
            self.router.set_result_cache_bypass(False)
        return True

    def _rollback(self, reason: str) -> None:
        """Failed bake: quarantine the candidate FIRST (a crash between
        the marker and the pointer flip recovers as a rollback, never as
        a re-promotion), then revert the pointer and the cohort."""
        from ..io.checkpoint import reject_bundle
        c = self._canary
        reject_bundle(c["path"], reason)
        self.quarantined += 1
        self._canary = None
        if self.router is not None:
            self.router.set_result_cache_bypass(False)
        self._finish_rollback(reason, bundle=os.path.basename(c["path"]),
                              step=c["step"])

    def _finish_rollback(self, reason: str, bundle: Optional[str] = None,
                         step: Optional[int] = None) -> bool:
        """Revert the pointer to the prior entry and converge every
        replica still on the quarantined model back onto it."""
        from ..io.checkpoint import (finalize_promotion, promoted_bundle,
                                     rollback_promoted)
        from ..utils.metrics import get_stream
        m = rollback_promoted(self.checkpoint_dir, reason)
        if m is None:
            # nothing older to roll back to (no history) — unreachable
            # through the normal flow (bootstrap never canaries); keep
            # serving what we have rather than wedging the watch loop
            self.last_error = f"rollback with no history: {reason}"
            self._last_manifest = finalize_promotion(self.checkpoint_dir)
            return False
        self._last_manifest = m
        self.canary_rollbacks += 1
        get_stream().emit("promotion_rollback", bundle=bundle, step=step,
                          reason=reason)
        fl = self._flight
        if fl.enabled:
            fl.record("promote.rollback",
                      f"step={step}{FS}reason={reason[:60]}")
        pb = promoted_bundle(self.checkpoint_dir, self._name)
        if pb is not None:
            self._converge(pb[1], pb[0])
        return True

    def _converge(self, path: str, step: int) -> bool:
        """Reload every replica NOT serving ``step`` onto ``path`` (one
        at a time, capacity never drops). Returns True when at least one
        replica moved and the whole fleet now agrees."""
        changed = False
        for r in self.replicas():
            if self._stop.is_set():
                return False
            if r.model_step == step:
                continue
            if not self._reload_replica(r, path, step):
                return False              # next watch tick retries
            changed = True
        if self.fleet_step != step:
            self.fleet_step = step
        return changed

    # -- obs -----------------------------------------------------------------
    def obs_section(self) -> dict:
        rs = self.replicas()
        d = {
            "replicas": len(rs),
            "ready": sum(1 for r in rs if r.ready),
            "respawns": self.respawns,
            "rolls": self.rolls,
            "roll_failures": self.roll_failures,
            "rejected_bundles": self.rejected_bundles,
            "fleet_step": self.fleet_step,
            "model_steps": {r.rid: r.model_step for r in rs},
            # where each replica scores, as its own /healthz states it
            "replica_platforms": {
                r.rid: (r.last_health or {}).get("platform")
                for r in rs},
            # per-replica memory gauges off the cached health polls
            # (docs/PERFORMANCE.md "Weight arena + quantized scoring"):
            # N replicas each reporting the same arena_mapped_bytes
            # while host RSS stays flat is the shared-pages evidence
            "replica_rss_bytes": {
                r.rid: (r.last_health or {}).get("host_rss_bytes")
                for r in rs},
            "arena_mapped_bytes": {
                r.rid: (r.last_health or {}).get("arena_mapped_bytes")
                for r in rs},
        }
        if self.last_error:
            d["last_error"] = self.last_error
        return d

    def promotion_section(self) -> dict:
        """The ``promotion`` obs registry section (promote mode): pointer
        state off the manifest cached by the watch tick (no filesystem
        access on the scrape path), gate verdict counters, live canary
        state, rollback count, and the SLO engine's ``retrain_wanted``
        votes (the changefinder watching the live prediction-score
        stream asking training for a fresh candidate)."""
        from .promote import promotion_stub
        d = promotion_stub()
        m = self._last_manifest
        cur = (m or {}).get("current") or {}
        c = self._canary
        canary_n = len(self._cohorts(c["step"])[0]) if c else 0
        baking = c["bake"].started_at if c else None
        d.update({
            "configured": True,
            "promoted_step": cur.get("step"),
            "state": (m or {}).get("state"),
            "promotions": self.promotions,
            "rollbacks": int((m or {}).get("rollbacks") or 0),
            "quarantined": self.quarantined,
            "canary": {"active": c is not None,
                       "step": c["step"] if c else None,
                       "cohort": canary_n,
                       "age_seconds": (round(time.monotonic() - baking, 3)
                                       if baking else None)},
            "retrain_wanted": int(getattr(self.slo, "retrain_wanted", 0)
                                  or 0),
            "retrain_acked": int(getattr(self.slo, "retrain_acked", 0)
                                 or 0),
        })
        if self.gate is not None:
            from .promote import shadow_counters
            d.update(self.gate.counters())
            d["shadow"] = shadow_counters(self.gate.shadow)
        return d

    def _register_obs(self) -> None:
        import weakref
        from ..obs.registry import FLEET_STUB, registry
        from .promote import promotion_stub
        ref = weakref.ref(self)

        def fleet() -> dict:
            m = ref()
            if m is None:              # manager GC'd: the shared registry
                return dict(FLEET_STUB)   # stub, so keys can't drift
            return m.obs_section()

        registry.register("fleet", fleet)
        if self.promote:
            def promotion() -> dict:
                m = ref()
                return m.promotion_section() if m is not None \
                    else promotion_stub()

            registry.register("promotion", promotion)

    # -- lifecycle -----------------------------------------------------------
    def stop(self, timeout: float = 15.0) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5)
        rs = self.replicas()
        for r in rs:
            if r.proc.poll() is None:
                r.proc.terminate()         # workers drain + exit on SIGTERM
        deadline = time.monotonic() + timeout
        for r in rs:
            try:
                r.proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                r.proc.kill()
                r.proc.wait(timeout=5)
            if self.router is not None:
                self.router.remove_replica(r.rid)
        with self._lock:
            self._replicas.clear()
        if self._uds_dir:
            shutil.rmtree(self._uds_dir, ignore_errors=True)
        if self.flight_dir:
            # unmap the router ring (leaktrack hygiene); the file stays —
            # it IS the record of this run
            self._flight.close()


class Fleet:
    """Router + replica manager as one unit — the `serve --replicas N`
    topology. ``port=0`` binds the router on an ephemeral port (read
    ``self.port`` after construction)."""

    def __init__(self, algo: str, options: str = "", *,
                 checkpoint_dir: Optional[str] = None,
                 bundle: Optional[str] = None,
                 replicas: int = 2,
                 host: str = "127.0.0.1", port: int = 0,
                 policy: str = "least_loaded",
                 env: Optional[dict] = None,
                 per_replica_env: Optional[List[dict]] = None,
                 serve_kwargs: Optional[dict] = None,
                 pin_cpus: bool = False,
                 plane: str = "threaded",
                 uds: Optional[bool] = None,
                 health_interval: float = 0.5,
                 watch_interval: float = 2.0,
                 spawn_timeout: float = 180.0,
                 slo_p99_ms: float = 100.0,
                 slo_availability: float = 0.999,
                 trace_sample: float = 0.01,
                 result_cache_entries: int = 0,
                 result_cache_bytes: int = 8 << 20,
                 promote: bool = False,
                 holdout=None,
                 gate_opts: Optional[dict] = None,
                 canary_fraction: float = 0.25,
                 canary_bake_s: float = 10.0,
                 bake_opts: Optional[dict] = None,
                 slo_opts: Optional[dict] = None,
                 retrain: bool = False,
                 retrain_opts: Optional[dict] = None,
                 train_input: Optional[str] = None,
                 flight_dir: Optional[str] = None):
        from ..obs.slo import SloEngine
        from ..obs.trace import get_tracer
        get_tracer().process_label = "router"   # the merged /trace view
        # ONE fleet-wide SLO engine: the manager samples it from health
        # polls, the router serves it at /slo
        self.slo = SloEngine(p99_ms=slo_p99_ms,
                             availability=slo_availability,
                             **(slo_opts or {}))
        gate = None
        if promote:
            from .promote import PromotionGate
            gopts = dict(gate_opts or {})
            # gate candidates the way the fleet will SERVE them: a
            # quantized fleet must pass the logloss/AUC/calibration
            # deltas on its quantized scores, not the f32 ones the
            # replicas never serve (the quantized-candidate guardrail)
            gopts.setdefault("precision",
                             (serve_kwargs or {}).get("precision")
                             or "f32")
            gate = PromotionGate(algo, options, holdout=holdout, **gopts)
        bake = dict(bake_opts or {})
        bake.setdefault("bake_seconds", canary_bake_s)
        self.router = RouterServer(host=host, port=port, policy=policy,
                                   on_reload_cb=self._on_reload,
                                   trace_sample=trace_sample,
                                   slo=self.slo,
                                   result_cache_entries=result_cache_entries,
                                   result_cache_bytes=result_cache_bytes,
                                   plane=plane)
        # retrain autopilot (serve.retrain, docs/RELIABILITY.md
        # "Autonomous retraining"): consumes the SLO engine's drift
        # votes; live traffic reaches its replay buffer through a
        # router-level tee of /predict bodies (the manager process never
        # sees parsed rows — the router sees every request)
        self.retrain = None
        if retrain:
            if not (promote and checkpoint_dir):
                raise ValueError("retrain=True needs promote=True and a "
                                 "checkpoint_dir (candidates go through "
                                 "the promotion gate)")
            from .retrain import RetrainController, RouterTee
            ropts = dict(retrain_opts or {})
            tee = None
            if ropts.get("label_fn") is not None:
                tee = RouterTee()
                self.router.predict_tee = tee
            self.retrain = RetrainController(
                algo, options, checkpoint_dir=checkpoint_dir,
                slo=self.slo, router_tee=tee,
                train_input=train_input, **ropts)
        self.manager = ReplicaManager(
            algo, options, checkpoint_dir=checkpoint_dir, bundle=bundle,
            replicas=replicas, router=self.router, env=env,
            per_replica_env=per_replica_env, serve_kwargs=serve_kwargs,
            pin_cpus=pin_cpus, plane=plane, uds=uds,
            health_interval=health_interval, watch_interval=watch_interval,
            spawn_timeout=spawn_timeout, slo=self.slo,
            gate=gate, promote=promote,
            canary_fraction=canary_fraction, bake_opts=bake,
            retrain=self.retrain, flight_dir=flight_dir)
        if self.manager.promote:
            # the router's /promotion admin surface: pointer manifest +
            # the manager's live section in one payload
            def _promotion_view() -> dict:
                from .promote import promotion_manifest_view
                out = promotion_manifest_view(checkpoint_dir)
                out["section"] = self.manager.promotion_section()
                return out

            self.router.promotion_provider = _promotion_view
        self.host = host
        self.port = self.router.port
        self.plane = plane

    def _on_reload(self, body: bytes) -> dict:
        obj = json.loads(body or b"{}")
        path = obj.get("path")
        if path and self.manager.promote:
            # gated fleet: the PROMOTED pointer is the only way a model
            # reaches traffic — an explicit-path roll would bypass the
            # gate and desync from the pointer (the next watch tick
            # would converge right back)
            return {"error": "fleet is promotion-gated; flip the pointer "
                             "with `hivemall_tpu promote` instead of an "
                             "explicit-path reload"}
        if path:
            # same trust boundary as the single server's /reload: the
            # router is network-reachable and the model directory is the
            # boundary — an out-of-tree path must not even be stat'd
            ckdir = self.manager.checkpoint_dir
            if not ckdir:
                return {"error": "explicit-path reload needs a watched "
                                 "checkpoint dir"}
            real = os.path.realpath(path)
            root = os.path.realpath(ckdir)
            if os.path.commonpath([real, root]) != root:
                return {"error": "reload path is outside the watched "
                                 "checkpoint directory"}
            from ..io.checkpoint import bundle_step, verify_bundle
            verify_bundle(path, self.manager._name)
            step = bundle_step(path) or 0
            self.manager.roll(path, step)
            rolled = self.manager.fleet_step == step
        else:
            rolled = self.manager.check_and_roll()
        return {"reloaded": rolled, "fleet_step": self.manager.fleet_step,
                "roll_failures": self.manager.roll_failures}

    def start(self, wait_ready: bool = True,
              timeout: float = 180.0) -> "Fleet":
        self.router.start()
        self.manager.start()
        if wait_ready:
            self.manager.wait_ready(timeout=timeout)
        return self

    def stop(self) -> None:
        self.manager.stop()
        if self.retrain is not None:
            self.retrain.stop()          # reaps a still-running child
        self.router.stop()


# ---------------------------------------------------------------------------
# worker entry: one replica process
# ---------------------------------------------------------------------------

def _worker(spec_json: str) -> int:
    """Run one replica: engine + micro-batcher + HTTP server on an
    ephemeral loopback port. Prints ONE json line (the bound port) on
    stdout, then serves until SIGTERM — on which it drains (accepted
    requests complete) and exits 0."""
    from ..testing import leaktrack, tsan
    tsan.maybe_enable()                  # inherited HIVEMALL_TPU_TSAN=1:
    #                                      replica-side races land in the
    #                                      shared HIVEMALL_TPU_TSAN_LOG
    if leaktrack.maybe_enable():         # inherited LEAKTRACK=1: the
        leaktrack.snapshot()             # replica runs its OWN census on
        #                                  drain; the summary lands in
        #                                  the shared artifact where the
        #                                  smoke-side gate counts it
    spec = json.loads(spec_json)
    aff = spec.get("cpu_affinity")
    if aff and hasattr(os, "sched_setaffinity"):
        # pin BEFORE jax spins up its host threadpool so every thread
        # this replica creates inherits the affinity
        try:
            os.sched_setaffinity(0, set(int(c) for c in aff))
        except OSError:
            pass                       # cores went away: run unpinned
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from ..obs.trace import get_tracer
    from .engine import PredictEngine

    def opt(key, default, conv):
        # explicit None check: `or default` would silently override a
        # legitimate 0 (e.g. --serve-max-delay-ms 0 = dispatch
        # immediately) and diverge fleet replicas from single-server mode
        v = spec.get(key)
        return default if v is None else conv(v)

    engine = PredictEngine(
        spec["algo"], spec.get("options") or "",
        bundle=spec.get("bundle"),
        checkpoint_dir=spec.get("checkpoint_dir"),
        max_batch=opt("max_batch", 256, int),
        max_row_features=opt("max_row_features", 4096, int),
        watch_interval=opt("watch_interval", 2.0, float),
        # background: bind + report the port NOW, warm concurrently —
        # the router health-gates on /healthz readiness
        warmup="background",
        warmup_len=opt("warmup_len", 16, int),
        # promote mode: boot from the PROMOTED pointer, not newest
        follow=spec.get("follow") or "newest",
        # zero-copy serving (docs/PERFORMANCE.md "Weight arena"): every
        # replica mmaps the shared arena instead of deserializing its
        # own bundle copy; precision picks the scoring tier
        arena=spec.get("arena") or "auto",
        precision=spec.get("precision") or "f32")
    srv_kwargs = dict(
        host=spec.get("host") or "127.0.0.1",
        port=opt("port", 0, int),
        max_delay_ms=opt("max_delay_ms", 2.0, float),
        max_queue_rows=spec.get("max_queue_rows"),
        deadline_ms=opt("deadline_ms", 0.0, float),
        # the MANAGER owns reload sequencing fleet-wide; a replica
        # polling on its own would race the roll and skew steps
        watch=bool(spec.get("self_watch") or False),
        # likewise the manager owns the fleet SLO engine (it sums the
        # replicas' cumulative /healthz totals); a per-replica sampler
        # would just burn a thread per process
        slo=False)
    if (spec.get("plane") or "threaded") == "evloop":
        from .evloop import EvloopPredictServer
        srv = EvloopPredictServer(engine, uds_path=spec.get("uds"),
                                  **srv_kwargs).start()
    else:
        from .http import PredictServer
        srv = PredictServer(engine, **srv_kwargs).start()
    # label this process's span export so the router-merged /trace
    # reads replica:<port> instead of a bare pid
    get_tracer().process_label = f"replica:{srv.port}"

    stop = threading.Event()

    def on_term(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)
    line = {"ready": True, "port": srv.port, "pid": os.getpid(),
            "model_step": engine.model_step}
    if getattr(srv, "uds_path", None):
        line["uds"] = srv.uds_path
    print(json.dumps(line), flush=True)
    while not stop.wait(1.0):            # timed wait: signal-interruptible
        pass
    srv.stop(drain=True)
    # unmap this replica's flight ring AFTER drain (the last batch.done
    # events must land) — census hygiene; the file itself stays on disk
    get_flight().close()
    if leaktrack.enabled():
        # the inherited metrics sink closes first — a sink left open
        # after drain would count as this replica's leak
        from ..utils.metrics import close_stream
        close_stream()
        n = leaktrack.check_and_report(f"replica:{srv.port} leaktrack")
        return 1 if n else 0     # exit codes wrap mod 256; the true
        #                          count is in the shared artifact
    return 0


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="hivemall_tpu.serve.fleet")
    ap.add_argument("--worker", metavar="SPEC_JSON",
                    help="run one replica worker from a json spec "
                         "(internal: spawned by ReplicaManager)")
    args = ap.parse_args(argv)
    if args.worker:
        return _worker(args.worker)
    ap.error("only --worker mode is runnable directly; use "
             "`hivemall_tpu serve --replicas N` for a fleet")
    return 2


if __name__ == "__main__":
    sys.exit(main())

