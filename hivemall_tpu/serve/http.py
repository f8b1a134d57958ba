"""HTTP front end for the serve subsystem (docs/SERVING.md).

A threaded ``http.server`` endpoint (one thread per connection — request
parsing/hashing runs concurrently on connection threads; actual scoring
is serialized through the MicroBatcher's single dispatch thread, which is
exactly what makes concurrent requests coalesce):

- ``POST /predict`` — body ``{"rows": [["f1:1", "f2:0.5"], ...]}`` (or
  ``{"features": [...]}`` for one row; FFM rows use
  ``"field:index:value"`` tokens), optional ``"deadline_ms"``. Features
  hash through the trainer's own ftvec/mhash path. Response:
  ``{"scores": [...], "model_step": N, "n": N}``. Shed requests get 503,
  expired deadlines 504, parse errors 400.
- ``GET /healthz`` — READINESS: 200 once warmup completed, 503 while
  warming (so the fleet router / an external LB can gate cold replicas),
  with model step, model/bundle age, queue depth and the cheap serving
  counters.
- ``POST /retrieve`` — the retrieval plane (docs/SERVING.md "Retrieval
  plane"): body ``{"queries": [{"user": 3, "k": 10}, {"item": 7,
  "tier": "lsh"}, ...]}`` (or one bare query object), optional
  ``"deadline_ms"``. Response ``{"results": [{"ids": [...], "scores":
  [...]}], "model_step": N, "n": N}`` (+ per-row ``"words"`` when the
  factor table carries a vocab). 404 unless the server was built with
  a retrieval engine; queries coalesce through their OWN MicroBatcher
  so ranking never queues behind predict scoring.
- ``POST /reload`` — force a hot-reload check (body optionally
  ``{"path": "...npz"}`` to load an explicit bundle).

Clients sending ``Accept: application/x-hivemall-frame`` get
``/predict`` and ``/retrieve`` responses as compact HMR1 binary frames
(serve.wire) instead of JSON — top-k responses are dominated by JSON
float encode at high k.
- ``GET /slo`` — the SLO engine's windowed burn rates + drift state
  (docs/OBSERVABILITY.md "Serving traces and SLOs").
- ``GET /promotion`` — the promotion control plane's status: the watched
  directory's ``PROMOTED`` pointer manifest, the engine's follow mode,
  and the live ``promotion`` registry section (docs/RELIABILITY.md
  "Promotion and rollback").
- ``GET /snapshot`` / ``GET /metrics`` / ``GET /trace`` — the central
  obs registry (the ``serve`` section rides next to
  pipeline/train/mix/checkpoint/spans) and the process span ring,
  inherited from the obs HTTP handler.

Request tracing + per-hop breakdown: a request carrying an
``x-hivemall-trace`` header (client-supplied, or minted by the fleet
router's sampler) has its id tagged onto the ``serve.enqueue`` /
``serve.batch`` / ``serve.predict`` spans and echoed on the response.
EVERY ``/predict`` response additionally carries ``x-hivemall-hop`` —
``parse=,queue=,assemble=,predict=,other=,total=`` milliseconds whose
parts sum to the replica's measured wall for that request — which the
router extends with its own relay hop.
"""

from __future__ import annotations

import http.server
import json
import threading
import time
from typing import Optional

import numpy as np

from ..io.weight_arena import host_rss_bytes as _host_rss
from ..obs.http import _Handler as _ObsHandler
from ..obs.slo import SloEngine
from ..obs.trace import get_tracer
from .batcher import MicroBatcher, ServeDeadline, ServeOverload
from .client import RawHTTPClient
from .wire import (CONTENT_TYPE_FRAME, WireError, decode_frame,
                   encode_response_frame)

__all__ = ["PredictServer", "KeepAliveClient", "health_payload"]


class KeepAliveClient(RawHTTPClient):
    """Historical name for the shared raw keep-alive client
    (serve.client.RawHTTPClient) — the bench/smoke drivers and a pile
    of tests construct this. One endpoint, one per thread; see the
    shared module for the wire details (binary frames, UDS)."""


def health_payload(engine, batcher) -> "tuple[bool, dict]":
    """The ``/healthz`` READINESS payload, shared verbatim by both
    serving planes (the fleet manager parses it on every health tick —
    the planes must not drift on a single key). Returns ``(ready,
    payload)``; serve 200 when ready, 503 while warming."""
    ready = engine.ready
    return ready, {
        "status": "ok" if ready else "warming",
        "ready": ready,
        "algo": engine.algo,
        "model_step": engine.model_step,
        "model_age_seconds": engine.model_age_seconds,
        "bundle_age_seconds": engine.bundle_age_seconds,
        "queue_depth": batcher.queue_depth,
        "requests": batcher.requests,
        "shed": batcher.shed,
        "expired": batcher.expired,
        "errors": batcher.errors,
        "reloads": engine.reloads,
        "reload_failures": engine.reload_failures,
        # zero-copy serving gauges: the fleet manager folds these into
        # the `fleet` registry section and the router's aggregated
        # snapshot (host RSS + mapped arena bytes per replica = the
        # memory-headroom evidence)
        "host_rss_bytes": _host_rss(),
        "arena_mapped_bytes": engine.arena_mapped_bytes,
        "precision": engine.precision,
        "platform": engine.platform,
        # cumulative SLO totals (latency histogram + score moments):
        # the fleet manager sums these across replicas into its SLO
        # engine every health tick
        "slo": batcher.slo_totals(),
    }


class _ServeHandler(_ObsHandler):
    """Extends the obs handler (/snapshot, /metrics, timeout, quiet logs)
    with the predict surface. The owning PredictServer is attached on the
    per-server subclass."""

    server_ref: "PredictServer" = None   # type: ignore[assignment]

    # HTTP/1.1 => keep-alive by default: per-request TCP setup (handshake
    # + slow-start + a fresh connection thread) is measurable overhead
    # at high concurrency, and the fleet router holds pooled
    # connections to every replica. Safe here because every response path
    # (_json, the obs handler, send_error) carries Content-Length; the
    # threaded server gives each kept-alive connection its own thread, and
    # the inherited 10s socket timeout reaps idle ones.
    protocol_version = "HTTP/1.1"
    # http.server writes status line / headers / body as SEPARATE small
    # sends; on a kept-alive connection Nagle + delayed ACK turns that
    # into ~40ms stalls per response (measured: fleet p50 went 73ms ->
    # sub-ms with NODELAY). The close-per-request HTTP/1.0 server never
    # saw it because close() flushed.
    disable_nagle_algorithm = True

    # -- helpers -------------------------------------------------------------
    _body_read = False                   # per-request; reset in do_*

    def _wants_frame(self) -> bool:
        """Did the client negotiate an HMR1 binary response?"""
        accept = (self.headers.get("Accept") or "").lower()
        return CONTENT_TYPE_FRAME in accept

    def _frame(self, body: bytes,
               extra_headers: Optional[dict] = None) -> None:
        """A 200 with a binary HMR1 body (success paths only — errors
        stay JSON on every protocol so clients always parse them)."""
        self.send_response(200)
        self.send_header("Content-Type", CONTENT_TYPE_FRAME)
        self.send_header("Content-Length", str(len(body)))
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        if self.close_connection:
            self.send_header("Connection", "close")
        self.end_headers()
        self.wfile.write(body)

    def _json(self, code: int, obj: dict,
              extra_headers: Optional[dict] = None) -> None:
        body = json.dumps(obj, default=str).encode()
        if code >= 400 and not self._body_read:
            # an error sent BEFORE the request body was consumed (e.g.
            # the 64MB cap rejects before reading) leaves bytes on the
            # wire that keep-alive would parse as the next request line —
            # those responses close the connection. Errors after a full
            # read (503 shed, 504 expired, 400 parse) keep it open: at
            # overload, forcing every shed client to re-handshake TCP
            # would amplify load exactly when the server is saturated
            self.close_connection = True
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        if self.close_connection:
            self.send_header("Connection", "close")
        self.end_headers()
        self.wfile.write(body)

    def _read_body(self) -> dict:
        ln = int(self.headers.get("Content-Length") or 0)
        if ln <= 0:
            self._body_read = True
            return {}
        if ln > (64 << 20):
            raise ValueError(f"request body {ln} bytes > 64MB cap")
        raw = self.rfile.read(ln)
        self._body_read = True           # wire is clean past this point
        obj = json.loads(raw or b"{}")
        if not isinstance(obj, dict):
            raise ValueError("request body must be a JSON object")
        return obj

    # -- routes --------------------------------------------------------------
    def do_GET(self):  # noqa: N802 — http.server API
        self._body_read = True           # GETs carry no body to drain
        path = self.path.split("?", 1)[0]
        s = self.server_ref
        if path == "/healthz":
            # READINESS, not bare liveness: 200 only once warmup completed
            # (503 while warming), so the fleet router — and any external
            # LB probing this port — can gate cold/warming replicas out of
            # rotation instead of routing requests into XLA compiles. The
            # payload is shared with the evloop plane (health_payload).
            # A retrieval-only server reports its retrieval engine here
            # (same keys — the fleet manager must not see plane drift).
            eng = s.engine if s.engine is not None else s.retrieval
            bat = s.batcher if s.batcher is not None else s.rbatcher
            ready, payload = health_payload(eng, bat)
            if s.retrieval is not None and s.engine is not None:
                # both planes up: readiness is the AND (a predict-ready
                # replica with a cold factor table must not take top-k)
                ready = ready and s.retrieval.ready
                payload["ready"] = ready
                if payload["status"] == "ok" and not ready:
                    payload["status"] = "warming"
            self._json(200 if ready else 503, payload)
            return
        if path == "/slo":
            slo = s.slo
            if slo is None:
                self._json(404, {"error": "no SLO engine configured"})
                return
            self._json(200, slo.evaluate())
            return
        if path == "/promotion":
            # promotion status (docs/RELIABILITY.md "Promotion and
            # rollback"): the watched dir's PROMOTED pointer manifest,
            # the engine's follow mode, and — when a controller/manager
            # registered one — the live `promotion` registry section
            from ..obs.registry import registry
            from .promote import promotion_manifest_view
            eng = s.engine if s.engine is not None else s.retrieval
            out = promotion_manifest_view(eng.checkpoint_dir)
            out["follow"] = eng.follow
            out["section"] = registry.snapshot().get("promotion")
            self._json(200, out)
            return
        super().do_GET()               # /snapshot, /metrics, /trace, 404

    def do_POST(self):  # noqa: N802 — http.server API
        self._body_read = False          # fresh request on this connection
        path = self.path.split("?", 1)[0]
        s = self.server_ref
        if path == "/reload":
            try:
                body = self._read_body()
            except (ValueError, json.JSONDecodeError) as e:
                self._json(400, {"error": str(e)})
                return
            # both planes follow the same checkpoint dir: one /reload
            # ticks whichever engines exist so a promoted bundle can
            # never serve predicts at step N and top-k at step N-1
            eng = s.engine if s.engine is not None else s.retrieval
            try:
                swapped = eng.reload(body.get("path"))
                if s.retrieval is not None and eng is not s.retrieval:
                    swapped = s.retrieval.reload(body.get("path")) \
                        or swapped
            except ValueError as e:    # out-of-tree path: the model dir
                self._json(403, {"error": str(e)})   # is the trust boundary
                return
            self._json(200, {"reloaded": swapped,
                             "model_step": eng.model_step,
                             "reload_failures": eng.reload_failures})
            return
        if path == "/retrieve":
            self._do_retrieve()
            return
        if path != "/predict":
            self.send_error(404, "unknown path (try /predict, /retrieve, "
                                 "/healthz, /reload, /slo, /snapshot or "
                                 "/metrics)")
            return
        if s.engine is None:
            # body unread -> _json closes the connection (wire hygiene)
            self._json(404, {"error": "no predict engine on this server "
                                      "(retrieval-only; try /retrieve)"})
            return
        t_req0 = time.monotonic()
        # request-scoped tracing: honor a client/router-supplied id —
        # the spans this request touches get tagged with it and the
        # response echoes it (docs/OBSERVABILITY.md)
        tid = self.headers.get("x-hivemall-trace")
        ctype = (self.headers.get("Content-Type") or "").lower()
        try:
            if ctype.startswith(CONTENT_TYPE_FRAME):
                # binary frame protocol (serve.wire): pre-hashed rows,
                # no libsvm string parse; bit-matches the JSON path
                ln = int(self.headers.get("Content-Length") or 0)
                if ln > (64 << 20):
                    raise ValueError(
                        f"request body {ln} bytes > 64MB cap")
                raw_body = self.rfile.read(ln) if ln > 0 else b""
                self._body_read = True
                frame_rows, deadline_ms = decode_frame(
                    raw_body, s.engine.max_row_features)
                parsed = [s.engine.parse(r) for r in frame_rows]
                rows = None            # no raw strings to tee
            else:
                body = self._read_body()
                rows = body.get("rows")
                if rows is None:
                    feats = body.get("features")
                    if feats is None:
                        raise ValueError(
                            'body needs "rows" or "features"')
                    rows = [feats]
                if not isinstance(rows, list) \
                        or not all(isinstance(r, list) for r in rows):
                    raise ValueError(
                        '"rows" must be a list of feature-string '
                        'lists (a bare string would be read as '
                        'per-character rows)')
                deadline_ms = body.get("deadline_ms")
                if deadline_ms is not None:
                    deadline_ms = float(deadline_ms)   # malformed -> 400
                # hashing/parsing on THIS connection thread — concurrent
                # requests parse in parallel, only scoring serializes
                parsed = [s.engine.parse(r) for r in rows]
        except WireError as e:
            # a desynced binary stream cannot be resynchronized
            # mid-connection: 400 AND close (JSON 400s keep alive)
            self.close_connection = True
            self._json(400, {"error": str(e)})
            return
        except (ValueError, TypeError, KeyError,
                json.JSONDecodeError) as e:
            self._json(400, {"error": str(e)})
            return
        t_parsed = time.monotonic()
        try:
            with s.tracer.context(tid):   # tags serve.enqueue
                # `rows` rides along as the raw feature strings so a
                # raw-capturing tee (the retrain replay buffer) can
                # mirror what the client actually sent
                fut = s.batcher.submit(parsed, deadline_ms=deadline_ms,
                                       trace_id=tid, raw=rows)
            res = fut.result(timeout=s.request_timeout)
        except ServeOverload as e:
            self._json(503, {"error": str(e), "shed": True})
            return
        except ServeDeadline as e:
            self._json(504, {"error": str(e), "expired": True})
            return
        except Exception as e:         # noqa: BLE001 — predict failure is
            # a 500 on THIS request, never a handler crash
            self._json(500, {"error": f"{type(e).__name__}: {e}"})
            return
        if isinstance(res, tuple):
            scores, step = res
        else:                          # zero-row request short-circuit
            scores, step = res, s.engine.model_step
        # per-hop latency breakdown: parts sum to the replica's measured
        # wall for THIS request ("other" closes the residual — result
        # pickup + response build). The router stacks its relay hop on
        # top; the fleet smoke and the benchmark's serve readers consume
        # these.
        hop = getattr(fut, "hop", None) or {}
        total_ms = (time.monotonic() - t_req0) * 1000.0
        parse_ms = (t_parsed - t_req0) * 1000.0
        queue_ms = hop.get("queue_s", 0.0) * 1000.0
        assemble_ms = hop.get("assemble_s", 0.0) * 1000.0
        predict_ms = hop.get("predict_s", 0.0) * 1000.0
        other_ms = max(0.0, total_ms - parse_ms - queue_ms
                       - assemble_ms - predict_ms)
        extra = {"x-hivemall-hop":
                 f"parse={parse_ms:.3f},queue={queue_ms:.3f},"
                 f"assemble={assemble_ms:.3f},predict={predict_ms:.3f},"
                 f"other={other_ms:.3f},total={total_ms:.3f}"}
        if tid:
            extra["x-hivemall-trace"] = tid
        if self._wants_frame():
            # HMR1: all scores as one frame row (scores-only layout) —
            # skips the per-float JSON encode on the response hot path
            self._frame(encode_response_frame([scores],
                                              model_step=int(step)),
                        extra_headers=extra)
            return
        self._json(200, {"scores": [float(v) for v in scores],
                         "model_step": int(step),
                         "n": len(scores)}, extra_headers=extra)

    def _do_retrieve(self) -> None:
        """POST /retrieve — top-k queries through the retrieval plane's
        own MicroBatcher (docs/SERVING.md "Retrieval plane")."""
        s = self.server_ref
        r = s.retrieval
        if r is None:
            self._json(404, {"error": "no retrieval engine on this "
                                      "server (serve --retrieval)"})
            return
        t_req0 = time.monotonic()
        tid = self.headers.get("x-hivemall-trace")
        try:
            body = self._read_body()
            queries = body.get("queries")
            if queries is None:
                # one bare query object rides at the top level
                queries = [body] if ("user" in body or "item" in body) \
                    else None
            if not isinstance(queries, list) or not queries:
                raise ValueError('body needs "queries": [{"user": id} | '
                                 '{"item": id}, ...]')
            deadline_ms = body.get("deadline_ms")
            if deadline_ms is not None:
                deadline_ms = float(deadline_ms)
            parsed = [r.parse_query(q) for q in queries]
        except (ValueError, TypeError, KeyError,
                json.JSONDecodeError) as e:
            self._json(400, {"error": str(e)})
            return
        t_parsed = time.monotonic()
        try:
            with s.tracer.context(tid):
                fut = s.rbatcher.submit(parsed, deadline_ms=deadline_ms,
                                        trace_id=tid)
            res = fut.result(timeout=s.request_timeout)
        except ServeOverload as e:
            self._json(503, {"error": str(e), "shed": True})
            return
        except ServeDeadline as e:
            self._json(504, {"error": str(e), "expired": True})
            return
        except Exception as e:         # noqa: BLE001 — ranking failure is
            # a 500 on THIS request, never a handler crash
            self._json(500, {"error": f"{type(e).__name__}: {e}"})
            return
        if isinstance(res, tuple):
            packed, step = res
        else:                          # zero-query short-circuit
            packed, step = res, r.model_step
        # unpack [n, max_k, 2] (ids|-1 pad, scores) into ragged lists
        ids_rows, scores_rows = [], []
        for i in range(len(parsed)):
            ids = packed[i, :, 0]
            valid = ids >= 0
            ids_rows.append(ids[valid].astype(np.int32))
            scores_rows.append(
                np.asarray(packed[i, valid, 1], np.float32))
        hop = getattr(fut, "hop", None) or {}
        total_ms = (time.monotonic() - t_req0) * 1000.0
        parse_ms = (t_parsed - t_req0) * 1000.0
        queue_ms = hop.get("queue_s", 0.0) * 1000.0
        assemble_ms = hop.get("assemble_s", 0.0) * 1000.0
        predict_ms = hop.get("predict_s", 0.0) * 1000.0
        other_ms = max(0.0, total_ms - parse_ms - queue_ms
                       - assemble_ms - predict_ms)
        extra = {"x-hivemall-hop":
                 f"parse={parse_ms:.3f},queue={queue_ms:.3f},"
                 f"assemble={assemble_ms:.3f},predict={predict_ms:.3f},"
                 f"other={other_ms:.3f},total={total_ms:.3f}"}
        if tid:
            extra["x-hivemall-trace"] = tid
        if self._wants_frame():
            self._frame(encode_response_frame(scores_rows, ids_rows,
                                              model_step=int(step)),
                        extra_headers=extra)
            return
        results = []
        for ids, sc in zip(ids_rows, scores_rows):
            row = {"ids": [int(v) for v in ids],
                   "scores": [float(v) for v in sc]}
            words = r.labels(ids)
            if words is not None:
                row["words"] = words
            results.append(row)
        self._json(200, {"results": results, "model_step": int(step),
                         "n": len(results)}, extra_headers=extra)


class _ThreadedHTTPServer(http.server.ThreadingHTTPServer):
    daemon_threads = True

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._conns: set = set()         # live accepted sockets
        self._conns_lock = threading.Lock()

    def handle_error(self, request, client_address):
        pass                           # client disconnects are routine

    def get_request(self):
        sock, addr = super().get_request()
        with self._conns_lock:
            self._conns.add(sock)
        return sock, addr

    def shutdown_request(self, request):
        with self._conns_lock:
            self._conns.discard(request)
        super().shutdown_request(request)

    def close_connections(self, timeout: float = 5.0) -> None:
        """Drain surviving keep-alive connections. shutdown() only
        stops the accept loop — a peer that holds its side open (the
        fleet router's conn pool) would park each handler thread in
        readline until the 30s idle reaper, leaving the accepted socket
        open past teardown (the leaktrack census counts that).

        Graceful by construction: EOF the READ side first, so an idle
        handler wakes and exits while one mid-request keeps its intact
        write side and finishes its response (drain=True's promise),
        then loops into the EOF. Each exiting handler closes its own
        socket via shutdown_request; only stragglers past ``timeout``
        get force-closed."""
        import socket as _socket
        with self._conns_lock:
            conns = list(self._conns)
        for sock in conns:
            try:
                sock.shutdown(_socket.SHUT_RD)
            except OSError:
                pass
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._conns_lock:
                if not self._conns:
                    return
            time.sleep(0.01)
        with self._conns_lock:
            leftovers = list(self._conns)
            self._conns.clear()
        for sock in leftovers:
            try:
                sock.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass


class PredictServer:
    """Engine + batcher + HTTP endpoint, wired into the obs registry.

    ``port=0`` binds an ephemeral port (read ``self.port``). Loopback-only
    by default; bind ``host="0.0.0.0"`` explicitly to serve a fleet.
    Starting the server also starts the engine's checkpoint watcher when a
    watch directory is configured (the train+serve shared-dir recipe).

    ``retrieval=`` mounts a serve.retrieve.RetrievalEngine on
    ``POST /retrieve`` behind its OWN MicroBatcher (top-k ranking must
    not queue behind predict scoring and vice versa — the two planes
    coalesce independently). ``engine=None`` with a retrieval engine is
    a retrieval-only server: /predict 404s, health/SLO ride the
    retrieval plane."""

    def __init__(self, engine=None, *, host: str = "127.0.0.1",
                 port: int = 0,
                 max_batch: Optional[int] = None,
                 max_delay_ms: float = 2.0,
                 max_queue_rows: Optional[int] = None,
                 deadline_ms: float = 0.0,
                 request_timeout: float = 60.0,
                 watch: bool = True,
                 slo: "bool | SloEngine" = True,
                 slo_p99_ms: float = 100.0,
                 slo_availability: float = 0.999,
                 retrieval=None):
        if engine is None and retrieval is None:
            raise ValueError("PredictServer needs an engine, a retrieval "
                             "engine, or both")
        self.engine = engine
        self.retrieval = retrieval
        self.request_timeout = float(request_timeout)
        self._watch = bool(watch)
        self.tracer = get_tracer()
        # the versioned predict fn: each response carries the step of the
        # model version that actually scored it (correct across hot swaps)
        self.batcher: Optional[MicroBatcher] = None
        if engine is not None:
            self.batcher = MicroBatcher(
                engine.predict_rows_versioned,
                max_batch=int(max_batch or engine.max_batch),
                max_delay_ms=max_delay_ms,
                max_queue_rows=max_queue_rows,
                deadline_ms=deadline_ms)
            engine.attach_batcher(self.batcher)
        self.rbatcher: Optional[MicroBatcher] = None
        if retrieval is not None:
            self.rbatcher = MicroBatcher(
                retrieval.retrieve_rows_versioned,
                max_batch=int(retrieval.max_batch),
                max_delay_ms=max_delay_ms,
                max_queue_rows=max_queue_rows,
                deadline_ms=deadline_ms)
            retrieval.attach_batcher(self.rbatcher)
        # SLO engine over this server's own batcher totals (the fleet
        # topology passes slo=False here and samples fleet-wide at the
        # manager instead — one engine per surface, never two)
        if isinstance(slo, SloEngine):
            self.slo: Optional[SloEngine] = slo
            self._own_slo = False
        elif slo:
            self.slo = SloEngine(p99_ms=slo_p99_ms,
                                 availability=slo_availability)
            self._own_slo = True
        else:
            self.slo = None
            self._own_slo = False
        handler = type("_BoundServeHandler", (_ServeHandler,),
                       {"server_ref": self})
        self._httpd = _ThreadedHTTPServer((host, port), handler)
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "PredictServer":
        if self._watch:
            if self.engine is not None:
                self.engine.start_watch()
            if self.retrieval is not None:
                self.retrieval.start_watch()
        if self._own_slo and self.slo is not None:
            bat = self.batcher if self.batcher is not None \
                else self.rbatcher
            self.slo.start(bat.slo_totals)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name=f"serve-http:{self.port}", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = False) -> None:
        """Shut down: stop accepting connections, then close the batcher.
        ``drain=True`` is the graceful path (a fleet replica on SIGTERM):
        requests already accepted score to completion before the batcher
        stops; the default fails queued requests fast."""
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if self._own_slo and self.slo is not None:
            self.slo.stop()
        if self.batcher is not None:
            self.batcher.close(drain=drain, timeout=30.0 if drain else 5.0)
        if self.rbatcher is not None:
            self.rbatcher.close(drain=drain,
                                timeout=30.0 if drain else 5.0)
        # EOF-drain surviving keep-alive conns: in-flight responses
        # (scores resolved during the batcher drain) still write to
        # completion; nothing outlives the server
        self._httpd.close_connections()
        if self.engine is not None:
            self.engine.close()
        if self.retrieval is not None:
            self.retrieval.close()
