#!/usr/bin/env python3
"""Open-loop load generator for /predict, run as a child process that never
imports JAX (the parent holds the chip).

The schedule is a pure function of the spec: exponential inter-arrivals
at a fixed rate, each request given a connection (a few hot connections
carry most of the traffic) and a number of rows (log-normal, clipped), all
from the run's seed, as are the Criteo-shaped rows of the pool that its
rows are taken from. Each keep-alive connection
is one thread that sends its requests in due order, one outstanding at a
time as HTTP/1.1 does; a request is timed from the instant it was DUE, so
a busy connection or a stalled server shows in the latency of everything
behind it. How late each request left is recorded beside it.

    loadgen.py <spec.json> <out.json>

prints `ready <monotonic start>` once bodies are built; the schedule's
time zero is that instant. time.monotonic() is one clock for every
process of a Linux host."""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import data  # noqa: E402


def schedule(spec: dict) -> dict:
    """Arrival times (seconds from time zero, lead-in included), connection
    and row count of every request, and which rows of the pool it carries:
    a pure function of the spec, `seed` included. Which large request
    lands behind which on a hot connection is part of what a seed draws,
    and it moves the tails: six seeds' p95 spread by 26% at 0.8 x the knee
    (PERF.md section 7), which is why no cell holds them to a bound yet."""
    base = np.random.default_rng([int(spec["seed"]), 0x5C4ED])
    rate = float(spec["rate_rps"])
    horizon = float(spec["lead_s"]) + float(spec["seconds"])
    n = max(1, int(round(rate * horizon)))
    gaps = base.exponential(1.0, n)
    gaps *= horizon / gaps.sum()                  # n arrivals in the horizon
    conns, hot = int(spec["connections"]), int(spec["hot_connections"])
    is_hot = base.random(n) < float(spec["hot_share"])
    conn = np.where(is_hot, base.integers(0, hot, n),
                    base.integers(hot, conns, n))
    mu = np.log(float(spec["rows_median"]))
    sigma = np.log(float(spec["rows_p95"]) / float(spec["rows_median"])) / 1.645
    rows = np.clip(np.rint(base.lognormal(mu, sigma, n)), 1,
                   int(spec["rows_max"])).astype(np.int64)
    due = np.minimum(np.cumsum(gaps), np.nextafter(horizon, 0.0))
    rng = np.random.default_rng([int(spec["seed"]), 0x10AD])
    start = rng.integers(0, int(spec["pool_rows"]), n)
    return {"due": due, "conn": conn, "rows": rows, "start": start}


def pool_rows(spec: dict) -> np.ndarray:
    """[pool_rows, fields] ids the requests' rows are slices of."""
    rs = data.RowSpec(spec["data"], int(spec["dims"]))
    rng = np.random.default_rng([int(spec["seed"]), 0x9001])
    return data.draw_ids(rs, int(spec["pool_rows"]), rng)


def request_ids(pool: np.ndarray, start: int, rows: int) -> np.ndarray:
    take = (start + np.arange(rows)) % len(pool)
    return pool[take]


def rows_json(ids: np.ndarray) -> list:
    """One JSON array per row: ["field:index:1", ...] as the CLI's clients
    send them. Encoded once for the pool; a body joins its rows' strings."""
    return [json.dumps([f"{f}:{int(i)}:1" for f, i in enumerate(r)],
                       separators=(",", ":")) for r in ids]


def body_of(row_strings) -> bytes:
    """{"rows": [row, ...]} from the rows' JSON strings."""
    return ('{"rows":[' + ",".join(row_strings) + "]}").encode()


class _ClosedBeforeResponse(ConnectionError):
    """Not one byte of a response came: the server had closed its end."""


def _read_response(rfile):
    try:
        line = rfile.readline(65537)
    except ConnectionResetError as e:
        raise _ClosedBeforeResponse(str(e)) from None
    if not line:
        raise _ClosedBeforeResponse("closed before response")
    status = int(line.split(None, 2)[1])
    clen, hop = 0, ""
    while True:
        h = rfile.readline(65537)
        if not h:
            raise ConnectionError("closed mid-headers")
        if h in (b"\r\n", b"\n"):
            break
        low = h.lower()
        if low.startswith(b"content-length:"):
            clen = int(h.split(b":", 1)[1])
        elif low.startswith(b"x-hivemall-hop:"):
            hop = h.split(b":", 1)[1].strip().decode("latin-1")
    payload = rfile.read(clen) if clen else b""
    if len(payload) != clen:
        raise ConnectionError("closed mid-body")
    return status, hop, payload


class _Conn:
    def __init__(self, host, port, timeout):
        self.addr, self.timeout = (host, port), timeout
        self.sock = self.rfile = None

    def open(self):
        self.close()
        self.sock = socket.create_connection(self.addr, timeout=self.timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.rfile = self.sock.makefile("rb")

    def close(self):
        for f in (self.rfile, self.sock):
            if f is not None:
                try:
                    f.close()
                except OSError:
                    pass
        self.sock = self.rfile = None


def _exchange(conn, message: bytes, rec: dict):
    """Send one request and read its response. A kept-alive connection the
    server closed while it sat idle (its idle reaper, 10 s) fails at the
    next send or before the first byte of the response: the request goes
    out once more on a fresh connection, as HTTP clients do, and the
    latency counts the detour. A timeout is never retried."""
    for again in (False, True):
        reused = conn.sock is not None
        try:
            if not reused:
                conn.open()
            conn.sock.sendall(message)
            return _read_response(conn.rfile)
        except (_ClosedBeforeResponse, BrokenPipeError,
                ConnectionResetError):
            conn.close()
            if again or not reused:
                raise
            rec["retried"] = 1


def _worker(host, port, todo, t_zero, out, timeout):
    """One keep-alive connection: its requests in due order."""
    conn = _Conn(host, port, timeout)
    try:
        conn.open()
        for i, due, body in todo:
            head = (f"POST /predict HTTP/1.1\r\nHost: {host}:{port}\r\n"
                    "Content-Type: application/json\r\n"
                    f"Content-Length: {len(body)}\r\n\r\n").encode("latin-1")
            wait = t_zero + due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            sent = time.monotonic()
            rec = {"i": i, "sent": sent - t_zero, "status": 0, "hop": "",
                   "scores": None, "step": None}
            try:
                status, hop, payload = _exchange(conn, head + body, rec)
                rec["status"], rec["hop"] = status, hop
                if status == 200:
                    ans = json.loads(payload)
                    rec["scores"], rec["step"] = ans["scores"], \
                        ans.get("model_step")
            except (OSError, ValueError, KeyError) as e:
                rec["error"] = f"{type(e).__name__}: {e}"
                conn.close()
            rec["done"] = time.monotonic() - t_zero
            out.append(rec)
    finally:
        conn.close()


def main(argv) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    sch = schedule(spec)
    pool = pool_rows(spec)
    conns = int(spec["connections"])
    todo = [[] for _ in range(conns)]
    encoded = rows_json(pool)
    for i in range(len(sch["due"])):
        take = (int(sch["start"][i]) + np.arange(int(sch["rows"][i]))) \
            % len(pool)
        body = body_of([encoded[k] for k in take])
        todo[int(sch["conn"][i])].append((i, float(sch["due"][i]), body))
    results: list = []
    t_zero = time.monotonic() + 0.25
    print(f"ready {t_zero!r}", flush=True)
    threads = [threading.Thread(
        target=_worker, daemon=True,
        args=(spec["host"], int(spec["port"]), todo[c], t_zero, results,
              float(spec["timeout_s"]))) for c in range(conns)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + float(spec["lead_s"]) \
        + float(spec["seconds"]) + float(spec["timeout_s"]) + 5.0
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    results.sort(key=lambda r: r["i"])
    with open(argv[2], "w") as f:
        json.dump({"t_zero": t_zero, "n_scheduled": len(sch["due"]),
                   "due": sch["due"].tolist(), "rows": sch["rows"].tolist(),
                   "conn": sch["conn"].tolist(),
                   "start": sch["start"].tolist(), "results": results}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
