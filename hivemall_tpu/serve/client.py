"""Shared raw keep-alive HTTP client for the serving plane.

One wire implementation for every caller: the router's pooled replica
connections (``serve/router.py``), the smokes' driver client
(``serve/http.py`` ``KeepAliveClient``) and the tests, so protocol
changes — the binary frame Content-Type (serve/wire.py), the UDS fast
path — land in ONE place.

Raw sockets, hand-built request heads, minimal response parse: cheaper
per request than ``http.client``, which matters for the router (one
Python process fronting many replicas) and for a load generator that
shares its host with the server.  NOT thread-safe — one client per
thread, by design.
"""

from __future__ import annotations

import json
import socket
from typing import Dict, List, Optional, Tuple

# hard cap on any single response body accepted by this client; the
# serving responses are small JSON — anything bigger is a desync
_MAX_BODY = 64 << 20


class RawConn:
    """One kept-alive raw socket to a server — TCP or UDS.

    When ``uds`` names a unix-domain socket path the connection skips
    TCP entirely (no handshake RTT, no Nagle, no port table) — the
    router's fast path to co-located replicas.  TCP connections set
    NODELAY: request head and body go out as separate small sends, and
    Nagle + delayed ACK would stall every kept-alive forward ~40ms.
    """

    def __init__(self, host: str, port: int, timeout: float,
                 uds: Optional[str] = None):
        # the socket stays a local until the object is fully built — a
        # constructor failure must close it, not leak it (GC12)
        if uds:
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            try:
                sock.settimeout(timeout)
                sock.connect(uds)
            except OSError:
                sock.close()
                raise
        else:
            sock = socket.create_connection((host, port), timeout=timeout)
        self.sock = sock
        try:
            if not uds:
                self.sock.setsockopt(socket.IPPROTO_TCP,
                                     socket.TCP_NODELAY, 1)
            self.rfile = self.sock.makefile("rb")
        except OSError:
            self.sock.close()
            raise
        self.uds = uds

    def close(self) -> None:
        try:
            self.rfile.close()
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


def build_request(host: str, port: int, path: str,
                  body: Optional[bytes] = None, method: str = "POST",
                  ctype: str = "application/json",
                  extra_head: str = "") -> bytes:
    """Hand-build one HTTP/1.1 request. ``extra_head`` is pre-formatted
    ``Name: value\\r\\n`` lines appended verbatim."""
    head = [f"{method} {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"]
    if body is not None:
        head.append(f"Content-Type: {ctype}\r\n"
                    f"Content-Length: {len(body)}\r\n")
    if extra_head:
        head.append(extra_head)
    head.append("\r\n")
    return "".join(head).encode("latin-1") + (body or b"")


def read_response(rfile) -> Tuple[int, List[bytes], bytes]:
    """Read one HTTP response off ``rfile``: returns ``(status,
    raw header lines incl. status line + terminating blank, payload)``.
    Raises ``ConnectionError`` on a half response (dead keep-alive)."""
    line = rfile.readline(65537)
    if not line:
        raise ConnectionError("connection closed before response")
    try:
        status = int(line.split(None, 2)[1])
    except (IndexError, ValueError):
        raise ConnectionError(f"bad status line {line!r}") from None
    lines = [line]
    clen = 0
    while True:
        h = rfile.readline(65537)
        if not h:
            raise ConnectionError("connection closed mid-headers")
        lines.append(h)
        if h in (b"\r\n", b"\n"):
            break
        if h.lower().startswith(b"content-length:"):
            clen = int(h.split(b":", 1)[1])
    if clen > _MAX_BODY:
        raise ConnectionError(f"response body {clen} bytes > cap")
    payload = rfile.read(clen) if clen else b""
    if len(payload) != clen:
        raise ConnectionError("connection closed mid-body")
    return status, lines, payload


def _headers_dict(lines: List[bytes]) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for h in lines[1:-1]:
        name, _, value = h.decode("latin-1").partition(":")
        out[name.strip()] = value.strip()
    return out


class RawHTTPClient:
    """Keep-alive client for ONE endpoint (TCP host:port or UDS path).

    Reconnects transparently once when the server side closed an idle
    connection (their idle reaper, an error response's ``Connection:
    close``); a server actively refusing still raises.  The last
    response's headers stay readable on ``self.last_headers`` and its
    raw hop headers on ``self.last_hops`` (the trace/hop assertions in
    smokes and tests read them)."""

    def __init__(self, host: str, port: int, timeout: float = 30.0,
                 uds: Optional[str] = None):
        self.host, self.port, self.timeout = host, int(port), timeout
        self.uds = uds
        self.last_headers: Dict[str, str] = {}
        self.last_hops: Optional[bytes] = None  # raw x-hivemall-hop* lines
        self._conn: Optional[RawConn] = None

    # -- connection management -------------------------------------------
    def _connect(self) -> RawConn:
        if self._conn is None:
            self._conn = RawConn(self.host, self.port, self.timeout,
                                 uds=self.uds)
        return self._conn

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    # -- request/response -------------------------------------------------
    def request(self, method: str, path: str, body: Optional[bytes] = None,
                headers: Optional[dict] = None) -> Tuple[int, bytes]:
        """Returns ``(status, payload bytes)``. Retries once on a dead
        kept-alive connection."""
        ctype = "application/json"
        extra = []
        for k, v in (headers or {}).items():
            if k.lower() == "content-type":
                ctype = v
            else:
                extra.append(f"{k}: {v}\r\n")
        req = build_request(self.host, self.port, path, body, method=method,
                            ctype=ctype, extra_head="".join(extra))
        for attempt in (0, 1):
            conn = self._connect()
            try:
                conn.sock.sendall(req)
                status, lines, payload = read_response(conn.rfile)
            except (ConnectionError, BrokenPipeError, socket.timeout,
                    OSError):
                self.close()
                if attempt:
                    raise
                continue
            self.last_headers = _headers_dict(lines)
            hops = [h for h in lines[1:-1]
                    if h.lower().startswith(b"x-hivemall-hop")]
            self.last_hops = b"".join(hops) if hops else None
            if any(h.lower().startswith(b"connection: close")
                   for h in lines[1:-1]):
                self.close()
            return status, payload
        raise AssertionError("unreachable")

    def post_json(self, path: str, obj: dict,
                  headers: Optional[dict] = None):
        """Returns ``(status, parsed json)``."""
        code, payload = self.request("POST", path, json.dumps(obj).encode(),
                                     headers=headers)
        return code, json.loads(payload)

    def post_frame(self, path: str, rows, deadline_ms=None,
                   headers: Optional[dict] = None, accept_frame: bool = False):
        """POST pre-parsed rows as one binary frame (serve/wire.py).
        Returns ``(status, parsed json)`` by default; with
        ``accept_frame`` the request negotiates an HMR1 response frame
        (``Accept:`` header) and a 200 comes back as the decoded tuple
        ``(scores_rows, ids_rows, model_step)`` — errors stay JSON on
        both protocols."""
        from .wire import CONTENT_TYPE_FRAME, encode_frame
        hdrs = dict(headers or {})
        hdrs["Content-Type"] = CONTENT_TYPE_FRAME
        if accept_frame:
            hdrs["Accept"] = CONTENT_TYPE_FRAME
        code, payload = self.request(
            "POST", path, encode_frame(rows, deadline_ms), headers=hdrs)
        return code, self._decode_payload(code, payload)

    def post_json_frame(self, path: str, obj: dict,
                        headers: Optional[dict] = None):
        """POST JSON but negotiate an HMR1 response frame — the
        retrieval plane's cheap-response path (queries are tiny, result
        rows are the bulk). A 200 returns the decoded ``(scores_rows,
        ids_rows, model_step)`` tuple; errors stay ``(status, json)``."""
        from .wire import CONTENT_TYPE_FRAME
        hdrs = dict(headers or {})
        hdrs["Accept"] = CONTENT_TYPE_FRAME
        code, payload = self.request("POST", path,
                                     json.dumps(obj).encode(), headers=hdrs)
        return code, self._decode_payload(code, payload)

    def _decode_payload(self, code: int, payload: bytes):
        """Dispatch one response body on the Content-Type the server
        chose: HMR1 frames decode to ``(scores_rows, ids_rows, step)``,
        everything else parses as JSON."""
        from .wire import CONTENT_TYPE_FRAME, decode_response_frame
        ctype = ""
        for k, v in self.last_headers.items():
            if k.lower() == "content-type":
                ctype = v.lower()
        if code == 200 and CONTENT_TYPE_FRAME in ctype:
            return decode_response_frame(payload)
        return json.loads(payload)

    # -- prebuilt-request fast path (load drivers) -------------------------
    @staticmethod
    def build(host: str, port: int, path: str, body: bytes,
              ctype: str = "application/json") -> bytes:
        """Pre-build one request's bytes for ``exchange`` — a load loop
        sends static bytes so the driver's share stays negligible."""
        return build_request(host, port, path, body, ctype=ctype)

    def exchange(self, request: bytes) -> int:
        """Send one pre-built request, read one response, return status.
        No retry (a driver wants the failure), hop headers land raw in
        ``self.last_hops``."""
        conn = self._connect()
        conn.sock.sendall(request)
        status, lines, _ = read_response(conn.rfile)
        hops = [h for h in lines[1:-1]
                if h.lower().startswith(b"x-hivemall-hop")]
        self.last_hops = b"".join(hops) if hops else None
        return status
