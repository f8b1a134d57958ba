"""Event-driven serving plane (hivemall_tpu/serve/evloop.py,
docs/SERVING.md "Serving planes"): the HMF1 binary wire codec, the
inline batch assembler's BatchPlane contracts, and the evloop server's
protocol surface — frame/JSON bit-match, malformed-frame teardown that
leaves the loop healthy, hop-header additivity on BOTH planes and the
UDS transport."""

import os

import numpy as np
import pytest

from hivemall_tpu.serve.wire import (CONTENT_TYPE_FRAME, MAGIC, WireError,
                                     decode_frame, encode_frame)

OPTS = "-dims 1024 -loss logloss -opt adagrad -mini_batch 32"


# --- wire codec (no server, no jax) -----------------------------------------

def _rows(n, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        k = int(rng.integers(1, 9))
        out.append((rng.integers(0, 1 << 20, k).astype(np.int32),
                    rng.random(k).astype(np.float32)))
    return out


def test_wire_frame_roundtrip():
    rows = _rows(5)
    dec, dl = decode_frame(encode_frame(rows))
    assert dl is None and len(dec) == len(rows)
    for (ai, av), (bi, bv) in zip(rows, dec):
        assert np.array_equal(ai, bi)
        assert np.array_equal(av, bv)          # f32 bits survive the wire
        assert bi.dtype == np.int32 and bv.dtype == np.float32
    # deadline flag carries a per-request budget
    _, dl = decode_frame(encode_frame(rows[:1], deadline_ms=7.5))
    assert dl == pytest.approx(7.5)
    # degenerate shapes: empty frame, zero-feature row
    assert decode_frame(encode_frame([])) == ([], None)
    dec, _ = decode_frame(encode_frame(
        [(np.zeros(0, np.int32), np.zeros(0, np.float32))]))
    assert len(dec) == 1 and len(dec[0][0]) == 0


def test_wire_rejects_malformed_frames():
    good = encode_frame(_rows(2))
    cases = [
        b"",                                   # shorter than the header
        b"NOPE" + good[4:],                    # bad magic
        bytes([good[0], good[1], good[2], good[3], 0xFE]) + good[5:],
        good[:-3],                             # truncated in row payload
        good[:7],                              # truncated at row length
        good + b"\x00",                        # trailing garbage
        encode_frame(_rows(1), deadline_ms=1.0)[:9],  # cut in deadline
    ]
    for bad in cases:
        with pytest.raises(WireError):
            decode_frame(bad)
    # per-row feature cap (the engine's bound) fails BEFORE allocation
    wide = encode_frame([(np.arange(3, dtype=np.int32),
                          np.ones(3, np.float32))])
    with pytest.raises(WireError, match="cap"):
        decode_frame(wide, max_row_features=1)
    # encode-side validation: mismatched idx/val shapes never hit the wire
    with pytest.raises(WireError, match="mismatch"):
        encode_frame([(np.zeros(3, np.int32), np.zeros(2, np.float32))])
    assert good[:4] == MAGIC


# --- inline assembler: BatchPlane contracts (pure, loop-free) ----------------

def _mk_done(sink):
    def done(scores, meta, hop, exc):
        sink.append((scores, meta, hop, exc))
    return done


def test_inline_assembler_contracts():
    from hivemall_tpu.serve.batcher import ServeDeadline, ServeOverload
    from hivemall_tpu.serve.evloop import InlineAssembler
    calls = []

    def predict(rows):
        calls.append(len(rows))
        return np.arange(len(rows), dtype=np.float32)

    a = InlineAssembler(predict, max_batch=4, max_delay_ms=0.0,
                        max_queue_rows=6)
    got = []
    # never-split: 3 + 2 rows > max_batch 4 -> two predict calls, each
    # request's slice intact
    a.submit([1, 2, 3], _mk_done(got))
    a.submit([4, 5], _mk_done(got))
    a.pump()
    assert calls == [3, 2]
    assert np.array_equal(got[0][0], [0.0, 1.0, 2.0])
    assert np.array_equal(got[1][0], [0.0, 1.0])
    # hop decomposition present on every completion
    assert {"queue_s", "assemble_s", "predict_s"} <= set(got[0][2])
    # shed rule: a full queue rejects synchronously...
    a.submit([1] * 5, _mk_done(got))
    with pytest.raises(ServeOverload):
        a.submit([1, 2], _mk_done(got))
    assert a.shed == 1
    a.pump()
    # ...but one oversized request against an EMPTY queue is admitted
    a.submit([1] * 9, _mk_done(got))
    a.pump()
    assert calls[-1] == 9
    # deadline is judged at pop: a lapsed budget completes with
    # ServeDeadline and never reaches the predict fn
    n_calls = len(calls)
    a.submit([1], _mk_done(got), deadline_ms=0.001)
    import time
    time.sleep(0.005)
    a.pump()
    assert len(calls) == n_calls and a.expired == 1
    assert isinstance(got[-1][3], ServeDeadline)
    # drain close scores everything pending; submit-after-close raises
    a.submit([7], _mk_done(got))
    a.close(drain=True)
    assert got[-1][3] is None and np.array_equal(got[-1][0], [0.0])
    with pytest.raises(RuntimeError):
        a.submit([8], _mk_done(got))


# --- evloop server protocol surface ------------------------------------------

@pytest.fixture()
def trained(tmp_path):
    from hivemall_tpu.io.libsvm import synthetic_classification
    from hivemall_tpu.models.linear import GeneralClassifier
    ds, _ = synthetic_classification(120, 64, seed=11)
    t = GeneralClassifier(OPTS)
    t.fit(ds)
    path = os.path.join(tmp_path, f"{t.NAME}-step{t._t:010d}.npz")
    t.save_bundle(path)
    return t, ds, str(tmp_path), path


def _engine(ckdir, **kw):
    from hivemall_tpu.serve.engine import PredictEngine
    kw.setdefault("warmup", False)
    kw.setdefault("max_batch", 8)      # few compile buckets: tier-1 budget
    return PredictEngine("train_classifier", OPTS, checkpoint_dir=ckdir,
                         **kw)


def _feat_rows(ds, n):
    out = []
    for i in range(n):
        idx, val = ds.row(i)
        out.append([f"{int(a)}:{float(v)!r}" for a, v in zip(idx, val)])
    return out


def _ref(t, rows):
    from hivemall_tpu.io.sparse import SparseDataset
    parsed = [t._parse_row(r) for r in rows]
    return t.predict_proba(SparseDataset.from_rows(parsed,
                                                   [1.0] * len(parsed)))


def _evsrv(eng, **kw):
    from hivemall_tpu.serve.evloop import EvloopPredictServer
    kw.setdefault("max_delay_ms", 1.0)
    return EvloopPredictServer(eng, port=0, watch=False, slo=False,
                               **kw).start()


def _srv(plane, eng):
    if plane == "evloop":
        return _evsrv(eng)
    from hivemall_tpu.serve.http import PredictServer
    return PredictServer(eng, port=0, max_delay_ms=1.0, watch=False,
                         slo=False).start()


def test_evloop_frame_bitmatches_json_and_mixed_clients(trained):
    """Binary frames and JSON strings negotiate per-request on ONE
    listener and score to identical bits — a frame client and a string
    client share a replica without either noticing the other."""
    from hivemall_tpu.serve.client import RawHTTPClient
    t, ds, ckdir, _ = trained
    rows = _feat_rows(ds, 6)
    ref = _ref(t, rows)
    srv = _evsrv(_engine(ckdir))
    cli_s = cli_b = None
    try:
        cli_s = RawHTTPClient("127.0.0.1", srv.port)
        cli_b = RawHTTPClient("127.0.0.1", srv.port)
        code, rs = cli_s.post_json("/predict", {"rows": rows})
        assert code == 200
        parsed = [t._parse_row(r) for r in rows]
        code, rb = cli_b.post_frame("/predict", parsed)
        assert code == 200
        js = np.asarray(rs["scores"], np.float32)
        fb = np.asarray(rb["scores"], np.float32)
        assert np.array_equal(js, ref)
        assert np.array_equal(fb, ref)          # bit-match across formats
        assert rb["model_step"] == rs["model_step"]
        # interleave the two protocols on their kept-alive connections
        for i in range(3):
            _, r1 = cli_b.post_frame("/predict", [parsed[i]])
            _, r2 = cli_s.post_json("/predict", {"rows": [rows[i]]})
            assert np.float32(r1["scores"][0]) == ref[i]
            assert np.float32(r2["scores"][0]) == ref[i]
    finally:
        for c in (cli_s, cli_b):
            if c is not None:
                c.close()
        srv.stop()


def test_evloop_malformed_frame_400_closes_without_poisoning_loop(trained):
    """A desynced binary stream answers 400 AND closes (no resync is
    possible mid-connection) — and the event loop keeps serving other
    connections untouched."""
    from hivemall_tpu.serve.client import (RawConn, RawHTTPClient,
                                           build_request, read_response)
    t, ds, ckdir, _ = trained
    rows = _feat_rows(ds, 2)
    ref = _ref(t, rows)
    srv = _evsrv(_engine(ckdir))
    cli = None
    try:
        conn = RawConn("127.0.0.1", srv.port, timeout=10.0)
        try:
            req = build_request("127.0.0.1", srv.port, "/predict",
                                b"JUNKJUNKJUNK", ctype=CONTENT_TYPE_FRAME)
            conn.sock.sendall(req)
            status, lines, payload = read_response(conn.rfile)
            assert status == 400
            assert b"error" in payload
            assert any(h.lower().startswith(b"connection: close")
                       for h in lines)
            # the server actually hangs up: EOF, not a stalled read
            conn.sock.settimeout(5.0)
            assert conn.rfile.read(1) == b""
        finally:
            conn.close()
        # a truncated frame (valid magic, lying row count) also tears down
        conn = RawConn("127.0.0.1", srv.port, timeout=10.0)
        try:
            parsed = [t._parse_row(r) for r in rows]
            cut = encode_frame(parsed)[:-3]
            conn.sock.sendall(build_request(
                "127.0.0.1", srv.port, "/predict", cut,
                ctype=CONTENT_TYPE_FRAME))
            status, lines, _ = read_response(conn.rfile)
            assert status == 400
            assert any(h.lower().startswith(b"connection: close")
                       for h in lines)
        finally:
            conn.close()
        # the loop is not poisoned: fresh clients, both protocols, still
        # score to the exact reference (a malformed JSON 400 keeps alive)
        cli = RawHTTPClient("127.0.0.1", srv.port)
        code, _ = cli.request("POST", "/predict", b"{nope")
        assert code == 400
        code, r = cli.post_json("/predict", {"rows": rows})  # same conn
        assert code == 200
        assert np.array_equal(np.asarray(r["scores"], np.float32), ref)
        code, r = cli.post_frame("/predict",
                                 [t._parse_row(x) for x in rows])
        assert code == 200
        assert np.array_equal(np.asarray(r["scores"], np.float32), ref)
    finally:
        if cli is not None:
            cli.close()
        srv.stop()


def test_hop_header_parts_sum_on_both_planes(trained):
    """Every /predict response decomposes its wall time into hop parts
    that sum to total on BOTH planes; the evloop plane adds a leading
    ``loop`` component (event-loop dwell) the threaded plane lacks."""
    from hivemall_tpu.serve.client import RawHTTPClient
    t, ds, ckdir, _ = trained
    rows = _feat_rows(ds, 2)
    threaded_keys = {"parse", "queue", "assemble", "predict", "other",
                     "total"}
    for plane in ("threaded", "evloop"):
        srv = _srv(plane, _engine(ckdir))
        cli = RawHTTPClient("127.0.0.1", srv.port)
        try:
            code, _ = cli.post_json("/predict", {"rows": rows})
            assert code == 200
            hdrs = {k.lower(): v for k, v in cli.last_headers.items()}
            hop = dict(kv.split("=")
                       for kv in hdrs["x-hivemall-hop"].split(","))
            want = (threaded_keys | {"loop"} if plane == "evloop"
                    else threaded_keys)
            assert set(hop) == want, plane
            total = float(hop.pop("total"))
            parts = sum(float(v) for v in hop.values())
            # "other" absorbs the residual -> the decomposition is
            # additive up to the 3-decimal header rounding
            assert parts == pytest.approx(total, abs=0.02), plane
            assert float(hop["predict"]) > 0, plane
        finally:
            cli.close()
            srv.stop()


def test_evloop_uds_transport_bitmatches_tcp(trained, tmp_path):
    """One evloop server listens on TCP and a unix socket at once; the
    UDS fast path returns byte-identical scores and survives keep-alive
    reuse (the router's co-located transport)."""
    from hivemall_tpu.serve.client import RawHTTPClient
    t, ds, ckdir, _ = trained
    rows = _feat_rows(ds, 3)
    ref = _ref(t, rows)
    uds = os.path.join(str(tmp_path), "replica.sock")
    srv = _evsrv(_engine(ckdir), uds_path=uds)
    tcp = via_uds = None
    try:
        assert srv.uds_path == uds and os.path.exists(uds)
        tcp = RawHTTPClient("127.0.0.1", srv.port)
        via_uds = RawHTTPClient("127.0.0.1", srv.port, uds=uds)
        code, ru = via_uds.post_json("/predict", {"rows": rows})
        assert code == 200
        code, rt = tcp.post_json("/predict", {"rows": rows})
        assert code == 200
        assert np.array_equal(np.asarray(ru["scores"], np.float32), ref)
        assert np.array_equal(np.asarray(rt["scores"], np.float32), ref)
        # keep-alive reuse over the unix socket, frames included
        for i in range(2):
            _, r = via_uds.post_frame("/predict", [t._parse_row(rows[i])])
            assert np.float32(r["scores"][0]) == ref[i]
        # /healthz answers on the UDS listener too
        code, hz = via_uds.post_json("/healthz", {})
        assert code == 200 and hz["status"] == "ok"
    finally:
        for c in (tcp, via_uds):
            if c is not None:
                c.close()
        srv.stop()
    assert not os.path.exists(uds)     # teardown unlinks the socket file


@pytest.mark.parametrize("plane,precision,wire", [
    ("threaded", "f32", "json"), ("threaded", "int8", "json"),
    ("evloop", "f32", "json"), ("evloop", "int8", "json"),
    ("evloop", "int8", "frame")])
def test_plane_tier_matrix_answers_every_request(trained, plane, precision,
                                                 wire):
    """Every point of the plane x tier x wire matrix, driven by concurrent
    keep-alive clients sending pre-built requests: every request answered
    200, nothing shed or expired, every row counted, and the scores
    right for the tier (f32 to the bit, int8 inside its margin)."""
    import json
    import threading
    from hivemall_tpu.serve.client import RawHTTPClient
    t, ds, ckdir, _ = trained
    rows = _feat_rows(ds, 8)
    ref = _ref(t, rows)
    eng = _engine(ckdir, precision=precision)
    srv = _srv(plane, eng)
    n_clients, per_client = 3, 8
    try:
        if wire == "frame":
            ctype = CONTENT_TYPE_FRAME
            bodies = [encode_frame([t._parse_row(r)]) for r in rows]
        else:
            ctype = "application/json"
            bodies = [json.dumps({"rows": [r]}).encode() for r in rows]
        reqs = [RawHTTPClient.build("127.0.0.1", srv.port, "/predict", b,
                                    ctype=ctype) for b in bodies]
        codes = []                  # (status, carried its hop header)

        def client(k):
            cli = RawHTTPClient("127.0.0.1", srv.port)
            try:
                for i in range(per_client):
                    code = cli.exchange(reqs[(i + k) % len(reqs)])
                    codes.append((code, cli.last_hops is not None))
            finally:
                cli.close()

        ths = [threading.Thread(target=client, args=(k,))
               for k in range(n_clients)]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        assert codes == [(200, True)] * (n_clients * per_client)
        st = srv.batcher.stats()
        assert st["shed"] == 0 and st["expired"] == 0
        assert st["rows"] == n_clients * per_client
        assert st["mean_batch_rows"] >= 1.0
        cli = RawHTTPClient("127.0.0.1", srv.port)
        try:
            code, r = cli.post_json("/predict", {"rows": rows})
        finally:
            cli.close()
        got = np.asarray(r["scores"], np.float32)
        assert code == 200
        if precision == "f32":
            assert np.array_equal(got, ref)
        else:
            assert eng.arena_mapped_bytes > 0
            assert np.abs(got - ref).max() < 0.05
    finally:
        srv.stop()
