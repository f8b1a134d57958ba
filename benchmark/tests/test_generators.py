"""The data generator and the load generator's schedule are pure functions
of the seed, take the driver's large seeds, and keep the shapes the
configurations state."""

import json
import os

import numpy as np
import pytest

from harness import data, loadgen
from harness.common import BENCH_DIR, load_json

CFG = load_json(os.path.join(BENCH_DIR, "configs", "ffm_criteo_joint.json"))
BIG = 2 ** 31 + 12345


def spec(dims=1 << 28):
    return data.RowSpec(CFG["data"], dims)


def test_rows_reproducible_and_thread_independent():
    a = data.make_rows(spec(), 70000, BIG, threads=1)
    b = data.make_rows(spec(), 70000, BIG, threads=5)
    c = data.make_rows(spec(), 70000, BIG + 1)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


def test_rows_keep_the_shape():
    ids, labels = data.make_rows(spec(1 << 26), 50000, 7)
    assert ids.shape == (50000, 39) and ids.dtype == np.int32
    assert ids.min() >= 1 and ids.max() <= (1 << 26) - 1
    assert set(np.unique(labels)) == {-1.0, 1.0}
    assert 0.2 < (labels > 0).mean() < 0.3
    # Zipf ids: a batch repeats its ids many times over
    batch = ids[:32768].ravel()
    assert len(np.unique(batch)) < 0.2 * len(batch)
    # small-vocabulary fields stay inside their vocabulary
    assert len(np.unique(ids[:, 13 + 5])) <= 3


def test_fingerprints_tell_rows_apart():
    ids, labels = data.make_rows(spec(), 40000, 3)
    fp = data.fingerprints(ids, labels)
    assert len(np.unique(fp)) == len(fp)
    flipped = labels.copy()
    flipped[0] = -flipped[0]
    assert data.fingerprints(ids, flipped)[0] != fp[0]


def test_shards_round_trip(tmp_path):
    import pyarrow.parquet as pq
    ids, labels = data.make_rows(spec(), 3000, 5)
    written = data.write_shards(ids, labels, str(tmp_path), 1024, True)
    files = sorted(os.listdir(tmp_path))
    assert files == [f"shard-{k:05d}.parquet" for k in range(3)]
    assert written == sum(os.path.getsize(tmp_path / f) for f in files)
    t = pq.read_table(tmp_path / files[1])
    assert t.column_names == ["indices", "label", "fields"]
    got = np.asarray(t.column("indices").combine_chunks().flatten())
    assert np.array_equal(got.reshape(-1, 39), ids[1024:2048])
    # a second write leaves only its own files behind
    data.write_shards(ids[:1000], labels[:1000], str(tmp_path), 1024, False)
    assert os.listdir(tmp_path) == ["shard-00000.parquet"]


def load_spec(seed=BIG, rate=50.0):
    tr = load_json(os.path.join(BENCH_DIR, "traffic", "predict_steady.json"))
    return {"seed": seed, "rate_rps": rate, "seconds": 20.0,
            "lead_s": tr["lead_s"], "connections": tr["connections"],
            "hot_connections": tr["hot_connections"],
            "hot_share": tr["hot_share"], "rows_median": tr["rows_median"],
            "rows_p95": tr["rows_p95"], "rows_max": tr["rows_max"],
            "pool_rows": 1024, "data": CFG["data"], "dims": 1 << 28}


def test_schedule_is_a_function_of_the_spec():
    a, b = loadgen.schedule(load_spec()), loadgen.schedule(load_spec())
    c = loadgen.schedule(load_spec(seed=BIG + 1))
    for k in a:
        assert np.array_equal(a[k], b[k])
    # another seed draws other arrivals, connections, sizes and rows
    for k in a:
        assert not np.array_equal(a[k], c[k])
    assert not np.array_equal(loadgen.pool_rows(load_spec()),
                              loadgen.pool_rows(load_spec(seed=BIG + 1)))


def test_schedule_keeps_the_mix():
    s = loadgen.schedule(load_spec(rate=400.0))
    n = len(s["due"])
    assert abs(n / 21.0 - 400.0) < 40.0
    assert np.all(np.diff(s["due"]) >= 0) and s["due"][-1] < 21.0
    assert 0.85 < np.mean(s["conn"] < 2) < 0.95           # two hot ones
    assert s["conn"].max() == 15
    assert s["rows"].min() >= 1 and s["rows"].max() <= 256
    assert 12 <= np.median(s["rows"]) <= 20
    assert 90 <= np.percentile(s["rows"], 95) <= 170


def test_request_bodies():
    sp = load_spec()
    pool = loadgen.pool_rows(sp)
    assert np.array_equal(pool, loadgen.pool_rows(sp))
    ids = loadgen.request_ids(pool, 1020, 7)             # wraps the pool
    assert ids.shape == (7, 39) and np.array_equal(ids[4], pool[0])
    rows = json.loads(loadgen.body_of(loadgen.rows_json(ids)))["rows"]
    assert len(rows) == 7 and len(rows[0]) == 39
    assert rows[0][38] == f"38:{int(ids[0, 38])}:1"


def test_stale_keepalive_connection_is_retried_once():
    """A server that closes a connection after every response (as the
    program's idle reaper does to one that sat idle): the next request on
    it goes out again on a fresh connection and is answered."""
    import socket
    import threading
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    port = srv.getsockname()[1]

    def serve():
        for _ in range(2):
            c, _addr = srv.accept()
            f = c.makefile("rb")
            n = 0
            while True:
                h = f.readline()
                if h in (b"\r\n", b""):
                    break
                if h.lower().startswith(b"content-length:"):
                    n = int(h.split(b":")[1])
            f.read(n)
            body = b'{"scores": [0.5], "model_step": 8}'
            c.sendall(b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n\r\n%s"
                      % (len(body), body))
            f.close()
            c.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    out = []
    todo = [(0, 0.0, b'{"rows": [["0:1:1"]]}'),
            (1, 0.05, b'{"rows": [["0:2:1"]]}')]
    import time
    loadgen._worker("127.0.0.1", port, todo, time.monotonic(), out, 5.0)
    t.join(5)
    srv.close()
    assert [r["status"] for r in out] == [200, 200]
    assert [r.get("retried", 0) for r in out] == [0, 1]
