"""MIX subsystem tests — on-mesh collectives (8-device CPU sim) and the async
host mix service over real localhost sockets, mirroring the reference's
in-process-MixServer test strategy (SURVEY.md §5.3)."""

import numpy as np
import pytest

from hivemall_tpu.parallel.averaging import (argmin_kld, merge_model_tables,
                                             voted_avg, weight_voted_avg)


# --- post-hoc averaging -----------------------------------------------------

def test_voted_avg():
    assert voted_avg([1.0, 2.0, -3.0]) == 1.5       # majority positive
    assert voted_avg([-1.0, -2.0, 3.0]) == -1.5     # majority negative
    assert voted_avg([]) == 0.0


def test_weight_voted_avg():
    # negative mass dominates despite fewer positives
    assert weight_voted_avg([1.0, -10.0, 2.0]) == -10.0
    assert weight_voted_avg([5.0, -1.0]) == 5.0


def test_argmin_kld_prefers_confident():
    w, c = argmin_kld([1.0, 3.0], [0.1, 10.0])   # first replica confident
    assert abs(w - 1.0) < 0.05
    assert c < 0.1


def test_merge_model_tables():
    t1 = {"a": 1.0, "b": -1.0}
    t2 = {"a": 3.0, "c": 2.0}
    m = merge_model_tables([t1, t2], "avg")
    assert m["a"] == 2.0 and m["b"] == -1.0 and m["c"] == 2.0


# --- on-mesh replica mixing -------------------------------------------------

def test_replica_step_mixes_to_mean():
    import jax
    import jax.numpy as jnp
    from hivemall_tpu.ops.losses import get_loss
    from hivemall_tpu.ops.optimizers import make_optimizer
    from hivemall_tpu.parallel.mesh import make_mesh
    from hivemall_tpu.parallel.mix import make_replica_train_step

    ndev = len(jax.devices())
    assert ndev == 8, "conftest should give 8 CPU devices"
    mesh = make_mesh(dp=ndev)
    N, B, L = 64, 16, 4
    opt = make_optimizer("adagrad", reg="no", eta_scheme="fixed", eta0=0.5)
    step = make_replica_train_step(mesh, get_loss("logloss"), opt, mix_every=4)

    rng = np.random.default_rng(0)
    w = jnp.zeros((ndev, N))
    state = {k: jnp.zeros((ndev, N))
             for k in opt.init(N)}
    # each replica sees a different feature -> weights diverge, then mix
    idx = np.zeros((B * ndev, L), np.int32)
    for d in range(ndev):
        idx[d * B:(d + 1) * B, 0] = d + 1
    val = np.ones((B * ndev, L), np.float32)
    val[:, 1:] = 0.0
    lab = np.ones(B * ndev, np.float32)

    for t in range(3):   # steps 1..3: no mix yet
        w, state, _ = step(w, state, float(t),
                           jnp.asarray(idx), jnp.asarray(val),
                           jnp.asarray(lab))
    w_before = np.asarray(w)
    # replicas diverged: each learned only its own feature
    assert w_before[0, 1] > 0 and w_before[0, 2] == 0.0
    w, state, _ = step(w, state, 3.0, jnp.asarray(idx), jnp.asarray(val),
                       jnp.asarray(lab))   # t=3 -> (t+1)%4==0 -> mix
    w_after = np.asarray(w)
    # after pmean all replicas are identical
    for d in range(1, 8):
        np.testing.assert_allclose(w_after[d], w_after[0], rtol=1e-6)
    # mixing pulled replica 0's private feature toward the replica mean
    # (only 1 of 8 replicas ever updates feature 1, so the mean is ~1/8 of
    # the local weight; exact value includes step 4's local update)
    assert 0 < w_after[0, 1] < 0.5 * w_before[0, 1]
    assert w_after[0, 1] >= w_before[:, 1].mean()


def test_argmin_kld_mix_on_mesh():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from hivemall_tpu.parallel.mesh import make_mesh
    from hivemall_tpu.parallel.mix import argmin_kld_mix

    mesh = make_mesh(dp=8)
    w = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    covar = jnp.ones((8, 1)) * jnp.asarray(
        [0.1, 10, 10, 10, 10, 10, 10, 10]).reshape(8, 1)

    f = jax.shard_map(
        lambda a, c: argmin_kld_mix(a[0], c[0], "dp")[0][None],
        mesh=mesh, in_specs=(P("dp", None), P("dp", None)),
        out_specs=P("dp", None))
    mixed = np.asarray(f(w, covar))
    assert abs(mixed[0, 0]) < 0.5     # confident replica 0 (w=0) dominates


# --- async host mix service -------------------------------------------------

def test_mix_server_roundtrip():
    from hivemall_tpu.parallel.mix_service import (EVENT_AVERAGE, MixClient,
                                                   MixMessage, MixServer)
    srv = MixServer().start()
    try:
        c = MixClient(f"127.0.0.1:{srv.port}", "g1", threshold=1)
        c._connect()
        msg = MixMessage(EVENT_AVERAGE, "g1",
                         np.asarray([5], np.int64),
                         np.asarray([2.0], np.float32),
                         np.asarray([1.0], np.float32),
                         np.asarray([1], np.int32))
        c._sock.sendall(msg.encode())
        r1 = c._read_reply()
        assert r1.weights[0] == 2.0            # first fold: avg == itself
        msg2 = MixMessage(EVENT_AVERAGE, "g1",
                          np.asarray([5], np.int64),
                          np.asarray([4.0], np.float32),
                          np.asarray([1.0], np.float32),
                          np.asarray([1], np.int32))
        c._sock.sendall(msg2.encode())
        r2 = c._read_reply()
        assert abs(r2.weights[0] - 3.0) < 1e-6  # (2+4)/2
    finally:
        srv.stop()


def test_trainers_converge_via_mix_service():
    """Two replicas with skewed shards of the same feature space; mixing pulls
    their weights for the shared feature toward a common value (the
    replicas-converge-to-the-mean assertion of the reference's
    ModelMixingSuite). Note the protocol only mixes features a replica itself
    ships — disjoint features never propagate, matching the reference."""
    from hivemall_tpu.models.linear import GeneralClassifier
    from hivemall_tpu.parallel.mix_service import MixServer

    def train(mix_opts: str):
        opts = ("-dims 64 -mini_batch 8 -eta fixed -eta0 0.5 -reg no "
                + mix_opts)
        a = GeneralClassifier(opts)
        b = GeneralClassifier(opts)
        for i in range(64):
            a.process(["1:1.0"], 1)              # A: feature 1 always +1
            b.process(["1:1.0"], -1 if i % 4 == 0 else 1)  # B: 25% conflicted
        return dict(a.close()), dict(b.close()), a, b

    srv = MixServer().start()
    try:
        ma, mb, a, b = train(f"-mix 127.0.0.1:{srv.port} -mix_session s1 "
                             f"-mix_threshold 2")
        assert a._mixer.exchanges > 0 and b._mixer.exchanges > 0
        mixed_gap = abs(ma["1"] - mb["1"])
        ua, ub, _, _ = train("")                 # unmixed control
        unmixed_gap = abs(ua["1"] - ub["1"])
        assert mixed_gap < 0.5 * unmixed_gap, (mixed_gap, unmixed_gap)
    finally:
        srv.stop()


def test_mix_client_fail_soft():
    """Dead server => training continues unmixed (reference §3.16
    fail-soft). With a zero breaker cooldown every exchange probes, so the
    breaker re-trips until the trip budget is spent and the client goes
    PERMANENTLY dead — the old first-error kill-switch as the breaker's
    end state, not its first reaction."""
    from hivemall_tpu.models.linear import GeneralClassifier
    clf = GeneralClassifier("-dims 32 -mini_batch 4 -eta0 0.5 "
                            "-mix 127.0.0.1:1 -mix_threshold 1 "
                            "-mix_retries 0 -mix_backoff 0.01 "
                            "-mix_breaker_cooldown 0")
    for _ in range(16):
        clf.process(["1:1.0"], 1)
        clf.process(["2:1.0"], -1)
    model = dict(clf.close())
    assert clf._mixer.alive is False
    assert clf._mixer.degraded
    assert clf._mixer.counters()["breaker_state"] == "dead"
    assert clf._mixer.dropped_exchanges > 0
    assert model["1"] > 0 > model["2"]   # learned fine without the server


def test_mix_client_stays_degraded_not_dead_under_default_breaker():
    """With the default cooldown the breaker opens but the trip budget is
    not spent inside a fast run: the client reports degraded (exchanges
    suspended), stays alive for a later half-open probe, and training is
    unaffected."""
    from hivemall_tpu.models.linear import GeneralClassifier
    clf = GeneralClassifier("-dims 32 -mini_batch 4 -eta0 0.5 "
                            "-mix 127.0.0.1:1 -mix_threshold 1 "
                            "-mix_retries 0 -mix_backoff 0.01")
    for _ in range(16):
        clf.process(["1:1.0"], 1)
        clf.process(["2:1.0"], -1)
    model = dict(clf.close())
    assert clf._mixer.degraded
    assert clf._mixer.alive             # breaker open, not permanent
    assert clf._mixer.counters()["breaker_trips"] >= 1
    assert model["1"] > 0 > model["2"]


def test_mix_fault_injection_drop():
    """Server that hangs up on every 2nd request: retry + reconnect rides
    through EVERY drop — all exchanges complete, the client never
    degrades, and the reconnect counter shows the recoveries (the old
    client died permanently on the first drop)."""
    from hivemall_tpu.models.linear import GeneralClassifier
    from hivemall_tpu.parallel.mix_service import MixServer
    srv = MixServer()
    srv.inject_drop_every = 2            # hang up on every 2nd exchange
    srv.start()
    try:
        clf = GeneralClassifier(
            f"-dims 32 -mini_batch 4 -eta0 0.5 -reg no -eta fixed "
            f"-mix 127.0.0.1:{srv.port} -mix_threshold 1 -mix_backoff 0.01")
        for _ in range(32):
            clf.process(["1:1.0"], 1)
            clf.process(["2:1.0"], -1)
        model = dict(clf.close())
        assert clf._mixer.alive                   # rode through every drop
        assert not clf._mixer.degraded
        assert clf._mixer.exchanges >= 8
        assert clf._mixer.reconnects >= 1
        assert clf._mixer.transport_errors >= 1
        assert model["1"] > 0 > model["2"]        # training kept going
    finally:
        srv.stop()


def test_mix_fault_injection_delay():
    """Server slower than the client timeout: every exchange times out, the
    breaker trips through its budget (zero cooldown) and the client
    degrades permanently — fail-soft, training unaffected."""
    from hivemall_tpu.models.linear import GeneralClassifier
    from hivemall_tpu.parallel.mix_service import MixServer
    srv = MixServer()
    srv.inject_delay_s = 0.5
    srv.start()
    try:
        clf = GeneralClassifier(
            f"-dims 32 -mini_batch 4 -eta0 0.5 -reg no -eta fixed "
            f"-mix 127.0.0.1:{srv.port} -mix_threshold 1 "
            f"-mix_timeout 0.05 -mix_retries 0 -mix_backoff 0.01 "
            f"-mix_breaker_cooldown 0")
        for _ in range(16):
            clf.process(["1:1.0"], 1)
            clf.process(["2:1.0"], -1)
        model = dict(clf.close())
        assert clf._mixer.alive is False
        assert model["1"] > 0 > model["2"]
    finally:
        srv.stop()


def test_close_group_releases_socket_on_dead_client():
    """Satellite: a permanently degraded client must still close/clear its
    half-open socket on close_group (the old guard skipped the cleanup
    whenever alive was False, leaking the fd)."""
    from hivemall_tpu.parallel.mix_service import MixClient, MixServer
    srv = MixServer().start()
    try:
        c = MixClient(f"127.0.0.1:{srv.port}", "g1", threshold=1)
        c._connect()
        sock = c._sock
        c.alive = False                  # degraded mid-run, socket open
        c.close_group()
        assert c._sock is None
        assert sock.fileno() == -1       # actually closed, not leaked
        c.close_group()                  # idempotent
    finally:
        srv.stop()


def test_mix_client_counters_surface():
    """counters() — the MixServer.counters() peer — reports a healthy
    client as closed-breaker/alive with its exchange tally."""
    from hivemall_tpu.models.linear import GeneralClassifier
    from hivemall_tpu.parallel.mix_service import MixServer
    srv = MixServer().start()
    try:
        clf = GeneralClassifier(
            f"-dims 32 -mini_batch 4 -eta0 0.5 -reg no -eta fixed "
            f"-mix 127.0.0.1:{srv.port} -mix_threshold 1")
        for _ in range(8):
            clf.process(["1:1.0"], 1)
        dict(clf.close())
        c = clf._mixer.counters()
        assert c["exchanges"] >= 1 and c["alive"]
        assert c["breaker_state"] == "closed" and not clf._mixer.degraded
        assert c["dropped_exchanges"] == 0 == c["transport_errors"]
        for k in ("reconnects", "breaker_trips", "touched_overflow"):
            assert k in c
    finally:
        srv.stop()


def test_covariance_trainers_mix_argmin_kld_e2e():
    """CW/AROW replicas mix through the TCP service via argmin-KLD
    (precision-weighted Gaussian posterior merge, SURVEY.md §3.16): the
    mixed weight sits between the replicas' locals, nearer the confident
    (low-variance) one, and the shared covariance shrinks."""
    import numpy as np
    from hivemall_tpu.models.classifier import AROWTrainer
    from hivemall_tpu.parallel.mix_service import (EVENT_ARGMIN_KLD,
                                                   MixServer)

    srv = MixServer().start()
    try:
        opts = (f"-dims 64 -mini_batch 4 -mix 127.0.0.1:{srv.port} "
                f"-mix_session kld -mix_threshold 2")
        a = AROWTrainer(opts)
        b = AROWTrainer(opts)
        assert a._mixer.event == EVENT_ARGMIN_KLD
        # A sees feature 1 often (confident); B sees it rarely (uncertain)
        for i in range(48):
            a.process(["1:1.0"], 1)
            b.process(["1:1.0", "2:1.0"], 1 if i % 2 else -1)
        ma = dict()
        for row in a.close():
            ma[row[0]] = row[1]
        assert a._mixer.exchanges > 0 and b._mixer.exchanges > 0
        # covariance for the shared feature shrank below the prior 1.0
        sig_a = np.asarray(a.sigma)
        assert sig_a[1] < 1.0
        assert np.isfinite(ma["1"])
    finally:
        srv.stop()


def test_mix_exchange_is_touched_keys_only():
    """The client ships/folds only touched keys — never the O(dims) table
    (VERDICT r1 weak #5). Untouched weights must be bit-identical after an
    exchange, and the sparse accessors must round-trip."""
    import numpy as np
    from hivemall_tpu.models.linear import GeneralClassifier
    from hivemall_tpu.parallel.mix_service import MixServer

    srv = MixServer().start()
    try:
        opts = (f"-dims 1024 -mini_batch 4 -eta fixed -eta0 0.5 -reg no "
                f"-mix 127.0.0.1:{srv.port} -mix_session t -mix_threshold 1")
        t = GeneralClassifier(opts)
        # seed an untouched weight far from zero via the sparse setter
        t._set_weights_at(np.asarray([900]), np.asarray([7.5], np.float32))
        before = float(t._get_weights_at(np.asarray([900]))[0])
        for _ in range(8):
            t.process(["1:1.0", "2:0.5"], 1)
        assert t._mixer.exchanges > 0
        after = float(t._get_weights_at(np.asarray([900]))[0])
        assert after == before == 7.5
    finally:
        srv.stop()


def test_fm_fused_layout_mixes_linear_weights():
    """The packed fused FM table stores w inside T (column K of each
    feature's block); the mix client's sparse weight access must read and
    fold mixed weights through the packed-layout overrides."""
    import numpy as np
    from hivemall_tpu.models.fm import FMTrainer
    from hivemall_tpu.parallel.mix_service import MixServer

    srv = MixServer().start()
    try:
        opts = (f"-dims 64 -factors 4 -classification -opt adagrad "
                f"-eta fixed -eta0 0.5 -mini_batch 8 "
                f"-mix 127.0.0.1:{srv.port} -mix_session fmf "
                f"-mix_threshold 2")
        a = FMTrainer(opts)
        b = FMTrainer(opts)
        assert a.fm_layout == "fused"
        for i in range(64):
            a.process(["1:1.0"], 1)
            b.process(["1:1.0"], -1 if i % 4 == 0 else 1)
        ma = {r[0]: r[1] for r in a.model_rows()}
        mb = {r[0]: r[1] for r in b.model_rows()}
        assert a._mixer.exchanges > 0 and b._mixer.exchanges > 0
        # mixed replicas' linear weight for the shared feature is pulled
        # toward a common value
        assert abs(ma["1"] - mb["1"]) < 0.35, (ma["1"], mb["1"])
    finally:
        srv.stop()


def _self_signed_cert(tmp_path):
    """Self-signed localhost cert via the cryptography package (skip the
    TLS tests cleanly where the container doesn't ship it)."""
    import datetime
    pytest.importorskip("cryptography")
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    from cryptography.x509.oid import NameOID

    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "127.0.0.1")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder()
            .subject_name(name).issuer_name(name)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(minutes=5))
            .not_valid_after(now + datetime.timedelta(days=1))
            .add_extension(x509.SubjectAlternativeName(
                [x509.IPAddress(__import__("ipaddress")
                                .ip_address("127.0.0.1"))]), critical=False)
            .sign(key, hashes.SHA256()))
    cert_p = tmp_path / "srv.pem"
    key_p = tmp_path / "srv.key"
    cert_p.write_bytes(cert.public_bytes(serialization.Encoding.PEM))
    key_p.write_bytes(key.private_bytes(
        serialization.Encoding.PEM, serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption()))
    return str(cert_p), str(key_p)


def test_mix_server_ssl_roundtrip(tmp_path):
    """-ssl (SURVEY.md §3.1): TLS-wrapped exchange against a self-signed
    cert, client verifying via -ssl_cafile; plaintext client against the
    TLS server must fail, not hang."""
    import socket as _socket
    from hivemall_tpu.parallel.mix_service import (
        EVENT_AVERAGE, MixClient, MixMessage, MixServer,
        make_client_ssl_context, make_server_ssl_context)

    cert, key = _self_signed_cert(tmp_path)
    srv = MixServer(ssl_context=make_server_ssl_context(cert, key)).start()
    try:
        c = MixClient(f"127.0.0.1:{srv.port}", "g1", threshold=1,
                      ssl_context=make_client_ssl_context(cafile=cert))
        c._connect()
        assert c._sock.cipher() is not None       # really TLS
        msg = MixMessage(EVENT_AVERAGE, "g1",
                         np.asarray([5], np.int64),
                         np.asarray([2.0], np.float32),
                         np.asarray([1.0], np.float32),
                         np.asarray([1], np.int32))
        c._sock.sendall(msg.encode())
        r1 = c._read_reply()
        assert r1.weights[0] == 2.0
        c.close_group()
        # plaintext client against the TLS port: the server's handshake
        # never completes and the read times out / resets — fail, not hang
        s = _socket.create_connection(("127.0.0.1", srv.port), timeout=1)
        s.settimeout(1)
        try:
            s.sendall(msg.encode())
            # the handshake fails: reads must terminate (EOF, a TLS alert
            # record — first byte 0x15 — or an OSError), never a valid
            # 4-byte little-endian MixMessage length frame
            try:
                got = s.recv(64)
                assert got == b"" or got[0] == 0x15, got
            except OSError:
                pass
        finally:
            s.close()
    finally:
        srv.stop()


def test_trainer_ssl_option_mixes(tmp_path):
    """-mix ... -ssl -ssl_cafile on a real trainer: exchanges flow over
    TLS and weights still fold (end-to-end -ssl parity)."""
    from hivemall_tpu.models.linear import GeneralClassifier
    from hivemall_tpu.parallel.mix_service import (MixServer,
                                                   make_server_ssl_context)

    cert, key = _self_signed_cert(tmp_path)
    srv = MixServer(ssl_context=make_server_ssl_context(cert, key)).start()
    try:
        t = GeneralClassifier(
            f"-dims 256 -loss logloss -opt adagrad -mini_batch 16 "
            f"-mix 127.0.0.1:{srv.port} -mix_threshold 1 "
            f"-ssl -ssl_cafile {cert}")
        rng = np.random.default_rng(0)
        for _ in range(48):
            i = int(rng.integers(1, 200))
            t.process([f"{i}:1"], 1 if i % 2 else -1)
        list(t.close())
        assert t._mixer.alive and t._mixer.exchanges > 0
        assert srv.counters()["requests"] > 0
    finally:
        srv.stop()
