"""Framed-transport unit tests (ISSUE 12): wire framing, stream
multiplexing + CANCEL, deadline propagation, connection AUTH, the
scatter pool's hygiene bounds (idle TTL + per-URL cap), hedge-loser
cancellation on the legacy HTTP hop, and the replica-side result
cache's epoch discipline — all in-process and CPU-cheap."""

from __future__ import annotations

import json
import socket
import threading
import time

import pytest

from oryx_tpu.cluster import transport as tr
from oryx_tpu.cluster.membership import Heartbeat, MembershipRegistry
from oryx_tpu.cluster.result_cache import ShardResultCache
from oryx_tpu.cluster.scatter import ScatterGather, _Pool
from oryx_tpu.common.config import from_dict
from oryx_tpu.lambda_rt.http import HttpApp, Route
from oryx_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _config(**extra):
    overlay = {
        "oryx.cluster.transport.enabled": True,
        "oryx.cluster.heartbeat-ttl-ms": 60000,
        "oryx.cluster.hedge-after-ms": 80,
        "oryx.cluster.shard-timeout-ms": 5000,
    }
    overlay.update(extra)
    return from_dict(overlay)


# -- wire framing -------------------------------------------------------------

def test_frame_round_trip_over_socketpair():
    a, b = socket.socketpair()
    try:
        lock = threading.Lock()
        payload = tr._pack_msg({"m": "GET", "p": "/x", "h": {"A": "1"}},
                               b"body-bytes")
        tr.write_frame(a, tr.FRAME_REQ, 7, payload, lock)
        rfile = b.makefile("rb")
        ftype, stream, got = tr.read_frame(rfile)
        assert (ftype, stream) == (tr.FRAME_REQ, 7)
        header, body = tr._unpack_msg(got)
        assert header == {"m": "GET", "p": "/x", "h": {"A": "1"}}
        assert body == b"body-bytes"
        a.close()
        with pytest.raises(ConnectionError):
            tr.read_frame(rfile)
    finally:
        for s in (a, b):
            try:
                s.close()
            except OSError:
                pass


def test_oversized_frame_is_rejected_not_buffered():
    a, b = socket.socketpair()
    try:
        a.sendall(tr._HEAD.pack((1 << 30), tr.FRAME_REQ, 1))
        with pytest.raises(ConnectionError):
            tr.read_frame(b.makefile("rb"))
    finally:
        a.close()
        b.close()


def test_heartbeat_tport_round_trips_and_defaults_none():
    hb = Heartbeat(replica="r", shard=0, of=1, url="http://h:1",
                   generation=1, ready=True, tport=4711)
    got = Heartbeat.from_json(hb.to_json())
    assert got.tport == 4711
    # pre-r14 heartbeats carry no tport: parse to None, never KeyError
    legacy = json.dumps({"replica": "r", "shard": 0, "of": 1,
                         "url": "http://h:1", "generation": 1,
                         "ready": True})
    assert Heartbeat.from_json(legacy).tport is None
    assert "tport" not in Heartbeat(
        replica="r", shard=0, of=1, url="u", generation=0,
        ready=False).to_json()


# -- scatter pool hygiene (satellite regression tests) ------------------------

def _sock_pair_entry():
    a, b = socket.socketpair()
    return (a, a.makefile("rb")), b


def test_pool_bounds_per_url_stack():
    pool = _Pool(idle_ttl_sec=60.0, max_per_url=2)
    peers = []
    conns = []
    for _ in range(4):
        conn_rf, peer = _sock_pair_entry()
        peers.append(peer)
        conns.append(conn_rf)
        pool.release("http://r:1", conn_rf)
    # the cap held: only the newest 2 pooled, oldest 2 closed (their
    # peers read EOF; the survivors' peers still see an open socket)
    assert pool.pooled("http://r:1") == 2
    assert pool.cap_evictions == 2
    peers[0].settimeout(2.0)
    assert peers[0].recv(1) == b""  # oldest was shut down
    assert not conns[3][0]._closed
    pool.close()
    for p in peers:
        p.close()


def test_pool_ages_out_idle_sockets_and_drops_dead_urls():
    pool = _Pool(idle_ttl_sec=0.05, max_per_url=8)
    conn_rf, peer = _sock_pair_entry()
    pool.release("http://gone:9", conn_rf)
    time.sleep(0.08)
    # acquire discards the stale socket and falls through to fresh —
    # which we prove by the idle eviction counter and the closed fd
    with pytest.raises(OSError):
        pool.acquire("http://gone:9")  # fresh connect to nowhere
    assert pool.idle_evictions == 1
    peer.settimeout(2.0)
    assert peer.recv(1) == b""  # the idle socket was shut down
    # the sweep reclaims idle sockets of OTHER urls too (long-gone
    # replicas on ephemeral ports) and drops their map keys
    conn2, peer2 = _sock_pair_entry()
    pool.release("http://gone:10", conn2)
    time.sleep(0.08)
    pool._last_sweep = 0.0  # force the time-gated sweep to run now
    conn3, peer3 = _sock_pair_entry()
    pool.release("http://live:1", conn3)
    assert pool.pooled("http://gone:10") == 0
    assert "http://gone:10" not in pool._conns
    assert pool.pooled("http://live:1") == 1
    pool.close()
    for p in (peer, peer2, peer3):
        p.close()


# -- hedge-loser cancellation on the legacy HTTP hop --------------------------

class _StubReplica:
    """Minimal keep-alive HTTP replica with a controllable delay."""

    def __init__(self, delay_sec=0.0, body=b'{"rows": []}'):
        self.delay_sec = delay_sec
        self.body = body
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(16)
        self.port = self.sock.getsockname()[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self.aborted_reads = 0
        self._stop = False
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        rfile = conn.makefile("rb")
        try:
            while True:
                line = rfile.readline()
                if not line:
                    return
                while rfile.readline() not in (b"\r\n", b"\n", b""):
                    pass
                if self.delay_sec:
                    time.sleep(self.delay_sec)
                try:
                    conn.sendall(
                        b"HTTP/1.1 200 OK\r\nContent-Length: "
                        + str(len(self.body)).encode() + b"\r\n\r\n"
                        + self.body)
                except OSError:
                    self.aborted_reads += 1
                    return
        except OSError:
            return
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def close(self):
        self._stop = True
        try:
            self.sock.close()
        except OSError:
            pass


def test_hedge_loser_socket_is_discarded_not_pooled():
    """The satellite's regression: when a hedge sibling wins, the
    loser's in-flight socket is torn down NOW (counted in
    hedge_abandoned) — it must never return to the keep-alive pool
    where its unread response bytes would desync the next request."""
    slow = _StubReplica(delay_sec=2.0)
    fast = _StubReplica(delay_sec=0.0)
    reg = MembershipRegistry(ttl_sec=60.0)
    reg.note(Heartbeat(replica="slow", shard=0, of=1, url=slow.url,
                       generation=1, ready=True))
    reg.note(Heartbeat(replica="fast", shard=0, of=1, url=fast.url,
                       generation=1, ready=True))
    sg = ScatterGather(reg, _config(
        **{"oryx.cluster.transport.enabled": False,
           "oryx.cluster.hedge-after-ms": 60}))
    try:
        # the registry rotates candidate order per query: within a few
        # queries the slow member leads at least once, forcing the
        # hedge whose fast sibling wins
        for _ in range(3):
            assert sg.query_shard(0, "GET", "/x").ok
        assert sg.hedges >= 1
        deadline = time.monotonic() + 5.0
        while sg.hedge_abandoned < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sg.hedge_abandoned >= 1
        # the loser's socket did NOT go back to the pool
        assert sg._pool.pooled(slow.url) == 0
        assert sg._pool.pooled(fast.url) == 1
    finally:
        sg.close()
        slow.close()
        fast.close()


def test_shard_timeout_fault_abandons_inflight_attempts():
    """``router-shard-timeout`` (mode=delay past the deadline) on a
    single-replica shard: the query gives up at the deadline AND the
    stalled attempt's socket is cancelled — counted, never pooled."""
    slow = _StubReplica(delay_sec=3.0)
    sibling = _StubReplica(delay_sec=3.0)
    reg = MembershipRegistry(ttl_sec=60.0)
    reg.note(Heartbeat(replica="a", shard=0, of=1, url=slow.url,
                       generation=1, ready=True))
    reg.note(Heartbeat(replica="b", shard=0, of=1, url=sibling.url,
                       generation=1, ready=True))
    sg = ScatterGather(reg, _config(
        **{"oryx.cluster.transport.enabled": False,
           "oryx.cluster.hedge-after-ms": 40}))
    from oryx_tpu.cluster.scatter import ShardUnavailable
    from oryx_tpu.resilience.policy import Deadline
    faults.inject("router-shard-timeout", mode="delay", times=1,
                  delay_sec=0.2)
    try:
        # 0.8 s are left after the injected delay for the 40 ms hedge
        # to launch the sibling (a loaded box can stall a thread for a
        # few tenths), and the stubs' 3 s are past every timer
        with pytest.raises(ShardUnavailable):
            sg.query_shard(0, "GET", "/x",
                           deadline=Deadline.after(1.0))
        assert faults.fired("router-shard-timeout") == 1
        deadline = time.monotonic() + 10.0
        while sg.hedge_abandoned < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        # both stalled attempts were abandoned at give-up: the pool
        # holds neither of their mid-response sockets
        assert sg.hedge_abandoned >= 2
        assert sg._pool.pooled(slow.url) == 0
        assert sg._pool.pooled(sibling.url) == 0
    finally:
        sg.close()
        slow.close()
        sibling.close()


@pytest.mark.parametrize("hedged", [True, False])
def test_a_hedged_attempts_own_timer_sits_behind_the_deadline(
        monkeypatch, hedged):
    """What made the test above red one run in a few under six xdist
    workers: a hedged attempt's socket timer was set to the deadline's
    remainder, the same instant at which its query gives up and fires
    the cancel token, and when the timer won the attempt was a timeout
    (a failure in the replica's breaker), not an abandoned hedge.  The
    query enforces the deadline; the attempt's timer is the backstop
    behind it, never past ``shard-timeout-ms``.  A lone attempt, which
    nobody else would end, keeps the remainder itself."""
    from oryx_tpu.cluster import scatter
    from oryx_tpu.resilience.policy import Deadline
    stub = _StubReplica()
    reg = MembershipRegistry(ttl_sec=60.0)
    hb = Heartbeat(replica="a", shard=0, of=1, url=stub.url,
                   generation=1, ready=True)
    reg.note(hb)
    sg = ScatterGather(reg, _config(
        **{"oryx.cluster.transport.enabled": False}))
    seen = []
    real = scatter._request

    def recording(conn, rfile, method, path, body, headers, timeout):
        seen.append((timeout, int(headers["X-Deadline-Ms"])))
        return real(conn, rfile, method, path, body, headers, timeout)

    monkeypatch.setattr(scatter, "_request", recording)
    try:
        for budget in (0.5, 60.0):
            sg._attempt(hb, 0, "GET", "/x", None, Deadline.after(budget),
                        cancel=scatter._CancelToken() if hedged else None)
        (short, short_ms), (long, _) = seen
        if hedged:
            assert 0.5 + 0.9 * scatter._GIVE_UP_GRACE_SEC < short \
                <= 0.5 + scatter._GIVE_UP_GRACE_SEC
        else:
            assert 0.4 < short <= 0.5
        # what the replica is told is the request's budget, either way
        assert 400 <= short_ms <= 500
        assert long == sg.shard_timeout_sec == 5.0
    finally:
        sg.close()
        stub.close()


# -- frame client <-> server loopback ----------------------------------------

def _echo_app(user=None, password=None):
    import time as _time

    def _echo(req):
        return {"path": req.path, "body": req.body.decode(),
                "deadline_ms": None if req.deadline is None
                else int(req.deadline.remaining() * 1000)}

    def _slow(req):
        _time.sleep(float(req.q1("sec", "0.5")))
        return {"slow": True}

    routes = [Route("POST", "/shard/echo", _echo),
              Route("GET", "/shard/slow", _slow),
              Route("GET", "/shard/meta", lambda req: {"meta": True})]
    return HttpApp(routes, context={}, user_name=user, password=password)


def _hb_for(server, url="http://127.0.0.1:1"):
    return Heartbeat(replica="r", shard=0, of=1,
                     url=f"http://127.0.0.1:{server.port}",
                     generation=1, ready=True, tport=server.port)


def test_framed_request_answers_through_the_app_dispatcher():
    app = _echo_app()
    server = tr.FrameServer(app, _config())
    server.start()
    client = tr.FrameTransport(_config())
    try:
        status, raw, _ = client.request(
            _hb_for(server), "POST", "/shard/echo", b"hello",
            {"X-Deadline-Ms": "2500"}, timeout=5.0)
        assert status == 200
        out = json.loads(raw)
        assert out["path"] == "/shard/echo"
        assert out["body"] == "hello"
        # deadline propagated: the handler saw a live remaining budget
        assert 0 < out["deadline_ms"] <= 2500
        assert client.open_connections() == 1
    finally:
        client.close()
        server.close()


def test_streams_multiplex_one_connection_and_do_not_holb():
    """Two interleaved streams on ONE connection: the slow one must
    not block the fast one (per-stream dispatch, completion-order
    responses)."""
    app = _echo_app()
    server = tr.FrameServer(app, _config())
    server.start()
    client = tr.FrameTransport(_config())
    try:
        hb = _hb_for(server)
        results = {}

        def call(name, path, method="GET", body=b""):
            t0 = time.monotonic()
            status, raw, _ = client.request(hb, method, path, body,
                                            {}, timeout=10.0)
            results[name] = (status, time.monotonic() - t0)

        slow_t = threading.Thread(
            target=call, args=("slow", "/shard/slow?sec=0.8"))
        slow_t.start()
        time.sleep(0.1)  # the slow stream is in flight on the conn
        call("fast", "/shard/echo", method="POST", body=b"x")
        slow_t.join(5.0)
        assert results["fast"][0] == 200
        assert results["slow"][0] == 200
        assert results["fast"][1] < 0.5  # never waited out the slow one
        assert client.open_connections() == 1  # ONE socket carried both
    finally:
        client.close()
        server.close()


def test_stream_timeout_sends_cancel_and_replica_drops_the_answer():
    app = _echo_app()
    server = tr.FrameServer(app, _config())
    server.start()
    client = tr.FrameTransport(_config())
    try:
        hb = _hb_for(server)
        with pytest.raises(TimeoutError):
            client.request(hb, "GET", "/shard/slow?sec=1.0", b"", {},
                           timeout=0.15)
        assert client.cancels_sent == 1
        # the replica saw the CANCEL and dropped the stream's answer
        deadline = time.monotonic() + 5.0
        while server.cancelled_streams < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert server.cancelled_streams >= 1
        # the connection survived the cancellation: next request flows
        status, _, _ = client.request(hb, "POST", "/shard/echo", b"y",
                                      {}, timeout=5.0)
        assert status == 200
        assert client.open_connections() == 1
    finally:
        client.close()
        server.close()


def test_replica_restart_retries_once_on_fresh_connection():
    app = _echo_app()
    server = tr.FrameServer(app, _config())
    server.start()
    port = server.port
    client = tr.FrameTransport(_config())
    try:
        hb = _hb_for(server)
        assert client.request(hb, "POST", "/shard/echo", b"1", {},
                              timeout=5.0)[0] == 200
        server.close()  # the replica restarts (supervised event)
        for _ in range(50):
            try:
                server = tr.FrameServer(_echo_app(), _config(),
                                        port=port)
                break
            except OSError:
                time.sleep(0.1)  # old conns draining off the port
        server.start()
        # the cached connection is dead: one internal retry, no error
        assert client.request(hb, "POST", "/shard/echo", b"2", {},
                              timeout=5.0)[0] == 200
    finally:
        client.close()
        server.close()


def test_auth_frame_gates_the_connection():
    app = _echo_app(user="oryx-admin", password="s3cret")
    server = tr.FrameServer(app, _config(
        **{"oryx.serving.api.user-name": "oryx-admin",
           "oryx.serving.api.password": "s3cret"}))
    server.start()
    good = tr.FrameTransport(_config(
        **{"oryx.serving.api.user-name": "oryx-admin",
           "oryx.serving.api.password": "s3cret"}))
    bad = tr.FrameTransport(_config(
        **{"oryx.serving.api.user-name": "oryx-admin",
           "oryx.serving.api.password": "wrong"}))
    try:
        hb = _hb_for(server)
        assert good.request(hb, "POST", "/shard/echo", b"ok", {},
                            timeout=5.0)[0] == 200
        with pytest.raises((ConnectionError, TimeoutError)):
            bad.request(hb, "POST", "/shard/echo", b"no", {},
                        timeout=2.0)
    finally:
        good.close()
        bad.close()
        server.close()


def test_frame_stall_chaos_stalls_one_stream_only():
    """``transport-frame-stall``: the armed stream's answer stalls;
    a second stream on the SAME connection is unaffected."""
    app = _echo_app()
    server = tr.FrameServer(app, _config())
    server.start()
    client = tr.FrameTransport(_config())
    faults.inject("transport-frame-stall", mode="delay", times=1,
                  delay_sec=1.0)
    try:
        hb = _hb_for(server)
        results = {}

        def call(name):
            t0 = time.monotonic()
            status, _, _ = client.request(hb, "POST", "/shard/echo",
                                          name.encode(), {},
                                          timeout=10.0)
            results[name] = (status, time.monotonic() - t0)

        stalled_t = threading.Thread(target=call, args=("stalled",))
        stalled_t.start()
        time.sleep(0.15)  # the armed stream consumed the fault
        call("bystander")
        stalled_t.join(5.0)
        assert faults.fired("transport-frame-stall") == 1
        assert results["bystander"][0] == 200
        assert results["bystander"][1] < 0.5  # unaffected by the stall
        assert results["stalled"][0] == 200
        assert results["stalled"][1] >= 0.9  # it really did stall
    finally:
        client.close()
        server.close()


# -- replica-side result cache ------------------------------------------------

def _cache_config(**extra):
    overlay = {"oryx.cluster.replica-cache.enabled": True,
               "oryx.cluster.replica-cache.quarantine-ms": 0}
    overlay.update(extra)
    return from_dict(overlay)


def test_shard_cache_serves_under_unchanged_epoch_only():
    cache = ShardResultCache(_cache_config())
    assert cache.lookup("POST", "/shard/query", b"q1") is None
    cache.store("POST", "/shard/query", b"q1", cache.epoch(), 200,
                {"x": "1"}, b"answer")
    assert cache.lookup("POST", "/shard/query", b"q1") == \
        (200, {"x": "1"}, b"answer")
    # ANY applied update record moves the epoch: the entry stops
    # serving instantly (exact by construction)
    cache.note_record()
    assert cache.lookup("POST", "/shard/query", b"q1") is None
    st = cache.stats()
    assert st["hits"] == 1 and st["misses"] == 2
    assert st["entries"] == 0  # the stale entry was reclaimed on touch


def test_shard_cache_refuses_stale_epoch_and_quarantined_stores():
    cache = ShardResultCache(_cache_config(
        **{"oryx.cluster.replica-cache.quarantine-ms": 100000}))
    e0 = cache.epoch()
    cache.note_record()
    # epoch moved during the request: refused
    cache.store("GET", "/shard/p", b"", e0, 200, {}, b"x")
    # within the quarantine after the bump: refused too
    cache.store("GET", "/shard/p", b"", cache.epoch(), 200, {}, b"x")
    assert cache.stats()["entries"] == 0
    assert cache.stats()["store_rejects"] == 2


def test_shard_cache_bounds_entries_and_bytes():
    cache = ShardResultCache(_cache_config(
        **{"oryx.cluster.replica-cache.max-entries": 2}))
    for i in range(4):
        cache.store("GET", f"/shard/p{i}", b"", cache.epoch(), 200,
                    {}, b"v")
    st = cache.stats()
    assert st["entries"] == 2 and st["evictions"] == 2
    assert cache.lookup("GET", "/shard/p3", b"") is not None
    assert cache.lookup("GET", "/shard/p0", b"") is None
    # non-200s are never stored
    cache.store("GET", "/shard/err", b"", cache.epoch(), 404, {}, b"e")
    assert cache.lookup("GET", "/shard/err", b"") is None


def test_shard_cache_tap_bumps_before_and_after_each_apply():
    """Pre-yield AND post-yield bumps: the post-apply fence retires
    anything a mid-apply request stored, no matter how long the apply
    ran (a sliced model load takes seconds — no fixed quarantine can
    cover it)."""
    cache = ShardResultCache(_cache_config())
    e0 = cache.epoch()
    tap = cache.tap(iter(["a", "b"]))
    assert next(tap) == "a"
    assert cache.epoch() == e0 + 1  # pre-apply fence
    # mid-apply store lands under the in-between epoch ...
    cache.store("GET", "/shard/mid", b"", cache.epoch(), 200, {}, b"x")
    assert cache.lookup("GET", "/shard/mid", b"") is not None
    assert next(tap) == "b"  # asking for the next record = apply done
    # ... and the post-apply bump retired it
    assert cache.epoch() == e0 + 3
    assert cache.lookup("GET", "/shard/mid", b"") is None
    assert list(tap) == []
    assert cache.epoch() == e0 + 4
