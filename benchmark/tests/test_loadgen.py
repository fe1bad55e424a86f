"""The load generator against a stub server: closed and open loops, the
latency from the moment due, and what counts as failed."""

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import loadgen  # noqa: E402
from benchmark.run import summarize  # noqa: E402

TEN = json.dumps([{"id": str(i), "value": 1.0 - i / 10} for i in range(10)])


class _Stub(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    wbufsize = 65536     # head and body leave in one segment
    delay_s = 0.0

    def log_message(self, *args):
        pass

    def do_GET(self):
        status, body = 200, TEN
        if self.path.startswith("/slow"):
            time.sleep(self.delay_s)
        elif self.path.startswith("/error"):
            status, body = 500, "no"
        elif self.path.startswith("/nine"):
            body = json.dumps(json.loads(TEN)[:9])
        payload = body.encode()
        self.send_response(status)
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)


@pytest.fixture
def stub():
    server = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    server.daemon_threads = True
    server.handle_error = lambda *a: None   # a client that gave up
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server.server_address[1]
    server.shutdown()
    server.server_close()
    thread.join(5)
    assert not thread.is_alive()


def _run(port, traffic, seconds=1.0, rate=None):
    gen = loadgen.Generator({
        "host": "127.0.0.1", "port": port, "seed": 1, "seconds": seconds,
        "traffic": traffic, "n_users": 100, "n_items": 100, "rate": rate,
        "sample_every": 4})
    try:
        gen.open_all()
        return gen.run(lambda t0, wall: None)
    finally:
        gen.close_all()


def _endpoint(path, **expect):
    return {"path": path, "expect": dict({"status": 200}, **expect),
            "check": "recommend"}


def test_closed_loop_two_callers(stub):
    _Stub.delay_s = 0.02
    result = _run(stub, {"loop": "closed", "clients": 2, "endpoints": [
        _endpoint("/slow/{user}", json_list_len=10)]})
    s = summarize(result)
    # two callers, 20 ms an answer: about 100 in the second
    assert 60 <= s["attempted"] <= 100 and s["failed"] == 0
    assert 20.0 <= s["latency_p50_ms"] < 40.0
    assert s["unsent"] == 0 and s["malformed"] == 0
    # a caller's next request leaves when its last one is answered
    assert s["generator_lag_ms"]["mean"] < 5.0
    assert 10 <= len(result["samples"]) <= 45   # one in four, seeded
    assert result["samples"][0]["body"][0]["id"] == "0"
    # a sample names the endpoint of the mix it answers, for the
    # application's check
    assert {s["endpoint"] for s in result["samples"]} == {0}


def test_open_loop_above_capacity_counts_lateness_and_unsent(stub):
    _Stub.delay_s = 0.05          # 4 connections x 20/s = 80/s at most
    result = _run(stub, {
        "loop": "open", "connections": 4,
        "arrivals": {"process": "poisson"},
        "endpoints": [_endpoint("/slow/{user}", json_list_len=10)]},
        seconds=2.0, rate=200.0)
    s = summarize(result)
    assert s["failed"] == 0
    assert 60.0 <= s["served_qps"] <= 85.0
    assert s["unsent"] > 150                 # due, never sent
    assert s["scheduled"] == s["attempted"] + s["unsent"]
    # latency runs from the moment due, so the backlog is in it
    assert s["latency_p50_ms"] > 300.0
    assert s["lateness"]["drift_ms"] > 100.0
    # ... and is not the generator's doing
    assert s["generator_lag_ms"]["mean"] < 10.0


def test_open_loop_below_capacity_is_on_time(stub):
    result = _run(stub, {
        "loop": "open", "connections": 8,
        "arrivals": {"process": "poisson"},
        "endpoints": [_endpoint("/ok/{user}", json_list_len=10)]},
        seconds=2.0, rate=300.0)
    s = summarize(result)
    # (a request due in the window's last instant may find it closed)
    assert s["failed"] == 0 and s["unsent"] <= 2
    assert abs(s["served_qps"] - 300.0) < 45.0
    assert s["lateness"]["mean_ms"] < 5.0
    assert s["latency_p50_ms"] < 20.0


def test_what_counts_as_failed(stub):
    _Stub.delay_s = 1.0
    mix = {"loop": "closed", "clients": 3, "timeout_s": 0.3, "endpoints": [
        _endpoint("/error/{user}"),                    # wrong status
        _endpoint("/nine/{user}", json_list_len=10),   # wrong length
        _endpoint("/slow/{user}"),                     # never in time
        _endpoint("/ok/{user}", json_list_len=10)]}
    result = _run(stub, mix, seconds=1.5)
    by_path = {}
    for rec in result["records"]:
        wire = loadgen.Plan(mix, 1, 1.5, 100, 100, None).request(rec[0])[0]
        by_path.setdefault(wire.split(b"/")[1], []).append(rec)
    assert all(r[4] == 500 and not r[5] for r in by_path[b"error"])
    assert all(r[4] == 200 and not r[5] for r in by_path[b"nine"])
    assert all(r[4] == 0 and not r[5] for r in by_path[b"slow"])
    assert all(r[4] == 200 and r[5] for r in by_path[b"ok"])
    s = summarize(result)
    assert s["failed"] == sum(len(by_path[k])
                              for k in (b"error", b"nine", b"slow"))
    assert s["malformed"] == len(by_path[b"nine"])
    assert result["reconnects"] >= len(by_path[b"slow"]) - 3
    for rec in by_path[b"slow"]:      # given up after the mix's timeout
        assert 0.3 <= rec[3] - rec[2] < 0.8
