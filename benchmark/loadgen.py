"""The load generator: a process of its own that never imports JAX or the
program, speaking HTTP/1.1 keep-alive to ``127.0.0.1`` from one thread.

The harness starts it with ``python benchmark/loadgen.py``, writes one
JSON line (the spec) to its stdin, reads ``{"event": "ready"}`` once the
connections are open, writes ``go``, reads ``{"event": "started"}`` with
the window's first instant, and at the end one ``{"event": "done"}``
line with every request's record.  Latency is taken here, per request,
from the moment it was due: in an open loop the scheduled arrival, in a
closed loop the moment its caller was free to send it.

One thread and a selector instead of a thread per connection: 512
threads would spend the window fighting over this process's own
interpreter lock, and the lateness they cause would read as a slow
server.
"""

from __future__ import annotations

import collections
import json
import os
import select
import selectors
import socket
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.traffic import Plan  # noqa: E402


class _Conn:
    __slots__ = ("sock", "buf", "req", "meta", "free_at")

    def __init__(self):
        self.sock = None
        self.buf = bytearray()
        self.req = None       # index into the records while one is out
        self.meta = None      # (endpoint, user, sampled) of that request
        self.free_at = 0.0    # when this caller became free (closed loop)


class Generator:
    def __init__(self, spec: dict):
        self.host, self.port = spec["host"], int(spec["port"])
        self.seconds = float(spec["seconds"])
        self.plan = Plan(spec["traffic"], spec["seed"], self.seconds,
                         spec["n_users"], spec["n_items"], spec.get("rate"),
                         spec.get("sample_every", 16))
        self.sel = selectors.DefaultSelector()
        self.conns = [_Conn() for _ in range(self.plan.connections)]
        self.free = collections.deque()
        # one record per request sent: [plan index, due, sent, done,
        # status, ok] with times in seconds on the monotonic clock
        self.records: list[list] = []
        self.samples: list[dict] = []
        self.deadlines = collections.deque()  # (deadline, conn, record)
        self.reconnects = 0
        self.busy = 0
        # lateness of requests that found a connection free when they
        # fell due: the generator's own share of the lateness
        self.idle_lag: list[float] = []

    # -- connections ------------------------------------------------------

    def _connect(self, c: _Conn) -> None:
        s = socket.create_connection((self.host, self.port), timeout=10.0)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.setblocking(False)
        c.sock, c.req = s, None
        del c.buf[:]
        self.sel.register(s, selectors.EVENT_READ, c)

    def _drop(self, c: _Conn) -> None:
        if c.sock is None:
            return
        try:
            self.sel.unregister(c.sock)
        except (KeyError, ValueError):
            pass
        try:
            c.sock.close()
        except OSError:
            pass
        c.sock = None

    def open_all(self) -> None:
        for c in self.conns:
            self._connect(c)
            self.free.append(c)

    def warm(self, spec: dict) -> int:
        """Before the window: ``warm_requests`` of the mix, one after
        another on a connection of their own, so that the server's first
        answer on each route is not inside the window.  Drawn from
        another stream than the window's requests."""
        n = int(spec["traffic"].get("warm_requests", 0))
        if not n:
            return 0
        plan = Plan(dict(spec["traffic"], loop="closed", clients=1),
                    int(spec["seed"]) ^ 0x5EED, 0.0, spec["n_users"],
                    spec["n_items"], None)
        with socket.create_connection((self.host, self.port),
                                      timeout=120.0) as s:
            f = s.makefile("rb")
            for i in range(n):
                s.sendall(plan.request(i)[0])
                length = 0
                while True:
                    h = f.readline(65537)
                    if h in (b"\r\n", b"\n", b""):
                        break
                    if h[:15].lower() == b"content-length:":
                        length = int(h[15:])
                f.read(length)
        return n

    def close_all(self) -> None:
        for c in self.conns:
            if c.sock is not None:
                self._drop(c)
        self.sel.close()

    # -- one request --------------------------------------------------------

    def _send(self, c: _Conn, i: int, due: float) -> None:
        wire, ep, user, sampled = self.plan.request(i)
        if c.sock is None:
            self._connect(c)
            self.reconnects += 1
        now = time.monotonic()
        view = memoryview(wire)
        try:
            while view:
                try:
                    view = view[c.sock.send(view):]
                except BlockingIOError:
                    # a full send buffer on an idle keep-alive
                    # connection: wait for room, the request is tiny
                    _wait_writable(c.sock)
        except OSError:
            self.records.append([i, due, now, time.monotonic(), 0, False])
            self._drop(c)
            self.free.append(c)
            return
        c.req, c.meta = len(self.records), (ep, user, sampled)
        self.records.append([i, due, now, None, 0, False])
        self.busy += 1
        self.deadlines.append((now + self.plan.timeout_s, c, c.req))

    def _finish(self, c: _Conn, status: int, body: bytes,
                done: float) -> None:
        rec = self.records[c.req]
        ep, user, sampled = c.meta
        ok = status == ep.status
        keep = ok and sampled and ep.check
        if ok and (ep.list_len is not None or keep):
            try:
                parsed = json.loads(body)
            except ValueError:
                ok = keep = False
            else:
                if ep.list_len is not None:
                    ok = isinstance(parsed, list) \
                        and len(parsed) == ep.list_len
        rec[3], rec[4], rec[5] = done, status, ok
        if ok and keep:
            self.samples.append({"i": rec[0], "user": user,
                                 "endpoint": ep.index, "body": parsed})
        c.req = None
        self.busy -= 1
        c.free_at = done
        self.free.append(c)

    def _fail(self, c: _Conn, done: float) -> None:
        """Timeout, reset or garbage: the request failed and the
        connection is not reused."""
        if c.req is not None:
            rec = self.records[c.req]
            rec[3], rec[4], rec[5] = done, 0, False
            c.req = None
            self.busy -= 1
        self._drop(c)
        c.free_at = done
        self.free.append(c)

    def _readable(self, c: _Conn) -> None:
        try:
            data = c.sock.recv(65536)
        except BlockingIOError:
            return
        except OSError:
            data = b""
        now = time.monotonic()
        if not data:
            if c.req is not None:
                self._fail(c, now)
            else:  # the server closed an idle connection
                self._drop(c)
            return
        c.buf += data
        if c.req is None:
            return  # bytes nobody asked for; the next parse fails on them
        end = c.buf.find(b"\r\n\r\n")
        if end < 0:
            return
        head = bytes(c.buf[:end]).split(b"\r\n")
        try:
            status = int(head[0].split(b" ", 2)[1])
            length = 0
            for h in head[1:]:
                if h[:15].lower() == b"content-length:":
                    length = int(h[15:])
        except (IndexError, ValueError):
            self._fail(c, now)
            return
        if len(c.buf) < end + 4 + length:
            return
        body = bytes(c.buf[end + 4:end + 4 + length])
        del c.buf[:end + 4 + length]
        self._finish(c, status, body, now)

    # -- the window -----------------------------------------------------------

    def run(self, announce) -> dict:
        plan = self.plan
        open_loop = plan.loop == "open"
        t0 = time.monotonic()
        announce(t0, time.time())
        t_end = t0 + self.seconds
        for c in self.conns:
            c.free_at = t0
        due = (plan.due + t0) if open_loop else None
        n_due = len(due) if open_loop else 0
        nxt = 0                     # next plan index to become due / be sent
        backlog = collections.deque()
        while True:
            now = time.monotonic()
            issuing = now < t_end
            if open_loop:
                while nxt < n_due and due[nxt] <= now:
                    # was a connection free when it fell due?  Then
                    # what lateness it has is the generator's own
                    backlog.append((nxt, len(self.free) > len(backlog)))
                    nxt += 1
                while issuing and backlog and self.free:
                    i, had_conn = backlog.popleft()
                    if had_conn:
                        self.idle_lag.append(now - due[i])
                    self._send(self.free.popleft(), i, float(due[i]))
                    now = time.monotonic()
            else:
                while issuing and self.free:
                    c = self.free.popleft()
                    self.idle_lag.append(now - c.free_at)
                    self._send(c, nxt, c.free_at)
                    nxt += 1
                    now = time.monotonic()
            if not issuing and not self.busy:
                break
            wake = t_end if issuing else now + 1.0
            if open_loop and issuing and nxt < n_due:
                wake = min(wake, due[nxt])
            if self.deadlines:
                wake = min(wake, self.deadlines[0][0])
            for key, _ in self.sel.select(max(0.0, wake - now)):
                self._readable(key.data)
            now = time.monotonic()
            while self.deadlines and self.deadlines[0][0] <= now:
                _, c, r = self.deadlines.popleft()
                if c.req == r:
                    self._fail(c, now)
            while self.deadlines and self.deadlines[0][1].req \
                    != self.deadlines[0][2]:
                self.deadlines.popleft()  # answered long ago
        unsent = len(backlog) + (n_due - nxt if open_loop else 0)
        return {"t0": t0, "seconds": self.seconds, "loop": plan.loop,
                "connections": plan.connections,
                "scheduled": n_due if open_loop else nxt,
                "unsent": unsent, "reconnects": self.reconnects,
                "records": self.records, "samples": self.samples,
                "idle_lag_ms": [x * 1e3 for x in self.idle_lag]}


def _wait_writable(sock) -> None:
    select.select([], [sock], [], 1.0)


def _say(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    gen = Generator(spec)
    try:
        gen.open_all()
        warmed = gen.warm(spec)
        _say({"event": "ready", "connections": len(gen.conns),
              "warmed": warmed})
        if sys.stdin.readline().strip() != "go":
            return 1
        result = gen.run(lambda t0, wall: _say(
            {"event": "started", "t0": t0, "wall": wall}))
        result["event"] = "done"
        _say(result)
    finally:
        gen.close_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())
