"""The one general traffic generator: a traffic mix is a data file under
``benchmark/mixes/`` and this module turns it, a seed and a window
length into requests.  No JAX, no program code: the load generator
process imports it.

A mix (JSON) has:

- ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last one completed) or ``"open"`` (requests leave on
  a schedule whatever the server does, over ``connections`` keep-alive
  connections);
- ``arrivals`` (open loop): ``process`` ``"poisson"`` or ``"bursty"``
  (Poisson inside on-phases of ``duty`` x ``period_s``, silent between,
  same mean rate), and ``rate_qps``: a number, or ``{"cell": key,
  "times": x}`` to take it from the cell's own file;
- ``users``: ``{"pick": "uniform"}`` or ``{"pick": "zipf", "a": 1.1}``;
- ``endpoints``: a list of ``{weight, method, path, body?, expect}``
  whose ``path``/``body`` may hold ``{user}``, ``{item}`` and
  ``{items:N}`` (N ids joined by ``/``); ``expect`` gives the status and,
  optionally, the length of the JSON list a correct answer carries;
  ``check`` names a full check of the configuration's application
  (``benchmark/apps/``), to which one answer in sixteen is handed with
  the endpoint it answers; what else the application needs to know of
  the request sits beside it (ALS: ``known_items`` ``"excluded"`` or
  ``"considered"``);
- ``timeout_s``: the client's patience;
- ``trace_sample_ratio``: the share of requests the program's tracer
  records in the traced run (1.0 where it is not given): a mix of
  thousands of requests a second sets it low, because a median needs
  thousands of spans and recording every one slows a busy door.

Ids are decimal strings of row numbers, as the synthetic model names
them, so the generator needs the population's sizes and no tables.
"""

from __future__ import annotations

import re

import numpy as np

_PLACEHOLDER = re.compile(r"\{(user|item|items:(\d+))\}")
_CHUNK = 8192


class TrafficError(Exception):
    """The mix asks for something the generator does not know."""


def offered_rate(traffic: dict, cell_params: dict) -> float | None:
    """The open loop's fixed rate in requests per second (None for a
    closed loop): a number in the mix, or taken from the cell's file."""
    if traffic.get("loop") != "open":
        return None
    rate = traffic.get("arrivals", {}).get("rate_qps")
    if isinstance(rate, dict):
        key = rate["cell"]
        if key not in cell_params:
            raise TrafficError(
                f"the mix takes its rate from the cell's {key!r}, which "
                "the cell's file does not give")
        return float(cell_params[key]) * float(rate.get("times", 1.0))
    if rate is None:
        raise TrafficError("an open loop needs arrivals.rate_qps")
    return float(rate)


class _Template:
    """``/recommend/{user}?howMany=10`` split once into literal parts
    and slots."""

    def __init__(self, text: str):
        self.parts: list = []
        self.n_items = 0
        pos = 0
        for m in _PLACEHOLDER.finditer(text):
            self.parts.append(text[pos:m.start()])
            if m.group(1) == "user":
                self.parts.append(("user", 0, 0))
            else:
                n = int(m.group(2)) if m.group(2) else 1
                self.parts.append(("items", self.n_items, n))
                self.n_items += n
            pos = m.end()
        self.parts.append(text[pos:])

    def fill(self, user: int, items) -> str:
        out = []
        for p in self.parts:
            if isinstance(p, str):
                out.append(p)
            elif p[0] == "user":
                out.append(str(user))
            else:
                out.append("/".join(str(int(i))
                                    for i in items[p[1]:p[1] + p[2]]))
        return "".join(out)


class _Endpoint:
    def __init__(self, spec: dict, index: int):
        self.index = index
        self.method = spec.get("method", "GET").upper()
        if self.method not in ("GET", "POST", "DELETE"):
            raise TrafficError(f"method {self.method!r} is not supported")
        self.path = _Template(spec["path"])
        self.body = _Template(spec["body"]) if "body" in spec else None
        self.weight = float(spec.get("weight", 1.0))
        expect = spec.get("expect", {})
        self.status = int(expect.get("status", 200))
        self.list_len = expect.get("json_list_len")
        # the application's full check a sampled answer is handed to
        self.check = spec.get("check")
        self.n_items = self.path.n_items + (
            self.body.n_items if self.body else 0)


def arrival_times(process: dict, rate: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Seconds from the window's start at which requests are due, all
    inside ``[0, seconds)``."""
    kind = process.get("process", "poisson")
    if rate <= 0:
        raise TrafficError("the offered rate must be positive")
    if kind == "poisson":
        on_rate, warp = rate, None
    elif kind == "bursty":
        duty = float(process["duty"])
        period = float(process["period_s"])
        if not 0 < duty <= 1:
            raise TrafficError("bursty arrivals need 0 < duty <= 1")
        on_rate, warp = rate / duty, (duty * period, period)
    else:
        raise TrafficError(f"arrival process {kind!r} is not known")
    busy_span = seconds if warp is None else seconds * warp[0] / warp[1]
    n_guess = int(on_rate * busy_span * 1.2) + 64
    gaps = rng.exponential(1.0 / on_rate, n_guess)
    t = np.cumsum(gaps)
    while t[-1] < busy_span:  # the guess fell short: extend
        more = np.cumsum(rng.exponential(1.0 / on_rate, n_guess)) + t[-1]
        t = np.concatenate([t, more])
    t = t[t < busy_span]
    if warp is not None:
        on_len, period = warp
        t = np.floor(t / on_len) * period + np.mod(t, on_len)
    return t[t < seconds]


class Plan:
    """Every request of one run, drawn from the seed: which endpoint,
    which user, which items, and (open loop) when it is due."""

    def __init__(self, traffic: dict, seed: int, seconds: float,
                 n_users: int, n_items: int, rate: float | None,
                 sample_every: int = 16):
        self.loop = traffic["loop"]
        if self.loop not in ("open", "closed"):
            raise TrafficError(f"loop {self.loop!r} is not known")
        self.endpoints = [_Endpoint(e, n)
                          for n, e in enumerate(traffic["endpoints"])]
        if not self.endpoints:
            raise TrafficError("a mix needs at least one endpoint")
        w = np.array([e.weight for e in self.endpoints], float)
        self._weights = w / w.sum()
        self._max_items = max(e.n_items for e in self.endpoints)
        self._users_spec = traffic.get("users", {"pick": "uniform"})
        self._n_users, self._n_items = n_users, n_items
        self._sample_every = sample_every
        self._rng = np.random.default_rng([int(seed), 0x7AFF1C])
        self.timeout_s = float(traffic.get("timeout_s", 10.0))
        self._zipf_p = None
        self._ep = self._user = self._items = self._sampled = None
        self._n = 0
        if self.loop == "open":
            self.connections = int(traffic["connections"])
            self.due = arrival_times(traffic.get("arrivals", {}), rate,
                                     seconds, self._rng)
            self._draw(len(self.due))
        else:
            self.connections = int(traffic["clients"])
            self.due = None

    def _pick_users(self, n: int) -> np.ndarray:
        pick = self._users_spec.get("pick", "uniform")
        if pick == "uniform":
            return self._rng.integers(0, self._n_users, n)
        if pick == "zipf":
            if self._zipf_p is None:
                ranks = np.arange(1, self._n_users + 1, dtype=np.float64)
                p = 1.0 / np.power(ranks, float(self._users_spec["a"]))
                self._zipf_p = p / p.sum()
            return self._rng.choice(self._n_users, size=n, p=self._zipf_p)
        raise TrafficError(f"user pick {pick!r} is not known")

    def _draw(self, n: int) -> None:
        ep = self._rng.choice(len(self.endpoints), size=n, p=self._weights)
        user = self._pick_users(n)
        items = self._rng.integers(0, self._n_items,
                                   (n, max(1, self._max_items)))
        sampled = self._rng.integers(0, self._sample_every, n) == 0
        if self._n:
            self._ep = np.concatenate([self._ep, ep])
            self._user = np.concatenate([self._user, user])
            self._items = np.concatenate([self._items, items])
            self._sampled = np.concatenate([self._sampled, sampled])
        else:
            self._ep, self._user, self._items = ep, user, items
            self._sampled = sampled
        self._n = len(self._ep)

    def request(self, i: int):
        """(bytes on the wire, endpoint, user index, sampled for the
        full check) of request ``i``."""
        while i >= self._n:  # closed loop: drawn as the run consumes them
            self._draw(_CHUNK)
        ep = self.endpoints[self._ep[i]]
        user = int(self._user[i])
        items = self._items[i]
        path = ep.path.fill(user, items)
        if ep.body is not None:
            body = ep.body.fill(user, items[ep.path.n_items:]).encode()
            head = (f"{ep.method} {path} HTTP/1.1\r\nHost: b\r\n"
                    f"Content-Length: {len(body)}\r\n\r\n")
            wire = head.encode("latin-1") + body
        else:
            wire = f"{ep.method} {path} HTTP/1.1\r\nHost: b\r\n\r\n" \
                .encode("latin-1")
        return wire, ep, user, bool(self._sampled[i])
