"""The ALS application, as the harness sees it: a configuration whose
``app`` is ``"als"`` is served, warmed and checked by this file.

``run.py`` knows no application.  It finds ``benchmark/apps/<app>.py`` by
the name in the configuration's file, the way it finds a reader by the
name in a layer metric's file, and asks it for four things: the config
overlay that makes ``ServingLayer`` serve the synthetic model
(``overlay``), the sizes the traffic generator draws ids from
(``population``), and, once the layer runs, a ``Checker`` that warms the
shapes the cell's traffic produces, holds answers to the plain reference
before and inside the window, and reads the program's counters.  A later
application (k-means, RDF) is a file like this one, with its manager and
its reference beside it.
"""

from __future__ import annotations

import http.client
import json

import numpy as np

from benchmark import costs
from benchmark.apps.als_reference import Reference

# users checked against the reference before the window, stratified by
# the fetched top-k width their known items force
PRECHECK_USERS = 32
# tries at making a certificate fail, per shape, before giving up
WARM_TRIES = 64
# the checks a mix's endpoint may name, and what each asks of an answer
CHECKS = ("recommend",)


def overlay(cell, seed: int) -> dict:
    """What ``python -m oryx_tpu serving`` would read from its config
    file for this application, with the benchmark's static manager in
    the place of the one that listens to the update topic."""
    return {
        "oryx.serving.application-resources": "oryx_tpu.serving.als",
        "oryx.serving.model-manager-class":
            "benchmark.apps.als_manager.SyntheticALSManager",
        "oryx.benchmark.config-file": cell.config_file,
        "oryx.benchmark.seed": int(seed),
    }


def population(config: dict) -> dict:
    """Ids are decimal row numbers, so the generator needs only sizes."""
    return {"n_users": int(config["users"]),
            "n_items": int(config["items"])}


def _excludes_known(endpoint: dict) -> bool:
    """Whether the request leaves the user's known items out (the
    endpoint's default) or says ``considerKnownItems=true``."""
    known = endpoint.get("known_items", "excluded")
    if known not in ("excluded", "considered"):
        raise ValueError(f"known_items {known!r} is not known")
    return known == "excluded"


def _fetch(port: int, path: str):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


class Checker:
    def __init__(self, layer, cell, seed: int):
        self.layer, self.seed = layer, int(seed)
        self.manager = layer.model_manager
        self.model = self.manager.get_model()
        self.endpoints = cell.traffic["endpoints"]
        for e in self.endpoints:
            if e.get("check") is not None and e["check"] not in CHECKS:
                raise ValueError(f"check {e['check']!r} is not one of "
                                 f"{CHECKS}")
            _excludes_known(e)
        self.traffic = cell.traffic
        self.how_many = int(cell.config["how_many"])
        # the top-k width the program fetches for each user's default
        # /recommend: how many plus the items the user knows, padded
        self.widths = np.array([costs.pad_k(self.how_many + int(c))
                                for c in self.manager.known_counts])
        self.reference = Reference(self.model)
        self.split = dict(self.manager.split)
        self.checked_before = 0

    # -- before the window --------------------------------------------------

    def warm(self) -> list[tuple[int, int]]:
        """Run every (window, fetched top-k) pair this cell's traffic can
        produce, through the model's own batched entry point, so that
        nothing compiles inside the window: the windows from the drains
        the mix can build, the widths from the known items of the
        population (the narrowest alone where every request lets known
        items come back).

        Each shape has two programs, the two-phase scan and the exact
        scan that answers when its certificate fails.  Real users'
        vectors are scored, new ones each time, until
        ``twophase_fallbacks`` has moved or ``WARM_TRIES`` are spent.
        Since PR 26 the scan selects max(32, 2k) blocks and no
        certificate fails at any width (``twophase_fallbacks`` 0 over
        ~146,000 requests), so every shape spends all its tries: the
        loop warms the two-phase program and hunts in vain for the
        other, about 5 s of ``setup_s`` (PERF.md section 7)."""
        model, widths, how_many = self.model, self.widths, self.how_many
        traffic = self.traffic
        deepest = int(traffic["clients"]) if traffic["loop"] == "closed" \
            else min(int(traffic["connections"]),
                     self.layer.top_n_batcher.max_batch)
        windows = sorted({w for n in range(1, deepest + 1)
                          for w in costs.window_sizes(n)})
        excluding = any(_excludes_known(e) for e in self.endpoints)
        wanted = sorted(set(widths.tolist())) if excluding \
            else [costs.pad_k(how_many)]
        n_users = len(widths)
        pairs = []
        for w in windows:
            for k in wanted:
                lead = int(np.flatnonzero(widths == k)[0]) \
                    if excluding else 0
                exclude = [model.get_known_items(str(lead)) if excluding
                           else set()] + [set()] * (w - 1)
                before = model.twophase_fallbacks
                for attempt in range(WARM_TRIES):
                    users = [lead] + [(lead + 1 + attempt * w + j) % n_users
                                      for j in range(w - 1)]
                    X = np.stack([model.get_user_vector(str(u))
                                  for u in users])
                    model.top_n_batch([how_many] * w, X, exclude)
                    if model.twophase_fallbacks != before:
                        break
                pairs.append((w, k))
        return pairs

    def precheck(self) -> list[str]:
        """``PRECHECK_USERS`` seeded users, an equal share from every
        fetched width the population has (so some know more than 22
        items), asked over HTTP and held to the reference."""
        rng = np.random.default_rng([self.seed, 0xC0221EC7])
        strata = sorted(set(self.widths.tolist()))
        users: list[int] = []
        for n, k in enumerate(strata):
            share = PRECHECK_USERS // len(strata) \
                + (1 if n < PRECHECK_USERS % len(strata) else 0)
            pool = np.flatnonzero(self.widths == k)
            users += rng.choice(pool, size=min(share, len(pool)),
                                replace=False).tolist()
        answers, problems = [], []
        for u in users:
            status, body = _fetch(
                self.layer.port, f"/recommend/{u}?howMany={self.how_many}")
            if status != 200:
                problems.append(f"/recommend/{u}: HTTP {status}")
                continue
            answers.append((str(u), json.loads(body)))
        self.checked_before = len(users)
        return problems + self.reference.check(answers, self.how_many)

    # -- the window ---------------------------------------------------------

    def check(self, samples: list[dict]) -> list[str]:
        """Hold the window's sampled answers to the reference; a sample
        names the endpoint of the mix it answers."""
        problems: list[str] = []
        for excluding in (True, False):
            problems += self.reference.check(
                [(str(s["user"]), s["body"]) for s in samples
                 if _excludes_known(self.endpoints[s["endpoint"]])
                 == excluding],
                self.how_many, exclude_known=excluding)
        return problems

    def counters(self) -> dict:
        batcher = self.layer.top_n_batcher
        return {"twophase_fallbacks": int(self.model.twophase_fallbacks),
                "dispatches": int(batcher.total_dispatches),
                "deadline_rejects": int(batcher.deadline_rejects)}

    def store(self) -> dict:
        """Rows, stored features and item size of the served item
        matrix: what the roofline's cost function needs."""
        vecs, _ = self.model.Y.device_arrays()
        return {"rows": int(vecs.shape[0]),
                "device_features": int(vecs.shape[1]),
                "itemsize": int(vecs.dtype.itemsize)}

    def detail(self) -> dict:
        """Readings for the line before the result; they judge nothing."""
        return {"kernel_route": self.model.metrics().get("kernel_route"),
                "solvers": self.manager.solvers,
                "checked": {"before_window": self.checked_before,
                            "in_all": self.reference.checked,
                            "worst_rel_dev": self.reference.worst_rel_dev}}
