"""The ALS application with its speed layer on: a configuration whose
``app`` is ``"als_lambda"`` serves the synthetic model of ``apps/als.py``
while ``/pref`` events are folded into it.

One process holds the chip, so the serving layer ``run.py`` starts and
the speed layer started here are co-located, over the in-process broker
(``SpeedLayer(config, serving=layer)``: one resident copy of the catalog
for both).  The reads are the cell's traffic mix, timed by ``run.py`` as
in every cell.  The writes belong to the CONFIGURATION (its ``writes``
block; the rate is the cell's ``write_rate_per_s``): an open-loop stream
from a second child process running ``loadgen.py`` as it stands, started
``lead_s`` before the window and steady until after it.

``correct`` has four parts (``Checker``): before the window the static
check of ``apps/als.py`` and a seeded burst of ``/pref`` held to the
NumPy fold-in (``als_lambda_reference.py``); in the window every sampled
answer, against the reference over the final factors where nothing it
touches changed and against every version of the factors that can have
been served where something did; after it the accounting (acked = input
= folded, nothing twice) and the served stores against the replay of
the update topic's log.
"""

from __future__ import annotations

import atexit
import http.client
import json
import os
import resource
import subprocess
import sys
import time

import numpy as np

from benchmark import stats
from benchmark.apps import als
from benchmark.apps import als_lambda_reference as ref
from benchmark.apps.als_reference import SCORE_ATOL, SCORE_RTOL

HERE = os.path.dirname(os.path.abspath(__file__))

CHECKS = als.CHECKS
population = als.population

# events of the burst before the window, and how they are spread
BURST_EVENTS = 64
BURST_USERS = 24
BURST_ITEMS = 40
# an acked event is servable within this many generation intervals
STALE_INTERVALS = 3
# touched users asked over HTTP once the stream has stopped
FINAL_USERS = 32
# fetched widths past the population's that a user reaches by the items
# the stream adds to those it knows (Zipf: a few users take most events)
EXTRA_WIDTHS = (512, 1024, 2048, 4096)
# the widest set of known items the accepted scan reference holds
REFERENCE_KNOWN = 246


def overlay(cell, seed: int) -> dict:
    # before the model is built: a program without the co-located mode
    # (the parent of PR 27) ends here, in seconds, instead of meeting
    # its second copy of the 10 GB store at the first update
    import inspect

    from oryx_tpu.lambda_rt.speed import SpeedLayer
    if "serving" not in inspect.signature(SpeedLayer.__init__).parameters:
        raise SystemExit(
            "benchmark: this program's SpeedLayer cannot be co-located with "
            "a serving layer (no `serving` argument): the als_lambda "
            "application cannot run on it")
    broker = f"memory://benchmark-{int(seed)}"
    out = als.overlay(cell, seed)
    out.update({
        "oryx.serving.model-manager-class":
            "benchmark.apps.als_lambda_manager.SyntheticALSLambdaManager",
        "oryx.speed.model-manager-class":
            "oryx_tpu.app.als.speed.ALSSpeedModelManager",
        "oryx.input-topic.broker": broker,
        "oryx.update-topic.broker": broker,
    })
    return out


class Writer:
    """The write stream: ``loadgen.py`` in a child of its own, told an
    open loop of ``POST /pref/{user}/{item}`` at a fixed rate."""

    def __init__(self, port: int, spec: dict, rate: float, seed: int,
                 pop: dict, status: int):
        traffic = {
            "loop": "open",
            "arrivals": {"process": spec["arrivals"], "rate_qps": rate},
            "connections": int(spec["connections"]),
            "users": spec["users"],
            "endpoints": [{
                "weight": 1.0, "method": "POST", "path": spec["path"],
                "body": spec["body"], "expect": {"status": status}}],
            "timeout_s": float(spec.get("timeout_s", 10)),
        }
        self.seconds = float(spec["seconds"])
        job = dict(pop, host="127.0.0.1", port=port,
                   seed=int(seed) ^ 0x3B17E5, seconds=self.seconds,
                   traffic=traffic, rate=float(rate), sample_every=1 << 30)
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(HERE),
                                          "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        atexit.register(self.kill)
        self._tell(json.dumps(job))
        self._hear("ready")
        self._tell("go")
        self.started = self._hear("started")

    def _tell(self, line: str) -> None:
        self.child.stdin.write(line + "\n")
        self.child.stdin.flush()

    def _hear(self, event: str) -> dict:
        line = self.child.stdout.readline()
        said = json.loads(line) if line else {}
        if said.get("event") != event:
            raise RuntimeError(f"the write generator said {line[:200]!r}, "
                               f"not {event!r}")
        return said

    def finish(self) -> dict:
        """Wait for the stream's end and count what it was told."""
        done = self._hear("done")
        self.child.wait(timeout=30)
        recs = done["records"]
        return {"scheduled": done["scheduled"], "unsent": done["unsent"],
                "sent": len(recs),
                "acked": sum(1 for r in recs if r[5]),
                "failed": sum(1 for r in recs if not r[5]),
                "reconnects": done["reconnects"],
                "t0": done["t0"], "seconds": done["seconds"],
                "ack_p50_ms": stats.percentile(
                    [(r[3] - r[1]) * 1e3 for r in recs if r[5]], 50),
                "ack_p99_ms": stats.percentile(
                    [(r[3] - r[1]) * 1e3 for r in recs if r[5]], 99)}

    def kill(self) -> None:
        if self.child.poll() is None:
            self.child.kill()
            self.child.wait()


class Checker(als.Checker):

    def __init__(self, layer, cell, seed: int):
        from oryx_tpu.kafka.inproc import resolve_broker
        from oryx_tpu.lambda_rt.speed import SpeedLayer

        super().__init__(layer, cell, seed)
        self.reference = ref.ScanReference(self.model)
        self.config = cell.config
        self.writes = cell.config["writes"]
        if "write_rate_per_s" not in cell.params:
            raise ValueError(f"cell {cell.name}: benchmark/cells/"
                             f"{cell.name}.json gives no write_rate_per_s")
        self.rate = float(cell.params["write_rate_per_s"])
        self.interval_s = float(cell.config["serving_config"][
            "oryx.speed.streaming.generation-interval-sec"])
        self.stale_ms = STALE_INTERVALS * self.interval_s * 1e3
        self.dtype = self.model.Y.dtype
        self.broker = resolve_broker(layer.input_broker)
        self.input_topic, self.update_topic = \
            layer.input_topic, layer.update_topic
        self.speed = SpeedLayer(layer.config, serving=layer)
        self.speed.start()
        atexit.register(self.speed.close)
        self.writer: Writer | None = None
        self.window_ms: list[float] = []
        self.pref_status = int(self.writes["expect_status"])
        self.readings: dict = {}

    # -- before the window --------------------------------------------------

    def warm(self) -> list[tuple[int, int]]:
        pairs = super().warm()
        model, how_many = self.model, self.how_many
        for k in EXTRA_WIDTHS:
            known = {f"-{j}" for j in range(k - how_many)}
            model.top_n_batch([how_many] * 8,
                              np.zeros((8, model.features), np.float32),
                              [known] + [set()] * 7)
            pairs.append((8, k))
        t = time.monotonic()
        self.readings["sync_programs"] = model.Y.warm_sync()
        # micro-batches up to four intervals of the stream at once
        self.readings["fold_in_programs"] = self.speed.model_manager.warm(
            int(4 * self.rate * self.interval_s) + BURST_EVENTS)
        self.split["warm_writes_s"] = round(time.monotonic() - t, 3)
        status, _ = als._fetch(self.layer.port,
                               "/recommendToAnonymous/0?howMany=10")
        self.readings["recommendToAnonymous"] = status
        return pairs

    def precheck(self) -> list[str]:
        problems = super().precheck()
        t = time.monotonic()
        problems += self.fold_in_check()
        self.split["fold_in_check_s"] = round(time.monotonic() - t, 3)
        if self.readings.get("recommendToAnonymous") != 200:
            problems.append("/recommendToAnonymous: HTTP "
                            f"{self.readings.get('recommendToAnonymous')}")
        self.start_writer(self.rate)
        time.sleep(float(self.writes["lead_s"]))
        return problems

    def start_writer(self, rate: float) -> None:
        self.writer = Writer(self.layer.port, self.writes, rate, self.seed,
                             population(self.config), self.pref_status)

    def _post_prefs(self, events) -> list[str]:
        problems = []
        conn = http.client.HTTPConnection("127.0.0.1", self.layer.port,
                                          timeout=30)
        try:
            for user, item, value in events:
                conn.request("POST", f"/pref/{user}/{item}",
                             body=repr(float(value)))
                resp = conn.getresponse()
                resp.read()
                if resp.status != self.pref_status:
                    problems.append(f"/pref/{user}/{item}: HTTP "
                                    f"{resp.status}")
        finally:
            conn.close()
        return problems

    def _applied(self) -> int:
        return int(self.manager.program.updates_applied
                   + self.manager.program.rejected_updates)

    def _await_quiet(self, timeout_s: float) -> bool:
        """Until every input record is folded and every update applied,
        twice in a row half an interval apart."""
        deadline = time.monotonic() + timeout_s
        group = self.speed._group
        quiet = 0
        while time.monotonic() < deadline:
            ends = self.broker.latest_offsets(self.input_topic)
            got = self.broker.get_offsets(group, self.input_topic)
            folded = all((g or 0) >= e for g, e in zip(got, ends))
            applied = self._applied() >= sum(
                self.broker.latest_offsets(self.update_topic))
            quiet = quiet + 1 if folded and applied else 0
            if quiet >= 2:
                return True
            time.sleep(self.interval_s / 2)
        return False

    def _update_log(self, start: int = 0) -> list:
        end = self.broker.latest_offsets(self.update_topic)[0]
        return [km for km in self.broker.read_range(
            self.update_topic, start, end) if km.key == "UP"]

    def _batches(self, log, in_start: list[int]):
        """The log's records by micro-batch, each with the input lines
        it was derived from (the ``in`` header: the input offsets the
        batch ends at)."""
        out, starts = [], list(in_start)
        for km in log:
            batch = km.headers["batch"]
            if not out or out[-1][0] != batch:
                ends = [int(e) for e in km.headers["in"].split(",")]
                lines = [r.message for r in self.broker.read_ranges(
                    self.input_topic, starts, ends)]
                out.append((batch, lines, []))
                starts = ends
            out[-1][2].append(km)
        return out

    def fold_in_check(self) -> list[str]:
        """A seeded burst of ``/pref`` with no other write in flight, then
        every update the speed layer derives from it against the NumPy
        fold-in from the state before it, the stores against those
        updates, and ``/recommend`` for the users against the scan
        reference over the updated factors."""
        model, problems = self.model, []
        f = model.features
        rng = np.random.default_rng([self.seed, 0xF01D1])
        pop = population(self.config)
        users = [str(u) for u in rng.choice(pop["n_users"], BURST_USERS,
                                            replace=False)]
        users.append(str(pop["n_users"]))  # new to the model
        items = [str(i) for i in rng.choice(pop["n_items"], BURST_ITEMS,
                                            replace=False)]
        events = [(users[j % len(users)],
                   items[int(rng.integers(len(items)))],
                   float(self.writes["strength"]))
                  for j in range(BURST_EVENTS)]
        events[-1] = events[0]  # one pair twice: the strengths add up
        x_state = {u: model.get_user_vector(u) for u in users}
        y_state = {i: model.get_item_vector(i) for i in items}
        gy = ref.gramian(model.Y.device_arrays()[0])[:f, :f]
        gx = ref.gramian(model.X.device_arrays()[0])[:f, :f]
        up0 = self.broker.latest_offsets(self.update_topic)[0]
        in0 = self.broker.latest_offsets(self.input_topic)
        acked0 = self.counters()["events_acked"]
        problems += self._post_prefs(events)
        if self.counters()["events_acked"] - acked0 != len(events):
            problems.append("burst: events_acked moved by "
                            f"{self.counters()['events_acked'] - acked0}, "
                            f"not {len(events)}")
        t = time.monotonic()
        if not self._await_quiet(10 * self.interval_s + 20):
            return problems + ["burst: not folded and applied in time"]
        self.readings["burst_servable_s"] = round(time.monotonic() - t, 3)
        log = self._update_log(up0)
        worst, ref_last = 0.0, {}
        for batch, lines, records in self._batches(log, in0):
            want = {}
            for u, i, v in ref.aggregate(lines, model.implicit):
                want[("X", u, i)] = ref.fold_in(
                    gy, v, x_state.get(u), y_state.get(i), model.implicit)
                want[("Y", i, u)] = ref.fold_in(
                    gx, v, y_state.get(i), x_state.get(u), model.implicit)
            want = {k: v for k, v in want.items() if v is not None}
            got = {}
            for km in records:
                kind, id_, vector, others = ref.parse_up(km.message)
                got[(kind, id_, others[0] if others else None)] = vector
            if set(got) != set(want):
                problems.append(
                    f"batch {batch}: updates for {sorted(set(got) ^ set(want))[:4]} "
                    "differ from the reference's")
            for key in set(got) & set(want):
                dev = float(np.max(np.abs(got[key] - want[key]))
                            / max(1e-30, np.max(np.abs(want[key]))))
                worst = max(worst, dev)
                if not dev <= ref.FOLD_RTOL:
                    problems.append(
                        f"batch {batch}: {key[0]} {key[1]} (with {key[2]}) "
                        f"is {dev:.3g} off the float64 fold-in "
                        f"(limit {ref.FOLD_RTOL})")
            # the next batch folds against what the stores hold now
            for km in records:
                kind, id_, vector, others = ref.parse_up(km.message)
                state, gram = (x_state, gx) if kind == "X" else (y_state, gy)
                new = ref.stored(vector, self.dtype)
                old = state.get(id_)
                gram += np.outer(new, new).astype(np.float64)
                if old is not None:
                    gram -= np.outer(old, old).astype(np.float64)
                state[id_] = new
                key = (kind, id_, others[0] if others else None)
                if key in want:
                    ref_last[(kind, id_)] = want[key]
        self.readings["fold_in_worst_rel"] = worst
        self.readings["burst_batches"] = len({km.headers["batch"]
                                              for km in log})
        problems += self._stores_against(ref_last, ulps=1)
        replayed, known = ref.replay(km.message for km in log)
        problems += self._stores_against(replayed, ulps=0)
        for u, new_items in known.items():
            if not new_items <= model.get_known_items(u):
                problems.append(f"user {u}: the items of its events are "
                                "not among its known items")
        asked = [u for u in users
                 if len(model.get_known_items(u)) <= REFERENCE_KNOWN]
        problems += self._recommend_against_reference(asked)
        return problems

    def _stores_against(self, vectors: dict, ulps: int) -> list[str]:
        """The host mirrors and the device rows of the ids in ``vectors``
        ({(kind, id): float vector}) against those vectors as the store's
        dtype holds them, to within ``ulps`` units in the last place:
        0 for the replay of a log (the same float32 numbers rounded the
        same way); 1 for the float64 fold-in, because the device's
        float32 result and the reference lie some 1e-6 apart, and where
        a rounding boundary of the 8-bit bfloat16 significand falls
        between them they round to neighbours — never further."""
        import jax
        import jax.numpy as jnp

        model, problems = self.model, []
        y_ids = [id_ for kind, id_ in vectors if kind == "Y"]
        rows = [model.Y.row_of(i) for i in y_ids]
        if any(r is None for r in rows):
            return [f"item {y_ids[rows.index(None)]} is not in the store"]
        vecs, _ = model.Y.device_arrays()
        on_device = np.asarray(jax.device_get(jnp.take(
            vecs, jnp.asarray(np.asarray(rows, np.int32)), axis=0))
        )[:, :model.features].astype(np.float32) if rows else []
        for (kind, id_), want in vectors.items():
            held = (model.get_user_vector(id_) if kind == "X"
                    else model.get_item_vector(id_))
            if held is None:
                problems.append(f"{kind} {id_} is not in the model")
                continue
            off = ref.ulps_apart(held, want, self.dtype)
            if off > ulps:
                problems.append(f"{kind} {id_}: the host mirror is {off} "
                                f"ulp off (limit {ulps})")
            if kind == "Y":
                off = ref.ulps_apart(on_device[y_ids.index(id_)], want,
                                     self.dtype)
                if off > ulps:
                    problems.append(f"Y {id_}: the device row is {off} "
                                    f"ulp off (limit {ulps})")
        return problems

    def _recommend_against_reference(self, users: list[str]) -> list[str]:
        answers, problems = [], []
        for u in users:
            status, body = als._fetch(
                self.layer.port, f"/recommend/{u}?howMany={self.how_many}")
            if status != 200:
                problems.append(f"/recommend/{u}: HTTP {status}")
                continue
            answers.append((u, json.loads(body)))
        return problems + self.reference.check(answers, self.how_many)

    # -- the window, and after it ---------------------------------------------

    def counters(self) -> dict:
        out = super().counters()
        program, store = self.manager.program, self.model.Y
        speed = self.speed.model_manager
        out.update({
            "events_acked": int(self.layer.metrics.counters_snapshot()
                                .get("events_acked", 0)),
            "events_folded": int(speed.events_folded),
            "micro_batches": int(speed.micro_batches),
            "updates_applied": int(program.updates_applied),
            "device_syncs": int(store.device_syncs),
            "rows_synced": int(store.rows_synced),
            "gramian_scans": int(store.gramian_scans
                                 + self.model.X.gramian_scans),
            # whole-matrix rebuilds of state derived from the item
            # matrix: none may fall in the window
            "derived_rebuilds": int(getattr(self.model,
                                            "derived_rebuilds", 0)),
            "solver_rebuilds": int(self.model.cached_yty_solver.rebuilds
                                   + self.model.cached_xtx_solver.rebuilds),
        })
        # run.py reads the counters at the window's start and end
        self.window_ms.append(time.time() * 1e3)
        return out

    def finish_writer(self) -> tuple[dict, list[str]]:
        """The end of the stream, then quiet; what the stream was told
        against what the layers counted."""
        problems = []
        told = self.writer.finish()
        self.writer = None
        if not self._await_quiet(10 * self.interval_s + 20):
            problems.append("after the stream: input not folded, or "
                            "updates not applied, in time")
        c = self.counters()
        self.window_ms.pop()
        in_topic = sum(self.broker.latest_offsets(self.input_topic))
        # an arrival the generator had not sent when its time was up
        # (``unsent``: the stream's last arrival, or a backlog) is offered
        # load the sweep judges a rate by; it breaks no guarantee
        if told["failed"]:
            problems.append(f"the write stream: {told['failed']} /pref "
                            f"failed ({told['unsent']} unsent)")
        if not (c["events_acked"] == in_topic == c["events_folded"]):
            problems.append(
                f"acked {c['events_acked']}, input topic {in_topic}, "
                f"folded {c['events_folded']}: an event was lost or "
                "folded twice")
        told.update(input_records=in_topic, counters=c)
        return told, problems

    def check(self, samples: list[dict]) -> list[str]:
        told, problems = self.finish_writer()
        self.readings["writes"] = told
        log = self._update_log()
        replayed, known = ref.replay(km.message for km in log)
        self.readings["ingest_to_applied"] = self._staleness_of(log)
        self.readings["touched"] = {
            "users": sum(1 for k, _ in replayed if k == "X"),
            "items": sum(1 for k, _ in replayed if k == "Y")}
        # served state = the update log applied in order
        problems += self._stores_against(replayed, ulps=0)
        for u, new_items in known.items():
            if not new_items <= self.model.get_known_items(u):
                problems.append(f"user {u}: known items of the log are "
                                "missing from the model")
        problems += self._window_check(samples, log)
        touched = sorted(u for kind, u in replayed if kind == "X"
                         and len(self.model.get_known_items(u))
                         <= REFERENCE_KNOWN)
        rng = np.random.default_rng([self.seed, 0xF1A1])
        asked = rng.choice(touched, size=min(FINAL_USERS, len(touched)),
                           replace=False).tolist() if touched else []
        problems += self._recommend_against_reference(asked)
        self.readings["final_users"] = len(asked)
        return problems

    def _staleness_of(self, log, skip: int = 0) -> dict:
        """Oldest input record of each micro-batch to its last update
        applied to the host mirror; the next drain carries it to the
        device (``speed.ingest_to_servable_ms`` in a traced run).  The
        whole log is walked for the input offsets; batches that begin
        before record ``skip`` are left out."""
        applied = self.manager.batch_applied_ms
        starts = [0] * len(self.broker.latest_offsets(self.input_topic))
        waits, seen = [], None
        for n, km in enumerate(log):
            batch = km.headers["batch"]
            if batch == seen:
                continue
            seen = batch
            ends = [int(e) for e in km.headers["in"].split(",")]
            if n >= skip and batch in applied:
                stamps = [int(r.headers["ts"])
                          for r in self.broker.read_ranges(
                              self.input_topic, starts, ends) if r.headers]
                if stamps:
                    waits.append(applied[batch] - min(stamps))
            starts = ends
        return {"batches": len(waits), "p50_ms": stats.percentile(waits, 50),
                "p99_ms": stats.percentile(waits, 99),
                "max_ms": max(waits) if waits else None}

    def _window_check(self, samples: list[dict], log) -> list[str]:
        """The window's sampled answers.  An id is SETTLED if no update
        of it was published from ``stale_ms`` before the window on: the
        factors it is served from are the final ones throughout.  An
        answer whose user, returned items and reference items are all
        settled is held to the scan reference over the final factors.
        For any other, every returned score has to be the dot product
        of SOME version of the user's vector and SOME version of the
        item's that can have been served inside the window, and no item
        the user came to know ``stale_ms`` before the window may
        appear."""
        model, how_many = self.model, self.how_many
        # run.py's two readings; the burst check made others before them
        start_ms, end_ms = self.window_ms[-2:]
        versions: dict = {}
        learnt: dict = {}
        for km in log:
            kind, id_, vector, others = ref.parse_up(km.message)
            ts = int(km.headers["ts"])
            versions.setdefault((kind, id_), []).append((ts, vector))
            if kind == "X" and ts <= start_ms - self.stale_ms:
                learnt.setdefault(id_, set()).update(others)
        unsettled = {key for key, v in versions.items()
                     if v[-1][0] >= start_ms - self.stale_ms}

        def servable(key):
            """Stored versions of ``key`` that can have been served in
            the window: published before its end and not replaced more
            than ``stale_ms`` before its start."""
            before = self.manager.before.get(key)
            out = [] if before is None else [(0, before)]
            out += versions.get(key, [])
            keep = [v for n, (ts, v) in enumerate(out) if ts <= end_ms and (
                n + 1 == len(out)
                or out[n + 1][0] >= start_ms - self.stale_ms)]
            return [ref.stored(v, self.dtype).astype(np.float64)
                    for v in keep]

        users = [str(s["user"]) for s in samples]
        narrow = [n for n, u in enumerate(users)
                  if len(model.get_known_items(u)) <= REFERENCE_KNOWN]
        self.readings["in_window"] = {
            "sampled": len(samples), "wide": len(samples) - len(narrow)}
        if not narrow:
            return []
        _, ref_rows = self.reference.top_rows(
            [users[n] for n in narrow], how_many)
        row_ids = model.Y.row_ids()
        settled, moving = [], []
        for at, n in enumerate(narrow):
            ids = {str(g.get("id")) for g in samples[n]["body"]} \
                | {row_ids[int(r)] for r in ref_rows[at]}
            if ("X", users[n]) in unsettled \
                    or any(("Y", i) in unsettled for i in ids):
                moving.append(n)
            else:
                settled.append(n)
        self.readings["in_window"].update(settled=len(settled),
                                          moving=len(moving))
        problems = self.reference.check(
            [(users[n], samples[n]["body"]) for n in settled], how_many)
        for n in moving:
            u = users[n]
            xs = servable(("X", u)) or [
                model.get_user_vector(u).astype(np.float64)]
            for rank, got in enumerate(samples[n]["body"]):
                item = str(got.get("id"))
                if item in learnt.get(u, ()):
                    problems.append(
                        f"/recommend/{u} rank {rank}: item {item}, known "
                        f"{self.stale_ms:.0f} ms before the window")
                    continue
                held = model.get_item_vector(item)
                if held is None:
                    problems.append(f"/recommend/{u} rank {rank}: "
                                    f"unknown item {item}")
                    continue
                ys = servable(("Y", item)) or [held.astype(np.float64)]
                score = float(got["value"])
                if not any(abs(score - float(x @ y))
                           <= max(SCORE_ATOL, SCORE_RTOL * abs(score))
                           for x in xs for y in ys):
                    problems.append(
                        f"/recommend/{u} rank {rank}: score {score!r} of "
                        f"{item} is the dot product of none of "
                        f"{len(xs)} x {len(ys)} servable versions")
        return problems

    def detail(self) -> dict:
        out = super().detail()
        out["lambda"] = dict(
            self.readings, write_rate_per_s=self.rate,
            peak_rss_bytes=1024 * resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss,
            solver_failures={
                "yty": self.model.cached_yty_solver.last_failure,
                "xtx": self.model.cached_xtx_solver.last_failure})
        return out
