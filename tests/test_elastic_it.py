"""Elastic-topology chaos IT (ISSUE 6 acceptance): REAL OS processes
over a durable ``file://`` broker — `python -m oryx_tpu serving
--shard i/N` replicas and the `router`, exactly the production
topology — proving, with one router process and no restarts anywhere:

1. killing one member of a 2-replica group yields ZERO partial answers
   and zero 5xx on ``/recommend`` after the TTL window, byte-identical
   ids to the pre-kill answers (a dead replica costs latency, not
   coverage);
2. a live 2→3 reshard under continuous load completes with no
   downtime and exact answers before, during, and after the atomic
   cutover — and the retired fleet's stale heartbeats are counted,
   never merged;
3. ``reshard-warm-stall``: a new-topology replica stalled mid-replay
   (conf-armed fault, so it fires in THAT process only) never becomes
   ready, so cutover never happens and the old topology keeps serving
   exact answers;
4. ``replica-group-flap``: a group member whose heartbeats straggle
   just past the TTL oscillates in and out of routing with zero
   partial answers and zero topology churn.

Scenarios share one module-scoped cluster and run in file order (the
topology evolves 2 → 3 across them).  Marker: chaos (tier-1).
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from oryx_tpu.cluster.sharding import shard_of
from oryx_tpu.common import pmml as pmml_io
from oryx_tpu.kafka.api import KEY_MODEL_REF
from oryx_tpu.kafka.inproc import resolve_broker
from tests.procs import (_await, _free_port, _get_json, _spawn,
                         _write_conf)

pytestmark = [pytest.mark.chaos, pytest.mark.slow]
# slow: this module is the retained real-process smoke for scenarios
# whose tier-1 coverage moved to the deterministic simulation
# (tests/test_sim_sweep.py) — hundreds of seeded interleavings per
# run instead of one wall-clock interleaving per CI run.

_USERS = [f"u{j}" for j in range(6)]
_ITEMS = [f"i{j}" for j in range(60)]
_FEATURES = 3
# fast membership so TTL transitions fit the tier-1 budget
_FAST = {
    "oryx.cluster.heartbeat-interval-ms": 150,
    "oryx.cluster.heartbeat-ttl-ms": 900,
    "oryx.cluster.hedge-after-ms": 60,
    "oryx.cluster.max-attempts-per-shard": 3,
    # ready only at FULL replay: a warming replica must never answer
    # for users it has not absorbed yet (exactness during cutover)
    "oryx.serving.min-model-load-fraction": 1.0,
}


def _publish_model(broker_dir: str, work_dir: str) -> None:
    """SHARDED publish (ISSUE 10): a manifest-carrying MODEL-REF whose
    murmur2 slices live in the shared store, and NO per-row UP flood —
    so every replica in this IT (including the 2→3 reshard's warming
    fleet) loads from slices + the topic tail, never a full-stream
    replay.  The ring (24) is divisible by both topologies this IT
    walks (2 and 3)."""
    from oryx_tpu.app.als import slices as model_slices

    broker = resolve_broker(f"file://{broker_dir}")
    rng = np.random.default_rng(11)
    doc = pmml_io.build_skeleton_pmml()
    pmml_io.add_extension(doc, "features", _FEATURES)
    pmml_io.add_extension(doc, "implicit", True)
    pmml_io.add_extension_content(doc, "XIDs", _USERS)
    pmml_io.add_extension_content(doc, "YIDs", _ITEMS)
    model_dir = os.path.join(work_dir, "model-gen1")
    os.makedirs(model_dir, exist_ok=True)
    pmml_path = os.path.join(model_dir, "model.pmml.xml")
    pmml_io.write(doc, pmml_path)
    Y = np.round(rng.standard_normal((len(_ITEMS), _FEATURES)), 3
                 ).astype(np.float32)
    X = np.round(rng.standard_normal((len(_USERS), _FEATURES)), 3
                 ).astype(np.float32)
    # monolithic artifacts alongside the slices — the production
    # layout, so a fail-closed load would degrade instead of hanging
    # (the IT still asserts the warm path took slices, zero fallbacks)
    from oryx_tpu.app.als.update import save_features
    save_features(os.path.join(model_dir, "Y"), _ITEMS, Y)
    save_features(os.path.join(model_dir, "X"), _USERS, X)
    slim = model_slices.publish_sliced(model_dir, _ITEMS, Y, _USERS, X,
                                       None, 24)
    broker.send("GwUp", KEY_MODEL_REF,
                model_slices.model_ref_message(pmml_path, model_dir,
                                               slim))
    broker.close()


def _get(port, path, timeout=15):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, dict(r.headers), json.loads(r.read() or b"null")


def _post_json(port, path, payload, timeout=15):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode("utf-8"), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read() or b"null")


class _Cluster:
    """Process bookkeeping for the module's evolving fleet."""

    def __init__(self, work_dir, broker_dir):
        self.work_dir = work_dir
        self.broker_dir = broker_dir
        self.procs: dict[str, tuple] = {}  # name -> (Popen, port)
        self.router_port: int | None = None

    def spawn_replica(self, name: str, shard: int, of: int,
                      extra: dict | None = None) -> int:
        port = _free_port()
        conf = os.path.join(self.work_dir, f"{name}.conf")
        overlay = {"oryx.cluster.enabled": True,
                   "oryx.cluster.shard": f"{shard}/{of}",
                   "oryx.cluster.replica-id": name, **_FAST,
                   **(extra or {})}
        _write_conf(conf, self.broker_dir, port, overlay)
        proc = _spawn(["serving", "--shard", f"{shard}/{of}"], conf,
                      os.path.join(self.work_dir, f"{name}.log"))
        self.procs[name] = (proc, port)
        return port

    def spawn_router(self) -> int:
        port = _free_port()
        conf = os.path.join(self.work_dir, "router.conf")
        _write_conf(conf, self.broker_dir, port, dict(_FAST))
        proc = _spawn(["router"], conf,
                      os.path.join(self.work_dir, "router.log"))
        self.procs["router"] = (proc, port)
        self.router_port = port
        return port

    def kill(self, name: str) -> None:
        proc, _ = self.procs.pop(name)
        proc.kill()  # SIGKILL: a crash, not a graceful drain
        proc.wait(timeout=15)

    def await_ready(self, names, timeout=240.0) -> None:
        ports = [self.procs[n][1] for n in names]
        _await(lambda: all(_get_json(p, "/shard/meta").get("ready")
                           for p in ports),
               f"replicas ready: {names}", timeout=timeout)

    def close(self) -> None:
        for name in list(self.procs):
            try:
                self.kill(name)
            except Exception:  # noqa: BLE001 — teardown best effort
                pass


class _LoadProbe(threading.Thread):
    """Continuous /recommend load with per-response verdicts: any
    non-200, any X-Oryx-Partial, any id-set drift from the expected
    exact answers is recorded."""

    def __init__(self, port, expected: dict[str, list[str]]):
        super().__init__(daemon=True)
        self.port = port
        self.expected = expected
        self.stop_event = threading.Event()
        self.count = 0
        self.failures: list[str] = []
        self.partials = 0

    def run(self):
        users = sorted(self.expected)
        i = 0
        while not self.stop_event.is_set():
            uid = users[i % len(users)]
            i += 1
            try:
                status, headers, rows = _get(
                    self.port, f"/recommend/{uid}?howMany=8")
                if status != 200:
                    self.failures.append(f"{uid}: HTTP {status}")
                elif headers.get("X-Oryx-Partial"):
                    self.partials += 1
                elif [d["id"] for d in rows] != self.expected[uid]:
                    self.failures.append(f"{uid}: ids drifted")
            except Exception as e:  # noqa: BLE001 — any failure counts
                self.failures.append(f"{uid}: {type(e).__name__}: {e}")
            self.count += 1
            time.sleep(0.02)

    def halt(self) -> "_LoadProbe":
        self.stop_event.set()
        self.join(10.0)
        return self


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    # the synthetic catalog must populate every shard of every
    # topology this IT walks through (2, 3, and the re-declared 2)
    for n in (2, 3):
        owners = {shard_of(i, n) for i in _ITEMS}
        assert owners == set(range(n)), f"catalog misses shards at {n}"
    work = tmp_path_factory.mktemp("elastic-it")
    broker_dir = str(work / "broker")
    os.makedirs(broker_dir)
    _publish_model(broker_dir, str(work))
    c = _Cluster(str(work), broker_dir)
    try:
        # shard 0 is a 2-way replica GROUP; shard 1 single-member
        c.spawn_replica("a1", 0, 2)
        c.spawn_replica("a2", 0, 2)
        c.spawn_replica("b", 1, 2)
        c.spawn_router()
        c.await_ready(["a1", "a2", "b"])
        _await(lambda: _get_json(c.router_port, "/metrics")
               ["cluster"]["covered_shards"] == [0, 1],
               "router coverage", timeout=60.0)
        # exact expected answers per user, captured while whole
        expected = {}
        for uid in _USERS:
            status, headers, rows = _get(c.router_port,
                                         f"/recommend/{uid}?howMany=8")
            assert status == 200 and not headers.get("X-Oryx-Partial")
            expected[uid] = [d["id"] for d in rows]
        c.expected = expected
        yield c
    finally:
        c.close()


def test_01_kill_group_member_zero_partials_zero_5xx(cluster):
    c = cluster
    c.kill("a2")  # one member of shard 0's 2-way group
    time.sleep(1.5 * _FAST["oryx.cluster.heartbeat-ttl-ms"] / 1000.0)
    # after the TTL window the dead member has aged out: the sibling
    # covers its shard — full coverage, zero partials, zero 5xx
    status, _, _ = _get(c.router_port, "/ready")
    assert status in (200, 204)
    for round_ in range(3):
        for uid in _USERS:
            status, headers, rows = _get(
                c.router_port, f"/recommend/{uid}?howMany=8")
            assert status == 200, (round_, uid)
            assert headers.get("X-Oryx-Partial") is None, (round_, uid)
            assert [d["id"] for d in rows] == c.expected[uid], uid
    # the failover left countable evidence on the router
    m = _get_json(c.router_port, "/metrics")
    assert m["cluster"]["membership"]["shards"] == 2


def test_02_live_reshard_2_to_3_under_continuous_load(cluster):
    # retained as the real-process smoke for this scenario; the
    # tier-1 coverage moved to the deterministic sim, which sweeps
    # hundreds of cutover interleavings per run at ~0.05 s each
    # (tests/test_sim_sweep.py, scenario "reshard-cutover")
    c = cluster
    # runbook step 1: declare the target
    status, st = _post_json(c.router_port, "/admin/topology", {"of": 3})
    assert status == 200 and st["reshard_target"] == 3
    probe = _LoadProbe(c.router_port, c.expected)
    probe.start()
    try:
        # step 2: start the M-way fleet (it warms from the same topic
        # through the murmur2 ring while the old fleet keeps serving)
        for s in range(3):
            c.spawn_replica(f"n{s}", s, 3)
        # step 3: watch /admin/topology until the atomic cutover
        _await(lambda: _get_json(c.router_port, "/admin/topology")
               ["merged_of"] == 3, "cutover to 3", timeout=240.0)
        time.sleep(1.0)  # keep load flowing across the cutover wake
    finally:
        probe.halt()
    assert probe.count > 50
    assert probe.failures == []
    assert probe.partials == 0
    # the old fleet still runs: its heartbeats are now stale — counted,
    # never merged
    _await(lambda: _get_json(c.router_port, "/metrics")["counters"]
           .get("stale_topology_heartbeats", 0) > 0,
           "stale heartbeats counted", timeout=30.0)
    snap = _get_json(c.router_port, "/metrics")["cluster"]["membership"]
    assert snap["shards"] == 3
    assert all(r["of"] == 3 for r in snap["replicas"].values())
    assert snap["topology_cutovers"] == 1
    # the warming fleet loaded from SLICES, not a full-stream replay:
    # every new replica shows slice bytes read, a stamped load clock,
    # and zero fallbacks to the monolithic artifacts (ISSUE 10
    # acceptance — reshard warmup is slices + topic tail)
    for s in range(3):
        g = _get_json(c.procs[f"n{s}"][1], "/metrics")["freshness"]
        assert g.get("slice_load_fallbacks") == 0, (s, g)
        assert g.get("model_slice_bytes", 0) > 0, (s, g)
        assert g.get("model_load_s", 0) > 0, (s, g)
    # step 4: retire the old fleet — answers stay exact and complete
    c.kill("a1")
    c.kill("b")
    time.sleep(1.5 * _FAST["oryx.cluster.heartbeat-ttl-ms"] / 1000.0)
    for uid in _USERS:
        status, headers, rows = _get(c.router_port,
                                     f"/recommend/{uid}?howMany=8")
        assert status == 200 and headers.get("X-Oryx-Partial") is None
        assert [d["id"] for d in rows] == c.expected[uid], uid


def test_03_reshard_warm_stall_never_cuts_over(cluster):
    c = cluster
    # scale back down: 2 was retired at the 2→3 cutover; re-declaring
    # un-retires it (the runbook's scale-down path)
    _post_json(c.router_port, "/admin/topology", {"of": 2})
    # shard 0's new replica stalls mid-replay — conf-armed, so the
    # fault fires in THAT process only; it never reaches ready
    c.spawn_replica("stall0", 0, 2, extra={
        "oryx.resilience.faults.reshard-warm-stall.mode": "delay",
        "oryx.resilience.faults.reshard-warm-stall.times": -1,
        "oryx.resilience.faults.reshard-warm-stall.delay-ms": 60000,
    })
    c.spawn_replica("ok1", 1, 2)
    c.await_ready(["ok1"])
    # give the would-be cutover every chance, under live checks: the
    # target topology never reaches full coverage, so the OLD topology
    # keeps serving exact, complete answers
    t_end = time.monotonic() + 4.0
    while time.monotonic() < t_end:
        status = _get_json(c.router_port, "/admin/topology")
        assert status["merged_of"] == 3
        t2 = status["topologies"].get("2")
        if t2 is not None:
            assert not t2["full_coverage"]
            assert t2["ready_shards"] <= 1
        uid = _USERS[0]
        s, headers, rows = _get(c.router_port,
                                f"/recommend/{uid}?howMany=8")
        assert s == 200 and headers.get("X-Oryx-Partial") is None
        assert [d["id"] for d in rows] == c.expected[uid]
        time.sleep(0.2)
    assert _get_json(c.router_port, "/metrics")["cluster"][
        "membership"]["topology_cutovers"] == 1  # still just 2→3
    # abandon the stalled reshard: cancel the target, stop its fleet
    _post_json(c.router_port, "/admin/topology", {"of": 3})
    c.kill("stall0")
    c.kill("ok1")


def test_04_replica_group_flap_causes_no_routing_churn(cluster):
    c = cluster
    cutovers_before = _get_json(c.router_port, "/metrics")["cluster"][
        "membership"]["topology_cutovers"]
    # a sibling for shard 0 whose heartbeats straggle past the TTL:
    # each publish sleeps 1.5 s against a 0.9 s TTL, so it keeps
    # aging out of routing and returning — the flap
    c.spawn_replica("flappy", 0, 3, extra={
        "oryx.resilience.faults.replica-group-flap.mode": "delay",
        "oryx.resilience.faults.replica-group-flap.times": -1,
        "oryx.resilience.faults.replica-group-flap.delay-ms": 1500,
    })
    _await(lambda: "flappy" in _get_json(
        c.router_port, "/metrics")["cluster"]["membership"]["replicas"],
        "flapping member announced", timeout=240.0)
    live_states = set()
    failures, partials = [], 0
    t_end = time.monotonic() + 5.0
    i = 0
    while time.monotonic() < t_end:
        uid = _USERS[i % len(_USERS)]
        i += 1
        try:
            status, headers, rows = _get(c.router_port,
                                         f"/recommend/{uid}?howMany=8")
            if status != 200:
                failures.append(status)
            elif headers.get("X-Oryx-Partial"):
                partials += 1
            elif [d["id"] for d in rows] != c.expected[uid]:
                failures.append(f"{uid} drifted")
        except Exception as e:  # noqa: BLE001 — any failure counts
            failures.append(str(e))
        snap = _get_json(c.router_port, "/metrics")["cluster"][
            "membership"]
        flap = snap["replicas"].get("flappy")
        if flap is not None:
            live_states.add(flap["live"])
        assert snap["shards"] == 3  # no topology churn, ever
        time.sleep(0.05)
    # the member really oscillated around the TTL...
    assert live_states == {True, False}, live_states
    # ...and routing never wavered: group siblings absorbed every flap
    assert failures == []
    assert partials == 0
    assert _get_json(c.router_port, "/metrics")["cluster"][
        "membership"]["topology_cutovers"] == cutovers_before
    c.kill("flappy")
