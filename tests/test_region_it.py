"""Active-active multi-region chaos IT (ISSUE 11 acceptance): REAL OS
processes — per region a `serving --shard 0/1` replica, a `router`, a
`speed` layer, and a `mirror` tailing the OTHER region's update topic —
over two durable ``file://`` brokers, proving:

1. steady state: a fold-in written to region A's router becomes
   servable in region B (and vice versa) through the mirror, and both
   regions answer byte-identically;
2. a partitioned mirror link (fault point ``mirror-link-partition``,
   conf-armed in the mirror processes so it fires there and only
   there): BOTH regions keep serving complete 200s — zero 5xx, zero
   partials — from their local fleets while the staleness gauges
   climb on both mirrors and writes land locally on each side;
3. heal (fresh mirror processes resume from the durable checkpoints):
   both regions converge to byte-identical answers for every user and
   item touched on either side during the partition — with the
   routers' exact result cache ARMED, so the mirrored-UP invalidation
   path is part of what byte-identity proves;
4. the A⇄B pair never ping-pongs: after convergence both topics stop
   growing (loop-prevention headers asserted on the mirrored records).

The mirror kill-mid-replay dedup fence is proven in-process in
tests/test_mirror.py (deterministic crash seam); this module is the
end-to-end topology.  Marker: chaos (tier-1).
"""

from __future__ import annotations

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from oryx_tpu.common import pmml as pmml_io
from oryx_tpu.kafka.api import KEY_MODEL, KEY_UP
from oryx_tpu.kafka.inproc import resolve_broker
from tests.procs import (_await, _free_port, _get_json,
                         _get_json_retry_cold, _spawn, _write_conf)

pytestmark = [pytest.mark.chaos, pytest.mark.slow]
# slow: this module is the retained real-process smoke for scenarios
# whose tier-1 coverage moved to the deterministic simulation
# (tests/test_sim_sweep.py) — hundreds of seeded interleavings per
# run instead of one wall-clock interleaving per CI run.

_USERS = [f"u{j}" for j in range(6)]
_ITEMS = [f"i{j}" for j in range(24)]
_FEATURES = 3
_FAST = {
    "oryx.cluster.heartbeat-interval-ms": 150,
    "oryx.cluster.heartbeat-ttl-ms": 900,
    "oryx.serving.min-model-load-fraction": 1.0,
    "oryx.speed.streaming.generation-interval-sec": 1,
}
# per-region touches stay on DISJOINT users and items: fold-in UP
# records are idempotent SETs, so disjoint ids make the cross-region
# interleaving commute — the convergence argument this IT proves
_TOUCH = {"a": ("u0", ["i1", "i2"]), "b": ("u5", ["i20", "i21"])}


def _publish_model(broker_dir: str) -> None:
    """Inline MODEL + per-row UP flood into region A's topic ONLY: the
    mirror carries the generation to region B — model distribution IS
    mirrored replay, same as every other update."""
    rng = np.random.default_rng(23)
    os.makedirs(broker_dir, exist_ok=True)
    doc = pmml_io.build_skeleton_pmml()
    pmml_io.add_extension(doc, "features", _FEATURES)
    pmml_io.add_extension(doc, "implicit", True)
    pmml_io.add_extension_content(doc, "XIDs", _USERS)
    pmml_io.add_extension_content(doc, "YIDs", _ITEMS)
    # small-magnitude factors: every (user, item) estimate starts well
    # below 1, so implicit fold-ins always have headroom to publish
    # (compute_target_qui is a designed no-op at estimates >= 1 —
    # see tests/test_cache_it.py's /estimate-picked pairs)
    y = np.round(rng.standard_normal((len(_ITEMS), _FEATURES)) * 0.05, 4)
    x = np.round(rng.standard_normal((len(_USERS), _FEATURES)) * 0.05, 4)
    with open(os.path.join(broker_dir, "GwUp.topic.jsonl"), "a",
              encoding="utf-8") as f:
        f.write(json.dumps([KEY_MODEL, pmml_io.to_string(doc)]) + "\n")
        for iid, row in zip(_ITEMS, y.tolist()):
            f.write(json.dumps(
                [KEY_UP, json.dumps(["Y", iid, row])]) + "\n")
        for uid, row in zip(_USERS, x.tolist()):
            f.write(json.dumps(
                [KEY_UP, json.dumps(["X", uid, row, []])]) + "\n")


def _get_raw(port, path, timeout=15):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, dict(r.headers), r.read()


def _post(port, path, body="", timeout=15):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=body.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status


class _Region:
    """One region's process set + addresses."""

    def __init__(self, name: str, work_dir: str):
        self.name = name
        self.work_dir = work_dir
        self.broker_dir = os.path.join(work_dir, f"broker-{name}")
        os.makedirs(self.broker_dir, exist_ok=True)
        self.procs: dict[str, object] = {}
        self.router_port: int | None = None
        self.mirror_obs_port: int | None = None
        self.mirror_ckpt = os.path.join(work_dir, f"mirror-ckpt-{name}")

    def _conf(self, tag: str, port: int, extra: dict) -> str:
        path = os.path.join(self.work_dir, f"{self.name}-{tag}.conf")
        overlay = {"oryx.cluster.region.name": self.name,
                   "oryx.id": f"region-{self.name}", **_FAST, **extra}
        _write_conf(path, self.broker_dir, port, overlay)
        return path

    def _log(self, tag: str) -> str:
        return os.path.join(self.work_dir, f"{self.name}-{tag}.log")

    def spawn_replica(self) -> None:
        port = _free_port()
        conf = self._conf("replica", port, {
            "oryx.cluster.enabled": True,
            "oryx.cluster.shard": "0/1",
            "oryx.cluster.replica-id": f"{self.name}-r0"})
        self.procs["replica"] = (_spawn(["serving", "--shard", "0/1"],
                                        conf, self._log("replica")), port)

    def spawn_router(self) -> None:
        port = _free_port()
        conf = self._conf("router", port, {
            # the exact result cache rides along: mirrored UP records
            # must evict through the router's tap like local ones, so
            # post-heal byte-identity also proves invalidation
            "oryx.cluster.cache.enabled": True,
            "oryx.cluster.coalesce.enabled": True})
        self.procs["router"] = (_spawn(["router"], conf,
                                       self._log("router")), port)
        self.router_port = port

    def spawn_speed(self) -> None:
        conf = self._conf("speed", _free_port(), {
            "oryx.speed.model-manager-class":
                "oryx_tpu.app.als.speed.ALSSpeedModelManager"})
        self.procs["speed"] = (_spawn(["speed"], conf,
                                      self._log("speed")), None)

    def spawn_mirror(self, source: "_Region",
                     partitioned: bool = False) -> None:
        """The inbound mirror: tails ``source``'s topic into ours.
        ``partitioned`` conf-arms ``mirror-link-partition`` unlimited
        in THAT process — every poll fails, the production shape of a
        dead inter-region link."""
        self.mirror_obs_port = _free_port()
        extra = {
            "oryx.cluster.region.mirror.source-broker":
                f"file://{source.broker_dir}",
            "oryx.cluster.region.mirror.source-region": source.name,
            "oryx.cluster.region.mirror.checkpoint-dir":
                self.mirror_ckpt,
            "oryx.cluster.region.mirror.poll-interval-ms": 150,
            "oryx.obs.metrics-port": self.mirror_obs_port,
            "oryx.resilience.supervisor.enabled": False,
        }
        if partitioned:
            extra.update({
                "oryx.resilience.faults.mirror-link-partition.mode":
                    "error",
                "oryx.resilience.faults.mirror-link-partition.times":
                    -1})
        conf = self._conf("mirror", _free_port(), extra)
        self.procs["mirror"] = (_spawn(["mirror"], conf,
                                       self._log("mirror")),
                                self.mirror_obs_port)

    def kill(self, tag: str) -> None:
        proc, _ = self.procs.pop(tag)
        proc.kill()
        proc.wait(timeout=15)

    def mirror_gauges(self) -> dict:
        return _get_json(self.mirror_obs_port, "/metrics").get(
            "freshness", {})

    def data_records(self) -> list:
        """The topic's non-heartbeat records (HB is periodic control
        plane — it grows forever and never mirrors)."""
        broker = resolve_broker(f"file://{self.broker_dir}")
        return [km for km in broker.read_range(
                    "GwUp", 0, broker.latest_offset("GwUp"))
                if km.key != "HB"]

    def close(self) -> None:
        for tag in list(self.procs):
            try:
                self.kill(tag)
            except Exception:  # noqa: BLE001 — teardown best effort
                pass


@pytest.fixture(scope="module")
def regions(tmp_path_factory):
    work = str(tmp_path_factory.mktemp("region-it"))
    a, b = _Region("alpha", work), _Region("beta", work)
    _publish_model(a.broker_dir)  # region A is where the model is born
    try:
        for r in (a, b):
            r.spawn_replica()
            r.spawn_router()
            r.spawn_speed()
        b.spawn_mirror(source=a)
        a.spawn_mirror(source=b)
        # region B's whole model arrives THROUGH the mirror; both
        # replicas must reach full load and both routers coverage
        for r in (a, b):
            _await(lambda r=r: _get_json(
                r.procs["replica"][1], "/shard/meta").get("ready")
                and _get_json(r.procs["replica"][1],
                              "/shard/meta").get("users", 0)
                >= len(_USERS),
                f"{r.name} replica load", timeout=240.0)
            _await(lambda r=r: _get_json(
                r.router_port, "/metrics")["cluster"]["covered_shards"]
                == [0], f"{r.name} router coverage", timeout=60.0)
        # warm the cold scoring path on both routers
        for r in (a, b):
            _get_json_retry_cold(r.router_port,
                                 f"/recommend/{_USERS[0]}?howMany=8")
        yield a, b
    finally:
        a.close()
        b.close()


def _answers(region: _Region, users, items) -> dict[str, bytes]:
    """Raw response bytes for every touched surface — byte-identity is
    the convergence claim, so compare bytes, not parsed floats."""
    out = {}
    for uid in users:
        status, headers, body = _get_raw(
            region.router_port, f"/recommend/{uid}?howMany=8")
        assert status == 200 and not headers.get("X-Oryx-Partial")
        out[f"recommend/{uid}"] = body
        status, _, body = _get_raw(region.router_port,
                                   f"/knownItems/{uid}")
        assert status == 200
        out[f"known/{uid}"] = body
    for i in range(0, len(items) - 1, 2):
        status, headers, body = _get_raw(
            region.router_port,
            f"/similarity/{items[i]}/{items[i + 1]}?howMany=6")
        assert status == 200 and not headers.get("X-Oryx-Partial")
        out[f"similarity/{items[i]}/{items[i + 1]}"] = body
    return out


def _await_gone_from_cache_and_folded(region: _Region, uid: str,
                                      item: str, timeout=90.0) -> None:
    """Wait until the region serves ``uid`` with ``item`` among its
    known items — the fold-in is servable locally."""
    def _has():
        _, _, body = _get_raw(region.router_port, f"/knownItems/{uid}")
        return item.encode() in body
    _await(_has, f"{region.name} serves fold-in {uid}/{item}",
           timeout=timeout)


def test_01_steady_state_fold_in_crosses_regions(regions):
    a, b = regions
    # identity probe — the failover runbook's first question
    assert _get_json(a.router_port, "/admin/region")["region"] == "alpha"
    assert _get_json(b.router_port, "/admin/region")["region"] == "beta"
    assert _get_json(b.mirror_obs_port,
                     "/admin/region")["source_region"] == "alpha"
    # a write in region A...
    assert _post(a.router_port, "/pref/u1/i5", "2.0") in (200, 204)
    # ...folds locally (speed A) and crosses the mirror into B
    _await_gone_from_cache_and_folded(a, "u1", "i5")
    _await_gone_from_cache_and_folded(b, "u1", "i5")
    # replayed mirrored records are visible on the mirror's counters
    m = _get_json(b.mirror_obs_port, "/metrics")
    assert m["counters"]["mirror_records_replayed"] >= 1
    # the headless mirror exposes breaker state (ISSUE 11 satellite)
    assert m["resilience"]["mirror-replay-dest"]["state"] == "closed"
    # both regions answer byte-identically once drained
    _await(lambda: _answers(a, ["u1"], []) == _answers(b, ["u1"], []),
           "steady-state byte identity", timeout=60.0)


def test_02_partition_serve_local_climb_then_converge(regions):
    # retained as the real-process smoke for this scenario; the
    # tier-1 coverage moved to the deterministic sim, which sweeps
    # hundreds of partition/heal interleavings per run at ~0.1 s each
    # (tests/test_sim_sweep.py, scenario "mirror-partition")
    a, b = regions
    # === partition the link: replace both healthy mirrors with ones
    # whose every poll fails at the mirror-link-partition seam ===
    a.kill("mirror")
    b.kill("mirror")
    b.spawn_mirror(source=a, partitioned=True)
    a.spawn_mirror(source=b, partitioned=True)
    _await(lambda: _get_json(a.mirror_obs_port, "/metrics")
           ["counters"].get("mirror_link_failures", 0) > 0
           and _get_json(b.mirror_obs_port, "/metrics")
           ["counters"].get("mirror_link_failures", 0) > 0,
           "both links down", timeout=60.0)

    # === divergent writes on both sides (disjoint users AND items) ===
    (ua, items_a), (ub, items_b) = _TOUCH["a"], _TOUCH["b"]
    for item in items_a:
        assert _post(a.router_port, f"/pref/{ua}/{item}", "3.0") in (200, 204)
    for item in items_b:
        assert _post(b.router_port, f"/pref/{ub}/{item}", "3.0") in (200, 204)
    # each side serves its OWN writes from its local fleet...
    _await_gone_from_cache_and_folded(a, ua, items_a[0])
    _await_gone_from_cache_and_folded(b, ub, items_b[0])

    # === both regions keep serving COMPLETE answers: zero 5xx, zero
    # partials, across the whole user population ===
    failures, partials = [], 0
    for round_ in range(3):
        for r in (a, b):
            for uid in _USERS:
                try:
                    status, headers, _ = _get_raw(
                        r.router_port, f"/recommend/{uid}?howMany=8")
                    if status != 200:
                        failures.append((r.name, uid, status))
                    elif headers.get("X-Oryx-Partial"):
                        partials += 1
                except Exception as e:  # noqa: BLE001 — any counts
                    failures.append((r.name, uid, str(e)))
    assert failures == []
    assert partials == 0

    # === the divergence is real (B hasn't seen A's write)... ===
    _, _, known_b = _get_raw(b.router_port, f"/knownItems/{ua}")
    assert items_a[0].encode() not in known_b
    # === ...and MEASURED: staleness gauges climb on both mirrors ===
    g1 = {r.name: r.mirror_gauges() for r in (a, b)}
    time.sleep(1.0)
    g2 = {r.name: r.mirror_gauges() for r in (a, b)}
    for name in ("alpha", "beta"):
        assert g2[name]["cross_region_staleness_ms"] \
            > g1[name]["cross_region_staleness_ms"], name
    # lag counts the unreplayed records stuck behind the partition
    assert g2["alpha"]["mirror_lag_records"] > 0
    assert g2["beta"]["mirror_lag_records"] > 0

    # === heal: fresh mirrors resume from the durable checkpoints ===
    a.kill("mirror")
    b.kill("mirror")
    b.spawn_mirror(source=a)
    a.spawn_mirror(source=b)
    _await(lambda: a.mirror_gauges().get("mirror_lag_records") == 0
           and b.mirror_gauges().get("mirror_lag_records") == 0,
           "mirrors drained after heal", timeout=120.0)
    # both speed layers + replicas must absorb the mirrored tail
    _await_gone_from_cache_and_folded(b, ua, items_a[0])
    _await_gone_from_cache_and_folded(a, ub, items_b[0])

    # === the mirrored UP records drove PRECISE evictions through each
    # router's tap (the invalidation path works cross-region exactly
    # like locally)... ===
    for r in (a, b):
        assert _get_json(r.router_port,
                         "/admin/cache")["invalidations"] > 0, r.name
    # ...but per-tag precision leaves PR 8's documented residual: an
    # entry for an UNtouched key whose rows reference a re-folded
    # item's vector persists until touch/eviction/generation — in
    # production bounded by live traffic and generation publishes, in
    # this frozen post-heal world by the runbook's one flush (the same
    # docs/SCALING.md "Result cache" argument, now cross-region)
    for r in (a, b):
        req = urllib.request.Request(
            f"http://127.0.0.1:{r.router_port}/admin/cache/flush",
            data=b"", method="POST")
        with urllib.request.urlopen(req, timeout=15) as resp:
            resp.read()

    # === convergence: byte-identical answers for EVERY user and item
    # touched on either side during the partition (result cache armed:
    # repeated reads below also pin hit==miss byte identity) ===
    touched_users = [ua, ub]
    touched_items = items_a + items_b

    def _converged():
        return _answers(a, touched_users, touched_items) \
            == _answers(b, touched_users, touched_items)

    try:
        _await(_converged, "post-heal byte identity", timeout=120.0)
    except RuntimeError:
        ans_a = _answers(a, touched_users, touched_items)
        ans_b = _answers(b, touched_users, touched_items)
        diff = {k: (ans_a.get(k), ans_b.get(k))
                for k in set(ans_a) | set(ans_b)
                if ans_a.get(k) != ans_b.get(k)}
        raise AssertionError(f"byte identity diff: {diff}")
    ans_a = _answers(a, touched_users, touched_items)
    ans_b = _answers(b, touched_users, touched_items)
    assert ans_a == ans_b
    # the divergent folds actually reached the answers (not a trivial
    # identity of untouched state)
    assert _TOUCH["a"][1][0].encode() in ans_a[f"known/{ua}"]
    assert _TOUCH["b"][1][0].encode() in ans_a[f"known/{ub}"]


def test_03_no_ping_pong_after_convergence(regions):
    """Loop prevention end to end: once both regions are drained, the
    A⇄B pair must reach a FIXED POINT — neither topic grows while no
    new writes arrive (a ping-pong would grow both forever)."""
    a, b = regions
    _await(lambda: a.mirror_gauges().get("mirror_lag_records") == 0
           and b.mirror_gauges().get("mirror_lag_records") == 0,
           "drained", timeout=60.0)
    counts1 = (len(a.data_records()), len(b.data_records()))
    time.sleep(2.0)  # many mirror poll intervals
    counts2 = (len(a.data_records()), len(b.data_records()))
    assert counts1 == counts2, \
        "data records grew with no writes: ping-pong"
    # loop-prevention headers did the work, countably
    la = _get_json(a.mirror_obs_port, "/metrics")["counters"]
    lb = _get_json(b.mirror_obs_port, "/metrics")["counters"]
    assert la.get("mirror_loop_drops", 0) > 0 \
        or lb.get("mirror_loop_drops", 0) > 0
    # and every mirrored record in each topic names the OTHER region
    for region, foreign in ((a, "beta"), (b, "alpha")):
        origins = {(km.headers or {}).get("origin-region")
                   for km in region.data_records()}
        origins.discard(None)
        assert origins == {foreign}, (region.name, origins)
