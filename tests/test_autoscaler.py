"""Gauge-driven autoscaler unit tests (ISSUE 6): the pure decision
core — consecutive-poll streaks, cooldown, thinnest-group targeting,
owned-only scale-down with the live floor — plus the interval-p99
computation over merged bucket deltas and the policy config surface.
The launcher and HTTP are faked; the real-process path is exercised by
the elastic chaos IT."""

from __future__ import annotations

from oryx_tpu.cluster.autoscaler import (Autoscaler, AutoscalePolicy,
                                         ReplicaLauncher, Signals)
from oryx_tpu.common.config import from_dict
from oryx_tpu.lambda_rt.metrics import MetricsRegistry
from oryx_tpu.obs.prom import LATENCY_BUCKETS_MS


class FakeLauncher(ReplicaLauncher):
    def __init__(self):
        self.spawned: list[tuple[int, int]] = []
        self.retired: list[tuple[int, int]] = []
        self._owned: dict[tuple[int, int], int] = {}

    def spawn(self, shard, of):
        self.spawned.append((shard, of))
        self._owned[(shard, of)] = self._owned.get((shard, of), 0) + 1
        return f"fake-{shard}of{of}-{len(self.spawned)}"

    def retire(self, shard, of):
        if self._owned.get((shard, of), 0) <= 0:
            return None
        self._owned[(shard, of)] -= 1
        self.retired.append((shard, of))
        return f"fake-{shard}of{of}"

    def owned(self, of):
        return {s: n for (s, o), n in self._owned.items()
                if o == of and n > 0}


def _policy(**kw):
    base = dict(p99_high_ms=500, p99_low_ms=50, queue_wait_high_ms=200,
                update_lag_high_records=0, scale_up_after=2,
                scale_down_after=3, cooldown_sec=10.0,
                min_replicas_per_shard=1, max_replicas_per_shard=3)
    base.update(kw)
    return AutoscalePolicy(**base)


def _scaler(policy=None, launcher=None, metrics=None):
    return Autoscaler(policy or _policy(), launcher or FakeLauncher(),
                      "http://r", metrics=metrics)


def _sig(p99=None, qw=None, lag=None, groups=None, of=2, ok=True):
    return Signals(ok=ok, merged_of=of,
                   group_sizes=groups or {0: 1, 1: 1},
                   p99_ms=p99, queue_wait_ms=qw,
                   update_lag_records=lag)


def test_scale_up_needs_consecutive_pressure_then_targets_thinnest():
    launcher = FakeLauncher()
    sc = _scaler(launcher=launcher)
    assert sc.step(_sig(p99=800, groups={0: 2, 1: 1}), now=0.0) is None
    action = sc.step(_sig(p99=800, groups={0: 2, 1: 1}), now=1.0)
    assert action == {"kind": "spawn", "shard": 1,
                      "member": "fake-1of2-1",
                      "reason": "p99 800ms > 500"}
    assert launcher.spawned == [(1, 2)]


def test_one_bad_poll_never_scales():
    sc = _scaler()
    assert sc.step(_sig(p99=800), now=0.0) is None
    assert sc.step(_sig(p99=30), now=1.0) is None  # calm resets streak
    assert sc.step(_sig(p99=800), now=2.0) is None
    assert sc.up_streak == 1


def test_cooldown_blocks_followup_actions():
    launcher = FakeLauncher()
    sc = _scaler(launcher=launcher)
    sc.step(_sig(qw=400), now=0.0)
    assert sc.step(_sig(qw=400), now=1.0) is not None
    # pressure persists, but the fleet must settle first
    for t in (2.0, 5.0, 10.9):
        assert sc.step(_sig(qw=400), now=t) is None
    # past the cooldown the streak re-accrues from zero
    assert sc.step(_sig(qw=400), now=12.0) is None
    assert sc.step(_sig(qw=400), now=13.0) is not None
    assert len(launcher.spawned) == 2


def test_max_replicas_per_shard_caps_scale_up():
    launcher = FakeLauncher()
    sc = _scaler(_policy(max_replicas_per_shard=2), launcher)
    sc.step(_sig(p99=900, groups={0: 2, 1: 2}), now=0.0)
    assert sc.step(_sig(p99=900, groups={0: 2, 1: 2}), now=1.0) is None
    assert launcher.spawned == []


def test_scale_down_retires_only_owned_and_respects_live_floor():
    launcher = FakeLauncher()
    sc = _scaler(launcher=launcher)
    # nothing owned: calm forever never touches the static fleet
    for t in range(5):
        assert sc.step(_sig(p99=10), now=float(t)) is None
    launcher.spawn(0, 2)
    launcher.spawn(1, 2)
    sc.up_streak = sc.down_streak = 0
    # shard 1's LIVE group is at the floor (1 member): not eligible
    # even though we own a member there; shard 0 has headroom
    groups = {0: 2, 1: 1}
    assert sc.step(_sig(p99=10, groups=groups), now=20.0) is None
    assert sc.step(_sig(p99=10, groups=groups), now=21.0) is None
    action = sc.step(_sig(p99=10, groups=groups), now=22.0)
    assert action["kind"] == "retire" and action["shard"] == 0
    assert launcher.retired == [(0, 2)]


def test_no_traffic_counts_as_calm():
    launcher = FakeLauncher()
    launcher.spawn(0, 2)
    sc = _scaler(launcher=launcher)
    groups = {0: 2, 1: 1}
    for t in range(2):
        assert sc.step(_sig(p99=None, groups=groups),
                       now=float(t)) is None
    assert sc.step(_sig(p99=None, groups=groups),
                   now=2.0)["kind"] == "retire"


def test_blind_polls_reset_streaks_and_never_act():
    sc = _scaler()
    sc.step(_sig(p99=900), now=0.0)
    assert sc.up_streak == 1
    assert sc.step(_sig(ok=False), now=1.0) is None
    assert sc.up_streak == 0


def test_update_lag_pressure_signal():
    policy = _policy(update_lag_high_records=1000)
    sc = _scaler(policy)
    sc.step(_sig(lag=5000.0), now=0.0)
    action = sc.step(_sig(lag=5000.0), now=1.0)
    assert action is not None and "update_lag" in action["reason"]


def test_gauges_published_each_step():
    metrics = MetricsRegistry()
    sc = _scaler(metrics=metrics)
    sc.step(_sig(p99=123.4, qw=5.6), now=0.0)
    g = metrics.gauges_snapshot()
    assert g["autoscale_p99_ms"] == 123.4
    assert g["autoscale_queue_wait_ms"] == 5.6
    assert g["autoscale_update_lag_records"] == -1.0  # unavailable
    assert g["autoscale_members"] == 0


def test_interval_p99_uses_bucket_deltas_not_history():
    sc = _scaler()

    def snap(counts):
        return {"routes": {
            "GET /recommend/{userID}": {"latency_ms":
                                        {"buckets": list(counts)}},
            # control surface must not vote
            "GET /metrics": {"latency_ms":
                             {"buckets": [1000] * 14}},
        }}

    fast = [0] * 14
    fast[1] = 100  # 100 requests in (1, 2] ms
    assert sc._interval_p99(snap(fast)) is None  # first poll: no delta
    # second poll: 10 NEW slow requests on top of the cumulative fast
    # history — the interval p99 must be slow although lifetime p99 is
    # still fast
    slow = list(fast)
    slow[10] = 10  # (1000, 2000] ms
    p99 = sc._interval_p99(snap(slow))
    assert p99 is not None and p99 > LATENCY_BUCKETS_MS[9]
    # third poll, nothing new: no traffic this interval
    assert sc._interval_p99(snap(slow)) is None


def test_policy_from_config_reads_autoscale_block():
    policy = AutoscalePolicy.from_config(from_dict({
        "oryx.cluster.autoscale.p99-high-ms": 300,
        "oryx.cluster.autoscale.scale-up-after": 4,
    }))
    assert policy.p99_high_ms == 300
    assert policy.scale_up_after == 4
    assert policy.min_replicas_per_shard == 1  # defaults resolve
    assert policy.max_replicas_per_shard == 4


def test_poll_signals_parses_router_metrics():
    payloads = {
        "http://r/metrics": {
            "cluster": {
                "membership": {
                    "shards": 2,
                    "replicas": {
                        "a": {"shard": 0, "of": 2, "ready": True,
                              "live": True, "url": "http://a"},
                        "a2": {"shard": 0, "of": 2, "ready": True,
                               "live": True, "url": "http://a2"},
                        "b": {"shard": 1, "of": 2, "ready": True,
                              "live": True, "url": "http://b"},
                        "dead": {"shard": 1, "of": 2, "ready": True,
                                 "live": False, "url": "http://d"},
                    }},
                "scatter": {"cluster_queue_wait_ms": 42.5}}},
        "http://r/metrics?format=prometheus-json": {"routes": {}},
    }
    sc = Autoscaler(_policy(), FakeLauncher(), "http://r",
                    fetch=lambda url, timeout=5.0: payloads[url])
    s = sc.poll_signals()
    assert s.ok and s.merged_of == 2
    assert s.group_sizes == {0: 2, 1: 1}
    assert s.queue_wait_ms == 42.5
    assert s.p99_ms is None  # first poll has no interval


def test_counter_reset_discards_interval_and_counts():
    """ISSUE 7 satellite: a replica/router restart resets cumulative
    bucket counters to 0 mid-poll.  Clamping per-bucket deltas at 0
    (the old behavior) produced a PARTIALLY-zeroed delta vector whose
    quantile was garbage — the whole interval must be discarded, the
    reset counted, and the next interval measured cleanly against the
    post-reset baseline."""
    metrics = MetricsRegistry()
    sc = _scaler(metrics=metrics)

    def snap(counts):
        return {"routes": {"GET /recommend/{userID}":
                           {"latency_ms": {"buckets": list(counts)}}}}

    healthy = [0] * 14
    healthy[1] = 500          # long fast history in (1, 2] ms
    healthy[10] = 40          # plus some old slow ones (1000, 2000]
    assert sc._interval_p99(snap(healthy)) is None   # first poll
    # the fake replica restarts: counters reset, then 10 fast requests
    # land before the next poll.  Under max(0, c-p) clamping the fast
    # bucket would delta to 0 while nothing else moved -> the old code
    # returned a garbage quantile of an all-zero-except-noise vector;
    # now the monotonicity violation discards the poll.
    restarted = [0] * 14
    restarted[1] = 10
    assert sc._interval_p99(snap(restarted)) is None
    assert sc.counter_resets == 1
    assert metrics.counters_snapshot()["autoscale_counter_resets"] == 1
    # next poll measures cleanly against the post-reset baseline
    after = list(restarted)
    after[1] += 100
    p99 = sc._interval_p99(snap(after))
    assert p99 is not None and p99 <= LATENCY_BUCKETS_MS[1]
    assert sc.counter_resets == 1


def test_slo_burn_pressure_signal_and_gauge():
    """The PR 6 autoscaler scales on raw thresholds; ISSUE 7 wires the
    SLO engine's error-budget burn in as an additional scale-up
    signal (oryx.cluster.autoscale.slo-burn-high)."""
    launcher = FakeLauncher()
    metrics = MetricsRegistry()
    sc = _scaler(_policy(slo_burn_high=10.0, p99_high_ms=0,
                         queue_wait_high_ms=0), launcher, metrics)
    s = _sig()
    s.slo_burn_rate = 25.0
    assert sc.step(s, now=0.0) is None
    action = sc.step(s, now=1.0)
    assert action is not None and "slo_burn 25.0 > 10.0" in action["reason"]
    assert metrics.gauges_snapshot()["autoscale_slo_burn_rate"] == 25.0
    # disabled (the default): the signal never votes
    sc2 = _scaler(_policy(slo_burn_high=0.0, p99_high_ms=0,
                          queue_wait_high_ms=0))
    s2 = _sig()
    s2.slo_burn_rate = 1e9
    assert sc2.policy.pressure(s2) == []


def test_poll_signals_reads_slo_gauge():
    payloads = {
        "http://r/metrics": {
            "cluster": {"membership": {"shards": 1, "replicas": {}},
                        "scatter": {}},
            "freshness": {"slo_burn_rate": 18.5}},
        "http://r/metrics?format=prometheus-json": {"routes": {}},
    }
    sc = Autoscaler(_policy(), FakeLauncher(), "http://r",
                    fetch=lambda url, timeout=5.0: payloads[url])
    assert sc.poll_signals().slo_burn_rate == 18.5
    # engine off -> gauge absent -> None, never 0.0 (absence of
    # evidence must not read as calm)
    del payloads["http://r/metrics"]["freshness"]
    assert sc.poll_signals().slo_burn_rate is None


def test_policy_from_config_reads_slo_burn_high():
    policy = AutoscalePolicy.from_config(from_dict({
        "oryx.cluster.autoscale.slo-burn-high": 14.4}))
    assert policy.slo_burn_high == 14.4
    assert AutoscalePolicy.from_config(
        from_dict({})).slo_burn_high == 0.0  # default: off


def test_poll_signals_survives_unreachable_router():
    def boom(url, timeout=5.0):
        raise OSError("connection refused")

    sc = Autoscaler(_policy(), FakeLauncher(), "http://r", fetch=boom)
    s = sc.poll_signals()
    assert not s.ok
    assert sc.step(s) is None
