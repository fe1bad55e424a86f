"""Speed-layer kill→restart chaos IT (ISSUE 17 acceptance): a REAL
``python -m oryx_tpu speed --shard 0/1`` process over a durable
``file://`` broker, killed by a conf-armed ``speed-crash-mid-batch``
crash — the exact window where every UP publish of the micro-batch is
durable but the checkpoint commit is lost — then restarted.

The restarted process must resolve the staged batch against the
destination log (every staged record found durable → dedup, zero
republishes), fold any remaining input exactly once, and leave an
update topic and folded factors BYTE-IDENTICAL to an uncrashed control
run over the same model, input, and batch boundaries: zero lost
records, zero double-folds.

Tier-1 coverage of this seam lives in the deterministic simulation
(tests/test_sim_sweep.py, scenario ``speed-shard-crash``: 200 seeded
interleavings per CI run) and the in-process unit proof
(tests/test_speed_shard.py).  This module is the retained real-process
smoke: one wall-clock interleaving through actual OS process death.
"""

from __future__ import annotations

import os
import shutil

import numpy as np
import pytest

from oryx_tpu.app.als.speed import ALSSpeedModelManager
from oryx_tpu.common.config import from_dict, keys_to_hocon
from oryx_tpu.kafka.api import KEY_UP
from oryx_tpu.kafka.inproc import resolve_broker
from oryx_tpu.lambda_rt.batch import BatchLayer
from oryx_tpu.lambda_rt.speed import SpeedLayer
from oryx_tpu.lambda_rt.speed_checkpoint import (H_SPEED_BATCH,
                                                 H_SPEED_SEQ,
                                                 H_SPEED_SHARD,
                                                 SpeedCheckpoint)
from tests.procs import _await, _free_port, _get_json, _spawn

pytestmark = [pytest.mark.chaos, pytest.mark.slow]

_GROUP = "OryxGroup-SpeedLayer-spit-0x1"
_NEW_LINES = ["u0,i1,3.0,1800000000000",
              "newuser,i2,1.0,1800000000001",
              "u3,i5,2.0,1800000000002",
              "u5,i7,1.5,1800000000003"]


def _overlay(broker_dir: str, tmp_path, **extra) -> dict:
    kv = {
        "oryx.id": "spit",
        "oryx.input-topic.broker": f"file://{broker_dir}",
        "oryx.input-topic.partitions": 1,
        "oryx.input-topic.message.topic": "ItInput",
        "oryx.update-topic.broker": f"file://{broker_dir}",
        "oryx.update-topic.message.topic": "ItUpdate",
        "oryx.batch.update-class": "oryx_tpu.app.als.update.ALSUpdate",
        "oryx.speed.model-manager-class":
            "oryx_tpu.app.als.speed.ALSSpeedModelManager",
        "oryx.batch.storage.data-dir": str(tmp_path / "data"),
        "oryx.batch.storage.model-dir": str(tmp_path / "model"),
        "oryx.als.iterations": 3,
        "oryx.als.implicit": True,
        "oryx.als.hyperparams.features": 3,
        "oryx.ml.eval.test-fraction": 0.0,
        "oryx.resilience.retry.max-attempts": 2,
        "oryx.resilience.retry.initial-backoff-ms": 1,
        "oryx.resilience.retry.max-backoff-ms": 2,
        "oryx.resilience.supervisor.enabled": False,
    }
    kv.update(extra)
    return kv


def _write_conf(path: str, kv: dict) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write(keys_to_hocon(sorted(kv.items())))


def _produce_history(broker) -> int:
    rng = np.random.default_rng(5)
    t = 1_700_000_000_000
    n = 0
    for u in range(20):
        for i in range(12):
            if rng.random() < 0.4:
                broker.send("ItInput", None,
                            f"u{u},i{i},{rng.exponential(1):.2f},{t}")
                t += 1000
                n += 1
    return n


def _up_records(broker):
    end = broker.latest_offset("ItUpdate")
    return [km for km in broker.read_range("ItUpdate", 0, end)
            if km.key == KEY_UP]


def _replay_manager(cfg, broker) -> ALSSpeedModelManager:
    mgr = ALSSpeedModelManager(cfg)
    mgr.consume(broker.consume("ItUpdate", from_beginning=True,
                               max_idle_sec=0.3))
    return mgr


def test_kill_restart_mid_micro_batch_zero_lost_zero_double(tmp_path):
    work = str(tmp_path)
    crash_dir = os.path.join(work, "broker-crash")
    ctl_dir = os.path.join(work, "broker-ctl")
    ckpt_dir = os.path.join(work, "speed-ckpt")
    os.makedirs(crash_dir)
    os.makedirs(ctl_dir)

    # one trained model, durable on the file broker: the real batch
    # layer's MODEL publish plus its input history
    batch_cfg = from_dict(_overlay(crash_dir, tmp_path))
    broker = resolve_broker(f"file://{crash_dir}")
    _produce_history(broker)
    BatchLayer(batch_cfg).run_one_generation()
    history_end = broker.latest_offset("ItInput")
    up_history = len(_up_records(broker))

    # control universe: the topic logs copied byte-wise (model
    # artifacts are shared on disk via the MODEL message), its own
    # checkpoint dir, no crash
    for fn in os.listdir(crash_dir):
        if fn.endswith(".topic.jsonl") or fn.endswith(".meta.json"):
            shutil.copy(os.path.join(crash_dir, fn),
                        os.path.join(ctl_dir, fn))
    ctl_broker = resolve_broker(f"file://{ctl_dir}")
    assert ctl_broker.latest_offset("ItInput") == history_end

    # both universes start their fold-in fence at the history head —
    # the worker tails new input, exactly like a deployed speed tier
    for b in (broker, ctl_broker):
        b.set_offsets(_GROUP, "ItInput", [history_end])
        b.flush()  # the child reads the preset group offsets from disk

    # -- the victim: a real speed worker, crash conf-armed ------------------
    obs_port = _free_port()
    conf1 = os.path.join(work, "speed-crash.conf")
    _write_conf(conf1, _overlay(crash_dir, tmp_path, **{
        "oryx.speed.checkpoint-dir": ckpt_dir,
        "oryx.speed.streaming.generation-interval-sec": 1,
        "oryx.obs.metrics-port": obs_port,
        # the kill, in THIS process only: after the batch's UP
        # publishes are durable, before the checkpoint commit
        "oryx.resilience.faults.speed-crash-mid-batch.mode": "crash",
        "oryx.resilience.faults.speed-crash-mid-batch.times": 1,
    }))
    log_path = os.path.join(work, "speed-it.log")
    proc = _spawn(["speed", "--shard", "0/1"], conf1, log_path)
    try:
        # fold-in needs the replayed model first: gate new input on the
        # child's own freshness gauges (records folded against a
        # half-replayed model would be silently skipped, not lost —
        # but then the control comparison would not be like-for-like)
        _await(lambda: (lambda g: g.get("update_lag_records") == 0
                        and g.get("model_generation_age_sec")
                        is not None)(
                            _get_json(obs_port, "/metrics")
                            .get("freshness", {})),
               "speed worker model replay", timeout=120.0)
        for line in _NEW_LINES:
            broker.send("ItInput", None, line)
        # the armed crash kills the batch thread mid-protocol and the
        # process drains out — OS process death at the exact seam
        proc.wait(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=15)

    # the dangerous intermediate state, read from the durable fence:
    # intent staged, UP records durable, input fence NOT advanced
    staged = SpeedCheckpoint(os.path.join(ckpt_dir, "shard-0-of-1"))
    assert staged.pending is not None, "crash fired outside the window"
    n_staged = len(staged.pending["updates"])
    assert n_staged > 0
    batch_a_end = staged.pending["ends"][0]
    assert history_end < batch_a_end <= history_end + len(_NEW_LINES)
    assert len(_up_records(broker)) == up_history + n_staged

    # -- control run: same model, same input, same batch boundaries ---------
    ctl_cfg = from_dict(_overlay(ctl_dir, tmp_path, **{
        "oryx.speed.shard": "0/1",
        "oryx.speed.checkpoint-dir": os.path.join(work, "ctl-ckpt")}))
    ctl = SpeedLayer(ctl_cfg)
    for line in _NEW_LINES[:batch_a_end - history_end]:
        ctl_broker.send("ItInput", None, line)
    ctl.model_manager.consume(ctl_broker.consume(
        "ItUpdate", from_beginning=True, max_idle_sec=0.3))
    ctl.run_one_micro_batch()
    remainder = _NEW_LINES[batch_a_end - history_end:]
    if remainder:
        for line in remainder:
            ctl_broker.send("ItInput", None, line)
        ctl.run_one_micro_batch()

    # -- the restart: fresh process, same checkpoint, no fault --------------
    obs_port2 = _free_port()
    conf2 = os.path.join(work, "speed-restart.conf")
    _write_conf(conf2, _overlay(crash_dir, tmp_path, **{
        "oryx.speed.checkpoint-dir": ckpt_dir,
        "oryx.speed.streaming.generation-interval-sec": 2,
        "oryx.obs.metrics-port": obs_port2,
    }))
    proc2 = _spawn(["speed", "--shard", "0/1"], conf2, log_path)
    try:
        # recovery resolves the stage before anything else: every
        # staged record found durable in the destination log — all
        # dedup, zero republishes — then the remaining input folds
        def _recovered() -> bool:
            m = _get_json(obs_port2, "/metrics")
            return (m["counters"].get("speed_shard_dedup_skips")
                    == n_staged
                    and m.get("freshness", {})
                    .get("input_lag_records") == 0)
        _await(_recovered, "crash recovery + drain", timeout=180.0)
    finally:
        proc2.terminate()
        try:
            proc2.wait(timeout=15)
        except Exception:  # noqa: BLE001 — teardown best effort
            proc2.kill()
            proc2.wait(timeout=15)

    # zero double-folds: the committed fence covers all input, every
    # stamped (shard, batch, seq) identity is durable exactly once
    after = SpeedCheckpoint(os.path.join(ckpt_dir, "shard-0-of-1"))
    assert after.pending is None
    assert after.input == {0: broker.latest_offset("ItInput")}
    ups = _up_records(broker)
    stamped = [(km.headers[H_SPEED_SHARD], km.headers[H_SPEED_BATCH],
                km.headers[H_SPEED_SEQ]) for km in ups
               if km.headers and H_SPEED_SHARD in km.headers]
    assert len(stamped) == len(set(stamped)), \
        "a staged record was republished over its durable copy"

    # zero lost, byte-identically: the update topic equals the
    # uncrashed control's, record for record
    ctl_ups = _up_records(ctl_broker)
    assert [km.message for km in ups] == [km.message for km in ctl_ups]

    # and the folded factors converge byte-identically on full replay
    got = _replay_manager(from_dict(_overlay(crash_dir, tmp_path)),
                          broker).model
    ref = _replay_manager(from_dict(_overlay(ctl_dir, tmp_path)),
                          ctl_broker).model
    assert sorted(got.X.all_ids()) == sorted(ref.X.all_ids())
    assert sorted(got.Y.all_ids()) == sorted(ref.Y.all_ids())
    for uid in ref.X.all_ids():
        assert np.array_equal(got.get_user_vector(uid),
                              ref.get_user_vector(uid))
    for iid in ref.Y.all_ids():
        assert np.array_equal(got.get_item_vector(iid),
                              ref.get_item_vector(iid))
