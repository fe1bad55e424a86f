"""The speed layer: incremental model updates from micro-batches.

Reference: framework/oryx-lambda/src/main/java/com/cloudera/oryx/lambda/
speed/SpeedLayer.java:58-221 — a consumer thread replays the update
topic from the beginning into the model manager (:107-137), while the
input stream is processed in micro-batches whose derived deltas are
published with key "UP" (SpeedLayerUpdate.java:37-65, async producer).

Sharded operation (docs/SCALING.md "Sharded speed layer"): with
``oryx.speed.shard = "i/N"`` (``python -m oryx_tpu speed --shard i/N``)
a worker still consumes the FULL input and update topics — fold-in
needs the whole catalog's Gramians and the full user store, exactly
like a serving replica — but its model manager folds only events whose
item lands on the worker's murmur2 ring slot, and all N workers
publish into the one update topic (the cross-region mirror already
proves multi-writer convergence).  A crash stalls freshness for 1/N of
the catalog instead of all of it.

Crash safety (lambda_rt/speed_checkpoint.py): with
``oryx.speed.checkpoint-dir`` set, each micro-batch durably stages its
derived update bytes BEFORE publishing, stamps every publish with
(shard, batch, seq) headers, and commits consumed input offsets
atomically AFTER the publishes.  Recovery scans the update topic from
the last ``dest_scanned`` mark to learn which staged records actually
landed and republishes only the missing ones, byte-exactly — a kill
between publish and checkpoint replays the batch but dedups
(``speed_shard_dedup_skips``) instead of double-folding.  Unset, the
worker keeps the legacy group-offset at-least-once contract.

Observability (docs/OBSERVABILITY.md): the tier is headless, so its
freshness gauges — input/update consumer lag, model generation age,
micro-batch duration, checkpoint age, and the end-to-end
``ingest_to_servable_ms`` measured from the ``ts`` record headers the
serving front end stamps — are served by the side-door ObsServer on
``oryx.obs.metrics-port``.  Records carrying a ``traceparent`` header
(sampled ``/ingest``-family requests) get a retroactive
``speed.fold_in`` span attached to their originating trace, so a
client request can be followed to the update that made it servable.

Co-located with a serving layer (``SpeedLayer(config, serving=layer)``:
one process holds the chip, README "Sharing a host") the two share what
would not fit twice: the model manager folds in against the serving
model's own stores (``attach_serving``: one copy of the catalog on the
device, one host mirror) and spans go to the serving layer's tracer, so
one ring holds a request and the micro-batch that changed its answer.
Each micro-batch is a ``speed.micro_batch`` trace (``speed.gramian``,
``speed.solve`` and ``speed.publish`` under it), and every UP record
carries its micro-batch's number (``batch``) and the input offsets it
ends at (``in``), so a reader of the update topic can tell which input
each update came from.
"""

from __future__ import annotations

import contextlib
import logging
import threading

from ..common import clock as clockmod
from ..common import compile_cache, store
from ..common.config import Config
from ..common.lang import load_instance, logging_call
from ..kafka import utils as kafka_utils
from ..kafka.api import KEY_UP, KeyMessage
from ..kafka.inproc import InProcTopicProducer, resolve_broker
from ..obs import (events_from_config, flight_from_config, freshness,
                   tracer_from_config)
from ..obs.server import ObsServer
from ..obs import trace as obstrace
from ..obs.trace import parse_traceparent
from ..resilience import faults
from ..resilience.policy import (ResilientTopicProducer, Retry,
                                 run_with_resubscribe)
from . import speed_checkpoint
from .metrics import MetricsRegistry
from .speed_checkpoint import SpeedCheckpoint

_log = logging.getLogger(__name__)

__all__ = ["SpeedLayer"]


def _wall_ms(t_mono: float) -> int:
    """The wall-clock millisecond of a monotonic stamp taken a moment
    ago (the ``ts`` headers it is compared with are wall-clock)."""
    return int((clockmod.now() - (clockmod.monotonic() - t_mono)) * 1000)


class SpeedLayer:

    def __init__(self, config: Config, serving=None):
        """``serving``: the ``ServingLayer`` of this process, when the
        two are co-located (module docstring)."""
        self.config = config
        self.id = config.get_optional_string("oryx.id")
        self.input_broker = config.get_string("oryx.input-topic.broker")
        self.input_topic = config.get_string("oryx.input-topic.message.topic")
        self.update_broker = config.get_string("oryx.update-topic.broker")
        self.update_topic = config.get_string("oryx.update-topic.message.topic")
        self.generation_interval_sec = config.get_int(
            "oryx.speed.streaming.generation-interval-sec")
        # ring-sharded fold-in: "i/N" gives this worker slice i of the
        # serving murmur2 ring; absent = the classic single worker
        shard_spec = config.get_optional_string("oryx.speed.shard")
        if shard_spec:
            from ..cluster.sharding import parse_shard_spec
            self.shard_index, self.shard_count = parse_shard_spec(shard_spec)
        else:
            self.shard_index, self.shard_count = 0, 1
        self.shard_tag = f"{self.shard_index}/{self.shard_count}"
        manager_class = config.get_string("oryx.speed.model-manager-class")
        self.model_manager = load_instance(manager_class, config)
        # each worker owns its consumer group: N workers all read the
        # full input topic, each folding only its owned item slices
        self._group = f"OryxGroup-SpeedLayer-{self.id or 'default'}" + (
            f"-{self.shard_index}x{self.shard_count}" if shard_spec else "")
        self._stop = threading.Event()
        self._consume_thread: threading.Thread | None = None
        self._batch_thread: threading.Thread | None = None
        faults.configure_from_config(config)
        # a transiently failing UP publish retries with backoff; offsets
        # advance only after every delta of the micro-batch is published,
        # so an exhausted retry costs redelivery, never loss
        self._producer = ResilientTopicProducer(
            InProcTopicProducer(self.update_broker, self.update_topic),
            retry=Retry.from_config("speed-publish", config))
        # durable micro-batch fence (speed_checkpoint.py); unset = the
        # legacy at-least-once group-offset contract
        ckpt_dir = config.get_optional_string("oryx.speed.checkpoint-dir")
        self.checkpoint: SpeedCheckpoint | None = None
        if ckpt_dir:
            self.checkpoint = SpeedCheckpoint(store.join(
                ckpt_dir, f"shard-{self.shard_index}-of-{self.shard_count}"))
        self._last_ckpt_mono: float | None = None
        self.dedup_skips = 0
        # freshness surface (obs/freshness.py), read via the side-door
        # ObsServer — the speed tier serves no public HTTP of its own
        self.metrics = MetricsRegistry()
        self.tracer = tracer_from_config(config, "speed")
        if serving is not None:
            self.tracer = serving.tracer
            # a manager that can fold in against the served model does;
            # another keeps its own copy, as in a process of its own
            attach = getattr(self.model_manager, "attach_serving", None)
            if attach is not None:
                attach(serving.model_manager)
        # micro-batches that found input, numbered from 1: the ``batch``
        # header of the UP records each one publishes
        self.batch_seq = 0
        self._update_tap = freshness.UpdateStreamTap()
        self.metrics.gauge_fn(
            "update_lag_records",
            freshness.topic_lag_fn(self.update_broker, self.update_topic,
                                   lambda: self._update_tap.consumed))
        self.metrics.gauge_fn("model_generation_age_sec",
                              self._update_tap.model_age_sec)
        self.metrics.gauge_fn(
            "input_lag_records",
            freshness.group_lag_fn(self.input_broker, self.input_topic,
                                   self._group))
        if self.checkpoint is not None:
            self.metrics.gauge_fn("speed_checkpoint_age_sec",
                                  self._checkpoint_age_sec)
        # wide-event log (obs/events.py; None = disabled): the speed
        # tier's side-door requests carry the shard coordinate so a
        # cluster-merged event stream attributes lines to the worker
        self.events = events_from_config(
            config, "speed", self.metrics,
            static_fields={"speed_shard": self.shard_tag})
        # flight recorder (obs/flight.py; None until the config gate
        # opens): a chaos fault or crash in this worker leaves a bundle
        # even though the tier serves no public HTTP
        self.flight = flight_from_config(config, "speed", self.metrics)
        self.obs_server = ObsServer(config, self.metrics, self.tracer,
                                    extra_context={
                                        "events": self.events,
                                        "flight": self.flight,
                                    })

    def _checkpoint_age_sec(self) -> float | None:
        """Seconds since the durable fence last advanced; None until the
        first save of this incarnation."""
        last = self._last_ckpt_mono
        if last is None:
            return None
        return round(max(0.0, clockmod.monotonic() - last), 3)

    def start(self) -> None:
        _log.info("Starting speed layer %s (micro-batch %ds)",
                  self.shard_tag, self.generation_interval_sec)
        self.obs_server.start()
        # JVM-parity cold start: fold-in kernels reload from disk cache
        compile_cache.enable_from_config(self.config)
        # create the input topic at its configured partition count before
        # any lazy access can freeze it at one partition
        kafka_utils.maybe_create_topic(
            self.input_broker, self.input_topic,
            partitions=kafka_utils.input_topic_partitions(self.config))
        # resolve any batch staged by a previous incarnation BEFORE the
        # first new micro-batch can run (or the consume thread matters:
        # recovery republishes staged BYTES, it never re-derives)
        if self.checkpoint is not None:
            try:
                self._recover()
            except Exception:  # noqa: BLE001 — broker down at start;
                _log.exception("Speed recovery failed; the staged batch "
                               "stays pending and resolves before the "
                               "next micro-batch")
        # model state = full update-topic replay from offset 0
        # (reference: auto.offset.reset=smallest, SpeedLayer.java:113)
        self._consume_thread = threading.Thread(
            target=logging_call(self._consume_updates, "speed-consume"),
            daemon=True, name="SpeedLayerConsume")
        self._consume_thread.start()
        self._batch_thread = threading.Thread(
            target=logging_call(self._micro_batch_loop, "speed-batch"),
            daemon=True, name="SpeedLayerBatch")
        self._batch_thread.start()

    def await_(self) -> None:
        while self._batch_thread and self._batch_thread.is_alive():
            self._batch_thread.join(1.0)

    def close(self) -> None:
        # stop first, then JOIN the worker threads, and only then tear
        # down the manager/obs/producer: a micro-batch in flight must
        # never race a closing model manager (the close/batch race —
        # regression-tested in tests/test_speed_shard.py)
        self._stop.set()
        for t in (self._consume_thread, self._batch_thread):
            if t:
                t.join(10.0)
        self.model_manager.close()
        if self.flight is not None:
            self.flight.close()
        if self.events is not None:
            self.events.close()
        self.obs_server.close()
        self._producer.close()

    def _consume_updates(self) -> None:
        broker = resolve_broker(self.update_broker)
        # serving-cluster heartbeats ride the same update topic; they
        # are control plane, filtered before the model manager
        from ..cluster.membership import without_heartbeats
        # the freshness tap counts RAW records (heartbeats included) so
        # its count compares against the topic head's raw offsets
        run_with_resubscribe(
            lambda: self.model_manager.consume(without_heartbeats(
                self._update_tap.wrap(
                    broker.consume(self.update_topic, from_beginning=True,
                                   stop=self._stop)))),
            stop=self._stop, what="speed update consumer", log=_log)

    def _note_micro_batch(self, new_data: list[KeyMessage],
                          n_updates: int, t_start: float) -> None:
        """Per-micro-batch freshness gauges + retroactive fold-in spans
        for records whose ``traceparent`` header carries a sampled
        trace (obs/trace.py) — strictly best-effort, after the commit-
        ordering-critical work is done."""
        now = clockmod.monotonic()
        self.metrics.set_gauge("micro_batch_duration_ms",
                               round((now - t_start) * 1000.0, 3))
        self.metrics.set_gauge("micro_batch_records", len(new_data))
        oldest = freshness.oldest_ingest_ts_ms(new_data)
        if oldest is not None:
            # worst case across the batch: the longest a record waited
            # between its /ingest and its deltas becoming servable
            self.metrics.set_gauge(
                "ingest_to_servable_ms",
                max(0, int(clockmod.now() * 1000) - oldest))
        if self.tracer is None:
            return
        for km in new_data:
            ctx = parse_traceparent((km.headers or {}).get("traceparent"))
            if ctx is None or not ctx[2]:
                continue
            self.tracer.record_span(
                "speed.fold_in", (ctx[0], ctx[1]), t_start, now,
                {"batch_records": len(new_data), "updates": n_updates})

    # -- the durable fence ---------------------------------------------------

    def _recover(self) -> None:
        """Resolve a staged-but-uncommitted micro-batch against the
        update topic (speed_checkpoint.recover_pending): found staged
        records dedup, missing ones republish byte-exactly."""
        assert self.checkpoint is not None
        kafka_utils.maybe_create_topic(self.update_broker, self.update_topic)
        dest = resolve_broker(self.update_broker)
        ends = dest.latest_offsets(self.update_topic)
        republished, deduped = speed_checkpoint.recover_pending(
            self.checkpoint, self.shard_tag,
            lambda starts, e: dest.read_ranges(self.update_topic, starts, e),
            ends,
            lambda msg, headers: self._producer.send(KEY_UP, msg,
                                                     headers=headers))
        self._last_ckpt_mono = clockmod.monotonic()
        if deduped:
            self.dedup_skips += deduped
            self.metrics.inc("speed_shard_dedup_skips", deduped)
        if republished or deduped:
            # mirror the recovered fence into the group offsets so the
            # input-lag gauge agrees with the durable state
            try:
                in_broker = resolve_broker(self.input_broker)
                in_broker.set_offsets(self._group, self.input_topic,
                                      self._checkpoint_pos(in_broker))
            except Exception:  # noqa: BLE001 — gauge bookkeeping only
                _log.exception("Group-offset mirror after recovery failed")

    def _checkpoint_pos(self, broker) -> list[int]:
        """The checkpoint's input fence as a dense per-partition list
        (missing partitions start at 0 — the durable default)."""
        assert self.checkpoint is not None
        n = len(broker.latest_offsets(self.input_topic))
        return [int(self.checkpoint.input.get(p, 0)) for p in range(n)]

    def _publish_batch(self, in_broker, updates: list[str],
                       ends: list[int]) -> int:
        """Publish one derived micro-batch and advance the fence.  With
        the checkpoint enabled this is the stage → publish → commit
        protocol; without it, the legacy publish → group-commit."""
        up_headers = {"ts": str(int(clockmod.now() * 1000)),
                      "batch": str(self.batch_seq),
                      "in": ",".join(str(e) for e in ends)}
        # co-located with a serving layer the publishing thread keeps to
        # a share of the interpreter (the model manager's ``pace``)
        pace = getattr(self.model_manager, "pace", None)
        working = pace.work if pace is not None else contextlib.nullcontext
        if self.checkpoint is None:
            for update in updates:
                # chaos seam: UP delta publish failure — offsets must
                # not advance past an unpublished delta
                faults.fire("speed-publish")
                with working():
                    self._producer.send(KEY_UP, update, headers=up_headers)
            in_broker.set_offsets(self._group, self.input_topic, ends)
            return len(updates)
        # durable intent BEFORE the first publish: recovery replays
        # these exact bytes, never re-derives them against a model the
        # consume thread has already moved
        batch = self.checkpoint.stage_batch(ends, updates, up_headers)
        for seq, update in enumerate(updates):
            faults.fire("speed-publish")
            with working():
                self._producer.send(
                    KEY_UP, update,
                    headers=speed_checkpoint.stamp_headers(
                        up_headers, self.shard_tag, batch, seq))
        # chaos seam: die AFTER the publishes, BEFORE the commit — the
        # exact window the staged batch + destination-log scan exists
        # for (docs/RESILIENCE.md)
        faults.fire("speed-crash-mid-batch")
        dest_ends = None
        try:
            dest_ends = resolve_broker(self.update_broker).latest_offsets(
                self.update_topic)
        except Exception:  # noqa: BLE001 — scan-mark advance is best
            pass  # effort; a stale mark only costs a longer next scan
        self.checkpoint.commit_batch(ends, dest_ends=dest_ends)
        self._last_ckpt_mono = clockmod.monotonic()
        try:
            # group offsets mirror the fence for the input-lag gauge
            in_broker.set_offsets(self._group, self.input_topic, ends)
        except Exception:  # noqa: BLE001 — gauge bookkeeping only
            _log.exception("Group-offset mirror after commit failed")
        return len(updates)

    # -- the micro-batch loop ------------------------------------------------

    def _init_pos(self, broker) -> list[int]:
        if self.checkpoint is not None and self.checkpoint.input:
            return self._checkpoint_pos(broker)
        latest = broker.latest_offsets(self.input_topic)
        pos = [p if p is not None else latest[i]
               for i, p in enumerate(broker.get_offsets(
                   self._group, self.input_topic))]
        if self.checkpoint is not None and self.checkpoint.pending is None:
            # pin the initial fence durably BEFORE the first micro-batch:
            # a worker killed before its first commit must resume from
            # here on restart, not re-tail the (moved) head and skip
            # every record accepted in between
            self.checkpoint.commit_batch(pos)
            self._last_ckpt_mono = clockmod.monotonic()
            try:
                # mirror so the input-lag gauge counts from the fence
                broker.set_offsets(self._group, self.input_topic, pos)
            except Exception:  # noqa: BLE001 — gauge bookkeeping only
                _log.exception("Group-offset mirror of the initial "
                               "fence failed")
        return pos

    def _run_batch(self, broker, pos: list[int]) -> list[int]:
        """One micro-batch: read [pos, ends), derive, publish, commit.
        Returns the new position (pos unchanged when idle/failed)."""
        if self.checkpoint is not None \
                and self.checkpoint.pending is not None:
            # an earlier attempt staged a batch but never committed
            # (publish failure mid-batch): finish it from its staged
            # bytes — the in-process form of crash recovery
            self._recover()
            return self._checkpoint_pos(broker)
        ends = broker.latest_offsets(self.input_topic)
        if all(e <= p for e, p in zip(ends, pos)):
            return pos
        self._derive_and_publish(broker, pos, ends)
        return ends

    def _derive_and_publish(self, broker, pos: list[int],
                            ends: list[int]) -> None:
        """Input [pos, ends) -> updates -> the update topic, as one
        ``speed.micro_batch`` trace where tracing is on."""
        t_batch = clockmod.monotonic()
        self.batch_seq += 1
        with obstrace.phase("speed.micro_batch", self.tracer,
                            batch=self.batch_seq) as span:
            new_data = broker.read_ranges(self.input_topic, pos, ends)
            updates = list(self.model_manager.build_updates(new_data))
            with obstrace.phase("speed.publish", updates=len(updates)):
                n_updates = self._publish_batch(broker, updates, ends)
            if span.sampled:
                oldest = freshness.oldest_ingest_ts_ms(new_data)
                span.set_attr("events", len(new_data))
                span.set_attr("updates", n_updates)
                span.set_attr("oldest_wait_ms", None if oldest is None
                              else _wall_ms(t_batch) - oldest)
                for key, value in getattr(self.model_manager, "last_batch",
                                          {}).items():
                    span.set_attr(key, value)
        self._note_micro_batch(new_data, n_updates, t_batch)

    def _micro_batch_loop(self) -> None:
        broker = resolve_broker(self.input_broker)
        pos = None
        while not self._stop.is_set():
            if pos is None:
                try:
                    pos = self._init_pos(broker)
                except Exception:  # noqa: BLE001 — broker down at start
                    _log.exception("Micro-batch position init failed")
                    clockmod.wait(self._stop, self.generation_interval_sec)
                    continue
            # the poll wait goes through the clock seam so close() (and
            # a sim ManualClock) interrupts it promptly
            clockmod.wait(self._stop, self.generation_interval_sec)
            if self._stop.is_set():
                break  # closing: never start a batch the join won't see
            try:
                pos = self._run_batch(broker, pos)
            except Exception:  # noqa: BLE001 — micro-batch failure is
                _log.exception("Micro-batch failed")  # survivable
                # pos is unchanged unless the commit landed; with the
                # checkpoint enabled the staged batch resolves first
                # thing next interval, without re-deriving

    def run_one_micro_batch(self) -> None:
        """Synchronously process pending input once (test/ops hook)."""
        broker = resolve_broker(self.input_broker)
        if self.checkpoint is not None:
            # hook semantics match the legacy branch below: a fresh
            # group reads from 0 (the loop's _init_pos tails instead)
            if self.checkpoint.input:
                pos = self._checkpoint_pos(broker)
            else:
                pos = [p or 0 for p in broker.get_offsets(
                    self._group, self.input_topic)]
            self._run_batch(broker, pos)
            return
        pos = [p or 0
               for p in broker.get_offsets(self._group, self.input_topic)]
        ends = broker.latest_offsets(self.input_topic)
        if all(e <= p for e, p in zip(ends, pos)):
            return
        self._derive_and_publish(broker, pos, ends)
