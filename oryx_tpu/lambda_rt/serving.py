"""The serving layer: HTTP API over an in-memory model fed by the update
topic.

Reference: framework/oryx-lambda-serving/src/main/java/com/cloudera/oryx/
lambda/serving/ServingLayer.java:58-339 (embedded Tomcat, connector
options, read-only mode, context wiring), ModelManagerListener.java:63-250
(input producer, update-topic consumer from offset 0 feeding
modelManager.consume, app-scope attributes), OryxApplication.java:41-98
(resource discovery from configured packages).
"""

from __future__ import annotations

import gc
import importlib
import logging
import sys
import threading

from ..cluster.membership import HeartbeatPublisher, without_heartbeats
from ..cluster.sharding import parse_shard_spec
from ..common import compile_cache
from ..common.config import Config
from ..common.lang import load_instance, logging_call
from ..kafka import utils as kafka_utils
from ..kafka.inproc import InProcTopicProducer, resolve_broker
from ..obs import (DeviceTimeAccountant, engine_from_config,
                   events_from_config, flight_from_config, freshness,
                   install_process_accountant, tracer_from_config)
from ..resilience import faults
from ..resilience.policy import (CircuitBreaker, ResilientTopicProducer,
                                 Retry, run_with_resubscribe)
from ..serving.batcher import TopNBatcher
from .http import HttpApp, Route, make_server
from .metrics import MetricsRegistry

_log = logging.getLogger(__name__)

__all__ = ["ServingLayer"]

# the interpreter's switch interval while a serving layer runs (start())
_SWITCH_INTERVAL_S = 0.0002


def _single_threaded_host_blas():
    """Host BLAS on one thread while a serving layer runs; returns what
    undoes it, or None.  What a serving process hands BLAS is k x k (a
    Gramian's factorisation, the corrections of
    ``FeatureVectorStore.vtv``).  OpenBLAS answers with a pool of one
    spinning thread a core: two solver rebuilds at once put twice the
    machine's cores into spin loops, a 6 ms SVD took 10-100 ms, and for
    as long every request thread waited for a core (45 stalls of 10-30
    ms in a 40 s window at 100 updates a second, PERF.md, PR 27).  On
    one thread the same calls take 1-6 ms and disturb nobody.  Without
    ``threadpoolctl`` installed nothing changes."""
    try:
        import threadpoolctl
    except ImportError:
        return None
    return threadpoolctl.threadpool_limits(limits=1, user_api="blas")


# the interpreter-wide settings of a process that serves: taken by the
# first layer to start, given back by the last to close
_tuning_lock = threading.Lock()
_tuned_layers = 0  # guarded-by: _tuning_lock
_untune = None  # guarded-by: _tuning_lock


def _tune_interpreter() -> None:
    global _tuned_layers, _untune
    with _tuning_lock:
        _tuned_layers += 1
        if _tuned_layers == 1:
            interval = sys.getswitchinterval()
            sys.setswitchinterval(min(interval, _SWITCH_INTERVAL_S))
            _untune = (interval, _single_threaded_host_blas())


def _untune_interpreter() -> None:
    global _tuned_layers, _untune
    with _tuning_lock:
        _tuned_layers -= 1
        if _tuned_layers == 0 and _untune is not None:
            interval, blas = _untune
            _untune = None
            sys.setswitchinterval(interval)
            if blas is not None:
                blas.restore_original_limits()


class ServingLayer:
    """start()/await_()/close() around the HTTP server + model consumer."""

    def __init__(self, config: Config, port: int | None = None):
        self.config = config
        api = "oryx.serving.api"
        # TLS: when a keystore (PEM certificate + key) is configured the
        # layer serves HTTPS on secure-port (reference connector spec:
        # ServingLayer.java:202-255; keys reference.conf:221-237).  The
        # JKS keystore becomes a PEM cert/key chain — the Python-native
        # equivalent — with keystore-password decrypting the key;
        # key-alias does not apply to PEM and is accepted but unused.
        self.keystore_file = config.get_optional_string(f"{api}.keystore-file")
        self.keystore_password = config.get_optional_string(
            f"{api}.keystore-password")
        self.key_alias = config.get_optional_string(f"{api}.key-alias")
        if port is not None:
            self.port = port
        elif self.keystore_file:
            self.port = config.get_int(f"{api}.secure-port")
        else:
            self.port = config.get_int(f"{api}.port")
        self.read_only = config.get_bool(f"{api}.read-only")
        self.user_name = config.get_optional_string(f"{api}.user-name")
        self.password = config.get_optional_string(f"{api}.password")
        self.context_path = config.get_string(f"{api}.context-path")
        self.input_broker = config.get_optional_string("oryx.input-topic.broker")
        self.input_topic = config.get_optional_string("oryx.input-topic.message.topic")
        self.update_broker = config.get_optional_string("oryx.update-topic.broker")
        self.update_topic = config.get_optional_string("oryx.update-topic.message.topic")
        self.no_init_topics = config.get_bool("oryx.serving.no-init-topics")
        self.min_model_load_fraction = config.get_double(
            "oryx.serving.min-model-load-fraction")
        # serving-cluster replica mode (oryx_tpu/cluster/): this process
        # serves one catalog shard, registers the internal /shard/*
        # scatter targets, and announces itself on the update topic so
        # the gateway routes to it
        self.cluster_enabled = config.get_bool("oryx.cluster.enabled")
        self.heartbeat: HeartbeatPublisher | None = None
        # framed internal transport (cluster/transport.py): a frame
        # listener next to the HTTP door, its port advertised in the
        # heartbeat; and the replica-side result cache the frame
        # dispatcher consults before touching the device
        self._frame_server = None
        self._shard_cache = None

        manager_class = config.get_string("oryx.serving.model-manager-class")
        self.model_manager = load_instance(manager_class, config)

        self._stop = threading.Event()
        # this layer holds the interpreter-wide settings (start/close)
        self._tuned = False
        self._consume_thread: threading.Thread | None = None
        self._server = None
        self._server_thread: threading.Thread | None = None

        faults.configure_from_config(config)
        self.input_producer = None
        # breaker around the serving tier's broker writes: a dead input
        # broker degrades /ingest//pref to fast 503s instead of stacking
        # blocked handler threads, and the half-open probe restores
        # service without a restart (tests/test_resilience_it.py)
        self.input_breaker = CircuitBreaker.from_config(
            "serving-input", config)
        if not self.read_only and self.input_broker and self.input_topic:
            if not self.no_init_topics:
                kafka_utils.maybe_create_topic(
                    self.input_broker, self.input_topic,
                    partitions=kafka_utils.input_topic_partitions(config))
            self.input_producer = ResilientTopicProducer(
                InProcTopicProducer(self.input_broker, self.input_topic),
                retry=Retry.from_config("serving-input-send", config),
                breaker=self.input_breaker)
        # write-path admission (serving/ingest.py; both gates 0 = off):
        # bounded in-flight broker appends + measured-send-lag shedding
        # around send_input/send_input_many ONLY — 503 + Retry-After,
        # never a silently dropped acked record
        from ..serving.ingest import IngestGate
        self.ingest_gate = IngestGate(config)
        if not self.ingest_gate.enabled:
            self.ingest_gate = None

        routes = self._discover_routes()
        idle_ms = config.get_int(f"{api}.batch-idle-wait-ms")
        # sampled distributed tracing (obs/trace.py; None = disabled):
        # the request span starts at the HTTP dispatcher, the batcher
        # splits queue-wait from device-execute under it
        self.tracer = tracer_from_config(config, "serving")
        self.metrics = MetricsRegistry()
        # continuous device-time accounting (obs/device_time.py): the
        # batcher books serve-class execute brackets, the kernel router
        # books measure-class sweeps via the process-level hook
        self.device_time = DeviceTimeAccountant(self.metrics)
        install_process_accountant(self.device_time)
        self.top_n_batcher = TopNBatcher(
            max_batch=config.get_int(f"{api}.max-batch"),
            pipeline=config.get_int(f"{api}.scoring-pipeline-depth"),
            idle_wait_s=None if idle_ms < 0 else idle_ms / 1000.0,
            tracer=self.tracer, accountant=self.device_time)
        if self.cluster_enabled:
            # replica-side exact result cache for /shard/* answers
            # (cluster/result_cache.py ShardResultCache; off by
            # default): consulted by the frame dispatcher so a
            # repeated shard query under an unchanged model epoch
            # skips the device — the update replay's tap moves the
            # epoch per applied record
            from ..cluster.result_cache import ShardResultCache
            self._shard_cache = ShardResultCache.from_config(
                config, self.metrics)
        # freshness surface: update-consumer lag + model generation age
        # from a passive tap on the replay (obs/freshness.py)
        self._update_tap = freshness.UpdateStreamTap()
        if self.update_broker and self.update_topic:
            self.metrics.gauge_fn(
                "update_lag_records",
                freshness.topic_lag_fn(self.update_broker,
                                       self.update_topic,
                                       lambda: self._update_tap.consumed))
            self.metrics.gauge_fn("model_generation_age_sec",
                                  self._update_tap.model_age_sec)
        # sharded model distribution (app/als/slices.py): how this
        # replica loaded its model — seconds to servable, slice bytes
        # read, and fallbacks to the monolithic artifacts.  Managers
        # without the attributes (non-ALS apps) simply don't register.
        if hasattr(self.model_manager, "model_load_s"):
            mgr = self.model_manager
            self.metrics.gauge_fn(
                "model_load_s", lambda: float(mgr.model_load_s))
            self.metrics.gauge_fn(
                "model_slice_bytes",
                lambda: float(mgr.model_slice_bytes))
            self.metrics.gauge_fn(
                "slice_load_fallbacks",
                lambda: float(mgr.slice_load_fallbacks))
            # IVF ANN serving index (app/als/ivf.py): device bytes the
            # generation's index pins, and generations that failed
            # CLOSED to the exact kernel (corrupt artifact or failed
            # build/certificate)
            self.metrics.gauge_fn(
                "ann_index_bytes",
                lambda: float(getattr(mgr, "ann_index_bytes", 0)))
            self.metrics.gauge_fn(
                "ann_index_fallbacks",
                lambda: float(getattr(mgr, "ann_index_fallbacks", 0)))
        # SLO burn-rate engine (obs/slo.py; None = disabled): evaluated
        # lazily whenever the gauges are read, alert state at /admin/slo
        self.slo_engine = engine_from_config(config, self.metrics)
        if self.slo_engine is not None:
            self.metrics.gauge_fn("slo_burn_rate",
                                  self.slo_engine.burn_gauge)
            self.metrics.gauge_fn("slo_error_budget_remaining",
                                  self.slo_engine.budget_gauge)
        # wide-event request log (obs/events.py; None = disabled)
        self.events = events_from_config(config, "serving", self.metrics)
        if self.events is not None and hasattr(self.model_manager,
                                               "model_load_s"):
            # schema catch-up (PR 18): a request that served while the
            # ANN index had failed closed carries the fallback count
            mgr = self.model_manager

            def _event_context() -> dict:
                n = int(getattr(mgr, "ann_index_fallbacks", 0) or 0)
                return {"ann_index_fallbacks": n} if n else {}

            self.events.context_fn = _event_context
        # flight recorder (obs/flight.py; None until oryx.obs.flight.dir
        # opens the gate): black-box rings + anomaly-triggered bundles
        self.flight = flight_from_config(
            config, "serving", self.metrics, slo=self.slo_engine,
            accountant=self.device_time)
        if self.flight is not None and self.slo_engine is not None:
            flight = self.flight
            # page transition -> one debounced local bundle; the
            # callback runs with the SLO lock held and trigger() never
            # re-enters the engine (bundle reads last_status, lock-free)
            self.slo_engine.on_page = lambda name, st: flight.trigger(
                "slo-page", {"objective": name,
                             "burn_5m": st.get("burn_5m")})
        self.app = HttpApp(
            routes,
            context={
                "model_manager": self.model_manager,
                "input_producer": self.input_producer,
                "ingest_gate": self.ingest_gate,
                "config": config,
                "min_model_load_fraction": self.min_model_load_fraction,
                "top_n_batcher": self.top_n_batcher,
                "metrics": self.metrics,
                "tracer": self.tracer,
                "slo": self.slo_engine,
                "events": self.events,
                "flight": self.flight,
                "device_time": self.device_time,
            },
            read_only=self.read_only,
            user_name=self.user_name,
            password=self.password,
            context_path=self.context_path,
            request_deadline_ms=config.get_int(
                "oryx.resilience.request-deadline-ms"),
        )

    def _discover_routes(self) -> list[Route]:
        """Load Route lists from the configured resource modules
        (reference: OryxApplication scanning application-resources
        packages for @Path classes)."""
        routes: list[Route] = []
        from ..serving import framework as framework_resources

        routes.extend(framework_resources.ROUTES)
        if self.cluster_enabled:
            # the gateway's internal scatter targets ride next to the
            # public resources (same server, same auth/TLS)
            from ..cluster import shard_resources
            routes.extend(shard_resources.ROUTES)
        resources = self.config.get_optional_string(
            "oryx.serving.application-resources")
        if resources:
            for module_name in resources.split(","):
                module = importlib.import_module(module_name.strip())
                routes.extend(getattr(module, "ROUTES"))
        return routes

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        # A request changes threads some six times (door, batcher,
        # dispatcher and back), and each change has to take the
        # interpreter lock.  At CPython's default switch interval a
        # thread that computes — the update consumer parsing UP records,
        # a co-located speed layer's micro-batch — keeps it 5 ms a time,
        # so a request that meets such a burst waits up to 30 ms for
        # nothing (measured on the chip host with two background threads:
        # 38 of 1,256 wake-ups late by over 3 ms at 5 ms, 8 at 0.2 ms;
        # PERF.md, PR 27).  Host BLAS goes to one thread with it.
        if not self._tuned:
            self._tuned = True
            _tune_interpreter()
        # What the model manager built before this point lives as long
        # as the process: out of the collector's sight.  A full
        # collection walks every container of a 20M-id model with the
        # interpreter lock held, 1.3-3.6 s in which nothing is answered
        # (one run in ten of the benchmark's 40 s windows, PERF.md, PR
        # 24-27); frozen objects are still freed by reference count.
        gc.freeze()
        # JVM-parity cold start: warm_serving_kernels' per-bucket scan
        # variants reload from the disk cache instead of recompiling
        compile_cache.enable_from_config(self.config)
        if self.update_broker and self.update_topic:
            if not self.no_init_topics:
                kafka_utils.maybe_create_topic(self.update_broker,
                                               self.update_topic)
            # model state = full update-topic replay from offset 0
            # (reference: auto.offset.reset=smallest,
            # ModelManagerListener.java:126)
            self._consume_thread = threading.Thread(
                target=logging_call(self._consume_updates, "serving-consume"),
                daemon=True, name="ServingLayerConsume")
            self._consume_thread.start()
        ssl_context = None
        if self.keystore_file:
            import ssl
            ssl_context = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ssl_context.load_cert_chain(self.keystore_file,
                                        password=self.keystore_password)
        self._server = make_server(self.app, self.port,
                                   ssl_context=ssl_context)
        self.port = self._server.server_address[1]
        self.scheme = "https" if ssl_context is not None else "http"
        self._server_thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name="ServingLayerHTTP")
        self._server_thread.start()
        _log.info("Serving layer listening on port %d", self.port)
        if self.cluster_enabled and self.update_broker and self.update_topic:
            c = "oryx.cluster"
            tport = None
            if self.config.get_bool(f"{c}.transport.enabled"):
                # the framed scatter listener rides next to the HTTP
                # door; its port travels in the heartbeat so the
                # router multiplexes one connection here instead of a
                # socket pool (cluster/transport.py)
                from ..cluster.transport import FrameServer
                self._frame_server = FrameServer(
                    self.app, self.config, metrics=self.metrics,
                    shard_cache=self._shard_cache)
                self._frame_server.start()
                tport = self._frame_server.port
                _log.info("Frame transport listening on port %d", tport)
            # announce this replica AFTER the port is bound (the
            # heartbeat carries the live URL)
            shard, of = parse_shard_spec(
                self.config.get_optional_string(f"{c}.shard") or "0/1")
            host = self.config.get_string(f"{c}.advertise-host")
            self.heartbeat = HeartbeatPublisher(
                InProcTopicProducer(self.update_broker, self.update_topic),
                shard=shard, of=of,
                url=f"{self.scheme}://{host}:{self.port}",
                manager=self.model_manager,
                min_fraction=self.min_model_load_fraction,
                interval_sec=self.config.get_int(
                    f"{c}.heartbeat-interval-ms") / 1000.0,
                replica_id=self.config.get_optional_string(
                    f"{c}.replica-id"),
                region=self.config.get_optional_string(
                    f"{c}.region.name"),
                tport=tport)
            self.heartbeat.start()

    @staticmethod
    def _replay_stall_seam(stream):
        """Chaos seam ``reshard-warm-stall``: mode=delay stalls the
        update replay per record — the new-topology replica that hangs
        mid-warm during a reshard.  It never reaches ready, so the
        router must keep serving the OLD topology exactly (the cutover
        gate is full ready coverage).  Unarmed: one boolean check per
        record."""
        for km in stream:
            faults.fire("reshard-warm-stall")
            yield km

    def _consume_updates(self) -> None:
        # broker loss mid-tail resubscribes with backoff, replaying the
        # update topic from offset 0 — recovery IS the cold-start path
        # (reference: auto.offset.reset=smallest), so the serving model
        # converges to the same state either way
        broker = resolve_broker(self.update_broker)

        # cluster heartbeats share the update topic; they are control
        # plane, not model state, and are filtered before the manager
        # the freshness tap counts RAW records (heartbeats included) so
        # its count compares against the topic head's raw offsets
        def stream():
            s = without_heartbeats(
                self._replay_stall_seam(self._update_tap.wrap(
                    broker.consume(self.update_topic,
                                   from_beginning=True,
                                   stop=self._stop))))
            if self._shard_cache is not None:
                # the replica cache's epoch feed: every model-state
                # record (heartbeats already filtered) moves the epoch
                # BEFORE the manager applies it
                s = self._shard_cache.tap(s)
            return s

        run_with_resubscribe(
            lambda: self.model_manager.consume(stream()),
            stop=self._stop, what="serving update consumer", log=_log)

    def await_(self) -> None:
        while self._server_thread and self._server_thread.is_alive():
            self._server_thread.join(1.0)

    def close(self) -> None:
        self._stop.set()
        if self._tuned:
            self._tuned = False
            _untune_interpreter()
        if self.heartbeat is not None:
            self.heartbeat.close()
        if self._frame_server is not None:
            self._frame_server.close()
        if self._server:
            self._server.shutdown()
        self.top_n_batcher.close()
        if self.flight is not None:
            self.flight.close()
        if self.events is not None:
            self.events.close()
        self.model_manager.close()
        if self.input_producer:
            self.input_producer.close()
        for t in (self._consume_thread, self._server_thread):
            if t:
                t.join(10.0)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.close()
