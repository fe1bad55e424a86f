"""Operator CLI: run layers and manage topics.

Reference: deploy/bin/oryx-run.sh:24-33 (subcommands batch | speed |
serving | kafka-setup | kafka-tail | kafka-input), `--conf` config file
(oryx-run.sh reads it via ConfigToProperties, here it's a HOCON overlay
on the built-in defaults), and the three ~10-line Main classes
(deploy/oryx-batch/.../batch/Main.java etc.: construct layer from
config, register shutdown hook, start, await).

Beyond the reference's surface: ``warmup`` (install-time AOT compile),
``serving --shard i/N`` (run one catalog shard of the serving
cluster), and ``router`` (the cluster's scatter-gather public gateway
— oryx_tpu/cluster/, docs/SCALING.md).

Usage:
    python -m oryx_tpu <subcommand> [--conf my.conf] ...
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

from ..common.config import Config, from_file, get_default
from ..common.lang import ShutdownHook

__all__ = ["main"]

_log = logging.getLogger(__name__)


def _load_config(conf: str | None) -> Config:
    return from_file(conf) if conf else get_default()


def _claim_device(role: str, config: Config) -> None:
    """Touch the accelerator ONCE, on the main thread, before any
    supervisor or worker thread exists, and say what it is.

    A chip belongs to one process at a time.  A second process on the
    same chip fails inside the runtime's start-up — and without this
    call that failure would first surface inside a layer's worker
    thread, where the survival handlers log it and retry for ever
    (a serving layer that answers 503 until someone reads its log).
    Here it is one named error and exit code 3, before anything is
    supervised.  How layers share a host: README "Sharing a host"."""
    import jax

    from ..parallel.mesh import initialize_multihost
    try:
        # a configured multi-host join must precede the first
        # jax.devices() call (no-op when oryx.distributed.* is unset)
        initialize_multihost(config)
        devs = jax.devices()
    except RuntimeError as e:  # backend initialization failed
        print(f"oryx_tpu {role}: cannot initialize the JAX backend — if "
              "another process holds the chip, run one process per chip "
              "(pin with TPU_VISIBLE_CHIPS) or all layers in one process "
              f"(README \"Sharing a host\").\n  {e}", file=sys.stderr)
        raise SystemExit(3) from None
    _log.info("%s computes on %d x %s (%s backend)", role, len(devs),
              devs[0].device_kind, jax.default_backend())
    if jax.default_backend() == "cpu" and not os.environ.get(
            "JAX_PLATFORMS"):
        # with no platform named, JAX drops to the CPU when the chip's
        # runtime cannot start (e.g. another process holds it) and says
        # so only in a warning about a missing libtpu
        _log.warning("%s: JAX chose the CPU backend on its own; if a "
                     "chip was expected, set JAX_PLATFORMS=tpu so a "
                     "chip that cannot be claimed is an error", role)


def _run_layer(make_layer, name: str, config: Config) -> None:
    """Run a layer to completion; with the supervisor enabled (the
    default, oryx.resilience.supervisor.*) a layer whose worker thread
    dies — anything harsher than the Exceptions the layers survive
    internally — is rebuilt and restarted with backoff instead of
    leaving a silently-dead process behind."""
    from ..resilience.policy import Supervisor
    hook = ShutdownHook()
    if config.get_bool("oryx.resilience.supervisor.enabled"):
        supervisor = Supervisor.from_config(make_layer, name, config)

        class _Stop:  # close() both halts the supervisor loop and the
            def close(self):  # current layer, for the shutdown hook
                supervisor.stop()
                if supervisor.layer is not None:
                    supervisor.layer.close()

        hook.add_close_at_shutdown(_Stop())
        supervisor.run()
        return
    layer = make_layer()
    hook.add_close_at_shutdown(layer)
    layer.start()
    try:
        layer.await_()
    except KeyboardInterrupt:
        pass
    finally:
        layer.close()


def _cmd_batch(args) -> int:
    from ..lambda_rt.batch import BatchLayer
    config = _load_config(args.conf)
    _claim_device("batch", config)
    _run_layer(lambda: BatchLayer(config), "batch", config)
    return 0


def _cmd_speed(args) -> int:
    from ..lambda_rt.speed import SpeedLayer
    config = _load_config(args.conf)
    _claim_device("speed", config)
    if getattr(args, "shard", None):
        # sharded fold-in worker: consume the full input topic, fold
        # only the murmur2 item slices this worker owns, publish into
        # the shared update topic (docs/SCALING.md "Sharded speed
        # layer"); run one worker per slice
        from ..cluster.sharding import parse_shard_spec
        from ..common.config import from_dict
        parse_shard_spec(args.shard)  # fail fast on a bad spec
        config = from_dict({"oryx.speed.shard": args.shard}, config)
    _run_layer(lambda: SpeedLayer(config), "speed", config)
    return 0


def _cmd_serving(args) -> int:
    from ..lambda_rt.serving import ServingLayer
    config = _load_config(args.conf)
    _claim_device("serving", config)
    if getattr(args, "shard", None):
        # replica mode of the sharded serving cluster: materialize one
        # catalog slice, expose /shard/* scatter targets, heartbeat on
        # the update topic (oryx_tpu/cluster/, docs/SCALING.md)
        from ..cluster.sharding import parse_shard_spec
        from ..common.config import from_dict
        parse_shard_spec(args.shard)  # fail fast on a bad spec
        config = from_dict({"oryx.cluster.enabled": True,
                            "oryx.cluster.shard": args.shard}, config)
    _run_layer(lambda: ServingLayer(config), "serving", config)
    return 0


def _cmd_router(args) -> int:
    """The scatter-gather gateway: public REST front end over a fleet
    of shard replicas (cluster/router.py).  ``--async``/``--no-async``
    overrides ``oryx.cluster.async.enabled`` (the C10K event-loop
    front end vs the threaded fallback) without editing the conf."""
    from ..cluster.router import RouterLayer
    config = _load_config(args.conf)
    if getattr(args, "async_mode", None) is not None:
        from ..common.config import from_dict
        config = from_dict(
            {"oryx.cluster.async.enabled": bool(args.async_mode)},
            config)
    _run_layer(lambda: RouterLayer(config), "router", config)
    return 0


def _cmd_mirror(args) -> int:
    """The cross-region update-topic mirror (cluster/mirror.py): tails
    a source region's update topic and replays it into this region's
    topic with exactly-once-effective dedup, loop prevention, and
    measured staleness gauges (docs/SCALING.md "Multi-region")."""
    from ..cluster.mirror import MirrorLayer
    config = _load_config(args.conf)
    if args.source_broker or args.source_region:
        from ..common.config import from_dict
        overlay = {}
        if args.source_broker:
            overlay["oryx.cluster.region.mirror.source-broker"] = \
                args.source_broker
        if args.source_region:
            overlay["oryx.cluster.region.mirror.source-region"] = \
                args.source_region
        config = from_dict(overlay, config)
    _run_layer(lambda: MirrorLayer(config), "mirror", config)
    return 0


def _cmd_autoscale(args) -> int:
    """The gauge-driven supervisor (cluster/autoscaler.py): polls the
    router's merged p99 buckets / measured queue wait / replica update
    lag / SLO error-budget burn (oryx.obs.slo.*) against
    oryx.cluster.autoscale.* thresholds and spawns or retires
    supervised `serving --shard i/N` replica-group members."""
    from ..cluster.autoscaler import run_autoscaler
    config = _load_config(args.conf)
    if args.router_url:
        from ..common.config import from_dict
        config = from_dict(
            {"oryx.cluster.autoscale.router-url": args.router_url},
            config)
    return run_autoscaler(config, args.conf)


def _topic_config(config: Config) -> list[tuple[str, str]]:
    return [
        (config.get_string("oryx.input-topic.broker"),
         config.get_string("oryx.input-topic.message.topic")),
        (config.get_string("oryx.update-topic.broker"),
         config.get_string("oryx.update-topic.message.topic")),
    ]


def _cmd_kafka_setup(args) -> int:
    from ..kafka import utils as kafka_utils
    config = _load_config(args.conf)
    # reference oryx-run.sh:343,356 — input topic 4 partitions (P7
    # parallel ingest), update topic 1 (total order for MODEL/UP replay)
    partitions = [kafka_utils.input_topic_partitions(config), 1]
    for (broker, topic), n in zip(_topic_config(config), partitions):
        kafka_utils.maybe_create_topic(broker, topic, partitions=n)
        print(f"{topic} @ {broker}: "
              f"{'exists' if kafka_utils.topic_exists(broker, topic) else 'missing'}")
    return 0


def _cmd_kafka_tail(args) -> int:
    from ..kafka.inproc import resolve_broker
    config = _load_config(args.conf)
    consumers = [(topic, resolve_broker(broker), 0)
                 for broker, topic in _topic_config(config)]
    print("Tailing input and update topics; Ctrl-C to stop", file=sys.stderr)
    try:
        import time
        offsets = {topic: [0] * broker.num_partitions(topic)
                   for topic, broker, _ in consumers}
        while True:
            idle = True
            for topic, broker, _ in consumers:
                ends = broker.latest_offsets(topic)
                for km in broker.read_ranges(topic, offsets[topic], ends):
                    print(f"{topic}\t{km.key}\t{km.message}")
                    idle = False
                offsets[topic] = ends
            if args.once and idle:
                return 0
            if idle:
                time.sleep(0.5)
    except KeyboardInterrupt:
        return 0


def _cmd_kafka_input(args) -> int:
    from ..kafka.inproc import resolve_broker
    config = _load_config(args.conf)
    broker_uri = config.get_string("oryx.input-topic.broker")
    topic = config.get_string("oryx.input-topic.message.topic")
    broker = resolve_broker(broker_uri)
    n = 0
    source = open(args.file) if args.file else sys.stdin
    try:
        for line in source:
            line = line.rstrip("\n")
            if line:
                broker.send(topic, None, line)
                n += 1
    finally:
        if args.file:
            source.close()
    print(f"Sent {n} lines to {topic}", file=sys.stderr)
    return 0


def _cmd_warmup(args) -> int:
    """AOT-compile the serving kernel shape ladder (and optionally one
    training iteration's programs) into the persistent XLA cache, so
    the FIRST-ever layer start on this machine pays cache loads instead
    of a multi-minute compile (deploy/warmup.py; the install-time
    answer to the JVM reference's zero first-run tax)."""
    import json

    from .warmup import run_warmup
    config = _load_config(args.conf)
    _claim_device("warmup", config)
    items_list = [round(float(x) * 1e6) if "." in x or float(x) < 1000
                  else int(x) for x in args.items.split(",") if x]
    # default dtype ladder = the DEPLOYMENT'S factor dtype: warming a
    # dtype the serving layer will never load is paid compile time
    # with zero first-start benefit
    dtypes = [d.strip() for d in args.dtypes.split(",") if d.strip()] \
        if args.dtypes else [config.get_string("oryx.als.factor-dtype")]
    report = run_warmup(
        config,
        items_list=items_list,
        features_list=[int(x) for x in args.features.split(",") if x],
        dtypes=dtypes,
        how_many=args.how_many,
        train_ratings=args.train_ratings,
        train_rank=args.train_rank)
    print(json.dumps(report if args.verbose else {
        k: v for k, v in report.items() if k not in ("compiled",)}))
    return 0 if report["ok"] else 1


def _cmd_config_to_properties(args) -> int:
    """Print the resolved ``oryx.*`` configuration as sorted
    ``key=value`` .properties lines on stdout, for shell consumption —
    the launcher-script bridge (reference: ConfigToProperties.java:29-58,
    invoked by oryx-run.sh:87 to render config into -D properties)."""
    props = _load_config(args.conf).to_properties()
    for k in sorted(props):
        if k == "oryx" or k.startswith("oryx."):
            print(f"{k}={props[k]}")
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="oryx_tpu",
        description="TPU-native lambda-architecture ML framework")
    parser.add_argument("--log-level", default="INFO")
    sub = parser.add_subparsers(dest="command", required=True)

    for name, fn, help_ in [
            ("batch", _cmd_batch, "run the batch (training) layer"),
            ("speed", _cmd_speed, "run the speed (incremental) layer"),
            ("serving", _cmd_serving, "run the serving (REST) layer"),
            ("router", _cmd_router,
             "run the cluster gateway: scatter-gather router over "
             "sharded serving replicas (see serving --shard)"),
            ("autoscale", _cmd_autoscale,
             "run the gauge-driven supervisor: scale replica groups "
             "from the router's measured p99/queue-wait/lag signals "
             "and SLO burn rate"),
            ("mirror", _cmd_mirror,
             "run the cross-region update-topic mirror: replay a "
             "source region's updates into this region's topic with "
             "exactly-once-effective dedup and measured staleness "
             "(oryx.cluster.region.*)"),
            ("kafka-setup", _cmd_kafka_setup, "create/check topics"),
            ("kafka-tail", _cmd_kafka_tail, "print topic traffic"),
            ("kafka-input", _cmd_kafka_input, "send lines to input topic"),
            ("warmup", _cmd_warmup,
             "AOT-compile the serving kernel ladder into the "
             "persistent XLA cache (install-time, kills the first-run "
             "compile tax)"),
            ("config-to-properties", _cmd_config_to_properties,
             "print resolved oryx.* config as key=value lines")]:
        p = sub.add_parser(name, help=help_)
        p.add_argument("--conf", help="HOCON config file overlaying defaults")
        p.set_defaults(fn=fn)
        if name == "router":
            p.add_argument("--async", dest="async_mode",
                           action=argparse.BooleanOptionalAction,
                           default=None,
                           help="serve the public door on the asyncio "
                                "event-loop front end (connection "
                                "ceiling in sockets, not threads); "
                                "--no-async forces the threaded "
                                "server.  Default: "
                                "oryx.cluster.async.enabled")
        if name == "speed":
            p.add_argument("--shard", default=None, metavar="i/N",
                           help="fold in only item slice i of N "
                                "(murmur2 ring); run N supervised "
                                "workers to split fold-in work — all "
                                "publish into the one update topic")
        if name == "serving":
            p.add_argument("--shard", default=None, metavar="i/N",
                           help="serve catalog shard i of N as a "
                                "cluster replica (enables heartbeats "
                                "+ /shard/* resources; front with "
                                "'router')")
        if name == "autoscale":
            p.add_argument("--router-url", default=None,
                           help="router base URL to poll (overrides "
                                "oryx.cluster.autoscale.router-url)")
        if name == "mirror":
            p.add_argument("--source-broker", default=None,
                           help="remote region's update-topic broker "
                                "(overrides oryx.cluster.region."
                                "mirror.source-broker)")
            p.add_argument("--source-region", default=None,
                           help="name recorded as origin-region for "
                                "records born at the source (overrides "
                                "oryx.cluster.region.mirror."
                                "source-region)")
        if name == "kafka-tail":
            p.add_argument("--once", action="store_true",
                           help="drain current contents and exit")
        if name == "kafka-input":
            p.add_argument("--file", help="read lines from a file "
                                          "instead of stdin")
        if name == "warmup":
            p.add_argument("--items", default="1,5,20",
                           help="comma list of item counts; values "
                                "under 1000 mean millions (default "
                                "the published envelope 1,5,20)")
            p.add_argument("--features", default="50,250",
                           help="comma list of feature ranks")
            p.add_argument("--dtypes", default=None,
                           help="comma list of factor dtypes to warm "
                                "(default: the config's "
                                "oryx.als.factor-dtype)")
            p.add_argument("--how-many", type=int, default=10)
            p.add_argument("--train-ratings", type=int, default=0,
                           help="also run ONE real training iteration "
                                "at this rating count to seed the "
                                "trainer's compiled programs")
            p.add_argument("--train-rank", type=int, default=0)
            p.add_argument("--verbose", action="store_true",
                           help="include the full per-kernel compile "
                                "list in the report")

    args = parser.parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    return args.fn(args)


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
