"""Framework-level serving resources: readiness, the error page, and
shared helpers.

Reference: app/oryx-app-serving/.../Ready.java:34 (HEAD/GET /ready ->
200/503 against min-model-load-fraction),
AbstractOryxResource.java:52-... (model gating, input send),
ErrorResource.java:36 (the error-page forward target).
"""

from __future__ import annotations

import zlib
from typing import Any

from ..common import clock as clockmod
from ..api.serving import OryxServingException
from ..lambda_rt.http import (HtmlResponse, Request, Route, TextResponse,
                              render_error_page)
from ..obs.server import (admin_diagnose, admin_flight,
                          admin_flight_dump, admin_profile,
                          admin_region, admin_slo, admin_tail,
                          admin_traces, prometheus_response)
from ..resilience.policy import CircuitOpenError, resilience_snapshot

__all__ = ["ROUTES", "get_serving_model", "send_input",
           "send_input_many"]


def get_serving_model(req: Request) -> Any:
    """The current model, or 503 until enough is loaded
    (reference: AbstractOryxResource.getServingModel :76-96)."""
    manager = req.context["model_manager"]
    model = manager.get_model()
    if model is not None:
        fraction = model.get_fraction_loaded()
        if fraction >= req.context["min_model_load_fraction"]:
            return model
    raise OryxServingException(503, "Model not available yet")


def send_input(req: Request, line: str) -> None:
    send_input_many(req, [line])


def send_input_many(req: Request, lines: list[str]) -> None:
    """Durably append ``lines`` to the input topic — one pipelined
    ``send_many`` produce, so a multi-line ``/ingest`` costs one broker
    call instead of one per record.  A normal return means every
    record is in the input topic (202 = durable); any failure maps to
    503 (retry), never a partial silent loss.  The ingest admission
    gate (serving/ingest.py) sheds HERE, inside the write path only,
    so health/admin/read routes are never gated."""
    producer = req.context.get("input_producer")
    if producer is None:
        raise OryxServingException(403, "no input topic configured")
    # record headers (kafka/api.py), preserved PER RECORD: `ts` stamps
    # ingest wall-clock so the speed layer can measure ingest→servable
    # freshness end to end; `traceparent` carries a sampled request's
    # trace context so the fold-in that makes each record servable
    # joins its trace
    headers = {"ts": str(int(clockmod.now() * 1000))}
    tracer = req.context.get("tracer")
    if tracer is not None:
        cur = tracer.current()
        if cur.sampled:
            headers["traceparent"] = cur.traceparent()
    # key = hash of the message, so identical records land in the same
    # partition (reference: AbstractOryxResource.sendInput :68 sends
    # Integer.toHexString(message.hashCode()) as the key)
    entries = [(format(zlib.crc32(line.encode("utf-8")), "x"), line,
                dict(headers)) for line in lines]
    gate = req.context.get("ingest_gate")
    try:
        if gate is not None:
            with gate.admitted(req.context.get("metrics"),
                               n=len(entries)):
                _produce(producer, entries)
        else:
            _produce(producer, entries)
    except OryxServingException:
        raise  # the gate's shed (503 + Retry-After) passes through
    except CircuitOpenError as e:
        # broker presumed down: degrade the write surface to fast 503s
        # (not 500 — the request was fine; the dependency is not) and
        # let the breaker's half-open probe restore it without restart
        raise OryxServingException(503, f"input unavailable: {e}") from e
    except Exception as e:  # noqa: BLE001 — any broker fault degrades,
        raise OryxServingException(                   # it doesn't error
            503, f"input send failed: {e}") from e
    # every record counted here is in the input topic: what the speed
    # layer's events_folded has to add up to
    metrics = req.context.get("metrics")
    if metrics is not None:
        metrics.inc("events_acked", len(entries))


def _produce(producer, entries: list[tuple[str, str, dict]]) -> None:
    if len(entries) == 1:
        key, line, headers = entries[0]
        producer.send(key, line, headers=headers)
        return
    send_many = getattr(producer, "send_many", None)
    if send_many is not None:
        send_many(entries)
        return
    for key, line, headers in entries:
        producer.send(key, line, headers=headers)


def _ready(req: Request):
    manager = req.context["model_manager"]
    model = manager.get_model()
    if model is not None and (model.get_fraction_loaded()
                              >= req.context["min_model_load_fraction"]):
        return None  # 204-ish empty 200
    raise OryxServingException(503, "Model not available yet")


def _error(req: Request):
    """Explicit error-page resource: renders error info carried in the
    query string, where the reference's container forwards errored
    requests with RequestDispatcher.ERROR_* attributes
    (ErrorResource.java:36; wired as the error page for every status in
    ServingLayer.java:305-311).  The hand-rolled server renders
    in-flight errors directly through render_error_page, so this
    endpoint is the addressable form of the same page."""
    code = req.q1("code", "")
    status = int(code) if code and code.isdigit() else 200
    payload, ctype = render_error_page(
        status, req.q1("uri"), req.q1("message"),
        req.headers.get("Accept", ""))
    if ctype.startswith("text/html"):
        return status, HtmlResponse(payload.decode())
    return status, TextResponse(payload.decode())


def _metrics(req: Request):
    """Per-route request counts, error counts, and latency percentiles
    (the reference exposes only logs + Spark UI — SURVEY §5.1/5.5; this
    is the serving-side step-metrics surface ops parity needs), plus the
    request micro-batcher's live pacing state and the streaming top-k
    certificate-fallback counter — the two internals an operator needs
    when throughput or result-exactness questions come up."""
    registry = req.context.get("metrics")
    if registry is None:
        raise OryxServingException(404, "metrics not enabled")
    # ?format=prometheus / prometheus-json (obs/server.py): the text
    # exposition and the mergeable structured snapshot the cluster
    # gateway scrapes; plain JSON stays the default
    prom = prometheus_response(req, registry)
    if prom is not None:
        return prom
    model = req.context["model_manager"].get_model()
    out = {
        "routes": registry.snapshot(),
        "model_fraction_loaded":
            model.get_fraction_loaded() if model is not None else 0.0,
    }
    batcher = req.context.get("top_n_batcher")
    if batcher is not None:
        out["scoring_batcher"] = batcher.stats()
    counters = registry.counters_snapshot()
    if counters:
        out["counters"] = counters
    # sharded-cluster replica: shard coordinates + generation, so an
    # operator can see per-replica catalog state without the router in
    # between
    mgr = req.context["model_manager"]
    if getattr(mgr, "shard_count", 1) > 1 or hasattr(mgr, "generation"):
        cluster = {"generation": getattr(mgr, "generation", 0)}
        if getattr(mgr, "shard_count", 1) > 1:
            cluster.update(shard=mgr.shard_index, of=mgr.shard_count,
                           skipped_remote_items=getattr(
                               mgr, "skipped_remote_items", 0))
        out["cluster"] = cluster
    # named retry / circuit-breaker counters (resilience.policy) — the
    # evidence surface for "is the breaker open, how often do we retry"
    out["resilience"] = resilience_snapshot()
    # app-agnostic hook: a serving model may contribute its own gauges
    # (e.g. the ALS model's streaming top-k fallback counter)
    app_metrics = getattr(model, "metrics", None)
    if callable(app_metrics):
        out["model_metrics"] = app_metrics()
    # consumer-side integrity counters: poison updates / corrupt model
    # documents the manager refused (numerical trust boundary evidence)
    manager = req.context["model_manager"]
    rejected_updates = getattr(manager, "rejected_updates", None)
    if rejected_updates is not None:
        out["model_integrity"] = {
            "rejected_updates": rejected_updates,
            "rejected_models": getattr(manager, "rejected_models", 0),
        }
    # lambda freshness gauges (obs/freshness.py): consumer lag, model
    # generation age — evaluated on read, best-effort
    gauges = registry.gauges_snapshot()
    if gauges:
        out["freshness"] = gauges
    tracer = req.context.get("tracer")
    if tracer is not None:
        out["obs"] = {"trace_record_failures": tracer.record_failures}
    # continuous device-time accounting (obs/device_time.py): which
    # kernel route owned the device, and how busy it is
    acct = req.context.get("device_time")
    if acct is not None:
        out["device_time"] = acct.snapshot()
    return out


ROUTES = [
    Route("GET", "/ready", _ready),
    Route("GET", "/error", _error),
    Route("GET", "/metrics", _metrics),
    Route("GET", "/admin/traces", admin_traces),
    # tail anatomy + SLO alert surface (obs/anatomy.py, obs/slo.py);
    # both 404 until their config gates open
    Route("GET", "/admin/tail", admin_tail),
    Route("GET", "/admin/slo", admin_slo),
    # region identity (multi-region serving, docs/SCALING.md)
    Route("GET", "/admin/region", admin_region),
    # flight recorder + auto-triage (obs/flight.py, obs/diagnose.py);
    # /admin/flight 404s until oryx.obs.flight.dir opens the gate
    Route("GET", "/admin/flight", admin_flight),
    Route("GET", "/admin/diagnose", admin_diagnose),
    # mutating: captures device state to disk — read-only mode and
    # DIGEST auth (when configured) both gate it
    Route("GET", "/admin/profile", admin_profile, mutates=True),
    # mutating for the same reason: writes a bundle to the store
    Route("POST", "/admin/flight/dump", admin_flight_dump,
          mutates=True),
]
