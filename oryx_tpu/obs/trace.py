"""Sampled distributed span tracer with W3C ``traceparent`` context.

Dapper's (Sigelman et al., 2010) two load-bearing ideas, sized for this
runtime: (1) sampling decided once at the trace root and carried in the
propagated context, so the common unsampled request costs one branch
and zero allocation at every instrumentation point; (2) spans recorded
locally per process into a bounded in-memory ring, joined by trace id
at read time (``/admin/traces`` on each tier) instead of shipped
through a collector the runtime would then depend on.

Context crosses process boundaries two ways:

- HTTP: the ``traceparent`` request header
  (``00-<trace-id>-<span-id>-<flags>``), sent by the router's scatter
  transport and honored by every serving front end, which also echoes
  the trace id back as ``X-Oryx-Trace`` on sampled responses so a
  client can correlate a slow answer with its recorded trace.
- Kafka: a ``traceparent`` record header attached by ``/ingest``-family
  writes, so the speed layer can attribute its fold-in work to the
  originating request's trace.

The batched device call is the one place where work is recorded once
and read twice: :class:`DrainPhases` holds the phases of one drain
(``serving.prepare`` / ``scan`` / ``fallback`` / ``decode``) and, one
level down, the steps inside a phase (``serving.upload`` / ``launch`` /
``device_wait`` / ``fetch``), each marked where the work happens.  A
phase or step is a ``jax.profiler.TraceAnnotation`` on the dispatcher
thread — so it sits on the profiler's clock beside the device's
operations — and a monotonic stamp; the batcher replays the stamps as
ring spans under every sampled job of that drain.

Recording is STRICTLY best-effort: a raising recorder (the
``obs-trace-drop`` chaos point stands in for any internal failure)
degrades that span to a no-op and bumps ``record_failures`` — tracing
must never fail a request.  Everything is config-gated under
``oryx.obs.tracing.*``; the span-name taxonomy lives in
docs/OBSERVABILITY.md and is linted by tests/test_obs_catalog.py.
"""

from __future__ import annotations

import json
import logging
import random
import threading
from collections import OrderedDict

from ..common import clock as clockmod
from ..resilience import faults

_log = logging.getLogger(__name__)

__all__ = ["Span", "NOOP_SPAN", "Tracer", "DrainPhases", "current_drain",
           "annotation", "phase", "open_span", "parse_traceparent", "format_traceparent",
           "unsampled_traceparent", "tracer_from_config"]

_FLAG_SAMPLED = 0x01
# spans kept per trace: a runaway instrumentation loop must not let one
# trace eat the whole ring's memory
_MAX_SPANS_PER_TRACE = 512


def parse_traceparent(value: str | None):
    """``(trace_id, span_id, sampled)`` from a W3C traceparent header,
    or None when absent/malformed — malformed context starts a fresh
    trace, never an error (the W3C processing model)."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if (len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16
            or len(flags) != 2):
        return None
    try:
        int(version, 16)
        int(trace_id, 16)
        int(span_id, 16)
        f = int(flags, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id, bool(f & _FLAG_SAMPLED)


def format_traceparent(trace_id: str, span_id: str,
                       sampled: bool = True) -> str:
    return f"00-{trace_id}-{span_id}-{'01' if sampled else '00'}"


def unsampled_traceparent() -> str:
    """A valid context whose flags say NOT sampled — propagated on the
    internal hops of unsampled requests so downstream tiers honor the
    root's decision instead of re-rolling their own sampling dice.
    Ids are fresh per call; callers cache ONE per process (the
    receiving side returns NOOP_SPAN and never records them), keeping
    the unsampled hot path allocation-free."""
    return format_traceparent(_new_trace_id(), _new_span_id(),
                              sampled=False)


def _new_trace_id() -> str:
    return f"{random.getrandbits(128) or 1:032x}"


def _new_span_id() -> str:
    return f"{random.getrandbits(64) or 1:016x}"


class _NoopSpan:
    """The shared do-nothing span handed out for every unsampled
    request: one instance for the whole process, so the unsampled hot
    path allocates nothing and every instrumentation point is one
    ``span.sampled`` branch."""

    __slots__ = ()
    sampled = False
    trace_id = None
    span_id = None
    parent_id = None

    def set_attr(self, key, value) -> None:
        pass

    def end(self, status: str | None = None) -> None:
        pass

    def traceparent(self) -> None:
        return None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class Span:
    """One sampled span.  Usable as a context manager (sets itself as
    the calling thread's current span for the duration) or ended
    explicitly with :meth:`end`."""

    __slots__ = ("_tracer", "name", "trace_id", "span_id", "parent_id",
                 "t_start", "attrs", "status", "_prev", "_note")
    sampled = True

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 parent_id: str | None):
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.t_start = clockmod.monotonic()
        self.attrs: dict = {}
        self.status = "ok"
        self._prev = None
        # a profiler annotation that opens and closes with the span
        # (phase()), or None
        self._note = None

    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def traceparent(self) -> str:
        return format_traceparent(self.trace_id, self.span_id)

    def end(self, status: str | None = None) -> None:
        if status is not None:
            self.status = status
        self._tracer._record(self.name, self.trace_id, self.span_id,
                             self.parent_id, self.t_start,
                             clockmod.monotonic(), self.attrs, self.status)

    def __enter__(self):
        self._prev = self._tracer._swap(self)
        if self._note is not None:
            self._note.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
            self.status = "error"
        if self._note is not None:
            self._note.__exit__(None, None, None)
        self.end()
        self._tracer._swap(self._prev)
        return False


def annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` of that name: a host event on
    the calling thread's line of whatever profiler trace is running
    (``/admin/profile``, ``jax.profiler.start_trace``), next to free
    when none is.  The one place this module reaches jax, and not at
    the top: the router tier traces without ever loading it."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


# the sampled span open on the calling thread, whichever tracer made it:
# code that has no tracer of its own (a model, a store, a solver cache)
# records under it through phase()
_open = threading.local()


def open_span():
    """The sampled span open on the calling thread, or None: what a
    caller hands to a helper thread as ``phase(..., parent=...)``."""
    return getattr(_open, "span", None)


def phase(name: str, tracer: "Tracer | None" = None, parent=None, **attrs):
    """A piece of work on the calling thread, recorded once and read
    twice like a drain's phases: a ring span and a profiler annotation
    of the same name.  It is a child of whatever sampled span is open
    on this thread, or of ``parent`` (``open_span()`` of the thread
    that handed this work over); with neither it starts a trace of its
    own in ``tracer`` (at that tracer's sampling ratio), and without
    that it is NOOP_SPAN: one thread-local lookup and a branch.  Use as
    a context manager; phases opened inside it nest under it."""
    cur = getattr(_open, "span", None) or parent
    if cur is not None:
        span = Span(cur._tracer, name, cur.trace_id, cur.span_id)
    elif tracer is not None and (tracer.sample_ratio >= 1.0
                                 or random.random() < tracer.sample_ratio):
        span = Span(tracer, name, _new_trace_id(), None)
    else:
        return NOOP_SPAN
    span.attrs.update(attrs)
    span._note = annotation(name)
    return span


# the drain being recorded on the calling thread, if any: the batcher's
# dispatcher thread opens it around model.top_n_batch, and the model
# reaches it through current_drain() without a tracer of its own
# (top_n_batch keeps its signature for the callers that record nothing)
_drain = threading.local()


def current_drain() -> "DrainPhases | None":
    """The recorder open on this thread, or None — tracing off, or a
    ``top_n_batch`` caller that is not the batcher.  A phase or step
    site is ``if rec is not None: rec.mark(...)``: with no recorder that
    is one branch, no annotation, no clock read, no allocation."""
    return getattr(_drain, "open", None)


class DrainPhases:
    """The phases of ONE batched device call, recorded once where the
    work happens.  While open (a context manager, on the thread that
    makes the call) :meth:`mark` closes the phase that is running and
    opens the next, and :meth:`step` does the same one level down, for a
    piece of work INSIDE the running phase: one clock read and one
    profiler annotation each.  Phases follow each other and never nest;
    a phase's steps follow each other inside it, the last one ending
    with the phase; the last phase closes with the recorder, and it and
    its running step as errors if an exception ends it.  The two
    readings differ in one thing.  On the profiler's line the
    annotations are FLAT: exactly one is open at any instant, the
    innermost piece of work running, so a phase's annotation closes
    when its first step opens (an idle gap of the device takes the name
    of the event that covers most of it, and an outer annotation would
    win every gap that straddles two steps).  In the ring
    (:meth:`replay`, under one job's ``serving.device_execute`` span) a
    phase keeps its whole duration and its steps are its children."""

    __slots__ = ("_phases", "_note", "_at", "_spans")

    def __init__(self):
        # [name, start, end, attrs, status, up] in the order they began:
        # ``up`` is None for a phase and, for a step, its phase's index
        self._phases: list[list] = []
        self._note = None
        # the running phase's index; its running step, if any, is the
        # last entry
        self._at = 0
        self._spans: tuple[list[dict], list] | None = None

    def __enter__(self):
        _drain.open = self
        return self

    def __exit__(self, exc_type, exc, tb):
        _drain.open = None
        self._close(clockmod.monotonic(),
                    "ok" if exc_type is None else "error")
        return False

    def _close(self, now: float, status: str) -> None:
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None
            # the running phase and its running step (one entry where
            # no step runs)
            for p in (self._phases[self._at], self._phases[-1]):
                p[2] = now
                p[4] = status

    def _open(self, name: str, now: float, attrs: dict, up) -> None:
        note = annotation(name)
        note.__enter__()
        # together, and last: an open annotation IS an open last entry,
        # so the recorder's exit leaves none without its end
        self._note = note
        self._phases.append([name, now, None, attrs, "ok", up])

    def mark(self, name: str, **attrs) -> None:
        """``name`` starts here, with the counts known at this boundary,
        and whatever phase was running ends here, its last step with
        it."""
        now = clockmod.monotonic()
        self._close(now, "ok")
        self._at = len(self._phases)
        self._open(name, now, attrs, None)

    def step(self, name: str, **counts) -> None:
        """``name``, a piece of work inside the running phase, starts
        here, and the step that was running, if any, ends here.  The
        one annotation open on the profiler's line from here on is the
        step's."""
        if self._note is None:  # no phase is running: it is one itself
            return self.mark(name, **counts)
        now = clockmod.monotonic()
        self._note.__exit__(None, None, None)
        self._note = None
        last = self._phases[-1]
        if last[5] is not None:
            last[2] = now
        self._open(name, now, counts, self._at)

    def annotate(self, **attrs) -> None:
        """Counts that are known only once the running phase's work is
        done (what a sync carried) go onto it here, whichever step of
        it is running."""
        if self._phases:
            self._phases[self._at][3].update(attrs)

    def replay(self, tracer: "Tracer", trace_id: str,
               parent_id: str) -> None:
        """The drain's phases as ring spans under ``parent_id`` and
        their steps under them, written under one acquisition of the
        tracer's lock.  What is the same for every sampled job of the
        drain — names, stamps, attributes (shared between the jobs'
        spans, read-only from here on), who is whose child — is worked
        out on the first call."""
        if self._spans is None:
            self._spans = ([tracer.span_fields(*p[:5])
                            for p in self._phases],
                           [p[5] for p in self._phases])
        tracer.record_spans(trace_id, parent_id, *self._spans)


class Tracer:
    """Per-process span recorder + sampling/propagation policy."""

    def __init__(self, service: str, sample_ratio: float = 0.01,
                 max_traces: int = 256,
                 slow_request_ms: int | None = None):
        self.service = service
        self.sample_ratio = float(sample_ratio)
        self.max_traces = int(max_traces)
        self.slow_request_ms = slow_request_ms
        # recorder failures degraded to no-ops (the best-effort contract)
        self.record_failures = 0
        self._local = threading.local()
        self._lock = threading.Lock()
        # trace id -> finished span dicts, oldest trace evicted first
        self._traces: "OrderedDict[str, list[dict]]" = OrderedDict()
        # anchor so spans recorded from stored monotonic stamps (the
        # batcher's enqueue time) still carry wall-clock start times
        self._mono_anchor = clockmod.now() - clockmod.monotonic()

    # -- thread-current context ---------------------------------------------

    def current(self):
        """The calling thread's active span (NOOP_SPAN when none)."""
        return getattr(self._local, "span", None) or NOOP_SPAN

    def _swap(self, span):
        prev = getattr(self._local, "span", None)
        self._local.span = span
        _open.span = span
        return prev

    # -- span creation -------------------------------------------------------

    def begin_request(self, name: str,
                      traceparent: str | None = None):
        """Server-side request span: a sampled inbound ``traceparent``
        is continued (the root already decided), an explicitly
        UNsampled one is honored, anything else samples locally.
        Returns NOOP_SPAN for the unsampled case — one branch, no
        allocation — and installs a sampled span as the thread's
        current span (cleared by :meth:`end_request`)."""
        ctx = parse_traceparent(traceparent) if traceparent else None
        if ctx is not None:
            trace_id, parent_id, sampled = ctx
            if not sampled:
                return NOOP_SPAN
        elif (self.sample_ratio >= 1.0
                or random.random() < self.sample_ratio):
            trace_id, parent_id = _new_trace_id(), None
        else:
            return NOOP_SPAN
        span = Span(self, name, trace_id, parent_id)
        self._swap(span)
        return span

    def end_request(self, span, status: int = 0,
                    route: str | None = None) -> None:
        if not span.sampled:
            return
        self._swap(None)
        if route:
            span.attrs["route"] = route
        span.attrs["http.status"] = status
        span.end("error" if status >= 500 or status == 0 else "ok")
        if self.slow_request_ms is not None:
            dur_ms = (clockmod.monotonic() - span.t_start) * 1000.0
            if dur_ms >= self.slow_request_ms:
                self._dump_slow(span.trace_id, route, dur_ms)

    def span(self, name: str):
        """Child of the calling thread's current span; NOOP_SPAN when
        the request is unsampled.  Use as a context manager."""
        cur = self.current()
        if not cur.sampled:
            return NOOP_SPAN
        return Span(self, name, cur.trace_id, cur.span_id)

    def child_span(self, parent, name: str):
        """Child of an explicit parent span — for work handed to other
        threads (scatter fan-out), where thread-local context does not
        follow."""
        if parent is None or not parent.sampled:
            return NOOP_SPAN
        return Span(self, name, parent.trace_id, parent.span_id)

    def record_span(self, name: str, trace_ctx: tuple[str, str] | None,
                    start_mono: float, end_mono: float,
                    attrs: dict | None = None,
                    status: str = "ok") -> str | None:
        """Retroactive span from stored monotonic stamps and a
        ``(trace_id, parent_span_id)`` context captured earlier (the
        batcher records queue-wait this way after the fact).

        Returns the new span's id, so the caller can record children
        under it — the batcher parents a drain's phases under each
        job's ``serving.device_execute`` this way — or None when there
        was no context to record under.  The id comes back even where
        the recorder failed: recording is best-effort, parenting is not
        an error path."""
        if not trace_ctx:
            return None
        span_id = _new_span_id()
        self._record(name, trace_ctx[0], span_id, trace_ctx[1],
                     start_mono, end_mono, attrs or {}, status)
        return span_id

    def span_fields(self, name: str, start_mono: float, end_mono: float,
                    attrs: dict, status: str = "ok") -> dict:
        """A finished span less its three ids, for :meth:`record_spans`:
        what one piece of work shared by several traces (a drain's
        phase) computes once."""
        return {
            "name": name,
            "service": self.service,
            "trace_id": None,
            "span_id": None,
            "parent_id": None,
            "start_ms": round((start_mono + self._mono_anchor) * 1000.0, 3),
            "duration_ms": round((end_mono - start_mono) * 1000.0, 3),
            "attrs": attrs,
            "status": status,
        }

    def record_spans(self, trace_id: str, parent_id: str,
                     fields: list[dict], under: list | None = None) -> None:
        """Several finished spans (:meth:`span_fields`) of one trace
        under ONE acquisition of the ring's lock: children of
        ``parent_id``, or, where ``under`` names for a span the index
        of an EARLIER one of ``fields``, of that span.  ``fields`` is
        left as it was, so the same list can be recorded under other
        traces; each span lost to a failing recorder counts in
        ``record_failures``."""
        try:
            spans = []
            for i, f in enumerate(fields):
                span = dict(f)
                span["trace_id"] = trace_id
                span["span_id"] = _new_span_id()
                up = under[i] if under is not None else None
                span["parent_id"] = parent_id if up is None \
                    else spans[up]["span_id"]
                spans.append(span)
            self._append(trace_id, spans)
        except Exception:  # noqa: BLE001 — observability is best-effort
            with self._lock:
                self.record_failures += len(fields)

    # -- recording (best-effort, bounded) ------------------------------------

    def _record(self, name, trace_id, span_id, parent_id, start_mono,
                end_mono, attrs, status) -> None:
        try:
            span = self.span_fields(name, start_mono, end_mono, attrs,
                                    status)
            span["trace_id"] = trace_id
            span["span_id"] = span_id
            span["parent_id"] = parent_id
            self._append(trace_id, [span])
        except Exception:  # noqa: BLE001 — observability is best-effort
            # under the lock: concurrent failing recorders must not
            # lose increments of the evidence counter
            with self._lock:
                self.record_failures += 1

    def _append(self, trace_id: str, spans: list[dict]) -> None:
        # chaos seam: a raising recorder must degrade to a no-op +
        # counter, never fail the request being traced
        faults.fire("obs-trace-drop")
        with self._lock:
            kept = self._traces.get(trace_id)
            if kept is None:
                while len(self._traces) >= self.max_traces:
                    self._traces.popitem(last=False)
                kept = self._traces[trace_id] = []
            kept.extend(spans[:_MAX_SPANS_PER_TRACE - len(kept)])

    def _dump_slow(self, trace_id: str, route: str | None,
                   dur_ms: float) -> None:
        try:
            with self._lock:
                spans = list(self._traces.get(trace_id) or ())
            _log.warning(
                "SLOW REQUEST %.1f ms (threshold %d ms) route=%s "
                "trace=%s spans=%s", dur_ms, self.slow_request_ms,
                route, trace_id, json.dumps(spans))
        except Exception:  # noqa: BLE001 — best-effort
            with self._lock:
                self.record_failures += 1

    # -- read side -----------------------------------------------------------

    def spans_for(self, trace_id: str) -> list[dict]:
        """The finished spans of one trace from this process's ring
        (empty when unknown/evicted) — the wide-event log reads the
        just-finished request's spans through this."""
        with self._lock:
            return list(self._traces.get(trace_id) or ())

    def traces_snapshot(self, limit: int = 64) -> dict:
        """Newest ``limit`` finished traces, each a flat span list the
        caller reassembles into a tree via parent_id."""
        with self._lock:
            ids = list(self._traces)[-max(1, limit):]
            return {tid: list(self._traces[tid]) for tid in ids}


def tracer_from_config(config, service: str) -> Tracer | None:
    """Build the layer's tracer from ``oryx.obs.tracing.*``; None when
    tracing is disabled (every instrumentation point then costs one
    ``is None`` check)."""
    t = "oryx.obs.tracing"
    if not config.get_bool(f"{t}.enabled"):
        return None
    return Tracer(
        service,
        sample_ratio=config.get_double(f"{t}.sample-ratio"),
        max_traces=config.get_int(f"{t}.max-traces"),
        slow_request_ms=config.get_optional_int(f"{t}.slow-request-ms"))
