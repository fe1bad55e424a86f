"""The phases of a drain (``serving.prepare`` / ``scan`` / ``fallback``
/ ``decode``) and the steps inside them (``serving.upload`` / ``launch``
/ ``device_wait`` / ``fetch``): recorded once where the work happens,
read twice — as ring spans under each sampled job's
``serving.device_execute`` and as profiler annotations on the
dispatcher thread, where one is open at a time — plus the dispatchers'
annotated waits and ``serving.release``, and the program names the
benchmark's device metrics tell the two-phase scan from its exact-scan
fallback by."""

import ast
import inspect
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oryx_tpu.app.als import serving_model as sm
from oryx_tpu.app.als.serving_model import ALSServingModel
from oryx_tpu.obs import trace as obstrace
from oryx_tpu.obs.trace import Tracer
from oryx_tpu.resilience import faults
from oryx_tpu.serving.batcher import TopNBatcher, _Job

ITEMS, FEATURES = 4096, 8
PHASES = ["serving.prepare", "serving.scan", "serving.decode"]
# the steps inside each phase of a one-chip drain, in the order they run
# (the sharded drain uploads inside ``serving.scan``: SHARDED_STEPS)
STEPS = {"serving.prepare": ["serving.upload"],
         "serving.scan": ["serving.launch", "serving.device_wait",
                          "serving.fetch"]}
SHARDED_STEPS = {"serving.scan": ["serving.upload", "serving.launch",
                                  "serving.device_wait", "serving.fetch"]}
STEP_NAMES = [step for phase in PHASES for step in STEPS.get(phase, [])]
# a drain's annotations on the dispatcher's line in the order they open
# (a phase's closes when its first step opens), and the batcher's own
# once the model has returned
LINE = [name for phase in PHASES
        for name in [phase, *STEPS.get(phase, [])]] + ["serving.release"]


@pytest.fixture(scope="module")
def model():
    rng = np.random.default_rng(24)
    m = ALSServingModel(FEATURES, implicit=True)
    m.Y.bulk_load([f"i{j}" for j in range(ITEMS)],
                  rng.standard_normal((ITEMS, FEATURES)).astype(np.float32))
    return m


@pytest.fixture
def ladder(monkeypatch):
    """The streaming two-phase branch at toy scale (tests/test_als.py's
    monkeypatches)."""
    monkeypatch.setattr(sm, "_FLAT_SCORES_LIMIT", 1)
    monkeypatch.setattr(sm, "_MAX_CHUNK_ROWS", 1024)
    monkeypatch.setattr(sm, "_BLOCK_ROWS", 64)
    monkeypatch.setattr(sm, "_BLOCK_KSEL", 8)


@pytest.fixture
def notes(monkeypatch):
    """A capturing stand-in for ``jax.profiler.TraceAnnotation``: every
    construction as ``(name, thread name)``, and the open/close order."""
    seen, order = [], []

    class StandIn:
        def __init__(self, name, **kwargs):
            self.name = name
            seen.append((name, threading.current_thread().name))

        def __enter__(self):
            order.append(("open", self.name))
            return self

        def __exit__(self, *exc):
            order.append(("close", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", StandIn)
    return seen, order


def _vectors(n, seed=7):
    return np.random.default_rng(seed).standard_normal(
        (n, FEATURES)).astype(np.float32)


def _drain(batcher, tracer, model, n, sampled, known=0):
    """One drain of ``n`` jobs dispatched on this thread, the first
    ``sampled`` of them traced, each excluding the first ``known``
    items; returns (jobs, their request spans)."""
    requests, jobs = [], []
    for i, vec in enumerate(_vectors(n)):
        ctx = None
        if i < sampled:
            req = tracer.begin_request("serving.request")
            tracer._swap(None)
            requests.append(req)
            ctx = (req.trace_id, req.span_id)
        jobs.append(_Job(model, 5, vec, {f"i{j}" for j in range(known)},
                         trace_ctx=ctx))
    assert batcher._dispatch(jobs) == n
    for j in jobs:
        assert j.error is None and len(j.result) == 5
    return jobs, requests


def _by_name(spans):
    out: dict = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


@pytest.fixture
def traced():
    tracer = Tracer("serving", sample_ratio=1.0)
    batcher = TopNBatcher(pipeline=1, tracer=tracer)
    yield batcher, tracer
    batcher.close()


@pytest.mark.parametrize("branch", ["ladder", "flat"])
def test_phases_land_under_each_jobs_device_execute(branch, model, traced,
                                                    request):
    if branch == "ladder":
        request.getfixturevalue("ladder")
    batcher, tracer = traced
    _, requests = _drain(batcher, tracer, model, 3, sampled=3)
    stamps = set()
    for req in requests:
        spans = _by_name(tracer.spans_for(req.trace_id))
        (execute,) = spans["serving.device_execute"]
        assert execute["parent_id"] == req.span_id
        assert "serving.fallback" not in spans
        lo = execute["start_ms"]
        hi = lo + execute["duration_ms"]
        total, at = 0.0, lo
        for name in PHASES:
            (s,) = spans[name]
            assert s["parent_id"] == execute["span_id"]
            assert s["trace_id"] == req.trace_id
            # inside the parent, one after the other (stamps are
            # rounded to a microsecond)
            assert at - 0.002 <= s["start_ms"]
            at = s["start_ms"] + s["duration_ms"]
            assert at <= hi + 0.002
            total += s["duration_ms"]
        assert total <= execute["duration_ms"] + 0.002
        # the counts known at the boundary where each phase begins
        assert spans["serving.prepare"][0]["attrs"] == {"rows": 3}
        # ... the width phase B selects among them: twice the fetch on
        # the ladder, 0 where no block is selected (the flat kernel);
        # the requests among the window's rows, and on the ladder the
        # share of its rows phase B rescores: those three of eight
        assert spans["serving.scan"][0]["attrs"] == dict(
            {"k": 8, "ksel": 16 if branch == "ladder" else 0,
             "windows": [8], "lane_rows": 0, "real_rows": 3},
            **({"phase_b_row_share": 37.5} if branch == "ladder" else {}))
        assert spans["serving.decode"][0]["attrs"] == {"rows": 3}
        stamps.add(tuple((spans[n][0]["start_ms"],
                          spans[n][0]["duration_ms"]) for n in PHASES))
    # recorded once, replayed three times: the same stamps under each job
    assert len(stamps) == 1


@pytest.mark.parametrize("known, k, ksel", [
    (0, 8, 16), (10, 16, 32),
    (20, 32, 63),   # 2k, capped under the toy store's 64 blocks
    (50, 64, 0)])   # the cap leaves ksel < k: the exact scan, alone
def test_the_scan_span_says_which_width_ran(known, k, ksel, model, traced,
                                            ladder, monkeypatch):
    """``serving.scan`` carries the block-selection width the fetched k
    chose, and a wide fetch is one scan with no fallback after it."""
    exact = []
    real = sm._batch_top_n_chunked_kernel
    monkeypatch.setattr(sm, "_batch_top_n_chunked_kernel",
                        lambda *a, **kw: exact.append(1) or real(*a, **kw))
    batcher, tracer = traced
    req = tracer.begin_request("serving.request")
    tracer._swap(None)
    before = model.twophase_fallbacks
    job = _Job(model, 5, _vectors(1)[0], {f"i{j}" for j in range(known)},
               trace_ctx=(req.trace_id, req.span_id))
    assert batcher._dispatch([job]) == 1
    assert job.error is None and len(job.result) == 5
    assert model.twophase_fallbacks == before
    assert len(exact) == (1 if ksel == 0 else 0)
    spans = _by_name(tracer.spans_for(req.trace_id))
    assert "serving.fallback" not in spans
    assert spans["serving.scan"][0]["attrs"] == dict(
        {"k": k, "ksel": ksel, "windows": [8], "lane_rows": 0,
         "real_rows": 1},
        # one request on an 8-wide window; no phase B where the exact
        # scan is the primary path
        **({"phase_b_row_share": 12.5} if ksel else {}))
    assert ksel == 0 or ksel == sm._block_ksel(k, ITEMS, 64)


@pytest.mark.parametrize("jobs, windows, lane_rows", [
    (2, [8], 1), (9, [32], 1), (33, [256], 0), (258, [256, 8], 1)])
def test_the_scan_span_counts_the_windows_scored_rows_on_lanes(
        jobs, windows, lane_rows, model, traced, ladder, monkeypatch):
    """``lane_rows``: how many of the drain's windows the pallas phase A
    scored with the store's rows on the lanes, which it does for a
    window narrower than a lane tile.  The CPU lowers no pallas build
    (every test above reads 0: the lax.scan build ran), so the build
    runs in interpret mode here."""
    real = sm._batch_top_n_twophase_pallas
    monkeypatch.setattr(
        sm, "_batch_top_n_twophase_pallas",
        lambda *a, **kw: real(*a, **kw, interpret=True))
    # the verdicts of the drains above, which could not lower it
    monkeypatch.setattr(sm, "_PALLAS_STATE", {})
    monkeypatch.setattr(sm, "_PALLAS_ERRORS", {})
    batcher, tracer = traced
    _, (req,) = _drain(batcher, tracer, model, jobs, sampled=1)
    attrs = _by_name(tracer.spans_for(req.trace_id))[
        "serving.scan"][0]["attrs"]
    # (phase B rescores the requests of a narrow window and every row
    # of a 256-wide one)
    rescored = sum(w if w >= 128 else min(w, jobs - at)
                   for w, at in zip(windows, np.cumsum([0] + windows)))
    assert attrs == {"k": 8, "ksel": 16, "windows": windows,
                     "lane_rows": lane_rows, "real_rows": jobs,
                     "phase_b_row_share": round(
                         100.0 * rescored / sum(windows), 3)}
    # ... and it ran, for every window: no shape fell to the scan build
    ran = {key[2]: state for key, state in sm._PALLAS_STATE.items()
           if key[-1] == "pallas"}
    assert ran == {w: "ok" for w in windows}


@pytest.mark.parametrize("jobs, windows, rescored", [
    (2, [8], 2), (8, [8], 8), (9, [32], 9), (258, [256, 8], 258),
    (140, [256], 256)])
def test_the_scan_span_says_how_many_rows_phase_b_rescored(
        jobs, windows, rescored, model, traced, ladder):
    """PR 38: ``real_rows`` is the drain's requests and
    ``phase_b_row_share`` the rows phase B rescored (a narrow window's
    requests, every row of a 256-wide one) over the rows of its windows:
    25.0 for two callers on an ``[8]``, 100.0 for eight.  ``/metrics``
    keeps the running totals beside ``twophase_fallbacks``.  The
    benchmark's ``kernel.phase_b_row_share`` is the mean of the share
    over the ``serving.scan`` spans."""
    batcher, tracer = traced
    before = model.metrics()
    _, (req,) = _drain(batcher, tracer, model, jobs, sampled=1)
    attrs = _by_name(tracer.spans_for(req.trace_id))[
        "serving.scan"][0]["attrs"]
    assert attrs["windows"] == windows
    assert attrs["real_rows"] == jobs
    assert attrs["phase_b_row_share"] == pytest.approx(
        100.0 * rescored / sum(windows), abs=1e-3)
    if jobs == 2:
        assert attrs["phase_b_row_share"] == 25.0
    after = model.metrics()
    assert after["phase_b_rows"] - before["phase_b_rows"] == rescored
    assert after["phase_b_window_rows"] - before["phase_b_window_rows"] \
        == sum(windows)
    assert after["twophase_fallbacks"] == before["twophase_fallbacks"]


@pytest.mark.parametrize("failing, widths", [("tail", [8]),
                                             ("all", [256, 8])])
def test_a_certificate_miss_records_a_fallback_per_failing_window(
        failing, widths, model, traced, ladder, monkeypatch):
    real = sm._batch_top_n_twophase_kernel

    def sabotaged(Y, Q, *args, **kw):
        ts, ti, cert = real(Y, Q, *args, **kw)
        if failing == "all" or Q.shape[0] == 8:
            return ts, ti, cert & False
        return ts, ti, cert | True

    monkeypatch.setattr(sm, "_batch_top_n_twophase_kernel", sabotaged)
    batcher, tracer = traced
    before = model.twophase_fallbacks
    want = model.top_n_batch(5, _vectors(257))
    assert model.twophase_fallbacks - before == sum(widths)
    before = model.twophase_fallbacks
    jobs, (req,) = _drain(batcher, tracer, model, 257, sampled=1)
    assert [j.result for j in jobs] == want
    spans = _by_name(tracer.spans_for(req.trace_id))
    (execute,) = spans["serving.device_execute"]
    fallbacks = spans["serving.fallback"]
    assert [s["attrs"] for s in fallbacks] == [
        {"k": 8, "width": w, "rows_failed": w} for w in widths]
    assert all(s["parent_id"] == execute["span_id"] for s in fallbacks)
    assert model.twophase_fallbacks - before \
        == sum(s["attrs"]["rows_failed"] for s in fallbacks)
    assert spans["serving.scan"][0]["attrs"]["windows"] == [256, 8]
    # one mark ends a phase and begins the next: scan runs up to the
    # first fallback, the last fallback up to decode (stamps are
    # rounded to a microsecond)
    in_order = [spans["serving.scan"][0], *fallbacks,
                spans["serving.decode"][0]]
    for before, after in zip(in_order, in_order[1:]):
        assert before["start_ms"] + before["duration_ms"] \
            == pytest.approx(after["start_ms"], abs=0.002)


def test_the_requests_own_children_stay_the_two_they_were(model, ladder):
    tracer = Tracer("serving", sample_ratio=1.0)
    batcher = TopNBatcher(pipeline=2, tracer=tracer)
    try:
        req = tracer.begin_request("serving.request")
        assert len(batcher.top_n(model, 5, _vectors(1)[0])) == 5
        tracer.end_request(req, 200, "GET /recommend/{userID}")
    finally:
        batcher.close()
    spans = tracer.spans_for(req.trace_id)
    children = sorted(s["name"] for s in spans
                      if s["parent_id"] == req.span_id)
    assert children == ["serving.device_execute", "serving.queue_wait"]
    (execute,) = [s for s in spans if s["name"] == "serving.device_execute"]
    assert sorted(s["name"] for s in spans
                  if s["parent_id"] == execute["span_id"]) == sorted(PHASES)
    assert len(spans) == 6 + len(STEP_NAMES)


@pytest.mark.parametrize("jobs, sampled", [(1, 0), (3, 1), (3, 3), (9, 2)])
def test_each_phase_is_annotated_once_per_drain(jobs, sampled, model,
                                                traced, ladder, notes):
    seen, order = notes
    batcher, tracer = traced
    me = threading.current_thread().name
    _, requests = _drain(batcher, tracer, model, jobs, sampled)
    mine = [name for name, thread in seen if thread == me]
    # whatever the drain's size and however many of it were sampled
    assert mine == LINE
    assert [e for e in order if e[1] in PHASES] == [
        (what, name) for name in PHASES for what in ("open", "close")]
    recorded = [s for r in requests for s in tracer.spans_for(r.trace_id)]
    assert sorted(s["name"] for s in recorded) == sorted(
        (PHASES + STEP_NAMES + ["serving.queue_wait",
                                "serving.device_execute"]) * sampled)


def _until(cond, what, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


class _Gated:
    """Stalls its first drain until released; the later ones go straight
    to the model."""

    def __init__(self, model):
        self.model = model
        self.first = True
        self.in_dispatch, self.release = threading.Event(), threading.Event()

    def top_n_batch(self, how_many, vectors, exclude):
        if self.first:
            self.first = False
            self.in_dispatch.set()
            assert self.release.wait(10.0)
        return self.model.top_n_batch(how_many, vectors, exclude)


def test_the_waits_are_annotated_and_never_ring_recorded(model, ladder,
                                                         notes):
    seen, _ = notes
    tracer = Tracer("serving", sample_ratio=1.0)
    batcher = TopNBatcher(pipeline=2, tracer=tracer)
    batcher._in_flight_target = lambda: 1
    gated, answers = _Gated(model), []

    def call():
        req = tracer.begin_request("serving.request")
        answers.append(batcher.top_n(gated, 5, _vectors(1)[0]))
        tracer.end_request(req, 200)

    callers = [threading.Thread(target=call) for _ in range(2)]
    try:
        callers[0].start()
        assert gated.in_dispatch.wait(10.0)
        # the second request finds the in-flight cap taken
        callers[1].start()
        _until(lambda: "serving.await_slot" in [n for n, _ in seen],
               "no dispatcher waited for a slot")
        gated.release.set()
        for t in callers:
            t.join(10.0)
            assert not t.is_alive()
    finally:
        gated.release.set()
        batcher.close()
    assert len(answers) == 2
    waits = {(n, t) for n, t in seen if n.startswith("serving.await")}
    assert {n for n, _ in waits} == {"serving.await_work",
                                     "serving.await_slot"}
    assert all(t.startswith("TopNBatcher-") for _, t in waits)
    ring = {s["name"] for spans in tracer.traces_snapshot().values()
            for s in spans}
    assert ring == set(PHASES) | set(STEP_NAMES) | {
        "serving.request", "serving.queue_wait", "serving.device_execute"}


def _open_waits(order):
    """How many wait annotations are open after each event."""
    n, out = 0, []
    for what, name in order:
        if name.startswith("serving.await"):
            n += 1 if what == "open" else -1
            out.append(n)
    return out


def test_one_dispatcher_carries_the_pools_wait_and_work_ends_it(
        model, ladder, notes):
    """``serving.await_work`` is the POOL's state — nothing queued,
    nothing in flight — so one of the eight idle threads says it, and
    the request that ends the state wakes that thread, so its
    annotation closes there and then, not some completions later."""
    seen, order = notes
    batcher = TopNBatcher(pipeline=8, tracer=Tracer("serving", 1.0))

    class Slow:
        """Long enough a drain that the woken thread has run by its
        end, however busy the machine."""

        def top_n_batch(self, how_many, vectors, exclude):
            time.sleep(0.1)
            return model.top_n_batch(how_many, vectors, exclude)

    def settled(times):
        _until(lambda: _open_waits(order)[-1:] == [1]
               and [n for n, _ in seen].count("serving.await_work")
               == times,
               "the idle pool did not settle on one annotated wait")

    try:
        for served in range(3):
            settled(served + 1)
            assert len(batcher.top_n(Slow(), 5, _vectors(1)[0])) == 5
        settled(4)
    finally:
        batcher.close()
    assert set(_open_waits(order)) == {0, 1}
    assert _open_waits(order)[-1] == 0  # close() let the last one go
    # each request ended one wait, and the drained pool began the next
    # (``serving.release`` twice: the callers released in the dispatch,
    # the lesson learnt in the loop)
    assert [name for what, name in order if what == "open"] == (
        ["serving.await_work"] + LINE + ["serving.release"]) * 3 \
        + ["serving.await_work"]
    closes = [i for i, e in enumerate(order)
              if e == ("close", "serving.await_work")]
    decodes = [i for i, e in enumerate(order)
               if e == ("close", "serving.decode")]
    # ... before that request's drain was over (whichever dispatcher
    # got to the queue first ran it)
    assert all(c < d for c, d in zip(closes, decodes))


def test_await_work_is_not_said_while_a_drain_is_in_flight(model, ladder,
                                                           notes):
    """With a drain in flight the device is not idle for want of
    requests: a dispatcher that finds the queue empty then waits
    without the annotation, and the gap belongs to the drain's phases."""
    seen, order = notes
    batcher = TopNBatcher(pipeline=4, tracer=Tracer("serving", 1.0))
    gated = _Gated(model)
    stalled = threading.Thread(
        target=lambda: batcher.top_n(gated, 5, _vectors(1)[0]))

    def said():
        return len([n for n, _ in seen if n == "serving.await_work"])

    try:
        _until(lambda: said() == 1, "no annotated wait on the idle pool")
        stalled.start()
        assert gated.in_dispatch.wait(10.0)
        # served by other dispatchers, which then find the queue empty
        for _ in range(3):
            assert len(batcher.top_n(model, 5, _vectors(1)[0])) == 5
        _until(lambda: batcher.stats()["in_flight"] == 1, "still busy")
        assert said() == 1 and _open_waits(order)[-1] == 0
        gated.release.set()
        stalled.join(10.0)
        # the pool ran dry again: whoever finished last says so
        _until(lambda: said() == 2 and _open_waits(order)[-1] == 1,
               "the drained pool did not annotate its wait")
    finally:
        gated.release.set()
        batcher.close()


def test_without_a_tracer_nothing_is_annotated_and_answers_match(
        model, traced, ladder, monkeypatch):
    batcher, tracer = traced
    jobs, _ = _drain(batcher, tracer, model, 3, sampled=3)

    def never(*args, **kwargs):
        raise AssertionError("an annotation was built with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", never)
    assert obstrace.current_drain() is None
    plain = TopNBatcher(pipeline=2)
    try:
        for j in jobs:
            assert plain.top_n(model, 5, j.vector) == j.result
        untraced_jobs = [_Job(model, 5, j.vector, set()) for j in jobs]
        assert plain._dispatch(untraced_jobs) == 3
        assert [j.result for j in untraced_jobs] == [j.result for j in jobs]
        # and a direct caller (route measurement, warm-up) records nothing
        assert model.top_n_batch(5, np.stack([j.vector for j in jobs])) \
            == [j.result for j in jobs]
    finally:
        plain.close()


def test_a_raising_recorder_still_answers_the_drain(model, traced, ladder):
    batcher, tracer = traced
    faults.clear()
    try:
        faults.inject("obs-trace-drop", mode="error", times=100)
        _, (req,) = _drain(batcher, tracer, model, 3, sampled=1)
        # queue_wait, device_execute, the three phases and their steps
        # all dropped
        assert tracer.record_failures == 2 + len(PHASES) + len(STEP_NAMES)
        assert tracer.spans_for(req.trace_id) == []
    finally:
        faults.clear()


def test_a_failing_scan_closes_its_phase_as_an_error(model, traced, ladder,
                                                     monkeypatch, notes):
    _, order = notes

    def broken(*args, **kwargs):
        raise RuntimeError("device lost")

    monkeypatch.setattr(ALSServingModel, "_dispatch_twophase", broken)
    batcher, tracer = traced
    req = tracer.begin_request("serving.request")
    tracer._swap(None)
    job = _Job(model, 5, _vectors(1)[0], set(),
               trace_ctx=(req.trace_id, req.span_id))
    batcher._dispatch([job])
    assert isinstance(job.error, RuntimeError)
    spans = _by_name(tracer.spans_for(req.trace_id))
    assert spans["serving.device_execute"][0]["status"] == "error"
    assert spans["serving.prepare"][0]["status"] == "ok"
    assert spans["serving.scan"][0]["status"] == "error"
    assert "serving.decode" not in spans
    # no annotation is left open on the dispatcher's line: the drain's
    # up to the step the failure ended, then the batcher's own
    ran = LINE[:LINE.index("serving.launch") + 1] + ["serving.release"]
    assert order == [(what, name) for name in ran
                     for what in ("open", "close")]
    assert obstrace.current_drain() is None


# -- the steps inside a phase (PR 39) -------------------------------------------

SHARDS, SHARD_ROWS = 4, 8192
# the three branches of the one-chip drain and the two of the sharded one
DRAINS = ["ladder", "exact", "flat", "sharded", "sharded-flat"]


@pytest.fixture(scope="module")
def sharded():
    """The same kind of model row-sharded over four of tier-1's virtual
    CPU devices: under ``ladder`` a shard's 8,192 rows run the two-phase
    scan inside the SPMD program, without it the flat body."""
    rng = np.random.default_rng(39)
    rows = SHARDS * SHARD_ROWS
    m = ALSServingModel(FEATURES, implicit=True, dtype="float32",
                        item_shards=SHARDS)
    m.Y.bulk_load([f"i{j}" for j in range(rows)],
                  rng.standard_normal((rows, FEATURES)).astype(np.float32))
    return m


def _drain_of(kind, request):
    """(model, excluded ids a job, steps by phase, the counts each step
    carries) for a three-job drain of that kind."""
    if kind in ("ladder", "exact", "sharded"):
        request.getfixturevalue("ladder")
    # 50 known items fetch k = 64, which the toy store's 64 blocks cannot
    # select for: the exact scan, as the primary path
    known = 50 if kind == "exact" else 0
    k = 64 if kind == "exact" else 8
    window = 8 * FEATURES * 4             # an [8] window of float32 queries
    result = 8 * k * 4                    # its scores, or its rows
    if kind.startswith("sharded"):
        # scores, rows and on the ladder a certificate a (shard, row)
        fetch = {"arrays": 3, "bytes": 2 * result + SHARDS * 8} \
            if kind == "sharded" else {"arrays": 2, "bytes": 2 * result}
        return (request.getfixturevalue("sharded"), known, SHARDED_STEPS,
                {"serving.upload": {"windows": 1,
                                    "bytes": window * SHARDS},
                 "serving.launch": {"programs": 1},
                 "serving.device_wait": {}, "serving.fetch": fetch})
    fetch = {"arrays": 3, "bytes": 2 * result + 8} if kind == "ladder" \
        else {"arrays": 2, "bytes": 2 * result}
    return (request.getfixturevalue("model"), known, STEPS,
            {"serving.upload": {"windows": 1, "bytes": window},
             "serving.launch": {"programs": 1},
             "serving.device_wait": {}, "serving.fetch": fetch})


def _traced_drain(batcher, tracer, model, known, sampled=3):
    """Three jobs in one drain on this thread; returns (jobs, each
    sampled job's request span and its trace's spans by name)."""
    jobs, requests = _drain(batcher, tracer, model, 3, sampled, known)
    return jobs, [(req, _by_name(tracer.spans_for(req.trace_id)))
                  for req in requests]


@pytest.mark.parametrize("kind", DRAINS)
def test_steps_land_under_their_phase_in_every_sampled_job(kind, traced,
                                                           request):
    model, known, steps, counts = _drain_of(kind, request)
    batcher, tracer = traced
    _, traces = _traced_drain(batcher, tracer, model, known)
    stamps = set()
    for req, spans in traces:
        (execute,) = spans["serving.device_execute"]
        # the request's children and the drain's phases are who they were
        assert sorted(s["name"] for ss in spans.values() for s in ss
                      if s["parent_id"] == req.span_id) == [
            "serving.device_execute", "serving.queue_wait"]
        assert sorted(s["name"] for ss in spans.values() for s in ss
                      if s["parent_id"] == execute["span_id"]) \
            == sorted(PHASES)
        for phase, names in steps.items():
            (parent,) = spans[phase]
            for name in names:
                (step,) = spans[name]
                assert step["parent_id"] == parent["span_id"]
                assert step["trace_id"] == req.trace_id
                assert step["status"] == "ok"
                assert step["attrs"] == counts[name], name
        assert sum(len(ss) for ss in spans.values()) \
            == 2 + len(PHASES) + len(STEP_NAMES)
        stamps.add(tuple((spans[n][0]["start_ms"], spans[n][0]["duration_ms"])
                         for n in STEP_NAMES))
    # recorded once, replayed three times
    assert len(stamps) == 1
    # the scan keeps its attributes, whichever step was running when
    # they became known
    scan = traces[0][1]["serving.scan"][0]["attrs"]
    assert {"k", "ksel", "windows", "lane_rows", "real_rows"} <= set(scan)
    assert ("phase_b_row_share" in scan) == (kind in ("ladder", "sharded"))
    assert ("shards" in scan) == kind.startswith("sharded")


@pytest.mark.parametrize("kind", DRAINS)
def test_steps_tile_their_phase(kind, traced, request):
    """From the first step's start to the last one's end a phase's steps
    follow each other with no hole, and the last ends with the phase:
    the phase less the sliver before its first step (stamps are rounded
    to a microsecond)."""
    model, known, steps, _ = _drain_of(kind, request)
    batcher, tracer = traced
    _, ((_, spans),) = _traced_drain(batcher, tracer, model, known,
                                     sampled=1)
    for phase, names in steps.items():
        (parent,) = spans[phase]
        end = parent["start_ms"] + parent["duration_ms"]
        at, covered = None, 0.0
        for name in names:
            (step,) = spans[name]
            if at is None:
                assert step["start_ms"] >= parent["start_ms"] - 0.002
            else:
                assert step["start_ms"] == pytest.approx(at, abs=0.002)
            at = step["start_ms"] + step["duration_ms"]
            covered += step["duration_ms"]
        assert at == pytest.approx(end, abs=0.002)
        sliver = spans[names[0]][0]["start_ms"] - parent["start_ms"]
        assert covered == pytest.approx(parent["duration_ms"] - sliver,
                                        abs=0.002 * (len(names) + 1))
    # the scan's sliver is the one mark: its steps ARE the scan
    (scan,) = spans["serving.scan"]
    first = spans[steps["serving.scan"][0]][0]
    assert first["start_ms"] - scan["start_ms"] < 0.5


@pytest.mark.parametrize("kind", DRAINS)
def test_one_annotation_is_open_at_a_time(kind, traced, request, notes):
    """The profiler's line is FLAT: a phase's annotation closes when its
    first step opens, a step's when the next step or phase opens, so an
    idle gap of the device takes the name of the piece of work that
    covers most of it and never the enclosing phase's."""
    model, known, steps, _ = _drain_of(kind, request)
    _, order = notes
    batcher, tracer = traced
    _traced_drain(batcher, tracer, model, known, sampled=1)
    mine = [e for e in order if not e[1].startswith("serving.await")]
    line = [name for phase in PHASES
            for name in [phase, *steps.get(phase, [])]] + ["serving.release"]
    assert mine == [(what, name) for name in line
                    for what in ("open", "close")]


@pytest.mark.parametrize("step, breaks", [
    ("serving.launch", (ALSServingModel, "_dispatch_twophase")),
    ("serving.device_wait", (jax, "block_until_ready")),
    ("serving.fetch", (jax, "device_get"))])
def test_a_failing_step_closes_itself_and_its_phase_as_errors(
        step, breaks, model, traced, ladder, monkeypatch, notes):
    _, order = notes

    def broken(*args, **kwargs):
        raise RuntimeError("device lost")

    monkeypatch.setattr(*breaks, broken)
    batcher, tracer = traced
    req = tracer.begin_request("serving.request")
    tracer._swap(None)
    job = _Job(model, 5, _vectors(1)[0], set(),
               trace_ctx=(req.trace_id, req.span_id))
    batcher._dispatch([job])
    assert isinstance(job.error, RuntimeError)
    spans = _by_name(tracer.spans_for(req.trace_id))
    ran = LINE[:LINE.index(step) + 1]
    assert sorted(spans) == sorted(
        ran + ["serving.queue_wait", "serving.device_execute"])
    status = {name: spans[name][0]["status"] for name in ran}
    assert status == dict({name: "ok" for name in ran},
                          **{"serving.scan": "error", step: "error"})
    (scan,), (last,) = spans["serving.scan"], spans[step]
    # ... at the same instant, the failure's
    assert last["start_ms"] + last["duration_ms"] == pytest.approx(
        scan["start_ms"] + scan["duration_ms"], abs=0.002)
    assert [e for e in order
            if not e[1].startswith("serving.await")] == [
        (what, name) for name in ran + ["serving.release"]
        for what in ("open", "close")]
    assert obstrace.current_drain() is None


@pytest.mark.parametrize("kind", DRAINS)
def test_without_a_recorder_nothing_waits_for_the_device_and_answers_match(
        kind, traced, request, monkeypatch):
    """``block_until_ready`` is what tells the wait for the device from
    the copy, and a recorder's alone: a drain with none makes the ONE
    ``device_get`` it made before the steps existed, and returns the
    traced drain's answers bit for bit."""
    model, known, _, _ = _drain_of(kind, request)
    batcher, tracer = traced
    jobs, _ = _traced_drain(batcher, tracer, model, known)

    def never(*args, **kwargs):
        raise AssertionError("an untraced drain waited for the device")

    fetches = []
    real = jax.device_get
    monkeypatch.setattr(jax, "block_until_ready", never)
    monkeypatch.setattr(jax, "device_get",
                        lambda x: fetches.append(1) or real(x))
    got = model.top_n_batch([5] * 3, np.stack([j.vector for j in jobs]),
                            [j.exclude for j in jobs])
    assert got == [j.result for j in jobs]
    assert len(fetches) == 1
    plain = TopNBatcher(pipeline=1)
    try:
        untraced = [_Job(model, 5, j.vector, j.exclude) for j in jobs]
        assert plain._dispatch(untraced) == 3
    finally:
        plain.close()
    assert [j.result for j in untraced] == [j.result for j in jobs]
    assert len(fetches) == 2


class _Ticks:
    """A clock that moves a millisecond a reading."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 0.001
        return self.now


def test_a_recorder_replays_its_steps_under_their_phases(monkeypatch,
                                                         notes):
    """``DrainPhases`` alone, on a clock that ticks once a reading: one
    reading a ``mark`` or ``step``; ``annotate`` reaches the running
    PHASE whichever step runs; a step with no phase running is a phase."""
    _, order = notes
    monkeypatch.setattr(obstrace.clockmod, "monotonic", _Ticks())
    with obstrace.DrainPhases() as rec:
        rec.step("serving.early")                    # 1 ms: a phase
        rec.mark("serving.scan", k=8)                # 2
        rec.step("serving.launch", programs=2)       # 3
        rec.annotate(lane_rows=1)
        rec.step("serving.device_wait")              # 4
        rec.annotate(lsh_steps=7)
        rec.mark("serving.decode", rows=3)           # 5
    assert [(p[0], round(p[1] * 1e3), round(p[2] * 1e3), p[3], p[5])
            for p in rec._phases] == [
        ("serving.early", 1, 2, {}, None),
        ("serving.scan", 2, 5, {"k": 8, "lane_rows": 1, "lsh_steps": 7},
         None),
        ("serving.launch", 3, 4, {"programs": 2}, 1),
        ("serving.device_wait", 4, 5, {}, 1),
        ("serving.decode", 5, 6, {"rows": 3}, None)]
    assert order == [(what, p[0]) for p in rec._phases
                     for what in ("open", "close")]
    tracer = Tracer("svc", sample_ratio=1.0)
    for trace in ("a" * 32, "b" * 32):
        rec.replay(tracer, trace, "e" * 16)
        by = {s["name"]: s for s in tracer.spans_for(trace)}
        named = {s["span_id"]: n for n, s in by.items()}
        assert {n: named.get(s["parent_id"]) for n, s in by.items()} == {
            "serving.early": None, "serving.scan": None,
            "serving.launch": "serving.scan",
            "serving.device_wait": "serving.scan", "serving.decode": None}
        assert by["serving.scan"]["duration_ms"] == 3.0
        assert by["serving.launch"]["duration_ms"] == 1.0


def test_record_span_hands_back_the_id_it_made():
    tracer = Tracer("svc", sample_ratio=1.0)
    ctx = ("f" * 32, "e" * 16)
    span_id = tracer.record_span("serving.device_execute", ctx, 1.0, 2.0)
    tracer.record_span("serving.scan", (ctx[0], span_id), 1.2, 1.8)
    parent, child = tracer.spans_for(ctx[0])
    assert parent["span_id"] == span_id == child["parent_id"]
    assert tracer.record_span("serving.scan", None, 1.0, 2.0) is None


# -- the program names the device metrics read --------------------------------

TWOPHASE_BUILDS = ["_batch_top_n_twophase_pallas",
                   "_batch_top_n_twophase_pallas_fold",
                   "_batch_top_n_twophase_pallas_i8",
                   "_batch_top_n_twophase_pallas_i8_fold",
                   "_batch_top_n_twophase_kernel"]
EXACT_SCAN = "_batch_top_n_chunked_kernel"
PRUNED_EXACT_SCAN = "_batch_top_n_pruned_exact_kernel"


def _scan_programs_called_by(method) -> set[str]:
    tree = ast.parse(textwrap.dedent(inspect.getsource(method)))
    return {n.func.id for n in ast.walk(tree)
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
            and n.func.id.startswith("_batch_top_n")}


def test_the_exact_ladder_enqueues_only_the_named_programs():
    """Every build ``_dispatch_kind`` can enqueue for the exact ladder
    (the IVF kind, ``ivf.batch_top_n_ivf``, is outside the contract) is
    one of the two-phase programs, and the fallback is the exact scan:
    ``benchmark/layers/kernel.twophase_ms.json`` matches the former by
    name in the device trace, and the latter's name is what a metric of
    the exact scan would match."""
    assert _scan_programs_called_by(ALSServingModel._dispatch_kind) \
        == set(TWOPHASE_BUILDS)
    # ... and nothing is enqueued but through it (a sharded model's
    # builds are the SPMD program of parallel/serving_dist.py, named
    # ``sharded_twophase_top_k``: tests/test_sharded_twophase.py)
    assert _scan_programs_called_by(ALSServingModel._dispatch_twophase) \
        == set()
    assert _scan_programs_called_by(ALSServingModel._sharded_top_n_batch) \
        == set()
    # the exact scan, over the whole store or (a model under LSH) over
    # a window's candidates, is enqueued in one place
    assert _scan_programs_called_by(ALSServingModel._exact_scan) \
        == {EXACT_SCAN, PRUNED_EXACT_SCAN}
    assert not any("twophase" in name for name in
                   _scan_programs_called_by(ALSServingModel.top_n_batch)
                   | _scan_programs_called_by(ALSServingModel._exact_scan))


@pytest.mark.parametrize("name, pattern",
                         [(n, "twophase") for n in TWOPHASE_BUILDS]
                         + [(EXACT_SCAN, "chunked_kernel"),
                            (PRUNED_EXACT_SCAN, "pruned_exact_kernel")])
def test_each_scan_program_is_jitted_under_its_own_name(name, pattern):
    """The device trace names a program ``jit_<function name>(<hash>)``:
    the wrapper must be ONE jitted function carrying that name."""
    fn = getattr(sm, name)
    assert hasattr(fn, "lower"), f"{name} is not a jitted function"
    assert fn.__name__ == name and pattern in name
    assert ("twophase" in name) != ("chunked_kernel" in name
                                    or "exact_kernel" in name)


def _toy_pruning(rows: int):
    """A pruned window's side inputs over ``rows`` zero rows: 3
    hyperplanes, every step in bucket 0."""
    n_steps = rows // sm._PA_TILE
    return sm.Pruning(jnp.zeros((n_steps,), jnp.int32),
                      jnp.full((n_steps,), sm._PA_TILE, jnp.int32),
                      jnp.ones((3, FEATURES), jnp.float32))


@pytest.mark.parametrize("pruned", [False, True], ids=["exact", "pruned"])
@pytest.mark.parametrize("width", sm._WINDOW_LADDER)
def test_the_pallas_build_is_one_named_program_at_every_ladder_width(
        width, pruned):
    """Whichever way phase A lays the window's scores (the store's rows
    on the lanes for the ladder's 8 and 32, the queries there for 256),
    kernel, transposition if any and phase B are ONE jitted program
    under the name the device metrics match; and a model under LSH
    enqueues the same two names (ISSUE 36: the plan of the steps to
    visit, the pass over them and phase B are one program whose name
    holds ``twophase``)."""
    rows, bs, k = 4 * sm._PA_TILE, 128, 8
    ksel = sm._block_ksel(k, rows, bs)
    Y = jnp.zeros((rows, FEATURES), jnp.float32)
    Q = jnp.zeros((width, FEATURES), jnp.float32)
    active = jnp.ones((rows,), bool)
    prune = _toy_pruning(rows) if pruned else None
    text = sm._batch_top_n_twophase_pallas.lower(
        Y, Q, sm._penalty_kernel(active, bs), active, prune,
        np.int32(width), k, bs, ksel, 1 if pruned else 0,
        interpret=True).as_text()
    assert "@jit__batch_top_n_twophase_pallas" in text[:200]
    assert text.count("func.func public") == 1
    assert sm._scores_rows_on_lanes(width) == (width < 128)
    if pruned:
        scan = sm._batch_top_n_twophase_kernel.lower(
            Y, Q, active, prune, np.int32(width), k, 0, bs, ksel,
            1).as_text()
        assert "@jit__batch_top_n_twophase_kernel" in scan[:200]
        assert scan.count("func.func public") == 1


@pytest.mark.parametrize("k, width, rows_at_once", [
    (8, 8, 8), (64, 8, 8), (64, 128, 128), (64, 128, 2)])
def test_the_lowered_programs_carry_the_names_the_trace_shows(
        k, width, rows_at_once, monkeypatch):
    """... for the widths a wide fetch selects too, where phase B loops
    over a narrow window's requests and where it runs a wide window in
    row groups: either is a loop INSIDE the one two-phase program, not
    programs of their own."""
    # a shape nothing else traces: the budget is read when a program
    # is traced, and a cached trace would keep the one it was made with
    rows, bs = 16384 + 256 * (k + width + rows_at_once), 8
    ksel = sm._block_ksel(k, rows, bs)
    assert ksel == max(sm._BLOCK_KSEL, 2 * k)
    monkeypatch.setattr(sm, "_PHASE_B_GATHER_BYTES",
                        rows_at_once * ksel * bs * FEATURES * 4)
    Y = jnp.zeros((rows, FEATURES), jnp.float32)
    Q = jnp.zeros((width, FEATURES), jnp.float32)
    active = jnp.ones((rows,), bool)
    two = sm._batch_top_n_twophase_kernel.lower(
        Y, Q, active, None, np.int32(width), k, 256, bs, ksel)
    assert "@jit__batch_top_n_twophase_kernel" in two.as_text()[:200]
    # the floor width's program is as it was; a wider selection by a
    # narrow window, or by a narrow group of a wide one, reads the
    # block maxima row-major (``_selects_row_major``)
    assert ("@LayoutConstraint" in two.as_text()) \
        == (k == 64 and rows_at_once < 128)
    # phase A's lax.scan is one loop; a narrow window's requests
    # (``_rescores_requests``) or a wide one's row groups a second
    assert two.as_text().count("stablehlo.while") \
        == (1 if rows_at_once == width == 128 else 2)
    exact = sm._batch_top_n_chunked_kernel.lower(
        Y, Q, active, k, 256)
    assert "@jit__batch_top_n_chunked_kernel" in exact.as_text()[:200]
