"""Request micro-batcher tests (SURVEY §2.14 P6: concurrent requests
coalesce into one device dispatch; reference contrast:
ServingLayer.java:235 thread-pool fan-out)."""

import functools
import threading
import time
import urllib.request

import numpy as np
import pytest

from oryx_tpu.api.serving import StaticModelManager
from oryx_tpu.app.als.serving_model import ALSServingModel
from oryx_tpu.common.config import from_dict
from oryx_tpu.lambda_rt.serving import ServingLayer
from oryx_tpu.serving.batcher import TopNBatcher


def _small_model(users=6, items=40, features=8, seed=5):
    rng = np.random.default_rng(seed)
    model = ALSServingModel(features=features, implicit=True)
    for u in range(users):
        model.set_user_vector(f"u{u}",
                              rng.standard_normal(features).astype(np.float32))
    for i in range(items):
        model.set_item_vector(f"i{i}",
                              rng.standard_normal(features).astype(np.float32))
    return model


def test_batcher_matches_single_request_path():
    model = _small_model()
    batcher = TopNBatcher()
    try:
        for u in range(6):
            vec = model.get_user_vector(f"u{u}")
            got = batcher.top_n(model, 5, vec, exclude={"i0", "i3"})
            want = model.top_n(5, user_vector=vec, exclude={"i0", "i3"})
            assert [i for i, _ in got] == [i for i, _ in want]
            assert np.allclose([v for _, v in got], [v for _, v in want])
    finally:
        batcher.close()


def test_batcher_concurrent_correctness_and_coalescing():
    model = _small_model()

    in_dispatch = threading.Event()
    release = threading.Event()

    class GatedModel:
        """Delegate that stalls the first dispatch so later submissions
        provably pile up into one drain."""

        def __init__(self, inner):
            self._inner = inner
            self._first = True

        def top_n_batch(self, how_many, vectors, exclude):
            if self._first:
                self._first = False
                in_dispatch.set()
                release.wait(5.0)
            return self._inner.top_n_batch(how_many, vectors, exclude)

    gated = GatedModel(model)
    batcher = TopNBatcher(pipeline=1)  # single drain: coalescing is provable
    results: dict[int, list] = {}

    def submit(idx, uid, how_many):
        results[idx] = batcher.top_n(gated, how_many,
                                     model.get_user_vector(uid))

    try:
        first = threading.Thread(target=submit, args=(0, "u0", 3))
        first.start()
        assert in_dispatch.wait(5.0)
        rest = [threading.Thread(target=submit, args=(i, f"u{i % 6}", 2 + i))
                for i in range(1, 9)]
        for t in rest:
            t.start()
        # the 8 jobs must all be pending before the gate opens
        deadline = time.time() + 5.0
        while len(batcher._pending) < 8 and time.time() < deadline:
            time.sleep(0.005)
        release.set()
        first.join(5.0)
        for t in rest:
            t.join(5.0)
    finally:
        release.set()
        batcher.close()

    assert len(results) == 9
    for i in range(1, 9):
        uid, how_many = f"u{i % 6}", 2 + i
        want = model.top_n(how_many,
                           user_vector=model.get_user_vector(uid))
        assert [x for x, _ in results[i]] == [x for x, _ in want]
        assert np.allclose([v for _, v in results[i]],
                           [v for _, v in want], rtol=1e-4)
    # everything after the gate went through as one coalesced drain
    assert max(batcher.batch_sizes) == 8


def test_batcher_propagates_errors():
    class Boom:
        def top_n_batch(self, *a, **k):
            raise ValueError("boom")

    batcher = TopNBatcher()
    try:
        with pytest.raises(ValueError, match="boom"):
            batcher.top_n(Boom(), 3, np.zeros(4, np.float32))
    finally:
        batcher.close()


def test_top_n_batch_empty_batch():
    model = _small_model()
    assert model.top_n_batch(5, np.zeros((0, 8), np.float32)) == []


def test_batcher_degrades_gracefully_after_close():
    batcher = TopNBatcher()
    batcher.close()
    model = _small_model()
    vec = model.get_user_vector("u0")
    got = batcher.top_n(model, 3, vec)
    want = model.top_n(3, user_vector=vec)
    assert [i for i, _ in got] == [i for i, _ in want]


class BatcherMockManager(StaticModelManager):
    model = None


def test_http_recommend_goes_through_batcher():
    BatcherMockManager.model = _small_model(users=20, items=100)
    cfg = from_dict({
        "oryx.serving.model-manager-class":
            "tests.test_batcher.BatcherMockManager",
        "oryx.serving.application-resources": "oryx_tpu.serving.als",
        "oryx.input-topic.broker": None,
        "oryx.input-topic.partitions": 1,
        "oryx.input-topic.message.topic": None,
        "oryx.update-topic.broker": None,
        "oryx.update-topic.message.topic": None,
    })
    layer = ServingLayer(cfg, port=0)
    layer.start()
    try:
        base = f"http://127.0.0.1:{layer.port}"
        errs = []

        def hit(u):
            try:
                with urllib.request.urlopen(
                        f"{base}/recommend/u{u}?howMany=4", timeout=10) as r:
                    assert r.status == 200
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=hit, args=(u % 20,))
                   for u in range(40)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(15.0)
        assert not errs
        # the shared batcher saw the traffic
        assert sum(layer.top_n_batcher.batch_sizes) == 40
    finally:
        layer.close()


def test_pacing_coalesces_under_slow_device():
    """When each dispatch is slow (big model), free dispatcher threads
    must NOT shred the queue into minimal batches: pacing at the
    measured service rate makes concurrent requests coalesce."""
    import time as _time

    class SlowModel:
        def __init__(self, model):
            self.model = model

        def top_n_batch(self, how_many, vectors, exclude=None):
            _time.sleep(0.05)  # 50 ms per dispatch, like a 5M-item scan
            return self.model.top_n_batch(how_many, vectors, exclude)

    model = _small_model(items=50, features=4)
    slow = SlowModel(model)
    batcher = TopNBatcher(pipeline=32)
    try:
        results = [None] * 80
        def call(i):
            results[i] = batcher.top_n(
                slow, 3, np.asarray([1, 0, 0, 0], np.float32) * (i + 1))
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(80)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(r is not None and len(r) == 3 for r in results)
        # without pacing, 32 idle dispatchers produce ~80 batches of ~1;
        # with pacing the tail coalesces into service-interval drains
        sizes = batcher.batch_sizes
        assert sum(sizes) == 80
        assert max(sizes) >= 4, sizes
        assert len(sizes) <= 40, sizes
    finally:
        batcher.close()


def test_pacing_relearns_after_hot_swap():
    """The service-rate estimate must relearn DOWNWARD when a big model
    is hot-swapped for a small one — otherwise pacing stays locked at
    the old model's interval and serializes dispatches forever."""
    import time as _time

    class SerialDevice:
        """Device-like: executions serialize behind one lock."""

        def __init__(self, model):
            self.model = model
            self.exec_s = 0.06
            self.lock = threading.Lock()

        def top_n_batch(self, hm, v, e=None):
            with self.lock:
                _time.sleep(self.exec_s)
            return self.model.top_n_batch(hm, v, e)

    model = _small_model(items=50, features=4)
    mm = SerialDevice(model)
    batcher = TopNBatcher(pipeline=8)
    try:
        def load(seconds, workers=12):
            stop = time.monotonic() + seconds
            def w():
                while time.monotonic() < stop:
                    batcher.top_n(mm, 3, np.zeros(4, np.float32))
            ts = [threading.Thread(target=w) for _ in range(workers)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()

        load(2.0)
        ewma_slow = batcher._exec_ewma
        assert ewma_slow > 0.02, ewma_slow  # learned the service time
        mm.exec_s = 0.001
        load(1.2)
        assert batcher._exec_ewma < ewma_slow / 3, \
            (ewma_slow, batcher._exec_ewma)
    finally:
        batcher.close()


def test_metrics_surface_exposes_batcher_and_fallback_state():
    """/metrics reports the pacing/batching internals and the streaming
    top-k certificate-fallback counter."""
    import json as _json
    import urllib.request

    BatcherMockManager.model = _small_model(users=4, items=30)
    cfg = from_dict({
        "oryx.serving.model-manager-class":
            "tests.test_batcher.BatcherMockManager",
        "oryx.serving.application-resources": "oryx_tpu.serving.als",
        "oryx.input-topic.broker": None,
        "oryx.input-topic.partitions": 1,
        "oryx.input-topic.message.topic": None,
        "oryx.update-topic.broker": None,
        "oryx.update-topic.message.topic": None,
    })
    layer = ServingLayer(cfg, port=0)
    layer.start()
    try:
        base = f"http://127.0.0.1:{layer.port}"
        for u in range(4):
            with urllib.request.urlopen(f"{base}/recommend/u{u}",
                                        timeout=10) as r:
                assert r.status == 200
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            m = _json.loads(r.read())
        sb = m["scoring_batcher"]
        assert sb["dispatches"] >= 4 and sb["mean_recent_batch"] >= 1
        assert sb["service_time_ms"] >= 0
        assert sb["in_flight_target"] >= 1
        assert m["model_metrics"]["twophase_fallbacks"] == 0
        assert m["model_metrics"]["items"] == 30
    finally:
        layer.close()


def test_close_submit_race_degrades_to_unbatched():
    """Shutdown race (batcher.top_n's stopped branch): keep-alive
    handler threads outliving close() must get a correct unbatched
    answer, never a 500."""
    model = _small_model()
    batcher = TopNBatcher(pipeline=2)
    batcher.close()
    vec = model.get_user_vector("u0")
    got = batcher.top_n(model, 4, vec, exclude={"i1"})
    want = model.top_n(4, user_vector=vec, exclude={"i1"})
    assert [i for i, _ in got] == [i for i, _ in want]


def test_concurrent_close_and_submit_never_errors():
    """Hammer submits from many threads while close() lands mid-stream:
    every request must complete correctly through either the batched or
    the degraded path."""
    model = _small_model()
    batcher = TopNBatcher(pipeline=4)
    errors: list[BaseException] = []
    results: list[int] = []
    start = threading.Event()

    def worker(uid):
        vec = model.get_user_vector(uid)
        start.wait(5.0)
        for _ in range(20):
            try:
                got = batcher.top_n(model, 3, vec)
                assert len(got) == 3
                results.append(1)
            except BaseException as e:  # noqa: BLE001 — recorded
                errors.append(e)

    threads = [threading.Thread(target=worker, args=(f"u{i % 6}",))
               for i in range(8)]
    for t in threads:
        t.start()
    start.set()
    # close lands while workers are mid-flight
    batcher.close()
    for t in threads:
        t.join(10.0)
    assert not errors
    assert len(results) == 8 * 20


def test_deadline_expired_at_submit_is_rejected():
    from oryx_tpu.resilience.policy import Deadline, DeadlineExceeded

    model = _small_model()
    batcher = TopNBatcher()
    try:
        with pytest.raises(DeadlineExceeded):
            batcher.top_n(model, 3, model.get_user_vector("u0"),
                          deadline=Deadline.after(0.0))
        assert batcher.stats()["deadline_rejects"] == 1
        # an ample deadline is untouched
        got = batcher.top_n(model, 3, model.get_user_vector("u0"),
                            deadline=Deadline.after(30.0))
        assert len(got) == 3
    finally:
        batcher.close()


def test_deadline_expiring_while_queued_is_shed_at_dispatch():
    """A job whose budget runs out while it waits behind a stalled
    dispatch is shed (DeadlineExceeded) instead of being scored."""
    from oryx_tpu.resilience.policy import Deadline, DeadlineExceeded

    model = _small_model()
    in_dispatch = threading.Event()
    release = threading.Event()

    class GatedModel:
        def __init__(self, inner):
            self._inner = inner
            self._first = True

        def top_n_batch(self, how_many, vectors, exclude):
            if self._first:
                self._first = False
                in_dispatch.set()
                release.wait(10.0)
            return self._inner.top_n_batch(how_many, vectors, exclude)

    gated = GatedModel(model)
    batcher = TopNBatcher(pipeline=1)
    outcome: dict = {}

    def stalled_submit():
        outcome["first"] = batcher.top_n(
            gated, 3, model.get_user_vector("u0"))

    def doomed_submit():
        deadline = Deadline.after(0.05)
        try:
            batcher.top_n(gated, 3, model.get_user_vector("u1"),
                          deadline=deadline)
            outcome["second"] = "scored"
        except DeadlineExceeded:
            outcome["second"] = "shed"

    try:
        first = threading.Thread(target=stalled_submit)
        first.start()
        assert in_dispatch.wait(5.0)
        # valid at submit, expired by the time the drain dispatches
        second = threading.Thread(target=doomed_submit)
        second.start()
        deadline = time.monotonic() + 5.0
        while not batcher._pending and time.monotonic() < deadline:
            time.sleep(0.002)
        # hold the gate until the queued job's budget is provably gone
        expiry = time.monotonic() + 0.06
        while time.monotonic() < expiry:
            time.sleep(0.005)
        release.set()
        first.join(5.0)
        second.join(5.0)
    finally:
        release.set()
        batcher.close()

    assert len(outcome["first"]) == 3
    assert outcome["second"] == "shed"
    assert batcher.deadline_rejects >= 1


# -- binding late, and holding a drain for the callers just answered ---------

class _SerialDevice:
    """What a locally attached chip looks like from the batcher: calls
    run one after another, ``exec_s`` each (``exec_by_size``: by the
    requests in the call, where a wider window is a longer pass), and
    answer ``host_s`` later, a part of the call that holds nobody else
    up (upload, launch, fetch).  The first feature of every query is
    its arrival, in seconds since ``t0``, so the log says how long each
    request waited while the device was free."""

    def __init__(self, exec_s: float, overlapping: bool = False,
                 host_s: float = 0.0, exec_by_size: dict | None = None):
        self.exec_s, self.overlapping = exec_s, overlapping
        self.host_s, self.exec_by_size = host_s, exec_by_size or {}
        self.lock = threading.Lock()
        self.t0 = time.monotonic()
        self.calls: list[tuple[float, float, list[float]]] = []

    def vector(self) -> np.ndarray:
        return np.asarray([time.monotonic() - self.t0, 0, 0, 0], np.float32)

    def top_n_batch(self, how_many, vectors, exclude=None):
        if self.overlapping:
            time.sleep(self.exec_s)
        else:
            with self.lock:
                start = time.monotonic() - self.t0
                time.sleep(self.exec_by_size.get(len(vectors), self.exec_s))
                self.calls.append((start, time.monotonic() - self.t0,
                                   [float(v[0]) for v in vectors]))
            if self.host_s:
                time.sleep(self.host_s)
        return [[("i0", 1.0)] * h for h in how_many]

    def idle_waits(self) -> list[float]:
        """Per request, the seconds it was queued while the device had
        nothing to run: from its arrival, or the end of the program
        before its own, to the start of its own."""
        out, free_at = [], 0.0
        for start, end, arrivals in self.calls:
            out += [max(0.0, start - max(free_at, a)) for a in arrivals]
            free_at = end
        return out


def _closed_loop(batcher, device, callers: int, seconds: float,
                 turnaround=(0.0005, 0.003), delay=None) -> list[float]:
    """``callers`` threads, each sending its next request a seeded
    turnaround after its last answer; ``delay`` = (request numbers,
    seconds) holds caller 0 back before each of them.  Returns every
    request's wall."""
    import random

    walls: list[float] = []
    stop = time.monotonic() + seconds

    def caller(i):
        rng, sent = random.Random(i), 0
        while time.monotonic() < stop:
            sent += 1
            if delay and i == 0 and sent in delay[0]:
                time.sleep(delay[1])
            t = time.monotonic()
            assert len(batcher.top_n(device, 3, device.vector())) == 3
            walls.append(time.monotonic() - t)
            time.sleep(rng.uniform(*turnaround))

    threads = [threading.Thread(target=caller, args=(i,))
               for i in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + 10.0)
        assert not t.is_alive()
    return walls


# sizes of the drains before the batcher has seen two programs queue one
# behind the other and the callers have fallen into step
_WARM_IN = 8


def _on_a_host_that_keeps_time(test):
    """The closed-loop tests marked with this pin, to a few
    milliseconds, what the batcher does for callers whose threads run
    when they are due.  Beside tier-1's five other workers (and other
    people's, on a shared machine) a thread is sometimes woken
    milliseconds late, longer than the patience of a hold (2.6-3.8 ms
    here): a caller is then left behind, or a 10 ms pass takes 16, and
    the run says nothing of the batcher.  So a thread that sleeps a
    millisecond at a time clocks how late it was woken at worst; a run
    that fails on a host that was more than 3 ms late is made again,
    twice at most, and one that fails on a host that kept time fails
    at once.  The bounds themselves are the same for every run."""

    @functools.wraps(test)
    def run(*args, **kwargs):
        for last in (False, False, True):
            late, done = [0.0], threading.Event()

            def clock():
                while not done.is_set():
                    t = time.monotonic()
                    time.sleep(0.001)
                    late[0] = max(late[0], time.monotonic() - t - 0.001)

            watch = threading.Thread(target=clock, daemon=True)
            watch.start()
            try:
                return test(*args, **kwargs)
            except AssertionError:
                if last or late[0] <= 0.003:
                    raise
            finally:
                done.set()
                watch.join()

    return run


@pytest.mark.parametrize("callers, mean_batch", [(2, 1.9), (8, 7.0)])
def test_closed_loop_callers_share_one_pass(callers, mean_batch):
    """On a serial device every caller that is waiting rides in the one
    program: a request is one service time and the hold long, not a
    program of its own behind everybody else's."""
    device = _SerialDevice(0.05)
    batcher = TopNBatcher(pipeline=8, idle_wait_s=0.02)
    try:
        walls = _closed_loop(batcher, device, callers, 2.0)
        stats = batcher.stats()
        sizes = batcher.batch_sizes[_WARM_IN:]
    finally:
        batcher.close()
    assert stats["in_flight_target"] == 1, stats
    assert stats["depth_reason"] == "serial", stats
    assert stats["return_hold"] == "on" and stats["return_holds"] > 0, stats
    assert stats["return_hit_share"] > 0.8, stats
    assert sum(sizes) / len(sizes) >= mean_batch, sizes
    assert sorted(sizes)[len(sizes) // 2] == callers, sizes
    walls.sort()
    hold = stats["return_hold_ms"] / 1e3
    # one service time, the turnaround of the slowest caller and the
    # hold; two programs would be 0.1 s
    assert walls[len(walls) // 2] < 0.05 + 0.003 + hold + 0.01, \
        walls[len(walls) // 2]


@pytest.mark.parametrize("turnaround, mean_batch, whole", [
    ((0.0005, 0.009), 7.5, 0.85), ((0.008, 0.016), 7.0, 0.75)])
@_on_a_host_that_keeps_time
def test_eight_callers_that_return_one_after_another_all_ride(
        turnaround, mean_batch, whole):
    """Eight callers come back through a door that serves them one after
    another: spread over more than one patience (6.25 ms here), each
    close behind the one before.  The hold's clock runs from the last
    return, so the drain leaves when the stream has ended, with all
    eight; a clock from the first return cut its tail in most cycles,
    and the caller it cut paid a second program.  In the second case
    even the first is back later than one patience after the
    completion: a drain that could leave at the completion, because a
    caller left behind is waiting, counts its hold from the first
    return and not from the completion, or the one would leave alone
    and the seven come back to a running program, cycle after cycle."""
    device = _SerialDevice(0.05)
    batcher = TopNBatcher(pipeline=8, idle_wait_s=0.02)
    try:
        walls = _closed_loop(batcher, device, 8, 4.0, turnaround=turnaround)
        stats = batcher.stats()
        sizes = batcher.batch_sizes[_WARM_IN:]
    finally:
        batcher.close()
    assert stats["return_hold"] == "on", stats
    # 8.0 / 7.9 and next to none left behind on a quiet host (6.1 / 4.0
    # with the clock run from the first return: four drains in five
    # short of a caller, or two groups of four for good); a stall of a
    # loaded one cuts a caller off now and then, and the drain after it
    # waits for the seven and is whole again
    assert sum(sizes) / len(sizes) >= mean_batch, sizes
    assert sizes.count(8) >= whole * len(sizes), sizes
    assert stats["return_left_behind"] <= len(sizes) // 10 + 8, stats
    # in the order they completed: less the requests of the warm-in.
    # One cycle; with the first return's clock one request in five
    # took two
    walls = sorted(walls[8 * _WARM_IN:])
    assert walls[int(len(walls) * 0.95)] < 1.5 * 0.05, walls[-24:]


def _hold_by_hand(batcher, returns, out=8, waiting=1, first_back=True):
    """Drive one hold through its own steps, without threads, on stamps
    relative to the moment a drain first could leave: ``out`` callers
    are out, ``waiting`` requests wait, and one caller comes back at
    each of ``returns``.  ``first_back``: the drain can leave because
    the first caller is back (it is the one waiting); False: because a
    caller left behind waited for the completion, and nobody is back.
    Returns when the drain left and the note it left with."""
    t0, at = time.monotonic(), 0.0
    batcher._pending = [None] * waiting
    try:
        batcher._awaited = out
        batcher._last_return = t0 if first_back else None
        assert batcher._hold_locked(t0) > 0
        for back in returns:
            # the dispatcher sleeps until the hold runs out or a caller
            # is back, whichever comes first
            ends = at + batcher._hold_locked(t0 + at)
            if ends <= back:
                at = ends
                break
            at = back
            batcher._pending.append(None)
            batcher._return_locked(t0 + at)
        else:
            at = max(at, at + batcher._hold_locked(t0 + at))
        return at, batcher._bind_locked(t0 + at)
    finally:
        # the lock is the caller's: no dispatcher has seen these
        batcher._pending = []


def test_a_hold_ends_with_the_stream_of_returns_and_within_its_bound():
    """S = 16 ms: one patience is 2 ms, and the hold ends 4 ms after the
    first return at the latest."""
    from oryx_tpu.serving import batcher as batcher_mod

    batcher = TopNBatcher(pipeline=2)
    try:
        with batcher._cond:
            batcher._exec_ewma, batcher._exec_measured = 0.016, True
            # six of seven come back 0.3 ms apart, the last never: the
            # drain leaves one patience after the sixth, past the 2 ms
            # of a clock that ran from the first
            at, note = _hold_by_hand(
                batcher, [0.0003 * i for i in range(1, 7)], out=7)
            assert at == pytest.approx(0.0018 + 0.002)
            assert (note["renewals"], note["left_behind"]) == (6, 1)
            assert note["held_ms"] == pytest.approx(3.8, abs=0.01)
            # all back: gone at once
            at, note = _hold_by_hand(
                batcher, [0.0003 * i for i in range(1, 8)], out=7)
            assert at == pytest.approx(0.0021)
            assert (note["renewals"], note["left_behind"]) == (6, 0)
            # they trickle back just under one patience apart: every
            # return renews the clock, and the hold still ends a quarter
            # of the service time after it began
            at, note = _hold_by_hand(
                batcher, [0.0019 * i for i in range(1, 8)], out=7)
            assert at == pytest.approx(0.004)
            assert (note["renewals"], note["left_behind"]) == (2, 5)
            # nobody comes back at all: one patience
            at, note = _hold_by_hand(batcher, [], out=7)
            assert at == pytest.approx(0.002)
            assert (note["renewals"], note["left_behind"]) == (0, 7)
            # a caller left behind waits at the completion and nobody is
            # back: until one is there is no stream to see the end of,
            # and only the bound runs ...
            at, note = _hold_by_hand(batcher, [], out=7, first_back=False)
            assert at == pytest.approx(0.004)
            assert (note["renewals"], note["left_behind"]) == (0, 7)
            # ... and counts anew from the first return, as it does for
            # a drain that can leave because the first is back
            at, note = _hold_by_hand(
                batcher, [0.003 + 0.0004 * i for i in range(7)], out=7,
                first_back=False)
            assert at == pytest.approx(0.0054)
            assert (note["renewals"], note["left_behind"]) == (6, 0)
            at, note = _hold_by_hand(
                batcher, [0.003 + 0.0019 * i for i in range(7)], out=7,
                first_back=False)
            assert at == pytest.approx(0.003 + 0.004)
            assert (note["renewals"], note["left_behind"]) == (3, 4)
            # many wait for one: a patience is worth no more than the
            # service time over the callers that pay it
            at, note = _hold_by_hand(batcher, [0.0001], out=2, waiting=31)
            assert at == pytest.approx(0.0001 + 0.016 / 32)
            assert batcher.stats()["return_left_behind"] \
                == 1 + 5 + 7 + 7 + 4 + 1
            # the score: a hold is scored on those who were out when
            # the first was back.  Where that one was the only one out
            # (an open loop's next arrival, or the other of two callers)
            # there is nothing to score, and the next completion holds
            # again to see; where nobody came at all it is a miss
            probe = batcher_mod._HOLD_PROBE_EVERY
            batcher._hit_share, batcher._since_hold = 0.4, probe
            _hold_by_hand(batcher, [0.001], out=1, first_back=False)
            assert (batcher._hit_share, batcher._since_hold) == (0.4, probe)
            _hold_by_hand(batcher, [], out=1, first_back=False)
            assert batcher._hit_share == pytest.approx(0.36)
            assert batcher._since_hold == 0
            batcher._since_hold = probe
            _hold_by_hand(batcher, [0.001, 0.0015], out=2,
                          first_back=False)
            assert batcher._hit_share == pytest.approx(0.36 + 0.064)
            assert batcher._since_hold == probe
    finally:
        batcher.close()


@_on_a_host_that_keeps_time
def test_a_delayed_caller_is_back_in_step_within_two_programs():
    device = _SerialDevice(0.05)
    batcher = TopNBatcher(pipeline=8, idle_wait_s=0.02)
    try:
        # past the 6.25 ms hold, inside the other caller's program
        _closed_loop(batcher, device, 2, 2.5, delay=((16,), 0.03))
        sizes = batcher.batch_sizes[_WARM_IN:]
    finally:
        batcher.close()
    assert 1 in sizes, sizes          # the pair did fall out of step
    alone = 0
    for n in sizes:
        alone = alone + 1 if n == 1 else 0
        assert alone <= 2, sizes      # and never stayed there
    assert sizes[-3:-1] == [2, 2], sizes


def test_a_lone_closed_loop_caller_is_never_held():
    device = _SerialDevice(0.03)
    batcher = TopNBatcher(pipeline=8, idle_wait_s=0.02)
    try:
        # two callers first, so that the device is known to be serial
        # and the hold is armed
        _closed_loop(batcher, device, 2, 0.6)
        assert batcher.stats()["depth_reason"] == "serial"
        assert batcher.stats()["return_hold_ms"] > 3.0
        # the last pair's second caller never comes back: one miss
        batcher.top_n(device, 3, device.vector())
        holds = batcher.return_holds
        walls = _closed_loop(batcher, device, 1, 0.5)
        assert batcher.return_holds == holds
        walls.sort()
        assert len(walls) >= 10 and walls[len(walls) // 2] < 0.03 + 0.01, \
            walls
    finally:
        batcher.close()


@pytest.mark.parametrize("exec_s, rate, seconds", [
    (0.01, 20.0, 4.0), (0.015, 100.0, 3.0), (0.015, 200.0, 3.0),
    (0.015, 400.0, 2.5)])
def test_open_loop_arrivals_switch_the_hold_off(exec_s, rate, seconds):
    """Seeded Poisson arrivals, from a fifth of what the device serves
    one by one to six a program: whoever was just answered does not come
    back, and the strangers that arrive inside a hold (they renew its
    clock like a returning caller would) are too few beside those the
    program before released, because the hold is bounded by a quarter
    of the service time from the first of them on, and as long before
    it.  The hit share falls, the hold goes off but for its probes, what
    it cost a request is well under one patience, and no hold outlasts
    its bound."""
    import random

    from oryx_tpu.obs.trace import Tracer

    tracer = Tracer("serving", sample_ratio=1.0, max_traces=8192)
    device = _SerialDevice(exec_s)
    batcher = TopNBatcher(pipeline=8, tracer=tracer)
    rng, due, t = random.Random(28), [], 0.0
    while t < seconds:
        t += rng.expovariate(rate)
        due.append(t)

    def one():
        req = tracer.begin_request("serving.request")
        assert len(batcher.top_n(device, 3, device.vector())) == 3
        tracer.end_request(req, 200)

    try:
        threads = []
        for d in due:
            time.sleep(max(0.0, device.t0 + d - time.monotonic()))
            threads.append(threading.Thread(target=one))
            threads[-1].start()
        for th in threads:
            th.join(10.0)
            assert not th.is_alive()
        stats = batcher.stats()
    finally:
        batcher.close()
    assert stats["depth_reason"] == "serial", stats
    assert stats["return_hold"] == "off", stats
    assert stats["return_hit_share"] < 0.5, stats
    # off, a drain holds once in 32 completions: far fewer than there
    # were requests behind a running program
    assert 7 <= stats["return_holds"] <= len(due) // 5, stats
    waited = sorted(device.idle_waits())
    assert len(waited) == len(due)
    # less the three worst: a stall of the test's host is no hold
    mean = sum(waited[:-3]) / len(waited[:-3])
    assert mean < stats["return_hold_ms"] / 1e3, (mean, stats)
    # one value a held drain (every request of a drain carries its note)
    held = sorted({s["attrs"]["held_ms"]
                   for spans in tracer.traces_snapshot(limit=8192).values()
                   for s in spans if s["name"] == "serving.queue_wait"
                   and s["attrs"]["held_ms"] > 0})
    assert len(held) >= 7, held
    # a quarter of the service time (read at the end, a little over the
    # program) for the first arrival, as much again from it on, and the
    # wake-up of a loaded host; the bound itself is pinned to the
    # microsecond, without threads, above
    bound_ms = stats["service_time_ms"] / 2 + 3.0
    assert held[len(held) * 9 // 10 - 1] <= bound_ms, (held, stats)


def test_an_overlapping_device_keeps_its_deep_pipeline():
    """Where device calls overlap, a second program in flight hides
    nearly all of the first: no binding late."""
    device = _SerialDevice(0.03, overlapping=True)
    batcher = TopNBatcher(pipeline=8)
    try:
        _closed_loop(batcher, device, 6, 0.6, turnaround=(0.001, 0.02))
        stats = batcher.stats()
    finally:
        batcher.close()
    assert stats["depth_reason"] == "pipelined", stats
    assert stats["in_flight_target"] > 1, stats
    assert stats["overlap_share"] > 0.5, stats
    # two completion gaps are a fraction of one 30 ms call
    assert stats["cycle_behind_ms"] < stats["cycle_shared_ms"], stats
    assert stats["cycle_shared_ms"] >= 30.0, stats


def _queue_waits_of_traced_callers(device, callers=2):
    """A second of closed-loop callers with every request sampled;
    returns (their ``serving.queue_wait`` spans in the order they were
    recorded, the batcher's stats at the end)."""
    from oryx_tpu.obs.trace import Tracer

    tracer = Tracer("serving", sample_ratio=1.0, max_traces=4096)
    batcher = TopNBatcher(pipeline=8, idle_wait_s=0.02, tracer=tracer)
    stop = time.monotonic() + 1.0

    def caller(i):
        while time.monotonic() < stop:
            req = tracer.begin_request("serving.request")
            batcher.top_n(device, 3, device.vector())
            tracer.end_request(req, 200)
            time.sleep(0.001 + 0.002 * i)

    threads = [threading.Thread(target=caller, args=(i,))
               for i in range(callers)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
            assert not t.is_alive()
        stats = batcher.stats()
    finally:
        batcher.close()
    waits = [s for spans in tracer.traces_snapshot(limit=4096).values()
             for s in spans if s["name"] == "serving.queue_wait"]
    assert waits
    return waits, stats


def test_the_queue_wait_span_says_what_the_batcher_did():
    waits, _ = _queue_waits_of_traced_callers(_SerialDevice(0.03))
    assert all({"depth", "depth_reason", "held_ms", "renewals",
                "left_behind", "return_hit_share"}
               <= set(s["attrs"]) for s in waits)
    late = [s["attrs"] for s in waits[len(waits) // 2:]]
    assert {a["depth_reason"] for a in late} == {"serial"}
    assert {a["depth"] for a in late} == {1}
    assert any(a["held_ms"] > 0 for a in late)
    # two callers: when the second is back nobody is out, so a hold is
    # never renewed, and a drain leaves nobody behind once in step
    assert {a["renewals"] for a in late} == {0}
    assert sum(a["left_behind"] for a in late) <= len(late) // 10


@pytest.mark.parametrize("overlapping", [False, True],
                         ids=["serial", "overlapping"])
def test_the_queue_wait_span_carries_the_verdict_and_its_measurements(
        overlapping):
    """What ``_depth`` decided is on the span (``depth``,
    ``depth_reason``); these say what it decided FROM and what became of
    the drain: ``in_flight`` (the drains dispatched and not completed
    when this one left: 0 a lone drain, from 1 on a drain bound behind a
    running program), the two cycles that were compared
    (``cycle_behind_ms``, ``cycle_shared_ms``; None while unmeasured)
    and the N they speak of (``cycle_n``), ``overlap_share`` (the
    host's share of a lone drain's wall, reported only) and
    ``service_ms`` (S).  The benchmark's ``batcher.behind_share`` is the
    share of the spans with ``in_flight`` >= 1."""
    # (on a device that overlaps, TWO closed-loop callers turn round as
    # soon in one shared call as in two, a call and the way back either
    # way: a tie, read either way.  Six keep several calls in flight,
    # and the gaps between their completions are a fraction of a call)
    spans, stats = _queue_waits_of_traced_callers(
        _SerialDevice(0.03, overlapping=overlapping),
        callers=6 if overlapping else 2)
    waits = [s["attrs"] for s in spans]
    assert all({"in_flight", "overlap_share", "service_ms", "cycle_n",
                "cycle_behind_ms", "cycle_shared_ms"} <= set(a)
               for a in waits)
    # nothing is known before two drains have queued one behind the
    # other, which is how the first two callers leave: one of them bound
    # behind the other's running program
    first = min(waits, key=lambda a: a["cycle_behind_ms"] is not None)
    assert first["cycle_behind_ms"] is None
    assert first["cycle_shared_ms"] is None
    assert first["overlap_share"] is None
    assert first["depth_reason"] == "unmeasured"
    assert any(a["in_flight"] >= 1 for a in waits)
    late = waits[len(waits) // 2:]
    assert all(0.0 <= a["overlap_share"] <= 1.0 for a in late)
    assert late[-1]["overlap_share"] == pytest.approx(
        stats["overlap_share"], abs=0.2)
    # a lone drain's wall is the 30 ms call, and the way back is added
    assert all(30.0 <= a["cycle_shared_ms"] <= 60.0 for a in late)
    if overlapping:
        # S is the gap between completions, most of a 30 ms call hidden
        assert all(0.0 < a["service_ms"] < 30.0 for a in late)
        assert all(2 <= a["cycle_n"] <= 6 for a in late)
        assert all(a["cycle_behind_ms"] < a["cycle_shared_ms"]
                   for a in late)
        assert {a["depth_reason"] for a in late} <= {"pipelined",
                                                     "pipelined-probe"}
        # two in flight hide each other: drains leave behind drains
        assert any(a["in_flight"] >= 1 for a in late)
    else:
        # the program's 30 ms, as the batcher has learnt it
        assert all(20.0 <= a["service_ms"] <= 45.0 for a in late)
        assert {a["cycle_n"] for a in late} == {2}
        # two programs one behind the other against one shared
        assert all(a["cycle_behind_ms"] >= 55.0 > a["cycle_shared_ms"]
                   for a in late)
        assert {a["depth_reason"] for a in late} == {"serial"}
        # one program at a time: every drain leaves alone
        assert {a["in_flight"] for a in late} == {0}


@_on_a_host_that_keeps_time
def test_a_host_share_over_a_quarter_does_not_make_a_device_overlap():
    """The four-chip cell in miniature (its times threefold, so that a
    loaded host's late wake-ups stay small beside them): a 21 ms program
    and 9 ms of host path that holds nobody else up, the same at one
    request and at two.  The host's share of a lone drain's wall is
    0.3, and no part of it is the device overlapping anything: two
    programs one behind the other turn a caller round in 42 ms, one
    shared pass in 30 and the way back.  The verdict is ``serial``, so a
    caller that slips once is back in step within two programs (read as
    ``pipelined``, the depth is two, the caller that slipped is bound
    alone behind the other's program, the other comes back to a running
    program and is bound alone in turn, and so on until a probe, 256
    drains later)."""
    device = _SerialDevice(0.021, host_s=0.009)
    batcher = TopNBatcher(pipeline=8)
    try:
        # past the hold (7.5 ms at most), inside the other's program
        _closed_loop(batcher, device, 2, 3.0, turnaround=(0.0003, 0.001),
                     delay=((20,), 0.015))
        stats = batcher.stats()
        sizes = batcher.batch_sizes[_WARM_IN:]
    finally:
        batcher.close()
    assert stats["depth_reason"] in ("serial", "serial-probe"), stats
    assert stats["cycle_n"] == 2, stats
    assert stats["cycle_behind_ms"] >= 40.0 > stats["cycle_shared_ms"], stats
    assert 1 in sizes, sizes          # the pair did fall out of step
    # a handful, the slip's own; out of step for good would be some
    # hundred and fifty
    assert sizes.count(1) <= 8, sizes
    assert sum(sizes) / len(sizes) >= 1.9, sizes


@_on_a_host_that_keeps_time
def test_a_pass_that_costs_more_shared_keeps_two_programs_in_flight():
    """The LSH cell in miniature (its times fourfold, for the same
    reason, and the shared pass dearer than the cell's 4.2 for 2.5, so
    that a host that runs at half speed beside tier-1's other workers
    does not carry the one side over the other): a pass of 10 ms at one
    request and 22 at two (the union of two Hamming balls), 8.8 ms of
    host path.  Two lone programs one behind the other turn a caller
    round in some 20 ms, a shared pass in 30.8 and the way back:
    ``pipelined``, and because the shared side is a lone drain OF TWO,
    not because a lone single's 18.8 ms wall is the least the batcher
    has seen.  (The cell's own numbers: the next test, without
    threads.)"""
    device = _SerialDevice(0.010, host_s=0.0088,
                           exec_by_size={2: 0.022})
    batcher = TopNBatcher(pipeline=8)
    try:
        _closed_loop(batcher, device, 2, 3.0, turnaround=(0.0003, 0.001))
        stats = batcher.stats()
        alone = [w.mid * 1e3 for w in batcher._lone_walls[:2]]
    finally:
        batcher.close()
    assert stats["depth_reason"] in ("pipelined", "pipelined-probe"), stats
    assert stats["cycle_n"] == 2, stats
    assert stats["cycle_behind_ms"] < stats["cycle_shared_ms"], stats
    # both sizes ran alone, and the shared side reads the wall of two
    assert 18.8 <= alone[0] < alone[1] and alone[1] >= 30.8, alone
    assert stats["cycle_shared_ms"] >= alone[1], (stats, alone)


def _ran_alone(batcher, wall: float, size: int) -> None:
    """A drain of ``size`` that was dispatched with nothing in flight
    (so after every completion before it) has taken ``wall`` seconds."""
    batcher._last_completion = 0.0
    batcher._learn_locked(time.monotonic() - wall, True, size, size)


def _taught(batcher, lone_walls: dict, gap: float, aboard: int,
            t_ret: float):
    """Feed the estimator alone, without threads: one lone drain a size
    of ``lone_walls`` (seconds), then a drain that queued behind another
    and completed ``gap`` after it with ``aboard`` requests in the loop,
    and the callers' way back.  Returns the verdict's reason."""
    for size, wall in lone_walls.items():
        _ran_alone(batcher, wall, size)
    t = time.monotonic()
    batcher._last_completion = t - gap
    batcher._learn_locked(t - gap - 0.001, False, 1, aboard)
    batcher._t_ret.add(t_ret)
    return batcher._depth()[1]


@pytest.mark.parametrize("lone_walls, gap, aboard, t_ret, verdict", [
    # four chips, 250f float32: 15 against 11.1
    ({1: 10.2, 2: 10.2}, 7.5, 2, 0.9, "serial"),
    # one chip, 250f and 50f: 28.2 against 17.1, 14.8 against 10.3
    ({1: 16.0, 2: 16.2}, 14.1, 2, 0.9, "serial"),
    ({1: 9.2, 2: 9.4}, 7.4, 2, 0.9, "serial"),
    # eight callers split seven and one: 29.2 against 20.3
    ({1: 16.0, 7: 17.0, 8: 17.0}, 14.6, 8, 3.3, "serial"),
    # LSH: one ball a program, two in a shared pass: 5.6 against 7.2
    ({1: 4.5, 2: 6.3}, 2.8, 2, 0.9, "pipelined"),
    # an overlapping or remote device
    ({1: 30.0}, 1.0, 6, 5.0, "pipelined"),
], ids=["x4", "250f", "50f", "eight-callers", "lsh", "overlapping"])
def test_the_verdict_compares_the_two_cycles_like_for_like(
        lone_walls, gap, aboard, t_ret, verdict):
    """ISSUE 40's table, ms: the walls and gaps the benchmark's cells
    read, fed to the estimator alone, give the verdict that turns each
    cell's callers round sooner.  No fraction between: four chips (the
    host's share of a lone wall 0.31) read serial and the LSH cell
    (0.33 of a lone single's wall) pipelined."""
    batcher = TopNBatcher(pipeline=2)
    try:
        with batcher._cond:
            assert _taught(batcher,
                           {n: w / 1e3 for n, w in lone_walls.items()},
                           gap / 1e3, aboard, t_ret / 1e3) == verdict
            note = batcher.stats()
            assert note["cycle_n"] == aboard
            assert note["cycle_behind_ms"] == pytest.approx(2 * gap,
                                                            abs=0.05)
            shared = lone_walls.get(aboard, lone_walls[1]) + t_ret
            assert note["cycle_shared_ms"] == pytest.approx(shared, abs=0.05)
            # where the hold is off nobody is coming back
            batcher._hit_share = 0.1
            assert batcher.stats()["cycle_shared_ms"] \
                == pytest.approx(shared - t_ret, abs=0.05)
    finally:
        batcher.close()


def test_the_shared_side_is_a_lone_drain_of_the_loops_size():
    """A lone single's wall does not move the verdict of a two-caller
    loop, and the verdict is read from what is known now: warmed on
    lone singles alone the LSH cell's numbers read serial (a pass is
    free to share until measured otherwise), and the first shared drain
    that runs alone turns that round without waiting for another gap."""
    from oryx_tpu.serving import batcher as batcher_mod

    batcher = TopNBatcher(pipeline=2)
    try:
        with batcher._cond:
            assert _taught(batcher, {1: 0.0045}, 0.0028, 2, 0.0009) \
                == "serial"
            assert batcher.stats()["cycle_shared_ms"] \
                == pytest.approx(5.4, abs=0.05)
            batcher._probe_every = 4 * batcher_mod._PROBE_EVERY
            _ran_alone(batcher, 0.0063, 2)
            assert batcher._depth()[1] == "pipelined"
            assert batcher.stats()["cycle_shared_ms"] \
                == pytest.approx(7.2, abs=0.05)
            # the answer changed: the probes come soon again
            assert batcher._probe_every == batcher_mod._PROBE_EVERY
            for _ in range(50):
                _ran_alone(batcher, 0.0045, 1)
            assert batcher._depth()[1] == "pipelined"
            assert batcher.stats()["round_trip_floor_ms"] == 4.5
            # a loop of three or four has no wall of its own yet: the
            # nearest size that has one speaks for it
            batcher._loop_n = 4
            assert batcher.stats()["cycle_shared_ms"] \
                == pytest.approx(7.2, abs=0.4)
    finally:
        batcher.close()


def _queued(batcher, gap: float, aboard: int = 2) -> None:
    """A drain bound behind a running program has completed ``gap``
    seconds after it, ``aboard`` requests in the loop; a lone drain ran
    between it and the pair before, as a probe's drain finds it."""
    t = time.monotonic()
    batcher._last_completion, batcher._since_gap = t - gap, 5
    batcher._learn_locked(t - gap - 0.001, False, 1, aboard)


@pytest.mark.parametrize("cell, odd, verdict", [
    # the LSH cell: a shared pass 6.5 ms, two lone ones 2 x 3.0
    ("lsh", ("wall", 2, 4.8), "pipelined"),  # one shared drain is short
    ("lsh", ("wall", 2, 15.0), "pipelined"),  # one is stalled
    ("lsh", ("gap", 7.0), "pipelined"),      # the host stalls in a gap
    # four chips: a shared pass 10.2 ms, two lone ones 2 x 7.5
    ("x4", ("gap", 0.5), "serial"),          # a completion stamped late
    ("x4", ("wall", 2, 25.0), "serial"),
    ("x4", ("back", 9.0), "serial"),         # a caller stops to think
], ids=lambda v: v if isinstance(v, str) else "-".join(map(str, v)))
def test_one_odd_reading_moves_neither_side_of_the_verdict(
        cell, odd, verdict):
    """Both sides are the middle one of their last five readings, so a
    single reading, short or long, carries neither over the line (on the
    chip one lone drain of two at 4.8 ms turned the LSH cell ``serial``
    while the shared side was a least wall, and every turn puts the
    probes back to one in 256); what the readings keep saying does."""
    from oryx_tpu.serving import batcher as batcher_mod

    wall2, gap = {"lsh": (6.5, 3.0), "x4": (10.2, 7.5)}[cell]
    wall1 = {"lsh": 4.5, "x4": 10.2}[cell]
    batcher = TopNBatcher(pipeline=2)

    def read(kind, *reading):
        if kind == "gap":
            _queued(batcher, reading[0] / 1e3)
        elif kind == "back":
            batcher._t_ret.add(reading[0] / 1e3)
        else:
            _ran_alone(batcher, reading[1] / 1e3, reading[0])

    try:
        with batcher._cond:
            for _ in range(5):
                read("wall", 1, wall1)
                read("wall", 2, wall2)
                read("gap", gap)
                read("back", 0.9)
            assert batcher._depth()[1] == verdict
            batcher._probe_every = 4 * batcher_mod._PROBE_EVERY
            before = batcher.stats()
            read(*odd)
            assert batcher._depth()[1] == verdict
            after = batcher.stats()
            for side in ("cycle_behind_ms", "cycle_shared_ms"):
                assert after[side] == pytest.approx(before[side], rel=0.02)
            # the answer held: the probes stay backed off
            assert batcher._probe_every == 4 * batcher_mod._PROBE_EVERY
            # the same reading three times over is no accident
            read(*odd)
            read(*odd)
            assert batcher.stats() != after
    finally:
        batcher.close()


def test_two_callers_on_an_overlapping_device_lose_nothing_to_a_tie():
    """Two closed-loop callers that think for 1 and 3 ms, on a device
    that overlaps: out of step their completions come in turns of a
    short gap and a long one, two of them a 30 ms call and a way back,
    and one shared call is a call and a way back too.  The two cycles
    tie, so the verdict may read either way and turn.  That is
    harmless: either way a caller's cycle is one call and its thinking,
    and a turn only puts the probes back to one in 256 completions."""
    spans, stats = _queue_waits_of_traced_callers(
        _SerialDevice(0.03, overlapping=True))
    late = [s["attrs"] for s in spans[len(spans) // 2:]]
    assert {a["depth_reason"] for a in late} <= {
        "serial", "serial-probe", "pipelined", "pipelined-probe"}
    assert all(a["cycle_n"] == 2 for a in late)
    assert all(30.0 <= a["cycle_shared_ms"] <= 45.0 for a in late)
    # a second of cycles of a call, the thinking and at most the hold:
    # with a call of its own behind the other's, half as many
    assert len(spans) >= 2 * 1.0 / 0.045, len(spans)
    # and no request waited out more than the rest of one call
    assert all(s["duration_ms"] < 45.0 for s in spans[8:]), \
        max(s["duration_ms"] for s in spans[8:])
    assert stats["probes"] <= 1, stats


def test_no_wakeup_is_lost_under_a_crowd_of_callers():
    """More callers than cores, a device fast enough that holds time out
    and arrivals end them all the time, and a short switch interval: a
    lost wake-up would leave a request queued with every dispatcher
    parked, and a lost update would unbalance the books."""
    import sys

    device = _SerialDevice(0.0005)
    batcher = TopNBatcher(pipeline=8, idle_wait_s=0.0005)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        walls = _closed_loop(batcher, device, 32, 1.5,
                             turnaround=(0.0, 0.0004))
        stats = batcher.stats()
        answered = sum(batcher.batch_sizes)
    finally:
        sys.setswitchinterval(interval)
        batcher.close()
    assert len(walls) == answered == sum(len(c[2]) for c in device.calls)
    assert stats["in_flight"] == 0 and stats["pending"] == 0, stats
    assert stats["dispatches"] == len(device.calls)
    assert 0 <= stats["return_hits"] and stats["return_holds"] > 0, stats
    assert max(walls) < 1.0, max(walls)


def test_a_probe_costs_one_program_and_asks_less_often(monkeypatch):
    """At a depth of one the completion gap of a queued pair is hidden,
    so once in a while ONE drain is bound behind a running program to
    take it.  Callers in step never arrive while a program runs, so the
    probe waits for a pair that has split by itself, costs one lone
    program beside the split's own, and every probe that finds the
    device still serial doubles the completions to the next."""
    from oryx_tpu.serving import batcher as batcher_mod

    monkeypatch.setattr(batcher_mod, "_PROBE_EVERY", 4)
    device = _SerialDevice(0.02)
    batcher = TopNBatcher(pipeline=8, idle_wait_s=0.005)
    splits = (20, 45, 80)
    try:
        # past the 2.5 ms hold, inside the other caller's program
        _closed_loop(batcher, device, 2, 2.6, turnaround=(0.0005, 0.002),
                     delay=(splits, 0.012))
        stats = batcher.stats()
        sizes = batcher.batch_sizes[_WARM_IN:]
    finally:
        batcher.close()
    # (armed again by the end, or not)
    assert stats["depth_reason"] in ("serial", "serial-probe"), stats
    # the splits let probes in (a loaded host adds splits of its own),
    # and probe n waits for 4 * 2 ** n completions after the one before
    probes = stats["probes"]
    assert probes >= 1 and 4 * (2 ** probes - 1) <= stats["dispatches"], \
        stats
    assert stats["probe_every"] == 4 * 2 ** probes, stats
    # (that a probe is ONE drain is pinned without threads, below)
    assert sum(sizes) / len(sizes) >= 1.5, sizes


def test_probes_back_off_while_the_answer_holds_and_return_when_it_changes():
    """The count that arms a probe doubles with every probe up to its
    cap, and an answer that changes puts it back: driven through the
    estimator alone, with stamps of a device that is serial and then is
    not."""
    from oryx_tpu.serving import batcher as batcher_mod

    batcher = TopNBatcher(pipeline=2)
    try:
        with batcher._cond:
            # a drain that ran alone for 10 ms, then one that was
            # dispatched 20 ms ago behind another that completed 10 ms
            # ago: a serial device
            batcher._learn_locked(time.monotonic() - 0.010, True, 1, 1)
            t = time.monotonic()
            batcher._last_completion = t - 0.010
            batcher._learn_locked(t - 0.020, False, 1, 2)
            assert batcher._depth() == (1, "serial")
            every = batcher_mod._PROBE_EVERY
            for _ in range(8):
                batcher._since_gap = batcher._probe_every
                assert batcher._depth() == (2, "serial-probe")
                batcher._in_flight = 1
                batcher._bind_locked(time.monotonic())
                assert batcher._probe_out
                # the probe's one drain is out: nobody else goes behind
                assert batcher._depth() == (1, "serial")
                batcher._in_flight = 0
                batcher._probe_out = False
                t = time.monotonic()
                batcher._last_completion = t - 0.010
                batcher._learn_locked(t - 0.020, False, 1, 2)
                every = min(batcher_mod._PROBE_EVERY_MAX, 2 * every)
                assert batcher._probe_every == every
                assert batcher._depth() == (1, "serial")
            assert every == batcher_mod._PROBE_EVERY_MAX
            assert batcher.probes == 8
            # the device starts to overlap: the queued drain completes
            # 1 ms after the one before it
            for _ in range(6):
                t = time.monotonic()
                batcher._last_completion = t - 0.001
                batcher._learn_locked(t - 0.011, False, 1, 2)
            assert batcher._depth()[1] == "pipelined"
            assert batcher._probe_every == batcher_mod._PROBE_EVERY
    finally:
        batcher.close()


def test_a_completion_wakes_nobody_who_has_nothing_to_do():
    """The dispatcher whose drain completed goes round and takes the
    next one itself; parked dispatchers woken at a completion would find
    nothing queued and take the interpreter from the handlers that have
    answers to send.  With two callers in step the only thread woken is
    the one that carries the pool's state, by an arrival."""
    device = _SerialDevice(0.02)
    batcher = TopNBatcher(pipeline=8, idle_wait_s=0.01)
    woken = []
    notify = batcher._cond.notify
    batcher._cond.notify = lambda n=1: (woken.append(n), notify(n))[1]
    try:
        _closed_loop(batcher, device, 2, 1.5)
        drains = batcher.total_dispatches
        sizes = batcher.batch_sizes[_WARM_IN:]
    finally:
        batcher.close()
    assert drains > 40 and sum(sizes) / len(sizes) >= 1.5, sizes
    # the parked pool is woken for the first requests, before the device
    # is known to be serial, and when a caller falls out of step; two
    # woken at every completion would be twice the drains
    assert sum(woken) <= drains, (sum(woken), drains)


def test_callers_that_turn_closed_loop_get_the_hold_back_soon():
    """After open-loop traffic has switched the hold off, one drain in
    32 completions holds all the same; a hold in which everyone came
    back is followed by another at the next completion, so callers that
    turn closed-loop have the hold back some forty programs later, not
    two hundred.  Driven through the hold's own steps, without threads:
    per completion one caller is out, and comes back inside any hold."""
    batcher = TopNBatcher(pipeline=2)
    try:
        with batcher._cond:
            batcher._exec_ewma, batcher._exec_measured = 0.016, True
            batcher._hit_share = 0.05   # what an open loop leaves
            completions = 0
            while batcher._hit_share < 0.5:
                completions += 1
                assert completions < 100
                batcher._since_hold += 1     # _learn_locked
                batcher._awaited = 1         # _dispatch, after the fetch
                if batcher._hold_locked(time.monotonic()) > 0:
                    batcher._awaited -= 1    # top_n: the caller is back
                    batcher._hold_hits += 1
                batcher._bind_locked(time.monotonic())
            assert 32 <= completions <= 40, completions
            # an open loop's caller does not come back: the one hold in
            # 32 is scored a miss and is not repeated
            batcher._hit_share, batcher._since_hold = 0.05, 0
            holds = batcher.return_holds
            for _ in range(64):
                batcher._since_hold += 1
                batcher._awaited = 1
                batcher._hold_locked(time.monotonic())
                batcher._bind_locked(time.monotonic())
            assert batcher.return_holds - holds == 2
    finally:
        batcher.close()
