"""Request micro-batcher: concurrent /recommend-family requests share
one device dispatch.

Reference equivalent: SURVEY §2.14 P6 — Tomcat's 400-thread pool fans a
single request out across cores (ServingLayer.java:235); the TPU-native
inversion batches many concurrent requests into ONE MXU matmul
(`ALSServingModel.top_n_batch`).

Design: adaptive queue-drain batching bounded by a measured in-flight
cap.  Handler threads enqueue a scoring job and block; dispatcher
threads drain whatever is queued and issue one batched kernel call
each.  WHEN pending requests are bound to a device program is the one
decision made here, and it follows two measurements:

- *How deep to keep the device's queue.*  What a second program in
  flight buys or costs the callers is their CYCLE, one answer to the
  next, and the batcher clocks both ways of running it, each in drains
  of the kind it speaks of.  *One behind another:* the loop's N
  requests ride two drains, the running program and the drain bound
  behind it, and every caller's program runs after the other's, so a
  cycle is twice the gap between the completions of drains that queued
  one behind the other.  *In one shared pass:* a cycle is the wall of a
  drain that ran ALONE and carried about N requests (kept by drain
  size, because a pass is free to share over an exact store and is not
  where a wider window streams more), plus the callers' way back (from
  the completion to the moment the drain the hold held for them left).
  Where the shared cycle is no longer than the other — a locally
  attached chip that runs one program after another — the cap is ONE: a
  request that arrives while a program runs is bound late, at that
  program's completion, together with everything else that is waiting.
  It loses nothing (its program could not have started earlier) and
  shares its pass.  Where two programs one behind the other turn the
  callers round sooner — device calls overlap, a transport round trip
  dominates, or a lone request's program is so much shorter than the
  shared one that two of them and the host path between beat it — the
  cap is ceil(round_trip / service_time) + 1: deeper only stacks
  device-queue latency (observed before the cap existed: free
  dispatchers shredded a 5M-item model's queue into tiny batches that
  serialized on the device, 3% of achievable throughput with 3 s
  device-queue latency), and a blocked dispatcher wakes on the next
  completion and drains everything that queued during one service
  interval.  Each depth hides one side of the comparison (at one
  nothing queues behind anything; a saturated pipeline never runs a
  drain alone), so now and then one drain is dispatched the other way
  to take the hidden one: :meth:`_depth` says when and what it costs.
- *Whether to hold a drain for the callers just answered.*  A
  completion releases n callers; closed-loop callers are back within a
  fraction of a service time, one after another through the door, and
  the drain that could leave at once would leave without them.  It
  waits for them, leaving as soon as they are back, for as long as they
  keep coming: the clock runs from the LAST caller's return, and the
  drain leaves when nobody has come back for one patience (an eighth of
  the service time at most, less where many wait for few), or a quarter
  of the service time after the first return, whoever is still out.
  A caller left behind finds a program running and rides the next: a
  whole service time, against the fraction of one that those waiting
  paid for it.  The hold keeps score: where the awaited callers do not
  come back inside it (open-loop arrivals) it switches itself off and
  tries again every so many completions.  A lone closed-loop caller is
  never held: when it is back, nobody else is out.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Iterable

import numpy as np

from ..common import clock as clockmod
from ..obs import trace as obstrace
from ..resilience import faults
from ..resilience.policy import Deadline, DeadlineExceeded

__all__ = ["TopNBatcher"]

# exec-time EWMA clamps: below 0.5 ms pacing is irrelevant; above this
# cap a single anomalous stall (e.g. a mid-run recompile) cannot freeze
# dispatching for minutes
_MIN_EXEC_S = 0.0005
_MAX_EXEC_S = 5.0

# Each depth hides one of the two measurements it is chosen by: at depth
# one no drain queues behind another, in a saturated pipeline none runs
# alone.  After this many completions without the hidden one, ONE drain
# is dispatched the other way to take it (a probe).  Every probe doubles
# the count, up to the second number, and an answer that changes (serial
# <-> pipelined) puts it back to the first: a device that keeps giving
# the same answer is asked 7 times in its first 32,512 drains and once
# in 16,384 from then on.
_PROBE_EVERY = 256
_PROBE_EVERY_MAX = 16384
# A drain that waits for the callers the last completion released
# leaves when none of them has come back for this fraction of the
# service time (one patience), and never waits longer than this for the
# next one.
_HOLD_FRACTION = 8
_MAX_HOLD_S = 0.002
# However they trickle back, a hold lasts at most this fraction of the
# service time from the first return on (and as long before it, where a
# drain could leave with nobody back): what a caller in step can lose to
# it, where a caller left behind loses a whole one.  It is also what
# keeps the score honest: Poisson arrivals cannot be told from returning
# callers, and S / 4 meets a quarter as many of them as the program
# before it released, well under _HIT_SHARE_ON.
_HOLD_TOTAL_FRACTION = 4
# The hold stays on while at least this share of the awaited callers
# came back inside it (running mean over holds); off, one drain in this
# many completions holds all the same, to see whether they do now, and
# so does the drain after a hold in which they all did.
_HIT_SHARE_ON = 0.5
_HIT_SHARE_GAIN = 0.1
_HOLD_PROBE_EVERY = 32


def _size_class(n: int) -> int:
    """Drains of 1, 2, 3-4, 5-8, ... requests: class 0, 1, 2, 3, ..."""
    return max(0, n - 1).bit_length()


class _Recent:
    """A time the batcher clocks, as the verdict reads it: ``mid``, the
    middle one of its last five readings (None before the first).  No
    single reading moves it, whichever way it is wrong: a stall of the
    host lengthens a wall, a way back or the gaps of both drains in
    flight, a completion stamped late shortens the next gap to nothing,
    and one of a hundred lone drains is short; a mean or a least value
    that one of those carries over the line stays there until the next
    probe.  Sorted when a reading comes in, so that reading it inside
    the wait loops is an attribute."""

    __slots__ = ("_last", "mid")

    def __init__(self):
        self._last: deque[float] = deque(maxlen=5)
        self.mid: float | None = None

    def add(self, reading: float) -> None:
        self._last.append(reading)
        self.mid = sorted(self._last)[len(self._last) // 2]


class _Job:
    __slots__ = ("model", "how_many", "vector", "exclude", "done",
                 "result", "error", "t_enq", "deadline", "trace_ctx")

    def __init__(self, model, how_many: int, vector: np.ndarray,
                 exclude: set[str], deadline: Deadline | None = None,
                 trace_ctx: tuple[str, str] | None = None):
        self.model = model
        self.how_many = how_many
        self.vector = vector
        self.exclude = exclude
        self.done = threading.Event()
        self.result: list[tuple[str, float]] | None = None
        self.error: BaseException | None = None
        self.t_enq = clockmod.monotonic()
        self.deadline = deadline
        # (trace_id, parent_span_id) captured at submit on sampled
        # requests; None (the overwhelmingly common case) costs nothing
        self.trace_ctx = trace_ctx


class TopNBatcher:
    """Coalesce concurrent dot-product top-N requests into batched
    device calls.  Safe across model hot-swaps: jobs carry their model,
    and each drain groups jobs by model identity."""

    def __init__(self, max_batch: int = 1024, pipeline: int = 32,
                 idle_wait_s: float | None = None, tracer=None,
                 accountant=None):
        """``pipeline`` dispatcher threads are the most batched device
        calls ever in flight at once; how many really are follows what
        the batcher measures (:meth:`_depth`): one on a device that
        runs them one after another, enough to cover the round trip
        where dispatch latency overlaps (sustained throughput there ~=
        mean_batch x depth / round_trip).  32 was chosen where the
        dispatch round trip was long; idle depth is just parked
        threads; configurable via
        oryx.serving.api.scoring-pipeline-depth.

        ``idle_wait_s`` caps one patience of the hold of the module's
        docstring: how long a drain that could leave goes on waiting
        after the last of the callers the previous completion released
        came back.  None (default) is 2 ms; the patience in force is
        the smaller of the cap and an eighth of the measured service
        time, the hold ends a quarter of it after the first return at
        the latest, and 0 switches the hold off.  Configurable via
        oryx.serving.api.batch-idle-wait-ms (-1 = the default cap).

        ``tracer`` (obs/trace.py, or None) splits each sampled
        request's batcher residence into a queue-wait span and a
        device-execute span — the evidence that separates "the device
        is slow" from "the queue is deep" — and opens a phase recorder
        (obs/trace.py ``DrainPhases``) around every batched call, so
        the model's prepare / scan / fallback / decode phases, and the
        upload / launch / device_wait / fetch steps inside them, land
        under each sampled job's device-execute span and, as profiler
        annotations, on the dispatcher thread's line of a device trace.
        The pool's three idle states are annotated there too, each on
        one thread at a time (``serving.await_work``: nothing queued and
        nothing in flight; ``serving.await_slot``: work queued behind
        the in-flight cap; ``serving.await_return``: a drain held for
        the callers just answered), and so is what a dispatcher does
        between the model's return and its next wait
        (``serving.release``), so that its line has no hole.

        ``accountant`` (obs/device_time.py, or None) books every
        batched device-execute bracket as route-class ``serve`` time
        against the model's kernel route and generation — the
        continuous occupancy accounting behind
        ``device_busy_fraction``."""
        self.max_batch = max_batch
        self._tracer = tracer
        self._accountant = accountant
        self._idle_wait = idle_wait_s
        lock = threading.RLock()
        self._cond = threading.Condition(lock)
        # the ONE dispatcher whose wait is the pool's state parks here
        # (same lock), so that whatever ends the state wakes that thread
        # first (_await_locked, _wake_locked); the token says whose turn
        # it is, so a wait that times out clears no later thread's
        self._noted = threading.Condition(lock)
        self._noted_by: object | None = None
        self._pending: list[_Job] = []
        self._stopped = False
        # pacing state (all under _cond)
        self._in_flight = 0
        self._last_completion = 0.0
        # service time: the gap between completions of drains that
        # queued one behind the other, or, where the depth in force is
        # one, the wall of a drain; optimistic until measured
        self._exec_ewma = _MIN_EXEC_S
        self._exec_measured = False
        # the two cycles the verdict compares (_cycles), every reading a
        # _Recent.  In one shared pass: the wall of a drain dispatched
        # with nothing in flight (round trip + one exec; a drain that
        # queued behind another teaches nothing here, its wall holds the
        # other one's device time), by the size of the drain (class c
        # holds 2^(c-1) < n <= 2^c: 1, 2, 3-4, 5-8, ...), the least of
        # them over the sizes (the round trip's floor, for the depth's
        # formula), and how long after a completion the drain the hold
        # held for the returning callers left.  One behind another: the
        # completion gap of drains that queued behind a running program
        # (only such drains feed it, where _exec_ewma turns into a lone
        # drain's wall at a depth of one), the gap before it, and how
        # many requests the last of them and the drains in flight
        # before it carried together, the N of the loop
        self._lone_walls = [_Recent()
                            for _ in range(1 + _size_class(max_batch))]
        self._wall_min = float("inf")
        self._t_ret = _Recent()
        self._gap = _Recent()
        self._last_gap = 0.0
        self._loop_n = 0
        # requests aboard the drains in flight
        self._aboard = 0
        # completions since a drain last ran alone / last queued behind
        # another, how many of them arm a probe now (_PROBE_EVERY and
        # its doubling), whether the one drain of a serial probe is out,
        # and how many probes were taken
        self._since_lone = 0
        self._since_gap = 0
        self._probe_every = _PROBE_EVERY
        self._probe_out = False
        self.probes = 0
        # the hold for returning callers: how many the last completion
        # released that are not back yet and when the last of them came
        # back (None: none has), the hold now running (start, where
        # its limit counts from, callers awaited once the first was
        # back, how many of them came, arrivals that left somebody
        # out), and its score
        self._awaited = 0
        self._last_return: float | None = None
        self._hold_t0: float | None = None
        self._hold_from = 0.0
        self._hold_for = 0
        self._hold_hits = 0
        self._hold_renewals = 0
        self._hit_share = 1.0
        self._since_hold = 0
        self.return_holds = 0
        self.return_hits = 0
        self.return_left_behind = 0
        self._threads = [
            threading.Thread(target=self._loop, daemon=True,
                             name=f"TopNBatcher-{i}")
            for i in range(max(1, pipeline))]
        for t in self._threads:
            t.start()
        # drain-size histogram, exposed for tests and the metrics surface
        self.batch_sizes: list[int] = []
        self.total_dispatches = 0
        # deadline sheds: refused at submit or expired while queued
        self.deadline_rejects = 0
        # measured queue wait (enqueue -> drain pickup), EWMA over
        # recent drains: the overload signal replicas report upstream
        # for the router's admission control (under _cond)
        self._qwait_ewma = 0.0
        self._qwait_at = 0.0

    def top_n(self, model, how_many: int, user_vector: np.ndarray,
              exclude: Iterable[str] = (),
              deadline: Deadline | None = None) -> list[tuple[str, float]]:
        """Blocking submit; returns the same pairs as ``model.top_n``
        (dot-product scores; on an LSH-configured model the batched
        dispatch applies the same Hamming-ball candidate mask the
        single-request path would).

        A ``deadline`` (resilience.policy.Deadline, minted at the HTTP
        front end) is enforced at the two queueing edges: an already-
        expired request is refused before it queues, and a request whose
        budget runs out while waiting is shed at dispatch instead of
        spending device time on an answer nobody is waiting for.  Both
        raise DeadlineExceeded (503 at the serving surface)."""
        if deadline is not None and deadline.expired:
            with self._cond:
                self.deadline_rejects += 1
            raise DeadlineExceeded("request deadline expired before "
                                   "scoring was queued")
        trace_ctx = None
        if self._tracer is not None:
            # submit runs on the request's handler thread, so the
            # thread-current span is the request span; its context is
            # captured here because the dispatcher thread that records
            # the queue-wait/device-execute split has no thread-local
            # trace state of its own
            cur = self._tracer.current()
            if cur.sampled:
                trace_ctx = (cur.trace_id, cur.span_id)
        job = _Job(model, how_many,
                   np.asarray(user_vector, dtype=np.float32), set(exclude),
                   deadline=deadline, trace_ctx=trace_ctx)
        with self._cond:
            if self._stopped:
                # shutdown race: keep-alive handler threads may outlive
                # close(); degrade to an unbatched dispatch, not a 500
                stopped = True
            else:
                stopped = False
                self._pending.append(job)
                self._return_locked(job.t_enq)
                self._wake_locked(1)
        if stopped:
            return model.top_n_batch([how_many], job.vector[None, :],
                                     [job.exclude])[0]
        job.done.wait()  # wall-clock: caller blocks on a real worker thread
        if job.error is not None:
            raise job.error
        return job.result

    def recent_queue_wait_ms(self) -> float:
        """The batcher's current queue-wait estimate in ms: the larger
        of the recent-drain EWMA (decayed to 0 after 5 idle seconds)
        and the LIVE age of the oldest still-queued job — so a queue
        that stopped draining reports a growing wait, not the stale
        average of better times.  At a depth of one a request that
        arrives while a program runs waits here, not on the device's
        queue, so under load the signal reads up to one service time
        where a deeper pipeline hid that wait inside the device call."""
        now = clockmod.monotonic()
        with self._cond:
            ew = self._qwait_ewma if now - self._qwait_at <= 5.0 else 0.0
            oldest = (now - self._pending[0].t_enq) if self._pending \
                else 0.0
        return max(ew, oldest) * 1000.0

    def stats(self) -> dict:
        """Live pacing/batching state for the /metrics surface."""
        qw = self.recent_queue_wait_ms()
        with self._cond:
            sizes = self.batch_sizes[-1000:]
            cycles = self._cycles()
            depth, why = self._depth(cycles)
            return {
                "dispatches": self.total_dispatches,
                "queue_wait_ms": round(qw, 2),
                "mean_recent_batch": round(sum(sizes) / len(sizes), 1)
                if sizes else 0.0,
                "service_time_ms": round(self._exec_ewma * 1e3, 2),
                "round_trip_floor_ms": round(self._wall_min * 1e3, 1)
                if self._wall_min != float("inf") else None,
                **self._verdict_note(cycles),
                "in_flight": self._in_flight,
                "in_flight_target": depth,
                "depth_reason": why,
                "probes": self.probes,
                "probe_every": self._probe_every,
                "pending": len(self._pending),
                "return_hold_ms": round(self._hold_cap() * 1e3, 2),
                "return_hold": "on" if self._hit_share >= _HIT_SHARE_ON
                else "off",
                "return_holds": self.return_holds,
                "return_hits": self.return_hits,
                "return_left_behind": self.return_left_behind,
                "return_hit_share": round(self._hit_share, 3),
                "deadline_rejects": self.deadline_rejects,
            }

    def close(self) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
            self._noted_by = None
            self._noted.notify_all()
        for t in self._threads:
            t.join(5.0)

    # -- dispatcher ----------------------------------------------------------

    def _cycles(self) -> tuple[float, float] | None:
        """The callers' cycle, one answer to the next, run both ways, in
        seconds: (one behind another, in one shared pass); None until a
        drain has queued behind another and one has run alone.  Read
        from what is known NOW each time it is asked: a lone wall of
        the loop's size that arrives after the last gap moves the
        verdict at once.

        *One behind another* is what a second program in flight does to
        closed-loop callers out of step: the loop's N requests ride two
        drains, the running program and the one bound behind it, each
        caller's program runs after the other's, and a cycle is two
        completion gaps.  Two, however deep the pipeline: the verdict
        is about the first step away from one program at a time, and
        how much deeper to go where that step pays is the formula's in
        :meth:`_depth`.  (Counted over a deep pipeline the product is
        the cycle by construction in a closed loop and means nothing in
        an open one.)  *In one shared pass* is what a depth of one and
        the hold do: the wall of a lone drain of about N requests, the
        nearest size seen where that size has not run alone yet (a pass
        is free to share until measured otherwise), plus the callers'
        way back while the hold is on (an open loop has nobody coming
        back).  Every reading is a :class:`_Recent`.  Plain float math:
        :meth:`_depth` calls this inside the wait loops."""
        gap = self._gap.mid
        if gap is None or self._wall_min == float("inf"):
            return None
        back = self._t_ret.mid
        if back is None or self._hit_share < _HIT_SHARE_ON:
            back = 0.0
        return 2.0 * gap, self._lone_wall(self._loop_n) + back

    def _lone_wall(self, size: int) -> float:
        """The wall of a lone drain of about ``size`` requests: of its
        size class, or of the nearest class that has run alone (the
        smaller of two as near); inf before any has."""
        walls = self._lone_walls
        c = min(len(walls) - 1, _size_class(size))
        for step in range(len(walls)):
            for near in (c - step, c + step):
                if 0 <= near < len(walls) and walls[near].mid is not None:
                    return walls[near].mid
        return float("inf")

    def _verdict_note(self, cycles: tuple[float, float] | None) -> dict:
        """What the verdict was taken from, for ``stats()`` and the
        ``serving.queue_wait`` span: the two cycles in ms (None while
        unmeasured), the N they speak of, and, reported only, the
        host's share of a lone drain's wall (1 - completion gap / least
        lone wall)."""
        if cycles is None:
            return {"overlap_share": None, "cycle_behind_ms": None,
                    "cycle_shared_ms": None, "cycle_n": self._loop_n}
        return {"overlap_share": round(max(
                    0.0, 1.0 - self._gap.mid / self._wall_min), 3),
                "cycle_behind_ms": round(cycles[0] * 1e3, 3),
                "cycle_shared_ms": round(cycles[1] * 1e3, 3),
                "cycle_n": self._loop_n}

    def _serial(self) -> bool:
        """Whether one program at a time turns the callers round at
        least as soon as two, one behind the other."""
        cycles = self._cycles()
        return cycles is not None and cycles[0] >= cycles[1]

    def _depth(self, cycles: tuple[float, float] | None | bool = False
               ) -> tuple[int, str]:
        """How many dispatches to keep in flight, and why (``cycles``:
        :meth:`_cycles` as the caller has just read it).  Called inside
        the dispatchers' wait loops — plain float math, no numpy scalars
        (they cost microseconds each).

        The verdict is :meth:`_cycles`' comparison: one program at a
        time (``serial``) where the callers' cycle in one shared pass is
        no longer than their cycle one behind another, both of them
        times the batcher clocked.  Its two sides come for free only in
        part, so the hidden one is taken by a probe, ``_probe_every``
        completions after it was last seen.  A pipeline that a closed
        loop keeps saturated never runs a drain alone: the cap drops to
        one until the pipeline has run dry and one drain has gone alone
        (``pipelined-probe``; costs one round trip of throughput).  At a
        depth of one no drain queues behind another: the next drain that
        finds a program running is bound behind it (``serial-probe``),
        that ONE drain and no other.  It costs the callers that come
        back while it runs one program more each — with two callers one
        request, with n at most n - 1 — and nothing until traffic splits
        by itself: callers in step arrive while no program runs, and the
        probe stays armed.
        """
        if cycles is False:
            cycles = self._cycles()
        if cycles is None:
            # (no lone wall yet, or no queued pair yet)
            # two, so that the first requests that coincide queue one
            # behind the other and their completion gap is seen; no
            # deeper, or a device that turns out to be serial starts
            # with a queue of lone-request programs
            return min(len(self._threads), 2), "unmeasured"
        if cycles[0] >= cycles[1]:
            if self._since_gap >= self._probe_every \
                    and not self._probe_out and len(self._threads) > 1:
                return 2, "serial-probe"
            return 1, "serial"
        if self._since_lone >= self._probe_every:
            return 1, "pipelined-probe"
        # enough to cover the transport round trip at the current
        # service rate, plus one.  More than this only deepens the
        # on-device queue (each extra dispatch adds a full service time
        # to every later request's latency)
        rtt = self._wall_min - self._exec_ewma
        if rtt <= 0.0:
            return min(len(self._threads), 2), "pipelined"
        return min(len(self._threads),
                   1 + max(1, -int(-rtt // self._exec_ewma))), "pipelined"

    def _in_flight_target(self) -> int:
        return self._depth()[0]

    def _hold_cap(self) -> float:
        """One patience: the longest a held drain may now go on waiting
        after the last returning caller's arrival.  No time at all
        before the service time has been measured."""
        if not self._exec_measured:
            return 0.0
        cap = _MAX_HOLD_S if self._idle_wait is None else self._idle_wait
        return min(cap, self._exec_ewma / _HOLD_FRACTION)

    def _return_locked(self, at: float) -> None:
        """A request arrived at ``at``.  While callers the last
        completion released are out, any arrival counts as one of them
        coming back: they cannot be told apart."""
        if self._awaited <= 0:
            return
        self._awaited -= 1
        if self._hold_t0 is not None:
            self.return_hits += 1
            if self._last_return is None:
                # the first one back, in a hold that began with nobody
                # back: the stream of returns begins here, and the hold
                # is scored like any other, on those who are out now
                # (an open loop's next arrival is no caller returning)
                self._hold_from, self._hold_for = at, self._awaited
            else:
                self._hold_hits += 1
            if self._awaited:
                # somebody is still out: the hold goes on from here
                self._hold_renewals += 1
        self._last_return = at

    def _hold_locked(self, now: float) -> float:
        """Seconds the drain that could leave now should still wait for
        the callers the last completion released; <= 0: go.  A hold
        starts here, when a drain first could leave; inside it the
        clock that counts is the one since the last awaited caller came
        back.  Nobody back for one patience: the stream of returns has
        ended, and the drain goes without whoever is still out.  One
        patience is worth no more than what the wait costs those who
        pay it: the callers waiting lose ``waiting x t`` to a wait of
        t, those left behind a service time each.  The limit of the
        whole hold counts from the first return.  Most cycles that is
        where the hold starts; where the drain could leave with nobody
        back (a caller left behind waited for the completion) only the
        limit runs, a caller that is not back having no age, and it
        counts anew when the first is back (:meth:`_return_locked`):
        cut at that distance from the completion, the one would leave
        alone and the others come back to a running program."""
        if self._awaited <= 0:
            return 0.0
        if self._hold_t0 is None:
            if self._hit_share < _HIT_SHARE_ON \
                    and self._since_hold < _HOLD_PROBE_EVERY:
                return 0.0
            if self._hold_cap() <= 0.0:
                return 0.0
            self._hold_t0 = self._hold_from = now
            self._hold_for, self._hold_hits = self._awaited, 0
            self._hold_renewals = 0
            self._since_hold = 0
            self.return_holds += 1
        end = self._hold_from + self._exec_ewma / _HOLD_TOTAL_FRACTION
        if self._last_return is not None:
            patience = min(self._hold_cap(), self._exec_ewma
                           * self._awaited / max(1, len(self._pending)))
            end = min(end, self._last_return + patience)
        return end - now

    def _bind_locked(self, now: float) -> dict:
        """A drain leaves: the hold it waited in, if any, is scored, and
        whoever is still out is left behind and no longer waited for
        (they find a program running and share the next).  Returns what
        the drain's ``serving.queue_wait`` spans say of the batcher's
        state: the verdict it leaves under (``in_flight``: the drains
        dispatched and not completed, 0 for a lone drain, from 1 on a
        drain bound BEHIND a running program), what the verdict was
        taken from (:meth:`_verdict_note`) and ``service_ms``, the S of
        the hold's rule."""
        if self._hold_t0 is not None:
            # the callers' way back, as the hold clocked it: from the
            # completion that released them to the held drain's leaving
            self._t_ret.add(max(0.0, now - self._last_completion))
        cycles = self._cycles()
        depth, why = self._depth(cycles)
        if why == "serial-probe" and self._in_flight:
            # this is the probe's one drain: whoever comes next waits
            # for a free device again
            self._probe_out = True
        held, renewals = 0.0, 0
        if self._hold_t0 is not None:
            held, renewals = now - self._hold_t0, self._hold_renewals
            if self._hold_for:
                share = min(1.0, self._hold_hits / self._hold_for)
                self._hit_share += _HIT_SHARE_GAIN * (
                    share - self._hit_share)
            if self._hold_hits >= self._hold_for:
                # all of them came back (or the first one back was the
                # only one out, which scores nothing): where the hold
                # is off, the next completion tries again, not the 32nd
                # (callers that turn closed-loop are in step within
                # some forty programs, and an open loop's rare hit
                # costs one hold)
                self._since_hold = _HOLD_PROBE_EVERY
            self._hold_t0 = None
        left_behind, self._awaited = self._awaited, 0
        self.return_left_behind += left_behind
        return {"depth": depth, "depth_reason": why,
                "in_flight": self._in_flight,
                **self._verdict_note(cycles),
                "service_ms": round(self._exec_ewma * 1e3, 3),
                "held_ms": round(held * 1e3, 3),
                "renewals": renewals, "left_behind": left_behind,
                "return_hit_share": round(self._hit_share, 3)}

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._stopped:
                    if not self._pending:
                        # the pool's state only while nothing is in
                        # flight either: the device then idles for want
                        # of requests, not behind host work
                        self._await_locked("serving.await_work",
                                           self._in_flight == 0)
                        continue
                    if self._in_flight >= self._in_flight_target():
                        # at the in-flight cap: a full queue must NOT
                        # add dispatches — extra depth only stacks
                        # device-queue latency onto every later request.
                        # Batching under load comes from HERE: a blocked
                        # dispatcher wakes on the next completion and
                        # drains everything that queued during one
                        # service interval.
                        self._await_locked("serving.await_slot", True)
                        continue
                    if len(self._pending) >= self.max_batch:
                        break
                    # a slot is free: go, unless callers the last
                    # completion released are still out and still
                    # coming back.  A lone request on an unloaded
                    # server never waits here: nobody is out.
                    wait = self._hold_locked(clockmod.monotonic())
                    if wait <= 0:
                        break
                    self._await_locked("serving.await_return", True, wait)
                stopped = self._stopped
                if stopped:
                    jobs, self._pending = self._pending, []
                    note = None
                else:
                    t0 = clockmod.monotonic()
                    note = self._bind_locked(t0)
                    jobs = self._pending[:self.max_batch]
                    del self._pending[:self.max_batch]
                    lone = self._in_flight == 0
                    probe = self._probe_out  # set by this bind, or not
                    self._in_flight += 1
                    # the loop as this drain's gap will speak of it: the
                    # requests aboard the drains in flight, its own too
                    self._aboard += len(jobs)
                    aboard = self._aboard
            scored = self._dispatch(jobs, note) if jobs else 0
            if stopped:
                return
            with self._releasing(), self._cond:
                self._in_flight -= 1
                self._aboard -= len(jobs)
                if probe:
                    self._probe_out = False
                # a drain whose every job was deadline-shed made no
                # device call: folding its near-zero wall into the
                # estimators would collapse them long after the
                # deadline burst ends
                if scored:
                    self._learn_locked(t0, lone, scored, aboard)
                # this thread goes round and takes the next drain
                # itself if one can leave.  It wakes another only for
                # what it cannot do: end the wait of the thread that
                # carries the pool's state, or fill a second slot where
                # the depth has room for one.  Anyone else woken here
                # finds nothing to do and takes the interpreter from
                # the handlers that have answers to send
                if self._noted_by is not None or (
                        self._pending
                        and self._in_flight + 1 < self._in_flight_target()):
                    self._wake_locked(1)

    def _learn_locked(self, t0: float, lone: bool, size: int,
                      aboard: int) -> None:
        """A drain of ``size`` requests dispatched at ``t0`` (``lone``:
        with nothing in flight; else ``aboard`` requests rode it and the
        drains in flight before it) has completed: what its wall and
        the gap since the last completion say of the device."""
        now = clockmod.monotonic()
        wall = now - t0
        was_serial = self._serial()
        self._since_lone += 1
        self._since_gap += 1
        self._since_hold += 1
        if lone:
            self._probe_taken(self._since_lone)
            # by the drain's size (a drain holds max_batch at most)
            self._lone_walls[_size_class(size)].add(wall)
            self._wall_min = min(w.mid for w in self._lone_walls
                                 if w.mid is not None)
            self._since_lone = 0
        gap = now - self._last_completion
        if t0 < self._last_completion and gap < _MAX_EXEC_S:
            # it was already dispatched when the drain before it
            # completed, so it waited on the device's queue and the gap
            # between the two completions is the device's per-dispatch
            # service time with its queue kept fed
            self._probe_taken(self._since_gap)
            self._learn_exec(gap)
            # for the verdict, half the time two drains took one behind
            # the other: the mean of this gap and the one before where
            # the last completion brought one too (callers out of step
            # on a device they do not saturate complete in turns of a
            # short gap and a long one), else this gap
            self._gap.add(0.5 * (gap + self._last_gap)
                          if self._since_gap == 1 and self._last_gap
                          else gap)
            self._last_gap = gap
            self._loop_n = aboard
            self._since_gap = 0
        elif lone and (self._serial() or len(self._threads) == 1):
            # at a depth of one no completion gap is ever seen; a lone
            # drain's wall IS the service time there
            self._learn_exec(wall)
        if self._serial() != was_serial:
            # the answer changed: look again soon
            self._probe_every = _PROBE_EVERY
        # a dispatch's whole wall (round trip + exec) upper-bounds exec:
        # clamping lets the estimate relearn DOWNWARD after a hot-swap
        # to a smaller model or an anomalous gap
        self._exec_ewma = max(_MIN_EXEC_S, min(self._exec_ewma, wall))
        self._last_completion = now

    def _probe_taken(self, since: int) -> None:
        """One of the two measurements has come in, ``since``
        completions after it was last seen.  Where that is more than the
        count that arms a probe, the depth in force was hiding it and a
        probe took it: ask again after twice as many."""
        if since > self._probe_every and self._cycles() is not None:
            self.probes += 1
            self._probe_every = min(_PROBE_EVERY_MAX,
                                    2 * self._probe_every)

    def _learn_exec(self, sample: float) -> None:
        if self._exec_measured:
            sample = 0.7 * self._exec_ewma + 0.3 * sample
        self._exec_measured = True
        self._exec_ewma = min(_MAX_EXEC_S, max(_MIN_EXEC_S, sample))

    def _await_locked(self, name: str, pool_state: bool,
                      timeout: float | None = None) -> None:
        """Wait on the condition (held by the caller) until notified or
        ``timeout`` seconds have passed.  Where the wait is the whole
        pool's state and not just this thread's (``pool_state``), ONE
        thread at a time takes it: that thread is the first one
        :meth:`_wake_locked` wakes, so whatever ends the state — work
        arrived, a dispatch completed, a caller came back — reaches the
        thread that waits for it.  With a tracer the wait is also a
        profiler annotation of that name: a device-idle gap in which
        the host had nothing to dispatch then says so, where it would
        otherwise carry no host event at all, and the annotation ends
        when the state does and covers no later gap.  No ring span: no
        request owns the wait.  (A wait that is this thread's alone
        stays bare: the pool's line is then another thread's drain or
        its ``serving.release``, :meth:`_releasing`.)"""
        if not pool_state or self._noted_by is not None:
            self._cond.wait(timeout)  # wall-clock: Condition poll on the real dispatch thread
            return
        me = self._noted_by = object()
        with obstrace.annotation(name) if self._tracer is not None \
                else obstrace.NOOP_SPAN:
            self._noted.wait(timeout)  # wall-clock: Condition poll on the real dispatch thread
        # cleared by whoever woke this thread (_wake_locked, close());
        # after a timeout it is still this thread's to clear, unless
        # another has taken the place since
        if self._noted_by is me:
            self._noted_by = None

    def _releasing(self):
        """What a dispatcher does between the model's return and its
        next wait or drain — the spans recorded, the lock taken, the
        callers released, the lesson learnt — as a profiler annotation,
        ``serving.release``, with a tracer only and like the waits no
        ring span: with it every instant of a dispatcher's cycle is a
        wait, a phase or step of the drain, or this."""
        return obstrace.annotation("serving.release") \
            if self._tracer is not None else obstrace.NOOP_SPAN

    def _wake_locked(self, n: int) -> None:
        """Wake ``n`` waiting dispatchers (the caller holds the
        condition): the one that carries the pool's state first, whose
        state has just ended — work arrived, or a dispatch completed."""
        if self._noted_by is not None:
            self._noted_by = None
            self._noted.notify()
            n -= 1
        if n:
            self._cond.notify(n)

    def _record_spans(self, group: list[_Job], t_exec: float,
                      t_done: float, status: str,
                      phases: obstrace.DrainPhases,
                      note: dict | None) -> None:
        """Queue-wait / device-execute spans for the sampled jobs of a
        drained group, and the drain's phases under each job's
        device-execute span (grandchildren of the request, so the
        request's own children stay the two they were), each phase's
        steps under it.  The queue-wait span carries ``note``: the depth
        in force and why, how many drains were in flight when this one
        left and the two measurements the depth was chosen by, how long
        the drain was held for returning callers, how often one of them
        extended the hold, how many it left behind, the hold's score
        (:meth:`_bind_locked`; shared, read-only from here on).  Recorded
        retroactively from stored monotonic stamps (the dispatcher has
        no thread-local trace context), and strictly best-effort — the
        tracer absorbs recorder failures."""
        traced = [j for j in group if j.trace_ctx is not None]
        if not traced:
            return
        route = getattr(group[0].model, "kernel_route_label", None)
        exec_attrs = {"batch_size": len(group)}
        if route:
            # which measured phase-A kernel variant served this drain
            # (app/als/kernel_router.py's dispatch decision)
            exec_attrs["kernel_route"] = route
        for j in traced:
            self._tracer.record_span("serving.queue_wait", j.trace_ctx,
                                     j.t_enq, t_exec, note)
            exec_id = self._tracer.record_span(
                "serving.device_execute", j.trace_ctx, t_exec, t_done,
                dict(exec_attrs), status)
            phases.replay(self._tracer, j.trace_ctx[0], exec_id)

    def _dispatch(self, jobs: list[_Job], note: dict | None = None) -> int:
        """Score a drained batch; returns how many jobs actually reached
        the device (0 = all shed, caller must not learn pacing from it).
        The slot the drain holds frees when this returns, after the
        decode of its results: 0.07 ms at two callers, and freeing it at
        the fetch would take the model telling the batcher so with
        tracing off, which only the phase recorder could carry."""
        # shed jobs whose budget expired while queued: their client has
        # already given up, and scoring them would tax every live job in
        # the same drain with their share of the device time
        expired = [j for j in jobs
                   if j.deadline is not None and j.deadline.expired]
        if expired:
            with self._cond:
                self.deadline_rejects += len(expired)
            for j in expired:
                j.error = DeadlineExceeded(
                    "request deadline expired while queued")
                j.done.set()
            jobs = [j for j in jobs if j.error is None]
        t_pickup = clockmod.monotonic()
        if jobs:
            # queue wait of this drain = the oldest job's enqueue->pickup
            # age; EWMA'd so the admission signal tracks load, not one
            # straggler.  Sampled BEFORE the dispatch seam below: an
            # injected device delay is service time, and folding it into
            # the wait would inflate the admission signal by one full
            # dispatch even with an empty queue
            qw = max(t_pickup - j.t_enq for j in jobs)
            with self._cond:
                self._qwait_ewma = 0.7 * self._qwait_ewma + 0.3 * qw
                self._qwait_at = t_pickup
        # chaos seam: one fire per drained dispatch.  mode=delay stands
        # in for per-dispatch device time the host does not burn CPU
        # on (a slow or stalled device); mode=error fails the whole
        # drain (surfaced per job, never killing the dispatcher thread)
        try:
            faults.fire("serving-scan-dispatch")
        except Exception as e:  # noqa: BLE001 — injected
            for j in jobs:
                j.error = e
                j.done.set()
            return 0
        by_model: dict[int, list[_Job]] = {}
        for j in jobs:
            by_model.setdefault(id(j.model), []).append(j)
        # the device window opens at drain PICKUP (before the chaos
        # seam): like the admission EWMA above, an injected device
        # delay is service time, so the recorded queue_wait/
        # device_execute split must put it on the device side — tail
        # attribution (obs/anatomy.py) otherwise blames the queue for
        # a slow device.  Groups after the first open at the previous
        # group's completion.
        next_exec_start = t_pickup
        for group in by_model.values():
            model = group[0].model
            t_exec = next_exec_start
            status = "ok"
            # per drain, whatever was sampled: the annotations belong to
            # the dispatcher thread, only the ring spans to requests
            phases = obstrace.DrainPhases() \
                if self._tracer is not None else None
            try:
                with phases or obstrace.NOOP_SPAN:
                    results = model.top_n_batch(
                        [j.how_many for j in group],
                        np.stack([j.vector for j in group]),
                        [j.exclude for j in group])
                for j, r in zip(group, results):
                    j.result = r
            except BaseException as e:  # noqa: BLE001 — surfaced per job
                status = "error"
                for j in group:
                    j.error = e
            next_exec_start = clockmod.monotonic()
            with self._releasing():
                if self._accountant is not None:
                    # continuous occupancy: the same bracket the
                    # device_execute span measures, booked as serve-class
                    # device time against the model's route + generation
                    self._accountant.note(
                        "serve",
                        getattr(model, "kernel_route_label", None),
                        getattr(model, "generation", None),
                        next_exec_start - t_exec)
                if phases is not None:
                    self._record_spans(group, t_exec, next_exec_start,
                                       status, phases, note)
                with self._cond:
                    # under the lock: up to `pipeline` dispatcher threads
                    # land here concurrently, and a bare += loses updates
                    self.batch_sizes.append(len(group))
                    self.total_dispatches += 1
                    if len(self.batch_sizes) > 10000:
                        del self.batch_sizes[:5000]
                    # these callers are out with their answers from here
                    # on (before they are released: one that is back at
                    # once is counted), as far as the next drain has room
                    self._awaited = max(0, min(
                        len(group), self.max_batch - len(self._pending)))
                    self._last_return = None
                for j in group:
                    j.done.set()
        return len(jobs)
