"""The arithmetic of the yardstick: percentiles, the mid-window rate and
generator lateness.  Pure Python over plain sequences, so the load
generator (which never imports JAX or the program) and the harness
compute the same numbers the same way.

The open-loop arithmetic is copied from ``oryx_tpu/bench/load.py``
``run_recommend_open_loop`` (latency from the moment a request was due,
completions over the [15%, 90%) middle of the span); the original is
listed under Open questions in ``PERF.md`` for a later PR to delete.
"""

from __future__ import annotations

import math
import statistics

# the middle of the window over which completions count as the served
# rate: ramp-in and drain excluded (load.py's bounds)
MID_LO, MID_HI = 0.15, 0.90


def percentile(values, p: float) -> float | None:
    """Nearest-rank percentile: the smallest value with at least ``p``
    percent of the sample at or below it.  None for an empty sample."""
    if not len(values):
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def samples_beyond(n: int, p: float) -> int:
    """How many of ``n`` samples lie beyond the nearest-rank ``p``th
    percentile.  The rule: report a percentile only as a tail where at
    least ten samples lie beyond it."""
    if n <= 0:
        return 0
    return n - max(1, math.ceil(p / 100.0 * n))


def median(values) -> float | None:
    return float(statistics.median(values)) if len(values) else None


def mid_window_rate(done_s, span_s: float) -> float | None:
    """Completions per second over the [15%, 90%) middle of a window of
    ``span_s`` seconds; ``done_s`` are completion times from its start.
    Below capacity this is the offered rate, above it the rate served."""
    if span_s <= 0:
        return None
    lo, hi = MID_LO * span_s, MID_HI * span_s
    n = sum(1 for t in done_s if lo <= t < hi)
    return n / (hi - lo)


def lateness(due_s, sent_s) -> dict:
    """How late requests left the generator, in ms from the moment each
    was due: the mean, the 99th percentile, and the drift between the
    third and the last quarter (a backlog that grows through the run).
    In an open loop above capacity this is mostly the wait for a free
    connection; ``generator_lag`` separates the generator's own share."""
    late = [max(0.0, (s - d) * 1e3) for d, s in zip(due_s, sent_s)]
    n = len(late)
    if not n:
        return {"n": 0, "mean_ms": None, "p99_ms": None, "drift_ms": None}
    drift = None
    if n >= 8:
        q3 = late[n // 2:3 * n // 4]
        q4 = late[3 * n // 4:]
        drift = sum(q4) / len(q4) - sum(q3) / len(q3)
    return {"n": n, "mean_ms": sum(late) / n,
            "p99_ms": percentile(late, 99), "drift_ms": drift}
