"""What one run hands the per-layer readers: the program's spans and
counters over the window and the reduced device trace.  A reader under
``benchmark/readers/`` is ``read(obs, params) -> float | None``; None
means it found nothing to read, and the harness leaves the metric out.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Observations:
    # finished spans of the window's requests, as obs/trace.py records
    # them: name, trace_id, span_id, parent_id, duration_ms, attrs
    spans: list[dict]
    # program counters read at the window's start and end
    counters_start: dict
    counters_end: dict
    # the size of every drain the batcher dispatched in the window
    batch_sizes: list[int]
    # trace_reduce.reduce_trace's result for the traced slice, or None
    trace: dict | None
    # rows, device_features and itemsize of the served item matrix
    store: dict
    # the device's row of peaks.json, or None where the run has no chip
    peaks: dict | None

    def delta(self, counter: str) -> float | None:
        a, b = self.counters_start.get(counter), self.counters_end.get(counter)
        return None if a is None or b is None else b - a
