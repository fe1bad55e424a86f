"""PR 38's two per-layer metrics, through their layer files, on
observations built by hand and on the recorded v5e trace:
``kernel.twophase_slowest_ms`` (the slowest of the traced slice's
two-phase programs, ``trace_module_slowest_ms.py``) and
``kernel.phase_b_row_share`` (``span_attr_mean.py`` over the
``serving.scan`` spans' ``phase_b_row_share``, which a tree before PR 38
does not write and reads as nothing)."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, trace_reduce  # noqa: E402
from benchmark.observe import Observations  # noqa: E402

MS = 1_000_000
RECORDED = os.path.join(ROOT, "benchmark", "testdata",
                        "v5e_als250_two_callers.json.gz")
CELLS = ["als250-20m.two-callers", "als50-20m.two-callers",
         "als250-20m-lambda.two-callers", "als250-20m-f32-x4.two-callers",
         "als250-20m.eight-callers", "als250-20m-lsh03.two-callers"]


def _metric(cell: str, name: str) -> manifest.LayerMetric:
    resolved = manifest.resolve(ROOT, "BENCHMARK.json", cell)
    return {m.name: m for m in resolved.per_layer}[name]


def _obs(spans=(), trace=None) -> Observations:
    return Observations(spans=list(spans), counters_start={},
                        counters_end={}, batch_sizes=[], trace=trace,
                        store={}, peaks=None)


def _trace(modules, devices=1):
    """``devices`` device planes that each ran ``modules`` ([name, start
    ms, duration ms]) back to back."""
    events = [[name, int(at * MS), int(dur * MS)]
              for name, at, dur in modules]
    return trace_reduce.reduce_trace({"planes": [
        {"name": f"/device:TPU:{d}", "lines": [
            {"name": "XLA Modules", "events": events},
            {"name": "XLA Ops", "events": [
                ["fusion.1", s, d] for _, s, d in events]}]}
        for d in range(devices)]})


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_lists_both_as_the_table_has_them(cell):
    slowest = _metric(cell, "kernel.twophase_slowest_ms")
    assert (slowest.unit, slowest.source, slowest.layer, slowest.moves,
            slowest.reader, slowest.params) == (
        "ms", "device_trace", "kernels", "latency_p99_ms",
        "trace_module_slowest_ms.py", {"module": "twophase"})
    share = _metric(cell, "kernel.phase_b_row_share")
    assert (share.unit, share.source, share.layer, share.moves,
            share.reader, share.params) == (
        "%", "program_span", "kernels", "latency_p99_ms",
        "span_attr_mean.py",
        {"span": "serving.scan", "attr": "phase_b_row_share"})


def test_the_slowest_program_not_the_mean_of_the_mix():
    m = _metric(CELLS[0], "kernel.twophase_slowest_ms")
    mean = _metric(CELLS[0], "kernel.twophase_ms")
    # three programs (the profiler gives each compiled program its own
    # id): k = 32 ran three times at 14 ms, k = 256 twice at 16 and 15.8;
    # the exact scan of a fallback is slower than both and is not one
    trace = _trace([
        ["jit__batch_top_n_twophase_pallas(11)", 0, 14.0],
        ["jit__batch_top_n_twophase_pallas(11)", 20, 14.0],
        ["jit__batch_top_n_twophase_pallas(14)", 40, 16.0],
        ["jit__batch_top_n_twophase_pallas(11)", 60, 14.0],
        ["jit__batch_top_n_twophase_pallas(14)", 80, 15.8],
        ["jit__batch_top_n_chunked_kernel(2)", 100, 120.0]])
    assert m.read(_obs(trace=trace)) == pytest.approx(15.9)
    assert mean.read(_obs(trace=trace)) == pytest.approx(73.8 / 5)


def test_on_the_recorded_trace_it_is_the_slowest_of_four_programs():
    """The kept piece of a real v5e trace (PR 22, the parent's phase B:
    seven 8-wide windows in four compiled two-phase programs, and three
    exact scans of ~46 ms that are none): 63.906 ms over four
    executions, and 15.970 / 15.993 / 15.973 ms once each."""
    with gzip.open(RECORDED, "rt") as fh:
        trace = trace_reduce.reduce_trace(json.load(fh))
    m = _metric(CELLS[0], "kernel.twophase_slowest_ms")
    mean = _metric(CELLS[0], "kernel.twophase_ms")
    assert m.read(_obs(trace=trace)) == pytest.approx(15.993198)
    assert mean.read(_obs(trace=trace)) == pytest.approx(
        (63.906042 + 15.969701 + 15.993198 + 15.972813) / 7)
    assert m.read(_obs(trace=trace)) > mean.read(_obs(trace=trace))


def test_on_four_chips_it_is_a_chips_program():
    m = _metric(CELLS[3], "kernel.twophase_slowest_ms")
    trace = _trace([["jit_sharded_twophase_top_k(7)", 0, 7.2],
                    ["jit_sharded_twophase_top_k(9)", 10, 10.1],
                    ["jit_sharded_twophase_top_k(9)", 30, 10.0]],
                   devices=4)
    assert trace["modules"]["jit_sharded_twophase_top_k(9)"]["count"] == 8
    assert m.read(_obs(trace=trace)) == pytest.approx(10.05)


def test_no_trace_and_no_such_program_read_as_nothing():
    m = _metric(CELLS[0], "kernel.twophase_slowest_ms")
    assert m.read(_obs()) is None
    assert m.read(_obs(trace=_trace(
        [["jit__batch_top_n_chunked_kernel(2)", 0, 120.0]]))) is None


def _scan(**attrs) -> dict:
    return {"name": "serving.scan", "duration_ms": 15.0, "attrs": attrs}


def test_the_row_share_is_the_mean_over_the_scan_spans():
    m = _metric(CELLS[0], "kernel.phase_b_row_share")
    # two callers on an [8] three times, one caller once
    spans = [_scan(k=32, ksel=64, windows=[8], lane_rows=1,
                   real_rows=2, phase_b_row_share=25.0)] * 3 \
        + [_scan(k=16, ksel=32, windows=[8], lane_rows=1,
                 real_rows=1, phase_b_row_share=12.5)] \
        + [{"name": "serving.decode", "duration_ms": 0.1,
            "attrs": {"rows": 2, "phase_b_row_share": 99.0}}]
    assert m.read(_obs(spans)) == pytest.approx((3 * 25.0 + 12.5) / 4)


def test_a_tree_before_pr_38_reads_as_nothing():
    m = _metric(CELLS[4], "kernel.phase_b_row_share")
    parent = [_scan(k=32, ksel=64, windows=[8], lane_rows=1)] * 5
    assert m.read(_obs(parent)) is None
    assert m.read(_obs([])) is None
