"""The per-layer metrics that open ``serving.device_execute``: medians
over the program's drain-phase spans (``span_median.py`` through layer
files) and one program's own device time from the reduced trace
(``trace_module_ms.py``, ``trace_module_roofline.py``), each checked
against numbers worked out by hand.  PR 24 brought six; the two that
read the exact-scan fallback (``dispatch.fallback_ms``,
``kernel.exact_scan_ms``) were retired in PR 31, since no certificate
has failed since PR 26.  Their readers stay, other metrics use them, so
the fallback's span and program are still read here, through metrics
built by hand (``RETIRED``)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import costs, manifest, trace_reduce  # noqa: E402
from benchmark.observe import Observations  # noqa: E402

RECORDED = os.path.join(ROOT, "benchmark", "testdata",
                        "v5e_als250_two_callers.json.gz")
CELLS = ["als250-20m.two-callers", "als50-20m.two-callers"]
# name -> (unit, source, layer, moves, reader, params)
NEW = {
    "dispatch.scan_ms": ("ms", "program_span", "dispatch",
                         "latency_p50_ms", "span_median.py",
                         {"span": "serving.scan"}),
    "dispatch.decode_ms": ("ms", "program_span", "dispatch",
                           "latency_p50_ms", "span_median.py",
                           {"span": "serving.decode"}),
    "kernel.twophase_ms": ("ms", "device_trace", "kernels",
                           "latency_p50_ms", "trace_module_ms.py",
                           {"module": "twophase"}),
    "kernel.twophase_roofline": ("%", "device_trace", "kernels",
                                 "latency_p50_ms",
                                 "trace_module_roofline.py",
                                 {"module": "twophase"}),
}
# what the two retired metrics read, as a later PR would name it again
RETIRED = {
    "dispatch.fallback_ms": manifest.LayerMetric(
        name="dispatch.fallback_ms", unit="ms", source="program_span",
        layer="dispatch", moves="latency_p99_ms", reader="span_median.py",
        params={"span": "serving.fallback"}),
    "kernel.exact_scan_ms": manifest.LayerMetric(
        name="kernel.exact_scan_ms", unit="ms", source="device_trace",
        layer="kernels", moves="latency_p99_ms",
        reader="trace_module_ms.py", params={"module": "chunked_kernel"}),
}
STORE_250F = {"rows": 20_054_016, "device_features": 250, "itemsize": 2}


def _listed(cell: str) -> dict:
    """The per-layer metrics ``BENCHMARK.json`` lists for ``cell``."""
    resolved = manifest.resolve(ROOT, "BENCHMARK.json", cell)
    return {m.name: m for m in resolved.per_layer}


def _metrics(cell: str = CELLS[0]) -> dict:
    return dict(RETIRED, **_listed(cell))


def _obs(spans=(), trace=None, batch_sizes=(), peaks=None) -> Observations:
    return Observations(spans=list(spans), counters_start={},
                        counters_end={}, batch_sizes=list(batch_sizes),
                        trace=trace, store=STORE_250F, peaks=peaks)


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.reduce_trace(trace_reduce.load_excerpt(RECORDED))


@pytest.mark.parametrize("cell", CELLS)
def test_the_four_resolve_in_both_cells_as_the_table_has_them(cell):
    metrics = _listed(cell)
    # the static cells' twelve, all of which read since PR 31
    assert len(metrics) == 12 and not set(RETIRED) & set(metrics)
    for name, (unit, source, layer, moves, reader, params) in NEW.items():
        m = metrics[name]
        assert (m.unit, m.source, m.layer, m.moves, m.reader, m.params) \
            == (unit, source, layer, moves, reader, params)


def test_the_manifest_keeps_them_in_order_for_every_cell():
    """PR 24 appended them in one block; PR 27 and PR 29 appended
    theirs behind, and PR 31 took the two retired ones out and their
    layer files with them."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        per_layer = json.load(f)["per_layer"]
    names = [m["name"] for m in per_layer]
    first = names.index(next(iter(NEW)))
    assert names[first:first + len(NEW)] == list(NEW)
    for m in per_layer[first:first + len(NEW)]:
        # no ``workloads`` key: every cell reports them
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert m["better"] == ("higher" if m["name"].endswith("_roofline")
                               else "lower")
    for name in RETIRED:
        assert name not in names
        assert not os.path.exists(os.path.join(
            ROOT, "benchmark", "layers", name + ".json"))


def test_one_programs_own_time_on_the_recorded_v5e_trace(recorded):
    """0.25 s of ``als250-20m.two-callers`` (my chip run, PR 22): seven
    two-phase programs in 0.111841754 s, three exact scans in
    0.139465854 s."""
    metrics = _metrics()
    obs = _obs(trace=recorded, batch_sizes=[1] * 7,
               peaks=costs.peaks_for("TPU v5 lite"))
    assert metrics["kernel.twophase_ms"].read(obs) \
        == pytest.approx(111.841754 / 7)                    # 15.977 ms
    assert metrics["kernel.exact_scan_ms"].read(obs) \
        == pytest.approx(139.465854 / 3)                    # 46.489 ms
    # 20,054,016 x 250 x 2 bytes at 819 GB/s = 12.243 ms least
    # (memory-bound at 8 wide), over 15.977 ms
    least_ms = 1e3 * 20_054_016 * 500 / 819e9
    assert metrics["kernel.twophase_roofline"].read(obs) \
        == pytest.approx(100 * least_ms / (111.841754 / 7))
    assert metrics["kernel.twophase_roofline"].read(obs) \
        == pytest.approx(76.627, abs=1e-3)
    # the old pair holds the fallbacks too: all busy time over 7 windows
    assert metrics["kernel.window_ms"].read(obs) \
        == pytest.approx(251.173658 / 7)
    assert metrics["kernel.window_ms"].read(obs) \
        > metrics["kernel.twophase_ms"].read(obs)


def test_no_matching_program_reads_as_nothing(recorded):
    metrics = _metrics()
    peaks = costs.peaks_for("TPU v5 lite")
    stripped = dict(recorded, modules={
        "jit__batch_top_n_kernel(7)": {"count": 4, "seconds": 0.01}})
    for obs in (_obs(trace=stripped, batch_sizes=[1], peaks=peaks),
                _obs(trace=None, batch_sizes=[1], peaks=peaks)):
        for name in ("kernel.twophase_ms", "kernel.exact_scan_ms",
                     "kernel.twophase_roofline"):
            assert metrics[name].read(obs) is None
    # a trace, but no chip's peaks (a rehearsal) or no drain to size a
    # window by: no roofline, the time still reads
    assert metrics["kernel.twophase_roofline"].read(
        _obs(trace=recorded, batch_sizes=[1])) is None
    assert metrics["kernel.twophase_roofline"].read(
        _obs(trace=recorded, peaks=peaks)) is None
    assert metrics["kernel.twophase_ms"].read(_obs(trace=recorded)) \
        is not None


def _request(n: int, phases: list[tuple[str, float]]) -> list[dict]:
    """The spans of one request as obs/trace.py records them: the
    request, its two children, the drain's phases under the second."""
    def span(name, sid, parent, ms, attrs=None):
        return {"name": name, "trace_id": f"t{n}", "span_id": sid,
                "parent_id": parent, "start_ms": 0.0, "duration_ms": ms,
                "attrs": attrs or {}, "status": "ok"}
    total = sum(ms for _, ms in phases)
    out = [span("serving.request", f"r{n}", None, total + 0.5,
                {"route": "GET /recommend/{userID}"}),
           span("serving.queue_wait", f"q{n}", f"r{n}", 0.1),
           span("serving.device_execute", f"e{n}", f"r{n}", total)]
    return out + [span(name, f"p{n}.{i}", f"e{n}", ms)
                  for i, (name, ms) in enumerate(phases)]


def test_span_medians_through_the_new_layer_files():
    metrics = _metrics()
    quick = _request(1, [("serving.prepare", 0.2), ("serving.scan", 30.0),
                         ("serving.decode", 0.1)])
    missed = _request(2, [("serving.prepare", 0.2), ("serving.scan", 32.0),
                          ("serving.fallback", 46.5),
                          ("serving.decode", 0.3)])
    obs = _obs(spans=quick + missed)
    assert metrics["dispatch.scan_ms"].read(obs) == pytest.approx(31.0)
    assert metrics["dispatch.fallback_ms"].read(obs) == pytest.approx(46.5)
    assert metrics["dispatch.decode_ms"].read(obs) == pytest.approx(0.2)
    # what was read before reads as before: the phases are grandchildren
    assert metrics["dispatch.execute_ms"].read(obs) \
        == pytest.approx((30.3 + 79.0) / 2)
    assert metrics["door.self_ms"].read(obs) == pytest.approx(0.4)
    assert metrics["batcher.queue_wait_ms"].read(obs) == pytest.approx(0.1)
    # no certificate missed: no such span, so no such metric
    assert metrics["dispatch.fallback_ms"].read(_obs(spans=quick)) is None
    # the parent program records none of the three
    parent = [s for s in quick + missed if s["name"] in (
        "serving.request", "serving.queue_wait", "serving.device_execute")]
    for name in ("dispatch.scan_ms", "dispatch.fallback_ms",
                 "dispatch.decode_ms"):
        assert metrics[name].read(_obs(spans=parent)) is None
