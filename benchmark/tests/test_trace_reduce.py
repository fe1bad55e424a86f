"""The reduction from a profiler trace to device metrics: on a trace
written by hand, where every number can be checked on paper, and on the
piece of a real v5e trace kept under ``benchmark/testdata/``."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import costs, trace_reduce  # noqa: E402
from benchmark.observe import Observations  # noqa: E402

MS = 1_000_000


def _by_hand():
    """10 ms of one device: two windows (a two-phase program each, the
    second followed by its fallback's exact scan), 4 ms idle in all."""
    ops = [
        ["fusion.1", 0 * MS, 2 * MS],
        ["while", 2 * MS, 1 * MS],          # encloses the next two
        ["top_k.1", 2 * MS, int(0.4 * MS)],
        ["top_k.1", int(2.5 * MS), int(0.4 * MS)],
        ["fusion.1", 5 * MS, 1 * MS],
        ["sort.9", 7 * MS, 2 * MS],
    ]
    modules = [
        ["jit__batch_top_n_twophase_pallas(1)", 0, 3 * MS],
        ["jit__batch_top_n_twophase_pallas(1)", 5 * MS, 1 * MS],
        ["jit__batch_top_n_chunked_kernel(2)", 7 * MS, 2 * MS],
    ]
    host = [
        ["TopNBatcher", 0, 10 * MS],                       # covers all
        ["PjRtCApiLoadedExecutable::Execute", int(3.2 * MS),
         int(1.5 * MS)],                                   # in gap 3..5
        ["device_get", int(6.1 * MS), int(0.8 * MS)],      # in gap 6..7
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python/4242", "events": host}]},
    ]}


def test_busy_idle_and_self_time_by_hand():
    r = trace_reduce.reduce_trace(_by_hand())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.006)      # 0-3, 5-6, 7-9
    assert r["idle_share"] == pytest.approx(0.4)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.003)
    assert ops["sort.9"] == pytest.approx(0.002)
    assert ops["top_k.1"] == pytest.approx(0.0008)
    assert ops["while"] == pytest.approx(0.0002)    # what it encloses is out
    assert r["device_ops"][0][0] == "fusion.1"      # most time first
    mods = r["modules"]
    assert mods["jit__batch_top_n_twophase_pallas(1)"]["count"] == 2
    assert trace_reduce.module_executions(r, "twophase") == (
        2, pytest.approx(0.004))


def test_idle_gaps_take_the_innermost_host_event():
    gaps = dict(trace_reduce.reduce_trace(_by_hand())["idle_gaps"])
    # gap 3..5 ms: the execute call covers 1.5 ms of it, the thread's
    # own span all 2 ms: the one that covers most wins, and of several
    # that cover it all the innermost would
    assert gaps["python: TopNBatcher"] == pytest.approx(0.004)
    trace = _by_hand()
    trace["planes"][1]["lines"][0]["events"].append(
        ["wait_for_fetch", 3 * MS, 2 * MS])
    gaps = dict(trace_reduce.reduce_trace(trace)["idle_gaps"])
    assert gaps["python: wait_for_fetch"] == pytest.approx(0.002)
    assert sum(gaps.values()) == pytest.approx(0.004)


def test_window_time_and_roofline_from_the_trace():
    r = trace_reduce.reduce_trace(_by_hand())
    # 6 ms of device time over 2 windows
    assert trace_reduce.device_ms_per_window(r, "twophase") \
        == pytest.approx(3.0)
    assert trace_reduce.device_ms_per_window(r, "no-such-kernel") is None
    peaks = costs.peaks_for("TPU v5 lite")
    obs = Observations(
        spans=[], counters_start={}, counters_end={},
        batch_sizes=[1, 2, 1, 1], trace=r,
        store={"rows": 1_000_000, "device_features": 128, "itemsize": 2},
        peaks=peaks)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(ROOT, "benchmark/readers/trace_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    share = reader.read(obs, {"window_module": "twophase"})
    # 256 MB at 819 GB/s = 0.3126 ms least (memory-bound at 8 wide), 3 ms
    assert share == pytest.approx(100 * 0.256e9 / 819e9 / 3e-3)
    assert reader.modal_window([1, 2, 300]) == 8
    assert reader.modal_window([256, 512, 3]) == 256


def test_no_device_plane_reads_as_nothing():
    trace = {"planes": [p for p in _by_hand()["planes"]
                        if p["name"].startswith("/host:")]}
    assert trace_reduce.reduce_trace(trace) is None


def test_costs_and_peaks():
    peaks = costs.peaks_for("TPU v5 lite")
    with pytest.raises(costs.UnknownDevice):
        costs.peaks_for("TPU v9 imaginary")
    n_bytes, flops = costs.scan_window(20_054_016, 250, 2, 256)
    assert n_bytes == 20_054_016 * 500
    assert flops == 2 * 256 * 20_054_016 * 250
    t, bound = costs.least_time_s(n_bytes, flops, peaks)
    assert bound == "compute" and t == pytest.approx(flops / 197e12)
    t, bound = costs.least_time_s(
        *costs.scan_window(20_054_016, 250, 2, 8), peaks)
    assert bound == "memory" and t == pytest.approx(n_bytes / 819e9)
    assert costs.window_sizes(1) == [8]
    assert costs.window_sizes(33) == [256]
    assert costs.window_sizes(257) == [256, 8]
    assert [costs.pad_k(10 + n) for n in (0, 6, 7, 22, 23, 118, 119, 240)] \
        == [16, 16, 32, 32, 64, 128, 256, 256]


RECORDED = os.path.join(ROOT, "benchmark", "testdata",
                        "v5e_als250_two_callers.json.gz")


def test_the_recorded_v5e_trace_reduces():
    """0.25 s of ``als250-20m.two-callers`` on the chip (my chip run,
    PR 22; ``testdata/record_excerpt.py`` cut it): seven 8-wide windows, one
    of them followed by its fallback's exact scan."""
    r = trace_reduce.reduce_trace(trace_reduce.load_excerpt(RECORDED))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.30012882)
    assert r["busy_s"] == pytest.approx(0.251173658)
    windows, seconds = trace_reduce.module_executions(r, "twophase")
    assert windows == 7 and seconds == pytest.approx(0.111841754)
    assert trace_reduce.module_executions(r, "chunked")[0] == 3
    assert trace_reduce.device_ms_per_window(r, "twophase") \
        == pytest.approx(1e3 * 0.251173658 / 7)
    # the Pallas phase A and the fallback's TopK lead; names are short
    assert [name.split()[1] for name, _ in r["device_ops"][:2]] \
        == ["tpu_custom_call", "TopK"]
    assert r["device_ops"][0] == [
        "tpu_custom_call.1 tpu_custom_call f32[156672,8]",
        pytest.approx(0.09647214)]
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    # (labels that sum to under a microsecond are left out)
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=5e-6)
    assert r["idle_gaps"][0][0] == "python3: np.asarray(jax.Array)"


def test_short_op_names():
    long = ('%custom-call.9 = (f32[8,128]{1,0:T(8,128)S(1)}, s32[8,128]'
            '{1,0:T(8,128)S(1)}) custom-call(f32[8,131072]{1,0:T(8,128)S(1)}'
            ' %fusion.18), custom_call_target="TopK", called_computations='
            '{%compare-greater-than.1.clone.clone.clone}')
    assert trace_reduce.short_op_name(long) \
        == "custom-call.9 TopK (f32[8,128], s32[8,128])"
    assert trace_reduce.short_op_name(
        "%fusion.16 = f32[8,131072]{1,0:T(8,128)S(1)} fusion(bf16[8,250] "
        "%a), kind=kOutput") == "fusion.16 fusion f32[8,131072]"
    assert trace_reduce.short_op_name("fusion.1") == "fusion.1"
