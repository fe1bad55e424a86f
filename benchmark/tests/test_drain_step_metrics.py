"""PR 39's seven per-layer metrics, through their layer files: the four
steps of a drain (``dispatch.upload_ms`` / ``launch_ms`` /
``device_wait_ms`` / ``fetch_ms``: ``span_median.py`` over the step
spans) and the batcher's verdict (``batcher.single_share`` and
``batcher.behind_share``: ``span_attr_share.py``;
``batcher.tail_single_share``: ``tail_attr_share.py``), on spans built
by hand, and all seven from the tiny rehearsal cell on the CPU.  A
program without the steps, as every tree before PR 39, reads as
nothing."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.observe import Observations  # noqa: E402

CELLS = ["als250-20m.two-callers", "als50-20m.two-callers",
         "als250-20m-lambda.two-callers", "als250-20m-f32-x4.two-callers",
         "als250-20m.eight-callers", "als250-20m-lsh03.two-callers"]
ROUTE = "GET /recommend/{userID}"
# name: (unit, layer, moves, reader, params)
SEVEN = {
    "dispatch.upload_ms": ("ms", "dispatch", "latency_p50_ms",
                           "span_median.py", {"span": "serving.upload"}),
    "dispatch.launch_ms": ("ms", "dispatch", "latency_p50_ms",
                           "span_median.py", {"span": "serving.launch"}),
    "dispatch.device_wait_ms": ("ms", "dispatch", "latency_p50_ms",
                                "span_median.py",
                                {"span": "serving.device_wait"}),
    "dispatch.fetch_ms": ("ms", "dispatch", "latency_p50_ms",
                          "span_median.py", {"span": "serving.fetch"}),
    "batcher.single_share": ("%", "batcher", "latency_p99_ms",
                             "span_attr_share.py",
                             {"span": "serving.device_execute",
                              "attr": "batch_size", "equals": 1}),
    "batcher.behind_share": ("%", "batcher", "latency_p99_ms",
                             "span_attr_share.py",
                             {"span": "serving.queue_wait",
                              "attr": "in_flight", "at_least": 1}),
    "batcher.tail_single_share": ("%", "batcher", "latency_p99_ms",
                                  "tail_attr_share.py",
                                  {"span": "serving.request", "route": ROUTE,
                                   "percentile": 99,
                                   "child": "serving.device_execute",
                                   "attr": "batch_size", "equals": 1}),
}


def _metrics(cell: str = CELLS[0]) -> dict:
    resolved = manifest.resolve(ROOT, "BENCHMARK.json", cell)
    return {m.name: m for m in resolved.per_layer}


def _obs(spans) -> Observations:
    return Observations(spans=list(spans), counters_start={},
                        counters_end={}, batch_sizes=[], trace=None,
                        store={}, peaks=None)


def _request(n: int, ms: float, batch_size=None, route: str = ROUTE,
             in_flight=None, steps=()) -> list[dict]:
    """One sampled request as the ring holds it: the root, its two
    children and, under ``serving.device_execute``, the scan with its
    ``steps`` [(name, ms), ...] as children."""
    root = {"name": "serving.request", "span_id": f"r{n}",
            "parent_id": None, "duration_ms": ms, "attrs": {"route": route}}
    wait = {"name": "serving.queue_wait", "span_id": f"w{n}",
            "parent_id": f"r{n}", "duration_ms": 0.2,
            "attrs": {"depth": 1, "depth_reason": "serial"}
            if in_flight is None else
            {"depth": 1, "depth_reason": "serial", "in_flight": in_flight,
             "overlap_share": None, "service_ms": 14.2}}
    execute = {"name": "serving.device_execute", "span_id": f"e{n}",
               "parent_id": f"r{n}", "duration_ms": ms - 1.0,
               "attrs": {"kernel_route": "pallas"} if batch_size is None
               else {"batch_size": batch_size, "kernel_route": "pallas"}}
    scan = {"name": "serving.scan", "span_id": f"s{n}",
            "parent_id": f"e{n}", "duration_ms": ms - 1.5,
            "attrs": {"k": 32}}
    return [root, wait, execute, scan] + [
        {"name": name, "span_id": f"{name}{n}", "parent_id": f"s{n}",
         "duration_ms": d, "attrs": {}} for name, d in steps]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_lists_the_seven_as_the_table_has_them(cell):
    metrics = _metrics(cell)
    for name, (unit, layer, moves, reader, params) in SEVEN.items():
        m = metrics[name]
        assert (m.unit, m.source, m.layer, m.moves, m.reader, m.params) \
            == (unit, "program_span", layer, moves, reader, params)


def test_the_manifest_has_them_last_and_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        per_layer = json.load(f)["per_layer"]
    assert [m["name"] for m in per_layer[-len(SEVEN):]] == list(SEVEN)
    for m in per_layer[-len(SEVEN):]:
        # no ``workloads`` key: every cell reports them
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert m["better"] == "lower"


def test_the_step_medians_read_the_step_spans():
    metrics = _metrics()
    spans = []
    for n, (launch, wait, fetch) in enumerate(
            [(0.30, 14.3, 0.40), (0.34, 14.9, 0.44), (0.90, 15.0, 0.50)]):
        spans += _request(n, 17.0, batch_size=2, in_flight=0, steps=[
            ("serving.upload", 0.25), ("serving.launch", launch),
            ("serving.device_wait", wait), ("serving.fetch", fetch)])
    obs = _obs(spans)
    assert metrics["dispatch.upload_ms"].read(obs) == 0.25
    assert metrics["dispatch.launch_ms"].read(obs) == 0.34
    assert metrics["dispatch.device_wait_ms"].read(obs) == 14.9
    assert metrics["dispatch.fetch_ms"].read(obs) == 0.44
    # the scan they tile still reads as it did
    assert metrics["dispatch.scan_ms"].read(obs) == 15.5


def test_a_tree_before_the_steps_reads_as_nothing():
    metrics = _metrics(CELLS[3])
    parent = [s for n in range(6) for s in _request(n, 11.0, batch_size=2)]
    for name in ("dispatch.upload_ms", "dispatch.launch_ms",
                 "dispatch.device_wait_ms", "dispatch.fetch_ms",
                 "batcher.behind_share"):
        assert metrics[name].read(_obs(parent)) is None
        assert metrics[name].read(_obs([])) is None
    # what the parent's spans do carry is read on the parent too
    assert metrics["batcher.single_share"].read(_obs(parent)) == 0.0
    assert metrics["batcher.tail_single_share"].read(_obs(parent)) == 0.0


@pytest.mark.parametrize("name, attr, hit, miss", [
    ("batcher.single_share", "batch_size", 1, 2),
    ("batcher.behind_share", "in_flight", 1, 0)])
def test_the_share_of_none_of_all_and_of_some(name, attr, hit, miss):
    m = _metrics(CELLS[5])[name]

    def spans(values):
        return [s for n, v in enumerate(values)
                for s in _request(n, 8.0, **{attr: v})]

    assert m.read(_obs(spans([miss] * 5))) == 0.0
    assert m.read(_obs(spans([hit] * 5))) == 100.0
    assert m.read(_obs(spans([hit] + [miss] * 3))) == 25.0
    # a span that lacks the attribute is not counted, and spans that all
    # lack it read as nothing
    assert m.read(_obs(spans([hit, miss]) + _request(9, 8.0))) == 50.0
    assert m.read(_obs(_request(9, 8.0))) is None
    assert m.read(_obs([])) is None


def test_behind_counts_every_drain_bound_behind_a_running_program():
    m = _metrics()["batcher.behind_share"]
    spans = [s for n, v in enumerate([0, 1, 2, 3])
             for s in _request(n, 8.0, in_flight=v)]
    assert m.read(_obs(spans)) == 75.0
    # ``overlap_share`` is null while unmeasured: a null is no value
    unmeasured = manifest.LayerMetric(
        name="x", unit="%", source="program_span", layer="batcher",
        moves="latency_p99_ms", reader="span_attr_share.py",
        params={"span": "serving.queue_wait", "attr": "overlap_share",
                "at_least": 0.25})
    assert unmeasured.read(_obs(spans)) is None


@pytest.mark.parametrize("requests, tail", [(100, 2), (7040, 71)])
def test_the_tail_is_the_nearest_rank_hundredth(requests, tail):
    """As the load generator counts p99 (``stats.percentile``): of 7,040
    requests the 71 from the 6,970th on (PERF.md section 7 (iv), "66 of
    71"), of 100 the last two."""
    m = _metrics(CELLS[3])["batcher.tail_single_share"]
    spans = []
    for n in range(requests):
        # the slowest ``tail`` ride drains of one, but for five; one
        # just under the rank does too and is not counted
        slow = n >= requests - tail
        single = (slow and n % 16 != 0) or n == requests - tail - 1
        spans += _request(n, 10.0 + n * 1e-3, batch_size=1 if single else 2)
    singles = sum(1 for n in range(requests - tail, requests) if n % 16)
    assert m.read(_obs(spans)) == pytest.approx(100.0 * singles / tail)
    # /pref roots, however slow, are no request of the route
    writes = [s for n in range(requests, requests + 50)
              for s in _request(n, 500.0, batch_size=2,
                                route="POST /pref/{userID}/{itemID}")]
    assert m.read(_obs(spans + writes)) \
        == pytest.approx(100.0 * singles / tail)


def test_a_tail_without_the_child_reads_as_nothing():
    m = _metrics()["batcher.tail_single_share"]
    roots = [s for n in range(10) for s in _request(n, 10.0 + n)
             if s["name"] == "serving.request"]
    assert m.read(_obs(roots)) is None
    assert m.read(_obs([])) is None
    # one request is its own tail
    assert m.read(_obs(_request(0, 10.0, batch_size=1))) == 100.0


def test_the_rehearsal_cell_reports_all_seven_on_the_cpu():
    """The whole command at a toy size, through a manifest of its own
    (``rehearsal_steps_manifest.json``) that lists the seven as counters
    because a rehearsal reads nothing else: that the steps are recorded,
    parented and read through the layer files is what is held here,
    never what a step takes on the CPU."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark/run.py"),
         "--workload", "tiny-steps.two-callers", "--seed", "3900000011",
         "--seconds", "3", "--trace", "1", "--manifest",
         "benchmark/tests/rehearsal_steps_manifest.json", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(metrics) == set(SEVEN) | {"batcher.mean_batch"}
    for name in SEVEN:
        assert metrics[name] >= 0.0
    for name in ("batcher.single_share", "batcher.behind_share",
                 "batcher.tail_single_share"):
        assert metrics[name] <= 100.0
    # a mean batch of two is no drain of one, and the other way round
    if metrics["batcher.mean_batch"] == 2.0:
        assert metrics["batcher.single_share"] == 0.0
    if metrics["batcher.mean_batch"] == 1.0:
        assert metrics["batcher.single_share"] == 100.0
