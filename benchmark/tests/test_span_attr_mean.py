"""``batcher.left_behind`` (PR 35): the mean of one attribute over the
spans of one name (``span_attr_mean.py`` through its layer file), on
spans built by hand.  A program whose ``serving.queue_wait`` spans lack
the attribute, as every tree before PR 35, reads as nothing."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.observe import Observations  # noqa: E402

CELLS = ["als250-20m.two-callers", "als250-20m.eight-callers",
         "als250-20m-f32-x4.two-callers"]


def _metric(cell: str) -> manifest.LayerMetric:
    resolved = manifest.resolve(ROOT, "BENCHMARK.json", cell)
    return {m.name: m for m in resolved.per_layer}["batcher.left_behind"]


def _obs(spans) -> Observations:
    return Observations(spans=list(spans), counters_start={},
                        counters_end={}, batch_sizes=[], trace=None,
                        store={}, peaks=None)


def _wait(**attrs) -> dict:
    return {"name": "serving.queue_wait", "duration_ms": 1.0,
            "attrs": attrs}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_lists_it_as_the_table_has_it(cell):
    m = _metric(cell)
    assert (m.unit, m.source, m.layer, m.moves, m.reader, m.params) == (
        "callers", "program_span", "batcher", "latency_p99_ms",
        "span_attr_mean.py",
        {"span": "serving.queue_wait", "attr": "left_behind"})


def test_the_mean_over_the_spans_that_carry_it():
    m = _metric(CELLS[1])
    # three drains of 8, 7 and 1 as their sampled requests saw them: one
    # span a request, the drain's note on each
    spans = ([_wait(left_behind=0, held_ms=0.6)] * 8
             + [_wait(left_behind=1, held_ms=2.0)] * 7
             + [_wait(left_behind=0, held_ms=0.0)]
             # other spans and other attributes are not read
             + [{"name": "serving.device_execute", "duration_ms": 17.0,
                 "attrs": {"left_behind": 5, "batch_size": 8}},
                {"name": "serving.scan", "duration_ms": 16.0, "attrs": None}])
    assert m.read(_obs(spans)) == pytest.approx(7 / 16)
    assert m.read(_obs([_wait(left_behind=0)] * 4)) == 0.0


def test_a_program_without_the_attribute_reads_as_nothing():
    m = _metric(CELLS[0])
    parent = [_wait(depth=1, depth_reason="serial", held_ms=0.2,
                    return_hit_share=1.0)] * 6
    assert m.read(_obs(parent)) is None
    assert m.read(_obs([{"name": "serving.queue_wait", "duration_ms": 1.0,
                         "attrs": None}])) is None
    assert m.read(_obs([])) is None
    # a span that carries it among spans that do not: only it counts
    assert m.read(_obs(parent + [_wait(left_behind=2)])) == 2.0
