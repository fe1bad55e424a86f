"""From the manifest to files: every cell of BENCHMARK.json resolves, a
name without a file is an error, and a cell, a mix and a layer metric
are added as files plus entries (the rehearsal manifest does just
that)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

REHEARSAL = "benchmark/tests/rehearsal_manifest.json"


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def test_every_cell_of_the_benchmark_resolves():
    bench = _bench()
    for w in bench["workloads"]:
        cell = manifest.resolve(ROOT, "BENCHMARK.json", w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, "every cell reports a per-layer metric"
        for m in cell.per_layer:
            assert m.moves in names
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "readers", m.reader))
        if cell.traffic["loop"] == "open":
            assert cell.params["offered_qps"] > 0


def test_the_manifest_keeps_the_contract():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            spec = json.load(f)
        assert spec["source"] == c["source"]
        assert sorted(spec["reduced"]) == sorted(c["reduced"])
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_configuration_names_an_app_with_a_file():
    for c in _bench()["configs"]:
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            app = json.load(f)["app"]
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "apps",
                                           app + ".py"))


def test_an_unknown_cell_is_an_error():
    with pytest.raises(manifest.ManifestError, match="not in"):
        manifest.resolve(ROOT, "BENCHMARK.json", "no-such-cell")


@pytest.mark.parametrize("broken, what", [
    ({"workloads": [{"name": "c", "config": "k", "traffic": "no-such-mix",
                     "chips": 1}]}, "traffic mix"),
    ({"configs": [{"name": "k", "file": "benchmark/configs/none.json"}]},
     "configuration"),
    ({"per_layer": [{"name": "no.such.metric", "unit": "ms",
                     "source": "program_span", "layer": "door",
                     "moves": "setup_s"}]}, "per-layer metric"),
])
def test_a_name_without_a_file_is_an_error(tmp_path, broken, what):
    base = {
        "configs": [{"name": "k",
                     "file": "benchmark/configs/rehearsal-tiny.json"}],
        "workloads": [{"name": "c", "config": "k",
                       "traffic": "two-callers", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }
    base.update(broken)
    path = tmp_path / "m.json"
    path.write_text(json.dumps(base))
    with pytest.raises(manifest.ManifestError, match=what):
        manifest.resolve(ROOT, str(path), "c")


def test_a_layer_metric_must_move_a_metric_the_cell_reports(tmp_path):
    m = {
        "configs": [{"name": "k",
                     "file": "benchmark/configs/rehearsal-tiny.json"}],
        "workloads": [{"name": "c", "config": "k",
                       "traffic": "two-callers", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "door.self_ms", "unit": "ms",
                       "source": "program_span", "layer": "door",
                       "moves": "latency_p50_ms"}],
    }
    path = tmp_path / "m.json"
    path.write_text(json.dumps(m))
    with pytest.raises(manifest.ManifestError, match="does not report"):
        manifest.resolve(ROOT, str(path), "c")


def test_adding_a_cell_a_mix_and_a_layer_metric_needs_files_only():
    """The rehearsal manifest's second cell uses a mix, a cell file, a
    layer metric and a reader that BENCHMARK.json's cells do not: all of
    them are files of their own."""
    cell = manifest.resolve(ROOT, REHEARSAL, "tiny.rehearsal-mix")
    assert cell.traffic["name"] == "rehearsal-mix"
    assert cell.params["knee_qps"] > 0
    assert [m.name for m in cell.per_layer] == ["rehearsal.dispatches",
                                                "batcher.mean_batch"]
    listed = {w["traffic"] for w in _bench()["workloads"]}
    assert "rehearsal-mix" not in listed
