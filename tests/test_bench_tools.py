"""Tier-1 coverage for the ISSUE 3 bench tooling: the grid-regression
CI guard (bench/check_regression.py), a small-shape roofline-probe
invocation, and the AOT warmup's shape planning — all CPU-cheap."""

from __future__ import annotations

import json

import numpy as np
import pytest

from oryx_tpu.bench import check_regression as cr


def _grid_doc(cells, backend="tpu"):
    return {"metric": "als_recommend_http_grid", "backend": backend,
            "rows": [{"features": f, "items": i, "lsh": lsh,
                      "open_loop_sustained_qps": qps, "qps": qps * 1.2,
                      "device_exec_ms": 10.0}
                     for (f, i, lsh, qps) in cells]}


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_check_regression_passes_within_threshold(tmp_path, capsys):
    prev = _grid_doc([(50, 10**6, False, 100.0), (50, 10**6, True, 200.0)])
    cur = _grid_doc([(50, 10**6, False, 95.0), (50, 10**6, True, 260.0)])
    rc = cr.main(["--previous", _write(tmp_path, "BENCH_GRID_r05.json", prev),
                  "--current", _write(tmp_path, "BENCH_GRID_r06.json", cur)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert not report["regressions"]
    assert len(report["improved"]) == 1


def test_check_regression_fails_on_over_10pct_drop(tmp_path, capsys):
    prev = _grid_doc([(50, 10**6, False, 100.0), (250, 10**6, False, 50.0)])
    cur = _grid_doc([(50, 10**6, False, 89.0), (250, 10**6, False, 50.0)])
    rc = cr.main(["--previous", _write(tmp_path, "BENCH_GRID_r05.json", prev),
                  "--current", _write(tmp_path, "BENCH_GRID_r06.json", cur)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert len(report["regressions"]) == 1
    assert report["regressions"][0]["cell"] == "50f/1M"


def test_check_regression_skips_cross_backend(tmp_path, capsys):
    prev = _grid_doc([(50, 10**6, False, 100.0)], backend="tpu")
    cur = _grid_doc([(50, 10**6, False, 1.0)], backend="cpu")
    rc = cr.main(["--previous", _write(tmp_path, "BENCH_GRID_r05.json", prev),
                  "--current", _write(tmp_path, "BENCH_GRID_r06.json", cur)])
    assert rc == 0
    assert "backend mismatch" in json.loads(capsys.readouterr().out)["skipped"]


def test_check_regression_discovers_newest_rounds(tmp_path, capsys):
    _write(tmp_path, "BENCH_GRID_r04.json",
           _grid_doc([(50, 10**6, False, 500.0)]))
    _write(tmp_path, "BENCH_GRID_r05.json",
           _grid_doc([(50, 10**6, False, 100.0)]))
    _write(tmp_path, "BENCH_GRID_r06.json",
           _grid_doc([(50, 10**6, False, 50.0)]))
    # newest (r06) vs prior (r05): the r04 value must NOT be the base
    rc = cr.main(["--dir", str(tmp_path)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert report["previous"] == "BENCH_GRID_r05.json"
    assert report["current"] == "BENCH_GRID_r06.json"
    # zero-sustained previous cells never divide by zero
    _write(tmp_path, "BENCH_GRID_r07.json",
           _grid_doc([(50, 10**6, False, 0.0)]))
    _write(tmp_path, "BENCH_GRID_r08.json",
           _grid_doc([(50, 10**6, False, 10.0)]))
    assert cr.main(["--dir", str(tmp_path)]) == 0


def test_check_regression_walks_back_to_same_backend_round(tmp_path,
                                                           capsys):
    """A CPU smoke round committed between two TPU rounds must not
    un-gate the TPU sequence: r07 (tpu) compares against r05 (tpu),
    skipping the cpu r06 — and a >10% drop across that gap still
    fails."""
    _write(tmp_path, "BENCH_GRID_r05.json",
           _grid_doc([(50, 10**6, False, 100.0)], backend="tpu"))
    _write(tmp_path, "BENCH_GRID_r06.json",
           _grid_doc([(50, 10**6, False, 1.0)], backend="cpu"))
    _write(tmp_path, "BENCH_GRID_r07.json",
           _grid_doc([(50, 10**6, False, 80.0)], backend="tpu"))
    rc = cr.main(["--dir", str(tmp_path)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert report["previous"] == "BENCH_GRID_r05.json"
    assert report["skipped_rounds"] == ["BENCH_GRID_r06.json"]
    assert len(report["regressions"]) == 1
    # no same-backend prior round at all -> skip, exit 0
    _write(tmp_path, "BENCH_GRID_r08.json",
           _grid_doc([(50, 10**6, False, 5.0)], backend="gpu"))
    assert cr.main(["--dir", str(tmp_path)]) == 0
    assert "no prior grid round" in \
        json.loads(capsys.readouterr().out)["skipped"]


def test_check_regression_single_round_is_ok(tmp_path, capsys):
    _write(tmp_path, "BENCH_GRID_r06.json", _grid_doc([]))
    assert cr.main(["--dir", str(tmp_path)]) == 0
    assert "skipped" in json.loads(capsys.readouterr().out)


def test_kernel_probe_small_shape_roofline():
    """Small-shape probe invocation: the roofline decomposition fields
    the grid publishes must be present and self-consistent on a CPU
    streaming shape (the tier-1-safe stand-in for the 20M cells)."""
    from oryx_tpu.app.als import serving_model as sm
    from oryx_tpu.app.als.serving_model import ALSServingModel
    from oryx_tpu.bench.kernel_probe import measure_peaks, probe_model

    rng = np.random.default_rng(3)
    model = ALSServingModel(features=50, implicit=True)
    n = 8192
    model.Y.bulk_load([f"i{j}" for j in range(n)],
                      rng.standard_normal((n, 50)).astype(np.float32))
    old = (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS, sm._PA_TILE)
    sm._FLAT_SCORES_LIMIT = 1
    sm._MAX_CHUNK_ROWS = 2048
    sm._PA_TILE = 2048
    try:
        peaks = measure_peaks(m=3)
        assert peaks["hbm_gb_per_s"] is None \
            or peaks["hbm_gb_per_s"] > 0
        out = probe_model(model, batch=32, m=3, peaks=peaks)
    finally:
        (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS, sm._PA_TILE) = old
    assert out["streaming"]
    tw = out["twophase"]
    roof = tw.get("roofline")
    if tw.get("unmeasurable") or roof is None:
        pytest.skip("timer noise swallowed the m-queue delta")
    # analytic bytes: the scan build streams the lane-padded store plus
    # the (B, N) score spill, write+read
    assert roof["phase_a_bytes"] >= n * 128 * 4
    assert roof["phase_a_flops"] == 2 * 32 * n * 128
    if "phase_b_ms" in roof:
        assert roof["phase_a_ms"] + roof["phase_b_ms"] == pytest.approx(
            tw["exec_ms"], rel=1e-6)


def test_warmup_planned_capacity_matches_bulk_load():
    """The AOT warmup's shape planning must predict the EXACT padded
    capacity a real bulk_load produces — a one-row drift would compile
    a ladder no model load ever hits."""
    from oryx_tpu.app.als.feature_vectors import (FeatureVectorStore,
                                                  planned_capacity)

    for n in (1, 16, 17, 40, 1000, 131072, 131073, 400000):
        store = FeatureVectorStore(8)
        store.bulk_load([f"i{j}" for j in range(n)],
                        np.zeros((n, 8), np.float32))
        assert len(store.row_ids()) == planned_capacity(n), n
    # ... and for the REAL serving load path: set_expected_ids
    # pre-sizes via reserve(), so a per-UP-message replay fills the
    # planned (warmed) capacity in place instead of pow2-regrowing
    # through shapes the warmup never compiled
    n = 3000
    store = FeatureVectorStore(8)
    store.reserve(n)
    assert len(store.row_ids()) == planned_capacity(n)
    for j in range(n):
        store.set_vector(f"i{j}", np.ones(8, np.float32))
    assert len(store.row_ids()) == planned_capacity(n)  # no regrow


def test_warmup_cli_reports_compiles(tmp_path):
    """The warmup subcommand compiles a tiny ladder into a fresh cache
    dir and reports per-kernel outcomes (pallas failures on CPU are
    recorded, never fatal)."""
    import os
    import subprocess
    import sys

    conf = tmp_path / "w.conf"
    conf.write_text(
        'oryx { compile-cache-dir = "%s" }\n' % (tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "-m", "oryx_tpu", "warmup", "--conf",
         str(conf), "--items", "0.002", "--features", "8",
         "--dtypes", "float32"],
        capture_output=True, text=True,
        # no exported cache placement: the conf's directory decides
        env={k: v for k, v in dict(os.environ, JAX_PLATFORMS="cpu").items()
             if k != "JAX_COMPILATION_CACHE_DIR"})
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["metric"] == "aot_warmup"
    assert report["compiled_count"] > 0 and report["ok"]
    assert report["cache_dir"] == str(tmp_path / "cache")


# -- gateway scaling regression gate (ISSUE 4 satellite) ---------------------

def _gateway_doc(cells, backend="cpu"):
    """Cells are (features, items, replicas, qps) or, since the r09
    replica-group dimension, (features, items, replicas, R, qps)."""
    rows = []
    for cell in cells:
        f, i, n, *rest = cell
        rps, qps = (rest[0], rest[1]) if len(rest) == 2 \
            else (None, rest[0])
        row = {"features": f, "items": i, "replicas": n,
               "open_loop_sustained_qps": qps,
               "merge_spotcheck_ok": True}
        if rps is not None:
            row["replicas_per_shard"] = rps
        rows.append(row)
    return {"metric": "gateway_recommend_scaling", "backend": backend,
            "rows": rows}


def test_check_regression_gateway_passes_and_reports_cells(tmp_path,
                                                           capsys):
    prev = _gateway_doc([(50, 65536, 1, 100.0), (50, 65536, 2, 170.0)])
    cur = _gateway_doc([(50, 65536, 1, 98.0), (50, 65536, 2, 200.0)])
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r07.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r08.json", cur)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert not report["regressions"]
    assert {c["cell"] for c in report["ok"] + report["improved"]} == \
        {"50f/0.065536M/1rep", "50f/0.065536M/2rep"}


def test_check_regression_gateway_fails_on_per_replica_cell_drop(
        tmp_path, capsys):
    """The 2-replica cell dropping >10% fails even when the 1-replica
    cell held — scaling regressions gate per replica count."""
    prev = _gateway_doc([(50, 65536, 1, 100.0), (50, 65536, 2, 170.0)])
    cur = _gateway_doc([(50, 65536, 1, 101.0), (50, 65536, 2, 140.0)])
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r07.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r08.json", cur)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert [c["cell"] for c in report["regressions"]] == \
        ["50f/0.065536M/2rep"]


def test_check_regression_gateway_replica_group_cells_gate_independently(
        tmp_path, capsys):
    """An R=2 replica-group cell regressing fails the gate even when
    its R=1 sibling at the same shard count improved — and rows
    without the field (pre-r09 artifacts) join the R=1 key."""
    prev = _gateway_doc([(50, 65536, 2, 170.0),          # implicit R=1
                         (50, 65536, 2, 2, 160.0)])
    cur = _gateway_doc([(50, 65536, 2, 1, 190.0),        # explicit R=1
                        (50, 65536, 2, 2, 120.0)])
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r08.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r09.json", cur)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert [c["cell"] for c in report["regressions"]] == \
        ["50f/0.065536M/2repx2"]
    assert [c["cell"] for c in report["improved"]] == \
        ["50f/0.065536M/2rep"]


def test_check_regression_gateway_new_replica_group_cell_not_gated(
        tmp_path, capsys):
    """A first-ever R-cell has no baseline: reported as new, exit 0."""
    prev = _gateway_doc([(50, 65536, 2, 170.0)])
    cur = _gateway_doc([(50, 65536, 2, 1, 168.0),
                        (50, 65536, 2, 2, 150.0)])
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r08.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r09.json", cur)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["new_cells"] == ["(50, 65536, 2, 2)"]
    assert not report["missing_cells"]


def test_check_regression_gateway_zipf_cells_gate_independently(
        tmp_path, capsys):
    """The r11 hot-user Zipf rung gates as its own pseudo-cell: a
    result-cache regression (zipf qps collapsing back toward the cold
    ceiling) fails the gate even when the cold cell held."""
    prev = _gateway_doc([(50, 65536, 1, 100.0)])
    prev["rows"][0]["zipf"] = {"a": 1.2,
                               "open_loop_sustained_qps": 900.0}
    cur = _gateway_doc([(50, 65536, 1, 101.0)])
    cur["rows"][0]["zipf"] = {"a": 1.2,
                              "open_loop_sustained_qps": 300.0}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r09.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r11.json", cur)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert [c["cell"] for c in report["regressions"]] == \
        ["50f/0.065536M/1rep/zipf"]


def test_check_regression_gateway_zipf_cell_back_compat(tmp_path,
                                                        capsys):
    """Pre-cache artifacts carry no zipf rung: the new pseudo-cell is
    reported as new and never gated against the cold baseline."""
    prev = _gateway_doc([(50, 65536, 1, 100.0)])           # r09 shape
    cur = _gateway_doc([(50, 65536, 1, 99.0)])
    cur["rows"][0]["zipf"] = {"a": 1.2,
                              "open_loop_sustained_qps": 800.0}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r09.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r11.json", cur)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["new_cells"] == ["(50, 65536, 1, 1, 'zipf')"]
    assert not report["regressions"]


def test_check_regression_gateway_load_cell_gates_on_load_speed(
        tmp_path, capsys):
    """The r12 model-load telemetry gates as its own pseudo-cell on
    1/model_load_s: a slice-load regression (load time blowing back up
    toward the full-replay cost) fails the gate even when the cold qps
    cell held."""
    prev = _gateway_doc([(50, 65536, 2, 100.0)])
    prev["rows"][0]["model_load"] = {"mode": "slices",
                                     "max_replica_load_s": 5.0}
    cur = _gateway_doc([(50, 65536, 2, 101.0)])
    cur["rows"][0]["model_load"] = {"mode": "slices",
                                    "max_replica_load_s": 20.0}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r11.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r12.json", cur)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert [c["cell"] for c in report["regressions"]] == \
        ["50f/0.065536M/2rep/load"]
    # and a faster load gates green (reported improved, never failed)
    cur["rows"][0]["model_load"]["max_replica_load_s"] = 2.0
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r11.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r12.json", cur)])
    assert rc == 0


def test_check_regression_gateway_load_cell_back_compat(tmp_path,
                                                        capsys):
    """r07/r09/r11 artifacts carry no model_load block: the load
    pseudo-cell is reported as new, never gated against them."""
    prev = _gateway_doc([(50, 65536, 2, 100.0)])           # r11 shape
    cur = _gateway_doc([(50, 65536, 2, 99.0)])
    cur["rows"][0]["model_load"] = {"mode": "slices",
                                    "max_replica_load_s": 4.2}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r11.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r12.json", cur)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["new_cells"] == ["(50, 65536, 2, 1, 'load')"]
    assert not report["regressions"]


def test_check_regression_gateway_mirror_cell_gates_on_catchup_speed(
        tmp_path, capsys):
    """The r13 two-region mirror probe (ISSUE 11) gates as its own
    pseudo-cell on healed-partition catch-up records/s: a mirror
    replay-throughput regression fails the gate even when the qps cell
    held, and steady staleness rides along for diagnosis."""
    prev = _gateway_doc([(50, 65536, 1, 100.0)])
    prev["rows"][0]["mirror"] = {"catch_up_records_per_s": 900.0,
                                 "catch_up_s": 2.2,
                                 "steady_staleness_ms": 90.0}
    cur = _gateway_doc([(50, 65536, 1, 101.0)])
    cur["rows"][0]["mirror"] = {"catch_up_records_per_s": 500.0,
                                "catch_up_s": 4.0,
                                "steady_staleness_ms": 95.0}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r12.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r13.json", cur)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert [c["cell"] for c in report["regressions"]] == \
        ["50f/0.065536M/1rep/mirror"]
    # a faster catch-up gates green
    cur["rows"][0]["mirror"]["catch_up_records_per_s"] = 1800.0
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r12.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r13.json", cur)])
    assert rc == 0


def test_check_regression_gateway_mirror_cell_back_compat(tmp_path,
                                                          capsys):
    """Pre-region artifacts carry no mirror block: the pseudo-cell is
    reported new, never gated against them."""
    prev = _gateway_doc([(50, 65536, 1, 100.0)])           # r12 shape
    cur = _gateway_doc([(50, 65536, 1, 99.0)])
    cur["rows"][0]["mirror"] = {"catch_up_records_per_s": 900.0,
                                "catch_up_s": 2.2,
                                "steady_staleness_ms": 90.0}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r12.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r13.json", cur)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["new_cells"] == ["(50, 65536, 1, 1, 'mirror')"]
    assert not report["regressions"]


def test_check_regression_gateway_conns_cell_gates_on_sustained_qps(
        tmp_path, capsys):
    """The r14 connection-count rung (C10K front end, ISSUE 12) gates
    as its own pseudo-cell: the async front end losing throughput at
    high connection counts fails the gate even when the low-
    concurrency cold cell held; socket/thread telemetry rides along."""
    prev = _gateway_doc([(50, 65536, 1, 100.0)])
    prev["rows"][0]["conns"] = {
        "connections": 4096, "open_loop_sustained_qps": 900.0,
        "router_threads_at_load": 44, "hit_p50_ms": 0.8}
    cur = _gateway_doc([(50, 65536, 1, 101.0)])
    cur["rows"][0]["conns"] = {
        "connections": 4096, "open_loop_sustained_qps": 400.0,
        "router_threads_at_load": 45, "hit_p50_ms": 2.2}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r13.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r14.json", cur)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert [c["cell"] for c in report["regressions"]] == \
        ["50f/0.065536M/1rep/conns"]
    # errors during the rung zero the gated number: also a failure
    cur["rows"][0]["conns"]["open_loop_sustained_qps"] = 0.0
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r13.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r14.json", cur)])
    assert rc == 1
    # and a healthy rung gates green
    cur["rows"][0]["conns"]["open_loop_sustained_qps"] = 950.0
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r13.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r14.json", cur)])
    assert rc == 0


def test_check_regression_gateway_conns_cell_back_compat(tmp_path,
                                                         capsys):
    """r13-and-earlier artifacts carry no conns rung: the pseudo-cell
    is reported as new, never gated against them — and an old round
    being compared AGAINST a conns round reports it missing without
    failing."""
    prev = _gateway_doc([(50, 65536, 1, 100.0)])           # r13 shape
    cur = _gateway_doc([(50, 65536, 1, 99.0)])
    cur["rows"][0]["conns"] = {
        "connections": 4096, "open_loop_sustained_qps": 900.0}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r13.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r14.json", cur)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["new_cells"] == ["(50, 65536, 1, 1, 'conns')"]
    assert not report["regressions"]


def test_check_regression_gateway_writes_cell_gates_independently(
        tmp_path, capsys):
    """The r15 write-heavy rung (durable-ack ingest, ISSUE 17) gates
    as its own pseudo-cell on sustained ACKED writes/s: a write-path
    regression — gate, pipelined produce, broker append — fails the
    gate even when the read cell held; the acked==durable ledger and
    fold-in freshness ride along for diagnosis."""
    prev = _gateway_doc([(50, 65536, 1, 100.0)])
    prev["rows"][0]["writes"] = {
        "open_loop_sustained_qps": 1200.0,
        "acked_equals_durable": True,
        "ingest_to_servable_ms": 700.0,
        "overload": {"p50_shed_ms": 1.5}}
    cur = _gateway_doc([(50, 65536, 1, 101.0)])
    cur["rows"][0]["writes"] = {
        "open_loop_sustained_qps": 500.0,
        "acked_equals_durable": True,
        "ingest_to_servable_ms": 2400.0,
        "overload": {"p50_shed_ms": 1.4}}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r14.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r15.json", cur)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert [c["cell"] for c in report["regressions"]] == \
        ["50f/0.065536M/1rep/writes"]
    # no rung sustained (errors or sheds on every rung) zeroes the
    # gated number: also a failure
    cur["rows"][0]["writes"]["open_loop_sustained_qps"] = 0.0
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r14.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r15.json", cur)])
    assert rc == 1
    # and a healthy rung gates green
    cur["rows"][0]["writes"]["open_loop_sustained_qps"] = 1180.0
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r14.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r15.json", cur)])
    assert rc == 0


def test_check_regression_gateway_writes_cell_back_compat(tmp_path,
                                                          capsys):
    """r14-and-earlier artifacts carry no write rung: the pseudo-cell
    is reported as new, never gated against them."""
    prev = _gateway_doc([(50, 65536, 1, 100.0)])           # r14 shape
    cur = _gateway_doc([(50, 65536, 1, 99.0)])
    cur["rows"][0]["writes"] = {
        "open_loop_sustained_qps": 1200.0,
        "acked_equals_durable": True}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r14.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r15.json", cur)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["new_cells"] == ["(50, 65536, 1, 1, 'writes')"]
    assert not report["regressions"]


def test_check_regression_gateway_ann_cell_gates_independently(
        tmp_path, capsys):
    """The r15 IVF-ANN rung (ISSUE 18, ``--ann``) gates as its own
    pseudo-cell on the ANN door's sustained qps: an index-build or
    routing regression — ANN silently failing closed serves correct
    answers at exact-kernel speed, collapsing the number — fails the
    gate even when the exact cells held; the recall certificate and
    speedup ride along for diagnosis."""
    prev = _gateway_doc([(50, 65536, 1, 100.0)])
    prev["rows"][0]["ann"] = {
        "open_loop_sustained_qps": 950.0,
        "speedup_vs_exact": 8.3,
        "certificate": {"recall": 0.988, "min_recall": 0.95},
        "sustained_p99_ms": 41.0}
    cur = _gateway_doc([(50, 65536, 1, 101.0)])
    cur["rows"][0]["ann"] = {
        "open_loop_sustained_qps": 120.0,   # fell back to exact speed
        "speedup_vs_exact": 1.05,
        "certificate": {"recall": 0.988, "min_recall": 0.95},
        "sustained_p99_ms": 600.0}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r14.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r15.json", cur)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert [c["cell"] for c in report["regressions"]] == \
        ["50f/0.065536M/1rep/ann"]
    # the rung never sustaining (door down, every rung shed) zeroes
    # the gated number: also a failure
    cur["rows"][0]["ann"]["open_loop_sustained_qps"] = 0.0
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r14.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r15.json", cur)])
    assert rc == 1
    # and a healthy rung gates green
    cur["rows"][0]["ann"]["open_loop_sustained_qps"] = 940.0
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r14.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r15.json", cur)])
    assert rc == 0


def test_check_regression_gateway_ann_cell_back_compat(tmp_path,
                                                       capsys):
    """r14-and-earlier artifacts carry no ANN rung — the pseudo-cell
    is new, never gated; and a probe that WITHHELD its headline (ivf
    never routed under emulation: the qps would be fantasy) drops the
    cell entirely rather than gating a number no device produced."""
    prev = _gateway_doc([(50, 65536, 1, 100.0)])           # r14 shape
    cur = _gateway_doc([(50, 65536, 1, 99.0)])
    cur["rows"][0]["ann"] = {
        "open_loop_sustained_qps": 950.0,
        "speedup_vs_exact": 8.3,
        "certificate": {"recall": 0.988}}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r14.json", prev),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r15.json", cur)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["new_cells"] == ["(50, 65536, 1, 1, 'ann')"]
    assert not report["regressions"]
    # headline withheld (None): the probe refused to certify a number
    # (ivf never routed under emulation) — the cell drops out and is
    # surfaced as MISSING, the same non-gating visibility every
    # skipped rung gets, rather than gating a fantasy qps
    prev2 = _gateway_doc([(50, 65536, 1, 100.0)])
    prev2["rows"][0]["ann"] = dict(cur["rows"][0]["ann"])
    cur2 = _gateway_doc([(50, 65536, 1, 99.0)])
    cur2["rows"][0]["ann"] = {
        "open_loop_sustained_qps": None,
        "ann_door_qps_raw": 950.0, "ivf_routed": False}
    rc = cr.main(["--kind", "gateway",
                  "--previous", _write(tmp_path,
                                       "BENCH_GATEWAY_r15a.json", prev2),
                  "--current", _write(tmp_path,
                                      "BENCH_GATEWAY_r15b.json", cur2)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert "(50, 65536, 1, 1, 'ann')" in report["missing_cells"]
    assert not report["regressions"]


def test_check_regression_gateway_discovers_rounds_and_skips_cross_backend(
        tmp_path, capsys):
    _write(tmp_path, "BENCH_GATEWAY_r07.json",
           _gateway_doc([(50, 65536, 2, 170.0)], backend="cpu"))
    _write(tmp_path, "BENCH_GATEWAY_r08.json",
           _gateway_doc([(50, 65536, 2, 100.0)], backend="cpu"))
    # grid artifacts in the same dir must not be picked up
    _write(tmp_path, "BENCH_GRID_r09.json", _grid_doc([]))
    rc = cr.main(["--kind", "gateway", "--dir", str(tmp_path)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert report["previous"] == "BENCH_GATEWAY_r07.json"
    assert report["current"] == "BENCH_GATEWAY_r08.json"
    # cross-backend rounds never compare
    _write(tmp_path, "BENCH_GATEWAY_r09.json",
           _gateway_doc([(50, 65536, 2, 1.0)], backend="tpu"))
    assert cr.main(["--kind", "gateway", "--dir", str(tmp_path)]) == 0


# -- --kind obs: the observability overhead gate (ISSUE 7) --------------------

def _obs_doc(unsampled_ns, full_ns=None, armed_ns=None,
             backend="cpu"):
    micro = {"unsampled_begin_branch_current": unsampled_ns,
             "sampled_begin_record_end": unsampled_ns * 6}
    if full_ns is not None:
        micro["unsampled_full_pipeline"] = full_ns
    if armed_ns is not None:
        micro["unsampled_recorder_armed"] = armed_ns
    return {"metric": "obs_tracing_overhead", "backend": backend,
            "microbench_ns_per_request": micro}


def test_check_regression_obs_passes_within_budget(tmp_path, capsys):
    rc = cr.main(["--kind", "obs",
                  "--previous", _write(tmp_path,
                                       "BENCH_OBS_OVERHEAD_r08.json",
                                       _obs_doc(2738)),
                  "--current", _write(tmp_path,
                                      "BENCH_OBS_OVERHEAD_r10.json",
                                      _obs_doc(2900, full_ns=3500))])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert not report["regressions"]
    assert report["budget_ns"] == 10_000


def test_check_regression_obs_hard_budget_gates(tmp_path, capsys):
    # even a round that "improved" relative to a terrible previous
    # round fails when the absolute single-digit-us budget is broken
    rc = cr.main(["--kind", "obs",
                  "--previous", _write(tmp_path,
                                       "BENCH_OBS_OVERHEAD_r09.json",
                                       _obs_doc(50_000, full_ns=60_000)),
                  "--current", _write(tmp_path,
                                      "BENCH_OBS_OVERHEAD_r10.json",
                                      _obs_doc(9_000, full_ns=12_000))])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert any(c.get("over_budget_ns") == 10_000
               for c in report["regressions"])


def test_check_regression_obs_relative_creep_gates(tmp_path, capsys):
    rc = cr.main(["--kind", "obs",
                  "--previous", _write(tmp_path,
                                       "BENCH_OBS_OVERHEAD_r08.json",
                                       _obs_doc(2000)),
                  "--current", _write(tmp_path,
                                      "BENCH_OBS_OVERHEAD_r10.json",
                                      _obs_doc(4000, full_ns=5000))])
    assert rc == 1   # 2x creep > the 50% obs threshold
    report = json.loads(capsys.readouterr().out)
    assert report["threshold"] == 0.5
    assert any(c["cell"] == "unsampled_begin_branch_current"
               for c in report["regressions"])


def test_check_regression_obs_discovers_rounds(tmp_path, capsys):
    _write(tmp_path, "BENCH_OBS_OVERHEAD_r08.json", _obs_doc(2738))
    _write(tmp_path, "BENCH_OBS_OVERHEAD_r10.json",
           _obs_doc(2800, full_ns=3100))
    # sibling families in the same dir must not be picked up
    _write(tmp_path, "BENCH_GRID_r09.json", _grid_doc([]))
    rc = cr.main(["--kind", "obs", "--dir", str(tmp_path)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["previous"] == "BENCH_OBS_OVERHEAD_r08.json"
    assert report["current"] == "BENCH_OBS_OVERHEAD_r10.json"


def test_check_regression_obs_recorder_armed_cell_gates_budget(
        tmp_path, capsys):
    # r16 (ISSUE 20): the recorder-armed cell is the WORST unsampled
    # cell, so the hard budget gates on it — a healthy full_pipeline
    # number cannot hide an over-budget armed recorder
    rc = cr.main(["--kind", "obs",
                  "--previous", _write(tmp_path,
                                       "BENCH_OBS_OVERHEAD_r10.json",
                                       _obs_doc(2000, full_ns=3000)),
                  "--current", _write(tmp_path,
                                      "BENCH_OBS_OVERHEAD_r16.json",
                                      _obs_doc(2100, full_ns=3100,
                                               armed_ns=12_000))])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert any(c.get("over_budget_ns") == 10_000
               and c.get("ns_cur") == 12_000
               for c in report["regressions"])


def test_check_regression_obs_recorder_armed_pre_r16_back_compat(
        tmp_path, capsys):
    # a pre-r16 previous round simply lacks the recorder-armed cell:
    # the relative gate skips it (never a phantom regression), the
    # budget still gates the current round's armed number
    rc = cr.main(["--kind", "obs",
                  "--previous", _write(tmp_path,
                                       "BENCH_OBS_OVERHEAD_r10.json",
                                       _obs_doc(2000, full_ns=3000)),
                  "--current", _write(tmp_path,
                                      "BENCH_OBS_OVERHEAD_r16.json",
                                      _obs_doc(2100, full_ns=3100,
                                               armed_ns=6_000))])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert not report["regressions"]
    compared = {c["cell"] for c in report["ok"]}
    assert "unsampled_recorder_armed" not in compared
    # ... and two armed rounds DO compare: 2x creep on the armed cell
    # alone gates even inside budget
    rc = cr.main(["--kind", "obs",
                  "--previous", _write(tmp_path,
                                       "BENCH_OBS_OVERHEAD_r16.json",
                                       _obs_doc(2000, full_ns=3000,
                                                armed_ns=4_000)),
                  "--current", _write(tmp_path,
                                      "BENCH_OBS_OVERHEAD_r17.json",
                                      _obs_doc(2100, full_ns=3100,
                                               armed_ns=9_000))])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert any(c["cell"] == "unsampled_recorder_armed"
               for c in report["regressions"])


def test_check_regression_obs_budget_gates_even_without_prior_round(
        tmp_path, capsys):
    # first-ever round (or first on a new backend): no relative
    # comparison exists, but the absolute budget must still gate
    _write(tmp_path, "BENCH_OBS_OVERHEAD_r10.json",
           _obs_doc(9_000, full_ns=12_000))
    rc = cr.main(["--kind", "obs", "--dir", str(tmp_path)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert "absolute budget only" in report["skipped"]
    assert any(c.get("over_budget_ns") == 10_000
               for c in report["regressions"])
    # ... and a within-budget first round passes
    _write(tmp_path, "BENCH_OBS_OVERHEAD_r10.json",
           _obs_doc(2_000, full_ns=3_000))
    assert cr.main(["--kind", "obs", "--dir", str(tmp_path)]) == 0
