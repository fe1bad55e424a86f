"""The whole command, rehearsed on the CPU backend at a tiny unlisted
configuration: it reports counts and ``correct`` only, never a time or a
rate.  Also what the command refuses.  Each run takes about half a
minute."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = "benchmark/tests/rehearsal_manifest.json"


def _run(*args, cwd=ROOT, script=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script or os.path.join(ROOT, "benchmark/run.py"),
         *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=600)


def _last(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_without_an_accelerator_there_is_no_result():
    proc = _run("--workload", "als250-20m.two-callers", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode == 3 and proc.stdout == ""
    assert "no accelerator" in proc.stderr


def test_an_unknown_cell_is_refused():
    proc = _run("--workload", "nope", "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert proc.returncode == 2 and proc.stdout == ""


def test_alone_in_a_directory_there_is_no_result(tmp_path):
    """BENCHMARK.json and the files under ``paths``, and nothing else."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run("--workload", "als250-20m.two-callers", "--seed", "1",
                "--seconds", "1", "--trace", "0", cwd=tmp_path,
                script=str(tmp_path / "benchmark" / "run.py"))
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no program beside the benchmark" in proc.stderr


@pytest.mark.parametrize("cell, trace", [("tiny.two-callers", 0),
                                         ("tiny.rehearsal-mix", 1)])
def test_rehearsal_reports_counts_and_correct_only(cell, trace):
    proc = _run("--workload", cell, "--seed", "11", "--seconds", "3",
                "--trace", str(trace), "--manifest", MANIFEST, "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    detail = json.loads(lines[-2])["detail"]
    last = _last(proc)
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    # nothing compiled inside the window, and the shapes were warmed
    assert detail["compile_cache"]["in_window"]["requests"] == 0
    assert [8, 64] in detail["warmed"]
    assert detail["app"]["checked"]["before_window"] == 32
    assert detail["in_window_full"] >= 1
    assert detail["problems"] == []
    if trace:
        # counts only: the span- and trace-sourced metrics stay out
        assert set(last["metrics"]) == {"rehearsal.dispatches",
                                        "batcher.mean_batch"}
        assert last["metrics"]["rehearsal.dispatches"]["value"] >= 1
        # the mix has the tracer record one request in two
        assert 0 < detail["spans_recorded"]
    else:
        assert last["metrics"] == {}
        # since PR 26 the scan selects as many blocks as the request
        # fetches: users with more than 22 known items are certified too
        assert detail["counters"]["end"]["twophase_fallbacks"] == 0
