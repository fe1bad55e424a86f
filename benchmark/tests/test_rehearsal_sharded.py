"""The sharded cell's whole command, rehearsed on virtual CPU devices at a
tiny unlisted configuration (``als_sharded``: the manager that draws on
each device, the shard-by-shard reference, the counters of the sharded
path): counts and ``correct`` only.  About a minute and a half."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = "benchmark/tests/rehearsal_sharded_manifest.json"


def test_the_sharded_cell_rehearsed_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark/run.py"),
         "--workload", "tiny-sharded.two-callers", "--seed", "3000000011",
         "--seconds", "3", "--trace", "1", "--manifest", MANIFEST,
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    detail, last = json.loads(lines[-2])["detail"], json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["count"] == 4
    assert detail["problems"] == []
    assert detail["compile_cache"]["in_window"]["requests"] == 0
    app = detail["app"]
    assert app["kernel_route"]["kind"] == "sharded_twophase"
    assert app["kernel_route"]["shards"] == 2
    assert app["shards"] == [655360, 655360]
    assert app["checked"]["before_window"] == 32
    # the host's resident set at every step of the load, and after
    assert {"draw", "slab0_fetched", "slab1_loaded", "bulk_load",
            "upload", "now"} <= set(app["host_resident_gb"])
    assert app["host_resident_gb"]["now"]["rss"] > 0
    assert app["checked"]["worst_rel_dev"] < 2e-5
    start, end = detail["counters"]["start"], detail["counters"]["end"]
    assert end["sharded_windows"] > start["sharded_windows"]
    assert end["shard_fallback_rows"] == end["twophase_fallbacks"] == 0
    # counts only: the span- and trace-sourced metrics stay out
    assert set(last["metrics"]) == {"route.fallback_share",
                                    "batcher.mean_batch",
                                    "shard.fallback_share"}
    assert last["metrics"]["shard.fallback_share"]["value"] == 0.0
