"""What the multi-process integration tests share: spawn a layer as a
child of this interpreter against a written conf, and wait on its HTTP
surface."""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from oryx_tpu.common.config import keys_to_hocon


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _write_conf(path: str, broker_dir: str, port: int,
                extra: dict) -> None:
    kv = {
        "oryx.id": "cluster-it",
        "oryx.input-topic.broker": f"file://{broker_dir}",
        "oryx.input-topic.message.topic": "GwIn",
        "oryx.input-topic.partitions": 1,
        "oryx.update-topic.broker": f"file://{broker_dir}",
        "oryx.update-topic.message.topic": "GwUp",
        "oryx.serving.model-manager-class":
            "oryx_tpu.app.als.serving_manager.ALSServingModelManager",
        "oryx.serving.application-resources": "oryx_tpu.serving.als",
        "oryx.serving.api.port": port,
        "oryx.resilience.supervisor.enabled": False,
        "oryx.cluster.heartbeat-interval-ms": 250,
        "oryx.cluster.heartbeat-ttl-ms": 1500,
    }
    kv.update(extra)
    with open(path, "w", encoding="utf-8") as f:
        f.write(keys_to_hocon(sorted(kv.items())))


def _spawn(args: list[str], conf: str, log_path: str) -> subprocess.Popen:
    with open(log_path, "ab") as log:  # the child keeps its own copy
        return subprocess.Popen(
            [sys.executable, "-m", "oryx_tpu", *args, "--conf", conf],
            env=dict(os.environ),  # children inherit the platform as given
            stdout=log, stderr=log)


def _get_json(port: int, path: str, timeout: float = 10.0):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return json.loads(r.read() or b"null")


def _await(predicate, what: str, timeout: float = 300.0) -> None:
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        try:
            if predicate():
                return
        except Exception:  # noqa: BLE001 — still coming up
            pass
        time.sleep(0.5)
    raise RuntimeError(f"timed out waiting for {what}")


def _get_json_retry_cold(port: int, path: str,
                         budget_sec: float = 180.0):
    """_get_json tolerating a COLD scoring path: the first dispatch a
    replica ever runs includes the XLA compile of its scan ladder,
    which can outlast the router's shard timeout — the router then
    reads the shard as down and answers 503 (or the direct call times
    out).  Those first-touch failures retry within the budget; any
    other status propagates immediately.  404 is cold too: /ready only
    means the HTTP stack is up — a replica mid-load answers 404 for a
    user its update consumer hasn't reached yet."""
    t_end = time.monotonic() + budget_sec
    while True:
        try:
            return _get_json(port, path, timeout=30.0)
        except urllib.error.HTTPError as e:
            e.read()
            if e.code not in (503, 404) or time.monotonic() >= t_end:
                raise
        except OSError:
            if time.monotonic() >= t_end:
                raise
        time.sleep(1.0)
