"""The start-up rules that keep a green run honest about the device:
``chip_smoke.py`` refuses to pass off-chip, its body rehearses on the
CPU backend at a toy size, and on a (faked) TPU backend a Pallas build
that fails to lower is loud — in ``kernel_route.errors``, in the log at
ERROR, in ``warmup``'s exit code, and in the smoke's verdict."""

from __future__ import annotations

import json
import logging
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from oryx_tpu.app.als import serving_model as sm
from oryx_tpu.app.als.serving_model import ALSServingModel

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run_script(*argv: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *argv],
        capture_output=True, text=True, cwd=str(REPO), timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_chip_smoke_exits_nonzero_without_a_tpu():
    out = _run_script()
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr
    assert out.stdout.strip() == ""  # no result line off-chip


def test_chip_smoke_default_invocation_cannot_be_made_small():
    """The rehearsal body takes a catalog size; the script does not: no
    argument reaches it, and the device check comes first."""
    out = _run_script("--items", "2000", "2000")
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_chip_smoke_body_rehearses_on_cpu():
    """The same body the chip runs, at a toy catalog on the CPU backend
    (flat kernel, inline MODEL): every phase and every check passes."""
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    result = chip_smoke.run_smoke(items=2000, iterations=2)
    assert result["ok"], result["failures"]
    assert result["device"]["platform"] == "cpu"
    assert result["kernel_route"]["path"] == "flat"
    assert result["recommend_checked"] == 9
    assert result["speed_up_deltas"] >= 2
    assert result["trainer"] == {"kind": "train_als", "mesh_devices": 1}
    json.dumps(result)  # the line the script prints must serialize


def test_chip_smoke_last_line_is_the_verdict_and_nothing_else(
        monkeypatch, capsys):
    """What the driver parses: the LAST stdout line is one JSON object
    with exactly ``ok`` and ``device`` = {platform, kind, count}; the
    readings ride on the line before it.  A failed run says
    ``"ok": false`` in the same shape and exits non-zero."""
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_info", lambda: {
        "device": dict(device), "backend": "tpu", "jax": "0.9.0"})
    for ok, code in ((True, 0), (False, 1)):
        monkeypatch.setattr(chip_smoke, "run_smoke", lambda ok=ok: {
            "device": dict(device), "backend": "tpu", "ok": ok,
            "stages_s": {"warmup": 1.0}, "failures": [] if ok else ["x"]})
        assert chip_smoke.main() == code
        lines = capsys.readouterr().out.strip().splitlines()
        assert json.loads(lines[-1]) == {"ok": ok, "device": device}
        readings = json.loads(lines[-2])
        assert readings["stages_s"] and "wall_s" in readings


def _force_streaming(monkeypatch) -> None:
    """Make a 4096-row catalog take the streaming two-phase path (the
    route 1M-item catalogs take), with clean Pallas bookkeeping."""
    monkeypatch.setattr(sm, "_FLAT_SCORES_LIMIT", 1)
    monkeypatch.setattr(sm, "_MAX_CHUNK_ROWS", 1024)
    monkeypatch.setattr(sm, "_BLOCK_KSEL", 4)
    monkeypatch.setattr(sm, "_PA_TILE", 1024)
    monkeypatch.setattr(sm, "_PALLAS_STATE", {})
    monkeypatch.setattr(sm, "_PALLAS_ERRORS", {})


@pytest.fixture
def streaming_toy(monkeypatch):
    _force_streaming(monkeypatch)
    rng = np.random.default_rng(21)
    model = ALSServingModel(features=6, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(4096)],
                      rng.standard_normal((4096, 6)).astype(np.float32))
    return model


def test_pallas_failure_is_loud_on_a_tpu_backend(streaming_toy,
                                                 monkeypatch, caplog):
    """The CPU backend cannot lower Pallas, which makes it a stand-in
    for a kernel Mosaic refuses: with the backend faked to ``tpu`` the
    same failure must be an ERROR and sit in ``kernel_route.errors``,
    both when the route measurement hits it and when a dispatch does
    (a ladder window the measurement never timed)."""
    import jax

    model = streaming_toy
    # on the real CPU backend the substitution is routine: WARNING
    assert sm.pallas_failure_level() == logging.WARNING
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sm.pallas_failure_level() == logging.ERROR

    # dispatch-time, before any route exists (a cold first drain): the
    # static chain tries the Pallas builds for this 8-query window,
    # each fails, the scan build serves — loudly
    with caplog.at_level(logging.WARNING, logger="oryx_tpu"):
        got = model.top_n_batch(4, np.ones((3, 6), np.float32))
    assert len(got) == 3 and all(len(g) == 4 for g in got)
    assert "broken" in sm._PALLAS_STATE.values()
    assert any(r.levelno == logging.ERROR and "lax.scan" in r.getMessage()
               for r in caplog.records)

    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="oryx_tpu"):
        route = model.refresh_route(force=True)
    assert route["path"] == "streaming"
    assert route["costs_exact_ms"]["scan"] is not None  # still serves
    assert route["costs_exact_ms"]["pallas"] is None
    assert "interpret" in route["errors"]["pallas"].lower()
    assert any(r.levelno == logging.ERROR and "pallas" in r.getMessage()
               for r in caplog.records)
    # /metrics shows ONE table: what the measurement hit at B=256 plus
    # what the dispatch hit on a window the measurement never timed
    errors = model.metrics()["kernel_route"]["errors"]
    assert "pallas" in errors and "pallas B=8" in errors, errors


def test_route_measurement_failure_is_published(streaming_toy,
                                                monkeypatch):
    """A measurement that dies as a whole still never aborts the model
    load — but it leaves an ``errors`` stub on /metrics instead of
    nothing, and the next load measures again."""
    from oryx_tpu.app.als import kernel_router

    def boom(*_a, **_k):
        raise RuntimeError("injected measurement failure")

    model = streaming_toy
    monkeypatch.setattr(kernel_router, "measure_routes", boom)
    assert model.refresh_route(force=True) is None
    route = model.metrics()["kernel_route"]
    assert route["measured"] is False
    assert "injected measurement failure" in \
        route["errors"]["measure_routes"]
    n_rows = len(model.Y.row_ids())
    assert model._route_current(n_rows) is None  # static chain serves
    monkeypatch.undo()
    assert model.refresh_route()["measured"] is True


def test_warmup_fails_on_a_tpu_backend_when_a_build_cannot_lower(
        monkeypatch, tmp_path, capsys):
    """``python -m oryx_tpu warmup`` over a streaming ladder: on the CPU
    backend the recorded Pallas failures are routine (exit 0 while
    anything compiled); on a TPU backend any entry in ``failed`` is
    exit != 0."""
    import jax

    from oryx_tpu.deploy import main as cli

    _force_streaming(monkeypatch)
    conf = tmp_path / "w.conf"
    conf.write_text("oryx { compile-cache-dir = null }\n")
    argv = ["warmup", "--conf", str(conf), "--items", "4096",
            "--features", "6", "--dtypes", "float32"]
    assert cli.main(argv) == 0               # CPU: recorded, not fatal
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["ok"] and report["compiled_count"] > 0
    assert any("pallas" in f["kernel"] for f in report["failed"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cli.main(argv) == 1               # TPU: a defect
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not report["ok"] and report["backend"] == "tpu"


def test_smoke_fails_when_a_pallas_build_fails(monkeypatch):
    """End to end on a faked TPU backend with the toy catalog forced to
    stream: no Pallas build can lower here, so the smoke's verdict must
    be a failure that names the builds and the errors."""
    import jax

    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    _force_streaming(monkeypatch)
    monkeypatch.setattr(chip_smoke, "_STREAMING_ROWS", 1024)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    result = chip_smoke.run_smoke(items=4096, iterations=1)
    assert not result["ok"]
    text = " ".join(result["failures"])
    assert "kernel_route.errors" in text
    assert "no measured cost for Pallas build(s)" in text
    assert "warmup failed to compile" in text


def test_layer_cli_fails_at_once_when_the_backend_cannot_start(
        monkeypatch, tmp_path, capsys):
    """One process per chip: a second process's runtime start-up
    failure must be ONE named error before anything is supervised —
    not a worker thread logging and retrying for ever."""
    import jax

    from oryx_tpu.deploy import main as cli

    def held(*_a, **_k):
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED: "
                           "libtpu multi-process lockfile")

    monkeypatch.setattr(jax, "devices", held)
    conf = tmp_path / "s.conf"
    conf.write_text('oryx.serving.model-manager-class = "unused"\n')
    for role in ("serving", "speed", "batch", "warmup"):
        with pytest.raises(SystemExit) as exc:
            cli.main([role, "--conf", str(conf)])
        assert exc.value.code == 3
        err = capsys.readouterr().err
        assert f"oryx_tpu {role}: cannot initialize the JAX backend" in err
        assert "one process per chip" in err and "lockfile" in err


def test_served_scores_keep_the_stores_precision():
    """On a TPU a float32 matmul runs as ONE bfloat16 pass unless told
    otherwise (measured on the v5e: 1.4e-3 relative on /recommend
    scores).  Every kernel whose products become SERVED scores must
    carry HIGHEST for a float32 store — visible in the lowered program
    on any backend — and must not for a bfloat16 store, whose products
    are exact in one pass.  Phase A of the two-phase scan only selects
    blocks, but the certificate holds the served scores against its
    maxima, so it carries HIGHEST too (PR 34)."""
    import jax
    import jax.numpy as jnp

    n, w, b, k = 2048, 128, 8, 16
    A = jax.ShapeDtypeStruct((n,), jnp.bool_)
    Q = jax.ShapeDtypeStruct((b, 50), jnp.float32)

    def dots(dtype):
        Y = jax.ShapeDtypeStruct((n, w), dtype)
        texts = {
            "dot_scores": sm._dot_scores.lower(
                Y, jax.ShapeDtypeStruct((50,), jnp.float32)),
            "cosine": sm._cosine_mean_scores.lower(
                Y, jax.ShapeDtypeStruct((50, 2), jnp.float32)),
            "flat": sm._batch_top_n_kernel.lower(Y, Q, A, k=k),
            "chunked_exact": sm._batch_top_n_chunked_kernel.lower(
                Y, Q, A, k=k, chunk=512),
            "twophase_scan": sm._batch_top_n_twophase_kernel.lower(
                Y, Q, A, None, jax.ShapeDtypeStruct((), jnp.int32), k=k,
                chunk=512, bs=128, ksel=4),
        }
        return {name: low.as_text().count("HIGHEST")
                for name, low in texts.items()}

    f32, bf16 = dots(jnp.float32), dots(jnp.bfloat16)
    assert all(count == 0 for count in bf16.values()), bf16
    # one dot per served-score kernel; the two-phase program has two,
    # phase A's maxima and phase B's rescore
    assert all(count >= 1 for count in f32.values()), f32
    assert f32["twophase_scan"] == 2 * f32["flat"], f32
