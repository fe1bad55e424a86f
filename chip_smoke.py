"""First light on the chip: the README quick start, in ONE process, at
the full width of the 50-feature / 1M-item ALS model.

    python chip_smoke.py

drives the system's main path once, through the classes a user runs
(``python -m oryx_tpu warmup | kafka-setup | batch | speed | serving``
are thin shells over exactly these): AOT warmup of the serving ladder,
a seeded synthetic rating log on the input topic, one ``BatchLayer``
generation (the trainer takes its steps on the chip and publishes a
MODEL-REF through the normal sliced publish path), one ``SpeedLayer``
micro-batch fold-in, then a ``ServingLayer`` that loads the model,
measures its kernel routes and answers real HTTP.  One process, because
a chip belongs to one process at a time.

It then checks what came out, by the repo's own means, and FAILS if the
device did not do the work: no float64 host rescue in training or in
the fold-in solvers, every Pallas phase-A build measured on the live
shape with no lowering error and no certificate fallback, and
``/recommend`` equal to a plain ``jax.numpy`` float32 ``matmul + top_k``
over the served factors at ``default_matmul_precision("highest")``.

It prints two JSON lines on stdout: the bring-up readings (stages,
route table, failures), then — LAST, and exactly this shape — the verdict

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exit code 0 and ``"ok": true`` mean all of it held ON A TPU.  Without an
accelerator (JAX_PLATFORMS=cpu, or JAX's own silent CPU fallback) the
script exits non-zero before doing anything and prints nothing on
stdout: there is no flag that makes the default invocation smaller or
lets it pass elsewhere.  ``run_smoke`` takes the catalog size so that
tests/test_chip_smoke.py can rehearse the same body at a toy size on
the CPU backend before chip time is spent.

The timings in the readings line are BRING-UP READINGS (one run, compile
and host-side text parsing included), not benchmark results.
"""

from __future__ import annotations

import json
import logging
import sys
import tempfile
import time
import urllib.error
import urllib.request

import numpy as np

# The reference's published exact-scan configuration (BASELINE.md).
# 1M items pad to a 1,048,576-row store: past the streaming threshold
# and a multiple of the Pallas tile, so all four Pallas phase-A builds
# are eligible.
FEATURES = 50
ITEMS = 1_000_000
PALLAS_KINDS = ("i8_fold", "fold", "i8", "pallas")
# capacities above this stream (two-phase scan) instead of the flat kernel
_STREAMING_ROWS = 1 << 19

BROKER = "chip-smoke"
NEW_USER = "brandnew"
HOW_MANY = 10
# /recommend vs the float32 reference (see _Reference.check)
SCORE_RTOL = 2e-5
SCORE_ATOL = 1e-6


def device_info() -> dict:
    """Touch the device first and say what it is."""
    import jax

    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    return {"device": info, "backend": jax.default_backend(),
            "jax": jax.__version__, "jaxlib": _version("jaxlib"),
            "libtpu": _version("libtpu")}


def _version(dist: str) -> str | None:
    from importlib import metadata
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return None


def _config(items: int, features: int, iterations: int, work_dir: str):
    from oryx_tpu.common.config import from_dict
    return from_dict({
        "oryx.id": BROKER,
        "oryx.input-topic.broker": f"memory://{BROKER}",
        "oryx.input-topic.message.topic": "In",
        "oryx.update-topic.broker": f"memory://{BROKER}",
        "oryx.update-topic.message.topic": "Up",
        "oryx.batch.update-class": "oryx_tpu.app.als.update.ALSUpdate",
        "oryx.speed.model-manager-class":
            "oryx_tpu.app.als.speed.ALSSpeedModelManager",
        "oryx.serving.model-manager-class":
            "oryx_tpu.app.als.serving_manager.ALSServingModelManager",
        "oryx.serving.application-resources": "oryx_tpu.serving.als",
        "oryx.batch.storage.data-dir": work_dir + "/data",
        "oryx.batch.storage.model-dir": work_dir + "/model",
        # the smoke drives its one micro-batch by hand; park the speed
        # layer's own ticker so that call is the sole producer
        "oryx.speed.streaming.generation-interval-sec": 3600,
        "oryx.als.iterations": iterations,
        "oryx.als.implicit": True,
        "oryx.als.hyperparams.features": features,
        "oryx.ml.eval.test-fraction": 0.0,
    })


def synth_ratings(items: int, seed: int) -> tuple[list[str], int]:
    """Seeded ``user,item,strength,timestamp`` lines touching EVERY item
    at least once (half of them twice).  Users = items / 20; the first
    tenth of them are heavy (three quarters of all ratings, ~225 each),
    the rest light (~8 each) — catalogs are skewed, and the two kinds
    take different serving paths (see ``run_smoke``).  Item degrees stay
    flat at 1-2: the distributed trainer's dense per-row layout pads
    every row to the widest one, and a million rows padded to a popular
    item's degree would not fit."""
    rng = np.random.default_rng(seed)
    n_users = max(20, items // 20)
    n_heavy = n_users // 10
    item_idx = np.concatenate([
        np.arange(items), rng.choice(items, items // 2, replace=False)])
    user_idx = np.where(rng.random(len(item_idx)) < 0.75,
                        rng.integers(0, n_heavy, len(item_idx)),
                        rng.integers(n_heavy, n_users, len(item_idx)))
    strength = np.round(rng.exponential(1.0, len(item_idx)) + 0.05, 2)
    t0 = 1_700_000_000_000
    lines = [f"{user_id(u)},{item_id(i)},{s},{t0 + n}"
             for n, (u, i, s) in enumerate(zip(
                 user_idx.tolist(), item_idx.tolist(), strength.tolist()))]
    return lines, n_users


def user_id(n: int) -> str:
    return f"user-{n:08d}"


def item_id(n: int) -> str:
    # catalog-style ids: at 1M items the PMML's id lists outgrow one
    # update-topic message (16 MiB), so the generation publishes the way
    # large models do — MODEL-REF plus sliced artifacts
    return f"item-{n:012d}"


class _StageMarks(logging.Handler):
    """Wall-clock marks inside ``BatchLayer.run_one_generation`` taken
    from the layers' own INFO lines (the generation is one public call;
    its stages are only visible in its log)."""

    _MARKS = (("sweep", "ALS iteration"),
              ("built", "Model eval for params"))

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.at: dict[str, float] = {}

    def emit(self, record):
        msg = record.getMessage()
        for key, prefix in self._MARKS:
            if msg.startswith(prefix):
                self.at[key] = time.perf_counter()  # last one wins


def _http(url: str, method: str = "GET", data: bytes | None = None,
          timeout: float = 600.0):
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def _get_json(url: str, timeout: float = 600.0):
    return json.loads(_http(url, timeout=timeout)[1])


def _wait(what: str, cond, timeout: float):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(0.2)
    raise TimeoutError(f"timed out after {timeout:.0f}s waiting for {what}")


class _Reference:
    """Plain ``jax.numpy`` float32 ``matmul + top_k`` over the SERVED
    factors at highest matmul precision, and the comparison of one
    ``/recommend`` answer against it."""

    def __init__(self, model):
        import jax.numpy as jnp

        self.model = model
        Y, self.active, self.row_ids = model.Y.host_arrays()
        self.Y = jnp.asarray(Y, jnp.float32)
        self.worst_rel_dev = 0.0

    def top_n(self, user_id: str, how_many: int):
        """(ids, scores) of the best ``how_many`` unknown items, plus
        the full score vector."""
        import jax
        import jax.numpy as jnp

        ok = self.active.copy()
        for iid in self.model.get_known_items(user_id):
            row = self.model.Y.row_of(iid)
            if row is not None:
                ok[row] = False
        x = jnp.asarray(self.model.get_user_vector(user_id), jnp.float32)
        with jax.default_matmul_precision("highest"):
            scores = jnp.where(jnp.asarray(ok), jnp.matmul(self.Y, x),
                               -jnp.inf)
            top_s, top_i = jax.lax.top_k(scores, how_many)
        top_s, top_i, scores = jax.device_get((top_s, top_i, scores))
        return ([self.row_ids[int(i)] for i in top_i], top_s.tolist(),
                np.asarray(scores))

    def check(self, user_id: str, served: list[dict],
              failures: list[str]) -> None:
        """``served`` must be the reference's ids in the reference's
        order with the reference's scores, to SCORE_RTOL (relative,
        floored at SCORE_ATOL): float32 factors are served at float32,
        so one bfloat16 MXU pass (relative error ~2^-8) fails this by
        two orders of magnitude.  Two items whose reference scores are
        closer than the tolerance are a TIE and may swap — a tie is
        decided by summation order, which no two correct kernels
        share."""
        ref_ids, ref_scores, all_scores = self.top_n(user_id, HOW_MANY)
        if len(served) != HOW_MANY:
            failures.append(f"/recommend/{user_id}: {len(served)} "
                            f"results, wanted {HOW_MANY}")
            return
        for rank, got in enumerate(served):
            row = self.model.Y.row_of(got["id"])
            if row is None:
                failures.append(f"/recommend/{user_id} rank {rank}: "
                                f"unknown item {got['id']}")
                continue
            exact = float(all_scores[row])
            tol = max(SCORE_ATOL, SCORE_RTOL * abs(exact))
            dev = abs(got["value"] - exact)
            self.worst_rel_dev = max(self.worst_rel_dev,
                                     dev / max(abs(exact), SCORE_ATOL))
            if not dev <= tol:
                failures.append(
                    f"/recommend/{user_id} rank {rank}: served score "
                    f"{got['value']!r} for {got['id']} vs float32 "
                    f"reference {exact!r} (tolerance {tol:.3g})")
            if got["id"] != ref_ids[rank] \
                    and not abs(exact - ref_scores[rank]) <= tol:
                failures.append(
                    f"/recommend/{user_id} rank {rank}: served "
                    f"{got['id']} ({exact!r}), reference "
                    f"{ref_ids[rank]} ({ref_scores[rank]!r})")


def run_smoke(items: int = ITEMS, features: int = FEATURES,
              iterations: int = 3, seed: int = 5) -> dict:
    """The smoke's body on whatever backend JAX has.  Returns the result
    dict; ``result["ok"]`` is False and ``result["failures"]`` says why
    when any phase or check failed.  Device-specific expectations (every
    Pallas build measured, no lowering error) apply when the backend is
    a TPU; the CPU backend cannot lower Pallas and serves the lax.scan
    build, which is what a rehearsal there exercises."""
    result = device_info()
    on_tpu = result["backend"] == "tpu"
    failures: list[str] = []
    stages: dict[str, float] = {}
    result.update(ok=False, features=features, items=items,
                  note="bring-up readings, not benchmark results",
                  stages_s=stages, failures=failures)

    import jax

    from oryx_tpu.app.als.feature_vectors import planned_capacity
    from oryx_tpu.deploy.warmup import run_warmup
    from oryx_tpu.kafka import utils as kafka_utils
    from oryx_tpu.kafka.api import KEY_MODEL, KEY_MODEL_REF, KEY_UP
    from oryx_tpu.kafka.inproc import drop_broker, resolve_broker
    from oryx_tpu.lambda_rt.batch import BatchLayer
    from oryx_tpu.lambda_rt.serving import ServingLayer
    from oryx_tpu.lambda_rt.speed import SpeedLayer

    # persistent-cache traffic, from jax's own monitoring events
    cache = {"requests": 0, "hits": 0}

    def _on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1

    jax.monitoring.register_event_listener(_on_event)
    streams = planned_capacity(items) > _STREAMING_ROWS
    marks = _StageMarks()
    layers_log = logging.getLogger("oryx_tpu")
    level_was = layers_log.level
    layers_log.setLevel(logging.INFO)  # the marks are INFO lines
    layers_log.addHandler(marks)
    speed = serving = None
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as work_dir:
        cfg = _config(items, features, iterations, work_dir)
        try:
            # -- 2. install-time AOT warmup for this shape ---------------
            t = time.perf_counter()
            warm = run_warmup(
                cfg, items_list=[items], features_list=[features],
                dtypes=[cfg.get_string("oryx.als.factor-dtype")])
            stages["warmup"] = round(time.perf_counter() - t, 2)
            result["compile_cache_dir"] = warm["cache_dir"]
            result["warmup"] = {
                "compiled": warm["compiled_count"],
                "failed": [f["kernel"] for f in warm["failed"]]}
            if on_tpu and warm["failed"]:
                failures.append(f"warmup failed to compile: {warm['failed']}")

            # -- 3. seed the input topic, one batch generation -----------
            t = time.perf_counter()
            for broker_uri, topic, parts in (
                    (cfg.get_string("oryx.input-topic.broker"), "In",
                     kafka_utils.input_topic_partitions(cfg)),
                    (cfg.get_string("oryx.update-topic.broker"), "Up", 1)):
                kafka_utils.maybe_create_topic(broker_uri, topic,
                                               partitions=parts)
            broker = resolve_broker(f"memory://{BROKER}")
            lines, n_users = synth_ratings(items, seed)
            for lo in range(0, len(lines), 100_000):
                broker.send_many("In", [(None, line, None)
                                        for line in lines[lo:lo + 100_000]])
            result["ratings"] = len(lines)
            result["users"] = n_users
            stages["seed"] = round(time.perf_counter() - t, 2)

            batch = BatchLayer(cfg)
            mesh = batch.update_instance.mesh
            result["trainer"] = {
                "kind": "train_als" if mesh is None
                else "train_als_distributed",
                "mesh_devices": 1 if mesh is None
                else int(mesh.devices.size)}
            t_gen = time.perf_counter()
            batch.run_one_generation()
            t_done = time.perf_counter()
            batch.close()
            t_built = marks.at.get("built", t_gen)
            if "sweep" in marks.at:
                # parse + aggregate + pack + compile + the sweeps
                stages["train"] = round(marks.at["sweep"] - t_gen, 2)
                stages["artifacts"] = round(t_built - marks.at["sweep"], 2)
            else:  # the distributed trainer logs no per-sweep line
                stages["train_and_artifacts"] = round(t_built - t_gen, 2)
            stages["publish"] = round(t_done - t_built, 2)
            up = broker.read_ranges("Up", [0], broker.latest_offsets("Up"))
            # MODEL-REF + sliced artifacts at full size (the PMML's id
            # lists outgrow a topic message); a toy catalog inlines
            if not up or up[0].key not in (KEY_MODEL, KEY_MODEL_REF):
                failures.append(
                    "batch layer published no model "
                    f"(first update key: {up[0].key if up else None})")
                raise _Abort
            result["published"] = up[0].key
            if items >= ITEMS and up[0].key != KEY_MODEL_REF:
                failures.append("a full-size generation must publish as "
                                f"MODEL-REF + slices, got {up[0].key}")
            from oryx_tpu.app.pmml_utils import \
                read_pmml_from_update_key_message
            from oryx_tpu.common import pmml as pmml_io
            pmml = read_pmml_from_update_key_message(up[0].key,
                                                     up[0].message)
            rescue = pmml_io.get_extension_value(pmml, "rescue")
            if rescue is not None:
                failures.append("trainer fell back to the float64 host "
                                f"rescue ladder: {rescue}")

            # -- 4. speed layer: load the model, one micro-batch ---------
            t = time.perf_counter()
            speed = SpeedLayer(cfg)
            # 'start from now': this smoke folds two new events, not the
            # whole history the batch layer just trained on
            broker.fill_in_latest_offsets(
                f"OryxGroup-SpeedLayer-{BROKER}", ["In"])
            speed.start()
            _wait("the speed model", lambda: (
                (m := speed.model_manager.model) is not None
                and m.item_count() >= items
                and m.get_fraction_loaded() >= 1.0), 600)
            known_user, known_item = user_id(0), item_id(1)
            ups_before = broker.latest_offsets("Up")
            now_ms = int(time.time() * 1000)
            broker.send("In", None, f"{known_user},{known_item},3.0,{now_ms}")
            broker.send("In", None,
                        f"{NEW_USER},{item_id(2)},1.0,{now_ms + 1}")
            speed.run_one_micro_batch()
            deltas = [json.loads(km.message) for km in broker.read_ranges(
                "Up", ups_before, broker.latest_offsets("Up"))
                if km.key == KEY_UP]
            folded = {(d[0], d[1]) for d in deltas}
            for want in (("X", NEW_USER), ("X", known_user)):
                if want not in folded:
                    failures.append(f"speed layer published no UP delta "
                                    f"for {want}; got {sorted(folded)}")
            result["speed_up_deltas"] = len(deltas)
            for name, solver in (
                    ("YtY", speed.model_manager.model.get_yty_solver()),
                    ("XtX", speed.model_manager.model.get_xtx_solver())):
                if solver is None or solver.precision != "float32":
                    failures.append(
                        f"speed {name} solver is "
                        f"{'missing' if solver is None else solver.precision}"
                        " (the float64 host path was taken)")
            speed.close()
            speed = None
            stages["speed"] = round(time.perf_counter() - t, 2)

            # -- 5. serving layer over real HTTP -------------------------
            t = time.perf_counter()
            serving = ServingLayer(cfg, port=0)
            serving.start()
            base = f"http://127.0.0.1:{serving.port}"

            def _ready():
                try:
                    return _http(f"{base}/ready", timeout=10)[0] < 300
                except (urllib.error.URLError, OSError):
                    return False

            _wait("/ready", _ready, 600)
            stages["serving_ready"] = round(time.perf_counter() - t, 2)
            model = serving.model_manager.get_model()
            # the whole update topic replayed: every item, and the
            # speed layer's UP deltas that ride behind the model
            _wait("the full model and the fold-in to reach serving",
                  lambda: model.item_count() >= items
                  and model.get_fraction_loaded() >= 1.0
                  and model.get_user_vector(NEW_USER) is not None, 600)
            stages["serving_loaded"] = round(time.perf_counter() - t, 2)
            _wait("the measured kernel route",
                  lambda: _get_json(f"{base}/metrics").get(
                      "model_metrics", {}).get("kernel_route"), 600)
            stages["serving_routed"] = round(time.perf_counter() - t, 2)

            # /recommend excludes a user's known items by fetching a
            # window of pad2(howMany + known): light users (and the
            # brand-new one) stay in the 16-wide window the warmup
            # ladder compiled and the two-phase certificate covers
            light = [u for u in sorted(model.all_user_ids())[-2000:]
                     if 1 <= len(model.get_known_items(u)) <= 6][:7]
            if len(light) < 7:
                failures.append(f"only {len(light)} light users to check")
            users = light + [NEW_USER]
            answers = {}
            for u in users:
                t_req = time.perf_counter()
                answers[u] = _get_json(
                    f"{base}/recommend/{u}?howMany={HOW_MANY}")
                stages.setdefault("first_request", round(
                    time.perf_counter() - t_req, 3))
            stages["last_request"] = round(time.perf_counter() - t_req, 3)
            reference = _Reference(model)
            for u in users:
                reference.check(u, answers[u], failures)
            result["recommend_checked"] = len(users) + 1  # + the heavy one
            fallbacks = model.metrics()["twophase_fallbacks"]
            if fallbacks != 0:
                failures.append(f"twophase_fallbacks = {fallbacks}")
            # ... while a heavy user's window (pad2(10 + ~225 known) =
            # 256) is wider than the 32 blocks phase B selects, so its
            # certificate cannot pass and the answer comes from the
            # exact-scan recompute: still exact, reported as a reading
            t_req = time.perf_counter()
            heavy = _get_json(f"{base}/recommend/{known_user}"
                              f"?howMany={HOW_MANY}")
            stages["heavy_user_request"] = round(
                time.perf_counter() - t_req, 3)
            reference.check(known_user, heavy, failures)
            result["recommend_max_rel_score_dev"] = reference.worst_rel_dev
            result["heavy_user"] = {
                "known_items": len(model.get_known_items(known_user)),
                "certificate_fallback_rows":
                    model.metrics()["twophase_fallbacks"] - fallbacks}
            i = item_id
            for path in (f"/similarity/{i(1)}/{i(3)}",
                         f"/similarity/{i(5)}",
                         f"/recommendToAnonymous/{i(1)}=2.0/{i(7)}",
                         f"/recommendToAnonymous/{i(9)}"):
                got = _get_json(f"{base}{path}?howMany={HOW_MANY}")
                vals = [g["value"] for g in got]
                if len(got) != HOW_MANY or not np.isfinite(vals).all() \
                        or vals != sorted(vals, reverse=True):
                    failures.append(f"{path}: bad answer {got}")
            in_before = broker.latest_offsets("In")
            status, _ = _http(f"{base}/pref/{known_user}/{item_id(3)}",
                              "POST", b"4.5")
            tail = broker.read_ranges("In", in_before,
                                      broker.latest_offsets("In"))
            if status not in (200, 204) or not any(
                    km.message.startswith(
                        f"{known_user},{item_id(3)},4.5") for km in tail):
                failures.append(f"POST /pref (status {status}) never "
                                "reached the input topic")

            metrics = _get_json(f"{base}/metrics")
            mm = metrics.get("model_metrics", {})
            route = mm.get("kernel_route") or {}
            result["kernel_route"] = {
                k: route.get(k) for k in (
                    "path", "chosen", "batch", "capacity", "use_lsh",
                    "costs_exact_ms", "errors")}
            result["model_load_s"] = metrics.get(
                "freshness", {}).get("model_load_s")
            stages["route_measure"] = round(sum(
                r["device_s"] for r in metrics.get(
                    "device_time", {}).get("by_route", [])
                if r["route_class"] == "measure"), 2)
            want_path = "streaming" if streams else "flat"
            if route.get("path") != want_path:
                failures.append(f"kernel route path {route.get('path')!r}, "
                                f"wanted {want_path!r}")
            costs = route.get("costs_exact_ms") or {}
            if not any(isinstance(c, (int, float)) for c in costs.values()):
                failures.append(f"no kernel cost was measured: {costs}")
            if on_tpu:
                # on the chip nothing may fall back
                if route.get("errors"):
                    failures.append(
                        f"kernel_route.errors: {route['errors']}")
                if streams:
                    missing = [k for k in PALLAS_KINDS if not isinstance(
                        costs.get(k), (int, float))]
                    if missing:
                        failures.append(
                            f"no measured cost for Pallas build(s) "
                            f"{missing}: {costs}")
        except _Abort:
            pass
        except Exception as e:  # noqa: BLE001 — reported, then exit != 0
            logging.getLogger("chip_smoke").exception("smoke phase failed")
            failures.append(f"{type(e).__name__}: {e}")
        finally:
            for layer in (speed, serving):
                if layer is not None:
                    layer.close()
            drop_broker(BROKER)
            layers_log.removeHandler(marks)
            layers_log.setLevel(level_was)
            jax.monitoring.unregister_event_listener(_on_event)
    result["compile_cache"] = dict(
        cache, misses=cache["requests"] - cache["hits"])
    result["ok"] = not failures
    return result


class _Abort(Exception):
    """A failed phase the later phases depend on (already recorded)."""


def verdict(result: dict) -> dict:
    """The script's last stdout line: the verdict and the device as JAX
    reports it, these keys and no others (the readings go on the line
    before it)."""
    dev = result["device"]
    return {"ok": bool(result["ok"]),
            "device": {"platform": str(dev["platform"]),
                       "kind": str(dev["kind"]), "count": int(dev["count"])}}


def main() -> int:
    logging.basicConfig(
        stream=sys.stderr, level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    try:
        import oryx_tpu  # noqa: F401 — the program this script drives
    except ImportError:
        print("chip_smoke: the oryx_tpu package is not beside this script",
              file=sys.stderr)
        return 2
    try:
        info = device_info()
    except Exception as e:  # noqa: BLE001 — no backend at all
        print(f"chip_smoke: JAX found no device: {e}", file=sys.stderr)
        return 2
    if info["backend"] != "tpu":
        # JAX itself drops to the CPU with a warning when it finds no
        # chip; here that is a failure, and nothing is printed to stdout
        print(f"chip_smoke: no TPU: jax.default_backend() is "
              f"{info['backend']!r} ({info['device']['kind']}); this "
              "script only passes on the chip", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    result = run_smoke()
    result["wall_s"] = round(time.perf_counter() - t0, 2)
    print(json.dumps(result))
    print(json.dumps(verdict(result)), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
