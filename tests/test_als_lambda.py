"""The write path of a serving model that is kept fresh while it serves
(PR 27): the in-place row sync and its ordering rule, the Gramian
without a copy, one resident model for a co-located speed and serving
layer, and the fold-in / replay references the benchmark's
``als_lambda`` application holds them to.  CPU, small sizes, seeded."""

import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.apps import als_lambda_reference as ref  # noqa: E402
from oryx_tpu.app.als import feature_vectors as fv  # noqa: E402
from oryx_tpu.app.als.feature_vectors import FeatureVectorStore  # noqa: E402
from oryx_tpu.app.als.serving_manager import ALSServingModelManager  # noqa: E402
from oryx_tpu.app.als.serving_model import ALSServingModel  # noqa: E402
from oryx_tpu.app.als.speed import ALSSpeedModelManager  # noqa: E402
from oryx_tpu.common.config import from_dict  # noqa: E402
from oryx_tpu.kafka.api import KEY_UP, KeyMessage  # noqa: E402


def _store(sharded: bool, n: int = 512, k: int = 12, dtype="bfloat16"):
    sharding = None
    if sharded:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.array(jax.devices()[:2]), ("items",))
        sharding = NamedSharding(mesh, PartitionSpec("items", None))
    store = FeatureVectorStore(k, dtype=dtype, device_sharding=sharding)
    rng = np.random.default_rng(7)
    store.bulk_load([f"i{j}" for j in range(n)],
                    rng.standard_normal((n, k)).astype(np.float32))
    return store, rng


# -- the in-place sync ----------------------------------------------------------

@pytest.mark.parametrize("sharded", [False, True],
                         ids=["single-device", "two-way-sharding"])
def test_in_place_sync_equals_out_of_place_row_for_row(sharded):
    store, rng = _store(sharded)
    first, active0, v0 = store.device_arrays_versioned()
    n_dev = len(first.sharding.device_set)
    assert n_dev == (2 if sharded else 1)
    for round_ in range(3):
        held = store.device_arrays()[0]
        for j in rng.choice(512, 37, replace=False):
            store.set_vector(f"i{j}", rng.standard_normal(12))
        store.set_vector(f"new{round_}", rng.standard_normal(12))
        store.remove(f"i{500 + round_}")
        assert store.pending_rows() == 39
        vecs, active, version = store.device_arrays_versioned()
        # the sync donated the resident array: no second copy was made
        assert held.is_deleted() and not vecs.is_deleted()
        assert len(vecs.sharding.device_set) == n_dev
        host, live, _ = store.host_arrays()
        # out of place: the whole mirror uploaded afresh
        want = np.asarray(jnp.asarray(store._pad_cols(host)))
        assert np.array_equal(np.asarray(vecs).view(np.uint16),
                              want.view(np.uint16))
        assert np.array_equal(np.asarray(active), live)
    assert version == v0 + 3 and store.device_syncs == 4
    # the first upload carried the whole padded capacity
    assert store.rows_synced == len(store.row_ids()) + 3 * 39


def test_a_sync_of_vectors_alone_keeps_the_mask_and_what_hangs_on_it():
    store, rng = _store(False)
    _, active, _ = store.device_arrays_versioned()
    store.set_vector("i3", rng.standard_normal(12))
    with store.dispatching() as snap:
        assert snap.synced_rows == 1 and snap.active is active
    store.set_vector("brand-new", rng.standard_normal(12), tag="b7")
    with store.dispatching() as snap:
        assert snap.tags == ("b7",) and snap.active is not active
    assert store.rows_changed_since(1).tolist() == sorted(
        {store.row_of("i3"), store.row_of("brand-new")})
    assert store.rows_changed_since(0) is None  # before the upload


def test_the_scatter_ladder_is_warmed_without_changing_a_row():
    store, _ = _store(False)
    before = np.asarray(store.device_arrays()[0]).copy()
    assert store.warm_sync(64) == 4  # 8, 16, 32, 64
    assert np.array_equal(np.asarray(store.device_arrays()[0]), before)


def test_drains_and_syncs_from_four_threads_see_no_deleted_or_torn_array(
        monkeypatch):
    """Two threads write whole rows of one repeated number while two
    drain: through the model's batched entry point, and through
    ``dispatching`` itself, where a row that is not one number repeated
    would be a torn write."""
    from oryx_tpu.app.als import serving_model as sm
    monkeypatch.setattr(sm, "_FLAT_SCORES_LIMIT", 1)  # the streaming path
    k, n = 8, 2048
    model = ALSServingModel(k, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(n)],
                      np.ones((n, k), np.float32))
    model.top_n_batch(4, np.ones((2, k), np.float32))
    stop, errors = threading.Event(), []
    counts = {"writes": 0, "drains": 0, "views": 0}

    def writer(offset):
        value = 1
        try:
            while not stop.is_set():
                value += 1
                for j in range(offset, n, 97):
                    model.set_item_vector(f"i{j}",
                                          np.full(k, float(value % 200)))
                    counts["writes"] += 1
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def drain():
        try:
            while not stop.is_set():
                out = model.top_n_batch(4, np.ones((3, k), np.float32))
                assert len(out) == 3 and all(len(r) == 4 for r in out)
                counts["drains"] += 1
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def view():
        rows = jnp.arange(0, n, 13)
        try:
            while not stop.is_set():
                with model.Y.dispatching() as snap:
                    taken = jnp.take(snap.vecs, rows, axis=0)
                got = np.asarray(taken)[:, :k]
                assert (got == got[:, :1]).all(), "a torn row"
                counts["views"] += 1
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(0,)),
               threading.Thread(target=writer, args=(1,)),
               threading.Thread(target=drain),
               threading.Thread(target=view)]
    for t in threads:
        t.start()
    threading.Event().wait(3.0)
    stop.set()
    for t in threads:
        t.join(30)
    assert not errors, errors
    assert min(counts.values()) > 5, counts
    assert model.Y.device_syncs > 5
    vecs, _ = model.Y.device_arrays()
    host, _, _ = model.Y.host_arrays()
    assert np.array_equal(np.asarray(vecs)[:, :k], host)


# -- the Gramian ----------------------------------------------------------------

def test_vtv_equals_numpy_and_holds_no_copy_of_the_store():
    # float32: the CPU backend widens a bfloat16 operand into a float32
    # temporary, which the chip's compiler does not (PERF.md: the AOT
    # compile for "TPU v5 lite" at 20M x 250 holds no temporary)
    store, rng = _store(False, n=4096, k=24, dtype="float32")
    host, _, _ = store.host_arrays()
    y = host.astype(np.float64)
    assert np.allclose(store.vtv(), y.T @ y, rtol=1e-5, atol=1e-3)
    vecs, _ = store.device_arrays()
    compiled = fv._gramian.lower(vecs).compile()
    text = compiled.as_text()
    store_shape = f"[{vecs.shape[0]},{vecs.shape[1]}]"
    transposed = f"[{vecs.shape[1]},{vecs.shape[0]}]"
    for line in text.splitlines():
        if " transpose(" in line or " copy(" in line:
            assert store_shape not in line.split("=")[0] \
                and transposed not in line.split("=")[0], line
    stats = compiled.memory_analysis()
    if stats is not None:
        assert stats.temp_size_in_bytes < vecs.nbytes // 4
    # from here on: corrections for the rows written, no second scan
    for j in rng.choice(4096, 50, replace=False):
        store.set_vector(f"i{j}", rng.standard_normal(24))
    store.set_vector("fresh", rng.standard_normal(24))
    store.remove("i9")
    host, _, _ = store.host_arrays()
    y = host.astype(np.float64)
    assert np.allclose(store.vtv(), y.T @ y, rtol=1e-5, atol=1e-3)
    assert store.gramian_scans == 1
    store.bulk_load(["i1", "i2"], rng.standard_normal((2, 24)))
    host, _, _ = store.host_arrays()
    y = host.astype(np.float64)
    assert np.allclose(store.vtv(), y.T @ y, rtol=1e-5, atol=1e-3)
    assert store.gramian_scans == 2


def test_the_reference_gramian_agrees_with_numpy():
    store, _ = _store(False, n=1024, k=12)
    vecs, _ = store.device_arrays()
    y = np.asarray(vecs).astype(np.float64)
    assert np.allclose(ref.gramian(vecs, block=256), y.T @ y, rtol=1e-6,
                       atol=1e-4)


# -- one resident model, fold-in against the reference ----------------------------

def _config(**extra):
    return from_dict(dict({
        "oryx.id": "t27",
        "oryx.input-topic.broker": None,
        "oryx.update-topic.broker": None,
        "oryx.speed.model-manager-class":
            "oryx_tpu.app.als.speed.ALSSpeedModelManager",
    }, **extra))


def _resident(implicit: bool, k: int = 10, nu: int = 60, ni: int = 200,
              dtype="float32"):
    config = _config(**{"oryx.als.factor-dtype": dtype})
    serving = ALSServingModelManager(config)
    rng = np.random.default_rng(27)
    model = ALSServingModel(k, implicit, dtype=dtype)
    # predictions mostly inside (0, 1), so that most events move both
    model.bulk_load_users([f"u{j}" for j in range(nu)],
                          rng.standard_normal((nu, k)) * 0.25)
    model.bulk_load_items([f"i{j}" for j in range(ni)],
                          rng.standard_normal((ni, k)) * 0.25)
    serving.model = model
    serving._triggered_solver = True
    speed = ALSSpeedModelManager(config)
    speed.attach_serving(serving)
    return serving, speed, model, rng


@pytest.mark.parametrize("implicit", [True, False],
                         ids=["implicit", "explicit"])
def test_build_updates_to_served_store_equals_the_numpy_fold_in(implicit):
    serving, speed, model, rng = _resident(implicit)
    k = model.features
    lines = [f"u{rng.integers(60)},i{rng.integers(200)},"
             f"{rng.uniform(0.2, 3.0):.3f}" for _ in range(40)]
    lines += ["u-new,i3,1.0", "u5,i-new,2.0", "u7,i8,1.5", "u7,i8,0.5"]
    x0 = {u: model.get_user_vector(u)
          for u in {ln.split(",")[0] for ln in lines}}
    y0 = {i: model.get_item_vector(i)
          for i in {ln.split(",")[1] for ln in lines}}
    gy = ref.gramian(model.Y.device_arrays()[0])[:k, :k]
    gx = ref.gramian(model.X.device_arrays()[0])[:k, :k]
    updates = list(speed.build_updates(
        [KeyMessage(None, ln) for ln in lines]))
    assert speed.events_folded == len(lines)
    # ONE resident model: the speed model's stores ARE the served ones
    assert speed.model.Y is model.Y and speed.model.X is model.X
    want = {}
    for u, i, v in ref.aggregate(lines, implicit):
        want[("X", u, i)] = ref.fold_in(gy, v, x0[u], y0[i], implicit)
        want[("Y", i, u)] = ref.fold_in(gx, v, y0[i], x0[u], implicit)
    want = {key: v for key, v in want.items() if v is not None}
    got = {}
    for message in updates:
        kind, id_, vector, others = ref.parse_up(message)
        got[(kind, id_, others[0])] = vector
    assert set(got) == set(want)
    assert ("X", "u-new", "i3") in got and ("Y", "i-new", "u5") in got
    assert ("X", "u5", "i-new") not in got  # no item vector to fold with
    for key in got:
        assert np.max(np.abs(got[key] - want[key])) \
            <= ref.FOLD_RTOL * np.max(np.abs(want[key])), key
    for message in updates:
        serving.consume_key_message(KEY_UP, message)
    assert serving.updates_applied == len(updates)
    last, known = ref.replay(updates)
    vecs, _ = model.Y.device_arrays()
    for (kind, id_), vector in last.items():
        held = model.get_user_vector(id_) if kind == "X" \
            else model.get_item_vector(id_)
        assert np.array_equal(held, vector)
        if kind == "Y":
            assert np.array_equal(
                np.asarray(vecs)[model.Y.row_of(id_), :k], vector)
    assert "i3" in model.get_known_items("u-new")
    assert known["u7"] <= model.get_known_items("u7")


def test_a_detached_speed_manager_still_keeps_its_own_copy():
    speed = ALSSpeedModelManager(_config())
    assert speed._serving is None and speed.model is None
    assert list(speed.build_updates([KeyMessage(None, "u1,i1,1.0")])) == []


def test_the_speed_log_says_which_solver_is_missing_and_why(caplog):
    serving, speed, model, _ = _resident(True, nu=3, ni=200)
    with caplog.at_level("INFO", logger="oryx_tpu.app.als.speed"):
        assert list(speed.build_updates(
            [KeyMessage(None, "u1,i1,1.0")])) == []
    said = [r.getMessage() for r in caplog.records
            if "solver" in r.getMessage()]
    assert said and "X^T X" in said[0] and "singular" in said[0]


# -- what the write path costs the threads that answer requests ------------------

def test_a_regular_gramian_gets_its_solver_without_the_svd(monkeypatch):
    """Two solvers are rebuilt every micro-batch beside the request
    threads: the SVD is for the Gramians the cheap test cannot clear."""
    from oryx_tpu.ops import solver

    rng = np.random.default_rng(3)
    y = rng.standard_normal((4000, 50))
    gram = y.T @ y

    def no_svd(*a, **k):
        raise AssertionError("the SVD ran for a well-conditioned Gramian")

    monkeypatch.setattr(np.linalg, "svd", no_svd)
    s = solver.get_solver(gram)
    b = rng.standard_normal(50)
    assert np.allclose(s.solve(b), np.linalg.solve(gram, b), rtol=1e-3,
                       atol=1e-6)


@pytest.mark.parametrize("case", ["regular", "near_singular", "indefinite",
                                  "ill_conditioned", "not_symmetric"])
def test_the_cheap_regularity_test_never_says_more_than_the_svd(case):
    """True only where the smallest singular value is above the
    threshold for certain; everything else goes to the SVD."""
    from oryx_tpu.ops import solver

    rng = np.random.default_rng(11)
    k = 12
    q, _ = np.linalg.qr(rng.standard_normal((k, k)))
    spectrum = {
        "regular": np.linspace(1.0, 40.0, k),
        "near_singular": np.r_[np.linspace(1.0, 40.0, k - 1), 1e-9],
        "indefinite": np.r_[np.linspace(1.0, 40.0, k - 1), -1.0],
        # regular by the SVD (six values just above the threshold),
        # past what the bound, the trace of the inverse, can show: it
        # must say "not shown", and the SVD then lets the matrix through
        "ill_conditioned": np.r_[np.linspace(10.0, 40.0, k - 6),
                                 np.full(6, 1.2e-3)],
        "not_symmetric": np.linspace(1.0, 40.0, k),
    }[case]
    a = (q * spectrum) @ q.T
    if case == "not_symmetric":
        a = a + np.triu(rng.standard_normal((k, k)), 1) * 5.0
    threshold = float(np.max(np.sum(np.abs(a), axis=1))) * 1e-5
    smallest = float(np.linalg.svd(a, compute_uv=False)[-1])
    shown = solver._smallest_singular_value_above(a, threshold)
    assert shown == (case == "regular")
    if shown:
        assert smallest > threshold
    if case == "near_singular":
        with pytest.raises(solver.SingularMatrixSolverException):
            solver.get_solver(a)
    if case == "ill_conditioned":
        assert smallest > threshold
        solver.get_solver(a)  # the SVD's verdict still lets it through


def test_an_update_record_carries_its_float32_exactly_in_nine_digits():
    serving, speed, model, rng = _resident(True)
    v = (rng.standard_normal(model.features)
         * 10.0 ** rng.integers(-12, 12, model.features)).astype(np.float32)
    message = speed._to_update_json("X", 'u"1', v, "i,2")
    kind, id_, vector, others = ref.parse_up(message)
    assert (kind, id_, others) == ("X", 'u"1', ["i,2"])
    assert np.array_equal(vector, v)
    assert len(message) < 14 * model.features + 40
    speed.no_known_items = True
    assert json.loads(speed._to_update_json("Y", "i1", v, "u1"))[0:2] \
        == ["Y", "i1"]


def test_a_solver_rebuilt_on_its_own_thread_is_a_span_of_the_micro_batch():
    from oryx_tpu.obs import trace as obstrace

    serving, speed, model, rng = _resident(True)
    tracer = obstrace.Tracer("speed", sample_ratio=1.0)
    for _ in range(2):  # the second finds both solvers dirty again
        with obstrace.phase("speed.micro_batch", tracer):
            updates = list(speed.build_updates(
                [KeyMessage(None, f"u{rng.integers(60)},i{j},1.0")
                 for j in range(8)]))
        # a rebuild may outlive its micro-batch: while one is in flight
        # a later get() hands out the solver before it (SolverCache)
        deadline = time.monotonic() + 30
        while (model.cached_xtx_solver._in_flight
               or model.cached_yty_solver._in_flight) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        for message in updates:
            serving.consume_key_message(KEY_UP, message)
    traces = tracer.traces_snapshot(limit=100)
    assert len(traces) == 2
    for spans in traces.values():
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        root = by_name["speed.micro_batch"][0]
        assert sorted(s["attrs"]["what"] for s in by_name["speed.gramian"]) \
            == ["X^T X", "Y^T Y"]
        assert all(s["trace_id"] == root["trace_id"]
                   for s in by_name["speed.gramian"] + by_name["speed.solve"])
    assert model.cached_yty_solver.rebuilds == 2


def test_a_background_thread_rests_in_proportion_to_its_work(monkeypatch):
    """``BackgroundShare``: a quarter of the interpreter means three
    times the work's length in rests, taken once half a millisecond of
    work has added up, and never inside the piece itself."""
    from oryx_tpu.common import lang

    now = [100.0]
    rests = []
    monkeypatch.setattr(lang.time, "perf_counter", lambda: now[0])
    monkeypatch.setattr(lang.time, "sleep", rests.append)
    share = lang.BackgroundShare(share=0.25, burst_s=0.0005)
    for _ in range(4):          # 4 x 0.1 ms: under the burst, no rest yet
        with share.work():
            now[0] += 0.0001
    assert rests == []
    with share.work():          # the fifth crosses it
        now[0] += 0.0001
    assert rests == [pytest.approx(0.0015)]
    with pytest.raises(KeyError):   # a piece that fails still counts
        with share.work():
            now[0] += 0.001
            raise KeyError("x")
    assert rests[1:] == [pytest.approx(0.003)]


def test_the_update_consumer_paces_itself_only_once_the_model_serves():
    serving, speed, model, rng = _resident(True)
    assert speed.pace is not None     # co-located: the micro-batch rests
    assert ALSSpeedModelManager(_config()).pace is None
    updates = list(speed.build_updates(
        [KeyMessage(None, f"u{j},i{j},1.0") for j in range(6)]))
    assert len(updates) >= 8
    worked = []
    real = serving._pace.work

    def counting():
        worked.append(1)
        return real()

    serving._pace.work = counting
    serving._triggered_solver = False          # a load: flat out
    fraction, serving.min_model_load_fraction = \
        serving.min_model_load_fraction, 2.0   # ... and not over yet
    serving.consume(KeyMessage(KEY_UP, m) for m in updates[:3])
    assert worked == []
    serving.min_model_load_fraction = fraction
    serving._triggered_solver = True           # a live model ...
    fresh = {"ts": str(int(time.time() * 1000))}
    stale = {"ts": str(int(time.time() * 1000) - 60_000)}
    serving.consume([KeyMessage(KEY_UP, updates[3], stale),   # a backlog
                     KeyMessage(KEY_UP, updates[4])])         # no stamp
    assert worked == []
    serving.consume(KeyMessage(KEY_UP, m, fresh) for m in updates[5:])
    assert len(worked) == len(updates) - 5     # ... and a live stream
    assert serving.updates_applied == len(updates)


def test_derived_state_is_rebuilt_whole_only_where_it_is_counted():
    """Penalties hang on the mask, so updates of vectors rebuild
    nothing; ``derived_rebuilds`` counts the whole-matrix builds."""
    model = ALSServingModel(8, True, dtype="bfloat16")
    rng = np.random.default_rng(2)
    model.bulk_load_items([f"i{j}" for j in range(256)],
                          rng.standard_normal((256, 8)))
    with model.Y.dispatching() as snap:
        pen = model._cached_penalty(snap.active, snap.version)
    model.set_item_vector("i3", rng.standard_normal(8))
    with model.Y.dispatching() as snap:
        assert snap.synced_rows == 1
        assert model._cached_penalty(snap.active, snap.version) is pen
    m = model.metrics()
    # the load's whole upload (the capacity) and the one row after it
    assert m["device_syncs"] == 2
    assert m["rows_synced"] == len(model.Y.row_ids()) + 1
    assert m["derived_rebuilds"] == 0
    with model.Y.dispatching() as snap:
        model._cached_i8(snap.vecs, snap.version)
    model.set_item_vector("i4", rng.standard_normal(8))
    with model.Y.dispatching() as snap:
        model._cached_i8(snap.vecs, snap.version)
    assert model.metrics()["derived_rebuilds"] == 2


def test_update_log_replay_equals_the_served_store_after_1000_events():
    """Co-located layers over the in-process broker: 1,000 mixed events
    (new strengths, repeats, deletes, new users and items) in ten
    micro-batches; the served stores are the log applied in order."""
    from oryx_tpu.kafka.inproc import get_broker
    from oryx_tpu.lambda_rt.speed import SpeedLayer

    serving, _speed, model, rng = _resident(True, dtype="bfloat16")

    class _Serving:  # what SpeedLayer asks of a ServingLayer
        tracer = None
        model_manager = serving

    config = _config(**{
        "oryx.input-topic.broker": "memory://t27-replay",
        "oryx.update-topic.broker": "memory://t27-replay",
        "oryx.input-topic.partitions": 2,
        "oryx.als.factor-dtype": "bfloat16"})
    layer = SpeedLayer(config, serving=_Serving())
    broker = get_broker("t27-replay")
    in_topic = config.get_string("oryx.input-topic.message.topic")
    up_topic = config.get_string("oryx.update-topic.message.topic")
    try:
        for _batch in range(10):
            for _ in range(100):
                u = f"u{rng.integers(70)}"      # u60..u69 are new
                i = f"i{rng.integers(215)}"     # i200..i214 are new
                v = "" if rng.random() < 0.1 else f"{rng.uniform(.2, 2):.2f}"
                broker.send(in_topic, u, f"{u},{i},{v}",
                            headers={"ts": "1"})
            layer.run_one_micro_batch()
            end = broker.latest_offsets(up_topic)[0]
            applied = serving.updates_applied
            serving.consume(broker.read_range(up_topic, applied, end))
    finally:
        layer.close()
    log = broker.read_range(up_topic, 0, broker.latest_offsets(up_topic)[0])
    assert layer.model_manager.events_folded == 1000
    assert serving.updates_applied == len(log) > 500
    assert {km.headers["batch"] for km in log} == {str(b)
                                                   for b in range(1, 11)}
    assert log[-1].headers["in"] == ",".join(
        str(e) for e in broker.latest_offsets(in_topic))
    last, known = ref.replay(km.message for km in log)
    vecs, _ = model.Y.device_arrays()
    on_device = np.asarray(vecs).astype(np.float32)
    for (kind, id_), vector in last.items():
        want = ref.stored(vector, model.Y.dtype)
        held = model.get_user_vector(id_) if kind == "X" \
            else model.get_item_vector(id_)
        assert ref.ulps_apart(held, want, model.Y.dtype) == 0
        if kind == "Y":
            row = on_device[model.Y.row_of(id_), :model.features]
            assert np.array_equal(row, want)
    for u, items in known.items():
        assert items <= model.get_known_items(u)
    assert model.Y.gramian_scans == 1 and model.X.gramian_scans == 1


def test_ulps_apart_counts_across_zero_and_in_both_dtypes():
    import ml_dtypes
    bf16 = np.dtype(ml_dtypes.bfloat16)
    one = np.array([1.0], np.float32)
    assert ref.ulps_apart(one, one, bf16) == 0
    assert ref.ulps_apart(one, one * (1 + 2 ** -7), bf16) == 1
    tiny = np.array([1e-40], np.float32).astype(bf16).astype(np.float32)
    assert ref.ulps_apart(tiny, -tiny, bf16) == 2 * int(
        tiny.astype(bf16).view(np.int16)[0])
    assert ref.ulps_apart(one, np.nextafter(one, 2), np.float32) == 1


# -- the benchmark's reader and application ---------------------------------------

def test_ingest_to_servable_joins_a_micro_batch_with_its_last_sync():
    from benchmark.observe import Observations
    from benchmark.readers import ingest_to_servable

    def span(name, start, dur, **attrs):
        return {"name": name, "start_ms": start, "duration_ms": dur,
                "attrs": attrs}

    spans = [
        span("speed.micro_batch", 1000.0, 80.0, batch=1,
             oldest_wait_ms=900),
        span("speed.micro_batch", 2000.0, 80.0, batch=2,
             oldest_wait_ms=950),
        span("speed.micro_batch", 3000.0, 80.0, batch=3,
             oldest_wait_ms=None),
        span("serving.apply_updates", 1050.0, 1.0, batches=["1"]),
        span("serving.apply_updates", 1100.0, 2.0, batches=["1"]),
        span("serving.apply_updates", 2110.0, 1.0, batches=["2", "1"]),
        span("serving.apply_updates", 9000.0, 1.0, batches=[]),
    ]
    obs = Observations(spans=spans, counters_start={}, counters_end={},
                       batch_sizes=[], trace=None, store={}, peaks=None)
    params = {"batch_span": "speed.micro_batch",
              "sync_span": "serving.apply_updates"}
    # batch 1: arrived 100, last servable 2111; batch 2: 1050 -> 2111
    assert ingest_to_servable.read(obs, params) \
        == pytest.approx((2011.0 + 1061.0) / 2)
    obs.spans = spans[:3]
    assert ingest_to_servable.read(obs, params) is None


def test_the_lambda_checker_rehearsed_on_the_cpu():
    """``benchmark/apps/als_lambda.py`` on ``rehearsal-tiny``-sized
    factors: the burst before the window against the NumPy fold-in
    (step 2), then a short write stream and the accounting and replay
    after it (step 4)."""
    from benchmark import manifest, run

    cell = manifest.resolve(
        ROOT, "benchmark/tests/rehearsal_lambda_manifest.json",
        "tiny-lambda.two-callers")
    cell.config["writes"]["seconds"] = 3
    app = run.load_app(cell.config["app"])
    layer, _ = run.start_layer(cell, app, 27, False)
    try:
        checker = app.Checker(layer, cell, 27)
        assert checker.fold_in_check() == []
        assert checker.readings["fold_in_worst_rel"] < 1e-5
        assert checker.speed.model_manager.model.Y is checker.model.Y
        checker.counters()   # the window's start ...
        checker.start_writer(30.0)
        problems = checker.check([])
        assert problems == []
        told = checker.readings["writes"]
        assert told["acked"] > 30 and told["failed"] == 0
        c = told["counters"]
        assert c["events_acked"] == c["events_folded"] \
            == told["input_records"] == told["acked"] + 64
        assert checker.readings["final_users"] > 0
        assert checker.readings["ingest_to_applied"]["max_ms"] < 3000
        # a store that lost an update is found out
        some = next(id_ for kind, id_ in
                    ref.replay(km.message for km in
                               checker._update_log())[0] if kind == "Y")
        checker.model.Y.set_vector(some, np.zeros(checker.model.features))
        assert any(some in p for p in checker._stores_against(
            ref.replay(km.message
                       for km in checker._update_log())[0], ulps=0))
    finally:
        checker.speed.close()
        layer.close()
