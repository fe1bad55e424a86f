"""ALS app tests (reference analogs: ALSUtilsTest, ALSUpdateIT,
ALSSpeedIT, ALSServingModelTest, ALSServingModelManagerIT,
LocalitySensitiveHashTest)."""

import math
import os

import numpy as np
import pytest

from oryx_tpu.app.als import common as als_common
from oryx_tpu.app.als import evaluation
from oryx_tpu.app.als.feature_vectors import FeatureVectorStore
from oryx_tpu.app.als.lsh import LocalitySensitiveHash, choose_hash_count
from oryx_tpu.app.als.serving_manager import ALSServingModelManager
from oryx_tpu.app.als.serving_model import ALSServingModel, SolverCache
from oryx_tpu.app.als.speed import ALSSpeedModelManager
from oryx_tpu.app.als.trainer import train_als, predict_pairs
from oryx_tpu.app.als.update import ALSUpdate, load_features, save_features
from oryx_tpu.common import pmml as pmml_io
from oryx_tpu.common.config import from_dict
from oryx_tpu.kafka.api import KEY_MODEL, KEY_UP, KeyMessage
from oryx_tpu.kafka.inproc import InProcBroker, InProcTopicProducer, get_broker


# -- common: parse/aggregate/known ------------------------------------------

def test_aggregate_implicit_sums_and_deletes():
    events = [("u", "i", 1.0, 1), ("u", "i", 2.0, 2), ("u", "j", 1.0, 3),
              ("v", "i", float("nan"), 4)]
    r = als_common.aggregate(events, implicit=True)
    pairs = {(r.user_ids[u], r.item_ids[i]): v
             for u, i, v in zip(r.users, r.items, r.values)}
    assert pairs[("u", "i")] == 3.0
    assert pairs[("u", "j")] == 1.0
    assert ("v", "i") not in pairs  # delete wiped the pair


def test_aggregate_implicit_delete_after_add():
    events = [("u", "i", 1.0, 1), ("u", "i", float("nan"), 2)]
    r = als_common.aggregate(events, implicit=True)
    assert len(r.values) == 0


def test_aggregate_explicit_last_wins():
    events = [("u", "i", 3.0, 1), ("u", "i", 5.0, 2)]
    r = als_common.aggregate(events, implicit=False)
    assert list(r.values) == [5.0]


def test_decay():
    day_ms = 86_400_000
    assert als_common.decay_value(1.0, 0, 3 * day_ms, 0.9) == pytest.approx(0.9 ** 3)
    assert als_common.decay_value(1.0, 5, 5, 0.9) == 1.0  # not older than now


def test_known_items_delete():
    events = [("u", "a", 1.0, 1), ("u", "b", 1.0, 2), ("u", "a", float("nan"), 3)]
    known = als_common.build_known_items(events)
    assert known["u"] == {"b"}


def test_parse_events_orders_by_timestamp():
    msgs = [KeyMessage(None, "u,i,1,300"), KeyMessage(None, "u,j,1,100")]
    events = als_common.parse_events(msgs)
    assert [e[3] for e in events] == [100, 300]


# -- trainer ----------------------------------------------------------------

def _synthetic_explicit(nu=120, ni=60, k=3, density=0.35, seed=0):
    rng = np.random.default_rng(seed)
    Xt = rng.standard_normal((nu, k))
    Yt = rng.standard_normal((ni, k))
    R = Xt @ Yt.T
    mask = rng.random((nu, ni)) < density
    us, its = np.nonzero(mask)
    return als_common.ParsedRatings(
        [f"u{i}" for i in range(nu)], [f"i{j}" for j in range(ni)],
        us.astype(np.int32), its.astype(np.int32),
        R[us, its].astype(np.float32)), R, mask


def test_train_als_explicit_recovers_low_rank():
    ratings, R, mask = _synthetic_explicit()
    m = train_als(ratings, features=3, lam=0.01, alpha=1.0, implicit=False,
                  iterations=6, seed=1)
    pred = predict_pairs(m.X, m.Y, ratings.users, ratings.items)
    rmse = float(np.sqrt(np.mean((pred - ratings.values) ** 2)))
    assert rmse < 0.1
    # held-out generalization
    held = ~mask & (np.random.default_rng(9).random(mask.shape) < 0.05)
    u2, i2 = np.nonzero(held)
    p2 = predict_pairs(m.X, m.Y, u2.astype(np.int32), i2.astype(np.int32))
    assert float(np.sqrt(np.mean((p2 - R[u2, i2]) ** 2))) < 0.3


def test_train_als_implicit_ranks_positives_higher():
    ratings, R, _ = _synthetic_explicit(seed=3)
    pos = R > 1.0
    us, its = np.nonzero(pos)
    r = als_common.ParsedRatings(ratings.user_ids, ratings.item_ids,
                                 us.astype(np.int32), its.astype(np.int32),
                                 np.ones(len(us), np.float32))
    m = train_als(r, 3, 0.01, 1.0, True, 5, seed=2)
    s = m.X @ m.Y.T
    assert float(s[pos].mean()) > float(s[~pos].mean()) + 0.3


def test_evaluation_auc_perfect_and_random():
    # construct scores where positives always outrank: AUC ~ 1
    X = np.eye(4, dtype=np.float32)
    Y = np.vstack([np.eye(4), -np.eye(4)]).astype(np.float32)
    users = np.arange(4, dtype=np.int32)
    items = np.arange(4, dtype=np.int32)  # item i == best for user i
    auc = evaluation.area_under_curve(X, Y, users, items)
    assert auc > 0.9


# -- artifacts --------------------------------------------------------------

def test_save_load_features_round_trip(tmp_path):
    ids = ["a", "b", "c"]
    mat = np.array([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]], dtype=np.float32)
    save_features(str(tmp_path / "X"), ids, mat)
    ids2, mat2 = load_features(str(tmp_path / "X"))
    assert ids2 == ids
    np.testing.assert_allclose(mat2, mat, rtol=1e-6)


# -- feature store ----------------------------------------------------------

def test_feature_store_basics():
    fs = FeatureVectorStore(2, initial_capacity=4)
    fs.set_vector("a", [1.0, 2.0])
    fs.set_vector("b", [3.0, 4.0])
    assert len(fs) == 2
    np.testing.assert_array_equal(fs.get_vector("a"), [1.0, 2.0])
    fs.remove("a")
    assert fs.get_vector("a") is None
    # grow beyond capacity
    for i in range(10):
        fs.set_vector(f"x{i}", [float(i), 0.0])
    assert len(fs) == 11
    vecs, active = fs.device_arrays()
    assert int(np.asarray(active).sum()) == 11


def test_feature_store_retain_recent():
    fs = FeatureVectorStore(2)
    fs.set_vector("old1", [1, 1])
    fs.set_vector("old2", [2, 2])
    fs.device_arrays()
    fs._recent.clear()  # simulate time passing: nothing recent
    fs.set_vector("recent", [3, 3])
    fs.retain_recent_and_ids(["old1"])
    assert "old1" in fs and "recent" in fs and "old2" not in fs


def test_feature_store_vtv():
    fs = FeatureVectorStore(2)
    fs.set_vector("a", [1.0, 2.0])
    fs.set_vector("b", [3.0, 4.0])
    expected = np.array([[1, 2], [3, 4]], dtype=np.float32)
    np.testing.assert_allclose(fs.vtv(), expected.T @ expected, rtol=1e-5)


def test_feature_store_incremental_device_sync():
    fs = FeatureVectorStore(2, initial_capacity=64)
    for i in range(20):
        fs.set_vector(f"v{i}", [float(i), 1.0])
    v1, _ = fs.device_arrays()
    fs.set_vector("v3", [99.0, 99.0])  # single dirty row -> scatter path
    v2, _ = fs.device_arrays()
    row = fs.row_of("v3")
    # device snapshot is lane-padded to 128 features; the true columns
    # carry the update and the padding stays exactly zero
    assert v2.shape[1] == fs.device_features == 128
    np.testing.assert_array_equal(np.asarray(v2)[row][:2], [99.0, 99.0])
    assert not np.asarray(v2)[row][2:].any()


# -- LSH --------------------------------------------------------------------

def test_choose_hash_count_full_sample():
    nh, _ = choose_hash_count(1.0, 8)
    assert nh <= 3  # near-trivial hashing at sample rate 1.0


def test_lsh_masks_fraction_of_items():
    lsh = LocalitySensitiveHash(0.3, 8, num_cores=8)
    assert lsh.num_hashes > 0
    rng = np.random.default_rng(5)
    items = rng.standard_normal((2000, 8)).astype(np.float32)
    import jax.numpy as jnp
    buckets = jnp.asarray(lsh.bucket_of(items))
    q = rng.standard_normal(8).astype(np.float32)
    mask = np.asarray(lsh.candidate_mask(q, buckets))
    frac = mask.mean()
    assert 0.02 < frac < 0.8  # prunes, but keeps a viable candidate set
    # query's own bucket always included: a vector equal to an item
    mask_self = np.asarray(lsh.candidate_mask(np.asarray(items[0]), buckets))
    assert mask_self[0]


# -- serving model ----------------------------------------------------------

def _make_serving_model(nu=20, ni=50, k=4, seed=0):
    rng = np.random.default_rng(seed)
    model = ALSServingModel(k, implicit=True)
    X = rng.standard_normal((nu, k)).astype(np.float32)
    Y = rng.standard_normal((ni, k)).astype(np.float32)
    for i in range(nu):
        model.set_user_vector(f"u{i}", X[i])
    for j in range(ni):
        model.set_item_vector(f"i{j}", Y[j])
    return model, X, Y


def test_top_n_matches_numpy():
    model, X, Y = _make_serving_model()
    got = model.top_n(5, user_vector=X[0])
    scores = Y @ X[0]
    want_idx = np.argsort(-scores)[:5]
    assert [g[0] for g in got] == [f"i{j}" for j in want_idx]
    np.testing.assert_allclose([g[1] for g in got], scores[want_idx], rtol=1e-5)


def test_top_n_excludes_known_items():
    model, X, Y = _make_serving_model()
    scores = Y @ X[0]
    best = f"i{int(np.argmax(scores))}"
    got = model.top_n(5, user_vector=X[0], exclude={best})
    assert best not in [g[0] for g in got]
    assert len(got) == 5


def test_top_n_cosine_and_lowest():
    model, X, Y = _make_serving_model()
    v = Y[7]
    got = model.top_n(3, cosine_to=v)
    # the item itself has cosine 1.0 -> top
    assert got[0][0] == "i7"
    assert got[0][1] == pytest.approx(1.0, abs=1e-5)
    low = model.top_n(3, user_vector=X[0], lowest=True)
    scores = Y @ X[0]
    assert low[0][0] == f"i{int(np.argmin(scores))}"


def test_top_n_with_rescorer():
    from oryx_tpu.app.als.rescorer import Rescorer

    class Halver(Rescorer):
        def rescore(self, item_id, score):
            return score * 0.5

        def is_filtered(self, item_id):
            return item_id == "i0"

    model, X, Y = _make_serving_model()
    got = model.top_n(5, user_vector=X[0], rescorer=Halver())
    assert "i0" not in [g[0] for g in got]
    scores = (Y @ X[0]) * 0.5
    order = [f"i{j}" for j in np.argsort(-scores) if j != 0][:5]
    assert [g[0] for g in got] == order


def test_fraction_loaded_and_retain():
    model, X, Y = _make_serving_model(nu=4, ni=4)
    assert model.get_fraction_loaded() == 1.0
    model.set_expected_ids(["u0", "new1", "new2"], ["i0"])
    # u0/i0 already loaded; new1,new2 expected -> 8/(8+2)
    assert model.get_fraction_loaded() == pytest.approx(8 / 10)
    model.add_known_items("u0", ["i1"])
    model.add_known_items("gone", ["i2"])
    # clear recency so only the new model's IDs are kept
    model.X._recent.clear()
    model.Y._recent.clear()
    model.retain_recent_and_known_items(["u0"], ["i1", "i3"])
    assert model.get_known_items("gone") == set()
    assert model.get_known_items("u0") == {"i1"}
    # items absent from the new model are pruned from surviving sets
    model.add_known_items("u0", ["i9"])
    model.Y._recent.clear()
    model.retain_recent_and_known_items(["u0"], ["i1"])
    assert model.get_known_items("u0") == {"i1"}


def test_item_popularity_counts_incremental():
    """The popularity counter tracks known-items writes AND model-swap
    pruning exactly (backs O(items) /mostPopularItems)."""
    model, X, Y = _make_serving_model(nu=4, ni=4)
    model.add_known_items("u0", ["i1", "i2"])
    model.add_known_items("u1", ["i1"])
    model.add_known_items("u1", ["i1"])          # duplicate: no double count
    assert model.get_item_popularity_counts() == {"i1": 2, "i2": 1}
    model.X._recent.clear()
    model.Y._recent.clear()
    # u1 dropped entirely; u0 keeps only i1
    model.retain_recent_and_known_items(["u0"], ["i1"])
    assert model.get_item_popularity_counts() == {"i1": 1}


def test_top_n_lowest_with_rescorer():
    from oryx_tpu.app.als.rescorer import Rescorer

    class Identity(Rescorer):
        def rescore(self, item_id, score):
            return score

    model, X, Y = _make_serving_model()
    got = model.top_n(3, user_vector=X[0], lowest=True, rescorer=Identity())
    scores = Y @ X[0]
    want = [f"i{j}" for j in np.argsort(scores)[:3]]
    assert [g[0] for g in got] == want


def test_solver_cache_returns_none_fast_when_singular():
    import time as _time
    cache = SolverCache(lambda: np.zeros((3, 3)))  # always singular
    t0 = _time.monotonic()
    assert cache.get(blocking=True) is None
    assert _time.monotonic() - t0 < 5.0  # no stall waiting on a timeout


def test_aggregate_log_strength_domain():
    # a pair whose sum is far negative must drop, not crash the build
    events = [("u", "i", -5.0, 1), ("u", "j", 2.0, 2)]
    r = als_common.aggregate(events, implicit=True, log_strength=True,
                             epsilon=1e-5)
    assert len(r.values) == 1  # only the positive pair survives
    assert r.values[0] == pytest.approx(math.log1p(2.0 / 1e-5))


def test_solver_cache_dirty_refresh():
    calls = []

    def supplier():
        calls.append(1)
        return np.eye(3) * (len(calls) + 1.0)

    cache = SolverCache(supplier)
    s1 = cache.get(blocking=True)
    assert s1 is not None and len(calls) == 1
    s2 = cache.get(blocking=True)
    assert len(calls) == 1  # not dirty: cached
    cache.set_dirty()
    cache.compute_now()
    assert len(calls) == 2


# -- ALSUpdate end-to-end (ALSUpdateIT level) --------------------------------

def _ratings_lines(seed=0, nu=60, ni=30, k=3):
    rng = np.random.default_rng(seed)
    Xt = rng.standard_normal((nu, k))
    Yt = rng.standard_normal((ni, k))
    R = Xt @ Yt.T
    lines = []
    t = 1_500_000_000_000
    for u in range(nu):
        for i in range(ni):
            if R[u, i] > 0.5:
                lines.append(KeyMessage(None, f"u{u},i{i},{R[u, i]:.3f},{t}"))
                t += 1000
    return lines


def test_als_update_end_to_end(tmp_path):
    cfg = from_dict({
        "oryx.als.iterations": 5,
        "oryx.als.implicit": True,
        "oryx.als.hyperparams.features": 4,
        "oryx.ml.eval.test-fraction": 0.2,
    })
    update = ALSUpdate(cfg)
    data = _ratings_lines()
    broker_name = "als-e2e"
    producer = InProcTopicProducer(f"memory://{broker_name}", "Up")
    model_dir = str(tmp_path / "model")
    update.run_update(0, data, [], model_dir, producer)

    broker = get_broker(broker_name)
    msgs = list(broker.consume("Up", from_beginning=True, max_idle_sec=0.2))
    # first a MODEL, then Y rows, then X rows with known-items
    assert msgs[0].key == KEY_MODEL
    doc = pmml_io.from_string(msgs[0].message)
    assert pmml_io.get_extension_value(doc, "features") == "4"
    assert pmml_io.get_extension_value(doc, "implicit") == "true"
    x_ids = pmml_io.get_extension_content(doc, "XIDs")
    y_ids = pmml_io.get_extension_content(doc, "YIDs")
    assert len(x_ids) > 0 and len(y_ids) > 0
    ups = [m for m in msgs if m.key == KEY_UP]
    kinds = [als_common.text_utils.read_json(m.message)[0] for m in ups]
    assert kinds.count("Y") == len(y_ids)
    assert kinds.count("X") == len(x_ids)
    # Y updates come before X updates (reference ordering)
    assert kinds.index("X") > kinds.index("Y")
    # X updates carry known items
    first_x = als_common.text_utils.read_json(
        ups[kinds.index("X")].message)
    assert len(first_x) == 4 and isinstance(first_x[3], list)
    # artifacts exist under the published model dir
    gen_dirs = [d for d in os.listdir(model_dir) if d.isdigit()]
    assert len(gen_dirs) == 1
    assert os.path.exists(os.path.join(model_dir, gen_dirs[0], "X",
                                       "part-00000.gz"))


def test_als_time_based_split():
    cfg = from_dict({"oryx.ml.eval.test-fraction": 0.25})
    update = ALSUpdate(cfg)
    data = [KeyMessage(None, f"u,i,1,{1000 + i}") for i in range(100)]
    train, test = update.split_new_data_to_train_test(data)
    assert len(test) == pytest.approx(25, abs=2)
    max_train_ts = max(int(km.message.split(",")[3]) for km in train)
    min_test_ts = min(int(km.message.split(",")[3]) for km in test)
    assert max_train_ts < min_test_ts  # split purely on time


# -- speed layer (ALSSpeedIT level) -----------------------------------------

def _speed_manager_with_model(nu=12, ni=12, k=3, seed=4):
    rng = np.random.default_rng(seed)
    cfg = from_dict({})
    mgr = ALSSpeedModelManager(cfg)
    doc = pmml_io.build_skeleton_pmml()
    pmml_io.add_extension(doc, "features", k)
    pmml_io.add_extension(doc, "implicit", True)
    pmml_io.add_extension(doc, "logStrength", False)
    x_ids = [f"u{i}" for i in range(nu)]
    y_ids = [f"i{j}" for j in range(ni)]
    pmml_io.add_extension_content(doc, "XIDs", x_ids)
    pmml_io.add_extension_content(doc, "YIDs", y_ids)
    mgr.consume_key_message(KEY_MODEL, pmml_io.to_string(doc))
    # small-norm vectors keep every current estimate below 1 so implicit
    # fold-in always has a non-NaN target
    X = (0.3 * rng.standard_normal((nu, k))).astype(np.float32)
    Y = (0.3 * rng.standard_normal((ni, k))).astype(np.float32)
    for i, id_ in enumerate(x_ids):
        mgr.consume_key_message(
            KEY_UP, als_common.text_utils.join_json(
                ["X", id_, [float(v) for v in X[i]]]))
    for j, id_ in enumerate(y_ids):
        mgr.consume_key_message(
            KEY_UP, als_common.text_utils.join_json(
                ["Y", id_, [float(v) for v in Y[j]]]))
    return mgr, X, Y


def test_speed_manager_builds_fold_in_updates():
    mgr, X, Y = _speed_manager_with_model()
    assert mgr.model.get_fraction_loaded() == 1.0
    new_data = [KeyMessage(None, "u0,i1,2.5,1000"),
                KeyMessage(None, "unew,i2,1.0,2000")]
    updates = list(mgr.build_updates(new_data))
    assert updates
    parsed = [als_common.text_utils.read_json(u) for u in updates]
    # updates reference both matrices and include the other-id as known
    kinds = {p[0] for p in parsed}
    assert kinds <= {"X", "Y"}
    x_up = [p for p in parsed if p[0] == "X" and p[1] == "u0"]
    assert x_up and x_up[0][3] == ["i1"]
    # new user gets a vector from nothing (fold-in from 'don't know')
    assert any(p[0] == "X" and p[1] == "unew" for p in parsed)
    # the update moves u0's estimate for i1 upward toward 1
    old_est = float(X[0] @ Y[1])
    new_xu = np.asarray(x_up[0][2], dtype=np.float32)
    new_est = float(new_xu @ Y[1])
    if old_est < 1.0:
        assert new_est > old_est


def test_speed_manager_skips_without_model():
    mgr = ALSSpeedModelManager(from_dict({}))
    assert list(mgr.build_updates([KeyMessage(None, "u,i,1,1")])) == []
    # UP before MODEL silently ignored
    mgr.consume_key_message(KEY_UP, '["X","u",[0.1,0.2]]')
    assert mgr.model is None


def test_speed_model_feature_change_resets():
    mgr, _, _ = _speed_manager_with_model(k=3)
    doc = pmml_io.build_skeleton_pmml()
    pmml_io.add_extension(doc, "features", 5)
    pmml_io.add_extension(doc, "implicit", True)
    pmml_io.add_extension(doc, "logStrength", False)
    pmml_io.add_extension_content(doc, "XIDs", ["u0"])
    pmml_io.add_extension_content(doc, "YIDs", ["i0"])
    mgr.consume_key_message(KEY_MODEL, pmml_io.to_string(doc))
    assert mgr.model.features == 5
    assert len(mgr.model.X) == 0  # fresh model


# -- serving manager (ALSServingModelManagerIT level) ------------------------

def test_serving_manager_full_replay(tmp_path):
    # run a real batch update, then replay its topic into a serving manager
    cfg = from_dict({
        "oryx.als.iterations": 3,
        "oryx.als.implicit": True,
        "oryx.als.hyperparams.features": 3,
        "oryx.ml.eval.test-fraction": 0.0,
    })
    data = _ratings_lines(seed=7, nu=25, ni=15)
    producer = InProcTopicProducer("memory://als-serve-replay", "Up2")
    ALSUpdate(cfg).run_update(0, data, [], str(tmp_path / "m"), producer)

    mgr = ALSServingModelManager(cfg)
    broker = get_broker("als-serve-replay")
    for km in broker.consume("Up2", from_beginning=True, max_idle_sec=0.2):
        mgr.consume_key_message(km.key, km.message)
    model = mgr.get_model()
    assert model is not None
    assert model.get_fraction_loaded() == 1.0
    assert model.user_count() > 0 and model.item_count() > 0
    # a user's recommendations exclude nothing by default and score sanely
    uid = model.all_user_ids()[0]
    recs = model.top_n(5, user_vector=model.get_user_vector(uid))
    assert len(recs) == 5
    assert all(isinstance(r[0], str) for r in recs)
    # known items were delivered with X updates
    counts = model.get_known_item_counts()
    assert counts and all(v > 0 for v in counts.values())


# -- batched serving scan + bulk load ----------------------------------------

def test_top_n_batch_matches_single():
    from oryx_tpu.app.als.serving_model import ALSServingModel
    rng = np.random.default_rng(9)
    model = ALSServingModel(features=5, implicit=True)
    ids = [f"I{j}" for j in range(40)]
    Y = rng.standard_normal((40, 5)).astype(np.float32)
    model.Y.bulk_load(ids, Y)
    Q = rng.standard_normal((6, 5)).astype(np.float32)
    batch = model.top_n_batch(4, Q)
    assert len(batch) == 6
    for b in range(6):
        single = model.top_n(4, user_vector=Q[b])
        assert [i for i, _ in batch[b]] == [i for i, _ in single]
        np.testing.assert_allclose([s for _, s in batch[b]],
                                   [s for _, s in single], rtol=1e-5)


def test_top_n_batch_respects_exclusions():
    from oryx_tpu.app.als.serving_model import ALSServingModel
    rng = np.random.default_rng(10)
    model = ALSServingModel(features=3, implicit=True)
    ids = [f"I{j}" for j in range(10)]
    model.Y.bulk_load(ids, rng.standard_normal((10, 3)).astype(np.float32))
    q = rng.standard_normal((1, 3)).astype(np.float32)
    full = model.top_n_batch(3, q)[0]
    excluded = model.top_n_batch(3, q, exclude=[{full[0][0]}])[0]
    assert full[0][0] not in [i for i, _ in excluded]
    assert len(excluded) == 3


def test_bulk_load_overwrites_and_grows():
    from oryx_tpu.app.als.feature_vectors import FeatureVectorStore
    store = FeatureVectorStore(4, initial_capacity=16)
    rng = np.random.default_rng(11)
    ids = [f"x{j}" for j in range(100)]
    M = rng.standard_normal((100, 4)).astype(np.float32)
    store.bulk_load(ids, M)
    assert len(store) == 100
    np.testing.assert_array_equal(store.get_vector("x7"), M[7])
    M2 = rng.standard_normal((100, 4)).astype(np.float32)
    store.bulk_load(ids, M2)
    assert len(store) == 100
    np.testing.assert_array_equal(store.get_vector("x7"), M2[7])
    vecs, active = store.device_arrays()
    assert int(np.asarray(active).sum()) == 100


def test_feature_store_bfloat16_storage():
    from oryx_tpu.app.als.feature_vectors import FeatureVectorStore
    store = FeatureVectorStore(4, dtype="bfloat16")
    v = np.array([1.5, -2.25, 0.125, 3.0], np.float32)  # bf16-exact values
    store.set_vector("a", v)
    got = store.get_vector("a")
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, v)
    vecs, active = store.device_arrays()
    assert str(vecs.dtype) == "bfloat16"
    # device matmul still accumulates f32 and round-trips the values
    model_scores = np.asarray(store.vtv())
    assert model_scores.dtype == np.float32


def test_bulk_load_exact_fit_capacity():
    from oryx_tpu.app.als.feature_vectors import (FeatureVectorStore,
                                                  _LARGE_ALIGN)
    store = FeatureVectorStore(2, initial_capacity=16)
    n = _LARGE_ALIGN + 5000
    ids = [str(i) for i in range(n)]
    store.bulk_load(ids, np.zeros((n, 2), np.float32))
    cap = len(store.row_ids())
    # large stores size to the next chunk multiple, not the next pow2
    assert cap % _LARGE_ALIGN == 0
    assert cap - n < _LARGE_ALIGN


def _exact_scan_only(monkeypatch):
    """No gather fits, so the streaming branch admits no two-phase
    program and the exact scan is its primary path."""
    from oryx_tpu.app.als import serving_model as sm

    def never(*args, **kwargs):
        raise AssertionError("a two-phase program was dispatched")

    monkeypatch.setattr(sm, "_PHASE_B_GATHER_BYTES", 0)
    monkeypatch.setattr(ALSServingModel, "_dispatch_twophase", never)


def test_top_n_batch_chunked_matches_flat(monkeypatch):
    from oryx_tpu.app.als import serving_model as sm
    rng = np.random.default_rng(3)
    ni, k = 1500, 8
    model = ALSServingModel(k, implicit=True)
    Y = rng.standard_normal((ni, k)).astype(np.float32)
    model.Y.bulk_load([f"i{j}" for j in range(ni)], Y)
    Q = rng.standard_normal((5, k)).astype(np.float32)
    flat = model.top_n_batch(6, Q)
    monkeypatch.setattr(sm, "_FLAT_SCORES_LIMIT", 1)
    monkeypatch.setattr(sm, "_MAX_CHUNK_ROWS", 256)
    _exact_scan_only(monkeypatch)
    chunked = model.top_n_batch(6, Q)
    for f, c in zip(flat, chunked):
        assert [i for i, _ in f] == [i for i, _ in c]
        np.testing.assert_allclose([s for _, s in f], [s for _, s in c],
                                   rtol=1e-5)


def test_top_n_batch_lsh_matches_single():
    rng = np.random.default_rng(4)
    ni, k = 3000, 8
    model = ALSServingModel(k, implicit=True, sample_rate=0.3)
    assert model.lsh is not None and model.lsh.num_hashes > 0
    model.Y.bulk_load([f"i{j}" for j in range(ni)],
                      rng.standard_normal((ni, k)).astype(np.float32))
    Q = rng.standard_normal((4, k)).astype(np.float32)
    batched = model.top_n_batch(5, Q)
    exact = model.top_n_batch(5, Q, use_lsh=False)
    assert batched != exact  # the Hamming-ball mask actually pruned
    for b in range(4):
        single = model.top_n(5, user_vector=Q[b])
        assert [i for i, _ in batched[b]] == [i for i, _ in single]
        np.testing.assert_allclose([s for _, s in batched[b]],
                                   [s for _, s in single], rtol=1e-5)


def test_top_n_batch_chunked_lsh(monkeypatch):
    """Where the two-phase program is not admitted, a model under LSH
    answers by the exact scan over the window's candidates: the same
    answers."""
    from oryx_tpu.app.als import serving_model as sm
    rng = np.random.default_rng(6)
    ni, k = 1800, 8
    monkeypatch.setattr(sm, "_PA_TILE", 128)
    model = ALSServingModel(k, implicit=True, sample_rate=0.3)
    model.Y.bulk_load([f"i{j}" for j in range(ni)],
                      rng.standard_normal((ni, k)).astype(np.float32))
    Q = rng.standard_normal((3, k)).astype(np.float32)
    two = model.top_n_batch(5, Q)
    _exact_scan_only(monkeypatch)
    chunked = model.top_n_batch(5, Q)
    for f, c in zip(two, chunked):
        assert [i for i, _ in f] == [i for i, _ in c]
        np.testing.assert_allclose([s for _, s in f], [s for _, s in c],
                                   rtol=1e-5)


def test_top_n_batch_twophase_matches_flat(monkeypatch):
    """The streaming two-phase path (block maxima + approx block pick +
    exact rescore + certificate) agrees with the flat exact kernel."""
    from oryx_tpu.app.als import serving_model as sm
    rng = np.random.default_rng(12)
    ni, k = 4096, 8
    model = ALSServingModel(k, implicit=True)
    Y = rng.standard_normal((ni, k)).astype(np.float32)
    model.Y.bulk_load([f"i{j}" for j in range(ni)], Y)
    Q = rng.standard_normal((5, k)).astype(np.float32)
    flat = model.top_n_batch(6, Q)
    monkeypatch.setattr(sm, "_FLAT_SCORES_LIMIT", 1)
    monkeypatch.setattr(sm, "_MAX_CHUNK_ROWS", 1024)
    monkeypatch.setattr(sm, "_BLOCK_ROWS", 64)
    monkeypatch.setattr(sm, "_BLOCK_KSEL", 8)
    two = model.top_n_batch(6, Q)
    assert model.twophase_fallbacks == 0
    for f, c in zip(flat, two):
        assert [i for i, _ in f] == [i for i, _ in c]
        np.testing.assert_allclose([s for _, s in f], [s for _, s in c],
                                   rtol=1e-5)
    # a model under LSH: the two-phase scan over the candidates' steps
    # against the single-request path's mask over the whole store
    monkeypatch.setattr(sm, "_PA_TILE", 64)
    model2 = ALSServingModel(k, implicit=True, sample_rate=0.3)
    model2.Y.bulk_load([f"i{j}" for j in range(ni)], Y)
    lsh_two = model2.top_n_batch(6, Q)
    assert model2.lsh_windows == 1 and model2.twophase_fallbacks == 0
    for b, c in enumerate(lsh_two):
        single = model2.top_n(6, user_vector=Q[b])
        assert [i for i, _ in single] == [i for i, _ in c]


def test_top_n_batch_twophase_cert_fallback(monkeypatch):
    """A failed exactness certificate triggers the exact-scan recompute
    and still returns correct results."""
    from oryx_tpu.app.als import serving_model as sm
    rng = np.random.default_rng(13)
    ni, k = 2048, 8
    model = ALSServingModel(k, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(ni)],
                      rng.standard_normal((ni, k)).astype(np.float32))
    Q = rng.standard_normal((3, k)).astype(np.float32)
    want = model.top_n_batch(5, Q)

    real = sm._batch_top_n_twophase_kernel

    def sabotaged(*args, **kw):
        ts, ti, cert = real(*args, **kw)
        return ts, ti, cert & False  # force every certificate to fail

    monkeypatch.setattr(sm, "_FLAT_SCORES_LIMIT", 1)
    monkeypatch.setattr(sm, "_MAX_CHUNK_ROWS", 512)
    monkeypatch.setattr(sm, "_BLOCK_ROWS", 64)
    monkeypatch.setattr(sm, "_BLOCK_KSEL", 8)
    monkeypatch.setattr(sm, "_batch_top_n_twophase_kernel", sabotaged)
    got = model.top_n_batch(5, Q)
    assert model.twophase_fallbacks >= 1
    for f, c in zip(want, got):
        assert [i for i, _ in f] == [i for i, _ in c]


def _spy_on_scans(monkeypatch):
    """Counts of two-phase dispatches and exact scans from here on."""
    from oryx_tpu.app.als import serving_model as sm
    seen = {"twophase": 0, "exact": 0}
    real_two = ALSServingModel._dispatch_twophase
    real_exact = sm._batch_top_n_chunked_kernel

    def two(self, *args, **kwargs):
        seen["twophase"] += 1
        return real_two(self, *args, **kwargs)

    def exact(*args, **kwargs):
        seen["exact"] += 1
        return real_exact(*args, **kwargs)

    monkeypatch.setattr(ALSServingModel, "_dispatch_twophase", two)
    monkeypatch.setattr(sm, "_batch_top_n_chunked_kernel", exact)
    return seen


@pytest.fixture(scope="module")
def wide_fetch_model():
    """8192 items in 1024 blocks of 8 rows once the ladder is forced:
    room for the 512 blocks a fetch of 256 selects."""
    rng = np.random.default_rng(26)
    model = ALSServingModel(8, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(8192)],
                      rng.standard_normal((8192, 8)).astype(np.float32))
    return model


@pytest.mark.parametrize("known, k", [(0, 16), (20, 32), (50, 64),
                                      (100, 128), (240, 256)])
def test_top_n_batch_twophase_certifies_every_fetched_width(
        known, k, wide_fetch_model, monkeypatch):
    """A user's known items widen the fetch (k = pad2(howMany + known)),
    and the block selection widens with it: every width of the 20M
    cells' traffic is certified by ONE two-phase program and equals the
    flat exact kernel, where 32 blocks at k >= 64 never certified and
    each such request ran the exact scan after it."""
    from oryx_tpu.app.als import serving_model as sm
    model = wide_fetch_model
    rng = np.random.default_rng(k)
    Q = rng.standard_normal((3, 8)).astype(np.float32)
    exclude = [{f"i{j}" for j in rng.choice(8192, known, replace=False)},
               set(), set()]
    assert sm._pad_k(10 + known) == k
    flat = model.top_n_batch(10, Q, exclude)
    monkeypatch.setattr(sm, "_FLAT_SCORES_LIMIT", 1)
    monkeypatch.setattr(sm, "_MAX_CHUNK_ROWS", 1024)
    monkeypatch.setattr(sm, "_BLOCK_ROWS", 8)
    seen = _spy_on_scans(monkeypatch)
    before = model.twophase_fallbacks
    two = model.top_n_batch(10, Q, exclude)
    assert seen == {"twophase": 1, "exact": 0}
    assert model.twophase_fallbacks == before
    assert sm._block_ksel(k, 8192, 8) == max(32, 2 * k)
    assert not {i for i, _ in two[0]} & exclude[0]
    for f, c in zip(flat, two):
        assert len(f) == 10
        assert [i for i, _ in f] == [i for i, _ in c]
        np.testing.assert_allclose([s for _, s in f], [s for _, s in c],
                                   rtol=1e-5)


def _store(n_rows):
    """The aval of a 250-feature bfloat16 item store."""
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((n_rows, 250), jnp.bfloat16)


@pytest.mark.parametrize("k, n_rows, bs, want", [
    (8, 20054016, 128, 32),      # the floor
    (16, 20054016, 128, 32),     # ... which k = 16 keeps
    (32, 20054016, 128, 64),     # twice the fetch from there on
    (256, 20054016, 128, 512),
    (16, 4096, 128, 31),         # capped under the 32 blocks
    (256, 8192, 64, 127),        # ... and then under k itself
    (8, 128, 128, 0)])           # one block: nothing to leave out
def test_block_ksel_is_twice_the_fetch_between_floor_and_cap(
        k, n_rows, bs, want):
    from oryx_tpu.app.als import serving_model as sm
    assert sm._BLOCK_KSEL == 32
    ksel = sm._block_ksel(k, n_rows, bs)
    assert ksel == want
    # the int8 builds double what they are given, under the same cap
    assert sm._i8_ksel(ksel, n_rows, bs) \
        == min(2 * ksel, max(1, n_rows // bs - 1))
    # two-phase runs where the selection is as wide as the fetch
    assert sm._twophase_admits(k, ksel, _store(n_rows), bs) == (want >= k)
    assert not sm._twophase_admits(k, ksel, _store(n_rows + 1), bs)


@pytest.mark.parametrize("b, ksel, want", [
    (8, 32, False),      # the floor: the k = 16 program stays as it was
    (8, 64, True), (32, 512, True),
    (128, 64, False), (256, 128, False)])   # the lanes are full
def test_narrow_windows_select_from_row_major_maxima(b, ksel, want):
    from oryx_tpu.app.als import serving_model as sm
    assert sm._selects_row_major(b, ksel) is want


def test_a_fetch_wider_than_the_cap_goes_straight_to_the_exact_scan(
        monkeypatch):
    """Where the block count caps ksel under k the certificate is sure
    to fail: such a fetch is ONE exact scan — no two-phase program
    first, and no fallback counted."""
    from oryx_tpu.app.als import serving_model as sm
    rng = np.random.default_rng(27)
    model = ALSServingModel(8, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(4096)],
                      rng.standard_normal((4096, 8)).astype(np.float32))
    Q = rng.standard_normal((3, 8)).astype(np.float32)
    exclude = [{f"i{j}" for j in range(0, 400, 8)}, set(), set()]
    flat = model.top_n_batch(10, Q, exclude)           # k = 64
    narrow = model.top_n_batch(10, Q)                  # k = 16
    monkeypatch.setattr(sm, "_FLAT_SCORES_LIMIT", 1)
    monkeypatch.setattr(sm, "_MAX_CHUNK_ROWS", 1024)
    monkeypatch.setattr(sm, "_BLOCK_ROWS", 64)         # 64 blocks
    assert sm._block_ksel(64, 4096, 64) == 63
    seen = _spy_on_scans(monkeypatch)
    assert model.top_n_batch(10, Q, exclude) == flat
    assert seen == {"twophase": 0, "exact": 1}
    # the same store certifies the fetch the cap leaves room for
    got = model.top_n_batch(10, Q)
    assert seen == {"twophase": 1, "exact": 1}
    assert [[i for i, _ in r] for r in got] \
        == [[i for i, _ in r] for r in narrow]
    assert model.twophase_fallbacks == 0


def test_phase_b_groups_fit_the_gather_budget_at_250f_20m():
    """No (window, k) of the ladder plans a phase-B gather over the
    budget at 250 features x 20M bfloat16 rows — the int8 builds'
    doubled width included — and every window that fits runs as ONE
    group, i.e. the ungrouped program."""
    from oryx_tpu.app.als import serving_model as sm
    n_rows, bs = 20054016, 128
    row_bytes = sm._row_bytes(_store(n_rows))
    assert row_bytes == 500
    budget = sm._PHASE_B_GATHER_BYTES
    assert budget == 1 << 30
    one_group = set()
    for b in sm._WINDOW_LADDER:
        for k in (16, 32, 64, 128, 256):
            ksel = sm._block_ksel(k, n_rows, bs)
            assert sm._twophase_admits(k, ksel, _store(n_rows), bs)
            for width in (ksel, sm._i8_ksel(ksel, n_rows, bs)):
                g = sm._phase_b_group_rows(b, width, bs, row_bytes)
                assert b % g == 0 and g >= 1
                assert g * width * bs * row_bytes <= budget
                # the largest such group: twice as many rows would not fit
                assert g == b or b % (2 * g) \
                    or 2 * g * width * bs * row_bytes > budget
                if g == b and width == ksel:
                    one_group.add((b, k))
    assert one_group == ({(8, k) for k in (16, 32, 64, 128, 256)}
                         | {(32, k) for k in (16, 32, 64, 128, 256)}
                         | {(256, 16), (256, 32)})
    # a 256-wide window fetching 256 would gather 8.4 GB at once
    assert sm._phase_b_group_rows(256, 512, bs, row_bytes) == 32
    # one row over the budget still runs, a row at a time ...
    assert sm._phase_b_group_rows(8, 1 << 20, bs, row_bytes) == 1
    # ... but the dispatch does not admit it
    assert not sm._twophase_admits(1 << 15, 1 << 16, _store(n_rows), bs)


@pytest.mark.parametrize("lsh", [False, True])
@pytest.mark.parametrize("rows_at_once, ksel", [(1, 32), (2, 32), (4, 32),
                                                (2, 64)])
def test_phase_b_in_row_groups_returns_what_one_gather_returns(
        rows_at_once, ksel, lsh, monkeypatch):
    """A window from 128 rows on that is over the gather budget runs
    phase B in equal row groups inside the same program; scores,
    indices and certificates are those of the ungrouped program, bit
    for bit.  (A narrower window gathers a request at a time and never
    meets the budget: tests/test_phase_b_rows.py.)"""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(28)
    n, f, b, k, bs = 4096, 8, 128, 16, 16
    assert not sm._rescores_requests(b)
    Y = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    Q = jnp.asarray(rng.standard_normal((b, f)).astype(np.float32))
    act = np.ones(n, bool)
    act[::7] = False
    active = jnp.asarray(act)
    prune = _toy_pruning(rng, active, n // 512, f) if lsh else None

    def program():
        # a fresh jit each time: the budget is read at trace time
        return jax.device_get(jax.jit(
            lambda: sm._batch_top_n_twophase_kernel.__wrapped__(
                Y, Q, active, prune, np.int32(b), k, 1024, bs, ksel,
                2)[:3])())

    whole = program()
    assert sm._phase_b_group_rows(b, ksel, bs, f * 4) == b
    monkeypatch.setattr(sm, "_PHASE_B_GATHER_BYTES",
                        rows_at_once * ksel * bs * f * 4)
    assert sm._phase_b_group_rows(b, ksel, bs, f * 4) == rows_at_once
    grouped = program()
    for w, g in zip(whole, grouped):
        assert w.shape == g.shape and w.dtype == g.dtype
        np.testing.assert_array_equal(w, g)
    assert whole[2].all()


def _toy_pruning(rng, active, n_steps: int, f: int):
    """What a pruned window's program takes beside the store, for a toy
    store of ``n_steps`` steps: 4 hyperplanes, every step given one of
    the 16 buckets at random (step 1 to nobody).  The kernels only read
    WHICH steps a window's rows can reach, so the rows of a step need
    not hash to its bucket here."""
    import jax.numpy as jnp

    from oryx_tpu.app.als import serving_model as sm

    table = rng.integers(0, 16, n_steps).astype(np.int32)
    table[1] = -1
    return sm.Pruning(
        jnp.asarray(table),
        sm._step_live_kernel(active, n_steps),
        jnp.asarray(rng.standard_normal((4, f)).astype(np.float32)))


def _phase_a_case(n, f, b, lsh, seed=11, integers=False):
    """A toy store for the pallas phase A in interpret mode: every fifth
    row retired, and for the exact scan a row that is query 0's best by
    far on the LAST row of the first tile, so that a block maximum
    sits where one grid step ends."""
    import jax.numpy as jnp

    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(seed)
    if integers:
        # small whole numbers: every product and every partial sum is
        # exact in float32, whatever order a layout accumulates in
        y = rng.integers(-4, 5, (n, f)).astype(np.float32)
        q = rng.integers(-4, 5, (b, f)).astype(np.float32)
    else:
        y = rng.standard_normal((n, f)).astype(np.float32)
        q = rng.standard_normal((b, f)).astype(np.float32)
    last = sm._PA_TILE - 1
    y[last] = 3.0 * q[0]
    act = np.ones(n, bool)
    act[1::5] = False
    assert act[last]
    Y, Q, active = jnp.asarray(y), jnp.asarray(q), jnp.asarray(act)
    prune = _toy_pruning(rng, active, n // sm._PA_TILE, f) \
        if lsh else None
    return Y, Q, active, prune, last


@pytest.mark.parametrize("rows", ["whole_output_tiles",
                                  "last_output_tile_partial"])
@pytest.mark.parametrize("lsh", [False, True], ids=["exact", "lsh"])
@pytest.mark.parametrize("b", [8, 32, 128])
def test_pallas_phase_a_interpret_agrees_with_scan_kernel(b, lsh, rows):
    """The pallas-built two-phase program (interpret mode, so it runs on
    the CPU test platform) must produce the same top-k as the lax.scan
    build — same phase B, same certificate semantics — in both of its
    layouts: the store's rows on the lanes for a window narrower than a
    lane tile (8, 32), the queries on the lanes from 128 on; over a
    capacity whose block maxima fill whole 128-lane output tiles (four
    grid steps each) and over one that is a multiple of the step only,
    which leaves the last tile partly filled."""
    import jax

    from oryx_tpu.app.als import serving_model as sm

    bs = 128
    n = (8 if rows == "whole_output_tiles" else 5) * sm._PA_TILE
    assert n % sm._PA_TILE == 0
    assert (n // bs % 128 == 0) == (rows == "whole_output_tiles")
    f, k, ksel, mb = 16, 8, 16, 2 if lsh else 0
    assert sm._scores_rows_on_lanes(b) == (b < 128)
    Y, Q, active, prune, last = _phase_a_case(n, f, b, lsh)
    # the last row of a pruned window is padding; every row of the
    # exact one is a request
    n_real = np.int32(b - 1 if lsh else b)
    penalty = sm._penalty_kernel(active, bs)
    ts_p, ti_p, cert_p, *stats_p = jax.device_get(
        sm._batch_top_n_twophase_pallas(
            Y, Q, penalty, active, prune, n_real, k, bs, ksel, mb,
            interpret=True))
    ts_s, ti_s, cert_s, *stats_s = jax.device_get(
        sm._batch_top_n_twophase_kernel(
            Y, Q, active, prune, n_real, k, sm._PA_TILE, bs, ksel, mb))
    if lsh:
        # the plan's numbers, and the padding row reaches nothing
        np.testing.assert_array_equal(stats_p[0], stats_s[0])
        assert 0 < stats_p[0][0] < n // sm._PA_TILE
        assert np.isneginf(ts_p[-1]).all()
    np.testing.assert_allclose(ts_p, ts_s, rtol=1e-5)
    assert (ti_p == ti_s).all()
    assert (cert_p == cert_s).all()
    assert cert_p.all()
    if not lsh:
        assert ti_p[0, 0] == last
    # no retired row is served
    assert np.asarray(active)[ti_p[np.isfinite(ts_p)]].all()


@pytest.mark.parametrize("lsh", [False, True], ids=["exact", "lsh"])
def test_pallas_phase_a_layouts_hand_over_the_same_block_maxima(lsh):
    """(B, rows) and (rows, B) reduce the same products: the block
    maxima are equal element for element, -inf for -inf, where the
    arithmetic is exact (the MXU's accumulation order differs between
    the layouts as it does between any two builds; phase B's margin
    covers that)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.app.als import serving_model as sm

    n, f, b, bs = 5 * sm._PA_TILE, 16, 8, 128
    Y, Q, active, prune, last = _phase_a_case(n, f, b, lsh,
                                              integers=True)
    # a block with no live row reads -inf in both
    active = active.at[2 * bs:3 * bs].set(False)
    penalty = sm._penalty_kernel(active, bs)
    Qc = sm._q_cast(Q, Y)
    # a pruned pass: steps 3, 0 and 2, in that order, of the five
    steps, n_visit = (jnp.asarray([3, 0, 2, 1, 4], jnp.int32),
                      jnp.int32(3)) if lsh else (None, None)
    on_lanes, on_sublanes = (
        np.asarray(jax.device_get(sm._pallas_block_maxima(
            Qc, Y, penalty, bs, layout, True, steps, n_visit)))
        for layout in (True, False))
    assert on_lanes.shape == on_sublanes.shape == (b, n // bs)
    np.testing.assert_array_equal(on_lanes, on_sublanes)
    assert np.isfinite(on_lanes).any()
    want = np.where(np.asarray(active)[None],
                    np.asarray(Q) @ np.asarray(Y).T, -np.inf
                    ).reshape(b, -1, bs).max(-1)
    per_step = sm._PA_TILE // bs
    if not lsh:
        assert np.isneginf(on_lanes[:, 2]).all()
        np.testing.assert_array_equal(on_lanes, want)
        assert on_lanes[0, last // bs] == want[0, last // bs]
    else:
        # the maxima leave in VISIT order, -inf past the last visited
        # step; the scan build hands over the same
        by_step = want.reshape(b, -1, per_step)
        np.testing.assert_array_equal(
            on_lanes.reshape(b, -1, per_step)[:, :3],
            by_step[:, [3, 0, 2]])
        assert np.isneginf(on_lanes[:, 3 * per_step:]).all()
        np.testing.assert_array_equal(
            on_lanes, np.asarray(sm._scan_step_maxima(
                Qc, Y, active, steps, n_visit, bs)))


def test_pallas_fallback_on_unsupported_backend():
    """On the CPU test platform the non-interpret pallas path cannot
    lower; the dispatcher must fall back to the scan kernel and still
    answer correctly (and permanently, without raising)."""
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(3)
    model = ALSServingModel(features=6, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(4096)],
                      rng.standard_normal((4096, 6)).astype(np.float32))
    q = rng.standard_normal((3, 6)).astype(np.float32)
    old_state = dict(sm._PALLAS_STATE)
    old_limits = (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS,
                  sm._BLOCK_KSEL, sm._PA_TILE)
    import jax  # noqa: F401 — device_get in the exercised path
    sm._PALLAS_STATE.clear()
    sm._FLAT_SCORES_LIMIT = 1
    sm._MAX_CHUNK_ROWS = 1024
    sm._BLOCK_KSEL = 4
    sm._PA_TILE = 1024
    try:
        got = model.top_n_batch(5, q)
        want = [model.top_n(5, user_vector=v) for v in q]
        for g, w in zip(got, want):
            assert [i for i, _ in g] == [i for i, _ in w]
        assert set(sm._PALLAS_STATE.values()) <= {"ok", "broken"}
        assert sm._PALLAS_STATE  # the dispatcher recorded a verdict
    finally:
        sm._PALLAS_STATE.clear()
        sm._PALLAS_STATE.update(old_state)
        (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS,
         sm._BLOCK_KSEL, sm._PA_TILE) = old_limits


def test_certificate_passes_when_all_unselected_blocks_masked():
    """m_rest of -inf (every unselected block masked away, e.g. a tight
    LSH ball) must leave the certificate passing, not poison it with
    -inf + inf = NaN."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(2)
    n, f, b, k, bs, ksel = 1024, 4, 8, 8, 64, 8
    Y = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    Q = jnp.asarray(rng.standard_normal((b, f)).astype(np.float32))
    # only the first ksel*bs rows are active: every unselected block's
    # maximum is -inf
    act = np.zeros(n, bool)
    act[:ksel * bs] = True
    ts, ti, cert = jax.device_get(sm._batch_top_n_twophase_kernel(
        Y, Q, jnp.asarray(act), None, np.int32(b), k, 256, bs, ksel))
    assert cert.all(), cert


def test_window_ladder_shapes():
    """Drains map to static window shapes: full 256-windows plus one
    ladder window sized to the tail, so an idle server's lone request
    pays an 8-window, not the full 256 (VERDICT r04: the 50f/20M LSH
    cell's unloaded p50 lost to the baseline purely on window
    padding)."""
    from oryx_tpu.app.als.serving_model import _window_sizes
    assert _window_sizes(1) == [8]
    assert _window_sizes(8) == [8]
    assert _window_sizes(9) == [32]
    assert _window_sizes(33) == [256]
    assert _window_sizes(256) == [256]
    assert _window_sizes(257) == [256, 8]
    assert _window_sizes(300) == [256, 256]
    assert _window_sizes(512 + 20) == [256, 256, 32]


def test_streaming_small_drain_matches_oracle():
    """A 3-query drain through the streaming two-phase path (forced at
    toy scale) pads to the 8-window and still matches the flat-path
    oracle exactly."""
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(31)
    model = ALSServingModel(features=6, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(4096)],
                      rng.standard_normal((4096, 6)).astype(np.float32))
    q = rng.standard_normal((3, 6)).astype(np.float32)
    old_limits = (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS,
                  sm._BLOCK_KSEL, sm._PA_TILE)
    sm._FLAT_SCORES_LIMIT = 1
    sm._MAX_CHUNK_ROWS = 1024
    sm._BLOCK_KSEL = 4
    sm._PA_TILE = 1024
    try:
        got = model.top_n_batch(5, q)
        want = [model.top_n(5, user_vector=v) for v in q]
        for g, w in zip(got, want):
            assert [i for i, _ in g] == [i for i, _ in w]
    finally:
        (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS,
         sm._BLOCK_KSEL, sm._PA_TILE) = old_limits


class _BoostRescorer:
    """Monotone-ish rescorer: halves every score; filters ids ending 7."""

    def is_filtered(self, id_):
        return id_.endswith("7")

    def rescore(self, id_, score):
        return score * 0.5


class _OnlyRescorer:
    def __init__(self, keep):
        self.keep = set(keep)

    def is_filtered(self, id_):
        return id_ not in self.keep

    def rescore(self, id_, score):
        return score


def test_rescorer_window_matches_full_scan():
    """The device top-M window path must agree with the full host scan
    for rescorers that keep enough of the head (the common case)."""
    rng = np.random.default_rng(50)
    model = ALSServingModel(features=8, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(3000)],
                      rng.standard_normal((3000, 8)).astype(np.float32))
    q = rng.standard_normal(8).astype(np.float32)
    got = model.top_n(10, user_vector=q, rescorer=_BoostRescorer())
    want = model._host_top_n(
        np.asarray((model.Y.device_arrays()[0].astype(np.float32)
                    @ np.pad(q, (0, model.Y.device_features - 8)))),
        np.asarray(model.Y.device_arrays()[1]), 10, set(),
        _BoostRescorer(), None, False)
    assert [i for i, _ in got] == [i for i, _ in want]
    for (_, a), (_, b) in zip(got, want):
        assert abs(a - b) < 1e-4


def test_rescorer_window_falls_back_when_filtered_out():
    """A rescorer that keeps only items far below the top-M window must
    still find them (fallback to the full pull — the window form never
    changes WHICH items are reachable)."""
    rng = np.random.default_rng(51)
    model = ALSServingModel(features=4, implicit=True)
    n = 3000
    mat = rng.standard_normal((n, 4)).astype(np.float32)
    q = rng.standard_normal(4).astype(np.float32)
    scores = mat @ q
    # keep exactly the three WORST-scoring ids: guaranteed outside any
    # top-512 window
    worst = np.argsort(scores)[:3]
    keep = {f"i{j}" for j in worst}
    model.Y.bulk_load([f"i{j}" for j in range(n)], mat)
    got = model.top_n(5, user_vector=q, rescorer=_OnlyRescorer(keep))
    assert {i for i, _ in got} == keep


def test_int8_twophase_matches_oracle_interpret():
    """The int8 phase-A selection (pallas interpret mode) must return
    the same top-k as the exact flat path: quantized block maxima are
    inflated into sound upper bounds, phase B rescores exactly, and the
    certificate flags any miss."""
    import jax.numpy as jnp
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(60)
    # ksel covers 24 of 32 blocks and k is small: the margin-inflated
    # bounds of the 8 worst blocks sit far below the 4th-best score,
    # so certificates pass robustly at toy scale (production uses
    # 64 of ~156k blocks where the gap is far wider)
    N, F, B, bs, ksel, k = 4096, 16, 8, 128, 24, 4
    Y = jnp.asarray(rng.standard_normal((N, F)).astype(np.float32))
    Q = jnp.asarray(rng.standard_normal((B, F)).astype(np.float32))
    active = jnp.ones((N,), bool)
    y8, sy_b, l1y_b = sm._quantize_items_kernel(Y, bs)
    pen_i = sm._penalty_kernel_i32(active, bs)
    old_tile = sm._PA_TILE
    sm._PA_TILE = 1024
    try:
        ts, ti, cert = sm._batch_top_n_twophase_pallas_i8(
            Y, y8, sy_b, l1y_b, Q, pen_i, active, np.int32(B),
            k=k, bs=bs, ksel=ksel, interpret=True)
    finally:
        sm._PA_TILE = old_tile
    want_s, want_i = sm._batch_top_n_kernel(Y, Q, active, k)
    import numpy as _np
    ok_rows = _np.asarray(cert)
    # rows whose certificate passed must match the oracle exactly
    assert ok_rows.sum() >= B // 2, ok_rows
    _np.testing.assert_array_equal(_np.asarray(ti)[ok_rows],
                                   _np.asarray(want_i)[ok_rows])
    _np.testing.assert_allclose(_np.asarray(ts)[ok_rows],
                                _np.asarray(want_s)[ok_rows], rtol=1e-5)


def test_int8_quantizer_bounds_are_sound():
    """Every exact block max must lie at or below the quantized bound
    (the certificate's soundness rests on this inequality)."""
    import jax.numpy as jnp
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(61)
    N, F, B, bs = 2048, 12, 16, 128
    # adversarial-ish: heavy-tailed rows so block scales vary a lot
    Y = (rng.standard_normal((N, F))
         * rng.lognormal(0, 1.5, (N, 1))).astype(np.float32)
    Q = rng.standard_normal((B, F)).astype(np.float32)
    Yj = jnp.asarray(Y)
    y8, sy_b, l1y_b = sm._quantize_items_kernel(Yj, bs)
    sq = np.maximum(np.max(np.abs(Q), axis=1), 1e-30) / 127.0
    q8 = np.clip(np.round(Q / sq[:, None]), -127, 127)
    s_int = np.asarray(y8, np.int32) @ q8.T                  # (N, B)
    m_int = s_int.reshape(-1, bs, B).max(1)                  # (N/bs, B)
    l1q = np.abs(Q).sum(1)
    sy = np.asarray(sy_b)
    bound = (m_int * sy[:, None] * sq[None, :]
             + 0.5 * sq[None, :] * np.asarray(l1y_b)[:, None]
             + 0.5 * sy[:, None] * l1q[None, :]
             + 0.25 * F * sy[:, None] * sq[None, :])
    exact = (Y @ Q.T).reshape(-1, bs, B).max(1)
    assert (bound >= exact - 1e-4).all(), \
        float((exact - bound).max())


def test_int8_selection_dispatch_path():
    """With int8-selection forced on, the streaming dispatch routes
    through the quantized kernel (falling back to the scan build on the
    CPU test platform) and still matches the flat oracle."""
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(62)
    model = ALSServingModel(features=6, implicit=True,
                            int8_selection="auto")
    assert model._int8_enabled()  # features 6 < 128 -> padded -> on
    model.Y.bulk_load([f"i{j}" for j in range(4096)],
                      rng.standard_normal((4096, 6)).astype(np.float32))
    q = rng.standard_normal((3, 6)).astype(np.float32)
    old_limits = (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS,
                  sm._BLOCK_KSEL, sm._PA_TILE)
    old_state = dict(sm._PALLAS_STATE)
    sm._PALLAS_STATE.clear()
    sm._FLAT_SCORES_LIMIT = 1
    sm._MAX_CHUNK_ROWS = 1024
    sm._BLOCK_KSEL = 4
    sm._PA_TILE = 1024
    try:
        got = model.top_n_batch(5, q)
        want = [model.top_n(5, user_vector=v) for v in q]
        for g, w in zip(got, want):
            assert [i for i, _ in g] == [i for i, _ in w]
    finally:
        sm._PALLAS_STATE.clear()
        sm._PALLAS_STATE.update(old_state)
        (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS,
         sm._BLOCK_KSEL, sm._PA_TILE) = old_limits
    # default-constructed models get the f<=64 auto default (ON — the
    # int8+fold mirror is the roofline lever at small F; ISSUE 3)
    assert ALSServingModel(features=6, implicit=True)._int8_enabled()
    # ... but auto stays off in the 64 < f < 128 wash zone, and at
    # unpadded widths where there is no byte tax to reclaim
    assert not ALSServingModel(features=100, implicit=True)._int8_enabled()
    assert not ALSServingModel(features=128, implicit=True)._int8_enabled()


def test_int8_certificate_passes_on_zero_padded_rows():
    """Window padding rows (all-zero queries) must not fail the int8
    certificate: their exact scores are 0 everywhere, so their bound is
    forced to -inf instead of a small positive quantization margin
    (a false failure would recompute EVERY padded drain on the exact
    scan)."""
    import jax.numpy as jnp
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(63)
    N, F, bs, ksel, k = 2048, 16, 128, 12, 4
    Y = jnp.asarray(rng.standard_normal((N, F)).astype(np.float32))
    Q = np.zeros((8, F), np.float32)
    Q[:3] = rng.standard_normal((3, F))  # 5 zero padding rows
    active = jnp.ones((N,), bool)
    y8, sy_b, l1y_b = sm._quantize_items_kernel(Y, bs)
    pen_i = sm._penalty_kernel_i32(active, bs)
    old_tile = sm._PA_TILE
    sm._PA_TILE = 1024
    try:
        ts, ti, cert = sm._batch_top_n_twophase_pallas_i8(
            Y, y8, sy_b, l1y_b, jnp.asarray(Q), pen_i, active,
            np.int32(len(Q)), k=k, bs=bs, ksel=ksel, interpret=True)
    finally:
        sm._PA_TILE = old_tile
    assert np.asarray(cert)[3:].all()  # padding rows always certify

def test_fold_mirror_layout_matches_numpy():
    """_fold_items_kernel's slot layout: logical row i*fold + j lives
    in lanes [j*w, j*w + w) of folded row i; penalty/bucket side inputs
    land in the (fold, N//bs, bs//fold) layout the kernel reads."""
    import jax
    import jax.numpy as jnp
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(70)
    N, F, W, fold, bs = 1024, 20, 128, 4, 128
    w = W // fold
    Y = np.zeros((N, W), np.float32)
    Y[:, :F] = rng.standard_normal((N, F)).astype(np.float32)
    act = rng.random(N) > 0.2
    yf, pen_f = jax.device_get(sm._fold_items_kernel(
        jnp.asarray(Y), jnp.asarray(act), fold, bs))
    assert yf.shape == (N // fold, W)
    for i in range(0, N // fold, 37):
        for j in range(fold):
            np.testing.assert_array_equal(yf[i, j * w:j * w + w],
                                          Y[i * fold + j, :w])
    pen = np.where(act, 0.0, -np.inf).astype(np.float32)
    assert pen_f.shape == (fold, N // bs, bs // fold)
    for j in range(fold):
        np.testing.assert_array_equal(
            pen_f[j].reshape(-1), pen.reshape(-1, fold)[:, j])


def test_fold_pallas_interpret_agrees_with_scan_kernel():
    """The folded phase-A program (pallas interpret mode) must produce
    the same top-k and certificates as the lax.scan build — phase B is
    shared, so this pins the folded block maxima to the canonical
    ones."""
    import jax
    import jax.numpy as jnp
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(71)
    N, F, W, B, k, bs, ksel = 8192, 20, 128, 8, 8, 128, 8
    fold = sm._fold_factor(W, F)
    assert fold == 4
    Y = np.zeros((N, W), np.float32)
    Y[:, :F] = rng.standard_normal((N, F)).astype(np.float32)
    Yj = jnp.asarray(Y)
    Q = jnp.asarray(rng.standard_normal((B, W)).astype(np.float32)
                    * np.concatenate([np.ones(F), np.zeros(W - F)]
                                     ).astype(np.float32))
    act = np.ones(N, bool)
    act[::7] = False
    active = jnp.asarray(act)
    old_tile = sm._PA_TILE
    sm._PA_TILE = 2048
    try:
        yf, pen_f = sm._fold_items_kernel(Yj, active, fold, bs)
        ts_f, ti_f, cert_f = jax.device_get(
            sm._batch_top_n_twophase_pallas_fold(
                Yj, yf, Q, pen_f, active, np.int32(len(Q)), k, bs, ksel,
                fold, interpret=True))
        ts_s, ti_s, cert_s = jax.device_get(
            sm._batch_top_n_twophase_kernel(
                Yj, Q, active, None, np.int32(len(Q)), k, 2048, bs, ksel))
        np.testing.assert_allclose(ts_f, ts_s, rtol=1e-5)
        np.testing.assert_array_equal(ti_f, ti_s)
        np.testing.assert_array_equal(cert_f, cert_s)
    finally:
        sm._PA_TILE = old_tile


def test_int8_selection_bool_normalizes_to_explicit_opt_in():
    """ADVICE r05 #1: a programmatic int8_selection=True (bool, allowed
    by the `str | bool` signature) must get the same explicit-opt-in
    precedence as the string "true" — the dispatch chain orders kinds
    by comparing against canonical strings."""
    model = ALSServingModel(features=6, implicit=True,
                            int8_selection=True)
    assert model._int8_selection == "true"
    assert model._int8_enabled()
    # False normalizes to the canonical off string, not bool identity
    off = ALSServingModel(features=6, implicit=True,
                          int8_selection=False)
    assert off._int8_selection == "false"
    assert not off._int8_enabled()
