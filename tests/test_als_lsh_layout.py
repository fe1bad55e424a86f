"""A store laid out by hash bucket (``FeatureVectorStore.partition_by``,
ISSUE 36): the invariant — every step of the array holds rows of ONE
bucket — through every way a row arrives or leaves, with the id <-> row
maps and the model's answers still right; and the layouts that must not
change (a store nobody partitioned, a model at sample-rate 1.0, a sharded
one)."""

import numpy as np
import pytest

from oryx_tpu.app.als import serving_model as sm
from oryx_tpu.app.als.feature_vectors import (FeatureVectorStore,
                                              planned_capacity)
from oryx_tpu.app.als.lsh import (PUBLISHED_CORES, LocalitySensitiveHash,
                                  choose_hash_count)
from oryx_tpu.app.als.serving_model import ALSServingModel

STEP = 8
BUCKETS = 4


def _bucket_of(matrix) -> np.ndarray:
    """Two sign bits of the first two columns."""
    m = np.asarray(matrix, np.float32)
    return (m[:, 0] > 0).astype(np.int64) * 2 + (m[:, 1] > 0)


def _store(step=STEP, features=4, dtype="float32") -> FeatureVectorStore:
    store = FeatureVectorStore(features, dtype=dtype)
    store.partition_by(_bucket_of, BUCKETS, step)
    return store


def _holds(store, vectors: dict) -> None:
    """THE invariant, and the maps: every id's row lies in a step of its
    vector's bucket, is live and holds the vector; every other row is
    inactive, zero and maps to no id; the device arrays are the host's."""
    table, step, _ = store.partition_layout()
    cap = len(store.row_ids())
    assert cap % step == 0 and len(table) == cap // step
    host, active, row_ids = store.host_arrays()
    rows = set()
    for id_, vector in vectors.items():
        row = store.row_of(id_)
        assert row is not None and store.id_of(row) == id_
        stored = np.asarray(vector, np.float32).astype(store.dtype)
        assert table[row // step] == _bucket_of(stored[None])[0], id_
        assert active[row]
        np.testing.assert_array_equal(host[row], stored)
        rows.add(row)
    assert len(rows) == len(vectors) == len(store)
    free = np.ones(cap, bool)
    free[list(rows)] = False
    assert not active[free].any() and not host[free].any()
    assert all(row_ids[r] is None for r in np.flatnonzero(free))
    vecs, dev_active = store.device_arrays()
    np.testing.assert_array_equal(np.asarray(dev_active), active)
    np.testing.assert_array_equal(
        np.asarray(vecs)[:, :store.features], host)


def _vectors(rng, n, start=0) -> dict:
    return {f"i{start + j}": rng.standard_normal(4).astype(np.float32)
            for j in range(n)}


def _load(store, vectors: dict) -> None:
    store.bulk_load(list(vectors), np.stack(list(vectors.values())))


def test_one_bucket_a_step_after_a_bulk_load():
    rng = np.random.default_rng(1)
    store, vectors = _store(), _vectors(rng, 200)
    _load(store, vectors)
    _holds(store, vectors)
    table, step, _ = store.partition_layout()
    # a region is whole steps: a bucket's steps hold all its rows and
    # less than one step of slack
    counts = np.bincount(_bucket_of(np.stack(list(vectors.values()))),
                         minlength=BUCKETS)
    for b in range(BUCKETS):
        assert (table == b).sum() == -(-counts[b] // step)
    # rows of a region ascend with the order of arrival
    first = [i for i, v in vectors.items() if _bucket_of(v[None])[0] == 0]
    assert [store.row_of(i) for i in first] \
        == sorted(store.row_of(i) for i in first)


def test_a_second_bulk_load_fills_regions_and_moves_known_ids():
    rng = np.random.default_rng(2)
    store, vectors = _store(), _vectors(rng, 60)
    _load(store, vectors)
    # 20 known ids, half of them with a vector that hashes elsewhere,
    # and 70 new ones, in one load
    again = {}
    for n, id_ in enumerate(list(vectors)[:20]):
        v = vectors[id_].copy()
        if n % 2:
            v[0] = -v[0]
        again[id_] = v
    again.update(_vectors(rng, 70, start=1000))
    _load(store, again)
    vectors.update(again)
    _holds(store, vectors)
    assert store.row_moves == 10


def test_an_update_whose_vector_hashes_elsewhere_moves_the_row():
    rng = np.random.default_rng(3)
    store, vectors = _store(), _vectors(rng, 50)
    _load(store, vectors)
    store.device_arrays()
    moved = 0
    for id_ in list(vectors)[:30]:
        v = vectors[id_].copy()
        if rng.random() < 0.5:
            v[1] = -v[1]        # the other side of the second hyperplane
            moved += 1
        else:
            v *= 1.5            # same bucket: the row stays where it is
        before = store.row_of(id_)
        store.set_vector(id_, v)
        assert (store.row_of(id_) == before) == (
            _bucket_of(v[None])[0] == _bucket_of(vectors[id_][None])[0])
        vectors[id_] = v
    assert store.row_moves == moved > 0
    _holds(store, vectors)
    # the rows a move touched reached the device by the in-place sync
    assert store.device_syncs == 2


def test_new_ids_one_by_one_take_steps_as_regions_fill_and_the_array_grows():
    rng = np.random.default_rng(4)
    store = _store()
    cap0 = len(store.row_ids())
    vectors = {}
    for id_, v in _vectors(rng, 3 * cap0).items():
        store.set_vector(id_, v)
        vectors[id_] = v
    assert len(store.row_ids()) > cap0          # _grow ran, more than once
    _holds(store, vectors)
    table, step, _ = store.partition_layout()
    # growth moved nothing: the first steps are whose they were
    assert store.row_moves == 0
    assert (table >= 0).sum() * step >= len(vectors)


def test_remove_and_retain_free_rows_inside_their_regions():
    rng = np.random.default_rng(5)
    store, vectors = _store(), _vectors(rng, 120)
    _load(store, vectors)
    for id_ in list(vectors)[:40]:
        store.remove(id_)
        del vectors[id_]
    _holds(store, vectors)
    table_before = store.partition_layout()[0]
    # a freed row is taken again by its own bucket, before a new step is
    fresh = _vectors(rng, 30, start=500)
    for id_, v in fresh.items():
        store.set_vector(id_, v)
    vectors.update(fresh)
    _holds(store, vectors)
    np.testing.assert_array_equal(store.partition_layout()[0], table_before)
    # retain: a model swap keeps the new model's ids and the recent ones
    store.retain_recent_and_ids([])          # clears the recent set
    keep = list(vectors)[::3]
    late = _vectors(rng, 5, start=900)
    for id_, v in late.items():
        store.set_vector(id_, v)
    store.retain_recent_and_ids(keep)
    vectors = {i: v for i, v in vectors.items() if i in set(keep)}
    vectors.update(late)
    _holds(store, vectors)


def test_reserve_and_planned_capacity_of_a_partitioned_store():
    store = _store(step=16)
    store.reserve(1000)
    cap = len(store.row_ids())
    assert cap % 16 == 0 and cap >= 1000 + BUCKETS * 16
    rng = np.random.default_rng(6)
    _load(store, _vectors(rng, 1000))
    assert len(store.row_ids()) == cap           # no regrow mid-replay
    # even buckets: ceil(rows / buckets / step) steps each
    assert planned_capacity(20_000_000, buckets=256, step=4096) \
        == 256 * 20 * 4096 == 20_971_520
    assert planned_capacity(1000, buckets=4, step=16) == 4 * 16 * 16


def test_only_an_empty_one_device_store_can_be_partitioned():
    store = FeatureVectorStore(4)
    store.set_vector("a", np.ones(4))
    with pytest.raises(ValueError):
        store.partition_by(_bucket_of, BUCKETS, STEP)
    with pytest.raises(ValueError):
        FeatureVectorStore(4).partition_by(_bucket_of, BUCKETS, 12)


def test_an_id_twice_in_one_load_keeps_the_last_vector():
    store = _store()
    a, b = np.array([1, 1, 0, 0], np.float32), \
        np.array([-1, 1, 0, 0], np.float32)
    store.bulk_load(["x", "y", "x"], np.stack([a, a, b]))
    _holds(store, {"x": b, "y": a})


def test_a_bfloat16_store_hashes_what_it_stores():
    """The bucket is the STORED vector's: a float32 value that rounds
    across a hyperplane in bfloat16 lives where the served value
    hashes."""
    store = _store(dtype="bfloat16")
    rng = np.random.default_rng(7)
    vectors = _vectors(rng, 64)
    _load(store, vectors)
    store.set_vector("tiny", np.array([1e-45, 1.0, 0, 0], np.float32))
    vectors["tiny"] = np.array([1e-45, 1.0, 0, 0], np.float32)
    _holds(store, vectors)   # 1e-45 stores as 0.0: not above the plane


# -- the layouts that must not change ------------------------------------------

def test_a_store_nobody_partitioned_keeps_the_order_of_arrival():
    store = FeatureVectorStore(4)
    rng = np.random.default_rng(8)
    vectors = _vectors(rng, 100)
    _load(store, vectors)
    assert not store.partitioned and store.row_moves == 0
    assert [store.row_of(i) for i in vectors] == list(range(100))
    store.remove("i3")
    store.set_vector("new", np.ones(4))
    assert store.row_of("new") == 3             # the free list, as ever


@pytest.mark.parametrize("kind", ["sample_rate_1", "sharded", "users"])
def test_models_that_do_not_prune_keep_todays_row_order(kind):
    rng = np.random.default_rng(9)
    n, f = 300, 6
    Y = rng.standard_normal((n, f)).astype(np.float32)
    ids = [f"i{j}" for j in range(n)]
    if kind == "sharded":
        model = ALSServingModel(f, True, sample_rate=0.3, item_shards=2)
        assert not model._lsh_active() and model.partitioning() is None
    elif kind == "users":
        model = ALSServingModel(f, True, sample_rate=0.3)
        model.bulk_load_users(ids, Y)
        assert not model.X.partitioned
        assert [model.X.row_of(i) for i in ids] == list(range(n))
        return
    else:
        model = ALSServingModel(f, True, sample_rate=1.0)
        assert model.lsh is None
    model.bulk_load_items(ids, Y)
    assert not model.Y.partitioned
    assert [model.Y.row_of(i) for i in ids] == list(range(n))
    assert "lsh" not in model.metrics()


# -- what 0.3 means --------------------------------------------------------------

def test_sample_rate_03_is_eight_hyperplanes_and_radius_two():
    """The reference's rule at the cores its published rows ran on."""
    assert PUBLISHED_CORES == 32
    assert choose_hash_count(0.3, PUBLISHED_CORES) == (8, 2)
    # ... and what the hidden default of 8 cores used to make of it
    assert choose_hash_count(0.3, 8) == (7, 1)
    lsh = LocalitySensitiveHash(0.3, 50)
    assert (lsh.num_hashes, lsh.max_bits_differing) == (8, 2)
    assert lsh.hyperplanes.shape == (8, 50)
    # near-orthogonal, unit rows
    np.testing.assert_allclose(lsh.hyperplanes @ lsh.hyperplanes.T,
                               np.eye(8), atol=1e-5)
    assert len(lsh.candidate_indices(np.ones(50, np.float32))) == 37


def test_a_model_at_03_is_partitioned_one_bucket_a_phase_a_step():
    model = ALSServingModel(10, True, sample_rate=0.3)
    assert model._lsh_active() and model.Y.partitioned
    rng = np.random.default_rng(10)
    n = 3000
    Y = rng.standard_normal((n, 10)).astype(np.float32)
    model.bulk_load_items([str(j) for j in range(n)], Y)
    part = model.partitioning()
    assert part == {"hashes": 8, "radius": 2, "buckets": 256,
                    "buckets_a_ball": 37, "step_rows": sm._PA_TILE,
                    "steps": part["steps"], "steps_assigned": 256}
    table, step, _ = model.Y.partition_layout()
    assert step == sm._PA_TILE
    buckets = model.lsh.bucket_of(Y)
    for j in range(0, n, 7):
        assert table[model.Y.row_of(str(j)) // step] == buckets[j]
    m = model.metrics()
    assert m["lsh"]["buckets"] == 256 and m["lsh"]["row_moves"] == 0
    # an UP that crosses a hyperplane moves the row, and is counted
    v = Y[0] - 2 * (Y[0] @ model.lsh.hyperplanes[0]) \
        * model.lsh.hyperplanes[0]
    model.set_item_vector("0", v)
    assert model.lsh_row_moves == 1
    assert table[model.Y.row_of("0") // step] \
        == model.lsh.bucket_of(v[None])[0] == buckets[0] ^ 1
