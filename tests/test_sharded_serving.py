"""Sharded serving as a CONFIGURED mode: `oryx.serving.api.item-shards`
row-shards the item matrix over the (virtual 8-device) mesh and the
live serving layer answers the ALS endpoint surface through the SPMD
merge kernel.

Reference parity: the reference's production serving path IS its
partitioned scan — PartitionedFeatureVectors.mapPartitionsParallel
(PartitionedFeatureVectors.java:84-148) wired into ALSServingModel.topN
(ALSServingModel.java:265-280).  Round-3 shipped the kernel as a
library class only; these tests pin the full wiring: config key ->
manager -> model -> batcher -> HTTP.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from oryx_tpu.app.als.serving_model import ALSServingModel
from oryx_tpu.common.config import from_dict


def _loaded_model(item_shards, features=6, items=200, users=12,
                  seed=0, dtype="float32"):
    rng = np.random.default_rng(seed)
    m = ALSServingModel(features=features, implicit=True,
                        item_shards=item_shards, dtype=dtype)
    m.Y.bulk_load([f"i{j}" for j in range(items)],
                  rng.standard_normal((items, features)).astype(np.float32))
    m.X.bulk_load([f"u{j}" for j in range(users)],
                  rng.standard_normal((users, features)).astype(np.float32))
    return m


def test_sharded_agrees_with_single_chip_exactly():
    single = _loaded_model(1)
    sharded = _loaded_model(8)
    rng = np.random.default_rng(3)
    Q = rng.standard_normal((5, 6)).astype(np.float32)
    a = single.top_n_batch(10, Q, use_lsh=False)
    b = sharded.top_n_batch(10, Q)
    for ra, rb in zip(a, b):
        assert [i for i, _ in ra] == [i for i, _ in rb]
        np.testing.assert_allclose([s for _, s in ra],
                                   [s for _, s in rb], rtol=1e-5)


def test_sharded_exclusions_and_per_request_howmany():
    sharded = _loaded_model(8)
    rng = np.random.default_rng(4)
    Q = rng.standard_normal((3, 6)).astype(np.float32)
    plain = sharded.top_n_batch([4, 2, 6], Q)
    excl = [{plain[0][0][0], plain[0][1][0]}, set(), {plain[2][0][0]}]
    got = sharded.top_n_batch([4, 2, 6], Q, exclude=excl)
    assert [len(r) for r in got] == [4, 2, 6]
    for r, e in zip(got, excl):
        assert not ({i for i, _ in r} & e)


def test_sharded_update_then_query_sees_new_item():
    sharded = _loaded_model(8, items=64)
    # a dominant new item via the UP-style single-vector write path
    sharded.set_item_vector("hot", np.full(6, 10.0, np.float32))
    got = sharded.top_n_batch(3, np.ones((1, 6), np.float32))[0]
    assert got[0][0] == "hot"


def test_sharded_model_ignores_lsh():
    m = _loaded_model(8)
    from oryx_tpu.app.als.lsh import LocalitySensitiveHash

    m.lsh = LocalitySensitiveHash(0.3, 6)
    assert not m._lsh_active()
    # and the scan still answers
    assert m.top_n_batch(5, np.ones((1, 6), np.float32))[0]


def test_manager_builds_sharded_model_from_config():
    from oryx_tpu.app.als.serving_manager import ALSServingModelManager
    from oryx_tpu.common import pmml as pmml_io

    cfg = from_dict({"oryx.serving.api.item-shards": 8})
    mgr = ALSServingModelManager(cfg)
    doc = pmml_io.build_skeleton_pmml()
    pmml_io.add_extension(doc, "features", 6)
    pmml_io.add_extension(doc, "implicit", True)
    pmml_io.add_extension_content(doc, "XIDs", ["u0"])
    pmml_io.add_extension_content(doc, "YIDs", ["i0", "i1"])
    mgr.consume_key_message("MODEL", pmml_io.to_string(doc))
    assert mgr.get_model()._item_shards == 8
    mgr.consume_key_message("UP", json.dumps(["Y", "i0", [1, 0, 0, 0, 0, 0]]))
    mgr.consume_key_message("UP", json.dumps(["Y", "i1", [0, 1, 0, 0, 0, 0]]))
    mgr.consume_key_message("UP", json.dumps(["X", "u0", [1, 1, 0, 0, 0, 0]]))
    got = mgr.get_model().top_n_batch(2, np.asarray([[1, 0, 0, 0, 0, 0]],
                                                    np.float32))[0]
    assert got[0][0] == "i0"


def test_manager_rejects_non_pow2_shards():
    from oryx_tpu.app.als.serving_manager import ALSServingModelManager

    with pytest.raises(ValueError):
        ALSServingModelManager(from_dict(
            {"oryx.serving.api.item-shards": 3}))


@pytest.fixture(scope="module")
def sharded_server():
    from oryx_tpu.api.serving import StaticModelManager
    from oryx_tpu.lambda_rt.http import HttpApp, make_server
    from oryx_tpu.serving import als as als_resources
    from oryx_tpu.serving import framework as framework_resources
    from oryx_tpu.serving.batcher import TopNBatcher

    model = _loaded_model(8, items=500, users=20)
    model.add_known_items("u0", ["i1", "i2"])
    StaticModelManager.model = model
    batcher = TopNBatcher(pipeline=2)
    app = HttpApp(
        framework_resources.ROUTES + als_resources.ROUTES,
        context={"model_manager": StaticModelManager(),
                 "input_producer": None, "config": None,
                 "min_model_load_fraction": 0.0,
                 "top_n_batcher": batcher},
        read_only=True)
    server = make_server(app, 0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield port, model
    server.shutdown()
    batcher.close()


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return json.loads(r.read())


def test_http_recommend_over_sharded_model(sharded_server):
    port, model = sharded_server
    recs = _get(port, "/recommend/u0?howMany=5")
    assert len(recs) == 5
    # known items are excluded, per the endpoint contract
    assert not ({r["id"] for r in recs} & {"i1", "i2"})
    # concurrent requests batch through the SPMD kernel
    results = []

    def hit(u):
        results.append(_get(port, f"/recommend/u{u}?howMany=3"))

    threads = [threading.Thread(target=hit, args=(u,)) for u in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 8 and all(len(r) == 3 for r in results)


def test_http_similarity_and_estimate_over_sharded_model(sharded_server):
    port, _ = sharded_server
    sims = _get(port, "/similarity/i3?howMany=4")
    assert len(sims) == 4
    est = _get(port, "/estimate/u1/i5")
    assert est and est[0]["id"] == "i5" \
        and isinstance(est[0]["value"], float)


def test_sharded_survives_exact_fit_odd_capacity():
    """bulk_load's exact-fit growth must round capacity to a multiple
    of the mesh size or the shard_map kernel rejects the leading dim."""
    m = _loaded_model(8, items=3001)
    assert int(m.Y.device_arrays()[0].shape[0]) % 8 == 0
    got = m.top_n_batch(5, np.ones((2, 6), np.float32))
    assert all(len(r) == 5 for r in got)
