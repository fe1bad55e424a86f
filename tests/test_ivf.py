"""ISSUE 18 tier-1 coverage: the IVF-ANN serving path.

Partition property tests on the test_cluster_merge-style exact-grid
harness (every item in exactly one cell, union == catalog), recall
monotone in ``nprobe``, the ``nprobe == cells`` byte-identity claim,
index determinism (PR 8/PR 11 result-cache byte-identity rides on it),
the ``mirror_shapes`` <-> warmup lock-step, the per-generation recall
certificate on quality-oracle-trained factors (PR 2 harness), the
certificate GATE (the router provably never serves ANN below
``oryx.als.ann.min-recall``), the ``ann-index-corrupt`` chaos point's
fail-closed fallback, and the per-slice index artifact round-trip.

All CPU-runnable: the IVF phase-A kernel is plain jit (no pallas), and
the streaming dispatch is forced with the test_int8_route knob idiom.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from oryx_tpu.app.als import ivf
from oryx_tpu.app.als import serving_model as sm
from oryx_tpu.app.als.serving_manager import ALSServingModelManager
from oryx_tpu.app.als.serving_model import ALSServingModel
from oryx_tpu.common import pmml as pmml_io
from oryx_tpu.common.config import from_dict
from oryx_tpu.kafka.api import KEY_MODEL, KEY_MODEL_REF, KEY_UP
from oryx_tpu.ops import ann as ops_ann
from oryx_tpu.resilience import faults

BS = sm._BLOCK_ROWS


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _cfg(cells, nprobe, **kw):
    kw.setdefault("enabled", True)
    kw.setdefault("min_recall", 0.95)
    kw.setdefault("recall_at", 50)
    kw.setdefault("recall_queries", 64)
    kw.setdefault("train_sample", max(cells, 1024))
    kw.setdefault("train_iterations", 8)
    return ivf.AnnConfig(cells=cells, nprobe=nprobe, **kw)


def _mixture(rng, n, features, ncomp, spread=0.25):
    """Clustered item factors (what trained ALS factors look like):
    a gaussian mixture, lane-padded to the 128-lane device width."""
    comp = rng.standard_normal((ncomp, features))
    pick = rng.integers(0, ncomp, size=n)
    y = (comp[pick] + spread * rng.standard_normal((n, features))
         ).astype(np.float32)
    yp = np.zeros((n, 128), np.float32)
    yp[:, :features] = y
    return y, yp


def _recall_vs_exact(an_i, ex_i, k):
    hits = total = 0
    for b in range(len(ex_i)):
        hits += len(set(map(int, an_i[b])) & set(map(int, ex_i[b])))
        total += k
    return hits / total


# -- partition properties -----------------------------------------------------

@pytest.mark.parametrize("cells", [4, 8])
def test_partition_every_row_in_exactly_one_cell(cells):
    """The cell-contiguous mirror is a PARTITION: walking every cell's
    block table visits each catalog row exactly once (union == catalog,
    pairwise disjoint by construction), every visited row's nearest
    centroid is the cell that holds it, and the sentinel block is
    empty."""
    import jax.numpy as jnp

    rng = np.random.default_rng(200 + cells)
    n = 1024
    _, yp = _mixture(rng, n, 16, cells * 2)
    cfg = _cfg(cells, nprobe=1, train_iterations=4)
    cents = ivf.train_generation_centroids(yp[:, :16], cfg)
    state = ivf.AnnState(cfg, cents)
    mirror = ivf.build_mirror(jnp.asarray(yp), jnp.ones(n, bool),
                              state, BS)
    shapes = ivf.mirror_shapes(n, cells, BS)
    assert int(mirror.y8p.shape[0]) == shapes["rows"]
    perm = np.asarray(mirror.perm)
    # all-active store: activep IS the valid-slot mask
    valid = np.asarray(mirror.activep)
    cell_blocks = np.asarray(mirror.cell_blocks)
    sentinel = shapes["blocks"] - 1
    assign = ops_ann.assign_cells(yp, np.asarray(mirror.cents))
    seen: list[int] = []
    for c in range(cells):
        for blk in cell_blocks[c]:
            if blk == sentinel:
                continue  # pow2 padding of the probe table
            slots = np.arange(blk * BS, (blk + 1) * BS)
            rows = perm[slots][valid[slots]]
            assert (assign[rows] == c).all()
            seen.extend(rows.tolist())
    # exactly once each, union == catalog
    assert sorted(seen) == list(range(n))
    # the sentinel block the padding points at holds nothing
    assert not valid[sentinel * BS:(sentinel + 1) * BS].any()


def test_recall_monotone_nondecreasing_in_nprobe():
    """Probe sets nest (top-1 cell is in every top-n probe), so the
    candidate universe only grows with ``nprobe`` — recall against the
    exact kernel must be monotone non-decreasing, reaching 1.0 at
    ``nprobe == cells``."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    n, features, cells, k = 2048, 16, 8, 50
    _, yp = _mixture(rng, n, features, cells // 2)
    cfg = _cfg(cells, nprobe=1)
    cents = ivf.train_generation_centroids(yp[:, :features], cfg)
    state = ivf.AnnState(cfg, cents)
    vecs = jnp.asarray(yp)
    active = jnp.ones(n, bool)
    mirror = ivf.build_mirror(vecs, active, state, BS)
    Q = np.zeros((16, 128), np.float32)
    Q[:, :features] = rng.standard_normal((16, features))
    Qd = jnp.asarray(Q)
    ex_s, ex_i = jax.device_get(sm._batch_top_n_kernel(vecs, Qd,
                                                       active, k))
    recalls = []
    for nprobe in (1, 2, 4, 8):
        # ksel wide open: this test isolates the PROBE approximation
        _, an_i, _ = jax.device_get(ivf.batch_top_n_ivf(
            mirror, vecs, Qd, k, BS, 10_000, nprobe))
        recalls.append(_recall_vs_exact(an_i, ex_i, k))
    assert recalls == sorted(recalls), recalls
    assert recalls[-1] == 1.0


def test_nprobe_equals_cells_byte_identical_to_exact():
    """With every cell probed the candidate universe is the whole
    catalog, and on a catalog whose scores are all exactly
    representable and pairwise distinct (the grid-vector trick plus a
    dominant distinct leading coordinate) the IVF kernel's output is
    byte-identical to the exact kernel's — scores AND indices."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(31)
    n, cells, k = 512, 4, 10
    yp = np.zeros((n, 128), np.float32)
    # coord 0: distinct integers (exact in f32); coords 1-3: grid
    # multiples of 1/4 — every dot is exact, and q0=64 makes adjacent
    # items' scores differ by 64 >> the |rest| <= 12 grid part, so all
    # scores are pairwise distinct: byte-identity is well-defined
    yp[:, 0] = np.arange(n) - n // 2
    yp[:, 1:4] = rng.integers(-8, 9, (n, 3)) / 4.0
    active = np.ones(n, bool)
    active[5::37] = False  # retired rows ride along
    cfg = _cfg(cells, nprobe=cells, train_iterations=4)
    cents = ivf.train_generation_centroids(yp[:, :4], cfg)
    state = ivf.AnnState(cfg, cents)
    vecs = jnp.asarray(yp)
    act = jnp.asarray(active)
    mirror = ivf.build_mirror(vecs, act, state, BS)
    Q = np.zeros((8, 128), np.float32)
    Q[:, 0] = 64.0
    Q[:, 1:4] = rng.integers(-8, 9, (8, 3)) / 4.0
    Qd = jnp.asarray(Q)
    an_s, an_i, cert = jax.device_get(ivf.batch_top_n_ivf(
        mirror, vecs, Qd, k, BS, 10_000, cells))
    ex_s, ex_i = jax.device_get(sm._batch_top_n_kernel(vecs, Qd,
                                                       act, k))
    assert bool(cert.all())
    np.testing.assert_array_equal(an_s, ex_s)
    np.testing.assert_array_equal(an_i, ex_i)


def test_index_build_and_kernel_are_deterministic():
    """Same generation -> same index -> same bytes (the PR 8/PR 11
    result-cache byte-identity contract): training, mirror layout, and
    kernel output must be reproducible from scratch."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(41)
    n, features, cells = 1024, 16, 8
    _, yp = _mixture(rng, n, features, cells)
    Q = np.zeros((8, 128), np.float32)
    Q[:, :features] = rng.standard_normal((8, features))

    def build():
        cfg = _cfg(cells, nprobe=2)
        cents = ivf.train_generation_centroids(yp[:, :features], cfg)
        state = ivf.AnnState(cfg, cents)
        vecs = jnp.asarray(yp)
        mirror = ivf.build_mirror(vecs, jnp.ones(n, bool), state, BS)
        out = jax.device_get(ivf.batch_top_n_ivf(
            mirror, vecs, jnp.asarray(Q), 10, BS, 8, 2))
        return cents, mirror, out

    c1, m1, o1 = build()
    c2, m2, o2 = build()
    assert np.array_equal(c1, c2)
    assert np.array_equal(np.asarray(m1.y8p), np.asarray(m2.y8p))
    assert np.array_equal(np.asarray(m1.perm), np.asarray(m2.perm))
    assert np.array_equal(np.asarray(m1.cell_blocks),
                          np.asarray(m2.cell_blocks))
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("rows_at_once", [1, 4])
def test_ivf_rescore_in_row_groups_returns_what_one_gather_returns(
        rows_at_once, monkeypatch):
    """The IVF kernel's rescore gathers like phase B and is bounded like
    it (``sm._PHASE_B_GATHER_BYTES``): a window over the budget runs in
    row groups inside the program and returns the ungrouped answer."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(43)
    n, features, cells, k, ksel = 2048, 16, 8, 10, 4
    _, yp = _mixture(rng, n, features, cells)
    Q = np.zeros((8, 128), np.float32)
    Q[:, :features] = rng.standard_normal((8, features))
    cfg = _cfg(cells, nprobe=4)
    state = ivf.AnnState(
        cfg, ivf.train_generation_centroids(yp[:, :features], cfg))
    vecs = jnp.asarray(yp)
    mirror = ivf.build_mirror(vecs, jnp.ones(n, bool), state, BS)

    def program():
        # jitted afresh: the budget is read when a program is traced
        monkeypatch.setattr(ivf, "_ivf_top_n_kernel", jax.jit(
            ivf._ivf_top_n_kernel.__wrapped__,
            static_argnames=("k", "bs", "ksel", "nprobe", "pchunk")))
        return jax.device_get(ivf.batch_top_n_ivf(
            mirror, vecs, jnp.asarray(Q), k, BS, ksel, 4))

    whole = program()
    row_bytes = 128 * 4
    assert sm._phase_b_group_rows(8, ksel, BS, row_bytes) == 8
    monkeypatch.setattr(sm, "_PHASE_B_GATHER_BYTES",
                        rows_at_once * ksel * BS * row_bytes)
    assert sm._phase_b_group_rows(8, ksel, BS, row_bytes) == rows_at_once
    grouped = program()
    # the same rows with the same certificates; a one-row einsum may
    # add its float32 products in another order than an 8-row one
    np.testing.assert_allclose(whole[0], grouped[0], rtol=1e-6)
    np.testing.assert_array_equal(whole[1], grouped[1])
    np.testing.assert_array_equal(whole[2], grouped[2])


# -- warmup lock-step (satellite 3) -------------------------------------------

def test_mirror_shapes_lockstep_with_build_and_warmup_ladder():
    """``mirror_shapes`` is THE shared derivation: the built mirror's
    padded layout must equal it exactly, and on a balanced catalog the
    probe-table width lands on the warmup ladder's expected rung
    (``e = pow2ceil(capacity / (cells * bs))``)."""
    import jax.numpy as jnp

    n, cells = 1024, 8
    cents = np.zeros((cells, 16), np.float32)
    for c in range(cells):
        cents[c, c % 16] = 10.0 * (1 + c)
    yp = np.zeros((n, 128), np.float32)
    yp[:, :16] = np.repeat(cents, n // cells, axis=0)  # balanced cells
    state = ivf.AnnState(_cfg(cells, nprobe=2), cents)
    mirror = ivf.build_mirror(jnp.asarray(yp), jnp.ones(n, bool),
                              state, BS)
    shapes = ivf.mirror_shapes(n, cells, BS)
    assert int(mirror.y8p.shape[0]) == shapes["rows"]
    assert int(mirror.sy_b.shape[0]) == shapes["blocks"]
    e = max(1, -(-n // (cells * BS)))
    e = 1 << (e - 1).bit_length()
    assert int(mirror.cell_blocks.shape[1]) in (e, 2 * e)


def test_warmup_compiles_ivf_ladder_from_avals():
    """``python -m oryx_tpu warmup`` must pre-compile the IVF phase-A
    ladder from avals alone, at BOTH probe-table widths (e, 2e), with
    zero failures — keyed on the same planned capacity + ANN config a
    later bulk_load produces (satellite 3)."""
    from oryx_tpu.deploy import warmup

    old = (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS, sm._BLOCK_KSEL,
           sm._PA_TILE)
    old_state = dict(sm._PALLAS_STATE)
    sm._PALLAS_STATE.clear()
    sm._FLAT_SCORES_LIMIT = 1
    sm._MAX_CHUNK_ROWS = 1024
    sm._BLOCK_KSEL = 4
    sm._PA_TILE = 1024
    report: dict = {"compiled": [], "failed": []}
    try:
        warmup.warm_serving_shapes(6, 4096, "float32", 1.0, report,
                                   ann=_cfg(8, nprobe=4))
    finally:
        sm._PALLAS_STATE.clear()
        sm._PALLAS_STATE.update(old_state)
        (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS, sm._BLOCK_KSEL,
         sm._PA_TILE) = old
    names = [c["kernel"] for c in report["compiled"]]
    # e = pow2ceil(4096 / (8 * 128)) = 4; ladder covers {e, 2e}
    assert any("ivf bpc=4" in nm for nm in names), names
    assert any("ivf bpc=8" in nm for nm in names), names
    assert not [f for f in report["failed"] if "ivf" in f["kernel"]], \
        report["failed"]


# -- certificate gate (tentpole b: router can never serve below it) ----------

def _streaming_knobs():
    return (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS, sm._BLOCK_KSEL,
            sm._PA_TILE)


def test_certificate_flip_gates_routing_and_answers_stay_exact():
    """The router provably never serves ANN below min-recall: with a
    passing certificate "ivf" heads the phase-A chain, is MEASURED by
    the router, and (at nprobe == cells) serves the exact answers;
    flipping the certificate below min-recall invalidates the cached
    route (the ann half of the re-measure key) and removes "ivf" from
    the chain entirely — below the gate there is no ANN kind to route."""
    rng = np.random.default_rng(50)
    n, features, cells = 4096, 6, 8
    model = ALSServingModel(features=features, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(n)],
                      rng.standard_normal((n, features)).astype(
                          np.float32))
    model.X.bulk_load(["u0"], rng.standard_normal(
        (1, features)).astype(np.float32))
    cfg = _cfg(cells, nprobe=cells)  # exact by construction
    yv, ya, _ = model.Y.host_arrays()
    cents = ivf.train_generation_centroids(
        yv[ya][:, :features], cfg)
    state = ivf.AnnState(cfg, cents)
    state.recall = 1.0  # certificate measured elsewhere; pin it
    old = _streaming_knobs()
    old_state = dict(sm._PALLAS_STATE)
    sm._PALLAS_STATE.clear()
    sm._FLAT_SCORES_LIMIT = 1
    sm._MAX_CHUNK_ROWS = 1024
    sm._BLOCK_KSEL = 4
    sm._PA_TILE = 1024
    try:
        model.attach_ann(state)
        n_rows = len(model.Y.row_ids())
        assert model._ann_routable(n_rows)
        kinds, _ = model._phase_a_kinds(n_rows, 128, BS)
        assert kinds[0] == "ivf"
        # static chain (no route yet): the drain dispatches ivf — and
        # at nprobe == cells it returns the exact answer set (scores
        # may differ in the last ulp between accumulation orders, so
        # compare the returned ids, which are ulp-stable here: random
        # gaussian scores have O(0.1) gaps at the top)
        q = rng.standard_normal((16, features)).astype(np.float32)
        got = [[i for i, _ in r] for r in model.top_n_batch(5, q)]
        assert model._ivf_mirror is not None  # ivf really dispatched
        model.attach_ann(None)
        want = [[i for i, _ in r] for r in model.top_n_batch(5, q)]
        assert got == want
        model.attach_ann(state)
        # the router measures the ivf kind alongside the others
        route = model.refresh_route(force=True)
        assert route["ann_key"] == cfg.route_key() + (True,)
        assert route["costs_exact_ms"].get("ivf") is not None
        # certificate flips below min-recall: the cached route is
        # stale (ann_key changed) and the re-measured chain has no
        # "ivf" kind at all
        state.recall = 0.20
        assert model._route_current(n_rows) is None
        route2 = model.refresh_route()
        assert route2 is not route
        assert route2["ann_key"] == cfg.route_key() + (False,)
        assert not model._ann_routable(n_rows)
        kinds2, _ = model._phase_a_kinds(n_rows, 128, BS)
        assert "ivf" not in kinds2
        assert [[i for i, _ in r]
                for r in model.top_n_batch(5, q)] == want
    finally:
        sm._PALLAS_STATE.clear()
        sm._PALLAS_STATE.update(old_state)
        (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS, sm._BLOCK_KSEL,
         sm._PA_TILE) = old


# -- quality-oracle recall certificate (tentpole b, tier-1 acceptance) --------

def _oracle_catalog(seed=17, n_users=192, n_items=1024, groups=8,
                    features=16):
    """Community-structured implicit ratings -> ALS factors via the
    PR 2 quality oracle: users mostly rate items of their own group,
    so the trained item factors carry the cluster structure real
    catalogs have."""
    from oryx_tpu.ml.oracle import train_als_oracle

    rng = np.random.default_rng(seed)
    users, items, vals = [], [], []
    for u in range(n_users):
        own = np.arange(u % groups, n_items, groups)
        for i in list(rng.choice(own, size=24, replace=False)) + \
                list(rng.choice(n_items, size=3, replace=False)):
            users.append(u)
            items.append(int(i))
            vals.append(1.0)
    X, Y = train_als_oracle(np.array(users), np.array(items),
                            np.array(vals), n_users, n_items, features,
                            0.01, 1.0, True, 8, seed=0)
    return X.astype(np.float32), Y.astype(np.float32)


def _replay(mgr, X, Y, features, known=None):
    doc = pmml_io.build_skeleton_pmml()
    pmml_io.add_extension(doc, "features", features)
    pmml_io.add_extension(doc, "implicit", True)
    pmml_io.add_extension_content(
        doc, "XIDs", [f"u{j}" for j in range(len(X))])
    pmml_io.add_extension_content(
        doc, "YIDs", [f"i{j}" for j in range(len(Y))])
    mgr.consume_key_message(KEY_MODEL, pmml_io.to_string(doc))
    for j, row in enumerate(Y):
        mgr.consume_key_message(KEY_UP, json.dumps(
            ["Y", f"i{j}", [float(v) for v in row]]))
    for j, row in enumerate(X):
        mgr.consume_key_message(KEY_UP, json.dumps(
            ["X", f"u{j}", [float(v) for v in row],
             (known or {}).get(j, [])]))


def _ann_manager(extra=None, spec=None):
    conf = {
        "oryx.serving.model-manager-class": "unused",
        "oryx.input-topic.broker": None,
        "oryx.update-topic.broker": None,
        # nprobe 6/8: the oracle catalog's 8 communities merge in the
        # row-sample-init k-means (measured recall@50 by nprobe:
        # 4 -> 0.9009, 5 -> 0.9437, 6 -> 0.9725); everything on the
        # measurement path is seeded, so the certificate is exact
        "oryx.als.ann.enabled": True,
        "oryx.als.ann.cells": 8,
        "oryx.als.ann.nprobe": 6,
        "oryx.als.ann.train-sample": 1024,
    }
    if spec is not None:
        conf["oryx.cluster.enabled"] = True
        conf["oryx.cluster.shard"] = spec
    conf.update(extra or {})
    return ALSServingModelManager(from_dict(conf))


@pytest.mark.numerics
def test_recall_certificate_on_oracle_factors_meets_bar():
    """recall@50 >= 0.95 on quality-oracle-trained factors — the
    ISSUE 18 acceptance bar, measured by the REAL load path: the
    manager trains the quantizer, builds the index inside
    ``model_load_s``, measures the certificate against the exact
    kernel on the generation's own user factors, and publishes it on
    /metrics with the routable verdict."""
    X, Y = _oracle_catalog()
    mgr = _ann_manager()
    _replay(mgr, X, Y, 16)
    model = mgr.model
    a = model._ann
    assert a is not None and a.recall is not None
    assert a.recall >= 0.95, a.recall
    assert mgr.ann_index_fallbacks == 0
    assert mgr.ann_index_bytes > 0
    assert mgr.model_load_s > 0.0  # index build is inside the clock
    n_rows = len(model.Y.row_ids())
    assert model._ann_routable(n_rows)
    kinds, _ = model._phase_a_kinds(n_rows, 128, BS)
    assert kinds[0] == "ivf"
    ann_m = model.metrics()["kernel_route"]["ann"]
    assert ann_m["recall"] == a.recall
    assert ann_m["routable"] is True
    assert ann_m["min_recall"] == 0.95
    assert ann_m["index_bytes"] == mgr.ann_index_bytes


# -- per-slice artifacts + chaos fail-closed (satellite 2) --------------------

def _publish_sliced_ann(tmp_path, Y, X, features, ring=24, ann=True):
    from oryx_tpu.app.als import slices

    y_ids = [f"i{j}" for j in range(len(Y))]
    x_ids = [f"u{j}" for j in range(len(X))]
    model_dir = str(tmp_path / "model")
    os.makedirs(model_dir, exist_ok=True)
    doc = pmml_io.build_skeleton_pmml()
    pmml_io.add_extension(doc, "features", features)
    pmml_io.add_extension(doc, "implicit", True)
    pmml_io.add_extension_content(doc, "XIDs", x_ids)
    pmml_io.add_extension_content(doc, "YIDs", y_ids)
    pmml_path = model_dir + "/model.pmml.xml"
    pmml_io.write(doc, pmml_path)
    pub_ann = None
    cells = None
    if ann:
        cfg = _cfg(8, nprobe=4)
        cents = ivf.train_generation_centroids(Y, cfg)
        cells = ops_ann.assign_cells(Y, cents)
        pub_ann = (cents, cells)
    slim = slices.publish_sliced(model_dir, y_ids, Y, x_ids, X, None,
                                 ring, ann=pub_ann)
    cents = pub_ann[0] if pub_ann else None
    return (model_dir, slim, cents, cells,
            slices.model_ref_message(pmml_path, model_dir, slim))


def test_ann_artifact_round_trip(tmp_path):
    """publish_sliced(ann=...) ships centroids once per generation and
    cell assignments per slice; reading them back must reproduce the
    trainer's partition exactly (crc-checked, manifest-aligned)."""
    X, Y = _oracle_catalog(n_users=32, n_items=512)
    model_dir, slim, cents, cells, _msg = _publish_sliced_ann(
        tmp_path, Y, X, 16)
    cents_rt = ivf.read_centroids(model_dir, slim["ann"])
    assert cents_rt.shape == (8, 16)
    np.testing.assert_allclose(cents_rt, cents, atol=1e-6)
    got: list[int] = []
    for entry in slim["slices"]:
        aent = entry.get("ann")
        assert aent is not None
        sc = ivf.read_slice_cells(model_dir, aent)
        assert len(sc) == int(aent["rows"])
        got.extend(sc)
    assert sorted(got) == sorted(int(c) for c in cells)


def test_manager_builds_ann_from_published_artifacts(tmp_path):
    """The sliced load path consumes the trainer-published index: the
    model certifies and routes without local k-means over rows the
    replica never trains on, and the load-time gauges are live."""
    X, Y = _oracle_catalog()
    _model_dir, _slim, _cents, _cells, msg = _publish_sliced_ann(
        tmp_path, Y, X, 16)
    mgr = _ann_manager(spec="0/1")
    mgr.consume_key_message(KEY_MODEL_REF, msg)
    model = mgr.model
    a = model._ann
    assert a is not None and a.recall is not None
    assert mgr.ann_index_fallbacks == 0
    assert a.recall >= 0.95, a.recall
    assert mgr.ann_index_bytes > 0
    assert model._ann_routable(len(model.Y.row_ids()))


def test_ann_index_corrupt_chaos_fails_closed_to_exact(tmp_path):
    """Chaos point ``ann-index-corrupt``: a corrupt/missing per-slice
    index artifact must NOT fail the model load — the replica serves
    on the exact kernel (fail CLOSED), counts ``ann_index_fallbacks``,
    and reports zero index bytes (docs/RESILIENCE.md row)."""
    X, Y = _oracle_catalog(n_users=32, n_items=512)
    _model_dir, _slim, _cents, _cells, msg = _publish_sliced_ann(
        tmp_path, Y, X, 16)
    faults.inject("ann-index-corrupt", mode="error", times=1)
    mgr = _ann_manager(spec="0/1")
    mgr.consume_key_message(KEY_MODEL_REF, msg)
    assert faults.fired("ann-index-corrupt") == 1
    model = mgr.model
    assert model is not None  # the load itself must survive
    assert mgr.ann_index_fallbacks == 1
    assert mgr.ann_index_bytes == 0
    assert model._ann is None
    kinds, _ = model._phase_a_kinds(len(model.Y.row_ids()), 128, BS)
    assert "ivf" not in kinds
    # and the replica actually serves
    assert model.top_n(5, user_vector=X[0])


def test_ann_centroid_artifact_bitrot_fails_closed(tmp_path):
    """Real on-disk corruption (not just the injected fault): a
    truncated centroid artifact fails the checksum and the load falls
    closed to the exact kernel the same way."""
    X, Y = _oracle_catalog(n_users=32, n_items=512)
    model_dir, _slim, _cents, _cells, msg = _publish_sliced_ann(
        tmp_path, Y, X, 16)
    path = os.path.join(model_dir, ivf.CENTROIDS_FILE)
    payload = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(payload[:len(payload) // 2])
    mgr = _ann_manager(spec="0/1")
    mgr.consume_key_message(KEY_MODEL_REF, msg)
    model = mgr.model
    assert model is not None
    assert mgr.ann_index_fallbacks == 1
    assert model._ann is None
    assert model.top_n(5, user_vector=X[0])
