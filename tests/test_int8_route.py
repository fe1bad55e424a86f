"""ISSUE 3 tier-1 coverage: int8 phase-A exactness at the shipped
f=50 shape (tie and retired-row edges included), the int8+fold mirror,
and the measured-cost kernel router (LSH auto-fallback under an
injected cost inflation).

All CPU-runnable: pallas kernels run in interpret mode; the router is
exercised with the injected-delay fault points it exposes for exactly
this purpose (kernel_router fires ``route-measure-lsh`` /
``route-measure-exact`` inside the timed region of each variant).
"""

from __future__ import annotations

import numpy as np
import pytest

from oryx_tpu.app.als.serving_model import ALSServingModel
from oryx_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _clear_faults():
    faults.clear()
    yield
    faults.clear()


def _exact_sets_match(got_s, got_i, want_s, want_i):
    """Exact-top-N equality that is honest about ties: scores must be
    bit-identical position-by-position, ids must match wherever the
    score is untied, and each tied-score group must select the same id
    SET (lax.top_k breaks ties by index order, which differs between
    the flat scan's global order and phase B's gathered-block order —
    either way the returned items all genuinely share the kth score)."""
    np.testing.assert_array_equal(got_s, want_s)
    for b in range(got_s.shape[0]):
        gs, ws = got_s[b], want_s[b]
        start = 0
        while start < len(gs):
            end = start
            while end < len(gs) and gs[end] == gs[start]:
                end += 1
            assert set(got_i[b, start:end].tolist()) == \
                set(want_i[b, start:end].tolist()), (b, start, end)
            start = end


def _f50_fixture(seed: int, n: int = 4096, b: int = 8):
    """Lane-padded f=50 item matrix with deliberate tie and retired-row
    edges: a duplicated head row (guaranteed score tie inside the
    top-N) and retired rows salted through the head blocks."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    F, W = 50, 128
    Y = np.zeros((n, W), np.float32)
    Y[:, :F] = rng.standard_normal((n, F)).astype(np.float32)
    # tie edge: three identical copies of one strong row, spread across
    # different 128-row blocks so phase B's gather order differs from
    # the flat scan's index order
    strong = (3.0 * rng.standard_normal(F)).astype(np.float32)
    for idx in (7, 700, 2900):
        Y[idx, :F] = strong
    act = np.ones(n, bool)
    act[5::11] = False          # retired rows, including head blocks
    act[701] = False            # retired right next to a tie copy
    Q = np.zeros((b, W), np.float32)
    Q[:, :F] = rng.standard_normal((b, F)).astype(np.float32)
    Q[0, :F] = strong / np.linalg.norm(strong)  # aims at the tied rows
    return jnp.asarray(Y), jnp.asarray(Q), jnp.asarray(act), F, W


@pytest.mark.numerics
def test_int8_certificate_exact_at_f50_ties_and_retired():
    """int8 phase A + f32 rescore must return exactly the f32 exact
    top-N at the shipped f=50 shape — including score ties and retired
    rows — wherever the certificate passes, and retired rows must never
    appear."""
    import jax
    from oryx_tpu.app.als import serving_model as sm

    Y, Q, active, F, W = _f50_fixture(80)
    n, b = int(Y.shape[0]), int(Q.shape[0])
    bs, ksel, k = 128, 24, 8
    y8, sy_b, l1y_b = sm._quantize_items_kernel(Y, bs)
    pen_i = sm._penalty_kernel_i32(active, bs)
    old_tile = sm._PA_TILE
    sm._PA_TILE = 1024
    try:
        ts, ti, cert = jax.device_get(sm._batch_top_n_twophase_pallas_i8(
            Y, y8, sy_b, l1y_b, Q, pen_i, active, np.int32(b),
            k=k, bs=bs, ksel=ksel, interpret=True))
    finally:
        sm._PA_TILE = old_tile
    want_s, want_i = jax.device_get(
        sm._batch_top_n_kernel(Y, Q, active, k))
    ok = np.asarray(cert)
    assert ok.sum() >= b - 1, ok  # margin must not mass-fail certs
    _exact_sets_match(np.asarray(ts)[ok], np.asarray(ti)[ok],
                      want_s[ok], want_i[ok])
    retired = set(np.nonzero(~np.asarray(active))[0].tolist())
    assert not (set(np.asarray(ti)[ok].ravel().tolist()) & retired)
    # the tie row the query aims at must surface through the int8 path
    assert {7, 700, 2900} & set(np.asarray(ti)[0, :3].tolist())


@pytest.mark.numerics
def test_int8_fold_certificate_exact_at_f50():
    """The int8+fold phase A (the deepened mirror that streams ~items x
    features bytes) must agree with the f32 exact scan at f=50 exactly
    like the unfolded int8 kernel — the folded integer dot is
    bit-identical, so bounds, certificates and phase B are shared."""
    import jax
    from oryx_tpu.app.als import serving_model as sm

    Y, Q, active, F, W = _f50_fixture(81)
    bs, ksel, k = 128, 24, 8
    fold = sm._fold_factor(W, F)
    assert fold == 2  # 50 <= 64 = 128/2
    y8, sy_b, l1y_b = sm._quantize_items_kernel(Y, bs)
    y8f, pen_i_f = sm._fold_items_i8_kernel(y8, active, fold, bs)
    old_tile = sm._PA_TILE
    sm._PA_TILE = 1024
    try:
        ts, ti, cert = jax.device_get(
            sm._batch_top_n_twophase_pallas_i8_fold(
                Y, y8f, sy_b, l1y_b, Q, pen_i_f, active,
                np.int32(Q.shape[0]),
                k=k, bs=bs, ksel=ksel, fold=fold, interpret=True))
        # and bit-identical to the UNFOLDED int8 build: same integer
        # maxima, same bounds, same phase B
        pen_i = sm._penalty_kernel_i32(active, bs)
        ts_u, ti_u, cert_u = jax.device_get(
            sm._batch_top_n_twophase_pallas_i8(
                Y, y8, sy_b, l1y_b, Q, pen_i, active,
                np.int32(Q.shape[0]),
                k=k, bs=bs, ksel=ksel, interpret=True))
    finally:
        sm._PA_TILE = old_tile
    np.testing.assert_array_equal(ts, ts_u)
    np.testing.assert_array_equal(ti, ti_u)
    np.testing.assert_array_equal(cert, cert_u)
    want_s, want_i = jax.device_get(
        sm._batch_top_n_kernel(Y, Q, active, k))
    ok = np.asarray(cert)
    assert ok.sum() >= Q.shape[0] - 1, ok
    _exact_sets_match(np.asarray(ts)[ok], np.asarray(ti)[ok],
                      want_s[ok], want_i[ok])


def _small_lsh_model(n=2048, features=10, seed=90):
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(seed)
    old_tile = sm._PA_TILE
    sm._PA_TILE = 128  # the store's step: 256 regions of 128 rows
    try:
        model = ALSServingModel(features=features, implicit=True,
                                sample_rate=0.3)
    finally:
        sm._PA_TILE = old_tile
    assert model._lsh_active() and model.Y.partitioned
    model.Y.bulk_load([f"i{j}" for j in range(n)],
                      rng.standard_normal((n, features)).astype(
                          np.float32))
    model.X.bulk_load(["u0"],
                      rng.standard_normal((1, features)).astype(
                          np.float32))
    return model


def test_router_serves_pruned_answers_even_when_lsh_measures_slower():
    """Pruning is the configuration's semantics, not a verdict of the
    measurement (ISSUE 36): a fault point that inflates the measured
    LSH cost changes the reported cost and nothing that is served."""
    model = _small_lsh_model()
    rng = np.random.default_rng(91)
    q = rng.standard_normal((3, model.features)).astype(np.float32)
    pruned = model.top_n_batch(5, q)
    faults.inject("route-measure-lsh", mode="delay", times=None,
                  delay_sec=0.05)
    route = model.refresh_route(force=True)
    assert faults.fired("route-measure-lsh") > 0
    assert route["measured"] and route["use_lsh"] is True
    lsh_cost = min(c for c in route["costs_lsh_ms"].values() if c)
    exact_cost = min(c for c in route["costs_exact_ms"].values() if c)
    assert lsh_cost > exact_cost      # it measured slower, and still:
    assert model.top_n_batch(5, q, use_lsh=True) == pruned
    assert pruned != model.top_n_batch(5, q, use_lsh=False)
    assert model.kernel_route_label.endswith("+lsh")
    # /metrics exposes both measured costs and the partitioning
    m = model.metrics()
    assert m["kernel_route"]["use_lsh"] is True
    assert m["kernel_route"]["costs_lsh_ms"]
    assert m["kernel_route"]["costs_exact_ms"]
    assert m["kernel_route"]["partitioning"]["buckets"] == 256
    # only the builds that can skip steps are offered or measured
    assert set(route["costs_lsh_ms"]) <= {"pallas", "scan"}
    assert set(route["costs_exact_ms"]) == {"flat"}   # a small store


def test_router_honors_lsh_when_it_measures_faster():
    """Inflate the EXACT side instead: the route says the same."""
    model = _small_lsh_model(seed=92)
    faults.inject("route-measure-exact", mode="delay", times=None,
                  delay_sec=0.05)
    route = model.refresh_route(force=True)
    assert faults.fired("route-measure-exact") > 0
    assert route["use_lsh"] is True
    assert route["chosen"] == "scan"   # the CPU lowers no pallas build


def test_router_streaming_orders_kinds_and_survives_pallas_fallback():
    """On the CPU streaming path every pallas build fails to lower; the
    router must still measure the lax.scan build, install a route, and
    leave the dispatch chain's static order intact for unmeasured
    kinds.  A synthetic cost table must reorder the chain strictly by
    measured cost."""
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(93)
    model = ALSServingModel(features=6, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(4096)],
                      rng.standard_normal((4096, 6)).astype(np.float32))
    old = (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS, sm._BLOCK_KSEL,
           sm._PA_TILE)
    old_state = dict(sm._PALLAS_STATE)
    sm._PALLAS_STATE.clear()
    sm._FLAT_SCORES_LIMIT = 1
    sm._MAX_CHUNK_ROWS = 1024
    sm._BLOCK_KSEL = 4
    sm._PA_TILE = 1024
    try:
        route = model.refresh_route(force=True)
        assert route["path"] == "streaming"
        # scan measured; pallas builds recorded as unavailable on CPU
        assert route["costs_exact_ms"].get("scan") is not None
        assert route["costs_exact_ms"].get("pallas") is None
        n_rows = len(model.Y.row_ids())
        # synthetic measured costs reorder the chain cheapest-first
        model._route = {"measured": True, "lsh_configured": False,
                        "phase_a_costs_ms": {"pallas": 1.0,
                                             "fold": 5.0,
                                             "i8_fold": 3.0}}
        model._route_capacity = n_rows
        assert model._route_order(
            ["i8_fold", "fold", "i8", "pallas"], n_rows) == \
            ["pallas", "i8_fold", "fold", "i8"]
        # a stale route (capacity mismatch) leaves the static order
        assert model._route_order(["fold", "pallas"], n_rows + 1) == \
            ["fold", "pallas"]
    finally:
        sm._PALLAS_STATE.clear()
        sm._PALLAS_STATE.update(old_state)
        (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS, sm._BLOCK_KSEL,
         sm._PA_TILE) = old
        model._route = None


def test_route_cached_per_capacity_and_refreshed_on_growth():
    """A route is reused while the padded capacity matches and is NOT
    consulted after the store regrows (hot-swap semantics)."""
    model = _small_lsh_model(seed=94)
    r1 = model.refresh_route()
    assert r1 is not None
    assert model.refresh_route() is r1  # cached, no re-measure
    n_rows = len(model.Y.row_ids())
    assert model._route_current(n_rows) is r1
    assert model._route_current(n_rows * 2) is None
    r2 = model.refresh_route(force=True)
    assert r2 is not r1


def test_router_skips_empty_and_sharded_models():
    model = ALSServingModel(features=6, implicit=True)
    assert model.refresh_route() is None
    assert model.kernel_route_label is None


def test_refresh_route_failure_never_escapes(monkeypatch):
    """Route measurement is advisory: a failure inside measure_routes
    (device OOM building a mirror, transport error) must not escape
    refresh_route — an escaped exception on the MODEL consume path
    would trap the serving update consumer in replay-from-0 against
    the same deterministic failure."""
    from oryx_tpu.app.als import kernel_router

    model = _small_lsh_model(seed=95)

    def boom(*_a, **_k):
        raise RuntimeError("injected measurement failure")

    monkeypatch.setattr(kernel_router, "measure_routes", boom)
    assert model.refresh_route(force=True) is None  # swallowed
    # serving continues config-driven: no route installed, and a
    # model under LSH still prunes
    assert model._route_current(len(model.Y.row_ids())) is None
    before = model.lsh_windows
    assert len(model.top_n_batch(
        3, np.ones((1, model.features), np.float32))[0]) == 3
    assert model.lsh_windows == before + 1


def test_route_measurement_evicts_losing_mirrors():
    """Measurement materializes every build's mirror; after routing,
    only the chosen kind's device arrays may stay pinned (at 20M rows
    the losers are ~5 GB of HBM next to the store)."""
    from oryx_tpu.app.als import serving_model as sm

    rng = np.random.default_rng(96)
    model = ALSServingModel(features=6, implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(4096)],
                      rng.standard_normal((4096, 6)).astype(np.float32))
    old = (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS, sm._BLOCK_KSEL,
           sm._PA_TILE)
    old_state = dict(sm._PALLAS_STATE)
    sm._PALLAS_STATE.clear()
    sm._FLAT_SCORES_LIMIT = 1
    sm._MAX_CHUNK_ROWS = 1024
    sm._BLOCK_KSEL = 4
    sm._PA_TILE = 1024
    try:
        route = model.refresh_route(force=True)
        # CPU routes to the scan build, which needs NO mirror: every
        # measured-and-lost mirror must be gone
        assert route["chosen"] == "scan"
        for attr in ("_i8", "_i8_fold", "_fold",
                     "_penalty", "_penalty_i"):
            assert getattr(model, attr) is None, attr
    finally:
        sm._PALLAS_STATE.clear()
        sm._PALLAS_STATE.update(old_state)
        (sm._FLAT_SCORES_LIMIT, sm._MAX_CHUNK_ROWS, sm._BLOCK_KSEL,
         sm._PA_TILE) = old


# -- the router's stopwatch (the m-queue estimator) on a fake clock ----------

class _FakeDevice:
    """A device whose programs run one after another for ``exec_s``
    each, ``rtt_s`` away: ``dispatch`` enqueues and returns at once,
    ``fetch(h)`` returns when program ``h`` has run and its result has
    travelled back.  ``perf_counter`` is the only clock."""

    def __init__(self, exec_s: float, rtt_s: float = 0.012,
                 compile_s: float = 0.0, lone_jitter_s: float = 0.0):
        self.exec_s, self.rtt_s, self.compile_s = exec_s, rtt_s, compile_s
        self.lone_jitter_s = lone_jitter_s   # extra on a 1-deep fetch
        self.now = 0.0
        self.free_at = 0.0     # when the device runs dry
        self.dispatched = 0
        self.fetch_depths: list[int] = []   # programs behind each fetch
        self._since_fetch = 0

    def perf_counter(self) -> float:
        return self.now

    def dispatch(self) -> float:
        cost = self.exec_s + (self.compile_s if not self.dispatched else 0)
        self.dispatched += 1
        self._since_fetch += 1
        self.free_at = max(self.free_at, self.now) + cost
        return self.free_at

    def fetch(self, done_at: float) -> None:
        self.fetch_depths.append(self._since_fetch)
        jitter = self.lone_jitter_s if self._since_fetch == 1 else 0.0
        self._since_fetch = 0
        self.now = max(self.now, done_at) + self.rtt_s + jitter


def _estimate(monkeypatch, dev: _FakeDevice, m: int) -> float:
    from oryx_tpu.app.als import kernel_router
    monkeypatch.setattr(kernel_router, "time", dev)
    return kernel_router._time_exec_ms(dev.dispatch, dev.fetch, m)


@pytest.mark.parametrize("case", ["difference", "deepens", "max_m",
                                  "floor", "compile"])
def test_route_stopwatch_on_a_fake_clock(monkeypatch, case):
    if case == "difference":
        # exec = (t_m - t_1) / (m - 1): the round trip cancels, at the
        # first depth when the delta already clears 30 ms
        dev = _FakeDevice(exec_s=0.020, rtt_s=0.1)
        assert _estimate(monkeypatch, dev, 3) == pytest.approx(20.0)
        assert max(dev.fetch_depths) == 3
    elif case == "deepens":
        # 2 ms a program: 3 deep the delta is 4 ms, 12 deep 22 ms, 48
        # deep 94 ms >= 30 — the queue went x4 twice and stopped there
        dev = _FakeDevice(exec_s=0.002)
        assert _estimate(monkeypatch, dev, 3) == pytest.approx(2.0)
        assert sorted(set(dev.fetch_depths)) == [1, 3, 12, 48]
    elif case == "max_m":
        # 0.1 ms a program never clears 30 ms: it stops at 96 deep and
        # still reads the cost
        dev = _FakeDevice(exec_s=0.0001)
        assert _estimate(monkeypatch, dev, 6) == pytest.approx(0.1)
        assert max(dev.fetch_depths) == 96
    elif case == "floor":
        # a delta it cannot resolve (none; then a negative one, the
        # lone fetch the slower) routes at the floor, so
        # indistinguishable kernels keep the static chain's order
        dev = _FakeDevice(exec_s=0.0)
        assert _estimate(monkeypatch, dev, 3) == 1e-4
        dev = _FakeDevice(exec_s=0.00001, lone_jitter_s=0.05)
        assert _estimate(monkeypatch, dev, 3) == 1e-4
        assert max(dev.fetch_depths) == 96
    else:
        # the first call is the compile and is not timed: a minute of
        # it leaves the reading where it was
        dev = _FakeDevice(exec_s=0.020, compile_s=60.0)
        assert _estimate(monkeypatch, dev, 3) == pytest.approx(20.0)
        assert dev.fetch_depths[0] == 1 and dev.now > 60.0
