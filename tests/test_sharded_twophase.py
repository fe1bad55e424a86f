"""The two-phase scan INSIDE the sharded SPMD program (PR 34): with
``item_shards > 1`` every shard runs the one-chip scan over its own rows
— phase A's block maxima, phase B, the certificate, the exact scan where
it fails — then one all_gather and the merge, one jitted program a
window.  Held here to the plain sharded reference of the benchmark
(``benchmark/apps/als_sharded_reference.py``: per shard a blockwise
float32 matmul + top_k, merged on the host) on the virtual CPU mesh, and
to the one-chip model over the same factors."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.apps.als_sharded_reference import ShardedReference  # noqa: E402
from benchmark.observe import Observations  # noqa: E402
from oryx_tpu.app.als import feature_vectors as fv  # noqa: E402
from oryx_tpu.app.als import serving_model as sm  # noqa: E402
from oryx_tpu.app.als.serving_model import ALSServingModel  # noqa: E402
from oryx_tpu.obs.trace import Tracer  # noqa: E402
from oryx_tpu.parallel import serving_dist as sd  # noqa: E402
from oryx_tpu.serving.batcher import TopNBatcher, _Job  # noqa: E402

FEATURES, USERS, BS = 8, 24, 8
# a shard of 8,192 rows is 8 chunks of 1,024 and 1,024 blocks of 8: the
# widest fetch (k = 256) selects 512 of them and leaves some unselected
SHARD_ROWS = 8192


@pytest.fixture
def toy(monkeypatch):
    """The streaming two-phase branch at toy scale (tests/test_als.py's
    monkeypatches), with a block small enough for k = 256."""
    monkeypatch.setattr(sm, "_FLAT_SCORES_LIMIT", 1)
    monkeypatch.setattr(sm, "_MAX_CHUNK_ROWS", 1024)
    monkeypatch.setattr(sm, "_BLOCK_ROWS", BS)
    monkeypatch.setattr(sm, "_BLOCK_KSEL", 8)


def _factors(seed, rows):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((rows, FEATURES)).astype(np.float32),
            rng.standard_normal((USERS, FEATURES)).astype(np.float32))


def _model(shards, Y, X, dtype="float32", known=0, seed=5):
    m = ALSServingModel(FEATURES, implicit=True, dtype=dtype,
                        item_shards=shards)
    m.Y.bulk_load([f"i{j}" for j in range(len(Y))], Y)
    m.X.bulk_load([f"u{j}" for j in range(len(X))], X)
    rng = np.random.default_rng(seed)
    for u in range(len(X)):
        m.add_known_items(f"u{u}", {f"i{j}" for j in rng.choice(
            len(Y), size=known, replace=False)})
    return m


def _answers(model, users, how_many=10):
    """What the HTTP door would send for these users' default
    /recommend, in the reference's own form."""
    X = np.stack([model.get_user_vector(u) for u in users])
    got = model.top_n_batch(how_many, X,
                            [model.get_known_items(u) for u in users])
    return [(u, [{"id": i, "value": s} for i, s in row])
            for u, row in zip(users, got)]


@pytest.mark.parametrize("known, k", [(0, 16), (20, 32), (100, 128),
                                      (240, 256)])
@pytest.mark.parametrize("shards", [2, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_sharded_program_equals_the_plain_reference(dtype, shards,
                                                        known, k, toy):
    Y, X = _factors(34, shards * SHARD_ROWS)
    model = _model(shards, Y, X, dtype, known)
    users = [f"u{j}" for j in range(11)]   # an [32] window, 21 rows padded
    answers = _answers(model, users)
    ref = ShardedReference(model)
    assert ref.check(answers, 10) == []
    assert ref.checked == len(users)
    # through the two-phase program, every certificate held
    assert sm._pad_k(10 + known) == k
    assert model.sharded_windows == 1
    assert model.twophase_fallbacks == model.shard_fallback_rows == 0
    assert model.metrics()["sharded_windows"] == 1
    # known items never come back
    for u, served in answers:
        assert not {g["id"] for g in served} & model.get_known_items(u)


def test_retired_rows_never_come_back(toy):
    Y, X = _factors(35, 4 * SHARD_ROWS)
    model = _model(4, Y, X)
    users = [f"u{j}" for j in range(8)]
    first = _answers(model, users)
    gone = {g["id"] for _, served in first for g in served[:3]}
    for i in gone:
        model.Y.remove(i)
    again = _answers(model, users)
    assert not gone & {g["id"] for _, served in again for g in served}
    assert ShardedReference(model).check(again, 10) == []
    assert model.twophase_fallbacks == 0


def test_a_store_whose_best_rows_all_sit_on_one_shard(toy):
    """The merge may not assume the shards contribute alike: here every
    answer is ten rows of shard 2."""
    Y, X = _factors(36, 4 * SHARD_ROWS)
    Y[2 * SHARD_ROWS:3 * SHARD_ROWS] *= 50.0
    model = _model(4, Y, X)
    answers = _answers(model, [f"u{j}" for j in range(8)])
    for _, served in answers:
        rows = [int(g["id"][1:]) for g in served]
        assert all(2 * SHARD_ROWS <= r < 3 * SHARD_ROWS for r in rows)
    assert ShardedReference(model).check(answers, 10) == []


def test_one_shard_fails_its_certificate_and_answers_by_its_exact_scan(
        toy, monkeypatch):
    """Shard 1's two-phase answer is thrown away and its certificate
    failed for every row; the merged answer is right all the same, so
    shard 1 answered by the exact scan over its own rows, inside the
    program, and the other shards' answers stood."""
    real = sm._phase_b

    def sabotaged(Y, *args, **kw):
        ts, ti, cert = real(Y, *args, **kw)
        bad = jax.lax.axis_index("items") == 1
        return (jnp.where(bad, -1.0, ts), jnp.where(bad, 0, ti),
                cert & ~bad)

    monkeypatch.setattr(sm, "_phase_b", sabotaged)
    Y, X = _factors(37, 4 * SHARD_ROWS)
    # ... and shard 1 holds the best rows, so a lost shard would show
    Y[SHARD_ROWS:2 * SHARD_ROWS] *= 3.0
    model = _model(4, Y, X)
    answers = _answers(model, [f"u{j}" for j in range(3)])
    assert ShardedReference(model).check(answers, 10) == []
    assert any(SHARD_ROWS <= int(g["id"][1:]) < 2 * SHARD_ROWS
               for _, served in answers for g in served)
    # one [8] window: 8 rows failed, on one shard of four
    assert model.sharded_windows == 1
    assert model.shard_fallback_rows == 8
    assert model.twophase_fallbacks == 8


@pytest.mark.parametrize("n_real", [1, 3, 7, 8])
def test_padding_rows_pass_on_every_shard_and_send_none_to_its_exact_scan(
        n_real, toy, monkeypatch):
    """PR 38: the SPMD program reads how many rows of its window are
    requests.  Every shard's phase B rescores those alone; the rows
    behind them come back -inf from every shard with a certificate of
    True, so ``cert.all()`` sends no shard to its exact scan for them,
    and the requests' merged answers are the full window's, bit for
    bit."""
    exact = []
    real = sm._batch_top_n_chunked_kernel
    monkeypatch.setattr(
        sm, "_batch_top_n_chunked_kernel",
        lambda *a, **kw: exact.append(1) or real(*a, **kw))
    Y, X = _factors(46, 4 * SHARD_ROWS)
    model = _model(4, Y, X)
    vecs, active = model.Y.device_arrays()
    kernels = model._shard_kernels
    plan = sm.shard_plan(vecs, 4, 16, 8)
    Q = kernels.replicate(X[:8])
    whole = jax.device_get(kernels.twophase(vecs, active, Q, 8, 16, plan))
    # the exact scan is TRACED as the branch of a failed certificate
    # (once a program), and never a program of its own
    assert len(exact) == 1
    ts, ti, cert = jax.device_get(
        kernels.twophase(vecs, active, Q, n_real, 16, plan))
    # one program a (window, k), whatever the window holds
    assert len(exact) == 1 and len(kernels._programs) == 1
    assert cert.shape == (4, 8) and cert.all()
    np.testing.assert_array_equal(ts[:n_real], whole[0][:n_real])
    np.testing.assert_array_equal(ti[:n_real], whole[1][:n_real])
    assert np.isfinite(ts[:n_real]).all()
    assert np.isneginf(ts[n_real:]).all()
    # the count rides replicated, placed once a value
    assert sorted(kernels._counts) == sorted({8, n_real})
    # ... and through the model: the requests of an [8] fail nothing
    got = model.top_n_batch(10, X[:n_real])
    assert [len(r) for r in got] == [10] * n_real
    assert (model.sharded_windows, model.shard_fallback_rows,
            model.twophase_fallbacks) == (1, 0, 0)
    m = model.metrics()
    assert (m["phase_b_rows"], m["phase_b_window_rows"]) == (n_real, 8)


def test_a_request_that_fails_on_one_shard_is_counted_once(toy, monkeypatch):
    """Shard 1 fails the certificate of the FIRST of three requests on
    an [8] window: that shard answers by its exact scan inside the
    program, one (row, shard) pair and one row are counted, and the
    five rows of padding behind the requests add nothing."""
    real = sm._phase_b

    def sabotaged(Y, *args, **kw):
        ts, ti, cert = real(Y, *args, **kw)
        bad = (jax.lax.axis_index("items") == 1) & (jnp.arange(8) == 0)
        return ts, ti, cert & ~bad

    monkeypatch.setattr(sm, "_phase_b", sabotaged)
    Y, X = _factors(47, 4 * SHARD_ROWS)
    model = _model(4, Y, X)
    answers = _answers(model, [f"u{j}" for j in range(3)])
    assert ShardedReference(model).check(answers, 10) == []
    assert (model.sharded_windows, model.shard_fallback_rows,
            model.twophase_fallbacks) == (1, 1, 1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_shards_together_are_the_one_chip_model(dtype, toy):
    """Same factors, one device and four: the same ids in the same
    order, and the same scores to the last bit (phase B rescores a row
    by the same einsum over the same stored values on both)."""
    Y, X = _factors(38, 4 * SHARD_ROWS)
    one = _model(1, Y, X, dtype, known=30)
    four = _model(4, Y, X, dtype, known=30)
    users = [f"u{j}" for j in range(USERS)]
    a, b = _answers(one, users), _answers(four, users)
    assert a == b
    assert one.twophase_fallbacks == four.twophase_fallbacks == 0
    assert four.sharded_windows == 1 and one.sharded_windows == 0


def test_the_pallas_phase_a_runs_inside_the_sharded_program(toy,
                                                            monkeypatch):
    """The CPU lowers no pallas build, so the drains above ran the
    lax.scan phase A on every shard; here the kernel runs in interpret
    mode under ``shard_map`` and hands phase B the same maxima."""
    real = sm._pallas_block_maxima
    monkeypatch.setattr(
        sm, "_pallas_block_maxima",
        lambda *a, **kw: real(*a, **kw, interpret=True))
    monkeypatch.setattr(sm, "_PALLAS_STATE", {})
    monkeypatch.setattr(sm, "_PALLAS_ERRORS", {})
    monkeypatch.setattr(sm, "_BLOCK_ROWS", 128)
    monkeypatch.setattr(sm, "_PA_TILE", 512)
    Y, X = _factors(39, 2 * SHARD_ROWS)
    two, one = _model(2, Y, X), _model(1, Y, X)
    users = [f"u{j}" for j in range(5)]
    assert _answers(two, users) == _answers(one, users)
    # the sharded model's verdicts carry its shard count before the kind
    ran = {key[-1]: state for key, state in sm._PALLAS_STATE.items()
           if key[-2] == 2}
    assert ran == {"pallas": "ok"}
    assert "errors" not in two.metrics().get("kernel_route", {})


# -- the certificate on a float32 store -----------------------------------------

def adversarial_store(rows, features, bs, ksel, seed=46):
    """A float32 store and a query on which a phase A at the MXU's
    default precision (one pass: both operands rounded to bfloat16)
    certifies a wrong answer.  ``ksel`` decoy blocks each hold one row
    that scores a true 1.0045+ (the last of them 1.0030, so that in
    float32 the block left unselected is well under the k-th score) and
    rounds UP to 1.0078; one more block holds the best row of the
    store, a true 1.0055 that rounds DOWN to 1.0; every other row
    scores under 0.1.  Rounded, the decoys fill the selection, the best
    row's block is the best UNSELECTED one at 1.0, and the k-th served
    score (rescored in float32: 1.0045) clears it with the guard's 1e-4
    to spare.  Returns (Y, q, the best row)."""
    rng = np.random.default_rng(seed)
    Y = (rng.standard_normal((rows, features)) * 0.01).astype(np.float32)
    Y[:, :2] = 0.0
    blocks = rng.choice(rows // bs, size=ksel + 1, replace=False)
    at = blocks * bs + rng.integers(0, bs, size=ksel + 1)
    Y[at] = 0.0
    Y[at[:-1], 0] = 1.0 + 1e-6 * np.arange(ksel)   # rounds to 1.0
    Y[at[0], 0] = 0.9985                           # rounds to 1.0 too
    Y[at[-1], 1] = 1.002                           # rounds to 1.0
    q = np.zeros(features, np.float32)
    q[0], q[1] = 1.0045, 1.0035                    # round to 1.0078, 1.0
    return Y, q, int(at[-1])


def _one_pass(a):
    return np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32))


@pytest.mark.parametrize("shards", [1, 2])
def test_a_best_row_a_one_pass_product_hides_is_served(shards, toy):
    """What the float32 store's certificate rests on (PR 34's review):
    phase A's maxima bound the unselected blocks only as well as its
    products are computed.  The store here is built so that bfloat16
    products would certify an answer that lacks the best row; the
    program, which multiplies a float32 store at HIGHEST in phase A as
    in phase B, serves it first with every certificate held.  (The CPU
    backend multiplies float32 whatever precision is asked: what pins
    the precision itself is the next test, and the same store on a chip
    is in PERF.md section 5.)"""
    k, ksel = 16, 32
    Y, q, best = adversarial_store(SHARD_ROWS, FEATURES, BS, ksel)
    if shards > 1:      # every decoy and the best row on shard 0's rows
        Y = np.concatenate([Y, np.zeros_like(Y)])
    # the construction is adversarial: rounded, the best row's block is
    # not among the ksel best, and the true k-th score clears it
    true = Y @ q
    rounded = (_one_pass(Y) @ _one_pass(q)).reshape(-1, BS).max(1)
    order = np.argsort(-rounded[:SHARD_ROWS // BS])
    assert best // BS not in order[:ksel]
    kth = np.sort(true)[-k]
    # ... by ten times the guard, and well inside one pass's 5e-3
    assert np.argmax(true) == best
    assert kth * (1 + 5e-4) < true[best] < kth * (1 + 5e-3)
    assert kth >= rounded[order[ksel]] * (1 + 1e-4)

    model = _model(shards, Y, np.stack([q] * 2))
    ((_, served),) = _answers(model, ["u0"])
    assert served[0]["id"] == f"i{best}"
    assert served[0]["value"] == pytest.approx(float(true[best]), rel=1e-6)
    assert model.twophase_fallbacks == 0
    if shards > 1:
        assert ShardedReference(model).check([("u0", served)], 10) == []
        assert model.sharded_windows == 1


def _dot_precisions(jaxpr, found=None):
    """The ``precision`` of every dot_general in a jaxpr, through the
    bodies of its scans, conds, calls, shard_maps and pallas_calls."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dot_precisions(sub, found)
    return found


def _phase_a_builds(dtype):
    """Every float build of the two-phase program the ladder can
    enqueue, traced (nothing is lowered: the CPU could not) over a toy
    store of ``dtype``: name -> its dots' precisions."""
    n, f, bs, k = 16384, 128, 128, 16
    ksel = sm._block_ksel(k, n, bs)
    Y = jnp.zeros((n, f), dtype)
    act = jnp.ones(n, bool)
    pen = jnp.zeros((n // bs, bs), jnp.float32)
    out = {}
    for b in (8, 128):      # rows on the lanes, and (rows, B)
        Q = jnp.zeros((b, f), jnp.float32)
        out[f"pallas-{b}"] = jax.make_jaxpr(
            lambda Y, Q: sm._batch_top_n_twophase_pallas(
                Y, Q, pen, act, None, np.int32(b), k, bs, ksel))(Y, Q)
    Q = jnp.zeros((8, f), jnp.float32)
    out["scan"] = jax.make_jaxpr(
        lambda Y, Q: sm._batch_top_n_twophase_kernel(
            Y, Q, act, None, np.int32(8), k, 1024, bs, ksel))(Y, Q)
    fold = 2
    Yf, pen_f = sm._fold_items_kernel(Y, act, fold, bs)
    out["fold"] = jax.make_jaxpr(
        lambda Y, Yf, Q: sm._batch_top_n_twophase_pallas_fold(
            Y, Yf, Q[:, :f // fold], pen_f, act, np.int32(8), k, bs, ksel,
            fold))(Y, Yf, Q)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("items",))
    plan = sm.ShardPlan(ksel, 1024, bs)
    for pallas in (False, True):
        prog = sd.build_program(mesh, "items", k, k, plan, pallas=pallas)
        out[f"sharded-{'pallas' if pallas else 'scan'}"] = jax.make_jaxpr(
            prog)(Y, act, Q, np.int32(8), *((pen,) if pallas else ()))
    return {name: _dot_precisions(j.jaxpr) for name, j in out.items()}


@pytest.mark.parametrize("build", ["pallas-8", "pallas-128", "scan", "fold",
                                   "sharded-scan", "sharded-pallas"])
def test_no_dot_of_a_float32_store_runs_at_the_default_precision(build):
    """Phase A's maxima, phase B's scores and the exact scan's: on a
    float32 store every product is a float32 product (the MXU's default
    rounds the operands to bfloat16, 5.4e-3 off on a block maximum
    where the certificate's guard is 1e-4), and a bfloat16 store's
    programs ask for nothing (their products are exact in one pass:
    what the accepted cells compiled is what they compile)."""
    highest = jax.lax.Precision.HIGHEST
    f32 = _phase_a_builds(jnp.float32)[build]
    assert len(f32) >= 2        # phase A's and phase B's at least
    for p in f32:
        assert p is not None and set(
            p if isinstance(p, tuple) else (p,)) == {highest}
    assert set(_phase_a_builds(jnp.bfloat16)[build]) == {None}


# -- capacity, warm-up, what the model reports ---------------------------------

def test_a_sharded_store_rounds_to_whole_chunks_on_every_shard(
        monkeypatch):
    """The 20M case in small: 3.25 chunks a shard (20,054,016 / 4 =
    5,013,504 = 38.25 x 131,072) would leave no shard splitting into
    whole streaming chunks; capacity rounds to devices x chunk."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    monkeypatch.setattr(fv, "_LARGE_ALIGN", 64)
    mesh = Mesh(np.array(jax.devices()[:4]), ("items",))
    store = fv.FeatureVectorStore(
        4, initial_capacity=16,
        device_sharding=NamedSharding(mesh, PartitionSpec("items", None)))
    n = 4 * 208                                   # 3.25 chunks a shard
    store.bulk_load([str(j) for j in range(n)],
                    np.ones((n, 4), np.float32))
    vecs, active = store.device_arrays()
    assert vecs.shape[0] == 4 * 4 * 64            # 4 whole chunks a shard
    assert {s.data.shape[0] for s in vecs.addressable_shards} == {256}
    assert int(np.asarray(active).sum()) == n
    # an unsharded store of that many rows rounds as it always has
    plain = fv.FeatureVectorStore(4, initial_capacity=16)
    plain.bulk_load([str(j) for j in range(n)], np.ones((n, 4), np.float32))
    assert plain.device_arrays()[0].shape[0] == 13 * 64
    # between one chunk and one chunk a device: whole chunks, split evenly
    small = fv.FeatureVectorStore(
        4, initial_capacity=16,
        device_sharding=NamedSharding(mesh, PartitionSpec("items", None)))
    small.bulk_load([str(j) for j in range(100)],
                    np.ones((100, 4), np.float32))
    assert small.device_arrays()[0].shape[0] == 128


class _Compiles:
    """Counts XLA compilations through jax's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


def test_a_warmed_sharded_model_compiles_nothing_on_its_first_requests(
        toy):
    """``warm_serving_kernels`` runs every window of the ladder through
    the SPMD program on the live mesh (the ahead-of-time tool cannot: it
    has no mesh), and that program holds its own exact-scan fallback."""
    Y, X = _factors(40, 2 * SHARD_ROWS)
    model = _model(2, Y, X)
    model.warm_serving_kernels(how_many=10, max_batch=256)
    route = dict(model.metrics()["kernel_route"])
    # the CPU lowers no pallas build, and the model says so as the
    # one-chip model does: the lax.scan phase A ran on every shard
    assert all(what.startswith("pallas B=")
               for what in route.pop("errors"))
    assert route == {"kind": "sharded_twophase", "shards": 2,
                     "capacity": 2 * SHARD_ROWS}
    compiles = _Compiles()
    for n in (1, 2, 8, 9, 33, 256):
        got = model.top_n_batch(10, X[np.arange(n) % USERS])
        assert [len(r) for r in got] == [10] * n
    assert compiles.n == 0
    # ... and a store too small for the two-phase scan says so
    Ys, Xs = _factors(41, 64)
    small = _model(2, Ys, Xs)
    assert small.refresh_route()["kind"] == "sharded_flat"
    assert len(small.top_n_batch(5, Xs[:3])[0]) == 5
    assert small.sharded_windows == 0


def test_the_ahead_of_time_tool_names_what_warms_a_sharded_ladder(caplog):
    """``deploy/warmup.py`` has no mesh and cannot warm the SPMD programs;
    its warning says what does."""
    import logging

    from oryx_tpu.common.config import from_dict
    from oryx_tpu.deploy.warmup import run_warmup

    with caplog.at_level(logging.WARNING, logger="oryx_tpu.deploy.warmup"):
        report = run_warmup(from_dict({"oryx.serving.api.item-shards": 2}),
                            [], [], [])
    assert report["sharded_not_warmed"] == 2 and report["compiled"] == []
    (said,) = [r.getMessage() for r in caplog.records
               if "item-shards=2" in r.getMessage()]
    assert "NOT warmed" in said and "warm_serving_kernels" in said


def test_the_sharded_drain_marks_the_phases_of_the_one_chip_drain(toy):
    Y, X = _factors(42, 4 * SHARD_ROWS)
    model = _model(4, Y, X)
    tracer = Tracer("serving", sample_ratio=1.0)
    batcher = TopNBatcher(pipeline=1, tracer=tracer)
    try:
        req = tracer.begin_request("serving.request")
        tracer._swap(None)
        jobs = [_Job(model, 5, X[j], {f"i{n}" for n in range(20)},
                     trace_ctx=(req.trace_id, req.span_id) if j == 0
                     else None) for j in range(9)]
        assert batcher._dispatch(jobs) == 9
    finally:
        batcher.close()
    spans = {s["name"]: s for s in tracer.spans_for(req.trace_id)}
    assert set(spans) == {"serving.queue_wait", "serving.device_execute",
                          "serving.prepare", "serving.scan",
                          "serving.decode",
                          # the scan's steps (PR 39): the sharded drain
                          # places its windows inside it
                          "serving.upload", "serving.launch",
                          "serving.device_wait", "serving.fetch"}
    assert {spans[s]["parent_id"] for s in (
        "serving.upload", "serving.launch", "serving.device_wait",
        "serving.fetch")} == {spans["serving.scan"]["span_id"]}
    assert spans["serving.prepare"]["attrs"] == {"rows": 9}
    assert spans["serving.scan"]["attrs"] == {
        "shards": 4, "k": 32, "ksel": 64, "windows": [32], "lane_rows": 0,
        "real_rows": 9, "phase_b_row_share": 28.125}
    assert spans["serving.decode"]["attrs"] == {"rows": 9}


@pytest.mark.parametrize("width", sm._WINDOW_LADDER)
def test_the_sharded_program_is_one_program_named_twophase(width, toy):
    """The device metrics find the scan by ``twophase`` in the program's
    name (benchmark/layers/kernel.twophase_ms.json); the exact scan a
    failed certificate runs is a branch INSIDE it, not a program."""
    Y, X = _factors(43, 2 * SHARD_ROWS)
    model = _model(2, Y, X)
    vecs, active = model.Y.device_arrays()
    plan = sm.shard_plan(vecs, 2, 16, width)
    assert plan == sm.ShardPlan(ksel=32, chunk=1024, bs=BS)
    prog = sd.build_program(model._mesh, "items", 16, 16, plan)
    text = prog.lower(vecs, active,
                      jnp.zeros((width, FEATURES), jnp.float32),
                      np.int32(width)).as_text()
    assert "@jit_sharded_twophase_top_k" in text[:200]
    assert text.count("func.func public") == 1
    assert "all_gather" in text and "stablehlo.case" in text \
        or "stablehlo.if" in text


def test_the_scorer_and_the_model_share_one_builder(toy):
    """``ShardedItemScorer`` reads the mesh and the store it was given:
    a shard large enough takes the two-phase body, by the same builder."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
    Y, X = _factors(44, 2 * SHARD_ROWS)
    ids = [f"i{j}" for j in range(len(Y))]
    scorer = sd.ShardedItemScorer(mesh, ids, Y, dtype="float32")
    got = scorer.top_n_batch(10, X[:3])
    want = _model(1, Y, X).top_n_batch(10, X[:3])
    assert got == want
    assert [key[0] for key in scorer._kernels._programs] == ["twophase"]
    small = sd.ShardedItemScorer(mesh, ids[:64], Y[:64], dtype="float32")
    assert len(small.top_n_batch(5, X[:1])[0]) == 5
    assert [key[0] for key in small._kernels._programs] == ["flat"]


# -- the benchmark's reader of what this path counts -----------------------------

def _obs(**kw):
    base = dict(spans=[], counters_start={}, counters_end={},
                batch_sizes=[], trace=None, store={}, peaks=None)
    return Observations(**dict(base, **kw))


def _reader(name):
    import importlib.util

    path = os.path.join(ROOT, "benchmark", "readers", name)
    spec = importlib.util.spec_from_file_location(name[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("start, end, sizes, want", [
    ({"shard_fallback_rows": 8}, {"shard_fallback_rows": 8}, [2, 2], 0.0),
    ({"shard_fallback_rows": 0}, {"shard_fallback_rows": 16}, [2] * 16,
     50.0),
    # a program without the counter (the parent): nothing to read
    ({}, {}, [2, 2], None),
    ({"shard_fallback_rows": 0}, {"shard_fallback_rows": 0}, [], None)])
def test_shard_fallback_share_is_the_counter_over_the_rows(start, end,
                                                           sizes, want):
    got = _reader("counter_share.py")(
        _obs(counters_start=start, counters_end=end, batch_sizes=sizes),
        {"counter": "shard_fallback_rows"})
    assert got == want
