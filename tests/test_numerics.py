"""Numerical fault tolerance (marker: numerics, in tier-1).

Three layers under test:
1. the f64 rescue ladder — solver-level (ops/solver.py) and
   trainer-level (app/als/trainer.py f32 -> f64 -> escalated lambda);
2. oracle parity — the TPU trainer must reach the in-tree float64
   NumPy ALS oracle's RMSE/AUC at equal hyperparams (the strongest
   available substitute for the MLlib side of the north-star gate);
3. the pre-publish validation gate — ml/mlupdate.py provably refuses
   to publish a model with non-finite factors or a non-finite eval.
"""

import os

import numpy as np
import pytest

from oryx_tpu.app.als.common import ParsedRatings
from oryx_tpu.app.als.evaluation import area_under_curve, rmse
from oryx_tpu.app.als.synthetic import synthesize_movielens
from oryx_tpu.app.als.trainer import train_als
from oryx_tpu.common import pmml as pmml_io
from oryx_tpu.common.config import from_dict
from oryx_tpu.kafka.api import KeyMessage
from oryx_tpu.kafka.inproc import InProcTopicProducer, get_broker
from oryx_tpu.ml.integrity import (ModelIntegrityError, check_finite_array,
                                   is_finite_array)
from oryx_tpu.ml.oracle import train_als_oracle
from oryx_tpu.ops.solver import SingularMatrixSolverException, get_solver
from oryx_tpu.resilience import faults

pytestmark = pytest.mark.numerics


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# -- integrity primitives ----------------------------------------------------

def test_is_finite_array():
    assert is_finite_array(np.zeros((3, 3), np.float32))
    assert is_finite_array(np.zeros((0, 4)))
    assert not is_finite_array(np.array([1.0, np.nan]))
    assert not is_finite_array(np.array([[np.inf]]))


def test_check_finite_array_raises_with_count():
    with pytest.raises(ModelIntegrityError, match="2 non-finite"):
        check_finite_array("X", np.array([1.0, np.nan, np.inf]))
    check_finite_array("ok", np.ones(4))  # no raise


# -- solver-level f64 rescue -------------------------------------------------

def test_solver_f64_rescue_solves_correctly():
    rng = np.random.default_rng(0)
    m = rng.standard_normal((12, 6))
    a = m.T @ m + 0.1 * np.eye(6)
    reference = get_solver(a)
    assert reference.precision == "float32"
    faults.inject("solver-f32-discard", mode="drop", times=1)
    rescued = get_solver(a)
    assert faults.fired("solver-f32-discard") == 1
    assert rescued.precision == "float64"
    b = rng.standard_normal((5, 6)).astype(np.float32)
    np.testing.assert_allclose(rescued.solve(b), reference.solve(b),
                               rtol=1e-4, atol=1e-5)
    # the device-facing factor stays finite and usable
    assert bool(np.all(np.isfinite(np.asarray(rescued.cholesky))))


def test_solver_marginally_conditioned_gramian_still_solves():
    """A Gramian just inside the singularity gate (condition ~5e4) must
    yield a working solver whichever precision path it takes."""
    q, _ = np.linalg.qr(np.random.default_rng(1).standard_normal((6, 6)))
    a = (q * np.array([1e4, 1e4, 1e4, 1e4, 1e4, 2e-1])) @ q.T
    a = (a + a.T) / 2.0
    s = get_solver(a)
    x = s.solve(np.ones(6, np.float32))
    resid = a @ x.astype(np.float64) - 1.0
    assert float(np.max(np.abs(resid))) < 1e-2


def test_solver_still_rejects_indefinite_and_nonfinite():
    with pytest.raises(SingularMatrixSolverException):
        get_solver(np.diag([1.0, -1.0, 1.0]))  # indefinite in f64 too
    with pytest.raises(SingularMatrixSolverException):
        get_solver(np.array([[np.nan, 0.0], [0.0, 1.0]]))


# -- trainer rescue ladder ---------------------------------------------------

def _ratings(n_u=60, n_i=40, nnz=800, seed=3, explicit=False):
    rng = np.random.default_rng(seed)
    users = rng.integers(0, n_u, nnz).astype(np.int32)
    items = rng.integers(0, n_i, nnz).astype(np.int32)
    vals = (np.clip(rng.normal(3.0, 1.0, nnz), 0.5, 5.0) if explicit
            else rng.exponential(1.0, nnz)).astype(np.float32)
    return ParsedRatings([f"u{u}" for u in range(n_u)],
                         [f"i{i}" for i in range(n_i)],
                         users, items, vals)


def test_trainer_rescue_produces_finite_equivalent_factors():
    ratings = _ratings()
    clean = train_als(ratings, 4, 0.01, 1.0, True, 3, seed=11)
    assert clean.rescue is None
    faults.inject("trainer-f32-poison", mode="drop", times=1)
    rescued = train_als(ratings, 4, 0.01, 1.0, True, 3, seed=11)
    assert faults.fired("trainer-f32-poison") == 1
    assert rescued.rescue is not None
    assert rescued.rescue["precision"] == "float64"
    assert rescued.rescue["escalated_lambda"] is None
    assert np.all(np.isfinite(rescued.X)) and np.all(np.isfinite(rescued.Y))
    # the f64 retrain optimizes the same objective from the same init:
    # factors match the healthy f32 run to f32 round-off
    np.testing.assert_allclose(rescued.X, clean.X, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(rescued.Y, clean.Y, rtol=1e-3, atol=1e-4)


def test_trainer_rescue_explicit_mode():
    ratings = _ratings(explicit=True)
    faults.inject("trainer-f32-poison", mode="drop", times=1)
    rescued = train_als(ratings, 4, 0.05, 1.0, False, 3, seed=11)
    assert rescued.rescue is not None
    assert np.all(np.isfinite(rescued.X)) and np.all(np.isfinite(rescued.Y))


# -- oracle parity (the north-star quality gate's runnable half) -------------

def _synthetic_100k(implicit: bool):
    users, items, imp_vals, exp_vals, _ = synthesize_movielens(
        n_users=1500, n_items=800, n_ratings=100_000, seed=7)
    vals = (imp_vals if implicit else exp_vals).astype(np.float32)
    n_users = int(users.max()) + 1
    n_items = int(items.max()) + 1
    # time-less random holdout: 10% test
    rng = np.random.default_rng(13)
    test_mask = rng.random(len(users)) < 0.1
    return (users, items, vals, n_users, n_items, test_mask)


def _trainer_factors(users, items, vals, n_users, n_items, k, lam, alpha,
                     implicit, iterations, seed):
    ratings = ParsedRatings([str(u) for u in range(n_users)],
                            [str(i) for i in range(n_items)],
                            users.astype(np.int32), items.astype(np.int32),
                            vals)
    model = train_als(ratings, k, lam, alpha, implicit, iterations,
                      seed=seed)
    assert model.rescue is None, "oracle-parity run should not need rescue"
    return model.X, model.Y


def test_oracle_parity_explicit_rmse_100k():
    users, items, vals, n_users, n_items, test_mask = _synthetic_100k(False)
    k, lam, alpha, iters = 12, 0.05, 1.0, 5
    tr_u, tr_i, tr_v = users[~test_mask], items[~test_mask], vals[~test_mask]
    te_u, te_i, te_v = users[test_mask], items[test_mask], vals[test_mask]

    X, Y = _trainer_factors(tr_u, tr_i, tr_v, n_users, n_items, k, lam,
                            alpha, False, iters, seed=5)
    oracle = train_als_oracle(tr_u, tr_i, tr_v, n_users, n_items, k, lam,
                              alpha, False, iters, seed=5)

    got = rmse(X, Y, te_u, te_i, te_v)
    want = rmse(oracle.X.astype(np.float32), oracle.Y.astype(np.float32),
                te_u, te_i, te_v)
    # equal-or-better within 5% relative: the trainer may not trail the
    # trusted f64 implementation at equal hyperparameters
    assert got <= want * 1.05, (got, want)


def test_oracle_parity_implicit_auc_100k():
    users, items, vals, n_users, n_items, test_mask = _synthetic_100k(True)
    k, lam, alpha, iters = 12, 0.01, 1.0, 5
    tr_u, tr_i, tr_v = users[~test_mask], items[~test_mask], vals[~test_mask]
    te_u, te_i = users[test_mask], items[test_mask]

    X, Y = _trainer_factors(tr_u, tr_i, tr_v, n_users, n_items, k, lam,
                            alpha, True, iters, seed=5)
    oracle = train_als_oracle(tr_u, tr_i, tr_v, n_users, n_items, k, lam,
                              alpha, True, iters, seed=5)

    got = area_under_curve(X, Y, te_u.astype(np.int32),
                           te_i.astype(np.int32))
    want = area_under_curve(oracle.X.astype(np.float32),
                            oracle.Y.astype(np.float32),
                            te_u.astype(np.int32), te_i.astype(np.int32))
    assert want > 0.6, f"oracle itself failed to learn (AUC {want})"
    assert got >= want - 0.03, (got, want)


def test_oracle_recovers_planted_structure_vs_unregularized_noise():
    """Sanity on the oracle itself: it must beat random factors by a
    wide margin on the planted-structure data, or parity with it means
    nothing."""
    users, items, vals, n_users, n_items, test_mask = _synthetic_100k(True)
    tr_u, tr_i, tr_v = users[~test_mask], items[~test_mask], vals[~test_mask]
    te_u, te_i = users[test_mask].astype(np.int32), \
        items[test_mask].astype(np.int32)
    oracle = train_als_oracle(tr_u, tr_i, tr_v, n_users, n_items, 12,
                              0.01, 1.0, True, 5, seed=5)
    rng = np.random.default_rng(0)
    rand_auc = area_under_curve(
        rng.standard_normal((n_users, 12)).astype(np.float32),
        rng.standard_normal((n_items, 12)).astype(np.float32), te_u, te_i)
    oracle_auc = area_under_curve(oracle.X.astype(np.float32),
                                  oracle.Y.astype(np.float32), te_u, te_i)
    assert oracle_auc > rand_auc + 0.15, (oracle_auc, rand_auc)


# -- pre-publish validation gate --------------------------------------------

def _als_cfg(**extra):
    overlay = {
        "oryx.als.implicit": False,
        "oryx.als.iterations": 2,
        "oryx.als.hyperparams.features": 3,
        "oryx.als.hyperparams.lambda": 0.1,
        "oryx.ml.eval.test-fraction": 0.1,
    }
    overlay.update(extra)
    return from_dict(overlay)


def _als_messages(n=300, seed=4):
    rng = np.random.default_rng(seed)
    t = 1_700_000_000_000
    msgs = []
    for j in range(n):
        u, i = rng.integers(0, 40), rng.integers(0, 25)
        msgs.append(KeyMessage(None, f"u{u},i{i},{rng.uniform(1, 5):.2f},"
                                     f"{t + j * 1000}"))
    return msgs


def test_mlupdate_refuses_to_publish_nonfinite_factors(tmp_path):
    """A candidate whose factor artifact carries NaN must never become
    the published generation, even when it is the only candidate."""
    from oryx_tpu.app.als.update import ALSUpdate, save_features

    class PoisonedALSUpdate(ALSUpdate):
        def build_model(self, train_data, hyper_parameters, candidate_path):
            doc = super().build_model(train_data, hyper_parameters,
                                      candidate_path)
            # corrupt the already-written Y artifact in place
            ids = [f"i{i}" for i in range(3)]
            bad = np.full((3, 3), np.nan, dtype=np.float32)
            save_features(os.path.join(candidate_path, "Y"), ids, bad)
            return doc

    update = PoisonedALSUpdate(_als_cfg())
    producer = InProcTopicProducer("memory://numerics-gate", "NumT1")
    model_dir = str(tmp_path / "model")
    update.run_update(0, _als_messages(), [], model_dir, producer)
    broker = get_broker("numerics-gate")
    msgs = list(broker.consume("NumT1", from_beginning=True,
                               max_idle_sec=0.1))
    assert msgs == [], "published a NaN model"
    assert [d for d in os.listdir(model_dir) if d.isdigit()] == []


def test_mlupdate_refuses_nonfinite_factors_even_with_eval_disabled(tmp_path):
    from oryx_tpu.app.als.update import ALSUpdate, save_features

    class PoisonedALSUpdate(ALSUpdate):
        def build_model(self, train_data, hyper_parameters, candidate_path):
            doc = super().build_model(train_data, hyper_parameters,
                                      candidate_path)
            ids = [f"i{i}" for i in range(3)]
            save_features(os.path.join(candidate_path, "Y"), ids,
                          np.full((3, 3), np.inf, dtype=np.float32))
            return doc

    update = PoisonedALSUpdate(_als_cfg(**{"oryx.ml.eval.test-fraction": 0.0}))
    model_dir = str(tmp_path / "model")
    update.run_update(0, _als_messages(), [], model_dir, None)
    assert [d for d in os.listdir(model_dir) if d.isdigit()] == []


def test_mlupdate_rejects_nonfinite_eval(tmp_path):
    """+Inf (or -Inf) eval is a degenerate metric: such a candidate may
    never outrank a real one."""
    from tests.test_ml import MockMLUpdate, _reset_mock

    _reset_mock([float("inf"), 0.4])
    cfg = from_dict({"oryx.ml.eval.candidates": 2,
                     "oryx.ml.eval.parallelism": 1})
    update = MockMLUpdate(cfg)
    producer = InProcTopicProducer("memory://numerics-gate", "NumT2")
    data = [KeyMessage(None, f"line{i}") for i in range(60)]
    update.run_update(0, data, [], str(tmp_path / "model"), producer)
    broker = get_broker("numerics-gate")
    msgs = list(broker.consume("NumT2", from_beginning=True,
                               max_idle_sec=0.1))
    assert len(msgs) == 1  # the finite candidate won; +Inf did not


def _run_sweep(tmp_path, features_grid=(20, 60),
               lambda_grid=(0.0005, 0.05)) -> dict:
    """``ALSUpdate.run_update``'s own candidate search over the
    reference's features x lambda grid (the lambda = 5e-4 half is the
    one f32 alone used to lose), on 3,000 synthetic explicit ratings as
    the CSV lines the batch layer hands it.  Returns every candidate's
    eval with the rescue rung that trained it, and what was published."""
    import json

    from oryx_tpu.app.als.update import ALSUpdate
    from oryx_tpu.ml.mlupdate import MODEL_FILE_NAME

    users, items, _, stars, _ = synthesize_movielens(
        n_users=150, n_items=80, n_ratings=3000, seed=7)
    # increasing timestamps: the train/test split is by time
    ts = 1_700_000_000_000
    msgs = [KeyMessage(None, f"{u},{i},{v:.2f},{ts + j}")
            for j, (u, i, v) in enumerate(zip(
                users.tolist(), items.tolist(),
                np.round(stars, 2).tolist()))]
    candidates: list[dict] = []

    def ext(doc, name):
        return pmml_io.get_extension_value(doc, name)

    class RecordingALSUpdate(ALSUpdate):
        def evaluate(self, model, candidate_path, test_data, train_data):
            e = super().evaluate(model, candidate_path, test_data,
                                 train_data)
            rescue = ext(model, "rescue")
            candidates.append({
                "features": int(ext(model, "features")),
                "lambda": float(ext(model, "lambda")),
                "eval": float(e),
                # None = clean f32, else {precision, trigger_iteration,
                # escalated_lambda}
                "rescue": json.loads(rescue) if rescue else None})
            return e

    n_candidates = len(features_grid) * len(lambda_grid)
    model_dir = str(tmp_path / "model")
    RecordingALSUpdate(_als_cfg(**{
        "oryx.als.hyperparams.features": list(features_grid),
        "oryx.als.hyperparams.lambda": list(lambda_grid),
        "oryx.ml.eval.candidates": n_candidates,
        "oryx.ml.eval.parallelism": 2,
    })).run_update(ts, msgs, [], model_dir, None)
    published = [d for d in os.listdir(model_dir) if d.isdigit()]
    assert len(published) == 1, published
    doc = pmml_io.read(os.path.join(model_dir, published[0],
                                    MODEL_FILE_NAME))
    finite = [c for c in candidates if np.isfinite(c["eval"])]
    best = max(finite, key=lambda c: c["eval"]) if finite else None
    rescued = [c for c in candidates if c["rescue"]]
    return {
        "candidates": candidates,
        "published_is_argmax": (
            best is not None and len(candidates) == n_candidates
            and int(ext(doc, "features")) == best["features"]
            and float(ext(doc, "lambda")) == best["lambda"]),
        "nan_candidates": len(candidates) - len(finite),
        # a candidate that never reached evaluate() (diverged beyond
        # rescue, or refused by the pre-publish gate) is as lost as a
        # NaN one
        "all_candidates_trained": len(finite) == n_candidates,
        "rescued_candidates": len(rescued),
        "rescues": {
            "float64": sum(1 for c in rescued
                           if c["rescue"].get("escalated_lambda") is None),
            "escalated_lambda": sum(
                1 for c in rescued
                if c["rescue"].get("escalated_lambda") is not None)},
    }


def test_sweep_records_rescue_and_gates_on_all_finite(tmp_path):
    """The candidate search carries per-candidate rescue records and
    the 0-NaN gate, at test scale over the reference's grid (including
    the lambda=5e-4 half that used to diverge)."""
    r = _run_sweep(tmp_path)
    assert r["published_is_argmax"]
    assert r["nan_candidates"] == 0 and r["all_candidates_trained"]
    assert len(r["candidates"]) == 4
    assert all("rescue" in c for c in r["candidates"])
    assert r["rescued_candidates"] == sum(
        1 for c in r["candidates"] if c["rescue"])


def test_sweep_poisoned_candidate_is_rescued_and_recorded(tmp_path):
    """One injected f32 divergence mid-sweep: the candidate retrains on
    the f64 rung, evaluates finite, and exactly one rescue is recorded
    — 0 NaN candidates either way."""
    faults.inject("trainer-f32-poison", mode="drop", times=1)
    r = _run_sweep(tmp_path)
    assert faults.fired("trainer-f32-poison") == 1
    assert r["nan_candidates"] == 0 and r["all_candidates_trained"]
    assert r["rescued_candidates"] == 1
    assert r["rescues"]["float64"] + r["rescues"]["escalated_lambda"] == 1
    assert r["published_is_argmax"]


def test_rescued_candidate_annotated_in_pmml(tmp_path):
    """End-to-end through ALSUpdate: a poisoned f32 factorization leads
    to a PUBLISHED, finite, rescue-annotated model — never a NaN one."""
    from oryx_tpu.app.als.update import ALSUpdate, load_features
    from oryx_tpu.ml.mlupdate import MODEL_FILE_NAME

    faults.inject("trainer-f32-poison", mode="drop", times=1)
    update = ALSUpdate(_als_cfg())
    model_dir = str(tmp_path / "model")
    update.run_update(0, _als_messages(), [], model_dir, None)
    published = [d for d in os.listdir(model_dir) if d.isdigit()]
    assert len(published) == 1
    doc = pmml_io.read(os.path.join(model_dir, published[0],
                                    MODEL_FILE_NAME))
    rescue = pmml_io.get_extension_value(doc, "rescue")
    assert rescue is not None and "float64" in rescue
    for side in ("X", "Y"):
        _, matrix = load_features(os.path.join(model_dir, published[0],
                                               side))
        assert matrix.size and np.all(np.isfinite(matrix))
