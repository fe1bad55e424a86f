"""Alternating least squares on JAX — implicit (Hu/Koren/Volinsky, the
paper cited at reference ALSUpdate.java:60-68) and explicit variants.

Reference behavior being matched: app/oryx-app-mllib/.../als/ALSUpdate.java
:141-152 delegates to Spark MLlib ALS (rank/iterations/lambda/alpha,
implicit flag); this module is the TPU-native replacement for that
distributed factorizer.  Same objective as MLlib:

  implicit:  min Σ_ui c_ui (p_ui - x_u·y_i)^2 + λ Σ_u n_u|x_u|^2 + ...
             c = 1 + α|r|,  p = 1 if r > 0 else 0
  explicit:  min Σ_observed (r_ui - x_u·y_i)^2 + λ n_u |x_u|^2 + ...
  (ALS-WR λ scaling by per-row rating count, as MLlib does)

TPU-native design (NOT a translation of MLlib's block solver):
 - interactions live as COO on host, grouped into CSR by the side being
   solved; users are sorted by degree and packed into degree-bucketed
   batches padded to power-of-2 widths, so XLA sees a handful of static
   shapes and every solve is a large batched MXU matmul;
 - one jitted kernel builds all B normal-equation systems of a batch at
   once:  A_u = [G +] Yg_u^T diag(w_u) Yg_u + λ n_u I,  b_u = Yg_u^T t_u
   with Yg the (B,P,k) gathered factor rows, then a batched
   jnp.linalg.solve — there is no per-user host loop anywhere;
 - the Gramian G = Y^T Y (implicit-only base term) is one matmul per
   half-sweep.

The same kernel solves the item side by swapping roles.
"""

from __future__ import annotations

import logging
import math
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ...common.rand import RandomManager
from ...ml.integrity import NumericalDivergenceError
from ...resilience.faults import fire as _fault
from .common import ParsedRatings

_log = logging.getLogger(__name__)

__all__ = ["train_als", "rescue_retrain_f64", "ALSModel", "predict_pairs",
           "score_all_items"]

# max padded interaction slots (B*P) per solve batch; bounds peak memory
# of the (B, P, k) gather at ~slots*k*4 bytes
_BATCH_SLOT_BUDGET = 1 << 19
_MAX_B = 4096

# floor for the escalated-regularization rescue rung: an effectively
# unregularized candidate (lambda ~ 0) whose f64 systems are still
# singular gets at least this much
_RESCUE_MIN_LAMBDA = 1e-3


class ALSModel(NamedTuple):
    user_ids: list[str]
    item_ids: list[str]
    X: np.ndarray  # (n_users, k) float32
    Y: np.ndarray  # (n_items, k) float32
    # non-None when the f32 factorization diverged and a rescue rung
    # produced these factors instead: {"precision", "trigger_iteration",
    # "escalated_lambda"} — carried into the candidate's PMML so the
    # generation records HOW it trained, not just that it did
    rescue: dict | None = None


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


def _csr_by(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, n_rows: int):
    """Group COO by row: returns (order-sorted cols, vals, row_ptr)."""
    order = np.argsort(rows, kind="stable")
    sorted_rows = rows[order]
    counts = np.bincount(sorted_rows, minlength=n_rows)
    row_ptr = np.concatenate([[0], np.cumsum(counts)])
    return cols[order], vals[order], row_ptr, counts


def _plan_batches(counts: np.ndarray) -> list[np.ndarray]:
    """Pack row indices into degree-bucketed batches.

    Rows are sorted by degree descending; each batch's padded width P is
    its max degree rounded to a power of two, and batch size B is capped
    so B*P stays within the slot budget.  Every batch is emitted at
    EXACTLY its width's full B — the tail of a degree class pads with
    dummy row index len(counts) (scattered to a sacrificial extra row) —
    so each P value compiles the solve kernel once; arbitrary tail sizes
    would compile a fresh executable per tail.  Returns (row indices,
    padded width P) pairs; the indices may contain the dummy index.
    """
    n = len(counts)
    order = np.argsort(-counts, kind="stable")
    batches = []
    i = 0
    while i < n:
        p = _next_pow2(max(1, int(counts[order[i]])))
        b = max(1, min(_MAX_B, _BATCH_SLOT_BUDGET // p))
        batch = order[i:i + b]
        if len(batch) < b:
            batch = np.concatenate(
                [batch, np.full(b - len(batch), n, dtype=batch.dtype)])
        batches.append((batch, p))
        i += b
    return batches


@partial(jax.jit, static_argnames=("implicit",))
def _solve_batch(Yg, vals, mask, G, lam, alpha, implicit: bool):
    """Solve the batch's normal equations.

    Yg:   (B, P, k) gathered opposite-side factor rows (zeros at padding)
    vals: (B, P)    interaction strengths (zeros at padding)
    mask: (B, P)    1.0 at real interactions
    G:    (k, k)    Y^T Y, the implicit base term (ignored if explicit)
    """
    k = Yg.shape[-1]
    n_u = jnp.sum(mask, axis=1)  # per-row interaction count (ALS-WR reg)
    if implicit:
        w = alpha * jnp.abs(vals) * mask          # c - 1
        t = (1.0 + w) * (vals > 0.0)              # c * p
    else:
        w = mask
        t = vals * mask
    # A_u = [G +] Yg^T diag(w) Yg + lam * n_u * I   — one batched matmul
    Yw = Yg * w[:, :, None]
    A = jnp.einsum("bpk,bpl->bkl", Yw, Yg,
                   preferred_element_type=jnp.float32)
    if implicit:
        A = A + G[None, :, :]
    # rows with no interactions would make A singular in explicit mode
    # (A = 0); regularize them with a unit count and zero the solution —
    # MLlib simply has no such row, so a zero factor is the equivalent
    A = A + (lam * jnp.maximum(n_u, 1.0))[:, None, None] * \
        jnp.eye(k, dtype=A.dtype)[None]
    b = jnp.einsum("bpk,bp->bk", Yg, t, preferred_element_type=jnp.float32)
    x = jnp.linalg.solve(A, b[..., None])[..., 0]
    return jnp.where((n_u > 0)[:, None], x, 0.0)


@jax.jit
def _gramian(Y):
    return jnp.matmul(Y.T, Y, preferred_element_type=jnp.float32)


class _SidePlan(NamedTuple):
    """Device-resident packed batches for one half-sweep.

    The sparsity pattern is fixed for the whole factorization, so the
    degree-bucketed packing (and its device upload) happens ONCE and is
    reused by every iteration — the per-iteration work is pure compute.
    """

    n_rows: int
    # per batch: (device row indices (B,), device cols (B,P),
    #             vals (B,P), mask (B,P))
    batches: list[tuple[jax.Array, jax.Array, jax.Array, jax.Array]]


def _pack_side(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
               n_rows: int) -> _SidePlan:
    """CSR-group by row, then pack into padded batches with vectorized
    scatter (no per-row Python loop).  Dummy row indices (== n_rows,
    from tail padding) carry zero interactions and scatter to the
    sacrificial extra row of the output."""
    s_cols, s_vals, row_ptr, counts = _csr_by(rows, cols, vals, n_rows)
    counts_ext = np.concatenate([counts, [0]])     # dummy row: degree 0
    row_ptr_ext = np.concatenate([row_ptr, [row_ptr[-1]]])
    batches = []
    for batch_rows, p in _plan_batches(counts):
        bsz = len(batch_rows)
        c = counts_ext[batch_rows].astype(np.int64)
        total = int(c.sum())
        # flat source/destination index vectors for all real slots at once
        within = np.arange(total, dtype=np.int64) - np.repeat(
            np.cumsum(c) - c, c)
        src = np.repeat(row_ptr_ext[batch_rows], c) + within
        dst = np.repeat(np.arange(bsz, dtype=np.int64) * p, c) + within
        bcols = np.zeros(bsz * p, dtype=np.int32)
        bvals = np.zeros(bsz * p, dtype=np.float32)
        bmask = np.zeros(bsz * p, dtype=np.float32)
        bcols[dst] = s_cols[src]
        bvals[dst] = s_vals[src]
        bmask[dst] = 1.0
        batches.append((jnp.asarray(batch_rows.astype(np.int32)),
                        jnp.asarray(bcols.reshape(bsz, p)),
                        jnp.asarray(bvals.reshape(bsz, p)),
                        jnp.asarray(bmask.reshape(bsz, p))))
    return _SidePlan(n_rows, batches)


@partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(out, rows, x):
    # donating `out` lets XLA scatter in place instead of copying the
    # full factor matrix every batch
    return out.at[rows].set(x)


def _solve_side(opposite: jax.Array, plan: _SidePlan,
                k: int, lam: float, alpha: float,
                implicit: bool) -> jax.Array:
    """One half-sweep: solve every row's factor given the opposite side.

    Everything stays on device — batches async-dispatch back to back,
    and the returned factor feeds the next half-sweep's gathers directly
    (factors cross the host<->device boundary only when the caller
    materializes them).  A backstop window bounds how many (B, P, k)
    gather buffers can be live at once without any device->host
    transfer: block_until_ready on an old batch is a sync, not a copy.
    The bound is slot-based and GENEROUS (~32 × slot-budget × k × 4B ≈
    6.7 GB at k=100) because each sync costs a full host<->device round
    trip and measurably serializes the dispatch pipeline (a window of 8
    cost more wall-clock at ML20M scale than it saved in memory) — it
    exists to stop a pathological many-hundred-batch side from pinning
    unbounded HBM, not to engage at normal scales."""
    G = _gramian(opposite) if implicit else jnp.zeros((k, k), jnp.float32)
    lam32, alpha32 = jnp.float32(lam), jnp.float32(alpha)
    # one sacrificial extra row absorbs the scatters of dummy (tail
    # padding) batch indices; sliced off on return
    out = jnp.zeros((plan.n_rows + 1, k), dtype=jnp.float32)
    pending: list[tuple[int, jax.Array]] = []
    pending_slots = 0
    for batch_rows, bcols, bvals, bmask in plan.batches:
        Yg = opposite[bcols]
        x = _solve_batch(Yg, bvals, bmask, G, lam32, alpha32, implicit)
        out = _scatter_rows(out, batch_rows, x)
        slots = int(bcols.shape[0] * bcols.shape[1])
        pending.append((slots, x))
        pending_slots += slots
        while pending_slots > 32 * _BATCH_SLOT_BUDGET:
            done_slots, done_x = pending.pop(0)
            done_x.block_until_ready()
            pending_slots -= done_slots
    return out[:plan.n_rows]


def _solve_side_f64_host(opposite: np.ndarray, plan: _SidePlan,
                         k: int, lam: float, alpha: float,
                         implicit: bool) -> np.ndarray:
    """Host float64 half-sweep over the SAME packed batches as the
    device kernel — identical masking, ALS-WR scaling, and empty-row
    semantics, only the arithmetic precision differs.  This is the
    rescue precision: MLlib factors in f64 (ALSUpdate.java:88-152), so
    a candidate whose f32 normal equations degenerate gets retried
    here rather than reported as untrainable."""
    G = opposite.T @ opposite if implicit else None
    # same sacrificial extra row absorbing dummy (tail padding) indices
    out = np.zeros((plan.n_rows + 1, k), dtype=np.float64)
    eye = np.eye(k, dtype=np.float64)
    for batch_rows, bcols, bvals, bmask in plan.batches:
        rows = np.asarray(batch_rows)
        Yg = opposite[np.asarray(bcols)]            # (B, P, k) float64
        vals = np.asarray(bvals, dtype=np.float64)
        mask = np.asarray(bmask, dtype=np.float64)
        n_u = mask.sum(axis=1)
        if implicit:
            w = alpha * np.abs(vals) * mask
            t = (1.0 + w) * (vals > 0.0)
        else:
            w = mask
            t = vals * mask
        A = np.einsum("bpk,bpl->bkl", Yg * w[:, :, None], Yg)
        if implicit:
            A = A + G[None, :, :]
        A += (lam * np.maximum(n_u, 1.0))[:, None, None] * eye[None]
        b = np.einsum("bpk,bp->bk", Yg, t)
        x = np.linalg.solve(A, b[..., None])[..., 0]
        x[n_u == 0] = 0.0
        out[rows] = x
    return out[:plan.n_rows]


def _train_f64_host(user_plan: _SidePlan, item_plan: _SidePlan,
                    n_users: int, n_items: int, k: int, lam: float,
                    alpha: float, implicit: bool, iterations: int,
                    seed_val: int) -> tuple[np.ndarray, np.ndarray] | None:
    """Full float64 host retrain from the same seed/init; returns
    (X, Y) as float32, or None when even f64 diverges or hits an
    exactly singular system."""
    rng = np.random.default_rng(seed_val)
    Y = rng.standard_normal((n_items, k)) / math.sqrt(k)
    try:
        for _ in range(iterations):
            X = _solve_side_f64_host(Y, user_plan, k, lam, alpha, implicit)
            Y = _solve_side_f64_host(X, item_plan, k, lam, alpha, implicit)
    except np.linalg.LinAlgError:
        return None
    if not (np.all(np.isfinite(X)) and np.all(np.isfinite(Y))):
        return None
    return X.astype(np.float32), Y.astype(np.float32)


def _factors_finite(X: jax.Array, Y: jax.Array) -> bool:
    # NaN-propagating sums: two scalars cross the transport, not the
    # factor matrices
    return bool(jnp.isfinite(jnp.sum(X)) & jnp.isfinite(jnp.sum(Y)))


def _f64_ladder(user_plan: _SidePlan, item_plan: _SidePlan,
                n_users: int, n_items: int, k: int, lam: float,
                alpha: float, implicit: bool, iterations: int,
                seed_val: int, trigger_iteration: int | None
                ) -> tuple[np.ndarray, np.ndarray, dict]:
    """The f64 -> escalated-lambda rungs shared by train_als and the
    distributed trainer's rescue; returns (X, Y, rescue annotation) or
    raises NumericalDivergenceError when both rungs fail."""
    rescue = {"precision": "float64", "trigger_iteration": trigger_iteration,
              "escalated_lambda": None}
    factors = _train_f64_host(user_plan, item_plan, n_users, n_items, k,
                              lam, alpha, implicit, iterations, seed_val)
    if factors is None:
        lam_esc = max(lam * 10.0, _RESCUE_MIN_LAMBDA)
        _log.warning("float64 retrain also diverged; escalating "
                     "regularization lambda %g -> %g", lam, lam_esc)
        rescue["escalated_lambda"] = lam_esc
        factors = _train_f64_host(user_plan, item_plan, n_users, n_items,
                                  k, lam_esc, alpha, implicit, iterations,
                                  seed_val)
        if factors is None:
            raise NumericalDivergenceError(
                f"ALS diverged at every rescue rung (features={k} "
                f"lambda={lam}, escalated {lam_esc})")
    X_r, Y_r = factors
    _log.info("ALS float64 rescue succeeded (%s)", rescue)
    return X_r, Y_r, rescue


def rescue_retrain_f64(ratings: ParsedRatings, features: int, lam: float,
                       alpha: float, implicit: bool, iterations: int,
                       seed: int | None = None) -> ALSModel:
    """Standalone f64 rescue for factorization paths without an in-loop
    ladder (the distributed trainer): repack the interactions and run
    the f64 -> escalated-lambda rungs directly.  Returns a
    rescue-annotated ALSModel or raises NumericalDivergenceError."""
    n_users = len(ratings.user_ids)
    n_items = len(ratings.item_ids)
    user_plan = _pack_side(ratings.users, ratings.items, ratings.values,
                           n_users)
    item_plan = _pack_side(ratings.items, ratings.users, ratings.values,
                           n_items)
    seed_val = RandomManager.random_seed() if seed is None else seed
    X_r, Y_r, rescue = _f64_ladder(user_plan, item_plan, n_users, n_items,
                                   features, lam, alpha, implicit,
                                   iterations, seed_val,
                                   trigger_iteration=None)
    return ALSModel(ratings.user_ids, ratings.item_ids, X_r, Y_r,
                    rescue=rescue)


def train_als(ratings: ParsedRatings,
              features: int,
              lam: float,
              alpha: float,
              implicit: bool,
              iterations: int,
              seed: int | None = None,
              on_iteration: Callable[[int, np.ndarray, np.ndarray], None]
              | None = None) -> ALSModel:
    """Factor the interaction matrix into X (users) and Y (items).

    `on_iteration(i, X, Y)` fires after each full sweep — used by the
    bench harness for per-epoch timing/convergence traces.

    Numerical rescue ladder: the f32 device factorization is checked
    for divergence after every sweep; on NaN/Inf the candidate retrains
    in float64 on host (same seed and init), and if even f64 cannot
    train it, once more with escalated regularization.  The returned
    model's ``rescue`` field records the rung taken; only a candidate
    that exhausts the ladder raises NumericalDivergenceError.  This
    keeps the usable hyperparameter region as wide as the reference's
    f64 MLlib trainer instead of silently narrower.
    """
    n_users = len(ratings.user_ids)
    n_items = len(ratings.item_ids)
    k = features
    if n_users == 0 or n_items == 0:
        return ALSModel(ratings.user_ids, ratings.item_ids,
                        np.zeros((0, k), np.float32), np.zeros((0, k), np.float32))

    user_plan = _pack_side(ratings.users, ratings.items, ratings.values,
                           n_users)
    item_plan = _pack_side(ratings.items, ratings.users, ratings.values,
                           n_items)

    seed_val = RandomManager.random_seed() if seed is None else seed
    rng = np.random.default_rng(seed_val)
    # small random init, scaled like MLlib's (normalized gaussian / sqrt(k))
    Y = jnp.asarray(
        (rng.standard_normal((n_items, k)) / math.sqrt(k)).astype(np.float32))
    X = jnp.zeros((n_users, k), dtype=jnp.float32)

    diverged_at = None
    for it in range(iterations):
        # factors never leave the device between half-sweeps
        X = _solve_side(Y, user_plan, k, lam, alpha, implicit)
        Y = _solve_side(X, item_plan, k, lam, alpha, implicit)
        # chaos seam: poison this sweep's factors so tests drive the
        # rescue ladder deterministically on healthy data
        if _fault("trainer-f32-poison") == "drop":
            X = X.at[0, 0].set(jnp.nan)
        # one transport round trip per sweep — deliberate: divergence
        # typically appears within the first couple of sweeps, and
        # breaking early saves whole sweeps of NaN compute (and pins
        # trigger_iteration), worth far more than the RTT the
        # INFO-logging sync below was already paying in practice
        if not _factors_finite(X, Y):
            diverged_at = it
            break
        _log.info("ALS iteration %d/%d done", it + 1, iterations)
        if on_iteration is not None:
            on_iteration(it, np.asarray(X), np.asarray(Y))

    if diverged_at is None:
        return ALSModel(ratings.user_ids, ratings.item_ids,
                        np.asarray(X), np.asarray(Y))

    _log.warning("ALS f32 factorization diverged at iteration %d/%d "
                 "(features=%d lambda=%g); rescuing in float64",
                 diverged_at + 1, iterations, k, lam)
    X_r, Y_r, rescue = _f64_ladder(user_plan, item_plan, n_users, n_items,
                                   k, lam, alpha, implicit, iterations,
                                   seed_val, trigger_iteration=diverged_at)
    return ALSModel(ratings.user_ids, ratings.item_ids, X_r, Y_r,
                    rescue=rescue)


@jax.jit
def _predict_pairs_kernel(X, Y, users, items):
    return jnp.einsum("nk,nk->n", X[users], Y[items])


def predict_pairs(model_x: np.ndarray, model_y: np.ndarray,
                  users: np.ndarray, items: np.ndarray) -> np.ndarray:
    """Predicted strengths for (user, item) index pairs — one gather+dot."""
    return np.asarray(_predict_pairs_kernel(
        jnp.asarray(model_x), jnp.asarray(model_y),
        jnp.asarray(users), jnp.asarray(items)))


@jax.jit
def score_all_items(x_u, Y):
    """All-items scores for one or more users: the serving-side matmul."""
    return jnp.matmul(x_u, Y.T, preferred_element_type=jnp.float32)
