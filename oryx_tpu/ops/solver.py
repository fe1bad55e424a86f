"""Linear system solving with singularity detection.

Reference: framework/oryx-common/src/main/java/com/cloudera/oryx/common/
math/LinearSystemSolver.java:39 (RRQR decomposition with singularity
threshold = inf-norm * 1e-5, SingularMatrixSolverException carrying the
apparent rank) and Solver.java:25 (solveDToD/solveFToF).

TPU-native notes: the matrices here are k x k Gramians (X^T X, Y^T Y)
with k = feature count (tens to hundreds) — tiny by device standards.
Singularity is checked once on host via SVD (the honest analog of
rank-revealing QR); the factorization kept for solving is a Cholesky
factor resident on device, so the hot path — thousands of fold-in solves
per micro-batch — is a single batched triangular solve on the MXU rather
than one host solve per event.

Numerical rescue: MLlib factors in float64 (ALSUpdate.java:88-152) while
the device factor here is float32, so a Gramian that is marginally
positive-definite in f64 can come back NaN from the f32 Cholesky.
Rather than surface that as "singular" (narrowing the usable
hyperparameter region below the reference's), ``get_solver`` retries the
factorization in float64 on host and, when that succeeds, returns a
solver that solves in f64 — slower per call, but these are k x k systems
and the rescue path is the exception, not the rule.  Only a matrix the
f64 Cholesky also rejects raises SingularMatrixSolverException.
"""

from __future__ import annotations

import logging
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..resilience.faults import fire as _fault

_log = logging.getLogger(__name__)

__all__ = ["Solver", "SingularMatrixSolverException", "get_solver", "unpack_packed"]

_SINGULARITY_THRESHOLD_RATIO = 1.0e-5


class SingularMatrixSolverException(Exception):
    """Raised when the system matrix is near-singular
    (reference: SingularMatrixSolverException.java:22)."""

    def __init__(self, apparent_rank: int, message: str):
        super().__init__(message)
        self.apparent_rank = apparent_rank


@jax.jit
def _cho_solve_batch(chol: jax.Array, b: jax.Array) -> jax.Array:
    return jax.scipy.linalg.cho_solve((chol, True), b.T).T


class Solver:
    """Solves A x = b for a fixed symmetric positive-definite A.

    ``solve`` accepts a single right-hand side (k,) or a batch (n, k) and
    returns the same shape; the batch path is one fused device solve.

    ``precision`` is "float32" (device Cholesky, the fast path) or
    "float64" (host f64 Cholesky, the rescue path for Gramians whose f32
    factorization degenerates — see module docstring).
    """

    def __init__(self, chol: jax.Array, chol64: np.ndarray | None = None):
        # f64 rescue mode: chol64 is the host float64 lower factor and
        # is authoritative for solves; the device f32 factor is kept
        # (cast from f64, finite by construction) for batched kernels
        # that consume .cholesky directly.
        self._chol = chol
        self._chol64 = chol64

    @property
    def precision(self) -> str:
        return "float32" if self._chol64 is None else "float64"

    def _solve64(self, b) -> np.ndarray:
        """Host float64 solve against the rescue factor; shape-preserving."""
        import scipy.linalg
        b64 = np.asarray(b, dtype=np.float64)
        single = b64.ndim == 1
        if single:
            b64 = b64[None, :]
        x = scipy.linalg.cho_solve((self._chol64, True), b64.T).T
        return x[0] if single else x

    def solve(self, b) -> np.ndarray:
        if self._chol64 is not None:
            return self._solve64(b).astype(np.float32)
        b = jnp.asarray(b, dtype=jnp.float32)
        single = b.ndim == 1
        if single:
            b = b[None, :]
        x = _cho_solve_batch(self._chol, b)
        out = np.asarray(x)
        return out[0] if single else out

    # reference Solver.solveDToD / solveFToF parity names
    def solve_d_to_d(self, b) -> np.ndarray:
        if self._chol64 is not None:
            return self._solve64(b)
        return self.solve(np.asarray(b, dtype=np.float64)).astype(np.float64)

    def solve_f_to_f(self, b) -> np.ndarray:
        return self.solve(np.asarray(b, dtype=np.float32)).astype(np.float32)

    @property
    def cholesky(self) -> jax.Array:
        """Lower Cholesky factor, for device-side batched kernels."""
        return self._chol

    def __repr__(self):  # pragma: no cover
        return f"Solver(k={self._chol.shape[0]}, {self.precision})"


def unpack_packed(packed: np.ndarray) -> np.ndarray:
    """BLAS lower-triangular packed column-major -> full symmetric matrix
    (reference: LinearSystemSolver.getSolver(double[]) :39)."""
    packed = np.asarray(packed)
    dim = int(round((np.sqrt(8.0 * packed.size + 1.0) - 1.0) / 2.0))
    full = np.zeros((dim, dim), dtype=packed.dtype)
    offset = 0
    for col in range(dim):
        n = dim - col
        full[col:, col] = packed[offset:offset + n]
        full[col, col:] = packed[offset:offset + n]
        offset += n
    return full


def _smallest_singular_value_above(a: np.ndarray, threshold: float) -> bool:
    """True only where the smallest singular value of ``a`` is certainly
    above ``threshold``, found without the SVD; False means "not shown",
    and the caller takes the SVD for the exact answer.

    For the positive definite matrix S that ``a``'s lower triangle
    spells (a float64 Cholesky factorisation says whether it is one):
    the smallest eigenvalue is 1 / ||S^-1||_2 >= 1 / ||S^-1||_F, and the
    singular values of ``a`` lie within ||a - a^T||_F of S's (Weyl).
    A factorisation and an inverse: a third of the SVD's time at
    250 x 250, which a process that rebuilds two Gramian solvers every
    micro-batch beside its request threads pays 80 times in 40 s
    (PERF.md, PR 27).  A Gramian whose condition number is under some
    1e5 / sqrt(k) passes; anything else, and anything not positive
    definite, goes the long way as before."""
    if a.ndim != 2 or a.shape[0] != a.shape[1] or not a.size:
        return False
    try:
        np.linalg.cholesky(a)
        inv = np.linalg.inv(np.tril(a) + np.tril(a, -1).T)
    except np.linalg.LinAlgError:
        return False
    bound = 1.0 / float(np.linalg.norm(inv)) - float(np.linalg.norm(a - a.T))
    return bool(np.isfinite(bound) and bound > threshold)


def get_solver(a) -> Solver:
    """Build a Solver for symmetric A, raising SingularMatrixSolverException
    when A is near-singular (threshold = inf-norm * 1e-5, matching
    LinearSystemSolver.java's RRQR singularity test).

    ``a`` may be a full (k, k) matrix or a BLAS packed lower triangle.
    """
    a = np.asarray(a, dtype=np.float64)
    if a.ndim == 1:
        a = unpack_packed(a)
    # a Gramian built from NaN-poisoned factors must surface as a clean
    # solver failure, not a LinAlgError out of the SVD below
    if a.size and not np.all(np.isfinite(a)):
        raise SingularMatrixSolverException(
            0, f"{a.shape[0]} x {a.shape[1]} matrix has non-finite entries")
    # inf-norm (max absolute row sum), as commons-math RealMatrix.getNorm()
    inf_norm = float(np.max(np.sum(np.abs(a), axis=1))) if a.size else 0.0
    threshold = inf_norm * _SINGULARITY_THRESHOLD_RATIO
    apparent_rank = a.shape[0]
    if not _smallest_singular_value_above(a, threshold):
        svals = np.linalg.svd(a, compute_uv=False)
        apparent_rank = int(np.sum(
            svals > 0.01 * (svals[0] if svals.size else 0.0)))
        if svals.size == 0 or svals[-1] <= threshold:
            raise SingularMatrixSolverException(
                apparent_rank,
                f"{a.shape[0]} x {a.shape[1]} matrix is near-singular "
                f"(threshold {threshold}). Apparent rank: {apparent_rank}")
    chol = jnp.linalg.cholesky(jnp.asarray(a, dtype=jnp.float32))
    # chaos seam: discard the f32 factorization so tests can drive the
    # f64 rescue branch deterministically on a healthy matrix
    f32_ok = _fault("solver-f32-discard") != "drop" \
        and not bool(jnp.any(jnp.isnan(chol)))
    if f32_ok:
        return Solver(chol)
    # Cholesky silently yields NaN for indefinite A (symmetric but not
    # PD can still pass the SVD singularity gate) and for matrices whose
    # positive-definiteness does not survive the f32 downcast.  Retry in
    # float64 on host (MLlib's working precision); only a matrix f64
    # also rejects is truly not PD.
    try:
        chol64 = np.linalg.cholesky(a)
    except np.linalg.LinAlgError:
        raise SingularMatrixSolverException(
            apparent_rank,
            f"matrix is not positive definite; apparent rank: "
            f"{apparent_rank}") from None
    _log.warning("f32 Cholesky degenerated for %dx%d Gramian; rescued "
                 "with float64 host factorization", a.shape[0], a.shape[1])
    return Solver(jnp.asarray(chol64.astype(np.float32)), chol64=chol64)
