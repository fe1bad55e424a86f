"""Shared base for the ALS speed and serving in-memory models.

Both layers hold the same core state — X/Y factor stores, expected-ID
accounting for fraction-loaded gating, and cached Gramian solvers
(reference: ALSSpeedModel.java:40-183 and ALSServingModel.java:57-150
carry this same shape in parallel).  The serving model layers known
items, LSH, and top-N on top.
"""

from __future__ import annotations

import threading
from typing import Callable, Sequence

import numpy as np

from ...obs import trace as obstrace
from ...ops.solver import Solver, SingularMatrixSolverException, get_solver
from .feature_vectors import FeatureVectorStore

__all__ = ["FactorModelBase", "SolverCache"]


class SolverCache:
    """Async-refreshed cached solver over a Gramian supplier.

    Reference: app/oryx-app-common/src/main/java/com/cloudera/oryx/app/
    als/SolverCache.java:35-150 — dirty flag, single in-flight recompute,
    blocking first get, non-blocking maybe-stale get thereafter.
    """

    def __init__(self, vtv_supplier: Callable[[], np.ndarray],
                 what: str = ""):
        self._supplier = vtv_supplier
        # which Gramian this is ("X^T X", "Y^T Y"), for spans and logs
        self.what = what
        # solvers built, and why the last attempt gave none
        self.rebuilds = 0
        self.last_failure: str | None = None
        self._solver: Solver | None = None
        self._dirty = True
        self._in_flight = False
        self._cond = threading.Condition()

    def set_dirty(self) -> None:
        with self._cond:
            self._dirty = True

    def compute_now(self, parent=None) -> None:
        """``parent``: the span this rebuild is recorded under when it
        runs on a thread of its own (``compute_async``)."""
        with self._cond:
            if self._in_flight:
                # another thread is computing; wait for that attempt
                while self._in_flight:
                    self._cond.wait(60.0)
                return
            self._in_flight = True
            # clear BEFORE computing: a set_dirty that lands during the
            # solve re-marks it and the next get() recomputes, so updates
            # arriving mid-solve are never lost
            self._dirty = False
        solver = failure = None
        try:
            # one span per rebuild: the Gramian (a scan of the store the
            # first time, corrections for the rows written since after
            # it: FeatureVectorStore.vtv) and its factorisation
            with obstrace.phase("speed.gramian", parent=parent,
                                what=self.what):
                try:
                    solver = get_solver(self._supplier())
                except SingularMatrixSolverException as e:
                    failure = f"singular: {e}"
                except Exception as e:  # noqa: BLE001 — named, re-raised
                    failure = f"{type(e).__name__}: {e}"
                    raise
        finally:
            with self._cond:
                self.last_failure = failure
                if solver is not None:
                    self.rebuilds += 1
                    self._solver = solver
                self._in_flight = False
                self._cond.notify_all()

    def compute_async(self) -> None:
        with self._cond:
            if self._in_flight or not self._dirty:
                return
        # under the caller's span: the micro-batch that asked
        threading.Thread(target=self.compute_now,
                         args=(obstrace.open_span(),), daemon=True).start()

    def get(self, blocking: bool = True) -> Solver | None:
        """Current solver, recomputing synchronously when dirty and
        blocking.  Returns None when the Gramian is (still) singular —
        a completed-but-failed attempt does NOT block, but an attempt
        currently in flight is awaited (compute_now waits on it)."""
        with self._cond:
            needs_wait = self._dirty or (self._solver is None and self._in_flight)
        if needs_wait:
            if blocking:
                self.compute_now()
            else:
                self.compute_async()
        return self._solver


class FactorModelBase:
    """X/Y stores + expected-ID accounting + cached solvers."""

    def __init__(self, features: int, implicit: bool, dtype="float32",
                 item_sharding=None, resident: "FactorModelBase | None" = None):
        """``resident``: another model of this process whose state this
        one SHARES instead of holding its own — the speed model of a
        co-located speed + serving process folds in against the stores
        the serving model serves (one copy of the catalog on the chip,
        one host mirror, one pair of solver caches), and the serving
        layer's update consumer is the only writer."""
        self.features = features
        self.implicit = implicit
        self.resident = resident
        if resident is not None:
            if resident.features != features:
                raise ValueError(
                    f"the resident model has {resident.features} "
                    f"features, not {features}")
            self.X, self.Y = resident.X, resident.Y
            self._expected_user_ids = resident._expected_user_ids
            self._expected_item_ids = resident._expected_item_ids
            self._expected_lock = resident._expected_lock
            self.cached_xtx_solver = resident.cached_xtx_solver
            self.cached_yty_solver = resident.cached_yty_solver
            return
        self.X = FeatureVectorStore(features, dtype=dtype)
        # item matrix optionally row-sharded over a device mesh — the
        # serving capacity mode past one chip's HBM (P4/P5)
        self.Y = FeatureVectorStore(features, dtype=dtype,
                                    device_sharding=item_sharding)
        self._expected_user_ids: set[str] = set()
        self._expected_item_ids: set[str] = set()
        self._expected_lock = threading.Lock()
        self.cached_xtx_solver = SolverCache(self.X.vtv, "X^T X")
        self.cached_yty_solver = SolverCache(self.Y.vtv, "Y^T Y")

    # -- vectors ------------------------------------------------------------

    def get_user_vector(self, user_id: str) -> np.ndarray | None:
        return self.X.get_vector(user_id)

    def get_item_vector(self, item_id: str) -> np.ndarray | None:
        return self.Y.get_vector(item_id)

    def set_user_vector(self, user_id: str, vector: np.ndarray) -> None:
        self.X.set_vector(user_id, vector)
        self.cached_xtx_solver.set_dirty()
        with self._expected_lock:
            self._expected_user_ids.discard(user_id)

    def set_item_vector(self, item_id: str, vector: np.ndarray,
                        tag=None) -> None:
        self.Y.set_vector(item_id, vector, tag=tag)
        self.cached_yty_solver.set_dirty()
        with self._expected_lock:
            self._expected_item_ids.discard(item_id)

    # -- bulk artifact loads (sharded model distribution) -------------------

    def bulk_load_users(self, ids, matrix: np.ndarray) -> None:
        """set_user_vector for a whole artifact at once: one vectorized
        store write, one solver invalidation, one expected-ID sweep —
        the slice-load path (app/als/slices.py) that replaces the
        per-row UP replay."""
        self.X.bulk_load(list(ids), matrix)
        self.cached_xtx_solver.set_dirty()
        with self._expected_lock:
            self._expected_user_ids.difference_update(ids)

    def bulk_load_items(self, ids, matrix: np.ndarray) -> None:
        """set_item_vector for a whole slice at once (see
        bulk_load_users)."""
        self.Y.bulk_load(list(ids), matrix)
        self.cached_yty_solver.set_dirty()
        with self._expected_lock:
            self._expected_item_ids.difference_update(ids)

    # -- model swap ---------------------------------------------------------

    def set_expected_ids(self, user_ids: Sequence[str],
                         item_ids: Sequence[str]) -> None:
        """Record the ID universe of an incoming MODEL for fraction-loaded
        accounting (reference expected-ID logic, ALSServingModel.java:318-343).
        Also pre-sizes both stores for that universe: the UP replay that
        follows then fills rows in place instead of regrowing (a regrow
        re-uploads the whole device snapshot AND lands on an
        intermediate pow2 capacity the AOT warmup never compiled)."""
        with self._expected_lock:
            self._expected_user_ids = {u for u in user_ids if u not in self.X}
            self._expected_item_ids = {i for i in item_ids if i not in self.Y}
            # rows occupied by the PREVIOUS generation stay occupied
            # until the retain pass after replay, so the reservation
            # must cover current occupancy PLUS the not-yet-present
            # expected ids — sizing to the new universe alone could
            # still regrow mid-replay
            self.X.reserve(len(self.X) + len(self._expected_user_ids))
            self.Y.reserve(len(self.Y) + len(self._expected_item_ids))

    def retain_recent_and_user_ids(self, ids: Sequence[str]) -> None:
        self.X.retain_recent_and_ids(ids)
        self.cached_xtx_solver.set_dirty()

    def retain_recent_and_item_ids(self, ids: Sequence[str]) -> None:
        self.Y.retain_recent_and_ids(ids)
        self.cached_yty_solver.set_dirty()

    def get_fraction_loaded(self) -> float:
        if self.resident is not None:
            return self.resident.get_fraction_loaded()
        with self._expected_lock:
            expected = len(self._expected_user_ids) + len(self._expected_item_ids)
        loaded = len(self.X) + len(self.Y)
        total = loaded + expected
        return 1.0 if total == 0 else loaded / total

    # -- solvers ------------------------------------------------------------

    def precompute_solvers(self) -> None:
        self.cached_xtx_solver.compute_async()
        self.cached_yty_solver.compute_async()

    def get_xtx_solver(self, blocking: bool = True) -> Solver | None:
        return self.cached_xtx_solver.get(blocking)

    def get_yty_solver(self, blocking: bool = True) -> Solver | None:
        return self.cached_yty_solver.get(blocking)

    def user_count(self) -> int:
        return len(self.X)

    def item_count(self) -> int:
        return len(self.Y)
