"""The plain reference for the write path of the ``als_lambda``
application: the fold-in of one interaction into a user and an item
vector, and the replay of an update-topic log.

(i) The fold-in is ``ALSUtils.computeTargetQui`` / ``computeUpdatedXu``
of the reference implementation (``SURVEY.md`` section 3.2) in NumPy
float64, one event at a time, with an LU solve against the Gramian: no
Cholesky factor, no batching, no padding, no JAX — nothing of
``oryx_tpu/ops/als_fold_in.py``.  The Gramians it solves against are
scanned here too (``gramian``): float32 row blocks at ``highest`` matmul
precision, summed in float64 on the host, not the program's one
``dot_general`` in storage precision.  (ii) ``replay`` applies a log of
``UP`` records in order to nothing but a dictionary.

The scan part (which items a user is recommended) is the accepted
``als_reference.Reference``; ``ScanReference`` adds the one thing the
in-window check needs of it, the reference's own rows.
"""

from __future__ import annotations

import json
import math

import numpy as np

from benchmark.apps.als_reference import Reference

# A folded-in float32 vector against the float64 reference, relative to
# the vector's largest component.  The device solves in float32 against
# a float32 Cholesky factor of a Gramian accumulated in float32: some
# 1e-6 relative at the 20M x 250 catalog (measured worst: PERF.md); a
# solve or a Gramian carried in bfloat16 (relative error 4e-3) misses
# this by more than an order of magnitude.
FOLD_RTOL = 1e-4


def target_qui(implicit: bool, value: float, current: float) -> float:
    """The strength the pair should have after the event; NaN: leave
    the vectors as they are."""
    if not implicit:
        return value
    if value > 0.0 and current < 1.0:
        return current + (value / (1.0 + value)) * (1.0 - max(0.0, current))
    if value < 0.0 and current > 0.0:
        return current + (value / (value - 1.0)) * -min(1.0, current)
    return math.nan


def fold_in(gram: np.ndarray, value: float, xu: np.ndarray | None,
            yi: np.ndarray | None, implicit: bool) -> np.ndarray | None:
    """The user vector after one interaction of strength ``value`` with
    the item whose vector is ``yi``, against ``gram`` = Y^T Y (swap the
    roles for the item side); None where the reference implementation
    returns null: no item vector, or a target that says no change."""
    if yi is None:
        return None
    yi = np.asarray(yi, np.float64)
    if xu is None:
        qui, current = 0.0, 0.5
    else:
        xu = np.asarray(xu, np.float64)
        qui = current = float(xu @ yi)
    target = target_qui(implicit, float(value), current)
    if math.isnan(target):
        return None
    d_xu = np.linalg.solve(np.asarray(gram, np.float64),
                           yi * (target - qui))
    return d_xu if xu is None else xu + d_xu


def aggregate(lines: list[str], implicit: bool) -> list[tuple]:
    """``user,item,strength`` input lines of one micro-batch to one
    (user, item, strength) each, in order of first appearance: implicit
    strengths add up and an empty one (a delete) wipes the pair;
    explicit, the last wins."""
    agg: dict = {}
    for line in lines:
        user, item, value = line.split(",")[:3]
        v = math.nan if value == "" else float(value)
        key = (user, item)
        agg[key] = agg[key] + v if implicit and key in agg else v
    return [(u, i, v) for (u, i), v in agg.items() if not math.isnan(v)]


def gramian(vecs, block: int = 1 << 16) -> np.ndarray:
    """V^T V of a device matrix (rows x stored features, any dtype):
    float32 blocks at ``highest`` precision, summed in float64 here."""
    import jax
    import jax.numpy as jnp

    rows = int(vecs.shape[0])
    while rows % block:
        block //= 2

    @jax.jit
    def part(v, start):
        b = jax.lax.dynamic_slice_in_dim(v, start, block) \
            .astype(jnp.float32)
        return jnp.matmul(b.T, b, precision=jax.lax.Precision.HIGHEST)

    total = np.zeros((int(vecs.shape[1]),) * 2, np.float64)
    pending = []
    for start in range(0, rows, block):
        pending.append(part(vecs, start))
        if len(pending) == 16:
            total += np.sum(np.asarray(jax.device_get(pending),
                                       np.float64), axis=0)
            pending = []
    if pending:
        total += np.sum(np.asarray(jax.device_get(pending), np.float64),
                        axis=0)
    return total


def parse_up(message: str):
    """(kind, id, float32 vector, other ids) of one ``UP`` record."""
    rec = json.loads(message)
    return (rec[0], str(rec[1]), np.asarray(rec[2], np.float32),
            [str(i) for i in rec[3]] if len(rec) > 3 else [])


def replay(messages) -> tuple[dict, dict]:
    """An update-topic log (``UP`` messages in order) applied to an
    empty dictionary: ({(kind, id): last float32 vector}, {user: ids it
    came to know})."""
    last: dict = {}
    known: dict = {}
    for message in messages:
        kind, id_, vector, others = parse_up(message)
        last[(kind, id_)] = vector
        if kind == "X":
            known.setdefault(id_, set()).update(others)
    return last, known


def stored(vector, dtype) -> np.ndarray:
    """What a store of ``dtype`` holds for ``vector``, as float32."""
    return np.asarray(vector, np.float32).astype(dtype).astype(np.float32)


def ulps_apart(a: np.ndarray, b: np.ndarray, dtype) -> int:
    """The largest distance between two stored vectors, in units in the
    last place of ``dtype`` (bfloat16 or float32)."""
    dtype = np.dtype(dtype)
    as_int = np.int16 if dtype.itemsize == 2 else np.int32
    ia = np.asarray(a).astype(dtype).view(as_int).astype(np.int64)
    ib = np.asarray(b).astype(dtype).view(as_int).astype(np.int64)
    # sign-magnitude to a monotonic integer line
    top = 1 << (8 * dtype.itemsize - 1)
    ia = np.where(ia < 0, -(ia + top), ia)
    ib = np.where(ib < 0, -(ib + top), ib)
    return int(np.max(np.abs(ia - ib))) if ia.size else 0


class ScanReference(Reference):
    """``als_reference.Reference`` with its own top rows handed out."""

    def top_rows(self, user_ids: list[str], how_many: int):
        """(reference scores, reference rows), one line a user, known
        items left out."""
        import jax

        Y, active, block = self._arrays()
        size = 32 if len(user_ids) <= 32 else 256
        scores, rows = [], []
        for start in range(0, len(user_ids), size):
            chunk = user_ids[start:start + size]
            pad = chunk + [chunk[-1]] * (size - len(chunk))
            X, known = self._queries(pad, int(Y.shape[1]), True)
            s, i = jax.device_get(
                self._top_k(Y, active, X, known, how_many, block))
            scores.append(s[:len(chunk)])
            rows.append(i[:len(chunk)])
        return np.concatenate(scores), np.concatenate(rows)
