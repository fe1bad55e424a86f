"""The plain reference for ``/recommend`` over a ROW-SHARDED store, and
through ``als_reference.Reference`` the comparison that decides
``correct`` in the sharded cell.

Per shard, on the device that holds it, the blockwise float32 ``matmul``
at ``highest`` precision + ``top_k`` of ``als_reference.py`` over that
shard's rows as they lie on the device; the shards' candidate lists are
merged on the host in NumPy.  It shares nothing with the program's
sharded path: no ``shard_map``, no collective, no Pallas, no two-phase
selection, no certificate.  Also the reference of the tier-1 parity tests
(``tests/test_sharded_twophase.py``), on the virtual CPU mesh.
"""

from __future__ import annotations

import numpy as np

from benchmark.apps.als_reference import _BLOCK, Reference

# Served scores against the float32 reference when the served factors
# ARE float32 (the reference's own dtype, nothing reduced).  Both sides
# multiply in float32 (the program at Precision.HIGHEST, six bfloat16
# passes on the MXU) and accumulate in float32, so they differ by
# summation order: ~250 terms, measured 1e-6 relative at most.  A
# one-pass bfloat16 product of float32 factors (the MXU's default
# precision, relative error 2^-8 a product, 1.4e-3 measured on served
# scores) misses 2e-5 by two orders of magnitude, as a bfloat16
# accumulator does.  So the limits of ``als_reference`` hold as they
# are; this file states why for this dtype.


def _shards(array):
    """(first global row, the single-device array) of every shard, in
    row order."""
    parts = [(s.index[0].start or 0, s.data)
             for s in array.addressable_shards]
    return sorted(parts, key=lambda p: p[0])


class ShardedReference(Reference):
    """``Reference`` with its two device functions run shard by shard."""

    def __init__(self, model):
        super().__init__(model)
        self._plain_top_k, self._plain_scores = self._top_k, self._scores_of
        self._top_k, self._scores_of = self._merged_top_k, self._owned_scores

    def _arrays(self):
        Y, active = self.model.Y.device_arrays()
        rows = min(int(part.shape[0]) for _, part in _shards(Y))
        block = _BLOCK
        while rows % block:
            block //= 2
        return Y, active, block

    def _merged_top_k(self, Y, active, X, known, k: int, block: int):
        import jax

        X, known = np.asarray(X), np.asarray(known)
        found = []
        for (base, y), (_, a) in zip(_shards(Y), _shards(active)):
            dev = next(iter(y.devices()))
            mine = (known >= base) & (known < base + y.shape[0])
            local = np.where(mine, known - base, -1).astype(np.int32)
            s, i = self._plain_top_k(
                y, a, jax.device_put(X, dev), jax.device_put(local, dev),
                k, block)
            found.append((s, i, base))   # every shard enqueued, then read
        scores = np.concatenate([np.asarray(s) for s, _, _ in found], 1)
        rows = np.concatenate([np.asarray(i) + base
                               for _, i, base in found], 1)
        best = np.argsort(-scores, axis=1, kind="stable")[:, :k]
        return (np.take_along_axis(scores, best, 1),
                np.take_along_axis(rows, best, 1))

    def _owned_scores(self, Y, X, rows):
        import jax

        X, rows = np.asarray(X), np.asarray(rows)
        out = np.zeros(rows.shape, np.float32)
        for base, y in _shards(Y):
            dev = next(iter(y.devices()))
            mine = (rows >= base) & (rows < base + y.shape[0])
            local = np.where(mine, rows - base, 0).astype(np.int32)
            got = np.asarray(self._plain_scores(
                y, jax.device_put(X, dev), jax.device_put(local, dev)))
            out[mine] = got[mine]
        return out
