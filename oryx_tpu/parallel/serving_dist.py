"""Sharded serving scan: the item matrix row-sharded over a device
mesh, per-shard top-k, on-device merge.

Reference: the serving model partitions its item matrix into hash
partitions scanned by a thread pool with a streaming top-N merge
(PartitionedFeatureVectors.java:84-148, ALSServingModel.java:265-280).
The TPU-native analog scales the same way across CHIPS: rows of Y live
sharded over a 1-D mesh, every query's partial top-k is computed on the
shard that owns the rows, partials ride one all_gather over ICI, and
the merge happens on device — one jitted SPMD program, no host fan-in.

This is the capacity story past a single chip's HBM: a 40M x 250 bf16
item matrix (20 GB) serves from 2 chips, 160M items from 8.  The
single-chip serving model (app/als/serving_model.py) remains the
production path up to ~20M items; this scorer is the P4/P5 scale-out
the driver dry-runs on a virtual mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..app.als.feature_vectors import resolve_dtype
from ..app.als.serving_model import _pad_k, _q_cast, _score_precision

__all__ = ["ShardedItemScorer"]


def _make_kernel(mesh: Mesh, k_shard: int, k_final: int, axis: str):
    """``k_shard`` candidates leave each shard; ``k_final`` survive the
    merge.  They are independent: a shard can never contribute more
    than its own row count, but the MERGED result may be wider than any
    one shard's candidate list (how_many > rows-per-shard)."""
    @partial(shard_map, mesh=mesh,
             in_specs=(P(axis, None), P(axis), P(None, None)),
             out_specs=(P(None, None), P(None, None)),
             # the all_gather-merged outputs ARE replicated, but the
             # static replication checker cannot infer that
             check_vma=False)
    def scorer(Y_local, active_local, Q):
        n_local = Y_local.shape[0]
        # bf16 stores: keep the scan on the native bf16 MXU path
        # (serving_model._q_cast rationale)
        scores = jnp.matmul(_q_cast(Q, Y_local), Y_local.T,
                            preferred_element_type=jnp.float32,
                            precision=_score_precision(Y_local))
        scores = jnp.where(active_local[None, :], scores, -jnp.inf)
        ls, li = jax.lax.top_k(scores, k_shard)        # (B, ks) local
        gi = li + jax.lax.axis_index(axis) * n_local   # global row ids
        # partials from every shard: (n_dev, B, ks) -> (B, n_dev*ks)
        gs = jax.lax.all_gather(ls, axis)
        gidx = jax.lax.all_gather(gi, axis)
        b = Q.shape[0]
        gs = jnp.moveaxis(gs, 0, 1).reshape(b, -1)
        gidx = jnp.moveaxis(gidx, 0, 1).reshape(b, -1)
        ms, sel = jax.lax.top_k(gs, k_final)
        mi = jnp.take_along_axis(gidx, sel, axis=1)
        return ms, mi

    return jax.jit(scorer)


class ShardKernelCache:
    """Per-(k_shard, k_final) compiled SPMD merge kernels for one mesh —
    the shard plan shared by :class:`ShardedItemScorer` and the serving
    model's configured sharded mode (``oryx.serving.api.item-shards``)."""

    def __init__(self, mesh: Mesh, axis: str = "d"):
        self.mesh = mesh
        self.axis = axis
        self._kernels: dict[tuple[int, int], object] = {}

    def top_k(self, Y, active, Q_dev, k: int):
        """(scores, global_row_idx) of the merged per-shard top-k for a
        replicated query batch; ``k`` is clamped to the global row
        count and each shard's contribution to its local rows."""
        n_rows = int(Y.shape[0])
        n_local = n_rows // self.mesh.devices.size
        k_shard = min(k, n_local)
        k_final = min(k, k_shard * self.mesh.devices.size)
        kern = self._kernels.get((k_shard, k_final))
        if kern is None:
            kern = self._kernels[(k_shard, k_final)] = _make_kernel(
                self.mesh, k_shard, k_final, self.axis)
        return kern(Y, active, Q_dev)

    def replicate(self, Q: np.ndarray):
        return jax.device_put(
            Q, NamedSharding(self.mesh, P(None, None)))


class ShardedItemScorer:
    """Row-sharded item matrix + batched exact top-N over a mesh.

    Built from an id list and factor matrix (e.g. a MODEL publish);
    rows pad to a multiple of the mesh size with inactive entries, so
    every shard is identical in shape and the whole scan is one SPMD
    dispatch."""

    def __init__(self, mesh: Mesh, ids: Sequence[str], Y: np.ndarray,
                 dtype="bfloat16", axis: str = "d"):
        if len(ids) != len(Y):
            raise ValueError("one id per row required")
        self.mesh = mesh
        self.axis = axis
        self._ids = list(ids)
        n_dev = mesh.devices.size
        n = len(self._ids)
        n_pad = max(n_dev, ((n + n_dev - 1) // n_dev) * n_dev)
        dt = resolve_dtype(dtype)
        padded = np.zeros((n_pad, Y.shape[1]), dtype=dt)
        padded[:n] = np.asarray(Y).astype(dt)
        active = np.zeros(n_pad, dtype=bool)
        active[:n] = True
        row = NamedSharding(mesh, P(axis))
        self._Y = jax.device_put(padded, row)
        self._active = jax.device_put(active, row)
        self.features = int(Y.shape[1])
        self._kernels = ShardKernelCache(mesh, axis)

    def __len__(self) -> int:
        return len(self._ids)

    def memory_bytes_per_device(self) -> int:
        return (self._Y.nbytes + self._active.nbytes) \
            // self.mesh.devices.size

    @property
    def items(self) -> jax.Array:
        """The row-sharded item matrix as placed on the mesh."""
        return self._Y

    def top_n_batch(self, how_many: int,
                    queries: np.ndarray) -> list[list[tuple[str, float]]]:
        Q = np.asarray(queries, dtype=np.float32)
        if Q.ndim != 2 or Q.shape[1] != self.features:
            raise ValueError("queries must be (B, features)")
        n_req = Q.shape[0]
        if n_req == 0:
            return []
        b_pad = _pad_k(n_req)
        if b_pad != n_req:
            Q = np.concatenate(
                [Q, np.zeros((b_pad - n_req, Q.shape[1]), np.float32)])
        scores, idx = jax.device_get(self._kernels.top_k(
            self._Y, self._active, self._kernels.replicate(Q),
            min(_pad_k(how_many), int(self._Y.shape[0]))))
        out: list[list[tuple[str, float]]] = []
        for b in range(n_req):
            row: list[tuple[str, float]] = []
            for s, i in zip(scores[b].tolist(), idx[b].tolist()):
                if s == float("-inf") or len(row) == how_many:
                    break
                row.append((self._ids[i], s))
            out.append(row)
        return out
