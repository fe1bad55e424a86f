"""Distributed k-means: Lloyd iterations over a device mesh.

Reference counterpart: Spark MLlib KMeans.train invoked at
app/oryx-app-mllib/.../kmeans/KMeansUpdate.java:107-120, where each
iteration is a map (assign) + reduceByKey (per-cluster sums) shuffle
over executors.

TPU-native redesign: points are ROW-SHARDED over the mesh axis and
never move; centers are replicated.  Each Lloyd iteration is, per
device, one (n_local, k) distance matmul + one one-hot reduction
matmul (both MXU work), followed by a single psum of the (k, d) sums /
(k,) counts over ICI — the collective that replaces the shuffle.  The
whole iteration loop is a lax.scan inside one shard_map-ed jit, so a
full training run is a single device program.

Initialization (k-means|| / random) runs on host exactly like the
single-device trainer — it is a few tiny passes — and the resulting
centers are broadcast.
"""

from __future__ import annotations

import logging
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..app.kmeans.common import ClusterInfo, assign_points
from ..app.kmeans.trainer import (K_MEANS_PARALLEL, RANDOM, _init_parallel)
from ..common.rand import RandomManager

_log = logging.getLogger(__name__)

__all__ = ["make_lloyd_step", "train_kmeans_distributed"]


def make_lloyd_step(mesh: Mesh, k: int, iterations: int, axis: str = "d"):
    """Build the jitted distributed Lloyd program:
    (points_local, weights_local, centers0) -> (centers, cost).

    ``points``/``weights`` sharded on rows; centers replicated.
    Padding rows carry weight 0 and never influence sums or cost.
    """

    def _run(points, w, centers0):
        pp = jnp.sum(points * points, axis=1)

        def step(centers, _):
            d = (pp[:, None]
                 - 2.0 * jnp.matmul(points, centers.T,
                                    preferred_element_type=jnp.float32)
                 + jnp.sum(centers * centers, axis=1)[None, :])
            idx = jnp.argmin(d, axis=1)
            onehot = jax.nn.one_hot(idx, k, dtype=points.dtype) * w[:, None]
            counts = jax.lax.psum(jnp.sum(onehot, axis=0), axis)
            sums = jax.lax.psum(
                jnp.matmul(onehot.T, points,
                           preferred_element_type=jnp.float32), axis)
            new_centers = jnp.where(
                (counts > 0)[:, None],
                sums / jnp.maximum(counts, 1.0)[:, None], centers)
            cost = jax.lax.psum(
                jnp.sum(w * jnp.maximum(jnp.min(d, axis=1), 0.0)), axis)
            return new_centers, cost

        centers, costs = jax.lax.scan(step, centers0, None,
                                      length=iterations)
        return centers, costs[-1]

    sharded = shard_map(
        _run, mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=(P(), P()))
    return jax.jit(sharded)


def train_kmeans_distributed(points: np.ndarray, k: int, iterations: int,
                             mesh: Mesh, runs: int = 1,
                             initialization: str = K_MEANS_PARALLEL,
                             seed: int | None = None,
                             axis: str = "d") -> list[ClusterInfo]:
    """Multi-device drop-in for train_kmeans (same model semantics)."""
    points = np.asarray(points, dtype=np.float32)
    n = len(points)
    if k < 2:
        raise ValueError("k must be > 1")
    if n < k:
        raise ValueError(f"fewer points ({n}) than clusters ({k})")
    rng = np.random.default_rng(
        RandomManager.random_seed() if seed is None else seed)
    n_dev = mesh.devices.size
    n_pad = max(n_dev, ((n + n_dev - 1) // n_dev) * n_dev)
    padded = np.zeros((n_pad, points.shape[1]), dtype=np.float32)
    padded[:n] = points
    weights = np.zeros(n_pad, dtype=np.float32)
    weights[:n] = 1.0

    row = NamedSharding(mesh, P(axis))
    dev_points = jax.device_put(padded, row)
    dev_w = jax.device_put(weights, row)
    step = make_lloyd_step(mesh, k, iterations, axis)

    best_centers, best_cost = None, math.inf
    for run in range(max(1, runs)):
        if initialization == RANDOM:
            centers0 = points[rng.choice(n, size=k, replace=False)]
        elif initialization == K_MEANS_PARALLEL:
            centers0 = _init_parallel(points, k, rng)
        else:
            raise ValueError(
                f"unknown initialization strategy: {initialization}")
        centers, cost = jax.device_get(
            step(dev_points, dev_w, jnp.asarray(centers0)))
        _log.info("dist k-means run %d/%d cost %.4f", run + 1, runs, cost)
        if cost < best_cost:
            best_centers, best_cost = centers, float(cost)

    idx, _ = assign_points(points, best_centers)
    counts = np.bincount(idx, minlength=k)
    return [ClusterInfo(i, best_centers[i], max(1, int(counts[i])))
            for i in range(k)]
