"""Sharded serving scan: the item matrix row-sharded over a device
mesh, per-shard top-k, on-device merge.

Reference: the serving model partitions its item matrix into hash
partitions scanned by a thread pool with a streaming top-N merge
(PartitionedFeatureVectors.java:84-148, ALSServingModel.java:265-280).
The TPU-native analog scales the same way across CHIPS: rows of Y live
sharded over a 1-D mesh, every query's partial top-k is computed on the
shard that owns the rows, partials ride one all_gather over ICI, and
the merge happens on device — one jitted SPMD program a window, no host
fan-in.

What a shard does with its rows is the serving model's to say
(``serving_model.shard_candidates``; this module holds the mesh, the
gather and the merge): what the one-chip model does with all of them,
by the one-chip model's own rules read on ONE shard's row count
(``serving_model.shard_plan``): where the two-phase scan is admitted,
phase A's block maxima (the pallas kernel, or the ``lax.scan`` build
where that cannot lower), phase B's selection and rescoring, and the
exactness certificate; a shard whose certificate fails answers by the
exact scan over its own rows, inside the same program, while the others
wait at the gather.  Small stores (the tests' meshes) keep the flat
body: one matmul, a mask and ``top_k``.

This is the capacity story past a single chip's HBM, and the only way
to serve the reference's largest published catalog at the reference's
own float32: 20M x 250 x 4 B = 20 GB, 5.1 GB a chip over the four chips
of one v5e host (``oryx.serving.api.item-shards = 4``; the benchmark's
``als250-20m-f32-x4.two-callers`` cell).  What that layout measured on
the chips is in PERF.md, sections 5 and 6 (PR 34).
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..app.als import serving_model as sm
from ..app.als.feature_vectors import resolve_dtype

__all__ = ["ShardedItemScorer", "ShardKernelCache", "build_program"]


def build_program(mesh: Mesh, axis: str, k_shard: int, k_final: int,
                  plan: sm.ShardPlan | None = None, pallas: bool = False):
    """THE builder of the sharded top-k program, for the serving model
    and for :class:`ShardedItemScorer`: ``k_shard`` candidates leave
    each shard, by the flat body (``plan`` None) or by the two-phase
    scan, ride one all_gather, and ``k_final`` survive the merge, with
    GLOBAL row ids.  They are independent: a shard can never contribute
    more than its own row count, but the MERGED result may be wider
    than any one shard's candidate list (how_many > rows-per-shard).

    Returns a jitted ``(Y, active, Q) -> (scores, rows)`` for the flat
    body and ``(Y, active, Q, n_real[, penalty]) -> (scores, rows,
    certificates (shards, B))`` for the two-phase one, where ``n_real``
    (int32 scalar, traced, the same on every shard) says how many
    leading rows of ``Q`` are requests.  The two-phase program's name
    contains ``twophase``: the device metrics find it by that
    (tests/test_serving_spans.py)."""
    rows = P(axis, None)
    in_specs = (rows, P(axis), P(None, None)) \
        + (() if plan is None else (P(),)) + ((rows,) if pallas else ())
    out_specs = (P(None, None),) * (2 if plan is None else 3)

    # the all_gather-merged outputs ARE replicated, but the static
    # replication checker cannot infer that
    @partial(shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
             check_vma=False)
    def scorer(Y_local, active_local, Q, n_real=None, penalty=None):
        found = sm.shard_candidates(Y_local, active_local, Q, penalty,
                                    n_real, k_shard, plan)
        ls, li = found[:2]
        gi = li + jax.lax.axis_index(axis) * Y_local.shape[0]
        # partials from every shard: (n_dev, B, ks) -> (B, n_dev*ks)
        gathered = jax.lax.all_gather((ls, gi) + tuple(found[2:]), axis)
        b = Q.shape[0]
        gs = jnp.moveaxis(gathered[0], 0, 1).reshape(b, -1)
        gidx = jnp.moveaxis(gathered[1], 0, 1).reshape(b, -1)
        ms, sel = jax.lax.top_k(gs, k_final)
        mi = jnp.take_along_axis(gidx, sel, axis=1)
        return (ms, mi) + tuple(gathered[2:])

    def program(*args):
        return scorer(*args)

    # the device trace names a program after the jitted function
    program.__name__ = "sharded_flat_top_k" if plan is None \
        else "sharded_twophase_top_k"
    return jax.jit(program)


class ShardKernelCache:
    """The compiled SPMD programs of one mesh — the shard plan shared by
    :class:`ShardedItemScorer` and the serving model's configured
    sharded mode (``oryx.serving.api.item-shards``)."""

    def __init__(self, mesh: Mesh, axis: str = "d"):
        self.mesh = mesh
        self.axis = axis
        self.shards = int(mesh.devices.size)
        self._programs: dict[tuple, object] = {}
        self._counts: dict[int, jax.Array] = {}

    def _program(self, key: tuple, **build):
        prog = self._programs.get(key)
        if prog is None:
            prog = self._programs[key] = build_program(
                self.mesh, self.axis, **build)
        return prog

    def flat(self, Y, active, Q_dev, k: int):
        """(scores, global rows) of the merged per-shard top-k by the
        flat body; ``k`` is clamped to the global row count and each
        shard's contribution to its local rows."""
        k_shard = min(k, int(Y.shape[0]) // self.shards)
        k_final = min(k, k_shard * self.shards)
        return self._program(("flat", k_shard, k_final), k_shard=k_shard,
                             k_final=k_final)(Y, active, Q_dev)

    def twophase(self, Y, active, Q_dev, n_real, k: int, plan: tuple,
                 penalty=None):
        """(scores, global rows, certificates (shards, B)) by the
        two-phase scan on every shard, by ``plan`` (a ShardPlan or its
        (ksel, chunk, bs)); the first ``n_real`` rows of ``Q_dev`` are
        requests, the rest padding (an argument of the program: one
        program a (window, k) whatever it holds); phase A is the pallas
        kernel where its ``penalty`` (the row-sharded (N // bs, bs)
        mask) is given, else the lax.scan build."""
        plan, pallas = sm.ShardPlan(*plan), penalty is not None
        prog = self._program(("twophase", k, plan, pallas), k_shard=k,
                             k_final=k, plan=plan, pallas=pallas)
        return prog(Y, active, Q_dev, self.count(int(n_real)),
                    *((penalty,) if pallas else ()))

    def count(self, n: int):
        """``n`` as an int32 scalar replicated over the mesh, placed
        once.  Handed over as a host scalar it is uploaded to every
        device on every call, on the dispatching thread: 0.66 ms of a
        four-chip drain's host time (PERF.md section 6, PR 37)."""
        dev = self._counts.get(n)
        if dev is None:
            dev = self._counts[n] = jax.device_put(
                np.int32(n), NamedSharding(self.mesh, P()))
        return dev

    def top_k(self, Y, active, Q_dev, n_real: int, k: int):
        """The merged top-``k`` of the first ``n_real`` rows of
        ``Q_dev`` by whichever body ``shard_plan`` admits (the lax.scan
        phase A where it is the two-phase one): (scores, global rows),
        the certificates dropped — a shard that failed one has answered
        by its exact scan."""
        plan = sm.shard_plan(Y, self.shards, k, int(Q_dev.shape[0]))
        if plan is None:
            return self.flat(Y, active, Q_dev, k)
        return self.twophase(Y, active, Q_dev, n_real, k, plan)[:2]

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
        b_pad = sm._pad_k(n_req)
        if b_pad != n_req:
            Q = np.concatenate(
                [Q, np.zeros((b_pad - n_req, Q.shape[1]), np.float32)])
        scores, idx = jax.device_get(self._kernels.top_k(
            self._Y, self._active, self._kernels.replicate(Q), n_req,
            min(sm._pad_k(how_many), int(self._Y.shape[0]))))
        out: list[list[tuple[str, float]]] = []
        for b in range(n_req):
            row: list[tuple[str, float]] = []
            for s, i in zip(scores[b].tolist(), idx[b].tolist()):
                if s == float("-inf") or len(row) == how_many:
                    break
                row.append((self._ids[i], s))
            out.append(row)
        return out
