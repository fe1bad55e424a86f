"""Distributed ALS: one jitted training step over a device mesh.

Reference counterpart: Spark MLlib's block-partitioned ALS, invoked at
app/oryx-app-mllib/.../als/ALSUpdate.java:141-152, where users x items
blocks are shuffled between executors each half-sweep.

TPU-native redesign (NOT a block-shuffle translation):
 - both factor matrices are ROW-SHARDED over the mesh axis "d"
   (X: users/d, Y: items/d) and live in HBM;
 - interactions are pre-blocked on host into a dense padded per-row
   layout (cols/vals/mask of shape (rows, P)), row-sharded the same way,
   so every device solves the normal equations for its own row block
   with ONE batched MXU matmul — static shapes, no per-row loop;
 - per half-sweep the opposite factor is all-gathered over ICI
   (lax.all_gather) and its Gramian is formed by psum of local partial
   Gramians (lax.psum) — these two collectives replace the Spark
   shuffle entirely;
 - the whole two-half-sweep step is a single shard_map-ed jitted
   program; run it `iterations` times.

This scales the memory of the blocked interaction layout and the solve
FLOPs linearly with devices; the all-gathered opposite factor is the
same replicate-the-smaller-side tradeoff MLlib makes with its block
broadcast.

Multi-host path (``mode="ring"``, the default when the mesh spans
processes): the all-gather + serialized psum become a **ring
half-sweep** — the opposite factor's row blocks rotate around the mesh
axis via ``lax.ppermute`` while each device accumulates the partial
normal equations for the interactions whose columns live in the
resident block (the interactions are pre-split per (row, owner-block)
on host, so total einsum slots stay ~P — no n_dev× FLOP blow-up).  The
Gramian accumulates per hop from the resident block, so the "psum" is
interleaved with — not serialized after — the per-row solve build, and
the full opposite factor is NEVER materialized on any device: peak
memory per half-sweep is one rotating block (rows/n_dev × k) instead
of the whole matrix.  Over DCN (multi-host) this is the difference
between overlapping each hop's transfer with a block's worth of MXU
work and stalling the whole step behind one all-gather.  Factor
buffers are donated to the jitted step (X/Y updated in place across
iterations) on backends that support donation.
"""

from __future__ import annotations

import math
import zlib
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..app.als.common import ParsedRatings
from ..app.als.trainer import (_BATCH_SLOT_BUDGET, _MAX_B, ALSModel,
                                _solve_batch)
from ..common.rand import RandomManager

__all__ = ["BlockedRatings", "block_ratings", "block_ratings_ring",
           "make_train_step", "train_als_distributed"]


class BlockedRatings(NamedTuple):
    """Dense padded per-row interaction blocks for both half-sweeps.

    Row counts are padded to a multiple of the mesh size; padding rows
    have all-zero masks and solve to zero-ish vectors that are sliced
    away at the end.
    """

    n_users: int          # true (unpadded) user count
    n_items: int          # true (unpadded) item count
    u_cols: np.ndarray    # (n_users_pad, Pu) int32 item index per slot
    u_vals: np.ndarray    # (n_users_pad, Pu) float32
    u_mask: np.ndarray    # (n_users_pad, Pu) float32 1.0 at real entries
    i_cols: np.ndarray    # (n_items_pad, Pi) int32 user index per slot
    i_vals: np.ndarray    # (n_items_pad, Pi) float32
    i_mask: np.ndarray    # (n_items_pad, Pi) float32


def _pad_rows(n: int, n_dev: int) -> int:
    return max(n_dev, ((n + n_dev - 1) // n_dev) * n_dev)


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


def _dense_block(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                 n_rows_pad: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    order = np.argsort(rows, kind="stable")
    rows, cols, vals = rows[order], cols[order], vals[order]
    counts = np.bincount(rows, minlength=n_rows_pad)
    p = 1 << max(0, int(counts.max(initial=1) - 1).bit_length())
    bcols = np.zeros((n_rows_pad, p), dtype=np.int32)
    bvals = np.zeros((n_rows_pad, p), dtype=np.float32)
    bmask = np.zeros((n_rows_pad, p), dtype=np.float32)
    slot = np.concatenate([np.arange(c) for c in counts if c > 0]) \
        if len(rows) else np.zeros(0, np.int64)
    bcols[rows, slot] = cols
    bvals[rows, slot] = vals
    bmask[rows, slot] = 1.0
    return bcols, bvals, bmask


def block_ratings(ratings: ParsedRatings, n_devices: int) -> BlockedRatings:
    """Build the device-blocked layout from aggregated COO interactions."""
    n_users = len(ratings.user_ids)
    n_items = len(ratings.item_ids)
    nu_pad = _pad_rows(n_users, n_devices)
    ni_pad = _pad_rows(n_items, n_devices)
    u_cols, u_vals, u_mask = _dense_block(
        ratings.users, ratings.items, ratings.values, nu_pad)
    i_cols, i_vals, i_mask = _dense_block(
        ratings.items, ratings.users, ratings.values, ni_pad)
    return BlockedRatings(n_users, n_items,
                          u_cols, u_vals, u_mask, i_cols, i_vals, i_mask)


def _owner_block(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                 n_rows_pad: int, block_rows: int, n_dev: int
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-(row, owner-block) padded layout for the ring half-sweep:
    slot (r, b, :) holds row r's interactions whose opposite index
    lives in block b, as LOCAL indices within the block.  Total real
    slots equal the dense layout's — the ring schedule then touches
    each interaction exactly once (at the hop its block is resident),
    so the per-row-solve FLOPs match the all-gather path instead of
    multiplying by n_dev."""
    owner = cols // block_rows
    key = rows.astype(np.int64) * n_dev + owner
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    counts = np.bincount(key_s, minlength=n_rows_pad * n_dev)
    p = _next_pow2(max(1, int(counts.max(initial=1))))
    # within-group slot index, vectorized (groups are contiguous in
    # key order)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(len(key_s), dtype=np.int64) - starts[key_s]
    bcols = np.zeros((n_rows_pad * n_dev, p), dtype=np.int32)
    bvals = np.zeros((n_rows_pad * n_dev, p), dtype=np.float32)
    bmask = np.zeros((n_rows_pad * n_dev, p), dtype=np.float32)
    bcols[key_s, slot] = (cols[order] - owner[order] * block_rows
                          ).astype(np.int32)
    bvals[key_s, slot] = vals[order]
    bmask[key_s, slot] = 1.0
    shape = (n_rows_pad, n_dev, p)
    return bcols.reshape(shape), bvals.reshape(shape), bmask.reshape(shape)


def block_ratings_ring(ratings: ParsedRatings,
                       n_devices: int) -> BlockedRatings:
    """The ring half-sweep's layout: same six arrays as
    :func:`block_ratings` but shaped ``(rows_pad, n_dev, P_block)`` —
    slab ``[:, b, :]`` is the interactions resolved while block ``b``
    of the opposite factor is resident on this device."""
    n_users = len(ratings.user_ids)
    n_items = len(ratings.item_ids)
    nu_pad = _pad_rows(n_users, n_devices)
    ni_pad = _pad_rows(n_items, n_devices)
    u = _owner_block(ratings.users, ratings.items, ratings.values,
                     nu_pad, ni_pad // n_devices, n_devices)
    i = _owner_block(ratings.items, ratings.users, ratings.values,
                     ni_pad, nu_pad // n_devices, n_devices)
    return BlockedRatings(n_users, n_items, *u, *i)


def make_train_step(mesh: Mesh, lam: float, alpha: float, implicit: bool,
                    axis: str = "d", mode: str = "gather",
                    donate: bool | None = None):
    """Build the jitted distributed step: (X, Y, blocks…) -> (X', Y').

    All array arguments are expected sharded with PartitionSpec((axis,))
    on their leading (row) dimension — blocks from :func:`block_ratings`
    for ``mode="gather"``, :func:`block_ratings_ring` for
    ``mode="ring"`` (the multi-host layout: per-row solves overlapped
    with the Gramian reduction, no materialized full opposite factor).

    ``donate`` donates the X/Y factor buffers to the step so iterations
    update HBM in place; None = donate wherever the backend supports it
    (CPU's donation is a no-op warning, so tests opt in explicitly).
    """
    n_dev = int(mesh.devices.size)

    def _half_gather(opposite_local, cols, vals, mask):
        # collectives: gather the opposite factor over ICI; Gramian by
        # psum of local partials (only needed for the implicit base term
        # but cheap either way, and it keeps one code path)
        full = jax.lax.all_gather(opposite_local, axis, axis=0, tiled=True)
        g_local = jnp.matmul(opposite_local.T, opposite_local,
                             preferred_element_type=jnp.float32)
        G = jax.lax.psum(g_local, axis)
        lam32, alpha32 = jnp.float32(lam), jnp.float32(alpha)

        def solve_row(row):
            # (_solve_batch pins rows without interactions — the mesh
            # padding — to zero, so they never poison the next
            # Gramian/gather)
            c, v, m = row
            return _solve_batch(full[c][None], v[None], m[None], G,
                                lam32, alpha32, implicit)[0]

        # the device's rows in slot-budgeted chunks, like the
        # single-device trainer's batches: one batched solve over ALL
        # local rows holds (rows, k, k) systems plus the LU's copies at
        # once — 15 GB of temporaries per device for 250k rows at k=50
        # (1M items over 4 chips; XLA:TPU memory analysis), i.e. an OOM
        # exactly where a mesh is needed
        chunk = max(1, min(_MAX_B, _BATCH_SLOT_BUDGET // cols.shape[1]))
        return jax.lax.map(solve_row, (cols, vals, mask),
                           batch_size=chunk)

    def _half_ring(opposite_local, cols_b, vals_b, mask_b):
        """One ring half-sweep: the opposite factor's blocks rotate via
        ppermute; each hop folds the resident block's interactions into
        the accumulating normal equations AND the Gramian, so the
        communication of hop t+1 overlaps the einsum of hop t (XLA
        async collectives) instead of the whole solve waiting on an
        all-gather + psum.  Padding slots carry zero mask/vals and
        clamp their gathers to row 0 — they contribute exact zeros,
        the same contract as the dense layout."""
        k = opposite_local.shape[1]
        d = jax.lax.axis_index(axis)
        rows_local = cols_b.shape[0]
        n_u = jnp.sum(mask_b, axis=(1, 2))
        A = jnp.zeros((rows_local, k, k), dtype=jnp.float32)
        b = jnp.zeros((rows_local, k), dtype=jnp.float32)
        G = jnp.zeros((k, k), dtype=jnp.float32)
        alpha32 = jnp.float32(alpha)
        block = opposite_local
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        for t in range(n_dev):
            # device d holds block (d - t) mod n_dev at hop t
            j = jax.lax.rem(d - t + n_dev, n_dev)
            cols = jnp.take(cols_b, j, axis=1)
            vals = jnp.take(vals_b, j, axis=1)
            mask = jnp.take(mask_b, j, axis=1)
            if implicit:
                w = alpha32 * jnp.abs(vals) * mask
                tt = (1.0 + w) * (vals > 0.0)
            else:
                w = mask
                tt = vals * mask
            Yg = block[cols]  # (rows_local, Pb, k)
            A = A + jnp.einsum("bpk,bpl->bkl", Yg * w[:, :, None], Yg,
                               preferred_element_type=jnp.float32)
            b = b + jnp.einsum("bpk,bp->bk", Yg, tt,
                               preferred_element_type=jnp.float32)
            if implicit:
                # the Gramian's block-j term, computed while block j is
                # HERE — the all-reduce dissolves into the ring
                G = G + jnp.matmul(block.T, block,
                                   preferred_element_type=jnp.float32)
            if t < n_dev - 1:
                block = jax.lax.ppermute(block, axis, perm)
        if implicit:
            A = A + G[None, :, :]
        A = A + (lam * jnp.maximum(n_u, 1.0))[:, None, None] * \
            jnp.eye(k, dtype=A.dtype)[None]
        x = jnp.linalg.solve(A, b[..., None])[..., 0]
        return jnp.where((n_u > 0.0)[:, None], x, 0.0)

    half = {"gather": _half_gather, "ring": _half_ring}[mode]

    def _step(X, Y, u_cols, u_vals, u_mask, i_cols, i_vals, i_mask):
        X = half(Y, u_cols, u_vals, u_mask)
        Y = half(X, i_cols, i_vals, i_mask)
        return X, Y

    spec = P(axis)
    sharded = shard_map(
        _step, mesh=mesh,
        in_specs=(spec,) * 8,
        out_specs=(spec, spec))
    if donate is None:
        donate = jax.default_backend() not in ("cpu",)
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())


def train_als_distributed(ratings: ParsedRatings, features: int, lam: float,
                          alpha: float, implicit: bool, iterations: int,
                          mesh: Mesh, seed: int | None = None,
                          axis: str = "d", mode: str = "auto",
                          donate: bool | None = None) -> ALSModel:
    """Full multi-device ALS training loop; returns host-side factors.

    ``mode``: "gather" (all_gather + psum — the single-host default),
    "ring" (ppermute ring with the Gramian reduction overlapped into
    the per-row-solve build — the multi-host path), or "auto" = ring
    exactly when the mesh spans processes (DCN hops are where the
    overlap pays; within one host's ICI the all-gather is cheap)."""
    n_dev = mesh.devices.size
    k = features
    if mode == "auto":
        mode = "ring" if jax.process_count() > 1 else "gather"
    if len(ratings.user_ids) == 0 or len(ratings.item_ids) == 0:
        return ALSModel(ratings.user_ids, ratings.item_ids,
                        np.zeros((0, k), np.float32),
                        np.zeros((0, k), np.float32))
    blocks = (block_ratings_ring(ratings, n_dev) if mode == "ring"
              else block_ratings(ratings, n_dev))

    if seed is None:
        if jax.process_count() > 1:
            # multi-controller SPMD: device_put of the init requires
            # the SAME host value on every process, and per-process RNG
            # state differs — derive the seed from the (identical by
            # contract) input instead
            seed = zlib.crc32(np.ascontiguousarray(
                ratings.values).tobytes()) & 0x7FFFFFFF
        else:
            seed = RandomManager.random_seed()
    rng = np.random.default_rng(seed)
    Y0 = (rng.standard_normal((blocks.i_cols.shape[0], k))
          / math.sqrt(k)).astype(np.float32)
    Y0[blocks.n_items:] = 0.0  # padding rows must not leak into the Gramian
    X0 = np.zeros((blocks.u_cols.shape[0], k), dtype=np.float32)

    row_sharding = NamedSharding(mesh, P(axis))
    put = partial(jax.device_put, device=row_sharding)
    X, Y = put(X0), put(Y0)
    args = tuple(put(a) for a in (blocks.u_cols, blocks.u_vals, blocks.u_mask,
                                  blocks.i_cols, blocks.i_vals, blocks.i_mask))
    step = make_train_step(mesh, lam, alpha, implicit, axis, mode=mode,
                           donate=donate)
    for _ in range(iterations):
        X, Y = step(X, Y, *args)
    if jax.process_count() > 1:
        # multi-host: a row-sharded factor is not fully addressable
        # from any one process; replicate (one all-gather each) so
        # every process fetches the complete model for PMML publish —
        # the analog of the reference collecting factors to the driver
        # (ALSUpdate.mfModelToPMML :430-473)
        rep = jax.jit(lambda a: a,
                      out_shardings=NamedSharding(mesh, P()))
        X, Y = rep(X), rep(Y)
    Xh = np.asarray(X)[:blocks.n_users]
    Yh = np.asarray(Y)[:blocks.n_items]
    return ALSModel(ratings.user_ids, ratings.item_ids, Xh, Yh)
