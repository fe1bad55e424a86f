"""The ALS serving model: factor matrices in device HBM, top-N as one
fused kernel.

Reference: app/oryx-app-serving/src/main/java/com/cloudera/oryx/app/
serving/als/model/ALSServingModel.java:57-422 — X single partition, Y
partitioned by LSH bucket with parallel partial top-N per partition and
a merge (:265-280); known-items map; expected-ID accounting for
getFractionLoaded; retainRecentAndUserIDs/ItemIDs MODEL-swap logic
(:318-383); TopNConsumer.java:30 (streaming top-N heap).

TPU-native redesign of the scan (P4/P5/P6 in SURVEY §2.14): instead of
a thread-pool scan over partitions, the WHOLE item matrix lives in one
device array and top-N is one program a window of requests: a flat
``matmul + top_k`` for a small catalog, for a large one the streaming
two-phase scan (phase A: one pass over the store that keeps only the
maxima of 128-row blocks; phase B: exact rescoring of the best blocks,
with a certificate).

With ``oryx.als.sample-rate`` < 1 the item store is LAID OUT by LSH
bucket, as the reference's PartitionedFeatureVectors is
(``FeatureVectorStore.partition_by``: every phase-A step of the store
holds rows of one bucket), and phase A's grid runs over the steps of the
buckets inside the Hamming balls of the window's queries only
(``_visit_plan``): pruning prunes bytes.  It is the configuration's
semantics, not a route the measurement may withdraw.  Until PR 36 the
candidates were a per-row mask over the whole store, which streamed the
exact scan's bytes and computed more, so the measured-cost router never
served it (PERF.md section 6, PR 36, has the chip's numbers).

When a rescorer plugin or an allowed-predicate is present the full score
vector is pulled to host and rescored exactly, preserving reference
semantics over speed.
"""

from __future__ import annotations

import contextlib
import logging
import math
import threading
from functools import partial
from typing import Callable, Iterable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ...api.serving import ServingModel
from ...common.lang import AutoReadWriteLock
from ...obs import trace as obstrace
from .factor_model import FactorModelBase, SolverCache  # noqa: F401 (re-export)
from .lsh import LocalitySensitiveHash, _popcount
from .rescorer import Rescorer

__all__ = ["ALSServingModel", "ShardPlan", "SolverCache", "shard_candidates",
           "shard_plan"]

_log = logging.getLogger(__name__)


def _pad_k(k: int) -> int:
    """Round requested top-N size up to a power of two so jitted top_k
    sees a handful of static shapes."""
    return 1 << max(3, (k - 1).bit_length())


# Above this many bytes of (B, N) score matrix, the batched kernel
# streams the item matrix in row chunks with a running top-k carry
# instead of materializing all scores: 1024 queries x 20M items would
# otherwise need an 80 GB buffer.  Chunk rows stay a power of two
# <= feature_vectors._LARGE_ALIGN so every store capacity (pow2 or
# multiple of 2^17) divides evenly.
_FLAT_SCORES_LIMIT = 1 << 30
_MAX_CHUNK_ROWS = 1 << 17

# The chunked path pads every request batch to a fixed window size and
# splits bigger drains into windows of it.  Streaming the item matrix
# from HBM dominates the dispatch up to roughly B = peak_flops /
# memory_bw (~240 on v5e), so the full window costs the same device
# time as pow2 buckets would — and the 20M x 250 scan kernel compiles
# once per LADDER size, not once per drain-size bucket.  The ladder's
# small windows exist for latency: the pass over the store costs the
# same at every width (PERF.md section 5, PR 33), but phase B selects,
# for every row of the WINDOW, ksel blocks out of the block maxima and
# sorts their scores, so an idle server's lone request on an 8-window
# pays a few ms less than on the full 256-window (VERDICT r04: the
# 50f/20M LSH cell's unloaded p50 lost to the baseline purely on window
# padding).  What moves bytes in proportion to rows, phase B's gather
# and rescoring of those blocks, runs over the rows that are REQUESTS
# (_rescores_requests): the zero rows a narrow window is padded with
# are free there.
_CHUNKED_BATCH = 256
_WINDOW_LADDER = (8, 32, 256)


def _window_sizes(n: int) -> list[int]:
    """Static window shapes covering an ``n``-query drain: full windows
    plus one ladder window that fits the tail."""
    out = [_CHUNKED_BATCH] * (n // _CHUNKED_BATCH)
    tail = n % _CHUNKED_BATCH
    if tail:
        out.append(next(w for w in _WINDOW_LADDER if w >= tail))
    return out


def _q_cast(Q, Y):
    """Match the query operand to a stored factor matrix: dtype and
    lane-padded width.  A mixed f32 x bf16 matmul promotes BOTH
    operands to f32 and runs at the MXU's f32 rate (~1/4 of bf16);
    casting the query keeps the scan on the native bf16 path with f32
    accumulation.  The store's device snapshot zero-pads features
    under 128 to the TPU's lane width (FeatureVectorStore.device_features
    — sub-width tiles measured ~2x slower); the query's trailing dim is
    zero-padded to match, which leaves every dot product bit-identical
    (0-column contributions are exactly 0 in the f32 accumulator)."""
    fp = Y.shape[-1]
    if Q.shape[-1] != fp:
        Q = jnp.pad(Q, [(0, 0)] * (Q.ndim - 1) + [(0, fp - Q.shape[-1])])
    return Q.astype(Y.dtype) if Y.dtype == jnp.bfloat16 else Q


def _score_precision(Y):
    """Matmul precision for kernels whose products become SERVED scores.
    On a TPU the MXU's default precision rounds float32 operands to
    bfloat16 (one pass, relative error ~2^-8 per product — measured
    1.4e-3 on /recommend scores at 50f/1M), which is a lower precision
    than a float32 store states; float32 stores therefore score at
    HIGHEST.  bfloat16 stores multiply exactly in one pass with float32
    accumulation either way, and the CPU backend ignores the setting.
    Phase A of the two-phase scan only SELECTS blocks, but its maxima
    are what the certificate holds the k-th served score against
    (_phase_b's ``m_guard``, 1e-4 relative), so on a float32 store
    it multiplies at HIGHEST too: a one-pass product's maxima lie up to
    5.4e-3 off (PERF.md section 5, PR 34), and a certificate that
    passed on those would prove nothing about an unselected block.
    The pass costs what the stream costs either way at the ladder's
    narrow windows (7.06 against 7.05 ms over 5.1M x 250 float32
    rows)."""
    return jax.lax.Precision.HIGHEST if Y.dtype == jnp.float32 else None


@jax.jit
def _dot_scores(Y, x):
    return jnp.matmul(Y, _q_cast(x, Y), preferred_element_type=jnp.float32,
                      precision=_score_precision(Y))


@jax.jit
def _cosine_mean_scores(Y, V):
    """Mean cosine similarity of each row of Y to each column vector in V
    (reference: CosineAverageFunction.java:25)."""
    if V.shape[0] != Y.shape[1]:  # lane-padded snapshot: pad V's rows
        V = jnp.pad(V, [(0, Y.shape[1] - V.shape[0]), (0, 0)])
    # bf16-stored factors: norms must accumulate in f32 like the dot
    # kernels do, or 250-term squared sums lose ~1% per item norm
    precision = _score_precision(Y)  # the STORE's dtype decides
    Y = Y.astype(jnp.float32)
    y_norm = jnp.linalg.norm(Y, axis=1, keepdims=True)
    v_norm = jnp.linalg.norm(V, axis=0, keepdims=True)
    denom = jnp.maximum(y_norm * v_norm, 1e-12)
    return jnp.mean(jnp.matmul(Y, V, preferred_element_type=jnp.float32,
                               precision=precision)
                    / denom, axis=1)


def _in_ball(buckets, target, max_bits: int):
    """The LSH candidate test: popcount(bucket XOR target) <= max_bits.
    The single definition every pruned window shares."""
    return _popcount(jnp.bitwise_xor(buckets, target)) <= max_bits


def _query_buckets(Q, hyperplanes):
    """LSH bucket id per query row, on device (no host round trip —
    matters when the device sits behind a high-latency transport).
    Delegates to the same kernel that bucketed the items, so query and
    item bucket ids can never drift apart."""
    from .lsh import _bucket_kernel
    return _bucket_kernel(Q, hyperplanes, int(hyperplanes.shape[0]))


class Pruning(NamedTuple):
    """What a pruned window's program needs beside the store (arrays;
    the Hamming radius rides as a static argument)."""

    step_bucket: jax.Array   # (steps,) int32: bucket of each store step
    step_live: jax.Array     # (steps,) int32: live rows of each step
    hyperplanes: jax.Array   # (hashes, features) float32


def _visit_plan(Q, prune: Pruning, n_real, max_bits: int):
    """Which steps of the store a window visits: the steps of every
    bucket inside the Hamming ball of some REAL query row's bucket (the
    first ``n_real`` rows, the scalar phase B reads too; padding rows
    add nothing).  Returns ``steps`` (every step of the store, the
    visited ones first, in store order), ``n_visit`` (how many are
    visited: phase A's grid bound), ``step_ok`` ((B, steps) in visit
    order: whether the step's bucket is a candidate of the row)
    and ``stats`` (int32 [steps visited, buckets in the union, live
    rows in them, live rows in the store])."""
    b = Q.shape[0]
    target = _query_buckets(Q, prune.hyperplanes)
    real = jnp.arange(b) < n_real
    # (B, buckets): the buckets inside each real row's ball ...
    n_buckets = 1 << int(prune.hyperplanes.shape[0])
    ball = _in_ball(jnp.arange(n_buckets, dtype=jnp.int32)[None, :],
                    target[:, None], max_bits) & real[:, None]
    # ... and (B, steps): the steps of those buckets (a step nobody has
    # yet carries bucket -1 and is nobody's candidate)
    sb = prune.step_bucket
    ok = jnp.take(ball, jnp.maximum(sb, 0), axis=1) & (sb >= 0)[None, :]
    visit = ok.any(0)
    n_visit = visit.sum(dtype=jnp.int32)
    steps = jnp.argsort(~visit, stable=True).astype(jnp.int32)
    step_ok = jnp.take(ok, steps, axis=1) \
        & (jnp.arange(steps.shape[0]) < n_visit)[None, :]
    stats = jnp.stack([n_visit, ball.any(0).sum(dtype=jnp.int32),
                       jnp.sum(jnp.where(visit, prune.step_live, 0),
                               dtype=jnp.int32),
                       prune.step_live.sum(dtype=jnp.int32)])
    return steps, n_visit, step_ok, stats


@partial(jax.jit, static_argnames=("k",))
def _batch_top_n_kernel(Y, Q, active, k: int):
    """Score a whole request batch in one device call: (B,k)·(N,k)^T ->
    masked top-k per row.  This is the serving-time request batcher's
    kernel (SURVEY §2.14 P6: Tomcat's 400-thread fan-out becomes one
    MXU matmul over the batched queries)."""
    scores = jnp.matmul(_q_cast(Q, Y), Y.T,
                        preferred_element_type=jnp.float32,
                        precision=_score_precision(Y))
    scores = jnp.where(active[None, :], scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


def _stream_plan(n_rows: int, b_pad: int) -> tuple[bool, int]:
    """(use_streaming_path, chunk_rows) for a batch of ``b_pad`` queries
    over ``n_rows`` items.  Stream whenever the item matrix is big —
    the flat path's lax.top_k over a (B, N) score tensor lowers to a
    per-row sort whose cost dwarfs the matmul (measured 18 ms vs ~1 ms
    of two-phase for a 256-window at 1M x 50f), and above ~0.5M rows
    every drain size also shares ONE compiled scan (the fixed
    _CHUNKED_BATCH shape) instead of compiling a multi-GB matmul per
    pow2 batch bucket."""
    chunk = _MAX_CHUNK_ROWS
    while chunk > 1024 and _CHUNKED_BATCH * chunk * 4 > _FLAT_SCORES_LIMIT:
        chunk //= 2
    big = (n_rows > (1 << 19)
           or b_pad * n_rows * 4 > _FLAT_SCORES_LIMIT)
    return big, chunk


# Two-phase streaming top-k tuning: 128-row blocks match the TPU's
# lane granularity (a block gather moves aligned ~13-64 KB slabs, not
# sub-tile rows).  What decides a certificate, in this order:
#
# 1. ``ksel`` against k.  The certificate passes when the k-th served
#    score is at least the best UNSELECTED block maximum.  Item rows
#    sit in arbitrary order, so the best k items lie in about k
#    different blocks and the (ksel+1)-th best block maximum is about
#    the (ksel+1)-th best item: with fewer blocks selected than rows
#    fetched it beats the k-th score and the certificate cannot hold
#    (ksel 32 at k = 64/128/256: 0 of 8 rows certified, the 20M cells'
#    23-27% "misses" of PERF.md PR 22/24, all of them fetches of
#    k >= 64).  With ksel >= k and an exact selection it cannot fail
#    but for the margin: at most k-1 blocks hold a better item.
# 2. The margin.  ``m_guard`` inflates the best unselected maximum by
#    a relative 1e-4 (below).  At ksel = k that maximum is about item
#    k+1, and a row whose k-th and (k+1)-th scores lie that close
#    fails (1 of 8 rows at k = ksel = 32; the "3-4% of rows at k <=
#    32").  At ksel = 2k it is about item 2k, some 3% under the k-th
#    score at 20M rows, and the margin never bites: _block_ksel.
# 3. Recall.  approx_max_k's ``recall_target`` is what a genuine miss
#    costs: at 0.999 over the 20M cells' 157k block maxima ~15% of
#    256-query windows had one row whose head block was really missed
#    (pallas kth 37.068 vs exact 37.223: a miss the certificate
#    caught, not rounding).  At 0.99999 the partial reduce keeps more
#    candidates than there are blocks, i.e. the selection is exact,
#    and still far cheaper than a lax.top_k over the maxima.  A head
#    block that IS missed stays missed however wide ksel is.
_BLOCK_ROWS = 128
_BLOCK_KSEL = 32
_APPROX_RECALL = 0.99999
# Phase B gathers (rows, ksel, bs, F) of the store's dtype.  A window
# from 128 rows on whose gather would pass this many bytes runs its
# rows in equal groups, one after the other inside the same program
# (_phase_b): a 256-wide window fetching k = 256 at 250f bfloat16 would
# otherwise ask for 8.4 GB beside a 10 GB store.  A narrower window
# gathers a request at a time (_rescores_requests), and a fetch whose
# ONE row would pass it is the exact scan's (_twophase_admits).  Of the
# order of _FLAT_SCORES_LIMIT, the other transient this module bounds.
_PHASE_B_GATHER_BYTES = 1 << 30


def _block_ksel(k: int, n_rows: int, bs: int) -> int:
    """How many ``bs``-row blocks phase B selects for a fetch of ``k``
    rows: twice the fetch (see the notes above: as many blocks as rows
    for the certificate to hold at all, twice for its margin never to
    bite), never under the floor ``_BLOCK_KSEL`` (k = 16 keeps 32) and
    always under the block count, since the certificate needs a block
    left unselected.  THE rule for every caller: the dispatch, the
    route measurement, the AOT warm-up, IVF's recall and the probe."""
    return min(max(_BLOCK_KSEL, 2 * k), n_rows // bs - 1)


def _row_bytes(Y) -> int:
    """Bytes of one stored row of ``Y`` (an array or its aval)."""
    return int(Y.shape[1]) * Y.dtype.itemsize


def _twophase_admits(k: int, ksel: int, Y, bs: int) -> bool:
    """Whether the two-phase program may answer a fetch of ``k`` from
    the store ``Y`` (an array or its aval): the store splits into whole
    blocks, the selection is at least as wide as the fetch (where the
    block count capped it under k the certificate is certain to fail,
    and the exact scan would run AFTER a two-phase program paid for
    nothing), and one query row's gather fits the budget.  Otherwise
    the exact scan is the primary path."""
    n_rows = int(Y.shape[0])
    return (n_rows % bs == 0 and k <= ksel < n_rows // bs
            and ksel * bs * _row_bytes(Y) <= _PHASE_B_GATHER_BYTES)


def _phase_b_group_rows(b: int, ksel: int, bs: int,
                        row_bytes: int) -> int:
    """Query rows a window from 128 rows on rescores at once: the
    largest divisor of its ``b`` rows whose (rows, ksel, bs, F) gather
    stays inside ``_PHASE_B_GATHER_BYTES`` (one row at least)."""
    fit = max(1, _PHASE_B_GATHER_BYTES // (ksel * bs * row_bytes))
    return next(g for g in range(min(b, fit), 0, -1) if b % g == 0)


def _map_row_groups(fn, g: int, *xs):
    """``fn`` over equal groups of ``g`` leading rows of every ``x``,
    one group after the other inside the program (lax.map), its
    results rejoined along the rows; ``fn(*xs)`` itself where one
    group holds them all."""
    b = xs[0].shape[0]
    if g == b:
        return fn(*xs)
    out = jax.lax.map(lambda x: fn(*x), tuple(
        x.reshape(b // g, g, *x.shape[1:]) for x in xs))
    return jax.tree.map(lambda o: o.reshape(b, *o.shape[2:]), out)


def _rescores_requests(b: int) -> bool:
    """Whether phase B gathers and rescores blocks for the REQUESTS of
    a ``b``-row window only, a row an iteration of a loop whose bound
    (``n_real``) the device reads: every window narrower than a lane
    tile, the ladder's 8 and 32, which a drain of one to 32 requests
    fills with zero rows.  Two callers on an 8-wide window were paying
    for eight rows' blocks, at k = 256 and 250f bfloat16 a 262 MB
    gather: whole programs over 20M rows on a v5e, 16.07 ms by one
    gather of the window, 14.99 by its two requests (a 5.1M-row float32
    shard: 10.16 and 8.01; PERF.md section 6, PR 38).  From 128 rows on
    a window is as good as full (a drain of over 32 requests), and its
    phase B stays the batched one, operation for operation: it is what
    the route measurement times."""
    return b < 128


def _selects_row_major(b: int, ksel: int) -> bool:
    """Whether phase B pins the block maxima to the row-major layout
    before it selects from them.  The builds that write them as
    (blocks, B) — the fold and int8 mirrors, and the pallas build from
    128 queries on — hand over a transposed view, and XLA hands that
    array to the selection's TopK as it lies: the query rows on the
    128 lanes, of which an 8-wide window fills 8, at a cost in
    proportion to ``ksel``.  Whole 8-wide programs at 250f x 20M on a
    v5e (PERF.md PR 26): 18.1 / 22.1 / 30.8 ms at k = 32 / 64 / 128 as
    it lies, 14.2 / 14.6 / 15.6 ms pinned (XLA makes the copy itself
    at ksel 512, either way 16.0).  From 128 rows on the lanes are full
    and the pin changes nothing (256 x k = 64, two groups of 128: 45.8
    ms both ways).  The floor width is not pinned: for those builds it
    keeps the layout it has had since these programs were first
    measured.  The pallas build's narrow windows need none of this
    since PR 33: their maxima arrive row-major (_scores_rows_on_lanes),
    the pin finds nothing to move, and the k = 16 program, which the
    missing pin held at 15.99 ms, runs in 14.0 (PERF.md section 5)."""
    return b < 128 and ksel > _BLOCK_KSEL


def _phase_b(Y, Qc, active, M, n_real, k: int, bs: int, ksel: int,
             steps=None):
    """Phase B shared by the scan- and pallas-built phase A: pick the
    ``ksel`` best 128-row blocks per query from the block maxima ``M``
    with approx_max_k, exactly rescore the gathered rows, and emit
    top-k plus the exactness certificate kth_score >= max(unselected
    block maxima).

    ``n_real`` (a traced int32 scalar: one program a (window, k),
    whatever the window holds) says how many leading rows of the window
    are requests; the dispatch appends a window's padding behind them.
    A narrow window (_rescores_requests) gathers and rescores blocks
    for those rows only (_phase_b_rows).  From 128 rows on the count is
    not looked at: every row is rescored, and a window too wide for one
    gather (_phase_b_group_rows) runs in equal row groups under
    lax.map: static shapes, one program, rows independent of each
    other.

    ``steps`` of None: ``M`` holds every block of the store in store
    order.  A pruned window (_visit_plan) hands its maxima over in
    VISIT order, a step's blocks side by side, -inf wherever a block is
    no candidate of the row, and ``steps`` maps them back to the store:
    selection, rescoring and the certificate then run over the visited
    blocks only."""
    if _rescores_requests(Qc.shape[0]):
        return _phase_b_rows(Y, Qc, active, M, n_real, k, bs, ksel, steps)
    g = _phase_b_group_rows(Qc.shape[0], ksel, bs, _row_bytes(Y))
    return _map_row_groups(
        lambda q, m: _phase_b_rows(Y, q, active, m, None, k, bs, ksel,
                                   steps),
        g, Qc, M)


def _phase_b_rows(Y, Qc, active, M, n_real, k: int, bs: int, ksel: int,
                  steps):
    """Phase B for one group of query rows: a whole narrow window, of
    which the first ``n_real`` rows are requests, or with ``n_real`` of
    None one gather's worth of a wide one, every row rescored.

    What reads the maxima or sorts scores (the selection, the best
    unselected maximum, the final top_k) runs once over the group's
    rows either way: a TopK costs over one row what it costs over eight
    sublanes.  What moves bytes in proportion to rows (_rescored: the
    gather of a row's ``ksel`` blocks, their products with the row, the
    mask) runs under ``n_real`` a request an iteration, and a row
    behind the requests returns -inf scores, row 0 and a certificate of
    True: nobody decodes it, and it must neither send a window to the
    exact scan nor be counted as a fallback."""
    b = Qc.shape[0]
    if _selects_row_major(b, ksel):
        M = with_layout_constraint(M, Layout(major_to_minor=(0, 1)))
    m_sel, bi = jax.lax.approx_max_k(M, ksel, recall_target=_APPROX_RECALL)
    m_rest = M.at[jnp.arange(b)[:, None], bi].set(-jnp.inf).max(-1)
    if steps is not None:
        # visited block -> the store's block
        per_step = M.shape[1] // steps.shape[0]
        bi = jnp.take(steps, bi // per_step) * per_step + bi % per_step
    # fewer candidate blocks than ``ksel`` (a pruned window): the
    # selection filled up with blocks outside the row's ball, whose rows
    # are no answer
    cand = m_sel > -jnp.inf if steps is not None else None
    if n_real is None:
        scores = _rescored(Y, Qc, active, bi, cand, bs)
    else:
        # a request an iteration, its blocks as a batch of two halves:
        # the same products and sums as the batched form's, where a
        # batch of ONE is a matrix-vector product that a backend may sum
        # in another order (the CPU's does; on the chip one, halves and
        # the whole window's gather agree bit for bit and halves cost
        # what one costs, PERF.md section 6, PR 38).  A FULL window
        # loses nothing to the loop: eight gathers of 33 MB beat one of
        # 262 (15.43 ms for 16.04 at k = 256, 14.20 for 14.18 at k = 32;
        # 32 of 32 rows 20.27 for 23.02).  Sorting inside the loop too
        # would win 0.1 ms at two requests and lose 0.7 at eight
        halves = 2 if ksel % 2 == 0 else 1

        def rescored(r, out):
            def at(x):
                return jax.lax.dynamic_index_in_dim(x, r, 0, False)

            q = jnp.broadcast_to(at(Qc), (halves, Qc.shape[1]))
            s = _rescored(Y, q, active, at(bi).reshape(halves, -1),
                          None if cand is None
                          else at(cand).reshape(halves, -1), bs)
            return jax.lax.dynamic_update_slice_in_dim(
                out, s.reshape(1, -1), r, 0)

        scores = jax.lax.fori_loop(
            0, n_real, rescored,
            jnp.full((b, ksel * bs), -jnp.inf, jnp.float32))
    ts, ti = jax.lax.top_k(scores, k)
    rows = (bi[:, :, None] * bs
            + jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(
                b, ksel * bs)
    idx = jnp.take_along_axis(rows, ti, axis=1)
    # conservative margin: phase A (MXU dot, per-tile accumulation) and
    # phase B (einsum) may round the same bf16 products differently by
    # ~F*ulp; inflating m_rest by a relative epsilon can only FAIL the
    # certificate more often (never pass a true miss), preserving
    # exactness under cross-kernel accumulation-order divergence
    # (relative only: zero-padded batch rows score exactly 0 on both
    # phases and must keep passing; -inf m_rest — every unselected
    # block masked, e.g. a tight LSH ball — must stay -inf, not
    # -inf + inf = NaN, which would fail every certificate)
    m_guard = jnp.where(jnp.isfinite(m_rest),
                        m_rest + jnp.abs(m_rest) * 1e-4, m_rest)
    cert = ts[:, k - 1] >= m_guard
    if n_real is not None:
        padding = jnp.arange(b) >= n_real
        idx = jnp.where(padding[:, None], 0, idx)
        cert = cert | padding
    return ts, idx, cert


def _rescored(Y, Qc, active, bi, cand, bs: int):
    """The exact scores of the blocks ``bi`` (rows, blocks) selected
    for the query rows ``Qc``, (rows, blocks * bs), -inf for a retired
    store row and for every row of a block that is no candidate
    (``cand`` False)."""
    b, n = bi.shape
    # gathered blocks stay in the store dtype: phase B must reduce the
    # SAME bf16 products phase A did or the exactness certificate's
    # phase-A-bounds-phase-B argument breaks at the rounding margin
    Yg = jnp.take(Y.reshape(-1, bs, Y.shape[1]), bi,
                  axis=0)                              # (B, ksel, bs, F)
    scores = jnp.einsum("bf,bkcf->bkc", Qc, Yg,
                        preferred_element_type=jnp.float32,
                        precision=_score_precision(Y)
                        ).reshape(b, n * bs)
    ok = jnp.take(active.reshape(-1, bs), bi, axis=0)
    if cand is not None:
        ok = ok & cand[:, :, None]
    return jnp.where(ok.reshape(b, n * bs), scores, -jnp.inf)


# Pallas phase A: rows per grid step.  The whole point is that the
# score tile lives and dies in VMEM — the XLA scan writes a (B, chunk)
# f32 score tensor to HBM every chunk and reads it back for the block
# max, an F-independent ~270 MB/chunk tax that measured as the bulk of
# the 20M-cell window time (155-176 ms regardless of F).  A narrow
# window's pass (_scores_rows_on_lanes) now takes what streaming the
# store takes, 7.0 / 13.8 ms at 128 lanes / 250f against 6.9 / 13.7
# for the tiles alone (~740 GB/s; PERF.md section 5, PR 33).  Tile 4096
# fits VMEM with double-buffering at F=250 bf16; steps of 8,192 and
# 16,384 rows measured the same.  It is also the step of an item store
# laid out by LSH bucket (one bucket a step; a model reads it when it
# is built), whose pruned pass is this kernel over a list of steps.
_PA_TILE = 4096
# runtime-fallback state for the pallas build, PER SHAPE: pallas cannot
# lower on the CPU backend (tier-1 serves the lax.scan build there), and
# a compile failure for one (rows, features, batch, lsh) signature must
# not disable the kernel for other models/shapes in the same process
_PALLAS_STATE: dict = {}  # shape key -> "ok" | "broken" | fail count
# why each non-"ok" shape failed (last error text): merged into the
# model's /metrics kernel_route.errors, so a failure that surfaced at
# dispatch time — a ladder window the route measurement never timed —
# is as visible as one the measurement itself hit
_PALLAS_ERRORS: dict = {}
# transient (non-lowering) failures tolerated on a shape before it is
# retired to the lax.scan build for the life of the process
_PALLAS_MAX_TRANSIENT = 3
# a failure whose message matches none of these is treated as
# transient (e.g. a device OOM from a concurrent dispatch) and gets
# retried on the next drain instead of permanently killing the kernel
_PALLAS_FATAL_MARKERS = ("mosaic", "pallas", "lowering", "unimplemented",
                         "not implemented", "not supported", "no support",
                         "cannot lower", "xla_tpu", "INTERNAL: Mosaic",
                         "interpret mode", "is supported on")


def _pallas_error_is_fatal(e: Exception) -> bool:
    text = f"{type(e).__name__} {e}".lower()
    return isinstance(e, NotImplementedError) or any(
        m.lower() in text for m in _PALLAS_FATAL_MARKERS)


def error_text(e: Exception, limit: int = 400) -> str:
    """One bounded line for an ``errors`` table on /metrics or in a
    report: the exception's type and message."""
    return f"{type(e).__name__}: {e}"[:limit]


def pallas_failure_level() -> int:
    """Log level for a phase-A build that failed to compile or run.
    The lax.scan build is the only phase A the CPU backend has, so
    there the substitution is routine (WARNING).  On a TPU every build
    is expected to lower: a failure is a defect in the program that
    the substitution would otherwise hide behind a green run (ERROR —
    and fatal to ``warmup`` and ``chip_smoke.py``).  Decided from the
    backend this process observes; there is no key."""
    return logging.ERROR if jax.default_backend() == "tpu" \
        else logging.WARNING


def _classify_pallas_failure(keys: list, e: Exception) -> None:
    """Record a pallas dispatch/fetch failure against the given shape
    keys: fatal (lowering/unsupported) retires them to the scan build;
    transient failures count toward the 3-strike retirement.  A failure
    attributed only to shapes that all worked before re-raises — that
    is a real runtime failure, not a fallback case."""
    fresh = [k for k in keys if _PALLAS_STATE.get(k) != "ok"]
    if not fresh:
        raise e
    for k in fresh:
        _PALLAS_ERRORS[k] = error_text(e)
    if _pallas_error_is_fatal(e):
        for k in fresh:
            _PALLAS_STATE[k] = "broken"
        _log.log(
            pallas_failure_level(),
            "pallas two-phase kernel unavailable for shape(s) %s; "
            "serving substitutes the lax.scan build: %s", fresh, e)
    else:
        # e.g. a device OOM from a concurrent dispatch: leave the
        # kernel eligible for the next drain
        for k in fresh:
            fails = _PALLAS_STATE.get(k, 0) + 1
            _PALLAS_STATE[k] = ("broken" if fails >= _PALLAS_MAX_TRANSIENT
                                else fails)
        _log.log(
            pallas_failure_level(),
            "pallas two-phase dispatch failed transiently for "
            "shape(s) %s (3 strikes retires a shape): %s", fresh, e)


def _scores_rows_on_lanes(b: int) -> bool:
    """Whether the pallas phase A lays a ``b``-query window's score
    tile (b, rows) — queries on the sublanes, the store's rows on the
    lanes — instead of (rows, b): for every window narrower than a lane
    tile, the ladder's 8 and 32.  (rows, 8) fills 8 of a vector
    register's 128 lanes and (8, rows) all of them, and its block
    maxima leave row-major, as phase B's selection reads them.  Phase A
    alone over 20,054,016 bfloat16 rows on a v5e, ms a pass (PERF.md
    section 5, PR 33), (rows, b) -> (b, rows), beside the 6.86 / 13.65
    that streaming the tiles takes with no dot at all:

        b      128 lanes (50f)     250f
        8      11.38 ->  6.98      13.96 -> 13.76
        32     11.39 ->  7.02      13.96 -> 13.76
        128    11.35  (7.79)       14.17  (13.92)
        256    11.35 (10.61)       15.38  (15.93)

    (rows, b) costs the same at every width, so it is not the scores'
    registers alone that hold it 4.5 ms over the stream; what does was
    not split further.  From 128 queries on the rule keeps (rows, b)
    (in brackets what (b, rows) read there: a loss at 256 x 250f), so
    the route measurement's 256-wide program is what it was.  It reads
    the window's width only: at 250f the gain is small and still a
    gain."""
    return b < 128


def _pallas_block_maxima(Qc, Y, penalty, bs: int, rows_on_lanes: bool,
                         interpret: bool = False, steps=None,
                         n_visit=None):
    """Phase A of the pallas build: the (B, N // bs) maxima of every
    ``bs``-row block's scores, from one pass over ``Y`` whose score
    tiles live and die in VMEM.  ``penalty`` is the (N // bs, bs) 0 /
    -inf active-row mask.  The two layouts (_scores_rows_on_lanes)
    reduce the same products and hand over the same maxima.

    ``steps`` of None is the exact scan: the grid is every step of the
    store.  With ``steps`` (a scalar-prefetched (N // T,) table, the
    visited steps first) the grid is its first ``n_visit`` entries, a
    bound known only on the device, and grid step i streams store step
    ``steps[i]``: the kernel body is the exact scan's, only the index
    maps differ, and the maxima leave in VISIT order (step i's blocks
    at columns [i * J, (i + 1) * J); -inf past the last visited step).
    Which of a visited step's maxima a query row may use is the
    caller's one comparison a (row, step)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, F = Y.shape
    B = Qc.shape[0]
    T = _PA_TILE
    J = T // bs                     # blocks a step
    pruned = steps is not None
    nt = (((1,), (1,)), ((), ()))   # contract both minor dimensions
    precision = _score_precision(Y)  # the certificate rests on these
    extra = {}
    if precision is not None:
        # HIGHEST splits a float32 tile into bfloat16 parts in VMEM: a
        # 256-wide window at 250f asks for 26.7 MB, the compiler's
        # scoped default is 16 of a v5e's 128
        extra["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=64 << 20)
    # per-row side inputs ride in lane-aligned (rows//bs, bs) layout —
    # an (N, 1) input would be lane-padded x128 by TPU tiling (9.5 GB
    # of padding at 20M rows; measured compile OOM)
    if pruned:
        # index maps take the prefetched table after the grid index,
        # and the kernel takes its ref first
        first, at, stay = (steps,), lambda i, st: (st[i], 0), \
            lambda i, st: (0, 0)

        def call(kern, out_spec, out_shape):
            body = lambda st_ref, *refs: kern(*refs)  # noqa: E731
            return pl.pallas_call(
                body, grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1, grid=(n_visit,),
                    in_specs=in_specs, out_specs=out_spec),
                out_shape=out_shape, interpret=interpret, **extra)
    else:
        first, at, stay = (), lambda i: (i, 0), lambda i: (0, 0)

        def call(kern, out_spec, out_shape):
            return pl.pallas_call(
                kern, grid=(N // T,), in_specs=in_specs,
                out_specs=out_spec, out_shape=out_shape,
                interpret=interpret, **extra)

    ins = [*first, Qc, Y, penalty]
    in_specs = [pl.BlockSpec((B, F), stay), pl.BlockSpec((T, F), at),
                pl.BlockSpec((J, bs), at)]

    if not rows_on_lanes:
        # (rows, B): Mosaic requires the minor dim of a stored tile to
        # be 128-aligned or full, so the maxima leave as (N // bs, B)
        def kern(q_ref, y_ref, p_ref, o_ref):
            s = jax.lax.dot_general(y_ref[...], q_ref[...], nt,
                                    preferred_element_type=jnp.float32,
                                    precision=precision)
            s3 = s.reshape(J, bs, B) + p_ref[...][:, :, None]
            o_ref[...] = s3.max(1)

        # the output follows the GRID index on both paths: a pruned
        # pass writes its maxima in visit order
        rows = (lambda i, st: (i, 0)) if pruned else (lambda i: (i, 0))
        Mt = call(kern, pl.BlockSpec((J, B), rows),
                  jax.ShapeDtypeStruct((N // bs, B), jnp.float32))(*ins)
        if pruned:
            Mt = jnp.where((jnp.arange(N // bs) < n_visit * J)[:, None],
                           Mt, -jnp.inf)
        return Mt.T

    # (B, rows): the dot is q . y^T, row j of a step's side inputs is
    # the lanes of its block j and is broadcast down the sublanes, each
    # block reduces along its lanes to one column, and the columns land
    # in M as they lie: row-major, the blocks on the lanes.  A stored
    # tile's minor dimension is a multiple of 128, so a step's J blocks
    # are J lanes of a (B, 128) output tile that stays resident while
    # 128 // J steps fill it (four, at 4,096 rows a step; larger steps
    # measured the same, PERF.md section 5), and M is padded to whole
    # tiles where the capacity is not
    W = max(J, 128)                 # blocks an output tile
    per_tile = W // J               # steps that fill one
    if J * per_tile != W:
        raise NotImplementedError(
            f"{J} blocks a step do not tile {W} lanes")
    n_blocks = N // bs

    def kern(q_ref, y_ref, p_ref, o_ref):
        s = jax.lax.dot_general(q_ref[...], y_ref[...], nt,
                                preferred_element_type=jnp.float32,
                                precision=precision)
        first = (pl.program_id(0) % per_tile) * J
        m = jnp.where(first == 0, -jnp.inf, o_ref[...])
        lane = jax.lax.broadcasted_iota(jnp.int32, (B, W), 1)
        for j in range(J):
            sj = s[:, j * bs:(j + 1) * bs] + p_ref[j:j + 1, :]
            m = jnp.where(lane == first + j, sj.max(1, keepdims=True), m)
        o_ref[...] = m

    tiles = (lambda i, st: (0, i // per_tile)) if pruned \
        else (lambda i: (0, i // per_tile))
    M = call(kern, pl.BlockSpec((B, W), tiles),
             jax.ShapeDtypeStruct((B, -(-n_blocks // W) * W),
                                  jnp.float32))(*ins)[:, :n_blocks]
    if pruned:
        # a tile the grid never reached was never written; the last
        # one it reached holds -inf past the last visited step already
        M = jnp.where((jnp.arange(n_blocks) < n_visit * J)[None, :], M,
                      -jnp.inf)
    return M


def _candidate_maxima(M, step_ok):
    """A pruned pass's block maxima (visit order) with every block of a
    step that is no candidate of the row at -inf: the LSH test, one
    comparison a (query row, step)."""
    b, n_steps = step_ok.shape
    return jnp.where(step_ok[:, :, None], M.reshape(b, n_steps, -1),
                     -jnp.inf).reshape(b, -1)


@partial(jax.jit, static_argnames=("k", "bs", "ksel", "max_bits",
                                   "interpret"))
def _batch_top_n_twophase_pallas(Y, Q, penalty, active, prune, n_real,
                                 k: int, bs: int, ksel: int,
                                 max_bits: int = 0,
                                 interpret: bool = False):
    """Two-phase streaming top-k with the phase-A block maxima computed
    by a fused pallas dot+blockmax kernel (scores never touch HBM), in
    the layout the window's width asks for (_scores_rows_on_lanes);
    ``penalty`` is the (N // bs, bs) 0/-inf active-row mask; ``n_real``
    (int32 scalar, traced) how many leading rows of ``Q`` are requests,
    the rest padding (_phase_b).  ``prune`` of None is the exact scan.
    With a ``Pruning`` the pass streams only the steps the window's
    rows can reach (_visit_plan) and the program returns a fourth
    result, the plan's ``stats``."""
    Qc = _q_cast(Q, Y)
    lanes = _scores_rows_on_lanes(Q.shape[0])
    if prune is None:
        M = _pallas_block_maxima(Qc, Y, penalty, bs, lanes, interpret)
        return _phase_b(Y, Qc, active, M, n_real, k, bs, ksel)
    steps, n_visit, step_ok, stats = _visit_plan(Q, prune, n_real,
                                                 max_bits)
    M = _pallas_block_maxima(Qc, Y, penalty, bs, lanes, interpret, steps,
                             n_visit)
    return (*_phase_b(Y, Qc, active, _candidate_maxima(M, step_ok), n_real,
                      k, bs, ksel, steps), stats)


def _fold_factor(width: int, features: int) -> int:
    """Rows-per-physical-row folding for the phase-A scan.  The device
    snapshot zero-pads features below 128 to the TPU's lane width, so
    an F=50 scan streams 2.56x its useful bytes from HBM; folding 2 (or
    4) logical rows into one 128-lane physical row of a mirror array
    restores the reference's time ∝ items x features proportionality
    (docs/docs/performance.html) that the padding broke.  Returns the
    largest fold in {4, 2} whose per-slot lane width still holds a full
    feature vector, else 1."""
    for fold in (4, 2):
        w = width // fold
        if width % fold == 0 and w >= features and w % 8 == 0:
            return fold
    return 1


def _fold_eligible(width: int, features: int, bs: int) -> int:
    """Fold factor the serving dispatch will actually use for this
    shape (1 = no folding): _fold_factor gated by the block/tile
    divisibility the kernel's reshape layout requires.  Shared by the
    dispatch and the kernel probe so published numbers time what
    serving runs."""
    fold = _fold_factor(width, features)
    if fold > 1 and bs % fold == 0 and _PA_TILE % fold == 0:
        return fold
    return 1


@partial(jax.jit, static_argnames=("fold", "bs"))
def _fold_items_kernel(vecs, active, fold: int, bs: int):
    """Build the folded phase-A mirror on device: logical row
    ``i*fold + j`` occupies lanes ``[j*w, j*w + w)`` of folded row
    ``i`` (w = width // fold), so folded rows ``[b*bs//fold,
    (b+1)*bs//fold)`` across all ``fold`` slots are exactly logical
    block ``b`` — block maxima land in the same (N//bs, B) layout the
    unfolded kernel produces.  Returns (Yf, penalty_fold) with the
    per-slot penalty in the (fold, N//bs, bs//fold) layout the
    kernel's block specs expect."""
    N, W = vecs.shape
    w = W // fold
    bsf = bs // fold
    Yf = vecs[:, :w].reshape(N // fold, W)
    pen = jnp.where(active, 0.0, -jnp.inf).astype(jnp.float32)
    pen_f = pen.reshape(-1, fold).T.reshape(fold, -1, bsf)
    return Yf, pen_f


@partial(jax.jit, static_argnames=("k", "bs", "ksel", "fold",
                                   "interpret"))
def _batch_top_n_twophase_pallas_fold(Y, Yf, Q, pen_f, active, n_real,
                                      k: int, bs: int, ksel: int,
                                      fold: int, interpret: bool = False):
    """Two-phase streaming top-k whose phase A scans the FOLDED mirror:
    one dot per fold slot against a slot-shifted query copy, per-block
    reduce, max across slots.  Phase B and the exactness certificate
    run on the canonical store arrays as always (the folded dot
    accumulates the same bf16 products in a different MXU tree order —
    exactly the cross-kernel divergence the certificate's relative
    margin already covers)."""
    from jax.experimental import pallas as pl

    Nf, W = Yf.shape
    N = Nf * fold
    B = Q.shape[0]
    w = W // fold
    bsf = bs // fold
    Tf = _PA_TILE // fold
    Qc = _q_cast(Q, Y)
    precision = _score_precision(Y)  # as _pallas_block_maxima
    # slot-shifted query copies: slot j's features live in lanes
    # [j*w, j*w + w), zeros elsewhere — the zero lanes kill the other
    # slots' features in the shared dot
    qw = Qc[:, :w]
    Qs = jnp.stack([jnp.pad(qw, ((0, 0), (j * w, W - (j + 1) * w)))
                    for j in range(fold)])

    def kern(q_ref, y_ref, p_ref, o_ref):
        m = None
        for j in range(fold):
            s = jax.lax.dot_general(y_ref[...], q_ref[j],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=precision)
            s3 = s.reshape(Tf // bsf, bsf, B) + p_ref[j][:, :, None]
            mj = s3.max(1)
            m = mj if m is None else jnp.maximum(m, mj)
        o_ref[...] = m

    ins = (Qs, Yf, pen_f)
    in_specs = [pl.BlockSpec((fold, B, W), lambda i: (0, 0, 0)),
                pl.BlockSpec((Tf, W), lambda i: (i, 0)),
                pl.BlockSpec((fold, Tf // bsf, bsf),
                             lambda i: (0, i, 0))]

    Mt = pl.pallas_call(
        kern, grid=(N // _PA_TILE,), in_specs=in_specs,
        out_specs=pl.BlockSpec((Tf // bsf, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N // bs, B), jnp.float32),
        interpret=interpret)(*ins)
    return _phase_b(Y, Qc, active, Mt.T, n_real, k, bs, ksel)


def _scan_block_maxima(Qc, Y, active, chunk: int, bs: int):
    """Phase A of the lax.scan build: the (B, N // bs) maxima of every
    ``bs``-row block's scores, one (B, chunk) score tile live at a
    time.  The build every backend lowers (the CPU has no other)."""
    b = Qc.shape[0]
    n_chunks = Y.shape[0] // chunk
    xs = (Y.reshape(n_chunks, chunk, Y.shape[1]),
          active.reshape(n_chunks, chunk))

    def step_a(_, x):
        scores = jnp.matmul(Qc, x[0].T,
                            preferred_element_type=jnp.float32,
                            precision=_score_precision(Y))
        scores = jnp.where(x[1][None, :], scores, -jnp.inf)
        return None, scores.reshape(b, chunk // bs, bs).max(-1)

    _, Ms = jax.lax.scan(step_a, None, xs)
    return jnp.transpose(Ms, (1, 0, 2)).reshape(b, -1)   # (B, n_blocks)


def _scan_step_maxima(Qc, Y, active, steps, n_visit, bs: int):
    """Phase A of the lax.scan build over a pruned window's steps
    (_visit_plan): the loop runs ``n_visit`` times, a bound known only
    on the device, slices store step ``steps[i]`` and writes its block
    maxima at visit position i; what it never reaches stays -inf."""
    b = Qc.shape[0]
    n_steps = steps.shape[0]
    tile = Y.shape[0] // n_steps

    def step_a(i, M):
        at = steps[i] * tile
        y = jax.lax.dynamic_slice_in_dim(Y, at, tile)
        a = jax.lax.dynamic_slice_in_dim(active, at, tile)
        scores = jnp.matmul(Qc, y.T, preferred_element_type=jnp.float32,
                            precision=_score_precision(Y))
        scores = jnp.where(a[None, :], scores, -jnp.inf)
        return jax.lax.dynamic_update_slice_in_dim(
            M, scores.reshape(b, 1, tile // bs, bs).max(-1), i, 1)

    M = jax.lax.fori_loop(
        0, n_visit, step_a,
        jnp.full((b, n_steps, tile // bs), -jnp.inf, jnp.float32))
    return M.reshape(b, -1)


@partial(jax.jit, static_argnames=("k", "chunk", "bs", "ksel", "max_bits"))
def _batch_top_n_twophase_kernel(Y, Q, active, prune, n_real, k: int,
                                 chunk: int, bs: int, ksel: int,
                                 max_bits: int = 0):
    """Streaming batched top-k, two-phase MIPS style, EXACT with a
    per-row certificate.

    Phase A scans the item matrix in row chunks and keeps only per-
    128-row-block score maxima (one (B, chunk) tile live in HBM, never
    (B, N) — what makes the reference's largest published model, 21M ids
    x 250 features, servable from one chip).  Phase B picks the ``ksel``
    best blocks per query with approx_max_k (the TPU-native partial
    reduce; a full lax.top_k over a multi-million-row chunk lowers to a
    per-row sort that costs ~40x the matmul itself), exactly rescores
    those blocks from gathered rows, and emits top-k plus a certificate:
    kth_score >= max(every unselected block's maximum) proves no
    unscanned block can hold a better item.  Rows whose certificate
    fails (approx selection missed a head block) are recomputed by the
    caller on the exact lax.top_k scan path.  The first ``n_real`` rows
    of ``Q`` (int32 scalar, traced) are requests, the rest padding that
    phase B neither gathers nor rescores.  ``prune`` of None is
    the exact scan; with a ``Pruning`` phase A loops over the steps the
    window's rows can reach only (_visit_plan; ``chunk`` is then not
    looked at) and the plan's ``stats`` are a fourth result."""
    Qc = _q_cast(Q, Y)
    if prune is None:
        M = _scan_block_maxima(Qc, Y, active, chunk, bs)
        return _phase_b(Y, Qc, active, M, n_real, k, bs, ksel)
    steps, n_visit, step_ok, stats = _visit_plan(Q, prune, n_real,
                                                 max_bits)
    M = _scan_step_maxima(Qc, Y, active, steps, n_visit, bs)
    return (*_phase_b(Y, Qc, active, _candidate_maxima(M, step_ok), n_real,
                      k, bs, ksel, steps), stats)


def _merge_top_k(best_s, best_i, scores, base, k: int):
    """The running best ``k`` (scores, rows) with the best of one more
    tile of ``scores``, whose first row is store row ``base``."""
    cs, ci = jax.lax.top_k(scores, min(k, scores.shape[1]))
    ns, sel = jax.lax.top_k(jnp.concatenate([best_s, cs], axis=1), k)
    ni = jnp.take_along_axis(
        jnp.concatenate([best_i, ci + base], axis=1), sel, axis=1)
    return ns, ni


@partial(jax.jit, static_argnames=("k", "chunk"))
def _batch_top_n_chunked_kernel(Y, Q, active, k: int, chunk: int):
    """Streaming batched top-k with exact per-chunk lax.top_k — the
    certainty fallback for two-phase certificate failures (and the
    reference semantics oracle in tests).  Carries the running (B, k)
    best scores/indices across item-row chunks."""
    n_chunks = Y.shape[0] // chunk
    xs = (Y.reshape(n_chunks, chunk, Y.shape[1]),
          active.reshape(n_chunks, chunk),
          jnp.arange(n_chunks, dtype=jnp.int32) * chunk)
    Qc = _q_cast(Q, Y)

    def step(carry, x):
        Yc, Ac, base = x
        scores = jnp.matmul(Qc, Yc.T,
                            preferred_element_type=jnp.float32,
                            precision=_score_precision(Y))
        return _merge_top_k(*carry, jnp.where(Ac[None, :], scores,
                                              -jnp.inf), base, k), None

    b = Q.shape[0]
    init = (jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.zeros((b, k), jnp.int32))
    (best_s, best_i), _ = jax.lax.scan(step, init, xs)
    return best_s, best_i


@partial(jax.jit, static_argnames=("k", "max_bits"))
def _batch_top_n_pruned_exact_kernel(Y, Q, active, prune, n_real, k: int,
                                     max_bits: int):
    """The exact scan held to a pruned window's candidates: the answer
    of a window whose two-phase certificate failed, and the primary
    path where the two-phase program is not admitted.  One store step
    at a time over the steps the window's rows can reach (_visit_plan),
    a row scoring only the steps of its own ball, with the running
    (B, k) best carried along.  Returns (scores, rows, stats)."""
    steps, n_visit, step_ok, stats = _visit_plan(Q, prune, n_real,
                                                 max_bits)
    tile = Y.shape[0] // steps.shape[0]
    Qc = _q_cast(Q, Y)

    def step(i, carry):
        at = steps[i] * tile
        y = jax.lax.dynamic_slice_in_dim(Y, at, tile)
        a = jax.lax.dynamic_slice_in_dim(active, at, tile)
        scores = jnp.matmul(Qc, y.T, preferred_element_type=jnp.float32,
                            precision=_score_precision(Y))
        ok = a[None, :] & jax.lax.dynamic_slice_in_dim(step_ok, i, 1, 1)
        return _merge_top_k(*carry, jnp.where(ok, scores, -jnp.inf), at,
                            k)

    b = Q.shape[0]
    init = (jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.zeros((b, k), jnp.int32))
    best_s, best_i = jax.lax.fori_loop(0, n_visit, step, init)
    return best_s, best_i, stats


class ShardPlan(NamedTuple):
    """How every shard of a row-sharded store scores one window on the
    two-phase path (parallel/serving_dist.py)."""

    ksel: int    # blocks phase B selects on a shard
    chunk: int   # rows a step of the scan builds and of the exact scan
    bs: int      # rows a block


def shard_plan(Y, n_shards: int, k: int, width: int) -> ShardPlan | None:
    """The two-phase plan for a fetch of ``k`` rows by a ``width``-query
    window over the row-sharded ``Y`` (an array or its aval), or None
    where the flat body answers: the one-chip dispatch's own conditions
    (``top_n_batch``), read on the rows ONE shard holds."""
    rows = int(Y.shape[0]) // n_shards
    local = jax.ShapeDtypeStruct((rows, int(Y.shape[1])), Y.dtype)
    big, chunk = _stream_plan(rows, width)
    bs = _BLOCK_ROWS
    ksel = _block_ksel(k, rows, bs)
    if big and rows % chunk == 0 and k <= chunk \
            and _twophase_admits(k, ksel, local, bs):
        return ShardPlan(ksel, chunk, bs)
    return None


def shard_candidates(Y, active, Q, penalty, n_real, k: int,
                     plan: ShardPlan | None):
    """The per-shard body of the sharded program: one shard's best ``k``
    rows (scores, LOCAL row ids) over its own ``Y``.  ``plan`` of None
    is the flat body, one matmul over all of them and ``top_k``.
    Otherwise the one-chip two-phase scan, and a third result, each
    query row's certificate (True for the padding behind the first
    ``n_real`` rows: a window of two requests must not send a shard to
    its exact scan for six rows of zeros); ``penalty`` of None selects
    the lax.scan phase A.  Where a certificate fails the shard scores
    its rows again with the exact scan: its neighbours' answers stand,
    and the merge waits for it."""
    if plan is None:
        return _batch_top_n_kernel.__wrapped__(Y, Q, active, k)
    Qc = _q_cast(Q, Y)
    if penalty is None:
        M = _scan_block_maxima(Qc, Y, active, plan.chunk, plan.bs)
    else:
        M = _pallas_block_maxima(Qc, Y, penalty, plan.bs,
                                 _scores_rows_on_lanes(Q.shape[0]))
    ts, ti, cert = _phase_b(Y, Qc, active, M, n_real, k, plan.bs,
                            plan.ksel)
    ts, ti = jax.lax.cond(
        cert.all(), lambda: (ts, ti),
        lambda: _batch_top_n_chunked_kernel(Y, Q, active, k, plan.chunk))
    return ts, ti, cert


@partial(jax.jit, static_argnames=("k",))
def _masked_top_k(scores, mask, k: int):
    masked = jnp.where(mask, scores, -jnp.inf)
    return jax.lax.top_k(masked, k)


@partial(jax.jit, static_argnames=("n_steps",))
def _step_live_kernel(active, n_steps: int):
    """Live rows of each of a partitioned store's ``n_steps`` steps."""
    return active.reshape(n_steps, -1).sum(1, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("bs",))
def _penalty_kernel(active, bs: int):
    """(N//bs, bs) additive mask for the pallas phase-A kernel.  The
    lane-aligned 2D layout matters: an (N, 1) input would be
    lane-padded x128 by TPU tiling — 9.5 GB of pure padding at 20M
    rows (measured compile OOM).  ``bs`` is an explicit static arg so
    jit caching keys on it — a captured module global would bake the
    FIRST caller's value into every same-shaped later call."""
    return jnp.where(active, 0.0, -jnp.inf).astype(jnp.float32).reshape(
        -1, bs)


# retired-row penalty for the int8 selection kernel: far below any real
# int8 dot product (|s_int| <= 127*127*F < 2^23 at F <= 512) yet far
# from int32 overflow when added to one
_I8_PENALTY = -(1 << 29)


def _i8_ksel(ksel: int, n_rows: int, bs: int) -> int:
    """Block-selection width for the int8 phase A (and IVF), given the
    width ``_block_ksel`` chose for the fetch: twice that.  Selection
    there runs on margin-inflated BOUNDS, so the best unselected BOUND
    sits above the block maximum it stands for by the quantization
    margin, on top of ``m_guard``'s; what decides the certificate is
    still ``ksel`` against k first (_block_ksel's notes), and the
    doubling moves the best unselected block from about item 2k to
    about item 4k, buying back the bound's false-failure rate for
    ~0.5 ms of extra gather at k = 16.  Shared by the serving dispatch
    and the kernel probe so published numbers time what serving
    runs."""
    return min(ksel * 2, max(1, n_rows // bs - 1))


@partial(jax.jit, static_argnames=("bs",))
def _penalty_kernel_i32(active, bs: int):
    return jnp.where(active, 0, _I8_PENALTY).astype(jnp.int32).reshape(
        -1, bs)


@partial(jax.jit, static_argnames=("bs",))
def _quantize_items_kernel(vecs, bs: int):
    """Per-128-row-block int8 quantization of the item matrix, on
    device: (Y8, per-block scale, per-block max row L1 norm).

    The block granularity is deliberate: phase A reduces scores to
    per-block maxima, and a SHARED scale within each block makes
    ``max(s_int) * scale`` a sound transform of the block's quantized
    maxima (per-row scales could not be applied after the max).  The
    L1 norms feed the quantization-error margin that turns quantized
    maxima into sound upper BOUNDS on exact block maxima."""
    f32 = vecs.astype(jnp.float32)
    blocks = f32.reshape(-1, bs, f32.shape[1])
    scale = jnp.max(jnp.abs(blocks), axis=(1, 2)) / 127.0
    safe = jnp.maximum(scale, 1e-30)
    y8 = jnp.clip(jnp.round(blocks / safe[:, None, None]),
                  -127, 127).astype(jnp.int8).reshape(f32.shape)
    l1 = jnp.max(jnp.sum(jnp.abs(blocks), axis=2), axis=1)
    return y8, scale, l1


@partial(jax.jit, static_argnames=("fold", "bs"))
def _fold_items_i8_kernel(y8, active, fold: int, bs: int):
    """Fold the int8 quantization mirror the same way _fold_items_kernel
    folds the bf16 store: logical row ``i*fold + j`` occupies lanes
    ``[j*w, j*w + w)`` of folded row ``i``.  Sound because quantized
    lanes at or beyond the feature count are exactly 0 (they quantize
    from exact 0.0), so the folded integer dot equals the unfolded one
    bit-for-bit — the per-block scales and L1 norms from the canonical
    quantizer apply unchanged.  Returns (Y8f, penalty_i_fold) with the
    int32 retired-row penalty in the (fold, N//bs, bs//fold) slot
    layout the kernel's block specs expect."""
    N, W = y8.shape
    w = W // fold
    bsf = bs // fold
    y8f = y8[:, :w].reshape(N // fold, W)
    pen = jnp.where(active, 0, _I8_PENALTY).astype(jnp.int32)
    pen_f = pen.reshape(-1, fold).T.reshape(fold, -1, bsf)
    return y8f, pen_f


@partial(jax.jit, static_argnames=("k", "bs", "ksel", "fold",
                                   "interpret"))
def _batch_top_n_twophase_pallas_i8_fold(Y, Y8f, sy_b, l1y_b, Q,
                                         pen_i_f, active, n_real, k: int,
                                         bs: int, ksel: int, fold: int,
                                         interpret: bool = False):
    """The deepest phase-A mirror: int8 quantized AND row-folded, so a
    50-feature scan streams ~items x features BYTES (one int8 per
    useful element) instead of the bf16 store's items x 128 x 2 — a 4x
    HBM-byte reduction at f<=64, which is what the roofline says the
    lane-padded small-F scan needs to reach the r04 target.  Block
    selection runs on margin-inflated integer bounds exactly like the
    unfolded int8 kernel (the folded integer dot is bit-identical to
    the unfolded one: quantized padding lanes are exact zeros); phase B
    rescores the winners from the canonical bf16/f32 store, and the
    kth >= max(unselected bound) certificate catches any
    quantization-induced miss."""
    from jax.experimental import pallas as pl

    Nf, W = Y8f.shape
    N = Nf * fold
    B = Q.shape[0]
    w = W // fold
    bsf = bs // fold
    Tf = _PA_TILE // fold
    Qc = _q_cast(Q, Y)
    Qf = Qc.astype(jnp.float32)
    sq = jnp.maximum(jnp.max(jnp.abs(Qf), axis=1), 1e-30) / 127.0
    q8 = jnp.clip(jnp.round(Qf / sq[:, None]), -127, 127).astype(jnp.int8)
    # slot-shifted int8 query copies: slot j's features live in lanes
    # [j*w, j*w + w), zeros elsewhere — integer zeros kill the other
    # slots' features in the shared dot
    q8w = q8[:, :w]
    q8s = jnp.stack([jnp.pad(q8w, ((0, 0), (j * w, W - (j + 1) * w)))
                     for j in range(fold)])

    def kern(q_ref, y_ref, p_ref, o_ref):
        m = None
        for j in range(fold):
            s = jax.lax.dot_general(y_ref[...], q_ref[j],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.int32)
            s3 = s.reshape(Tf // bsf, bsf, B) + p_ref[j][:, :, None]
            mj = s3.max(1)
            m = mj if m is None else jnp.maximum(m, mj)
        o_ref[...] = m

    ins = (q8s, Y8f, pen_i_f)
    in_specs = [pl.BlockSpec((fold, B, W), lambda i: (0, 0, 0)),
                pl.BlockSpec((Tf, W), lambda i: (i, 0)),
                pl.BlockSpec((fold, Tf // bsf, bsf),
                             lambda i: (0, i, 0))]

    Mt_int = pl.pallas_call(
        kern, grid=(N // _PA_TILE,), in_specs=in_specs,
        out_specs=pl.BlockSpec((Tf // bsf, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N // bs, B), jnp.int32),
        interpret=interpret)(*ins)
    # identical bound algebra to the unfolded int8 kernel (the folded
    # integer maxima ARE the unfolded ones)
    l1q = jnp.sum(jnp.abs(Qf), axis=1)
    masked = Mt_int <= _I8_PENALTY // 2
    bound = (Mt_int.astype(jnp.float32) * sy_b[:, None] * sq[None, :]
             + 0.5 * sq[None, :] * l1y_b[:, None]
             + 0.5 * sy_b[:, None] * l1q[None, :]
             + 0.25 * W * sy_b[:, None] * sq[None, :])
    bound = jnp.where(masked | (l1q[None, :] == 0.0), -jnp.inf, bound)
    return _phase_b(Y, Qc, active, bound.T, n_real, k, bs, ksel)


@partial(jax.jit, static_argnames=("k", "bs", "ksel", "interpret"))
def _batch_top_n_twophase_pallas_i8(Y, Y8, sy_b, l1y_b, Q, penalty_i,
                                    active, n_real, k: int, bs: int,
                                    ksel: int, interpret: bool = False):
    """Two-phase streaming top-k with an INT8 phase A: block selection
    runs on a quantized mirror of the item matrix (half the HBM bytes
    of bf16, double MXU rate — measured 11.6 -> 5.3 ms per 256-window
    at 20M padded-128 rows), while phase B rescores the winners from
    the EXACT bf16/f32 factors as always.  Exactness is preserved by
    construction: quantized block maxima are inflated by the worst-case
    quantization error into sound upper bounds, selection/certificate
    run on the bounds, and the existing kth >= max(unselected bound)
    certificate catches any quantization-induced miss (falling back to
    the exact scan).  ``penalty_i`` is the int32 retired-row mask."""
    from jax.experimental import pallas as pl

    N, F = Y8.shape
    B = Q.shape[0]
    T = _PA_TILE
    # per-query symmetric quantization of the SAME operand phase B
    # reduces (the lane-padded, possibly bf16-cast query): the error
    # bound must cover the scores the certificate checks, and a bf16
    # store rescores against bf16(Q), not raw f32(Q)
    Qc = _q_cast(Q, Y)
    Qf = Qc.astype(jnp.float32)
    sq = jnp.maximum(jnp.max(jnp.abs(Qf), axis=1), 1e-30) / 127.0
    q8 = jnp.clip(jnp.round(Qf / sq[:, None]), -127, 127).astype(jnp.int8)

    def kern(q_ref, y_ref, p_ref, o_ref):
        s = jax.lax.dot_general(y_ref[...], q_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.int32)
        s3 = s.reshape(T // bs, bs, B) + p_ref[...][:, :, None]
        o_ref[...] = s3.max(1)

    ins = (q8, Y8, penalty_i)
    in_specs = [pl.BlockSpec((B, F), lambda i: (0, 0)),
                pl.BlockSpec((T, F), lambda i: (i, 0)),
                pl.BlockSpec((T // bs, bs), lambda i: (i, 0))]

    Mt_int = pl.pallas_call(
        kern, grid=(N // T,), in_specs=in_specs,
        out_specs=pl.BlockSpec((T // bs, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N // bs, B), jnp.int32),
        interpret=interpret)(*ins)
    # sound upper bound on each block's EXACT max score:
    #   s = sy*sq*s_int + err, |err| <= sq/2*L1(y) + sy/2*L1(q) + F*sy*sq/4
    # (y = y8*sy + ey with |ey| <= sy/2, q likewise; cross terms
    # bounded by the L1 norms, quadratic term by F/4 scale products).
    # Masked entries stay -inf so a fully-retired block can never fail
    # a certificate.
    l1q = jnp.sum(jnp.abs(Qf), axis=1)                      # (B,)
    masked = Mt_int <= _I8_PENALTY // 2
    bound = (Mt_int.astype(jnp.float32) * sy_b[:, None] * sq[None, :]
             + 0.5 * sq[None, :] * l1y_b[:, None]
             + 0.5 * sy_b[:, None] * l1q[None, :]
             + 0.25 * F * sy_b[:, None] * sq[None, :])
    # a zero query row (window padding) scores exactly 0 everywhere on
    # both phases; a small positive margin bound would fail its
    # certificate on EVERY padded drain — its true bound is 0^- = -inf
    bound = jnp.where(masked | (l1q[None, :] == 0.0), -jnp.inf, bound)
    return _phase_b(Y, Qc, active, bound.T, n_real, k, bs, ksel)


class ALSServingModel(FactorModelBase, ServingModel):
    """Factor stores + known-items, with device top-N."""

    def __init__(self, features: int, implicit: bool,
                 sample_rate: float = 1.0, rescorer_provider=None,
                 dtype="float32", item_shards: int = 1, mesh=None,
                 int8_selection: str | bool = "auto",
                 fold_scan: str | bool = "auto", ann_config=None):
        """``item_shards`` > 1 row-shards the item matrix over that many
        devices (``oryx.serving.api.item-shards``) and routes the
        dot-product top-N scan through one SPMD program a window: on
        every shard the two-phase scan of the one-chip path over that
        shard's rows (the flat matmul + top_k where the shard is too
        small for it), then one all_gather and an on-device top-k
        merge (parallel/serving_dist.py) — the serving mode for item
        matrices past one chip's HBM (reference's partitioned scan,
        PartitionedFeatureVectors.java:84-148 via
        ALSServingModel.java:265-280).  LSH pruning is bypassed in
        sharded mode (a sharded store is not laid out by bucket:
        PERF.md section 7); cosine and rescorer paths run on the
        sharded arrays through XLA's sharding propagation.  ``mesh``
        overrides the auto-built 1-D mesh (tests).

        ``sample_rate`` < 1 (``oryx.als.sample-rate``) on one chip lays
        the item store out by LSH bucket, one bucket a ``_PA_TILE``-row
        step, and every dot-product top-N then scans the buckets inside
        the query's Hamming ball only (module docstring)."""
        self._item_shards = int(item_shards)
        self._mesh = None
        item_sharding = None
        if self._item_shards > 1:
            from jax.sharding import (Mesh, NamedSharding,
                                      PartitionSpec)

            if mesh is None:
                devs = jax.devices()
                if len(devs) < self._item_shards:
                    raise ValueError(
                        f"item-shards={self._item_shards} but only "
                        f"{len(devs)} devices visible")
                mesh = Mesh(
                    np.array(devs[:self._item_shards]), ("items",))
            self._mesh = mesh
            self._mesh_axis = mesh.axis_names[0]
            item_sharding = NamedSharding(
                mesh, PartitionSpec(self._mesh_axis, None))
            from ...parallel.serving_dist import ShardKernelCache
            self._shard_kernels = ShardKernelCache(mesh, self._mesh_axis)
        super().__init__(features, implicit, dtype=dtype,
                         item_sharding=item_sharding)
        self.rescorer_provider = rescorer_provider
        self._known_items: dict[str, set[str]] = {}
        # incremental item -> #users-who-know-it counts, maintained on
        # every known-items write so /mostPopularItems is O(items) per
        # request instead of O(users × known-items) (the reference
        # recounts per request: MostPopularItems.java:52)
        self._item_pop: dict[str, int] = {}
        self._known_lock = AutoReadWriteLock()
        self.lsh = (LocalitySensitiveHash(sample_rate, features)
                    if sample_rate < 1.0 else None)
        # rows a step of the item store under LSH (0: not laid out)
        self._lsh_step = _PA_TILE if self._lsh_active() else 0
        if self._lsh_step:
            self.Y.partition_by(self.lsh.bucket_of,
                                self.lsh.num_partitions, self._lsh_step)
        # what a pruned window's program reads beside the store: the
        # bucket of every step (cached against the layout's version)
        # and the live rows of every step (against the active mask)
        self._step_bucket: jax.Array | None = None
        self._step_bucket_version: int = -1
        self._step_live: jax.Array | None = None
        self._step_live_src = None
        # pruned windows scored, the live rows inside their queries'
        # Hamming balls (the work the semantics oblige) and the rows
        # phase A streamed for them (whole steps)
        self.lsh_windows = 0
        self.lsh_candidate_rows = 0
        self.lsh_streamed_rows = 0
        # the penalties are functions of the active mask alone, whose
        # handle outlives every sync that writes vectors only: cached
        # against the mask they were built from (held, so that its
        # identity cannot be reused), not against the store's version
        self._penalty: jax.Array | None = None
        self._penalty_src = None
        # int8 block-selection mirror (oryx.serving.api.int8-selection):
        # "auto" (the default) enables it at f <= 64, where it composes
        # with the fold mirror into the int8+fold phase A that streams
        # ~items x features BYTES — the r05 roofline decomposition
        # showed the small-F scan 4x over its useful bytes, and this is
        # the designed lever (exactness preserved by the certificate:
        # f32/bf16 rescore of the selected window, quantized maxima
        # inflated into sound upper bounds).  The unfolded int8 path at
        # 64 < f < 128 measured a wash, so auto stays off there.
        # Programmatic booleans normalize to the canonical strings so a
        # True opt-in gets the same explicit-outranks-auto-fold
        # precedence as "true" (the dispatch chain compares strings)
        if isinstance(int8_selection, bool):
            int8_selection = "true" if int8_selection else "false"
        self._int8_selection = int8_selection
        self._i8: tuple | None = None
        self._i8_version: int = -1
        # int8 x fold combined mirror: (Y8f, penalty_i_fold, scale, L1)
        self._i8_fold: tuple | None = None
        self._i8_fold_version: int = -1
        # measured-cost route: {kinds, costs_ms, ...} chosen by
        # kernel_router.measure_routes at model load / hot-swap, keyed
        # on the Y store's padded capacity (the compiled-shape key —
        # UP-stream version bumps must NOT trigger re-measurement)
        self._route: dict | None = None
        self._route_capacity: int = -1
        self._route_lock = threading.Lock()
        # folded phase-A mirror (oryx.serving.api.fold-scan): at
        # features <= 64 the lane-padded scan reads 2-4x its useful
        # bytes; the fold mirror restores time ∝ items x features.
        # "auto" (default) folds whenever the shape allows; the mirror
        # costs 1/fold of the canonical snapshot's HBM
        self._fold_scan = fold_scan
        self._fold: tuple | None = None
        self._fold_version: int = -1
        self._penalty_i: jax.Array | None = None
        self._penalty_i_src = None
        # IVF ANN serving path (oryx.als.ann.*, ISSUE 18): the small
        # per-generation state (centroids + recall certificate) is
        # attached by the manager at model load; the big device mirror
        # is version-keyed like every other phase-A mirror.  "ivf"
        # joins the routed kind chain only while the certificate holds
        # (_ann_routable) — below min-recall the chain is exactly what
        # it was before ANN existed
        self._ann_cfg = ann_config
        self._ann = None
        self._ivf_mirror = None
        self._ivf_mirror_version: int = -1
        self._bucket_lock = threading.Lock()
        # observability: exact-scan recomputes forced by a failed
        # two-phase certificate (expected ~0; see _block_ksel's notes)
        self.twophase_fallbacks = 0
        # the rows phase B rescored (the requests of the narrow windows,
        # every row of the wide ones), and the rows of the windows the
        # two-phase program was dispatched for
        self.phase_b_rows = 0
        self.phase_b_window_rows = 0
        self._counts: dict[int, jax.Array] = {}   # _count
        # the sharded path's own: two-phase windows scored by the SPMD
        # program, and the (query row, shard) pairs whose certificate
        # failed there, each of which that shard answered by its exact
        # scan (a query row counts once in twophase_fallbacks however
        # many of its shards failed)
        self.sharded_windows = 0
        self.shard_fallback_rows = 0
        # whole-matrix builds of state derived from the item matrix (the
        # phase-A mirrors, the IVF mirror): one a kind at load.  The
        # penalties follow the rows a sync wrote (and a store laid out
        # by bucket needs no bucket per row: its steps' table follows
        # the writes); the mirrors and the IVF state do not yet, so under
        # a write stream a route that uses one pays a pass over the
        # store per device sync, and this count grows with the syncs
        self.derived_rebuilds = 0

    # -- known items ---------------------------------------------------------

    def add_known_items(self, user_id: str, item_ids: Iterable[str]) -> None:
        with self._known_lock.write():
            known = self._known_items.setdefault(user_id, set())
            for iid in item_ids:
                if iid not in known:
                    known.add(iid)
                    self._item_pop[iid] = self._item_pop.get(iid, 0) + 1

    def get_known_items(self, user_id: str) -> set[str]:
        with self._known_lock.read():
            return set(self._known_items.get(user_id, ()))

    def get_known_item_counts(self) -> dict[str, int]:
        with self._known_lock.read():
            return {u: len(s) for u, s in self._known_items.items() if s}

    def get_item_popularity_counts(self) -> dict[str, int]:
        """item -> number of users that know it, from the incremental
        counter (not a rescan)."""
        with self._known_lock.read():
            return {i: c for i, c in self._item_pop.items() if c > 0}

    def retain_recent_and_known_items(self, user_ids: Sequence[str],
                                      item_ids: Sequence[str]) -> None:
        """Prune known-items on MODEL swap: keep entries for users in the
        new model or recently updated in X, and within each set keep
        items in the new model or recently updated in Y
        (reference: ALSServingModel.retainRecentAndKnownItems :350-383).
        Must run BEFORE retain_recent_and_user/item_ids, which clear the
        recent sets."""
        keep_users = set(user_ids) | self.X.recent_ids()
        keep_items = set(item_ids) | self.Y.recent_ids()
        with self._known_lock.write():
            for u in [u for u in self._known_items if u not in keep_users]:
                for iid in self._known_items.pop(u):
                    self._item_pop[iid] -= 1
            for items in self._known_items.values():
                for iid in items - keep_items:
                    self._item_pop[iid] -= 1
                items &= keep_items
            self._item_pop = {i: c for i, c in self._item_pop.items()
                              if c > 0}

    # -- scoring -------------------------------------------------------------

    def metrics(self) -> dict:
        """App-level gauges merged into /metrics (framework hook)."""
        out = {
            # where this model's kernels actually run, as JAX reports
            # it: JAX drops to the CPU with only a warning when it
            # finds no chip, and nothing else on /metrics would say so
            "backend": jax.default_backend(),
            "users": len(self.X),
            "items": len(self.Y),
            # exact-scan recomputes forced by a failed streaming top-k
            # certificate; nonzero is worth an operator's attention
            "twophase_fallbacks": self.twophase_fallbacks,
            # the rows phase B rescored, and the rows of the windows
            # dispatched: what a drain's padding no longer costs
            "phase_b_rows": self.phase_b_rows,
            "phase_b_window_rows": self.phase_b_window_rows,
            "sharded_windows": self.sharded_windows,
            "shard_fallback_rows": self.shard_fallback_rows,
            # the update path: in-place syncs of the item store, the
            # rows they carried (a load counts its whole upload), and
            # the whole-matrix rebuilds of derived state beside them
            "device_syncs": self.Y.device_syncs,
            "rows_synced": self.Y.rows_synced,
            "derived_rebuilds": self.derived_rebuilds,
        }
        part = self.partitioning()
        if part is not None:
            # the layout LSH pruning rests on, and what it saved: rows
            # streamed against the candidates' and the store's
            out["lsh"] = dict(
                part, windows=self.lsh_windows,
                candidate_rows=self.lsh_candidate_rows,
                streamed_rows=self.lsh_streamed_rows,
                row_moves=self.lsh_row_moves)
        # measured-cost route: which kernel path serves this shape and
        # the per-path device costs the choice was made from — the
        # operator-visible answer to "which build ran, at what cost"
        r = self._route
        if r is not None:
            # plus every build that failed at DISPATCH for this shape
            # (a ladder window the measurement never timed): one
            # `errors` table answers "did any kernel fail to lower"
            late = {f"{key[-1]}{'/lsh' if key[4] else ''} B={key[2]}": err
                    for key, err in list(_PALLAS_ERRORS.items())
                    if key[0] == r.get("capacity")
                    and _PALLAS_STATE.get(key) != "ok"}
            if late:
                r = dict(r, errors={**late, **r.get("errors", {})})
            if part is not None:
                r = dict(r, partitioning=part)
            out["kernel_route"] = r
        return out

    def partitioning(self) -> dict | None:
        """How the item store is laid out under LSH (None without):
        hyperplanes, Hamming radius, buckets (37 of 256 inside a ball
        at the reference's 0.3), rows a step, and the steps the store
        has and has given to buckets."""
        if not self._lsh_active():
            return None
        step_bucket, step, _ = self.Y.partition_layout()
        return {"hashes": self.lsh.num_hashes,
                "radius": self.lsh.max_bits_differing,
                "buckets": self.lsh.num_partitions,
                "buckets_a_ball": sum(
                    math.comb(self.lsh.num_hashes, d)
                    for d in range(self.lsh.max_bits_differing + 1)),
                "step_rows": step, "steps": int(len(step_bucket)),
                "steps_assigned": int((step_bucket >= 0).sum())}

    @property
    def lsh_row_moves(self) -> int:
        """Item rows that changed region because a write changed their
        vector's bucket."""
        return self.Y.row_moves

    @property
    def kernel_route_label(self) -> str | None:
        """Compact label of the measured-cost route serving this shape
        (kernel_router.measure_routes' ``chosen`` kind, ``+lsh`` on a
        model whose scans are pruned) — attached to every sampled
        device-execute span by the scoring batcher so a slow trace
        names the phase-A variant that ran.  None before routing has
        measured (or on paths routing cannot time)."""
        r = self._route
        if not r:
            return None
        # a sharded model has one path and says which body it runs
        chosen = r.get("chosen") or r.get("kind")
        if chosen is None:
            return None
        return f"{chosen}+lsh" if r.get("use_lsh") else str(chosen)

    def _lsh_active(self) -> bool:
        """True when this model's LSH configuration actually prunes
        (hashes exist and the Hamming ball is a strict subset): the
        item store is then laid out by bucket.  Always False in sharded
        mode: a row-sharded store keeps rows where they were appended
        (PERF.md section 7), and the sharded exact scan already splits
        the bandwidth bill."""
        return (self._item_shards == 1 and self.lsh is not None
                and self.lsh.num_hashes > 0
                and self.lsh.max_bits_differing < self.lsh.num_hashes)

    # -- IVF ANN path (app/als/ivf.py, ISSUE 18) -----------------------------

    def attach_ann(self, state) -> None:
        """Install the generation's ANN state (ivf.AnnState: centroids
        + recall certificate).  None detaches — the "ivf" kind leaves
        the chain and any mirror is dropped.  The manager calls this
        at model load, BEFORE refresh_route: the route's re-measure
        key includes the ANN shape (_ann_route_key), so an attach is
        what invalidates a cached route."""
        with self._bucket_lock:
            self._ann = state
            self._ivf_mirror = None
            self._ivf_mirror_version = -1

    def _ann_routable(self, n_rows: int) -> bool:
        """True when the "ivf" kind may serve: state attached, the
        per-generation recall certificate measured AND at or above
        ``oryx.als.ann.min-recall``, single-chip, block-aligned
        capacity.  ONE derivation gating the dispatch chain, the
        router, and the warmup — the router can provably never serve
        ANN below min-recall because below it "ivf" is not a kind at
        all."""
        a = self._ann
        return (a is not None and self._item_shards == 1
                and a.recall is not None
                and a.recall >= a.cfg.min_recall
                and n_rows % _BLOCK_ROWS == 0
                and n_rows // _BLOCK_ROWS
                >= int(a.centroids.shape[0]))

    def _ann_route_key(self) -> tuple | None:
        """ANN half of the kernel-route cache key: config shape plus
        whether the certificate currently admits routing.  A new
        generation's certificate flipping either way must force a
        re-measure (the kind chain changed)."""
        a = self._ann
        if a is None:
            return None
        return a.cfg.route_key() + (
            self._ann_routable(len(self.Y.row_ids())),)

    def _cached_ivf(self, vecs, active, version):
        """Cell-contiguous int8 IVF mirror (ivf.IVFMirror), rebuilt
        device-to-device when the Y snapshot version changes — same
        lifecycle as the other phase-A mirrors.  The first build after
        a generation load consumes the trainer-published assignment if
        one shipped; later version bumps reassign on device (same
        centroids, same lowest-index tie-break: same cells)."""
        from . import ivf as _ivf
        with self._bucket_lock:
            a = self._ann
            if a is None:
                raise ValueError("no ANN state attached")
            if self._ivf_mirror is None \
                    or self._ivf_mirror_version != version:
                cells = a.cells if a.cells is not None \
                    and len(a.cells) == int(vecs.shape[0]) else None
                self.derived_rebuilds += 1
                a.cells = None  # one-shot: stale after any store write
                self._ivf_mirror = _ivf.build_mirror(
                    vecs, active, a, _BLOCK_ROWS, cells=cells)
                self._ivf_mirror_version = version
                a.index_bytes = self._ivf_mirror.index_bytes
            return self._ivf_mirror

    def warm_serving_kernels(self, how_many: int = 10,
                             max_batch: int = 1024) -> None:
        """Compile every kernel variant the serving hot path can hit
        for ``how_many``-sized requests before traffic arrives: each
        pow2 batch bucket, and on streaming-path models ALSO the
        exact-scan fallback, so a rare two-phase certificate failure
        costs one extra dispatch instead of a multi-second XLA compile
        inside a live request."""
        b = 8
        while b <= max_batch:
            self.top_n_batch(how_many,
                             np.zeros((b, self.features), np.float32))
            b *= 2
        # the counts of requests a narrow window can hold, placed on
        # the device(s) before traffic: no drain uploads one
        place = self._shard_kernels.count if self._item_shards > 1 \
            else self._count
        for n in range(1, _WINDOW_LADDER[1] + 1):
            place(n)
        # the in-place row sync's ladder of scatter programs: the first
        # UP records of a live model compile nothing
        self.Y.warm_sync()
        if self._item_shards > 1:
            # the loop above ran every window of the ladder through the
            # SPMD program on the live mesh, and that program holds its
            # own exact-scan fallback: nothing is left to compile
            self.refresh_route()
            return
        n_rows = len(self.Y.row_ids())
        k = min(_pad_k(how_many), n_rows)
        big, chunk = _stream_plan(n_rows, _CHUNKED_BATCH)
        pruned = self._lsh_active()
        if pruned or (big and n_rows % chunk == 0 and k <= chunk):
            for w in _WINDOW_LADDER:
                # exact-scan fallback per ladder window shape, so a rare
                # certificate failure costs one extra dispatch, never an
                # in-request XLA compile (a pruned window's loops run to
                # a bound the device computes: one program a shape,
                # however many steps a window visits)
                jax.device_get(self._enqueue_exact(
                    jnp.zeros((w, self.features), jnp.float32), k, chunk,
                    w if pruned else None))
        # measure per-path costs for the live shape and install the
        # route while still pre-traffic: kernel choice is cost-driven,
        # not config-driven, from the first real request on
        self.refresh_route()

    def _cached_penalty(self, active, version) -> jax.Array:
        """Lane-aligned (N//128, 128) f32 additive mask (0 for live
        rows, -inf for retired) for the pallas phase-A kernel,
        recomputed only when the store hands out another mask (a row
        came to life or was retired; ``version`` is not looked at: an
        update of vectors leaves the penalty as it is).  NEVER
        shape this (N, 1): TPU tiling lane-pads that x128 (9.5 GB of
        padding at 20M rows — a measured compile OOM)."""
        with self._bucket_lock:
            if self._penalty is None or self._penalty_src is not active:
                self._penalty = _penalty_kernel(active, _BLOCK_ROWS)
                self._penalty_src = active
            return self._penalty

    def _int8_enabled(self) -> bool:
        if self._int8_selection == "auto":
            # default-on at f <= 64 (ISSUE 3 tentpole): that's where the
            # lane-padded bf16 scan pays a 2-2.56x byte tax AND the fold
            # mirror divides, so the quantized+folded phase A streams
            # ~items x features bytes — the roofline lever.  At
            # 64 < f < 128 the unfolded int8 path measured a wash
            # (bound bookkeeping returns the gain), so auto stays off
            # there; "true" still forces it.
            return (self.features <= 64
                    and self.Y.device_features != self.features)
        return bool(self._int8_selection) and self._int8_selection != "false"

    def _fold_enabled(self) -> bool:
        return bool(self._fold_scan) and self._fold_scan != "false"

    def _cached_fold(self, vecs, active, version, fold: int,
                     bs: int) -> tuple:
        """(Yf, penalty_fold) phase-A fold mirror, recomputed
        device-to-device when the Y snapshot version changes."""
        with self._bucket_lock:
            if self._fold is None or self._fold_version != version:
                self.derived_rebuilds += 1
                self._fold = _fold_items_kernel(vecs, active, fold, bs)
                self._fold_version = version
            return self._fold

    def _cached_i8(self, vecs, version):
        """(Y8, per-block scale, per-block L1) quantization mirror,
        recomputed device-to-device when the Y snapshot version
        changes."""
        with self._bucket_lock:
            if self._i8 is None or self._i8_version != version:
                self.derived_rebuilds += 1
                self._i8 = _quantize_items_kernel(vecs, _BLOCK_ROWS)
                self._i8_version = version
            return self._i8

    def _cached_i8_fold(self, vecs, active, version, fold: int,
                        bs: int) -> tuple:
        """(Y8f, penalty_i_fold, scale, L1) int8+fold
        phase-A mirror.  Quantizes with the SAME kernel as the unfolded
        path (identical scales/L1 norms — the bound algebra must agree)
        but deliberately does NOT go through ``_cached_i8``: the
        unfolded Y8 (full lane width — 2.56 GB at 20M rows) is only an
        intermediate here and must not stay pinned on the model when
        the folded mirror is the one that serves."""
        with self._bucket_lock:
            if self._i8_fold is None or self._i8_fold_version != version:
                self.derived_rebuilds += 1
                y8, sy_b, l1y_b = _quantize_items_kernel(vecs, bs)
                y8f, pen_i_f = _fold_items_i8_kernel(y8, active, fold, bs)
                self._i8_fold = (y8f, pen_i_f, sy_b, l1y_b)
                self._i8_fold_version = version
            return self._i8_fold

    def _evict_unused_mirrors(self, keep_kind: str | None) -> None:
        """Drop the phase-A mirror caches the routed kind does not use.
        Route measurement necessarily materializes EVERY build's mirror
        (the timed program must be the served program); once one kind
        is chosen, the losers' device arrays — up to ~5 GB of int8 /
        bf16 mirrors at 20M rows — must not stay pinned next to the
        store for the model's lifetime.  Version-keyed caches rebuild
        on demand if a fallback ever routes back to an evicted kind."""
        keep = {
            "i8_fold": {"_i8_fold"},
            "i8": {"_i8", "_penalty_i"},
            "fold": {"_fold"},
            "pallas": {"_penalty"},
            "ivf": {"_ivf_mirror"},
        }.get(keep_kind, set())
        with self._bucket_lock:
            for attr, ver in (("_i8", "_i8_version"),
                              ("_i8_fold", "_i8_fold_version"),
                              ("_fold", "_fold_version"),
                              ("_penalty", "_penalty_src"),
                              ("_penalty_i", "_penalty_i_src"),
                              ("_ivf_mirror", "_ivf_mirror_version")):
                if attr not in keep:
                    setattr(self, attr, None)
                    setattr(self, ver, -1)

    def _cached_penalty_i(self, active, version) -> jax.Array:
        with self._bucket_lock:
            if self._penalty_i is None \
                    or self._penalty_i_src is not active:
                self._penalty_i = _penalty_kernel_i32(active, _BLOCK_ROWS)
                self._penalty_i_src = active
            return self._penalty_i

    def _pruning(self, active) -> Pruning:
        """What a pruned program over the resident arrays reads beside
        them (the caller holds the store's dispatch lock, so the table
        of the steps' buckets, read after the sync, is at least as new
        as the rows on the device: a step a bucket took since holds no
        live row there yet)."""
        with self._bucket_lock:
            if self._step_bucket is None \
                    or self._step_bucket_version != self.Y.partition_version \
                    or self._step_live_src is not active:
                table, step, version = self.Y.partition_layout()
                # by the RESIDENT arrays' steps: a store that grew since
                # the sync has steps the device does not hold yet
                n_steps = int(active.shape[0]) // step
                self._step_bucket = jnp.asarray(table[:n_steps])
                self._step_bucket_version = version
                if self._step_live_src is not active:
                    self._step_live = _step_live_kernel(active, n_steps)
                    self._step_live_src = active
            return Pruning(self._step_bucket, self._step_live,
                           self.lsh._device_hyperplanes())

    def _lsh_mask(self, query_vec: np.ndarray | None, active):
        """``active`` held to the rows of the buckets inside the
        query's Hamming ball: a row's bucket is its step's."""
        if query_vec is None or not self._lsh_active():
            return active
        table = self._pruning(active).step_bucket   # -1: in no ball
        ok = self.lsh.candidate_mask(query_vec, table)
        return active & jnp.repeat(ok, active.shape[0] // table.shape[0])

    def top_n(self, how_many: int,
              user_vector: np.ndarray | None = None,
              cosine_to: np.ndarray | None = None,
              exclude: Iterable[str] = (),
              rescorer: Rescorer | None = None,
              allowed: Callable[[str], bool] | None = None,
              lowest: bool = False,
              use_lsh: bool = True) -> list[tuple[str, float]]:
        """Top (or bottom, with ``lowest``) scoring items with scores.

        Exactly one of ``user_vector`` (dot-product scores, the
        reference's DotsFunction) or ``cosine_to`` (mean-cosine scores,
        CosineAverageFunction) selects the kernel.  ``use_lsh=False``
        forces an exact scan even on an LSH-configured model.
        """
        # everything that reads the resident arrays is enqueued inside
        # the store's dispatch lock; what is used after it (scores, the
        # mask) is this request's own
        with self.Y.dispatching() as snap:
            vecs, active, version = snap.vecs, snap.active, snap.version
            if user_vector is not None:
                q = np.asarray(user_vector, dtype=np.float32)
                scores = _dot_scores(vecs, jnp.asarray(q))
                lsh_query = q
            else:
                V = np.asarray(cosine_to, dtype=np.float32)
                if V.ndim == 1:
                    V = V[:, None]
                scores = _cosine_mean_scores(vecs, jnp.asarray(V))
                lsh_query = V.mean(axis=1)
            if lowest:
                scores = -scores
            mask = self._lsh_mask(lsh_query if use_lsh else None, active)
            if mask is active:
                mask = jnp.copy(active)

        exclude = set(exclude)
        if rescorer is not None or allowed is not None:
            # device-side top-M, rescore the M candidates on host: the
            # full score pull is ~80 MB per query at 20M items through
            # whatever transport fronts the chip.  Falls back to the
            # full pull only when filtering eats the whole window
            # (reference: Recommend.java:91-107 streams every candidate
            # through the rescorer; the window form trades that for a
            # bounded fetch — a rescorer can only reorder/filter the
            # top-M pre-rescore candidates unless the fallback runs).
            n_rows = int(vecs.shape[0])
            m = min(_pad_k(max(4 * (how_many + len(exclude)), 512)),
                    n_rows)
            if m < n_rows:
                out = self._rescored_from_window(
                    scores, mask, m, how_many, exclude, rescorer,
                    allowed, lowest)
                if out is not None:
                    return out
            return self._host_top_n(np.asarray(scores), np.asarray(mask),
                                    how_many, exclude, rescorer, allowed,
                                    lowest)
        # pull a padded window to absorb excluded ids, then host-filter
        k = min(_pad_k(how_many + len(exclude)), int(vecs.shape[0]))
        top_scores, top_idx = jax.device_get(_masked_top_k(scores, mask, k))
        out: list[tuple[str, float]] = []
        for s, i in zip(top_scores, top_idx):
            if not math.isfinite(s):
                break
            id_ = self.Y.id_of(int(i))
            if id_ is None or id_ in exclude:
                continue
            out.append((id_, -float(s) if lowest else float(s)))
            if len(out) == how_many:
                break
        if len(out) < how_many and k < int(vecs.shape[0]):
            # excluded set ate into the window; fall back to exact host scan
            return self._host_top_n(np.asarray(scores), np.asarray(mask),
                                    how_many, exclude, None, None, lowest)
        return out

    def top_n_batch(self, how_many: int | Sequence[int],
                    user_vectors: np.ndarray,
                    exclude: Sequence[Iterable[str]] | None = None,
                    use_lsh: bool = True) -> list[list[tuple[str, float]]]:
        """Batched top-N: one device dispatch for a whole batch of
        /recommend requests.  ``user_vectors`` is (B, features);
        ``how_many`` is one size for all requests or one per request;
        ``exclude`` optionally gives per-request excluded item IDs.
        Rescorers/allowed-predicates take the single-request path.

        On an LSH-configured model the same dispatch plans, on the
        device, which steps of the bucket-laid-out store the window's
        queries can reach, and scans those only.  ``use_lsh=False``
        forces the exact scan.

        The batch dimension is zero-padded up to a power of two so the
        request micro-batcher's varying drain sizes hit a handful of
        compiled shapes, and above ~1 GB of score matrix the kernel
        streams item-row chunks with a running top-k carry instead of
        materializing (B, N) scores."""
        Q = np.asarray(user_vectors, dtype=np.float32)
        if Q.ndim != 2 or Q.shape[1] != self.features:
            raise ValueError("user_vectors must be (B, features)")
        n_req = Q.shape[0]
        if n_req == 0:
            return []
        hm = [how_many] * n_req if isinstance(how_many, int) \
            else list(how_many)
        if len(hm) != n_req:
            raise ValueError("one how_many per user vector required")
        excl = [set(e) for e in exclude] if exclude is not None \
            else [set()] * n_req
        if self._item_shards > 1:
            return self._sharded_top_n_batch(hm, Q, excl, use_lsh)
        # the phases of this drain — prepare, scan, a fallback per
        # window whose certificate failed, decode — for the batcher's
        # recorder (obs/trace.py); None, and one branch a site, for
        # every caller that opened none
        rec = obstrace.current_drain()
        with self._drain_snapshot(rec, n_req) as snap:
            vecs, active, version = snap.vecs, snap.active, snap.version
            n_rows = int(vecs.shape[0])
            k = min(_pad_k(max(h + len(e) for h, e in zip(hm, excl))),
                    n_rows)
            # pow2 floor of 8 for the FLAT path sizing decision: a
            # (1,F)x(F,N) matvec hits a much slower XLA path than a small
            # batched matmul, and zero rows are free
            b_pad = 1 << max(3, (n_req - 1).bit_length())
            # a model laid out by bucket scans its candidates only, at
            # every size: there is no flat pruned path
            pruned = use_lsh and self._lsh_active()
            big, chunk = _stream_plan(n_rows, b_pad)
            bs = _BLOCK_ROWS
            ksel = _block_ksel(k, n_rows, bs)
            streaming = pruned or (big and n_rows % chunk == 0
                                   and k <= chunk)
            twophase = streaming and _twophase_admits(k, ksel, vecs, bs) \
                and (not pruned or self._lsh_step % bs == 0)
            attempted: list = []
            if streaming:
                # streaming path: static window shapes from the ladder
                # (computed from the TRUE request count — a 257-query
                # drain is [256, 8], not two full windows), dispatched
                # async before ONE fetch
                sizes = _window_sizes(n_req)
                padded = sum(sizes)
                if n_req < padded:
                    Q = np.concatenate(
                        [Q, np.zeros((padded - n_req, Q.shape[1]),
                                     np.float32)])
                if rec is not None:
                    rec.step("serving.upload", windows=len(sizes),
                             bytes=Q.nbytes)
                # ``reals``: the requests of each window, THE count its
                # program reads: its padding rows reach no bucket (a
                # pruned pass) and phase B neither gathers nor rescores
                # them
                windows, reals, w = [], [], 0
                for size in sizes:
                    windows.append(jnp.asarray(Q[w:w + size]))
                    reals.append(min(size, n_req - w))
                    w += size
                if rec is not None:
                    # from the first program enqueued to the last result
                    # on the host, in three steps: ``serving.launch``
                    # (the enqueues, under the dispatch lock),
                    # ``serving.device_wait`` (until every result is
                    # ready: it waits on the device, and on whatever
                    # other drain the device is running) and
                    # ``serving.fetch`` (_fetch).  ``ksel`` is the
                    # width phase B selects (the int8 builds double it),
                    # 0 where the exact scan is the primary path;
                    # ``lane_rows`` how many of the windows phase A
                    # scored with the store's rows on the lanes;
                    # ``real_rows`` the requests among the windows' rows
                    rec.mark("serving.scan", k=k,
                             ksel=ksel if twophase else 0, windows=sizes,
                             lane_rows=0, real_rows=n_req)
                    rec.step("serving.launch", programs=len(sizes))
                if twophase:
                    handles, attempted = self._dispatch_twophase(
                        vecs, windows, active, version, reals, pruned, k,
                        chunk, bs, ksel)
                    self._note_phase_b(rec, sizes, reals)
                    if rec is not None:
                        # known once each window's build is: a shape
                        # that did not lower ran the lax.scan build
                        rec.annotate(lane_rows=sum(
                            key[-1] == "pallas"
                            and _scores_rows_on_lanes(key[2])
                            for key in attempted))
                else:
                    handles = [
                        self._exact_scan(vecs, qw, active, k, chunk,
                                         reals[w] if pruned else None)
                        for w, qw in enumerate(windows)]
            else:
                if b_pad != n_req:
                    Q = np.concatenate(
                        [Q, np.zeros((b_pad - n_req, Q.shape[1]),
                                     np.float32)])
                if rec is not None:
                    rec.step("serving.upload", windows=1, bytes=Q.nbytes)
                Qd = jnp.asarray(Q)
                if rec is not None:
                    rec.mark("serving.scan", k=k, ksel=0, windows=[b_pad],
                             lane_rows=0, real_rows=n_req)
                    rec.step("serving.launch", programs=1)
                handles = _batch_top_n_kernel(vecs, Qd, active, k)
            if rec is not None:
                # every program of the drain is enqueued; the dispatch
                # lock goes on the way out
                rec.step("serving.device_wait")
        # from here on the handles above are not touched again: a sync
        # may have donated them.  What a fallback needs it fetches anew
        # (_enqueue_exact), and answers from the version it finds
        if twophase:
            fetched = self._fetch_twophase(rec, handles, attempted, windows,
                                           k, chunk, bs, ksel, reals, pruned)
            if pruned:
                self._note_pruned(rec, [f[3] for f in fetched])
            for w, (ts, ti, cert, *_) in enumerate(fetched):
                if not cert.all():
                    # a genuine miss (the margin, or a head block
                    # the approx selection dropped) for some row;
                    # recompute on the exact scan (a pruned window's:
                    # over the same candidates).  Count
                    # per certificate-failing row, under the lock —
                    # batcher dispatcher threads race on this gauge.
                    rows_failed = int((~cert).sum())
                    with self._bucket_lock:
                        self.twophase_fallbacks += rows_failed
                    if rec is not None:
                        rec.mark("serving.fallback", k=k,
                                 width=sizes[w],
                                 rows_failed=rows_failed)
                    ts, ti, *_ = jax.device_get(self._enqueue_exact(
                        windows[w], k, chunk,
                        reals[w] if pruned else None))
                    fetched[w] = (ts, ti, None)
            top_scores = np.concatenate([f[0] for f in fetched])
            top_idx = np.concatenate([f[1] for f in fetched])
        elif streaming:
            fetched = self._fetch(rec, handles)
            if pruned:
                self._note_pruned(rec, [f[2] for f in fetched])
            top_scores = np.concatenate([f[0] for f in fetched])
            top_idx = np.concatenate([f[1] for f in fetched])
        else:
            # fetch both outputs in ONE host round-trip (matters when the
            # device sits behind a high-latency transport)
            top_scores, top_idx = self._fetch(rec, handles)
        if rec is not None:
            rec.mark("serving.decode", rows=n_req)
        return self._decode_top_n(top_scores, top_idx, hm, excl, n_req,
                                  k < n_rows, np.asarray(user_vectors,
                                                         np.float32),
                                  use_lsh)

    @staticmethod
    def _fetch(rec, handles):
        """A drain's results on the host, in ONE fetch.  With a recorder
        open, and only then, the wait for the device and the copy are
        told apart: the running ``serving.device_wait`` step ends when
        every result is ready on the device, and ``serving.fetch``
        (``arrays``, ``bytes``: what crosses) runs from there to the
        drain's next phase.  The copies are started BEFORE that wait,
        as ``device_get`` starts them, so that they follow the programs
        on the device's queue as they do with no recorder: started
        after it, each would cost a round trip of its own that an
        untraced drain never pays (0.6 ms for three arrays, measured).
        Without a recorder this is the one ``device_get`` and no other
        device call."""
        if rec is not None:
            leaves = jax.tree_util.tree_leaves(handles)
            for x in leaves:
                x.copy_to_host_async()
            jax.block_until_ready(handles)
            rec.step("serving.fetch", arrays=len(leaves),
                     bytes=sum(x.nbytes for x in leaves))
        return jax.device_get(handles)

    def _note_phase_b(self, rec, sizes: list, reals: list) -> None:
        """Book a drain whose windows the two-phase program was just
        enqueued for: the rows phase B rescores (a narrow window's
        requests, every row of a wide one: _rescores_requests) against
        the rows of the windows, on the model's counters and on the
        recorder's open ``serving.scan`` phase."""
        rescored = sum(n if _rescores_requests(size) else size
                       for size, n in zip(sizes, reals))
        rows = sum(sizes)
        with self._bucket_lock:
            self.phase_b_rows += rescored
            self.phase_b_window_rows += rows
        if rec is not None:
            rec.annotate(phase_b_row_share=round(100.0 * rescored / rows,
                                                 3))

    def _note_pruned(self, rec, stats: list) -> None:
        """Book a drain's pruned windows from their programs' ``stats``
        ([steps visited, buckets in the union, live rows in them, live
        rows in the store] each): the model's counters, and on the
        recorder's open ``serving.scan`` phase the drain's own numbers,
        summed over its windows."""
        steps, buckets, rows, live = (int(v) for v in np.sum(stats, axis=0))
        streamed = steps * self._lsh_step
        with self._bucket_lock:
            self.lsh_windows += len(stats)
            self.lsh_candidate_rows += rows
            self.lsh_streamed_rows += streamed
        if rec is not None:
            rec.annotate(lsh_buckets=buckets, lsh_candidate_rows=rows,
                         lsh_steps=steps,
                         lsh_streamed_share=round(
                             100.0 * streamed / max(1, live), 3))

    @contextlib.contextmanager
    def _drain_snapshot(self, rec, n_req: int):
        """The resident arrays for one drain's programs, under the
        store's ordering rule: they are fetched and every program that
        reads them is enqueued inside the dispatch lock; results are
        fetched outside it.  Rows the update consumer wrote since the
        last drain go to the device here, in place, before this drain's
        programs: a phase of its own on the recorder ``rec``, first,
        where there are any; then ``serving.prepare``."""
        syncing = rec is not None and self.Y.pending_rows() > 0
        if syncing:
            rec.mark("serving.apply_updates")
        elif rec is not None:
            rec.mark("serving.prepare", rows=n_req)
        with self.Y.dispatching() as snap:
            if syncing:
                rec.annotate(rows=snap.synced_rows, bytes=snap.synced_bytes,
                             version=snap.version,
                             batches=sorted(snap.tags))
                rec.mark("serving.prepare", rows=n_req)
            yield snap

    def _exact_scan(self, vecs, qw, active, k: int, chunk: int,
                    n_real: int | None):
        """Enqueue one window's exact scan (the dispatch lock held):
        over the whole store, or with ``n_real`` (how many of a pruned
        window's rows are requests) over its rows' candidates."""
        if n_real is None:
            return _batch_top_n_chunked_kernel(vecs, qw, active, k, chunk)
        return _batch_top_n_pruned_exact_kernel(
            vecs, qw, active, self._pruning(active), self._count(n_real), k,
            self.lsh.max_bits_differing)

    def _enqueue_exact(self, qw, k: int, chunk: int, n_real: int | None):
        """Enqueue one window's exact scan (``_exact_scan``) over the
        resident arrays as they are NOW (its own acquisition of the
        dispatch lock): the fallback of a drain whose first handles a
        sync may have donated since."""
        with self.Y.dispatching() as snap:
            return self._exact_scan(snap.vecs, qw, snap.active, k, chunk,
                                    n_real)

    def _enqueue_scan_build(self, qw, k: int, chunk: int, bs: int,
                            ksel: int, n_real: int, pruned: bool):
        """Enqueue one window's ``lax.scan`` two-phase build over the
        resident arrays as they are now (see ``_enqueue_exact``)."""
        with self.Y.dispatching() as snap:
            return self._dispatch_kind(
                "scan", qw, snap.vecs, snap.active, snap.version,
                self._pruning(snap.active) if pruned else None, n_real,
                k, bs, ksel, 1, {}, chunk=chunk)

    def _dispatch_twophase(self, vecs, windows, active, version,
                          reals: list, pruned: bool, k: int, chunk: int,
                          bs: int, ksel: int) -> tuple[list, list]:
        """Enqueue every window's two-phase program (async; the caller
        holds the store's dispatch lock) and return the handles with
        the keys of the shapes attempted, for ``_fetch_twophase``.
        Prefers the pallas phase-A builds (scores never leave
        VMEM); substitutes the lax.scan build per WINDOW SHAPE where a
        build cannot lower — routine on the CPU backend, a logged ERROR
        that lands in ``kernel_route.errors`` on a TPU
        (``pallas_failure_level``).  A drain may mix full windows and
        one small tail window, and each shape stands or falls alone.
        ``reals``: how many leading rows of each window are requests;
        ``pruned`` makes every window a pruned one."""
        n_rows = int(vecs.shape[0])
        mb = self.lsh.max_bits_differing if pruned else 0
        static_kinds, fold = self._phase_a_kinds(n_rows,
                                                 int(vecs.shape[1]), bs)

        def key_of(qw, kind):
            # a sharded model's verdicts are its own: the same kernel
            # under shard_map is another program (the shard count rides
            # before the kind, which stays last)
            mesh = (self._item_shards,) if self._item_shards > 1 else ()
            return (n_rows, int(vecs.shape[1]), int(qw.shape[0]),
                    str(vecs.dtype), pruned, k, mb, *mesh, kind)

        ctx: dict = {}
        handles, attempted = [], []
        # fallback chain (_phase_a_kinds — ONE derivation shared with
        # the router, so what is measured is what can be served),
        # reordered by MEASURED ascending cost once measure_routes has
        # timed the live shape (config stops deciding, the stopwatch
        # does); invariant across a drain's windows
        kinds = self._route_order(static_kinds, n_rows, lsh_on=pruned)
        prune = self._pruning(active) if pruned else None
        for w, qw in enumerate(windows):
            dispatched = False
            for kind in kinds:
                key = key_of(qw, kind)
                if _PALLAS_STATE.get(key) == "broken":
                    continue
                try:
                    handles.append(self._dispatch_kind(
                        kind, qw, vecs, active, version, prune, reals[w],
                        k, bs, ksel, fold, ctx, chunk=chunk))
                    attempted.append(key)
                    dispatched = True
                    break
                except Exception as e:  # noqa: BLE001 — classified
                    # compile/lowering failures surface here, at
                    # dispatch, attributed to exactly this shape; a
                    # shape that worked before re-raises
                    _classify_pallas_failure([key], e)
            if not dispatched:
                handles.append(self._dispatch_kind(
                    "scan", qw, vecs, active, version, prune, reals[w],
                    k, bs, ksel, fold, ctx, chunk=chunk))
        return handles, attempted

    def _fetch_twophase(self, rec, handles: list, attempted: list, windows,
                        k: int, chunk: int, bs: int, ksel: int,
                        reals: list, pruned: bool) -> list:
        """ONE fetch for the drain's two-phase programs (``_fetch``),
        outside the dispatch lock."""
        try:
            out = self._fetch(rec, handles)
        except Exception as e:  # noqa: BLE001 — classified below
            fresh = [kk for kk in attempted
                     if _PALLAS_STATE.get(kk) != "ok"]
            if not fresh:
                raise  # every shape worked before: real runtime failure
            # a batched fetch cannot attribute the failure to one
            # window; classify the not-yet-proven shapes (the transient
            # 3-strike counter protects an innocent shape from a single
            # misattribution) and serve the drain on the scan build
            _classify_pallas_failure(fresh, e)
            return jax.device_get([
                self._enqueue_scan_build(qw, k, chunk, bs, ksel, reals[w],
                                         pruned)
                for w, qw in enumerate(windows)])
        for kk in attempted:
            _PALLAS_STATE[kk] = "ok"
        return out

    def _dispatch_kind(self, kind: str, qw, vecs, active, version,
                       prune: Pruning | None, n_real: int, k: int, bs: int,
                       ksel: int, fold: int, ctx: dict, chunk: int = 0):
        """Enqueue ONE window's phase-A build of the given kind and
        return its output handle(s) without blocking.  ``ctx`` caches
        the lazily-built device mirrors across windows of a drain (and
        across the router's timing repetitions).  Shared by the serving
        dispatch and the measured-cost router — the timed program must
        BE the served program.  ``n_real`` of the window's leading rows
        are requests: an argument of the program, never a shape.
        ``prune`` makes the window a pruned one: the two builds that can
        skip steps take it (_phase_a_kinds offers a model under LSH no
        other)."""
        mb = self.lsh.max_bits_differing if prune is not None else 0
        if prune is not None and kind not in ("pallas", "scan"):
            raise ValueError(f"no pruned phase-A kind {kind!r}")
        if self._item_shards > 1:
            # the same two builds of the canonical store's phase A, on
            # every shard's own rows inside the SPMD program
            if kind not in ("pallas", "scan"):
                raise ValueError(f"no sharded phase-A kind {kind!r}")
            if kind == "pallas" and "penalty" not in ctx:
                ctx["penalty"] = self._cached_penalty(active, version)
            return self._shard_kernels.twophase(
                vecs, active, qw, n_real, k, (ksel, chunk, bs),
                ctx["penalty"] if kind == "pallas" else None)
        n_real = self._count(n_real)
        if kind == "i8_fold":
            if "i8_fold" not in ctx:
                ctx["i8_fold"] = self._cached_i8_fold(
                    vecs, active, version, fold, bs)
            y8f, pen_i_f, sy_b, l1y_b = ctx["i8_fold"]
            return _batch_top_n_twophase_pallas_i8_fold(
                vecs, y8f, sy_b, l1y_b, qw, pen_i_f, active, n_real, k,
                bs, _i8_ksel(ksel, int(vecs.shape[0]), bs), fold)
        if kind == "fold":
            if "fold" not in ctx:
                ctx["fold"] = self._cached_fold(
                    vecs, active, version, fold, bs)
            yf, pen_f = ctx["fold"]
            return _batch_top_n_twophase_pallas_fold(
                vecs, yf, qw, pen_f, active, n_real, k, bs, ksel, fold)
        if kind == "i8":
            if "i8" not in ctx:
                ctx["i8"] = (self._cached_i8(vecs, version),
                             self._cached_penalty_i(active, version))
            (y8, sy_b, l1y_b), penalty_i = ctx["i8"]
            return _batch_top_n_twophase_pallas_i8(
                vecs, y8, sy_b, l1y_b, qw, penalty_i, active, n_real, k,
                bs, _i8_ksel(ksel, int(vecs.shape[0]), bs))
        if kind == "pallas":
            if "penalty" not in ctx:
                ctx["penalty"] = self._cached_penalty(active, version)
            return _batch_top_n_twophase_pallas(
                vecs, qw, ctx["penalty"], active, prune, n_real, k, bs,
                ksel, mb)
        if kind == "ivf":
            from . import ivf as _ivf
            if "ivf" not in ctx:
                ctx["ivf"] = self._cached_ivf(vecs, active, version)
            return _ivf.batch_top_n_ivf(
                ctx["ivf"], vecs, qw, k, bs,
                _i8_ksel(ksel, int(vecs.shape[0]), bs),
                self._ann.cfg.nprobe)
        if kind == "scan":
            return _batch_top_n_twophase_kernel(
                vecs, qw, active, prune, n_real, k, chunk, bs, ksel, mb)
        raise ValueError(f"unknown phase-A kind {kind!r}")

    def _count(self, n: int) -> jax.Array:
        """``n`` as an int32 scalar on the device, placed once: what a
        window's program is handed for its count of requests.  Always
        the same strong type (a Python int would trace as a weak one and
        compile the program a second time), and no host scalar, which
        is uploaded on every call, on the dispatching thread (on four
        chips, to each: ``ShardKernelCache.count``)."""
        dev = self._counts.get(n)
        if dev is None:
            dev = self._counts[n] = jnp.asarray(np.int32(n))
        return dev

    # -- measured-cost routing (kernel_router) -------------------------------

    def _phase_a_kinds(self, n_rows: int, width: int,
                       bs: int) -> tuple[list[str], int]:
        """(static fallback chain of phase-A build kinds, fold factor)
        for a streaming shape — the SINGLE derivation shared by the
        serving dispatch and kernel_router.measure_routes, so a new
        build or eligibility gate can never desync what is measured
        from what is served.  Order: int8+fold -> {fold | int8} ->
        bf16/f32 pallas -> lax.scan — fewest phase-A HBM bytes first
        (the cold-start default before any route is measured), with an
        EXPLICIT int8-selection="true" outranking the auto fold (the
        operator opted into the quantized mirror's HBM profile).  The
        lax.scan build is a first-class routable kind: where it
        MEASURES cheapest, routing chooses it rather than merely
        falling back to it.  A sharded model has the canonical store's
        two builds, by the rows ONE shard holds: no mirror is sharded."""
        if self._item_shards > 1:
            tiles = (n_rows // self._item_shards) % _PA_TILE == 0
            return (["pallas"] if tiles else []) + ["scan"], 1
        eligible = n_rows % _PA_TILE == 0
        if self._lsh_active():
            # a store laid out by bucket: the two builds whose grid can
            # be a list of steps.  No mirror is laid out by bucket, so
            # none is offered, measured or built (the layout's step is
            # the tile the model was built with)
            tiles = eligible and self._lsh_step == _PA_TILE
            return (["pallas"] if tiles else []) + ["scan"], 1
        want_i8 = self._int8_enabled()
        fold = _fold_eligible(width, self.features, bs) \
            if self._fold_enabled() else 1
        kinds: list[str] = []
        # IVF heads the static chain where its certificate admits it:
        # it streams ~nprobe/cells of everyone else's bytes.  It is an
        # exact-variant kind only (the Hamming mask and the cell probe
        # are competing pruners — _dispatch_twophase and the router
        # drop it on masked drains)
        if self._ann_routable(n_rows):
            kinds.append("ivf")
        if eligible:
            if want_i8 and fold > 1:
                kinds.append("i8_fold")
            if want_i8 and self._int8_selection == "true":
                kinds.append("i8")
            if fold > 1:
                kinds.append("fold")
            if want_i8 and "i8" not in kinds:
                kinds.append("i8")
            kinds.append("pallas")
        kinds.append("scan")
        return kinds, fold

    def _route_order(self, kinds: list[str], n_rows: int,
                     lsh_on: bool = False) -> list[str]:
        """Reorder the eligible phase-A kinds by MEASURED ascending
        cost for the live shape — using THE DRAIN'S OWN variant's cost
        table (pruning can invert the ranking between builds, so an
        exact drain must not be ordered by pruned costs).  Kinds
        without a measurement keep their static order after the
        measured ones.  No route yet (or a route for a different
        capacity) leaves the static order untouched."""
        r = self._route_current(n_rows)
        if not r:
            return kinds
        costs = (r.get("costs_lsh_ms") if lsh_on
                 else r.get("costs_exact_ms")) \
            or r.get("phase_a_costs_ms") or {}
        measured = [kk for kk in kinds if costs.get(kk) is not None]
        if not measured:
            return kinds
        measured.sort(key=lambda kk: costs[kk])
        return measured + [kk for kk in kinds if costs.get(kk) is None]

    def refresh_route(self, batch: int | None = None, m: int = 3,
                      force: bool = False) -> dict | None:
        """Measure per-path device cost for the live shape and install
        the route (kernel_router.measure_routes).  Called at model load
        and on hot-swap; concurrent callers serialize and the loser
        reuses the winner's fresh measurement.  A cached route is
        reused while the padded capacity AND the LSH configuration are
        unchanged (kernel cost is a property of the compiled shape, not
        of UP-stream version bumps); ``force`` re-measures anyway."""
        from .kernel_router import measure_routes
        if self._item_shards > 1:
            return self._refresh_sharded_route()
        with self._route_lock:
            n_rows = len(self.Y.row_ids())
            r = self._route
            if (not force and r is not None
                    and self._route_capacity == n_rows
                    and r.get("lsh_configured") == self._lsh_active()
                    and r.get("ann_key") == self._ann_route_key()):
                return r
            try:
                route = measure_routes(self, batch=batch, m=m)
            except Exception as e:  # noqa: BLE001 — never a load gate
                # a failure here (device OOM building a mirror) must
                # NOT abort the MODEL consume — an escaped exception
                # would trap the update consumer in replay-from-0
                # against the same deterministic failure.  Serving
                # continues on the static config-driven chain, and the
                # failure is published where the route would have
                # been: an unmeasured stub (capacity -1, so
                # _route_current ignores it and the next load
                # re-measures) whose `errors` an operator — and
                # chip_smoke.py — can see.
                _log.exception(
                    "kernel route measurement failed; serving keeps "
                    "the static config-driven kernel order")
                self._route = {
                    "measured": False,
                    "errors": {"measure_routes": error_text(e)}}
                self._route_capacity = -1
                return None
            self._route = route
            self._route_capacity = n_rows
            self._evict_unused_mirrors(
                (route or {}).get("chosen") if (route or {}).get(
                    "path") == "streaming" else None)
        return route

    def _route_current(self, n_rows: int) -> dict | None:
        """The installed route if it matches the live padded capacity
        AND LSH configuration (a hot-swap that regrew the store, or a
        re-configured sample rate, invalidates it)."""
        r = self._route
        return r if (r is not None and self._route_capacity == n_rows
                     and r.get("lsh_configured") == self._lsh_active()
                     and r.get("ann_key") == self._ann_route_key()) \
            else None

    def _refresh_sharded_route(self) -> dict | None:
        """What a sharded model reports where the one-chip model reports
        its measured route: the body the SPMD program runs on every
        shard at the live capacity, read off the mesh and the store.
        Nothing is measured and nothing configured: a shard has one
        path."""
        n_rows = len(self.Y.row_ids())
        store = jax.ShapeDtypeStruct((n_rows, self.Y.device_features),
                                     self.Y.dtype)
        plan = shard_plan(store, self._item_shards,
                          min(_pad_k(1), n_rows), _WINDOW_LADDER[0])
        route = {"kind": "sharded_twophase" if plan else "sharded_flat",
                 "shards": self._item_shards, "capacity": n_rows}
        with self._route_lock:
            self._route, self._route_capacity = route, n_rows
        return route

    def _sharded_top_n_batch(self, hm: list[int], Q: np.ndarray,
                             excl: list[set[str]],
                             use_lsh: bool) -> list[list[tuple[str, float]]]:
        """Batched top-N over the mesh-sharded item matrix, one SPMD
        program a window (parallel/serving_dist.py, the builder shared
        with ShardedItemScorer): every shard scans its own rows, one
        all_gather, an on-device merge.  Where a shard is large enough
        (``shard_plan``: the one-chip conditions on one shard's rows)
        the windows are the one-chip ladder's and the scan the
        two-phase one, its builds tried and substituted as on one chip
        (``_dispatch_twophase``); a failed certificate is answered
        inside the program, by that shard's exact scan, and only
        counted here.  The drain's phases are the one-chip drain's."""
        n_req = Q.shape[0]
        rec = obstrace.current_drain()
        kernels, shards = self._shard_kernels, self._item_shards
        with self._drain_snapshot(rec, n_req) as snap:
            vecs, active = snap.vecs, snap.active
            n_rows = int(vecs.shape[0])
            k = min(_pad_k(max(h + len(e) for h, e in zip(hm, excl))),
                    n_rows)
            b_pad = _pad_k(n_req)
            plan = shard_plan(vecs, shards, k, b_pad)
            sizes = _window_sizes(n_req) if plan is not None else [b_pad]
            if n_req < sum(sizes):
                Q = np.concatenate(
                    [Q, np.zeros((sum(sizes) - n_req, Q.shape[1]),
                                 np.float32)])
            if rec is not None:
                rec.mark("serving.scan", shards=shards, k=k,
                         ksel=plan.ksel if plan is not None else 0,
                         windows=sizes, lane_rows=0, real_rows=n_req)
                # the one-chip drain's steps; the windows go to every
                # device of the mesh, inside this phase
                rec.step("serving.upload", windows=len(sizes),
                         bytes=Q.nbytes * shards)
            windows, reals, w = [], [], 0
            for size in sizes:
                windows.append(kernels.replicate(Q[w:w + size]))
                reals.append(min(size, n_req - w))
                w += size
            if rec is not None:
                rec.step("serving.launch", programs=len(sizes))
            if plan is None:
                handles = kernels.flat(vecs, active, windows[0], k)
            else:
                handles, attempted = self._dispatch_twophase(
                    vecs, windows, active, snap.version, reals, False, k,
                    plan.chunk, plan.bs, plan.ksel)
                self._note_phase_b(rec, sizes, reals)
                if rec is not None:
                    rec.annotate(lane_rows=sum(
                        _scores_rows_on_lanes(key[2]) for key in attempted
                        if key[-1] == "pallas"))
            if rec is not None:
                rec.step("serving.device_wait")
        if plan is None:
            top_scores, top_idx = self._fetch(rec, handles)
        else:
            fetched = self._fetch_twophase(
                rec, handles, attempted, windows, k, plan.chunk, plan.bs,
                plan.ksel, reals, False)
            failed = [~f[2] for f in fetched]       # (shards, B) a window
            with self._bucket_lock:
                self.sharded_windows += len(fetched)
                self.shard_fallback_rows += sum(int(f.sum())
                                                for f in failed)
                self.twophase_fallbacks += sum(int(f.any(0).sum())
                                               for f in failed)
            top_scores = np.concatenate([f[0] for f in fetched])
            top_idx = np.concatenate([f[1] for f in fetched])
        if rec is not None:
            rec.mark("serving.decode", rows=n_req)
        window = min(k, top_scores.shape[1])
        return self._decode_top_n(top_scores, top_idx, hm, excl, n_req,
                                  window < n_rows, Q, use_lsh)

    def _decode_top_n(self, top_scores, top_idx, hm: list[int],
                      excl: list[set[str]], n_req: int, window_partial: bool,
                      Q: np.ndarray,
                      use_lsh: bool) -> list[list[tuple[str, float]]]:
        """Host decode shared by the flat, streaming and sharded batched
        paths: map rows to ids, drop excluded/retired rows, and retry a
        request on the single-request path when its exclusions ate the
        whole fetched window (only possible when the window was smaller
        than the full item count)."""
        row_ids = self.Y.row_ids()
        results: list[list[tuple[str, float]]] = []
        for b in range(n_req):
            out: list[tuple[str, float]] = []
            for s, i in zip(top_scores[b].tolist(), top_idx[b].tolist()):
                if not math.isfinite(s):
                    break
                id_ = row_ids[i]
                if id_ is None or id_ in excl[b]:
                    continue
                out.append((id_, s))
                if len(out) == hm[b]:
                    break
            if len(out) < hm[b] and window_partial:
                out = self.top_n(hm[b], user_vector=Q[b],
                                 exclude=excl[b], use_lsh=use_lsh)
            results.append(out)
        return results

    def _rescored_from_window(self, scores, mask, m: int, how_many: int,
                              exclude: set[str],
                              rescorer: Rescorer | None,
                              allowed: Callable[[str], bool] | None,
                              lowest: bool) -> list[tuple[str, float]] | None:
        """Rescore/filter the device top-``m`` window; None when the
        filters ate the window without filling ``how_many`` AND more
        candidates exist beyond it (caller falls back to the full
        pull).  A window that contained every live candidate is final
        regardless of fill."""
        ts, ti = jax.device_get(_masked_top_k(scores, mask, m))
        out: list[tuple[str, float]] = []
        exhausted = False
        for s, i in zip(ts.tolist(), ti.tolist()):
            if not math.isfinite(s):
                exhausted = True  # -inf tail: no candidates remain
                break
            id_ = self.Y.id_of(int(i))
            if id_ is None or id_ in exclude:
                continue
            if allowed is not None and not allowed(id_):
                continue
            score = -float(s) if lowest else float(s)
            if rescorer is not None:
                if rescorer.is_filtered(id_):
                    continue
                score = rescorer.rescore(id_, score)
                if math.isnan(score):
                    continue
            out.append((id_, score))
        if len(out) < how_many and not exhausted:
            return None
        out.sort(key=lambda t: t[1] if lowest else -t[1])
        return out[:how_many]

    def _host_top_n(self, scores: np.ndarray, mask: np.ndarray,
                    how_many: int, exclude: set[str],
                    rescorer: Rescorer | None,
                    allowed: Callable[[str], bool] | None,
                    lowest: bool) -> list[tuple[str, float]]:
        """Exact host-side top-N.  ``scores`` arrive already negated when
        ``lowest``; emitted scores are restored to original sign, so the
        final rescored ordering must ascend for lowest."""
        order = np.argsort(-scores)
        out: list[tuple[str, float]] = []
        for i in order:
            if not mask[i] or not math.isfinite(scores[i]):
                continue
            id_ = self.Y.id_of(int(i))
            if id_ is None or id_ in exclude:
                continue
            if allowed is not None and not allowed(id_):
                continue
            score = -float(scores[i]) if lowest else float(scores[i])
            if rescorer is not None:
                if rescorer.is_filtered(id_):
                    continue
                score = rescorer.rescore(id_, score)
                if math.isnan(score):
                    continue
            out.append((id_, score))
            if rescorer is None and len(out) == how_many:
                return out
        if rescorer is not None:
            out.sort(key=lambda t: t[1] if lowest else -t[1])
            return out[:how_many]
        return out

    # -- misc queries --------------------------------------------------------

    def all_user_ids(self) -> list[str]:
        return self.X.all_ids()

    def all_item_ids(self) -> list[str]:
        return self.Y.all_ids()

    def __repr__(self):  # pragma: no cover
        return (f"ALSServingModel[features:{self.features}, "
                f"X:({len(self.X)} users), Y:({len(self.Y)} items)]")
