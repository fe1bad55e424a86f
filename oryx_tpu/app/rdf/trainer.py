"""Random decision forest trainer: level-synchronous histogram splits
in JAX.

Capability parity with the reference's batch trainer (app/oryx-app-mllib/
.../rdf/RDFUpdate.java:141-163, which delegates to Spark MLlib
``RandomForest.trainClassifier/trainRegressor`` with maxBins =
max-split-candidates, impurity gini/entropy/variance, per-tree
bootstrap, and "auto" feature subsetting = sqrt(P) for classification,
P/3 for regression), re-designed for TPU:

* All trees grow together, level by level.  Each level is two fused
  device passes — a weighted histogram scatter-add over
  (tree, node, predictor, bin[, class]) and a vectorized best-split
  scan over the cumulative histograms — instead of MLlib's shuffle-
  based node aggregation.  No data-dependent control flow; shapes per
  level depend only on the (padded) frontier width, so XLA caches one
  executable per level width.
* Numeric features are pre-binned once into ``max_split_candidates``
  quantile bins (exactly MLlib's binning role); categorical features
  use their encodings as bins and are split by the classic
  ordered-category trick (sort categories by class-0 probability /
  mean target, scan prefixes).
* Bootstrap = Poisson(1) example weights per tree, the standard
  vectorized equivalent of sampling with replacement.

The output is host `DecisionTree`s (tree.py) — the mutable/serializable
model form — with PMML record counts and feature importances collected
LIVE per level from the frontier occupancy (every example's node is in
slot_of already; re-routing the training set after the build measured
44 s of a 72 s warm build), mirroring RDFUpdate.treeNodeExampleCounts /
predictorExampleCounts.
"""

from __future__ import annotations

import logging
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import shard_map

import numpy as np

from ...common.rand import RandomManager
from ..classreg import CategoricalPrediction, NumericPrediction
from ..schema import InputSchema
from .tree import (CategoricalDecision, DecisionForest, DecisionNode,
                   DecisionTree, NumericDecision, TerminalNode)

_log = logging.getLogger(__name__)

__all__ = ["train_forest", "IMPURITIES"]

IMPURITIES = ("gini", "entropy", "variance")


# -- device kernels -----------------------------------------------------------

# samples per matmul tile in the histogram scan; bounds the one-hot
# slot matrix to [CHUNK, M] and the bin/class tensor to [CHUNK, P*S*C]
_HIST_CHUNK = 1 << 16


def _chunk_examples(num_b: int, cap: int, *arrays):
    """Shared example-axis chunking for the level kernels: pick the
    chunk size (small inputs must not pay for a full tile), pad the
    example axis (slot arrays use -1 = settled as the pad sentinel),
    and reshape each array to [n_chunks, ...].  Arrays are passed as
    (array, example_axis, pad_value) triples."""
    chunk = min(cap, 1 << max(0, (num_b - 1).bit_length()))
    n_chunks = -(-num_b // chunk)
    pad = n_chunks * chunk - num_b
    out = []
    for arr, axis, pad_value in arrays:
        if pad:
            widths = [(0, 0)] * arr.ndim
            widths[axis] = (0, pad)
            arr = jnp.pad(arr, widths, constant_values=pad_value)
        if axis == 0:
            out.append(arr.reshape((n_chunks, chunk) + arr.shape[1:]))
        else:  # [T, B] -> [NC, T, CH]
            out.append(jnp.moveaxis(
                arr.reshape(arr.shape[0], n_chunks, chunk), 1, 0))
    return chunk, out


def _histogram_body(binned, ychan, w, slot_of, num_slots: int,
                    num_bins: int, exact_lowp: bool):
    """Weighted per-(tree, slot, predictor, bin) stats.

    binned:  [B, P] int32   pre-binned predictor values
    ychan:   [B, C] f32     per-class one-hot, or (1, y, y^2) channels
    w:       [T, B] f32     bootstrap weights
    slot_of: [T, B] int32   frontier slot per sample, -1 = settled
    returns  [T, M, P, S, C]

    MXU formulation: the triple one-hot contraction
    hist[m,p,s,c] = sum_b w[b]*[slot=m]*[bin(p)=s]*y[b,c] is computed
    as (one_hot(slot)*w)^T @ (one_hot(bins) x ychan) — matmuls per
    sample tile with f32 accumulation.  A segment_sum formulation
    lowers to TPU scatters and measured ~30x slower at bench scale.
    The chunk scan is the OUTER loop so the bin/class expansion Ey
    (the largest tensor, tree-invariant) is built once per chunk and
    shared by every tree's matmul.
    ``exact_lowp``: classification inputs (0/1 one-hots, small integer
    Poisson weights) are exact in bfloat16, which doubles MXU rate;
    regression channels carry arbitrary floats and must stay f32 —
    callers must choose explicitly.
    """
    num_b, num_p = binned.shape
    num_c = ychan.shape[1]
    num_t = w.shape[0]
    dt = jnp.bfloat16 if exact_lowp else jnp.float32
    chunk, (br, yr, wr, sr) = _chunk_examples(
        num_b, _HIST_CHUNK, (binned, 0, 0), (ychan, 0, 0.0),
        (w, 1, 0.0), (slot_of, 1, -1))

    def chunk_step(acc, xs):
        b_c, y_c, w_c, s_c = xs      # [CH,P], [CH,C], [T,CH], [T,CH]
        E = jax.nn.one_hot(b_c, num_bins, dtype=dt)  # [CH, P, S]
        Ey = (E[:, :, :, None] * y_c.astype(dt)[:, None, None, :]
              ).reshape(chunk, num_p * num_bins * num_c)

        def per_tree(w_t, s_t):
            alive = s_t >= 0
            wt = jnp.where(alive, w_t, 0.0).astype(dt)
            S = jax.nn.one_hot(jnp.where(alive, s_t, 0), num_slots,
                               dtype=dt) * wt[:, None]
            return jnp.matmul(S.T, Ey,
                              preferred_element_type=jnp.float32)

        # lax.map (not vmap) over trees bounds peak memory to one
        # [CH, M] slot matrix at a time alongside the shared Ey
        contrib = jax.lax.map(lambda a: per_tree(*a), (w_c, s_c))
        return acc + contrib, None

    # seed the carry from input data (+0) so that under shard_map its
    # varying-axes type matches the loop output's — a plain zeros
    # literal is device-invariant and newer JAX rejects the mismatch
    acc0 = jnp.zeros((num_t, num_slots, num_p * num_bins * num_c),
                     jnp.float32) + (w[0, 0] * 0).astype(jnp.float32)
    acc, _ = jax.lax.scan(chunk_step, acc0, (br, yr, wr, sr))
    return acc.reshape(num_t, num_slots, num_p, num_bins, num_c)


_histograms = partial(jax.jit, static_argnums=(4, 5, 6))(_histogram_body)


@lru_cache(maxsize=64)
def _dist_histograms_fn(mesh, axis: str, num_slots: int, num_bins: int,
                        exact_lowp: bool):
    """Data-parallel histograms over a device mesh: examples are
    row-sharded, each device aggregates its shard's stats, and one
    psum over ICI replaces MLlib's node-stats shuffle.  The replicated
    result feeds the (cheap) split scan identically on every device."""
    from jax.sharding import PartitionSpec as P

    def inner(binned, ychan, w, slot_of):
        local = _histogram_body(binned, ychan, w, slot_of,
                                num_slots, num_bins, exact_lowp)
        return jax.lax.psum(local, axis)

    return jax.jit(shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(None, axis),
                  P(None, axis)),
        out_specs=P()))


def _impurity(stats, kind: str):
    """stats [..., C] -> (count, impurity) with the channel convention
    above."""
    if kind == "variance":
        n = stats[..., 0]
        safe = jnp.maximum(n, 1e-12)
        mean = stats[..., 1] / safe
        imp = stats[..., 2] / safe - mean * mean
    else:
        n = stats.sum(-1)
        p = stats / jnp.maximum(n[..., None], 1e-12)
        if kind == "gini":
            imp = 1.0 - (p * p).sum(-1)
        else:  # entropy (nats)
            imp = -(p * jnp.where(p > 0, jnp.log(jnp.maximum(p, 1e-12)),
                                  0.0)).sum(-1)
    return n, jnp.maximum(imp, 0.0)


@partial(jax.jit, static_argnums=(3, 4))
def _best_splits(hist, is_cat_p, feat_mask, impurity: str, k_features: int):
    """Scan every (predictor, split point) for every (tree, slot).

    hist:      [T, M, P, S, C]
    is_cat_p:  [P] bool
    feat_mask: [T, M, P] f32 uniforms for per-node feature subsetting
    returns (gain, best_p, best_b, default_right, right_mask [T,M,S],
             totals [T,M,C])
    """
    num_bins = hist.shape[3]
    totals = hist[:, :, 0].sum(2)                       # [T, M, C]
    parent_n, parent_imp = _impurity(totals, impurity)  # [T, M]

    # order bins: identity for numeric; score-sorted for categorical
    if impurity == "variance":
        score = hist[..., 1] / jnp.maximum(hist[..., 0], 1e-12)
    else:
        score = hist[..., 0] / jnp.maximum(hist.sum(-1), 1e-12)
    order = jnp.argsort(score, axis=3)                  # [T, M, P, S]
    order = jnp.where(is_cat_p[None, None, :, None], order,
                      jnp.arange(num_bins)[None, None, None, :])
    sorted_hist = jnp.take_along_axis(hist, order[..., None], axis=3)

    cum = jnp.cumsum(sorted_hist, axis=3)               # [T, M, P, S, C]
    left = cum[:, :, :, :-1]                            # prefixes
    right = totals[:, :, None, None] - left
    n_left, imp_left = _impurity(left, impurity)
    n_right, imp_right = _impurity(right, impurity)
    n = jnp.maximum(parent_n[:, :, None, None], 1e-12)
    gain = parent_imp[:, :, None, None] - \
        (n_left * imp_left + n_right * imp_right) / n   # [T, M, P, S-1]
    gain = jnp.where((n_left > 0) & (n_right > 0), gain, -jnp.inf)

    # per-(tree, slot) random feature subset of size k ("auto" strategy)
    kth = jnp.sort(feat_mask, axis=2)[:, :, k_features - 1]
    selected = feat_mask <= kth[:, :, None]             # [T, M, P]
    gain = jnp.where(selected[..., None], gain, -jnp.inf)

    flat = gain.reshape(gain.shape[0], gain.shape[1], -1)
    best = jnp.argmax(flat, axis=2)
    best_gain = jnp.take_along_axis(flat, best[..., None], axis=2)[..., 0]
    best_p = best // (num_bins - 1)
    best_b = best % (num_bins - 1)

    # gather chosen feature's split data
    take_p = best_p[:, :, None, None]                   # [T, M, 1, 1]

    def _at_best(arr):  # [T, M, P, S'] -> [T, M] at (best_p, best_b)
        by_p = jnp.take_along_axis(
            arr, jnp.broadcast_to(take_p, arr.shape[:2] + (1, arr.shape[3])),
            axis=2)[:, :, 0]                            # [T, M, S']
        return jnp.take_along_axis(by_p, best_b[:, :, None], axis=2)[..., 0]

    default_right = _at_best(n_right) > _at_best(n_left)

    order_best = jnp.take_along_axis(
        order, jnp.broadcast_to(take_p, order.shape[:2] + (1, num_bins)),
        axis=2)[:, :, 0]                                # [T, M, S]
    rank = jnp.argsort(order_best, axis=2)              # invert permutation
    right_mask = rank > best_b[:, :, None]              # [T, M, S]

    return best_gain, best_p, best_b, default_right, right_mask, totals


# samples per matmul tile in the advance scan; bounds the one-hot slot
# matrix to [CHUNK, M] alongside the shared chunk of binned values
_ADV_CHUNK = 1 << 16


def _advance_body(slot_of, binned, split, best_p, best_b, is_cat_slot,
                  right_mask, child_slots):
    """Route samples to child slots (or settle them at leaves).

    slot_of [T, B], binned [B, P], split/best_p/best_b/is_cat_slot
    [T, M], right_mask [T, M, S], child_slots [T, M, 2] -> new [T, B]

    MXU formulation mirroring the histogram kernel: per-slot decision
    data packs into one [M, 6+S] table fetched per example by a one-hot
    matmul, and the per-example feature/bin selections are one-hot
    contractions over P and S.  The straightforward per-example
    take_along_axis gathers lower to TPU element gathers and measured
    1.6 s PER LEVEL at bench scale (900k x 20 trees) — ~20x this form.
    All values rounding through the f32 matmul are small exact
    integers/booleans, so routing is bit-identical to the gather form.
    """
    num_t, num_b = slot_of.shape
    num_p = binned.shape[1]
    num_m = split.shape[1]
    num_s = right_mask.shape[2]
    table = jnp.concatenate([
        split[:, :, None].astype(jnp.float32),
        best_p[:, :, None].astype(jnp.float32),
        best_b[:, :, None].astype(jnp.float32),
        is_cat_slot[:, :, None].astype(jnp.float32),
        child_slots.astype(jnp.float32),
        right_mask.astype(jnp.float32),
    ], axis=2)                                          # [T, M, 6+S]
    _, (br, sr) = _chunk_examples(num_b, _ADV_CHUNK, (binned, 0, 0),
                                  (slot_of, 1, -1))
    p_iota = jnp.arange(num_p, dtype=jnp.float32)
    s_iota = jnp.arange(num_s, dtype=jnp.float32)

    def chunk_step(carry, xs):
        b_c, s_c = xs                           # [CH, P], [T, CH]
        bf = b_c.astype(jnp.float32)

        def per_tree(slot_t, table_t):
            alive = slot_t >= 0
            oh = jax.nn.one_hot(jnp.where(alive, slot_t, 0), num_m,
                                dtype=jnp.float32)       # [CH, M]
            # HIGHEST precision: the TPU's default matmul pass
            # truncates f32 operands to bfloat16, which rounds child
            # slot ids above 256 — exact f32 passes keep every table
            # value (ids up to 2*M) bit-exact
            row = jnp.matmul(oh, table_t,
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
            feat, thr_b, cat = row[:, 1], row[:, 2], row[:, 3]
            bin_val = jnp.sum(
                jnp.where(feat[:, None] == p_iota[None, :], bf, 0.0),
                axis=1)
            numeric_right = bin_val > thr_b
            cat_right = jnp.sum(
                jnp.where(bin_val[:, None] == s_iota[None, :],
                          row[:, 6:], 0.0), axis=1) > 0.5
            went_right = jnp.where(cat > 0.5, cat_right, numeric_right)
            child = jnp.where(went_right, row[:, 5], row[:, 4])
            return jnp.where(alive & (row[:, 0] > 0.5),
                             child.astype(jnp.int32), -1)

        # lax.map (not vmap) over trees bounds peak memory to one
        # [CH, M] one-hot at a time (histogram-kernel rationale)
        out = jax.lax.map(lambda a: per_tree(*a), (s_c, table))
        return carry, out

    _, outs = jax.lax.scan(chunk_step, None, (br, sr))  # [NC, T, CH]
    return jnp.moveaxis(outs, 0, 1).reshape(num_t, -1)[:, :num_b]


_advance = jax.jit(_advance_body)


def _slot_counts_body(slot_of, num_slots: int):
    """Unweighted examples per (tree, slot): the node example counts
    the reference derives by re-routing the FULL training set
    (RDFUpdate.treeNodeExampleCounts) — here every example's node is
    already in slot_of each level, so counts are one chunked one-hot
    sum instead of a post-hoc 900k x trees re-route (measured 44 s of
    a 72 s warm build before this)."""
    num_t, num_b = slot_of.shape
    _, (sr,) = _chunk_examples(num_b, _ADV_CHUNK, (slot_of, 1, -1))

    def chunk_step(acc, s_c):
        def per_tree(slot_t):
            alive = slot_t >= 0
            # int32 accumulation: counts are PMML record counts and
            # must stay exact past 2^24 examples per node (f32 one-hot
            # sums saturate there)
            oh = jax.nn.one_hot(jnp.where(alive, slot_t, 0), num_slots,
                                dtype=jnp.int32)
            return jnp.sum(jnp.where(alive[:, None], oh, 0), axis=0)

        return acc + jax.lax.map(per_tree, s_c), None

    # seed the carry from input data (+0) so that under shard_map its
    # varying-axes type matches the loop output's (histogram-kernel
    # rationale: a device-invariant literal carry is rejected)
    acc0 = jnp.zeros((num_t, num_slots), jnp.int32) + slot_of[0, 0] * 0
    acc, _ = jax.lax.scan(chunk_step, acc0, sr)
    return acc


_slot_counts = partial(jax.jit, static_argnums=(1,))(_slot_counts_body)


@lru_cache(maxsize=16)
def _dist_slot_counts_fn(mesh, axis: str, num_slots: int):
    """Sharded per-slot example counts: local one-hot sums + one psum."""
    from jax.sharding import PartitionSpec as P

    def body(slot_of):
        local = _slot_counts_body(slot_of, num_slots)
        return jax.lax.psum(local, axis)

    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(None, axis),), out_specs=P()))


@lru_cache(maxsize=16)
def _dist_advance_fn(mesh, axis: str):
    """Sharded routing step: purely per-sample, no collectives."""
    from jax.sharding import PartitionSpec as P

    return jax.jit(shard_map(
        _advance_body, mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)) + (P(),) * 6,
        out_specs=P(None, axis)))


# -- binning ------------------------------------------------------------------

def _bin_features(x: np.ndarray, is_cat: np.ndarray, num_bins: int):
    """Pre-bin predictors: quantile cut points for numeric features
    (MLlib's findSplits role), identity encodings for categorical."""
    binned = np.zeros_like(x, dtype=np.int32)
    thresholds = np.zeros((x.shape[1], num_bins - 1), dtype=np.float64)
    for p in range(x.shape[1]):
        col = x[:, p]
        if is_cat[p]:
            binned[:, p] = col.astype(np.int32)
            continue
        qs = np.quantile(col, np.linspace(0.0, 1.0, num_bins + 1)[1:-1])
        thresholds[p] = qs
        binned[:, p] = np.searchsorted(qs, col, side="right")
    return binned, thresholds


# -- the training loop --------------------------------------------------------

def train_forest(x: np.ndarray, y: np.ndarray, schema: InputSchema,
                 category_counts: dict[int, int], num_trees: int,
                 max_depth: int, max_split_candidates: int,
                 impurity: str, seed: int | None = None,
                 num_classes: int | None = None,
                 mesh=None, mesh_axis: str = "d",
                 timings: dict | None = None) -> DecisionForest:
    """Train a forest on predictors ``x`` [B, P] (categorical values as
    encodings) and targets ``y`` (class encodings or regression values).

    ``category_counts`` maps predictor index -> number of categories.
    With ``mesh``, examples are sharded over the mesh axis and the
    per-level histogram reduction runs as a psum over ICI (data
    parallelism; split selection replicates).
    """
    if impurity not in IMPURITIES:
        raise ValueError(f"bad impurity: {impurity}")
    classification = schema.is_classification()
    if classification == (impurity == "variance"):
        raise ValueError(f"impurity {impurity} does not match problem type")
    if max_split_candidates < 2:
        raise ValueError("max-split-candidates must be at least 2")
    if max_depth < 1:
        raise ValueError("max-depth must be at least 1")
    batch, num_p = x.shape
    if batch == 0:
        raise ValueError("no training data")

    import time as _time

    def _mark(stage: str, t0: float) -> float:
        # optional stage-time decomposition for the bench artifact;
        # device work is async, so each device_get absorbs pending
        # kernel time into its stage
        now = _time.perf_counter()
        if timings is not None:
            timings[stage] = timings.get(stage, 0.0) + (now - t0)
        return now

    t0 = _time.perf_counter()

    is_cat = np.zeros(num_p, dtype=bool)
    for p, count in category_counts.items():
        is_cat[p] = True
        if count > max_split_candidates:
            raise ValueError(
                f"categorical predictor {p} has {count} values > "
                f"max-split-candidates {max_split_candidates}")

    num_bins = int(max_split_candidates)
    binned_np, thresholds = _bin_features(x, is_cat, num_bins)
    binned = jnp.asarray(binned_np)
    t0 = _mark("bin_features", t0)

    if classification:
        if num_classes is None:
            num_classes = int(np.max(y)) + 1
        ychan = jax.nn.one_hot(jnp.asarray(y, dtype=jnp.int32),
                               num_classes, dtype=jnp.float32)
        k_features = max(1, int(math.ceil(math.sqrt(num_p))))
    else:
        yj = jnp.asarray(y, dtype=jnp.float32)
        ychan = jnp.stack([jnp.ones_like(yj), yj, yj * yj], axis=1)
        k_features = max(1, num_p // 3)

    key = jax.random.PRNGKey(
        RandomManager.random_seed() if seed is None else seed)
    w = jax.random.poisson(key, 1.0, (num_trees, batch)).astype(jnp.float32)

    slot_of = jnp.zeros((num_trees, batch), dtype=jnp.int32)

    if mesh is not None:
        # pad the example axis to the mesh size; padding rows have
        # weight 0 and slot -1, so they never contribute
        n_dev = mesh.devices.size
        pad = (-batch) % n_dev
        if pad:
            binned = jnp.pad(binned, ((0, pad), (0, 0)))
            ychan = jnp.pad(ychan, ((0, pad), (0, 0)))
            w = jnp.pad(w, ((0, 0), (0, pad)))
            slot_of = jnp.pad(slot_of, ((0, 0), (0, pad)),
                              constant_values=-1)
        from jax.sharding import NamedSharding, PartitionSpec as P
        row = NamedSharding(mesh, P(mesh_axis))
        col = NamedSharding(mesh, P(None, mesh_axis))
        binned = jax.device_put(binned, row)
        ychan = jax.device_put(jnp.asarray(ychan), row)
        w = jax.device_put(w, col)
        slot_of = jax.device_put(slot_of, col)
    t0 = _mark("init_upload", t0)
    # per-(tree, slot) node-ID strings for the current frontier
    frontier_ids = [["r"] for _ in range(num_trees)]
    # per-tree accumulated node records: id -> dict
    records: list[dict[str, dict]] = [dict() for _ in range(num_trees)]

    is_cat_j = jnp.asarray(is_cat)

    for depth in range(max_depth + 1):
        real_slots = max(len(ids) for ids in frontier_ids)
        if real_slots == 0:
            break
        # pad the frontier width to a power of two: levels then hit at
        # most log2(max width) distinct kernel shapes, so the whole
        # growth loop compiles once per width and every later
        # generation (the batch layer retrains every interval) is pure
        # cache hits.  Padding slots hold no samples — their histogram
        # rows are zero and their (garbage) split decisions are never
        # read on host.
        num_slots = 1 << (real_slots - 1).bit_length()
        if mesh is not None:
            hist = _dist_histograms_fn(
                mesh, mesh_axis, num_slots, num_bins,
                classification)(binned, ychan, w, slot_of)
        else:
            hist = _histograms(binned, ychan, w, slot_of, num_slots,
                               num_bins, classification)
        feat_u = jax.random.uniform(
            jax.random.fold_in(key, depth + 1),
            (num_trees, num_slots, num_p))
        gain, best_p, best_b, default_right, right_mask, totals = \
            _best_splits(hist, is_cat_j, feat_u, impurity, k_features)
        # unweighted examples per frontier node — the PMML record
        # counts, collected live instead of re-routing the training
        # set after the build (treeNodeExampleCounts semantics)
        if mesh is not None:
            counts = _dist_slot_counts_fn(mesh, mesh_axis,
                                          num_slots)(slot_of)
        else:
            counts = _slot_counts(slot_of, num_slots)
        t0 = _mark("level_dispatch", t0)

        # ONE host fetch for all outputs: each np.asarray is a full
        # device round trip, and behind a high-latency transport seven
        # of them per level dominate the (fast) kernels
        (gain, best_p_np, best_b_np, default_np, right_np, totals_np,
         counts_np) = jax.device_get(
            (gain, best_p, best_b, default_right, right_mask, totals,
             counts))
        totals_np = np.asarray(totals_np, dtype=np.float64)
        t0 = _mark("level_fetch", t0)

        # decide split vs leaf per (tree, slot) on host; assign child slots
        split_np = np.zeros((num_trees, num_slots), dtype=bool)
        is_cat_slot = np.zeros((num_trees, num_slots), dtype=bool)
        child_slots = np.full((num_trees, num_slots, 2), -1, dtype=np.int32)
        next_ids: list[list[str]] = [[] for _ in range(num_trees)]
        for t in range(num_trees):
            for m, node_id in enumerate(frontier_ids[t]):
                do_split = depth < max_depth and gain[t, m] > 0.0 and \
                    np.isfinite(gain[t, m])
                if not do_split:
                    records[t][node_id] = {"leaf": True,
                                           "stats": totals_np[t, m],
                                           "count": int(counts_np[t, m])}
                    continue
                p = int(best_p_np[t, m])
                split_np[t, m] = True
                is_cat_slot[t, m] = is_cat[p]
                if is_cat[p]:
                    n_vals = category_counts[p]
                    right_set = [c for c in range(n_vals)
                                 if right_np[t, m, c]]
                    decision = ("cat", p, right_set)
                else:
                    decision = ("num", p,
                                float(thresholds[p, int(best_b_np[t, m])]))
                records[t][node_id] = {
                    "leaf": False, "decision": decision,
                    "default_right": bool(default_np[t, m]),
                    "count": int(counts_np[t, m])}
                child_slots[t, m, 0] = len(next_ids[t])
                next_ids[t].append(node_id + "-")
                child_slots[t, m, 1] = len(next_ids[t])
                next_ids[t].append(node_id + "+")

        t0 = _mark("level_host_partition", t0)
        if not any(next_ids[t] for t in range(num_trees)):
            break
        advance = _advance if mesh is None \
            else _dist_advance_fn(mesh, mesh_axis)
        slot_of = advance(slot_of, binned, jnp.asarray(split_np),
                          best_p, best_b, jnp.asarray(is_cat_slot),
                          right_mask, jnp.asarray(child_slots))
        frontier_ids = next_ids
        t0 = _mark("level_advance_dispatch", t0)

    forest = _build_forest(records, schema, classification,
                           num_classes if classification else 0)
    _mark("build_forest", t0)
    return forest


def _build_forest(records, schema: InputSchema, classification: bool,
                  num_classes: int) -> DecisionForest:
    """Reconstruct host trees from per-node training records, carrying
    the full-set example counts collected per level into PMML record
    counts and feature importances (reference:
    RDFUpdate.treeNodeExampleCounts / predictorExampleCounts — counts
    come from routing EVERY example, not the bootstrap sample; leaf
    distributions stay the bootstrap-weighted stats, rescaled)."""
    trees = []
    importance_counts = np.zeros(schema.num_features, dtype=np.float64)
    for tree_records in records:

        def build(node_id: str):
            rec = tree_records[node_id]
            count = rec.get("count", 0)
            if rec["leaf"]:
                stats = rec["stats"]
                if classification:
                    counts = np.maximum(stats, 0.0)
                    if counts.sum() <= 0:
                        counts = np.ones(num_classes)
                    prediction = CategoricalPrediction(counts)
                    probs = prediction.category_probabilities
                    prediction.category_counts = probs * max(1, count)
                    prediction.count = count
                    prediction._recompute()
                else:
                    n = max(stats[0], 1e-12)
                    prediction = NumericPrediction(stats[1] / n, count)
                return TerminalNode(node_id, prediction)
            kind, p, arg = rec["decision"]
            feature_number = schema.predictor_to_feature_index(p)
            if kind == "cat":
                decision = CategoricalDecision(feature_number, arg,
                                               rec["default_right"])
            else:
                decision = NumericDecision(feature_number, arg,
                                           rec["default_right"])
            node = DecisionNode(node_id, decision, build(node_id + "-"),
                                build(node_id + "+"))
            node.count = count
            importance_counts[feature_number] += count
            return node

        trees.append(DecisionTree(build("r")))
    forest = DecisionForest(trees)
    total = importance_counts.sum()
    forest.feature_importances = (importance_counts / total if total > 0
                                  else importance_counts)
    return forest


