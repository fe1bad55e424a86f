"""IVF ANN serving index: coarse centroid partition + int8 residual
scoring of the ``nprobe`` nearest cells (ROADMAP item 1 — the catalog
scale axis).

PR 3's measured-cost router showed LSH often *loses* to the exact int8
phase-A kernel at 50 features: the Hamming mask still streams the whole
item matrix and only thins the VPU work.  An IVF index attacks the HBM
bytes themselves — the one cost the roofline says matters at 10M+
items: a k-means coarse quantizer (``ops/ann.py``, reusing the k-means
app's assignment kernel shape) partitions the catalog into cells, the
items are laid out cell-contiguously in an int8 mirror, and a query
scores ONLY the blocks of its ``nprobe`` nearest cells — streaming
``nprobe/cells`` of the catalog instead of all of it.

Exactness discipline is inherited wholesale from the int8 phase A
(docs/NUMERICS.md): quantized block maxima are inflated into sound
upper bounds, selection runs on the bounds, and phase B rescores the
winners from the exact store factors under the usual
``kth >= max(unselected bound)`` certificate.  What the certificate
can NOT see is the pruned cells — that approximation is measured
instead: at each generation load the manager samples queries, compares
IVF answers against the exact kernel, and publishes recall@N on
``/metrics`` (``model_metrics.kernel_route.ann``).  The router refuses
to route ANN below ``oryx.als.ann.min-recall`` — the certificate is a
*gate*, not a hope.

Determinism (PR 8/PR 11 result-cache byte-identity): centroid training
is seeded, nearest-centroid assignment breaks ties by lowest index,
and the cell-contiguous layout uses a stable argsort — the same
generation always builds the same index and the same query always
returns the same bytes.  With ``nprobe == cells`` every block is
probed and the result is the exact kernel's (same phase-B rescore over
the same candidate universe).

The trainer may publish the index per slice (``slices.publish_sliced``
``ann=`` argument): centroids once per generation plus each slice's
cell assignments, so a serving replica's index build stays
O(catalog/N) — assignment rides the slice artifacts it already reads.
A corrupt/missing index artifact (chaos point ``ann-index-corrupt``)
fails CLOSED to the exact kernel with the ``ann_index_fallbacks``
counter: the replica stays servable, just not sublinear.
"""

from __future__ import annotations

import gzip
import io
import json
import logging
import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...common import store
from ...ops import ann as ops_ann
from ...resilience.faults import fire as _fault

_log = logging.getLogger(__name__)

__all__ = [
    "AnnConfig", "AnnState", "AnnIndexError", "IVFMirror",
    "build_mirror", "batch_top_n_ivf", "measure_recall", "mirror_shapes",
    "publish_centroids", "read_centroids", "read_slice_cells",
    "CENTROIDS_FILE",
]

CENTROIDS_FILE = "ann-centroids.json.gz"
# probe-dimension chunk for the phase-A scan: bounds for this many
# probed 128-row blocks are computed per lax.scan step, so the live
# int8 gather stays ~B x 64 x 128 x W bytes regardless of nprobe
_PROBE_CHUNK = 64
# deterministic seeds: index builds must be a pure function of the
# generation (result-cache byte-identity), so nothing here draws from
# ambient randomness
_TRAIN_SEED = 13
_RECALL_SEED = 29


class AnnIndexError(Exception):
    """A per-slice ANN index artifact is missing, corrupt, or the
    index build failed — the caller fails CLOSED to the exact kernel
    (the replica stays servable) and counts ``ann_index_fallbacks``."""


class AnnConfig:
    """Parsed ``oryx.als.ann.*`` block (validated at boot, not hours
    later on the consumer thread)."""

    def __init__(self, enabled: bool, cells: int, nprobe: int,
                 min_recall: float, recall_at: int, recall_queries: int,
                 train_sample: int, train_iterations: int):
        if cells < 2:
            raise ValueError("oryx.als.ann.cells must be >= 2")
        if not 1 <= nprobe <= cells:
            raise ValueError("oryx.als.ann.nprobe must be in [1, cells]")
        if not 0.0 <= min_recall <= 1.0:
            raise ValueError("oryx.als.ann.min-recall must be in [0, 1]")
        if recall_at < 1 or recall_queries < 1:
            raise ValueError("oryx.als.ann recall-at and recall-queries "
                             "must be >= 1")
        if train_sample < cells or train_iterations < 1:
            raise ValueError("oryx.als.ann train-sample must be >= cells "
                             "and train-iterations >= 1")
        self.enabled = enabled
        self.cells = int(cells)
        self.nprobe = int(nprobe)
        self.min_recall = float(min_recall)
        self.recall_at = int(recall_at)
        self.recall_queries = int(recall_queries)
        self.train_sample = int(train_sample)
        self.train_iterations = int(train_iterations)

    @classmethod
    def from_config(cls, config) -> "AnnConfig":
        return cls(
            enabled=config.get_bool("oryx.als.ann.enabled"),
            cells=config.get_int("oryx.als.ann.cells"),
            nprobe=config.get_int("oryx.als.ann.nprobe"),
            min_recall=config.get_double("oryx.als.ann.min-recall"),
            recall_at=config.get_int("oryx.als.ann.recall-at"),
            recall_queries=config.get_int("oryx.als.ann.recall-queries"),
            train_sample=config.get_int("oryx.als.ann.train-sample"),
            train_iterations=config.get_int(
                "oryx.als.ann.train-iterations"))

    def route_key(self) -> tuple:
        """The ANN half of the kernel-route re-measure key: a route
        measured under one ANN shape must not be reused under
        another."""
        return (self.enabled, self.cells, self.nprobe, self.min_recall)


class AnnState:
    """Per-generation ANN state attached to the serving model: the
    trained centroids (small, survive mirror eviction) plus the
    load-time recall certificate.  The big device arrays live in the
    version-keyed mirror cache, rebuilt on demand."""

    def __init__(self, cfg: AnnConfig, centroids: np.ndarray,
                 cells: np.ndarray | None = None):
        self.cfg = cfg
        self.centroids = np.asarray(centroids, dtype=np.float32)
        # optional published full-catalog assignment aligned to the
        # builder's row order — consumed once by the FIRST mirror
        # build; later version bumps reassign on device (same
        # centroids, same argmin tie-break: same cells)
        self.cells = cells
        self.recall: float | None = None
        self.index_bytes: int = 0


# -- index layout -------------------------------------------------------------

def mirror_shapes(n_rows: int, ncells: int, bs: int) -> dict:
    """Static padded layout for an ``n_rows``-capacity store and a
    ``ncells`` partition: every cell's rows pad to whole ``bs`` blocks
    (worst case one part-empty block per cell) plus one always-empty
    sentinel block the probe table's padding points at.  Shared by the
    mirror build and the AOT warmup so warmed shapes stay lock-stepped
    with what a model load will actually build."""
    n_blocks = n_rows // bs + ncells + 1
    return {"blocks": n_blocks, "rows": n_blocks * bs}


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


class IVFMirror:
    """The device-resident IVF mirror for one Y-snapshot version."""

    def __init__(self, y8p, sy_b, l1y_b, pen_i, activep, perm, cents,
                 cell_blocks, index_bytes: int):
        self.y8p = y8p                  # (Npad, W) int8, cell-contiguous
        self.sy_b = sy_b                # (nb,) f32 per-block scale
        self.l1y_b = l1y_b              # (nb,) f32 per-block max row L1
        self.pen_i = pen_i              # (nb, bs) int32 retired-row mask
        self.activep = activep          # (Npad,) bool
        self.perm = perm                # (Npad,) int32 -> original row
        self.cents = cents              # (C, W) f32 lane-padded centroids
        self.cell_blocks = cell_blocks  # (C, bpc) int32 block table
        self.index_bytes = index_bytes


@partial(jax.jit, static_argnames=("fill",))
def _permute_kernel(vecs, active, perm, valid, fill: int = 0):
    """Cell-contiguous device permutation of the store snapshot: pad
    slots (valid False) become exact-zero rows so the per-block int8
    scales/L1 norms see no garbage, and their active bit is forced
    off."""
    del fill
    yp = jnp.where(valid[:, None], jnp.take(vecs, perm, axis=0), 0)
    ap = jnp.take(active, perm) & valid
    return yp, ap


def build_mirror(vecs, active, state: AnnState, bs: int,
                 cells: np.ndarray | None = None) -> IVFMirror:
    """Build the device mirror for the live snapshot: assign every row
    to its nearest centroid (or consume a published assignment), lay
    the rows out cell-contiguously in whole ``bs`` blocks, and
    quantize the permuted matrix with the SAME per-block int8 kernel
    the unpermuted int8 phase A uses — identical bound algebra."""
    from . import serving_model as sm

    n_rows, width = int(vecs.shape[0]), int(vecs.shape[1])
    ncells = int(state.centroids.shape[0])
    if n_rows % bs:
        raise AnnIndexError(f"store capacity {n_rows} not divisible by "
                            f"the {bs}-row block size")
    if cells is None:
        cells = ops_ann.assign_cells(vecs, state.centroids)
    cells = np.asarray(cells, dtype=np.int64)
    if cells.shape != (n_rows,) or cells.min(initial=0) < 0 \
            or cells.max(initial=0) >= ncells:
        raise AnnIndexError("cell assignment does not match the store")
    shapes = mirror_shapes(n_rows, ncells, bs)
    n_blocks, n_pad = shapes["blocks"], shapes["rows"]
    counts = np.bincount(cells, minlength=ncells)
    nblocks_c = -(-counts // bs)  # ceil; empty cells own 0 blocks
    if int(nblocks_c.sum()) > n_blocks - 1:
        raise AnnIndexError("cell layout overflow")  # cannot happen
    order = np.argsort(cells, kind="stable")
    # host layout: cell c's rows occupy blocks [starts[c], +nblocks_c)
    starts = np.zeros(ncells, dtype=np.int64)
    np.cumsum(nblocks_c[:-1], out=starts[1:])
    perm = np.zeros(n_pad, dtype=np.int32)
    valid = np.zeros(n_pad, dtype=bool)
    row_starts = starts * bs
    offsets = np.arange(n_rows) - np.repeat(
        np.cumsum(np.concatenate(([0], counts[:-1]))), counts)
    slots = np.repeat(row_starts, counts) + offsets
    perm[slots] = order
    valid[slots] = True
    bpc = _pow2_ceil(max(1, int(nblocks_c.max(initial=1))))
    cell_blocks = np.full((ncells, bpc), n_blocks - 1, dtype=np.int32)
    for c in range(ncells):
        nb = int(nblocks_c[c])
        if nb:
            cell_blocks[c, :nb] = np.arange(starts[c], starts[c] + nb)
    # lane-pad the centroids once so query-cell distances and row
    # assignment see the same zero-padded geometry
    cents = np.zeros((ncells, width), dtype=np.float32)
    cents[:, :state.centroids.shape[1]] = state.centroids
    permd = jnp.asarray(perm)
    yp, ap = _permute_kernel(vecs, active, permd, jnp.asarray(valid))
    y8p, sy_b, l1y_b = sm._quantize_items_kernel(yp, bs)
    pen_i = sm._penalty_kernel_i32(ap, bs)
    del yp  # the f32/bf16 permuted copy is an intermediate only
    arrays = (y8p, sy_b, l1y_b, pen_i, ap, permd)
    index_bytes = sum(a.size * a.dtype.itemsize for a in arrays) \
        + cents.nbytes + cell_blocks.nbytes
    return IVFMirror(y8p, sy_b, l1y_b, pen_i, ap, permd,
                     jnp.asarray(cents), jnp.asarray(cell_blocks),
                     int(index_bytes))


# -- the phase-A kernel -------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "bs", "ksel", "nprobe",
                                   "pchunk"))
def _ivf_top_n_kernel(Y, Q, y8p, sy_b, l1y_b, pen_i, activep, perm,
                      cents, cell_blocks, k: int, bs: int, ksel: int,
                      nprobe: int, pchunk: int):
    """IVF batched top-k: the ``nprobe`` highest-dot cells by centroid
    inner product, int8 bounds for ONLY those cells' blocks (lax.scan
    over probe chunks — the gather never materializes the probe set),
    then the standard phase-B exact rescore from the ORIGINAL store
    rows with the ``kth >= max(unselected bound)`` certificate.
    Returned indices are original row indices; rows outside the probed
    cells are simply not candidates — that pruning is what the recall
    certificate measured at generation load."""
    from .serving_model import (_I8_PENALTY, _map_row_groups,
                                _phase_b_group_rows, _q_cast,
                                _row_bytes, _score_precision)

    B = Q.shape[0]
    W = int(y8p.shape[1])
    bpc = int(cell_blocks.shape[1])
    n_blocks = int(y8p.shape[0]) // bs
    Qc = _q_cast(Q, Y)
    Qf = Qc.astype(jnp.float32)
    sq = jnp.maximum(jnp.max(jnp.abs(Qf), axis=1), 1e-30) / 127.0
    q8 = jnp.clip(jnp.round(Qf / sq[:, None]), -127, 127).astype(jnp.int8)
    l1q = jnp.sum(jnp.abs(Qf), axis=1)

    # probe cells by INNER PRODUCT with the query — the metric the
    # serving score ranks by — NOT the euclidean metric the rows were
    # assigned with.  The asymmetry is deliberate (MIPS probing): the
    # euclidean order's -||c||^2 term down-ranks exactly the
    # high-norm cells whose items dominate a dot-product top-k, a
    # measured ~0.54 -> ~0.92 recall@50 swing at 50 features
    _, probe_cells = jax.lax.top_k(
        jnp.matmul(Qf, cents.T, preferred_element_type=jnp.float32),
        nprobe)                                           # (B, nprobe)
    bi = jnp.take(cell_blocks, probe_cells,
                  axis=0).reshape(B, nprobe * bpc)        # (B, P)
    P = nprobe * bpc
    P2 = -(-P // pchunk) * pchunk
    if P2 != P:  # pad with the sentinel (always-empty) block
        bi = jnp.pad(bi, ((0, 0), (0, P2 - P)),
                     constant_values=n_blocks - 1)
    y8r = y8p.reshape(n_blocks, bs, W)

    def step(_, bc):  # bc: (B, pchunk) block ids
        blk = jnp.take(y8r, bc, axis=0)                # (B, pc, bs, W)
        s = jnp.einsum("bw,bpcw->bpc", q8, blk,
                       preferred_element_type=jnp.int32)
        s = s + jnp.take(pen_i, bc, axis=0)
        return None, s.max(-1)                          # (B, pc) int32

    _, ms = jax.lax.scan(step, None,
                         jnp.transpose(bi.reshape(B, P2 // pchunk,
                                                  pchunk), (1, 0, 2)))
    m_int = jnp.transpose(ms, (1, 0, 2)).reshape(B, P2)
    # sound upper bound on each probed block's exact max score — the
    # int8 phase-A algebra verbatim (docs/NUMERICS.md)
    syg = jnp.take(sy_b, bi, axis=0)
    l1g = jnp.take(l1y_b, bi, axis=0)
    bound = (m_int.astype(jnp.float32) * syg * sq[:, None]
             + 0.5 * sq[:, None] * l1g
             + 0.5 * syg * l1q[:, None]
             + 0.25 * W * syg * sq[:, None])
    masked = m_int <= _I8_PENALTY // 2
    bound = jnp.where(masked | (l1q[:, None] == 0.0), -jnp.inf, bound)

    def rescore(Qc, bound, bi):
        b = Qc.shape[0]
        _, pi = jax.lax.approx_max_k(bound, ksel, recall_target=0.99999)
        m_rest = bound.at[jnp.arange(b)[:, None], pi].set(-jnp.inf).max(-1)
        m_guard = jnp.where(jnp.isfinite(m_rest),
                            m_rest + jnp.abs(m_rest) * 1e-4, m_rest)
        bi_sel = jnp.take_along_axis(bi, pi, axis=1)      # (b, ksel)
        rows_p = (bi_sel[:, :, None] * bs
                  + jnp.arange(bs, dtype=jnp.int32)[None, None, :]
                  ).reshape(b, ksel * bs)
        orig = jnp.take(perm, rows_p)                     # (b, R)
        ok = jnp.take(activep, rows_p)
        Yg = jnp.take(Y, orig, axis=0)                    # (b, R, W)
        scores = jnp.einsum("bf,brf->br", Qc, Yg,
                            preferred_element_type=jnp.float32,
                            precision=_score_precision(Y))
        scores = jnp.where(ok, scores, -jnp.inf)
        ts, ti = jax.lax.top_k(scores, k)
        idx = jnp.take_along_axis(orig, ti, axis=1)
        return ts, idx, ts[:, k - 1] >= m_guard

    # the gather is phase B's, and bounded like it: a window too wide
    # for one runs in row groups (serving_model._phase_b)
    g = _phase_b_group_rows(B, ksel, bs, _row_bytes(Y))
    return _map_row_groups(rescore, g, Qc, bound, bi)


def batch_top_n_ivf(mirror: IVFMirror, Y, Q, k: int, bs: int,
                    ksel: int, nprobe: int):
    """Dispatch one window through the IVF kernel (async — the caller
    fetches).  ``ksel`` widens like the int8 path (selection runs on
    margin-inflated bounds) and clamps to the probe set; a probe set
    too small to even hold ``k`` rows refuses loudly so the dispatch
    chain falls to the next kind."""
    bpc = int(mirror.cell_blocks.shape[1])
    nprobe = min(nprobe, int(mirror.cell_blocks.shape[0]))
    P = nprobe * bpc
    ksel = max(ksel, -(-k // bs))
    ksel = min(ksel, P)
    if ksel * bs < k:
        raise AnnIndexError(
            f"probe set of {P} blocks cannot hold top-{k}")
    return _ivf_top_n_kernel(
        Y, Q, mirror.y8p, mirror.sy_b, mirror.l1y_b, mirror.pen_i,
        mirror.activep, mirror.perm, mirror.cents, mirror.cell_blocks,
        k, bs, ksel, nprobe, min(_PROBE_CHUNK, P))


# -- recall certificate -------------------------------------------------------

def measure_recall(model, mirror: IVFMirror, cfg: AnnConfig) -> float:
    """recall@N of the IVF path against the exact kernel on a sampled
    query set — THE per-generation certificate.  Queries are real user
    factors when the generation shipped any (the distribution recall
    actually serves), topped up with seeded standard normals; both
    paths run on the live device snapshot, so the measurement covers
    the quantizer, the layout, and the probe pruning together."""
    from . import serving_model as sm

    vecs, active, _version = model.Y.device_arrays_versioned()
    n_rows = int(vecs.shape[0])
    k = min(cfg.recall_at, max(1, len(model.Y)))
    rng = np.random.default_rng(_RECALL_SEED)
    qs: list[np.ndarray] = []
    if len(model.X):
        xv, xa, _ids = model.X.host_arrays()
        user_rows = xv[xa]
        if len(user_rows):
            take = min(cfg.recall_queries, len(user_rows))
            qs.append(np.asarray(
                user_rows[rng.permutation(len(user_rows))[:take],
                          :model.features], dtype=np.float32))
    short = cfg.recall_queries - sum(len(q) for q in qs)
    if short > 0:
        qs.append(rng.standard_normal(
            (short, model.features)).astype(np.float32))
    Q = np.concatenate(qs)
    Qd = jnp.asarray(Q)
    big, chunk = sm._stream_plan(n_rows, len(Q))
    if big and n_rows % chunk == 0 and k <= chunk:
        ex_s, ex_i = jax.device_get(sm._batch_top_n_chunked_kernel(
            vecs, Qd, active, k, chunk))
    else:
        ex_s, ex_i = jax.device_get(sm._batch_top_n_kernel(
            vecs, Qd, active, k))
    bs = sm._BLOCK_ROWS
    ksel = sm._i8_ksel(sm._block_ksel(k, n_rows, bs), n_rows, bs)
    an_s, an_i, _cert = jax.device_get(batch_top_n_ivf(
        mirror, vecs, Qd, k, bs, ksel, cfg.nprobe))
    hits = total = 0
    for b in range(len(Q)):
        truth = {int(i) for s, i in zip(ex_s[b], ex_i[b])
                 if math.isfinite(s)}
        if not truth:
            continue
        got = {int(i) for s, i in zip(an_s[b], an_i[b])
               if math.isfinite(s)}
        hits += len(truth & got)
        total += len(truth)
    return 1.0 if total == 0 else hits / total


# -- per-slice index artifacts (sharded distribution) -------------------------

def publish_centroids(model_dir: str, centroids: np.ndarray) -> dict:
    """Write the generation's centroid artifact (deterministic gzip,
    like every slice artifact) and return its manifest entry."""
    c64 = np.round(np.asarray(centroids, dtype=np.float32)
                   .astype(np.float64), 8)
    payload = _gzip_bytes(json.dumps(
        {"cells": int(c64.shape[0]), "features": int(c64.shape[1]),
         "centroids": c64.tolist()}, separators=(",", ":")))
    with store.open_write(store.join(model_dir, CENTROIDS_FILE)) as f:
        f.write(payload)
    return {"path": CENTROIDS_FILE, "bytes": len(payload),
            "crc32": zlib.crc32(payload), "cells": int(c64.shape[0])}


def _gzip_bytes(text: str) -> bytes:
    buf = io.BytesIO()
    with gzip.GzipFile(fileobj=buf, mode="wb", mtime=0) as gz:
        gz.write(text.encode("utf-8"))
    return buf.getvalue()


def _read_checked_ann(model_dir: str, entry: dict) -> bytes:
    """Checksum-verified ANN artifact bytes.  The chaos point
    ``ann-index-corrupt`` models a corrupt/missing per-slice index
    artifact (docs/RESILIENCE.md): the manager fails CLOSED to the
    exact kernel with the ``ann_index_fallbacks`` counter — the
    replica stays servable, just not sublinear."""
    _fault("ann-index-corrupt", error=lambda: AnnIndexError(
        f"injected corrupt ANN index artifact at {entry.get('path')}"))
    path = store.join(model_dir, entry["path"])
    try:
        with store.open_read(path) as f:
            payload = f.read()
    except OSError as e:
        raise AnnIndexError(f"unreadable ANN artifact {path}: {e}") from e
    if zlib.crc32(payload) != int(entry["crc32"]):
        raise AnnIndexError(f"checksum mismatch for {path}")
    return payload


def read_centroids(model_dir: str, entry: dict) -> np.ndarray:
    try:
        with gzip.open(io.BytesIO(_read_checked_ann(model_dir, entry)),
                       "rt", encoding="utf-8") as f:
            doc = json.load(f)
        c = np.asarray(doc["centroids"], dtype=np.float32)
        if c.shape != (int(doc["cells"]), int(doc["features"])) \
                or not np.isfinite(c).all():
            raise ValueError(f"bad centroid shape {c.shape}")
    except AnnIndexError:
        raise
    except (OSError, EOFError, ValueError, KeyError, TypeError) as e:
        raise AnnIndexError(f"undecodable centroid artifact: {e}") from e
    return c


def read_slice_cells(model_dir: str, entry: dict) -> list[int]:
    """One slice's per-row cell assignments, aligned to the slice
    artifact's row order."""
    try:
        with gzip.open(io.BytesIO(_read_checked_ann(model_dir, entry)),
                       "rt", encoding="utf-8") as f:
            cells = json.load(f)
        if not isinstance(cells, list) \
                or len(cells) != int(entry["rows"]):
            raise ValueError(
                f"{len(cells)} cells, manifest says {entry['rows']}")
    except AnnIndexError:
        raise
    except (OSError, EOFError, ValueError, KeyError, TypeError) as e:
        raise AnnIndexError(f"undecodable cell artifact: {e}") from e
    return [int(c) for c in cells]


def train_generation_centroids(Y, cfg: AnnConfig) -> np.ndarray:
    """The generation's coarse quantizer: k-means over a seeded sample
    of the item factors (deterministic — same factors, same
    centroids)."""
    Y = np.asarray(Y, dtype=np.float32)
    rng = np.random.default_rng(_TRAIN_SEED)
    sample = Y if len(Y) <= cfg.train_sample else \
        Y[rng.permutation(len(Y))[:cfg.train_sample]]
    return ops_ann.train_centroids(sample, cfg.cells,
                                   cfg.train_iterations, _TRAIN_SEED)
