"""Deploy-time AOT warmup: compile the serving kernel ladder into the
persistent XLA cache before any traffic (or model) exists.

Why: the JVM reference serves within seconds of process start; this
runtime pays XLA compilation per (program, shape) pair — COLDSTART_r05
measured 284 s of first-EVER-run compile (63.7 s of it serving-kernel
warm) that the persistent cache only rescues from the SECOND cold start
on.  Install time is when an operator expects to pay one-time costs, so
``python -m oryx_tpu warmup`` moves the whole tax there:

- **Serving ladder (pure AOT)** — every kernel variant the serving
  dispatch can choose (two-phase scan + the pallas phase-A builds:
  bf16/f32, folded, int8, int8+fold; the exact-scan fallback; the flat
  kernels; the mirror-building kernels) is lowered from
  ``jax.ShapeDtypeStruct`` avals — NO device arrays are allocated, so
  the 20M-row ladder warms without 10 GB of HBM — and compiled into the
  persistent cache for each (items, features) rung of the standard
  shape ladder x each request-window size.  A later model load of the
  same shape hits the disk cache instead of the compiler: the store's
  padded capacity is derived by ``feature_vectors.planned_capacity``,
  the same function ``bulk_load`` obeys.

- **Training shapes (optional, executes)** — ``--train-ratings N``
  runs one real ALS iteration on synthetic data at the target scale.
  The trainer's degree-bucketed pow2 batch plans make its compiled
  shapes a function of scale rather than of exact data, so one
  install-time iteration seeds the per-epoch programs a first real
  generation would otherwise compile.

The CPU backend cannot lower a pallas build: it records the failure and
continues — exactly mirroring the serving dispatch's own fallback
chain, so what warms is what serves.  On a TPU backend every build is
expected to lower, and any entry in ``failed`` makes the report not
``ok`` (the CLI exits non-zero).
"""

from __future__ import annotations

import logging
import time

import numpy as np

__all__ = ["run_warmup", "warm_serving_shapes"]

_log = logging.getLogger(__name__)


def _aval(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _compile(report: dict, name: str, fn, *args, **static) -> None:
    """Lower+compile one jitted function from avals, recording outcome.
    Compilation lands in the persistent cache (keyed by HLO
    fingerprint).  A failure is recorded per variant and the ladder
    carries on, so ONE run lists every build the backend refuses; what
    a non-empty ``failed`` means is the caller's call (``run_warmup``'s
    ``ok``): routine on the CPU backend, which cannot lower pallas, a
    defect on a TPU."""
    from ..app.als.serving_model import error_text, pallas_failure_level

    t0 = time.perf_counter()
    try:
        fn.lower(*args, **static).compile()
        report["compiled"].append(
            {"kernel": name, "sec": round(time.perf_counter() - t0, 2)})
    except Exception as e:  # noqa: BLE001 — backend-dependent builds
        report["failed"].append(
            {"kernel": name, "error": error_text(e, 2000)})
        _log.log(pallas_failure_level(), "warmup: %s failed to compile: "
                 "%s", name, e)


def warm_serving_shapes(features: int, items: int, dtype: str,
                        sample_rate: float, report: dict,
                        how_many: int = 10,
                        max_flat_batch: int = 1024,
                        ann=None) -> None:
    """AOT-compile every serving kernel variant for one (items,
    features) ladder rung, from avals only.  ``ann`` (an
    ``ivf.AnnConfig``) additionally warms the IVF phase-A ladder —
    index shapes derive from ``ivf.mirror_shapes`` over the SAME
    ``planned_capacity`` that ``bulk_load`` obeys, so warmed shapes
    stay lock-stepped with what a model load will build.

    A ``sample_rate`` < 1 model's item store is laid out by LSH bucket
    (its capacity is ``planned_capacity``'s for that layout: exact for
    factors that hash evenly) and every window is a pruned one: the
    ladder is the two builds that can skip steps (``pallas``, ``scan``)
    and the exact scan over a window's candidates, each ONE program a
    window shape however many steps a window visits (the grid's bound
    is computed on the device), plus the exact scan over the whole
    store that ``use_lsh=False`` callers take.  No mirror is built for
    such a model, so none is warmed."""
    import jax.numpy as jnp

    from ..app.als import ivf as ivf_mod
    from ..app.als import serving_model as sm
    from ..app.als.feature_vectors import planned_capacity, resolve_dtype
    from ..app.als.lsh import (LocalitySensitiveHash, _BUCKET_CHUNK,
                               _bucket_kernel)

    lsh = (LocalitySensitiveHash(sample_rate, features)
           if sample_rate < 1.0 else None)
    lsh_on = lsh is not None and lsh.num_hashes > 0 \
        and lsh.max_bits_differing < lsh.num_hashes
    cap = planned_capacity(items, buckets=lsh.num_partitions,
                           step=sm._PA_TILE) if lsh_on \
        else planned_capacity(items)
    W = features if features >= 128 else 128
    dt = jnp.dtype(resolve_dtype(dtype))
    F = features
    k = min(sm._pad_k(how_many), cap)
    Y = _aval((cap, W), dt)
    A = _aval((cap,), jnp.bool_)
    # how many of a window's rows are requests: an argument of every
    # two-phase program and of the pruned exact scan, never a shape
    R = _aval((), jnp.int32)
    variants: list[tuple] = [(None, 0)]
    if lsh_on:
        n_steps = cap // sm._PA_TILE
        variants.append((sm.Pruning(
            _aval((n_steps,), jnp.int32), _aval((n_steps,), jnp.int32),
            _aval((lsh.num_hashes, F), jnp.float32)),
            lsh.max_bits_differing))
        # item bucketing (model-load path: bulk_load hashes the host
        # matrix a chunk of rows at a time, at its true width); the
        # per-drain QUERY bucketing compiles inside each serving kernel
        _compile(report, f"{F}f/{items}: lsh_buckets", _bucket_kernel,
                 _aval((min(items, _BUCKET_CHUNK), F), dt),
                 _aval((lsh.num_hashes, F), jnp.float32),
                 num_hashes=lsh.num_hashes)
        _compile(report, f"{F}f/{items}: lsh_step_live",
                 sm._step_live_kernel, A, n_steps=n_steps)

    big, chunk = sm._stream_plan(cap, sm._CHUNKED_BATCH)
    bs = sm._BLOCK_ROWS
    ksel = sm._block_ksel(k, cap, bs)
    twophase_ok = (big and cap % chunk == 0 and k <= chunk
                   and sm._twophase_admits(k, ksel, Y, bs))
    pallas_ok = twophase_ok and cap % sm._PA_TILE == 0
    fold = sm._fold_eligible(W, F, bs)
    tag = f"{F}f/{items}"

    # mirror-building kernels (model-load path, one per shape; only
    # meaningful on block-divisible streaming shapes, like serving)
    if twophase_ok:
        _compile(report, f"{tag}: penalty", sm._penalty_kernel, A,
                 bs=bs)
    if twophase_ok and not lsh_on:
        _compile(report, f"{tag}: penalty_i8", sm._penalty_kernel_i32,
                 A, bs=bs)
        _compile(report, f"{tag}: quantize", sm._quantize_items_kernel,
                 Y, bs=bs)
        if fold > 1:
            _compile(report, f"{tag}: fold_items",
                     sm._fold_items_kernel, Y, A, fold=fold, bs=bs)
            _compile(report, f"{tag}: fold_items_i8",
                     sm._fold_items_i8_kernel,
                     _aval((cap, W), jnp.int8), A, fold=fold, bs=bs)

    # single-request path (top_n): dot scores + masked top-k
    _compile(report, f"{tag}: dot_scores", sm._dot_scores, Y,
             _aval((F,), jnp.float32))
    _compile(report, f"{tag}: masked_top_k", sm._masked_top_k,
             _aval((cap,), jnp.float32), A, k=k)

    if big or lsh_on:
        windows = sm._WINDOW_LADDER
    else:
        windows, b = [], 8
        while b <= max_flat_batch:
            windows += (b,)
            b *= 2
    for w in windows:
        Q = _aval((w, F), jnp.float32)
        for prune, mb in variants:
            suffix = f" B={w}" + ("/lsh" if prune is not None else "")
            if prune is not None:
                # the pruned ladder: the exact scan over the window's
                # candidates, and the two builds that can skip steps
                _compile(report, f"{tag}: pruned_exact{suffix}",
                         sm._batch_top_n_pruned_exact_kernel, Y, Q, A,
                         prune, R, k=k, max_bits=mb)
                if not sm._twophase_admits(k, ksel, Y, bs):
                    continue
                _compile(report, f"{tag}: twophase_scan{suffix}",
                         sm._batch_top_n_twophase_kernel, Y, Q, A, prune,
                         R, k=k, chunk=chunk, bs=bs, ksel=ksel,
                         max_bits=mb)
                if cap % sm._PA_TILE == 0:
                    _compile(report, f"{tag}: pallas{suffix}",
                             sm._batch_top_n_twophase_pallas, Y, Q,
                             _aval((cap // bs, bs), jnp.float32), A,
                             prune, R, k=k, bs=bs, ksel=ksel, max_bits=mb)
                continue
            if not big:
                _compile(report, f"{tag}: flat{suffix}",
                         sm._batch_top_n_kernel, Y, Q, A, k=k)
                continue
            # streaming ladder: exact-scan fallback + scan build +
            # every pallas phase-A build the dispatch can route to
            _compile(report, f"{tag}: chunked_exact{suffix}",
                     sm._batch_top_n_chunked_kernel, Y, Q, A, k=k,
                     chunk=chunk)
            if not twophase_ok:
                continue
            _compile(report, f"{tag}: twophase_scan{suffix}",
                     sm._batch_top_n_twophase_kernel, Y, Q, A, None, R,
                     k=k, chunk=chunk, bs=bs, ksel=ksel)
            if lsh_on:
                # a model under LSH is offered pallas and scan only
                # (serving_model._phase_a_kinds): no mirror to warm
                if pallas_ok:
                    _compile(report, f"{tag}: pallas{suffix}",
                             sm._batch_top_n_twophase_pallas, Y, Q,
                             _aval((cap // bs, bs), jnp.float32), A,
                             None, R, k=k, bs=bs, ksel=ksel)
                continue
            if (ann is not None and ann.enabled
                    and cap // bs >= ann.cells):
                # IVF phase-A ladder (exact variant only — the kind is
                # never dispatched on masked drains).  The permuted
                # layout is static in (cap, cells, bs); only the probe
                # table's pow2 width (bpc) is data-dependent, so warm
                # the expected width and the next one up — cell-count
                # skew past 2x the mean block load recompiles once at
                # load, no worse than a cold shape
                shp = ivf_mod.mirror_shapes(cap, ann.cells, bs)
                nb, rows = shp["blocks"], shp["rows"]
                C = ann.cells
                nprobe = min(ann.nprobe, C)
                e = max(1, -(-cap // (C * bs)))
                e = 1 << (e - 1).bit_length()
                for bpc in (e, e * 2):
                    pp = nprobe * bpc
                    ks = min(max(sm._i8_ksel(ksel, cap, bs),
                                 -(-k // bs)), pp)
                    if ks * bs < k:
                        continue
                    _compile(
                        report, f"{tag}: ivf bpc={bpc}{suffix}",
                        ivf_mod._ivf_top_n_kernel, Y,  # noqa: SLF001
                        Q, _aval((rows, W), jnp.int8),
                        _aval((nb,), jnp.float32),
                        _aval((nb,), jnp.float32),
                        _aval((nb, bs), jnp.int32),
                        _aval((rows,), jnp.bool_),
                        _aval((rows,), jnp.int32),
                        _aval((C, W), jnp.float32),
                        _aval((C, bpc), jnp.int32),
                        k=k, bs=bs, ksel=ks, nprobe=nprobe,
                        pchunk=min(ivf_mod._PROBE_CHUNK, pp))
            if not pallas_ok:
                continue
            P = _aval((cap // bs, bs), jnp.float32)
            _compile(report, f"{tag}: pallas{suffix}",
                     sm._batch_top_n_twophase_pallas, Y, Q, P, A, None, R,
                     k=k, bs=bs, ksel=ksel)
            ksel_i8 = sm._i8_ksel(ksel, cap, bs)
            _compile(report, f"{tag}: pallas_i8{suffix}",
                     sm._batch_top_n_twophase_pallas_i8, Y,
                     _aval((cap, W), jnp.int8),
                     _aval((cap // bs,), jnp.float32),
                     _aval((cap // bs,), jnp.float32), Q,
                     _aval((cap // bs, bs), jnp.int32), A, R,
                     k=k, bs=bs, ksel=ksel_i8)
            if fold > 1:
                _compile(report, f"{tag}: pallas_fold{suffix}",
                         sm._batch_top_n_twophase_pallas_fold, Y,
                         _aval((cap // fold, W), dt), Q,
                         _aval((fold, cap // bs, bs // fold),
                               jnp.float32), A, R,
                         k=k, bs=bs, ksel=ksel, fold=fold)
                _compile(report, f"{tag}: pallas_i8_fold{suffix}",
                         sm._batch_top_n_twophase_pallas_i8_fold, Y,
                         _aval((cap // fold, W), jnp.int8),
                         _aval((cap // bs,), jnp.float32),
                         _aval((cap // bs,), jnp.float32), Q,
                         _aval((fold, cap // bs, bs // fold),
                               jnp.int32), A, R,
                         k=k, bs=bs, ksel=ksel_i8, fold=fold)


def _warm_training(ratings: int, rank: int, sample_rate: float,
                   factor_dtype: str, report: dict) -> None:
    """Seed the training programs by executing ONE real iteration at
    the target scale: the trainer's degree-bucketed pow2 packing makes
    compiled shapes a function of scale, so the install-time iteration
    compiles what the first real generation will run.  Then AOT the
    serving ladder for the trained model's own (items, rank) shape —
    the generation a batch layer at this scale publishes is exactly
    what its serving layer will load."""
    t0 = time.perf_counter()
    from ..app.als.common import ParsedRatings
    from ..app.als.synthetic import synthesize_movielens
    from ..app.als.trainer import train_als

    users, items_arr, implicit_vals, _, _ = synthesize_movielens(
        n_ratings=ratings, seed=11)
    n_items = int(items_arr.max()) + 1
    parsed = ParsedRatings(
        users=users, items=items_arr, values=implicit_vals,
        user_ids=[f"u{i}" for i in range(int(users.max()) + 1)],
        item_ids=[f"i{i}" for i in range(n_items)])
    train_als(parsed, rank, lam=0.01, alpha=1.0, implicit=True,
              iterations=1, seed=3)
    report["train_warm"] = {
        "ratings": ratings, "rank": rank, "items": n_items,
        "sec": round(time.perf_counter() - t0, 2),
    }
    # the serving layer will load THIS deployment's factor dtype — a
    # hardcoded dtype here would warm kernels no model load ever hits
    warm_serving_shapes(rank, n_items, factor_dtype, sample_rate,
                        report)


def run_warmup(config, items_list: list[int], features_list: list[int],
               dtypes: list[str], how_many: int = 10,
               train_ratings: int = 0, train_rank: int = 0) -> dict:
    """Warm the persistent compile cache for the given shape ladder.
    Returns the report dict (counts, per-kernel outcomes, cache dir)."""
    from ..common import compile_cache

    cache_dir = compile_cache.enable_from_config(config)
    if cache_dir is None:
        _log.warning(
            "oryx.compile-cache-dir is null: warmup compilations will "
            "NOT persist — this run warms only the current process")
    sample_rate = config.get_double("oryx.als.sample-rate")
    from ..app.als.ivf import AnnConfig
    ann = AnnConfig.from_config(config)
    report: dict = {"metric": "aot_warmup", "cache_dir": cache_dir,
                    "compiled": [], "failed": []}
    if ann.enabled:
        report["ann"] = {"cells": ann.cells, "nprobe": ann.nprobe}
    item_shards = config.get_int("oryx.serving.api.item-shards")
    if item_shards > 1:
        # the sharded SPMD scan compiles against a live device mesh —
        # not AOT-able from avals here.  Say so loudly instead of
        # reporting a successful warm of single-chip kernels the
        # sharded serving layer will never dispatch, and name what
        # does warm them.
        _log.warning(
            "item-shards=%d: the sharded programs are NOT warmed by "
            "this tool (it has no mesh). The serving process warms "
            "them itself: ALSServingModel.warm_serving_kernels runs "
            "every window of the ladder through the SPMD program on "
            "the live mesh, exact-scan fallback included, before "
            "traffic. Warming the single-chip ladder anyway for "
            "tools/benches.", item_shards)
        report["sharded_not_warmed"] = item_shards
    t0 = time.perf_counter()
    import jax

    report["backend"] = jax.default_backend()
    report["jax_version"] = jax.__version__
    for dtype in dtypes:
        for items in items_list:
            for features in features_list:
                warm_serving_shapes(features, items, dtype, sample_rate,
                                    report, how_many=how_many,
                                    ann=ann if ann.enabled else None)
    if train_ratings and train_rank:
        _warm_training(train_ratings, train_rank, sample_rate,
                       config.get_string("oryx.als.factor-dtype"),
                       report)
    report["compiled_count"] = len(report["compiled"])
    report["failed_count"] = len(report["failed"])
    report["wall_s"] = round(time.perf_counter() - t0, 2)
    # on a TPU nothing may fall back: a build that fails to lower there
    # is the defect this command exists to find before traffic does
    report["ok"] = report["compiled_count"] > 0 and not (
        report["failed"] and report["backend"] == "tpu")
    return report
