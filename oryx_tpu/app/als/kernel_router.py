"""Measured-cost kernel routing for the ALS serving scan.

The phase-A build menu (int8+fold / fold / int8 / bf16 pallas /
lax.scan): which one wins depends on shape, dtype, and backend, and a
static preference list encodes yesterday's chip.

This module replaces config-only selection with a stopwatch: at model
load (and again on hot-swap, keyed to the store's padded capacity) it
times each eligible path FOR THE LIVE SHAPE with an m-deep
dispatch-queue estimator (one dispatch+fetch = rtt + exec; m queued
dispatches fetched once = rtt + m*exec; the difference isolates device
execution from the transport), then orders the phase-A fallback chain
by measured ascending cost.

What it does NOT decide is whether an LSH-configured model prunes.
``oryx.als.sample-rate`` < 1 is the configuration's semantics (the
candidates are the items inside the query's Hamming ball), the store is
laid out by bucket for it and every scan is a pruned one.  Until PR 36
LSH was a mask over the exact scan's bytes, the measurement always
found it slower, and a server configured for 0.3 silently served the
exact scan; since then both costs are still measured and reported
(``costs_exact_ms``: a pass over the whole store; ``costs_lsh_ms``: a
window whose one request reaches one Hamming ball), and ``use_lsh``
only says that pruning is configured.

The decision and every measured cost are exposed on ``/metrics`` via
``ALSServingModel.metrics()["kernel_route"]``, and the chosen variant
rides every sampled device-execute trace span as the ``kernel_route``
attribute (``ALSServingModel.kernel_route_label``, attached by
serving/batcher.py) so a slow trace names the kernel that served it.

Fault points ``route-measure-lsh`` / ``route-measure-exact`` fire
inside the timed region of the corresponding variant, so a chaos test
(or ``oryx.resilience.faults``) can inflate one side's measured cost
with ``mode="delay"`` and assert the router's fallback — the routing
logic is testable on CPU without a 20M-row model.
"""

from __future__ import annotations

import logging
import time

import numpy as np

from ...common import clock as clockmod
from ...obs import device_time as device_time_mod
from ...resilience import faults

__all__ = ["measure_routes"]

_log = logging.getLogger(__name__)

# measurement batch: the serving streaming window (throughput regime);
# flat-path models measure at the largest pow2 drain bucket <= this
_DEFAULT_BATCH = 256
# timing repetitions: median of reps, each an m-queue pair
_REPS = 2
# the m-queue delta must clear this much transport jitter before it is
# believed; the queue deepens x4 towards _MAX_M until it does
_MIN_DELTA_MS = 30.0
_MAX_M = 96


def _time_exec_ms(dispatch, fetch, m: int) -> float:
    """Per-exec milliseconds of one queued device program, transport
    excluded.  ``dispatch()`` must enqueue one device program and
    return its output handle(s) without blocking; ``fetch(h)`` must
    block until that handle's program completed.  One dispatch+fetch is
    rtt + exec, ``m`` queued dispatches fetched once are rtt + m*exec
    (the chip executes queued programs in order), so

        exec = (t_m - t_1) / (m - 1)

    Small kernels (exec << round-trip jitter) would make the delta
    indistinguishable from noise, and occasionally negative, so the
    queue is deepened until it clears ``_MIN_DELTA_MS``.  A delta the
    estimator still could not resolve routes as a tiny floor cost:
    indistinguishable kernels keep the static order (ties never
    reorder)."""
    fetch(dispatch())  # ensure compiled
    while True:
        t1s, tms = [], []
        for _ in range(_REPS):
            t0 = time.perf_counter()
            fetch(dispatch())
            t1s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            hs = [dispatch() for _ in range(m)]
            fetch(hs[-1])
            tms.append(time.perf_counter() - t0)
        t1 = float(np.median(t1s))
        tm = float(np.median(tms))
        if (tm - t1) * 1e3 >= _MIN_DELTA_MS or m >= _MAX_M:
            break
        m = min(_MAX_M, m * 4)
    return max(1e-4, round((tm - t1) / (m - 1) * 1e3, 3))


def _pruning(model, active, lsh_on: bool):
    """What the pruned variant's program takes beside the store (None
    for the exact one)."""
    return model._pruning(active) if lsh_on else None


def _n_real(batch: int, lsh_on: bool) -> int:
    """How many rows of the measured window are requests.  The pruned
    variant's holds ONE, so the pass it times streams one Hamming ball,
    the least a window can (and phase B rescores one row); every row of
    the exact variant's is one, so its cost is a full window's."""
    return 1 if lsh_on else batch


def measure_routes(model, batch: int | None = None,
                   m: int = 3) -> dict | None:
    """Time every eligible serving kernel path for ``model``'s live
    shape and return the route decision (installed by
    ``ALSServingModel.refresh_route``).

    Streaming-path models time each phase-A build kind x {exact, LSH}
    variant; flat-path models time the flat kernel (a model under LSH
    has no flat path: its variants are the streaming builds at every
    size).  Returns None when the model has no scannable items yet."""
    import jax

    from . import serving_model as sm

    vecs, active, version = model.Y.device_arrays_versioned()
    n_rows = int(vecs.shape[0])
    if n_rows == 0 or len(model.Y) == 0:
        return None
    t_measure = clockmod.monotonic()
    features = model.features
    k = min(sm._pad_k(10), n_rows)
    big, chunk = sm._stream_plan(n_rows, sm._CHUNKED_BATCH)
    lsh_configured = model._lsh_active()
    # whether an exact scan of this store is a streaming one (a small
    # store under LSH prunes by the streaming builds all the same, and
    # answers ``use_lsh=False`` by the flat kernel)
    exact_streams = big and n_rows % chunk == 0 and k <= chunk
    streaming = lsh_configured or exact_streams
    if batch is None:
        batch = sm._CHUNKED_BATCH if streaming else min(
            _DEFAULT_BATCH, 1 << max(3, (n_rows - 1).bit_length() - 2))
    rng = np.random.default_rng(17)
    Q = jax.numpy.asarray(
        rng.standard_normal((batch, features)).astype(np.float32))
    # False: the exact streaming scan; True: the pruned one
    variants = ([False] if exact_streams or not lsh_configured else []) \
        + ([True] if lsh_configured else [])

    route: dict = {
        "measured": True,
        "batch": int(batch),
        "path": "streaming" if streaming else "flat",
        "capacity": n_rows,
        "lsh_configured": lsh_configured,
        # ANN half of the re-measure key: a route measured under one
        # ANN shape (or certificate verdict) is stale under another
        "ann_key": model._ann_route_key(),
    }
    ann = model._ann
    if ann is not None:
        # the per-generation recall certificate, published verbatim on
        # /metrics as model_metrics.kernel_route.ann — the operator-
        # visible answer to "is ANN serving, and on what evidence"
        route["ann"] = {
            "recall": ann.recall,
            "min_recall": ann.cfg.min_recall,
            "recall_at": ann.cfg.recall_at,
            "cells": int(ann.centroids.shape[0]),
            "nprobe": ann.cfg.nprobe,
            "routable": model._ann_routable(n_rows),
            "index_bytes": ann.index_bytes,
        }
    costs_exact: dict = {}
    costs_lsh: dict = {}

    if streaming:
        bs = sm._BLOCK_ROWS
        ksel = sm._block_ksel(k, n_rows, bs)
        twophase_ok = sm._twophase_admits(k, ksel, vecs, bs) and (
            not lsh_configured or model._lsh_step % bs == 0)
        # the dispatch's own chain — one derivation, so what is
        # measured IS what can be served
        kinds, fold = model._phase_a_kinds(n_rows, int(vecs.shape[1]),
                                           bs)
        if not twophase_ok:
            kinds = []
        # KIND-outer loop with per-kind eviction: measurement must
        # materialize each build's device mirror (the timed program IS
        # the served program), but only ONE candidate mirror may be
        # live at a time — the full set is ~6 GB of transient HBM next
        # to the 20M store.  The winner's mirror rebuilds on the first
        # drain (one cheap version-keyed device op).
        for kind in kinds:
            if kind == "scan" and any(
                    costs_exact.get(kk) is not None
                    or costs_lsh.get(kk) is not None
                    for kk in kinds if kk != "scan"):
                # the lax.scan build spills (B, chunk) score tiles to
                # HBM (~40 GB of traffic per 20M window) and has never
                # measured within 3x of a WORKING pallas build — time
                # it only as the fallback when nothing else lowered
                continue
            for lsh_on in variants:
                prune = _pruning(model, active, lsh_on)
                mb = model.lsh.max_bits_differing if lsh_on else 0
                costs = costs_lsh if lsh_on else costs_exact
                point = (
                    "route-measure-lsh" if lsh_on    # chaos-point: route-measure-lsh
                    else "route-measure-exact")      # chaos-point: route-measure-exact
                ctx: dict = {}
                key = (n_rows, int(vecs.shape[1]), batch,
                       str(vecs.dtype), lsh_on, k, mb, kind)
                if sm._PALLAS_STATE.get(key) == "broken":
                    costs[kind] = None
                    continue
                try:
                    costs[kind] = round(_time_exec_ms(
                        lambda: (faults.fire(point),
                                 model._dispatch_kind(
                                     kind, Q, vecs, active, version,
                                     prune, _n_real(batch, lsh_on), k,
                                     bs, ksel, fold, ctx,
                                     chunk=chunk))[1],
                        jax.device_get, m), 3)
                    sm._PALLAS_STATE[key] = "ok"
                except Exception as e:  # noqa: BLE001 — backend-dep.
                    # the CPU backend cannot lower pallas (routine: it
                    # serves the scan build); on a TPU this is a defect
                    # — same level policy as the serving dispatch
                    costs[kind] = None
                    label = f"{kind}{'/lsh' if lsh_on else ''}"
                    route.setdefault("errors", {})[label] = \
                        sm.error_text(e)
                    _log.log(sm.pallas_failure_level(),
                             "phase-A build %s failed to measure for "
                             "%d rows x %df: %s", label, n_rows,
                             features, e)
            model._evict_unused_mirrors(None)
        if not twophase_ok:
            for lsh_on in variants:
                costs = costs_lsh if lsh_on else costs_exact
                point = ("route-measure-lsh" if lsh_on
                         else "route-measure-exact")
                try:
                    costs["chunked_exact"] = round(_time_exec_ms(
                        lambda: (faults.fire(point),
                                 model._exact_scan(
                                     vecs, Q, active, k, chunk,
                                     1 if lsh_on else None))[1],
                        jax.device_get, m), 3)
                except Exception as e:  # noqa: BLE001
                    costs["chunked_exact"] = None
                    route.setdefault("errors", {})[
                        "chunked_exact"] = str(e)[:120]
    if not exact_streams:
        try:
            costs_exact["flat"] = round(_time_exec_ms(
                lambda: (
                    faults.fire("route-measure-exact"),
                    sm._batch_top_n_kernel(vecs, Q, active, k))[1],
                jax.device_get, m), 3)
        except Exception as e:  # noqa: BLE001
            route.setdefault("errors", {})["flat"] = str(e)[:120]

    def best(costs: dict):
        finite = {kk: c for kk, c in costs.items() if c is not None}
        if not finite:
            return None, None
        kk = min(finite, key=finite.get)
        return kk, finite[kk]

    route["costs_exact_ms"] = costs_exact
    # pruning is the configuration's semantics, not a verdict of this
    # measurement: a model under LSH serves pruned answers whatever the
    # two tables say (module docstring), both are reported, and
    # ``use_lsh`` says which one the served programs belong to (None:
    # not configured)
    route["use_lsh"] = True if lsh_configured else None
    if lsh_configured:
        route["costs_lsh_ms"] = costs_lsh
    # order/report the costs of the variant that will actually SERVE
    # (possibly empty: then no reorder happens and `chosen` stays None,
    # honest "no evidence")
    serving_lsh = lsh_configured
    effective = costs_lsh if serving_lsh else costs_exact
    route["phase_a_costs_ms"] = effective
    route["chosen"] = best(effective)[0]
    if streaming and route["chosen"] in ("i8_fold", "i8", "fold",
                                         "pallas", "ivf"):
        # rebuild the WINNER's mirror pre-traffic: the per-kind
        # eviction above dropped it with the losers, and the first
        # live drain must not pay the O(N) mirror build + upload
        # inside a request (refresh_route's trailing eviction keeps
        # exactly this kind's caches)
        try:
            jax.device_get(model._dispatch_kind(
                route["chosen"], Q, vecs, active, version,
                _pruning(model, active, serving_lsh),
                _n_real(batch, serving_lsh), k, bs, ksel, fold, {},
                chunk=chunk))
        except Exception as e:  # noqa: BLE001 — never a load gate,
            # but the build that just measured fastest failing to run
            # again must not pass silently
            route.setdefault("errors", {})[
                f"rebuild/{route['chosen']}"] = sm.error_text(e)
            _log.exception("re-materializing the routed %s mirror "
                           "failed", route["chosen"])
    _log.info(
        "kernel route for %d rows x %df (%s): chosen=%s use_lsh=%s "
        "exact=%s lsh=%s", n_rows, features, route["path"],
        route["chosen"], route.get("use_lsh"), costs_exact,
        costs_lsh or None)
    # device-time accounting (obs/device_time.py): the measurement
    # sweep is device-execute dominated, and it competes with serving
    # for the chip — book it under its own route-class so the busy
    # fraction and /admin/tail attribute re-route storms honestly
    acct = device_time_mod.process_accountant()
    if acct is not None:
        acct.note("measure", route.get("chosen"),
                  getattr(model, "generation", None),
                  clockmod.monotonic() - t_measure)
    return route
