"""Headline benchmark: ALS /recommend throughput over LIVE HTTP at
reference scale.

Serves a 1M-item x 50-feature ALS model (the reference's published
exact-scan configuration) through the real serving stack — stdlib HTTP
server, route dispatch, model gating, the request micro-batcher, and
the fused matmul+mask+top_k device kernel — and drives it with
concurrent HTTP clients.  Every request scores ALL 1M items exactly
(no LSH pruning).

Reference baselines (docs/docs/performance.html; BASELINE.md), 32-core
Haswell Xeon at saturating concurrency:
  exact scan (no LSH):  70 qps / 28 ms
  LSH 0.3 (approx):    437 qps /  7 ms
This measures the EXACT scan end-to-end over HTTP and should beat both.

vs_baseline = our_http_qps / 70  (>1 means more throughput than the
reference's same-config exact number).

Prints ONE JSON line; extra fields carry latency percentiles and the
in-process kernel ceiling.
"""

from __future__ import annotations

import json
import time

import numpy as np

N_ITEMS = 1_000_000
N_USERS = 10_000
FEATURES = 50
TOP_N = 10
HTTP_WORKERS = 512
HTTP_WARMUP = 1024
HTTP_REQUESTS = 16384
KERNEL_BATCH = 512
KERNEL_BATCHES = 8
BASELINE_QPS = 70.0  # Oryx 2, 50 features / 1M items, exact scan


def main() -> None:
    from oryx_tpu.app.als.serving_model import ALSServingModel
    from oryx_tpu.bench.load import (StaticModelManager,
                                     run_recommend_load,
                                     run_recommend_open_loop)
    from oryx_tpu.lambda_rt.http import HttpApp, make_server
    from oryx_tpu.serving import als as als_resources
    from oryx_tpu.serving import framework as framework_resources
    from oryx_tpu.serving.batcher import TopNBatcher

    rng = np.random.default_rng(0)
    model = ALSServingModel(features=FEATURES, implicit=True)
    item_ids = [str(i) for i in range(N_ITEMS)]
    Y = rng.standard_normal((N_ITEMS, FEATURES)).astype(np.float32)
    model.Y.bulk_load(item_ids, Y)
    model.Y.device_arrays()  # upload once, before the timed region
    user_ids = [f"u{u}" for u in range(N_USERS)]
    X = rng.standard_normal((N_USERS, FEATURES)).astype(np.float32)
    model.X.bulk_load(user_ids, X)
    model.warm_serving_kernels(TOP_N)  # all compiles before timed work

    # in-process kernel ceiling (what the batched device dispatch alone
    # sustains, no HTTP): context for how much the serving stack costs
    queries = rng.standard_normal(
        ((2 + KERNEL_BATCHES) * KERNEL_BATCH, FEATURES)).astype(np.float32)
    for b in range(2):
        model.top_n_batch(TOP_N,
                          queries[b * KERNEL_BATCH:(b + 1) * KERNEL_BATCH])
    t0 = time.perf_counter()
    for b in range(2, 2 + KERNEL_BATCHES):
        out = model.top_n_batch(
            TOP_N, queries[b * KERNEL_BATCH:(b + 1) * KERNEL_BATCH])
        assert len(out) == KERNEL_BATCH and len(out[0]) == TOP_N
    kernel_qps = KERNEL_BATCHES * KERNEL_BATCH / (time.perf_counter() - t0)

    # live HTTP through the real serving stack, at the serving layer's
    # default batcher configuration
    StaticModelManager.model = model
    batcher = TopNBatcher()
    app = HttpApp(
        framework_resources.ROUTES + als_resources.ROUTES,
        context={
            "model_manager": StaticModelManager(),
            "input_producer": None,
            "config": None,
            "min_model_load_fraction": 0.0,
            "top_n_batcher": batcher,
        },
        read_only=True)
    server = make_server(app, 0)
    port = server.server_address[1]
    import threading
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{port}"
    try:
        run_recommend_load(base, user_ids, requests=HTTP_WARMUP,
                           workers=HTTP_WORKERS, how_many=TOP_N)
        warm_drains = len(batcher.batch_sizes)
        stats = run_recommend_load(base, user_ids, requests=HTTP_REQUESTS,
                                   workers=HTTP_WORKERS, how_many=TOP_N)
        measured_drains = len(batcher.batch_sizes)
        # open-loop ladder above the closed-loop rate: the closed-loop
        # number is bounded by workers / request round trip;
        # sustaining a higher offered arrival rate (TrafficUtil-style,
        # exponential inter-arrival) demonstrates the server was not
        # the closed-loop binding constraint.  If even 1.0x fails
        # (closed-loop overshoot), descend so the artifact reports a
        # measured rate, not 0.0.
        from oryx_tpu.bench.grid import descend_until_sustained
        ladder: list = []
        for mult in (1.0, 1.5, 2.0, 3.0):
            o = run_recommend_open_loop(
                base, user_ids, rate_qps=stats.qps * mult,
                duration_sec=6.0, workers=HTTP_WORKERS, how_many=TOP_N)
            ladder.append(o)
            if not o["sustained"]:
                break
        if not any(o["sustained"] for o in ladder):
            # same 25 qps floor as grid.bench_config's ladder: below it
            # a 6 s window has too few arrivals for the kept-up gate;
            # dedupe so a low closed-loop qps doesn't re-bench the
            # floored rate three times
            descend_until_sustained(
                base, user_ids,
                list(dict.fromkeys(
                    max(25.0, stats.qps * m) for m in (0.7, 0.5, 0.35))),
                ladder,
                duration_sec=6.0, workers=HTTP_WORKERS, how_many=TOP_N)
        open_loop_sustained = max(
            (o["offered_qps"] for o in ladder if o["sustained"]),
            default=0.0)
    finally:
        server.shutdown()
        batcher.close()

    assert stats.errors == 0, f"{stats.errors} HTTP errors during bench"
    qps = stats.qps
    # closed-loop measured run only: the open-loop ladder's drains at
    # other offered rates would otherwise dominate the mean
    sizes = batcher.batch_sizes[warm_drains:measured_drains]
    # HEADLINE = open-loop SUSTAINED qps: the
    # highest offered arrival rate (TrafficUtil-style exponential
    # inter-arrival) the server held without backlog divergence.  The
    # closed-loop number stays as a secondary column — it is bounded by
    # workers / request round trip and can overstate what the
    # server holds under arrival-driven load.
    headline = open_loop_sustained if open_loop_sustained > 0.0 else qps
    print(json.dumps({
        "metric": "als_recommend_http_sustained_qps_50f_1M_exact",
        "value": round(headline, 1),
        "unit": "qps",
        "vs_baseline": round(headline / BASELINE_QPS, 2),
        "open_loop_sustained_qps": open_loop_sustained,
        "closed_loop_qps": round(qps, 1),
        "vs_baseline_closed_loop": round(qps / BASELINE_QPS, 2),
        "headline_is_closed_loop_fallback": open_loop_sustained <= 0.0,
        "p50_ms": round(stats.percentile_ms(50), 2),
        "p95_ms": round(stats.percentile_ms(95), 2),
        "p99_ms": round(stats.percentile_ms(99), 2),
        "mean_device_batch": round(float(np.mean(sizes)), 1) if sizes else 0,
        "kernel_qps": round(kernel_qps, 1),
    }))


if __name__ == "__main__":
    main()
