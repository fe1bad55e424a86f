"""Cold-start benchmark: process start -> trained generation + first query.

The JVM reference's layers do useful work seconds after exec (deploy/
oryx-batch/src/main/java/com/cloudera/oryx/batch/Main.java — construct,
start, await; nothing to compile).  The TPU runtime pays XLA compilation
instead.  The persistent compilation cache (common/compile_cache.py)
converts that to a per-machine cost.  This bench quantifies it end to
end:

  parent: fresh cache dir — the one place that wants a NEW directory on
          purpose, handed to every child through
          ``JAX_COMPILATION_CACHE_DIR`` (the program then sets no cache
          directory in code) — then an INSTALL-TIME WARMUP (the ``warmup``
          CLI subcommand: one real training iteration at this scale +
          AOT of the resulting serving ladder, all landing in the
          persistent cache — deploy/warmup.py), then TWO child
          processes in sequence —
  child:  enable cache -> synthesize ALS data -> train 2 epochs
          (epoch1 = compile+exec, epoch2 = steady exec) -> build the
          serving model -> warm serving kernels -> first query.

With the warmup stage, run 1 — the FIRST-ever layer start on the
machine — already pays cache loads instead of compilation.  Run 2
re-proves the restart case.  ``--skip-warmup`` restores the
uninstalled-cold measurement for comparison.

Usage:  python -m oryx_tpu.bench.coldstart [--ratings N --rank K --out F]
One process holds the chip at a time: the parent never touches JAX, and
its children run strictly in sequence.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

__all__ = ["main"]


def _child(args) -> None:
    import numpy as np

    if args.log_cache:
        import logging

        # compiler logger only: one hit/miss line per compilation
        # (~160 total, negligible timing perturbation) — the dispatch
        # logger would add per-dispatch chatter to a timed run
        logging.basicConfig(level=logging.WARNING)
        logging.getLogger("jax._src.compiler").setLevel(logging.DEBUG)

    t_proc = time.perf_counter()
    from ..common import compile_cache
    from ..common.config import from_dict

    # the directory arrives through JAX_COMPILATION_CACHE_DIR (parent)
    cfg = from_dict({"oryx.compile-cache-min-compile-secs":
                     args.min_compile_secs})
    compile_cache.enable_from_config(cfg)

    import jax

    jax.devices()  # backend contact
    t_backend = time.perf_counter()

    from .train import synthesize_movielens
    from ..app.als.common import ParsedRatings

    users, items, implicit_vals, _, _ = synthesize_movielens(
        n_ratings=args.ratings, seed=11)
    n_users = int(users.max()) + 1
    n_items = int(items.max()) + 1
    ratings = ParsedRatings(
        users=users, items=items, values=implicit_vals,
        user_ids=[f"u{i}" for i in range(n_users)],
        item_ids=[f"i{i}" for i in range(n_items)])
    t_synth = time.perf_counter()

    from ..app.als.trainer import train_als

    epoch_times: list[float] = []
    last = [time.perf_counter()]

    def on_it(i, X, Y):
        now = time.perf_counter()
        epoch_times.append(now - last[0])
        last[0] = now

    model = train_als(ratings, args.rank, lam=0.01, alpha=1.0,
                      implicit=True, iterations=2, seed=3,
                      on_iteration=on_it)
    t_train = time.perf_counter()

    from ..app.als.serving_model import ALSServingModel

    sm = ALSServingModel(features=args.rank, implicit=True)
    sm.Y.bulk_load(ratings.item_ids, model.Y)
    sm.X.bulk_load(ratings.user_ids, model.X)
    sm.warm_serving_kernels(10)
    t_warm = time.perf_counter()
    got = sm.top_n_batch(10, model.X[:2])
    assert len(got) == 2 and got[0]
    t_query = time.perf_counter()

    print(json.dumps({
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "backend_up_s": round(t_backend - t_proc, 2),
        "synth_s": round(t_synth - t_backend, 2),
        "epoch1_s": round(epoch_times[0], 2),
        "epoch2_s": round(epoch_times[1], 2),
        "train_total_s": round(t_train - t_synth, 2),
        "serving_warm_s": round(t_warm - t_train, 2),
        "first_query_s": round(t_query - t_warm, 2),
        # compile cost a restart pays beyond steady-state execution
        "compile_overhead_s": round(
            (epoch_times[0] - epoch_times[1])
            + (t_warm - t_train) + (t_query - t_warm), 2),
    }))


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--ratings", type=int, default=20_000_000)
    p.add_argument("--rank", type=int, default=100)
    p.add_argument("--cache-dir", default=None)
    p.add_argument("--child", action="store_true")
    p.add_argument("--log-cache", action="store_true")
    p.add_argument("--skip-warmup", action="store_true",
                   help="measure the UNinstalled first cold start "
                        "(the pre-ISSUE-3 behavior)")
    p.add_argument("--min-compile-secs", type=float, default=0.5,
                   help="persistence threshold for the compile cache; "
                        "lower it for CPU-scale smoke runs whose "
                        "kernels compile under the production 0.5 s "
                        "gate (they would otherwise never persist and "
                        "the restart leg mis-reads as cache misses)")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    if args.child:
        _child(args)
        return

    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="oryx-cc-")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir)
    warmup_stats = None
    if not args.skip_warmup:
        # install-time warmup in its own process (its compilations must
        # reach the child through the DISK cache, not process state)
        conf_path = os.path.join(cache_dir, "warmup.conf")
        with open(conf_path, "w") as f:
            f.write('oryx { compile-cache-min-compile-secs = %s }\n'
                    % args.min_compile_secs)
        cmd = [sys.executable, "-m", "oryx_tpu", "warmup",
               "--conf", conf_path, "--items", "", "--features", "",
               "--train-ratings", str(args.ratings),
               "--train-rank", str(args.rank)]
        t0 = time.perf_counter()
        out = subprocess.run(cmd, capture_output=True, text=True,
                             env=env, check=False)
        wall = round(time.perf_counter() - t0, 2)
        if out.returncode != 0:
            sys.stderr.write(out.stderr)
            raise SystemExit(f"warmup failed rc={out.returncode}")
        warmup_stats = json.loads(out.stdout.strip().splitlines()[-1])
        warmup_stats["process_wall_s"] = wall
    runs = []
    hits = misses = 0
    # the restart run also counts persistent-cache hits/misses via the
    # jax compiler logger: its residual compile_overhead is NOT all
    # compilation — it contains serialized-executable loads and the
    # first sweep's data-plan upload — so the restart gate is "~zero
    # XLA cache misses + serving warm < 5 s", not a bare wall-time bound
    for label, log_cache in (("cold", False), ("second_cold", True)):
        cmd = [sys.executable, "-m", "oryx_tpu.bench.coldstart", "--child",
               "--min-compile-secs", str(args.min_compile_secs),
               "--ratings", str(args.ratings), "--rank", str(args.rank)]
        if log_cache:
            cmd.append("--log-cache")
        t0 = time.perf_counter()
        out = subprocess.run(cmd, capture_output=True, text=True,
                             env=env, check=False)
        wall = round(time.perf_counter() - t0, 2)
        if out.returncode != 0:
            sys.stderr.write(out.stderr)
            raise SystemExit(f"{label} child failed rc={out.returncode}")
        stats = json.loads(out.stdout.strip().splitlines()[-1])
        stats["label"] = label
        stats["process_wall_s"] = wall
        runs.append(stats)
        if log_cache:
            import re

            # count UNIQUE cache keys (jax 0.9.0's jax._src.compiler
            # wording): the child's logging setup emits every record
            # twice (timestamped handler + plain root), so a raw line
            # count double-counts each event
            text = out.stdout + out.stderr
            misses = len(set(re.findall(
                r"PERSISTENT COMPILATION CACHE MISS for '[^']*' "
                r"with key '([^']+)'", text)))
            hits = len(set(re.findall(
                r"Persistent compilation cache hit for '[^']*' "
                r"with key '([^']+)'", text)))

    cold, warm = runs
    result = {
        "metric": "als_cold_start",
        "ratings": args.ratings, "rank": args.rank,
        # backend from the measured child process — the parent never
        # touches the device (one process holds the chip at a time)
        "backend": warm.get("backend"),
        "min_compile_secs": args.min_compile_secs,
        # install-time warmup: the one-time cost that makes the FIRST
        # cold start below a cache-load story instead of a compile
        # story (null when --skip-warmup measured the uninstalled tax)
        "install_warmup": warmup_stats,
        "first_cold_after_install": not args.skip_warmup,
        "jax_version": warm.get("jax_version"),
        "cache_dir": cache_dir,
        "cold": cold, "second_cold": warm,
        "compile_overhead_cold_s": cold["compile_overhead_s"],
        "compile_overhead_second_cold_s": warm["compile_overhead_s"],
        "compile_speedup": round(
            cold["compile_overhead_s"]
            / max(warm["compile_overhead_s"], 1e-9), 1),
        "second_cold_cache_log": {"xla_cache_misses": misses,
                                  "xla_cache_hits": hits},
        # hits >= 10 makes the log channel self-validating: zero hits
        # fails the gate instead of passing it vacuously.  The serving
        # bound is relative to the cold run's own serving warm-up: the
        # restart's residual is executable LOADING, which scales with
        # the ladder the cold run compiled.
        "warm_restart_ok": misses <= 1 and hits >= 10
        and warm["serving_warm_s"]
        < max(5.0, cold["serving_warm_s"] / 3.0),
        "warm_restart_ok_definition": (
            "~zero XLA cache misses on the logged restart (<=1 "
            "tolerates jax's per-process _broadcast_arrays helper) "
            "with >= 10 logged hits proving the detection channel "
            "works; serving warm < max(5 s, cold_serving_warm / 3).  "
            "Residual overhead is executable/plan loading, not "
            "compilation."),
    }
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
