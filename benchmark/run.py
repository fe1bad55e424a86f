"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It starts the serving stack the way
``python -m oryx_tpu serving`` does — ``ServingLayer(config)`` with the
resources of the configuration's application, no topics, and the
benchmark's static manager building the cell's synthetic model from the
seed — so the HTTP door, the batcher, pipeline depth, pacing, routing
and kernels are the program's own at its own ``reference.conf``.  What
belongs to one application (which manager, which shapes to warm, which
reference) is ``benchmark/apps/<app>.py``, found by the ``app`` the
configuration's file names.  The run then checks answers against the
plain reference, warms the shapes the cell's traffic produces, and
measures for ``--seconds`` with load from a child process that never
imports JAX (``loadgen.py``).  ``--trace 0`` prints the cell's end-to-end metrics;
``--trace 1`` turns the program's span tracing on, profiles a slice of
the window and prints the cell's per-layer metrics.

The last line of stdout is the result object; the line before it holds
the readings that judge nothing (set-up split, route table, lateness,
tails above capacity, compile-cache traffic).  No accelerator, fewer chips
than the cell asks for, or no program beside the benchmark: a non-zero
exit and no result.

Not for the driver: ``--sweep r1,r2,...`` offers an open-loop cell's mix
at each rate in turn (how a cell's rate is found, once, when it is
defined); ``--rehearse`` lets the
command run on the CPU backend and report counts and ``correct`` only;
``--manifest`` reads another manifest than ``BENCHMARK.json`` (the
tests' tiny unlisted cell); ``--trace-dir`` keeps the traced run's raw
profile there (``testdata/record_excerpt.py`` cuts the recorded trace
of the reduction's test from it).
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import costs, manifest, stats  # noqa: E402
from benchmark.observe import Observations  # noqa: E402
from benchmark.traffic import TrafficError, offered_rate  # noqa: E402

# one answer in this many is checked in full inside the window
SAMPLE_EVERY = 16
# seconds of the window a traced run profiles, from its middle
TRACE_SLICE_S = 3.0


def _mean(values) -> float | None:
    return sum(values) / len(values) if values else None


class Fatal(Exception):
    """The run cannot give a result; the message goes to stderr."""

    def __init__(self, message: str, code: int):
        super().__init__(message)
        self.code = code


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--manifest", default="BENCHMARK.json")
    p.add_argument("--sweep", default=None)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--trace-dir", default=None)
    return p.parse_args(argv)


# -- the system under test ----------------------------------------------------

def load_app(name: str):
    """``benchmark/apps/<name>.py``: what belongs to one application."""
    if not os.path.isfile(os.path.join(HERE, "apps", name + ".py")):
        raise Fatal(f"the configuration's app {name!r} has no file under "
                    f"{HERE}/apps", 2)
    return importlib.import_module("benchmark.apps." + name)


def start_layer(cell, app, seed: int, traced: bool):
    """The serving layer over the cell's synthetic model, started as the
    serving CLI starts it."""
    from oryx_tpu.common import compile_cache
    from oryx_tpu.common.config import from_dict
    from oryx_tpu.lambda_rt.serving import ServingLayer

    overlay = {"oryx.id": "benchmark",
               "oryx.input-topic.broker": None,
               "oryx.update-topic.broker": None}
    overlay.update(app.overlay(cell, seed))
    overlay.update(cell.config.get("serving_config", {}))
    if traced:
        overlay.update({
            "oryx.obs.tracing.enabled": True,
            "oryx.obs.tracing.sample-ratio":
                float(cell.traffic.get("trace_sample_ratio", 1.0)),
            # every recorded request of the window stays in the ring
            "oryx.obs.tracing.max-traces": 4_000_000,
        })
    config = from_dict(overlay)
    # before anything compiles: the cache at the fixed path the program
    # resolves (JAX_COMPILATION_CACHE_DIR, else .jax_cache in the checkout)
    cache_dir = compile_cache.enable_from_config(config)
    layer = ServingLayer(config, port=0)
    layer.start()
    return layer, cache_dir


class DrainSampler(threading.Thread):
    """Collects the size of every drain the batcher dispatches while it
    runs (``batch_sizes`` is a bounded list, so it is read as it grows)."""

    def __init__(self, batcher, period_s: float = 0.2):
        super().__init__(name="benchmark-drain-sampler", daemon=True)
        self._batcher, self._period = batcher, period_s
        self._stop_event = threading.Event()
        self._seen = self._read()[0]
        self.sizes: list[int] = []

    def _read(self):
        while True:
            n = self._batcher.total_dispatches
            sizes = list(self._batcher.batch_sizes)
            if n == self._batcher.total_dispatches:
                return n, sizes

    def _collect(self) -> None:
        n, sizes = self._read()
        new = n - self._seen
        if new > 0:
            self.sizes.extend(sizes[-new:])
        self._seen = n

    def run(self) -> None:
        while not self._stop_event.wait(self._period):
            self._collect()

    def finish(self) -> list[int]:
        self._stop_event.set()
        self.join()
        self._collect()
        return self.sizes


# -- one measured window ------------------------------------------------------

class Window:
    """One window of load from a child process, with the program's
    counters read at its start and end."""

    def __init__(self, layer, checker, traffic: dict, population: dict,
                 seed: int, seconds: float, rate: float | None):
        self.layer, self.checker, self.seconds = layer, checker, seconds
        spec = dict(population, host="127.0.0.1", port=layer.port,
                    seed=int(seed), seconds=seconds, traffic=traffic,
                    rate=rate, sample_every=SAMPLE_EVERY)
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        try:
            self._tell(json.dumps(spec))
            self._hear("ready")
        except BaseException:
            self.close()
            raise

    def _tell(self, line: str) -> None:
        self.child.stdin.write(line + "\n")
        self.child.stdin.flush()

    def _hear(self, event: str) -> dict:
        line = self.child.stdout.readline()
        if not line:
            raise Fatal("the load generator ended without saying "
                        f"{event!r} (exit {self.child.wait()})", 5)
        said = json.loads(line)
        if said.get("event") != event:
            raise Fatal(f"the load generator said {said.get('event')!r}, "
                        f"not {event!r}", 5)
        return said

    def run(self, profile=None) -> dict:
        """Run the window.  ``profile(t0)`` is called in it to trace a
        slice; returns the generator's result with the counters and the
        drains of the window."""
        sampler = DrainSampler(self.layer.top_n_batcher)
        before = self.checker.counters()
        self._tell("go")
        started = self._hear("started")
        sampler.start()
        t0 = started["t0"]
        if profile is not None:
            profile(t0)
        time.sleep(max(0.0, t0 + self.seconds - time.monotonic()))
        after = self.checker.counters()
        drains = sampler.finish()
        result = self._hear("done")
        result.update(wall0=started["wall"], counters_start=before,
                      counters_end=after, batch_sizes=drains)
        return result

    def close(self) -> None:
        if self.child.poll() is None:
            try:
                self.child.stdin.close()
            except OSError:
                pass
            try:
                self.child.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.child.kill()
                self.child.wait()
        for f in (self.child.stdin, self.child.stdout):
            try:
                f.close()
            except OSError:
                pass


def summarize(result: dict) -> dict:
    """The window's numbers from the generator's records."""
    t0, span = result["t0"], result["seconds"]
    recs = result["records"]
    good = [r for r in recs if r[5]]
    inside = [r for r in good if r[3] <= t0 + span]
    lat = [(r[3] - r[1]) * 1e3 for r in inside]
    late = stats.lateness([r[1] for r in recs], [r[2] for r in recs])
    idle_lag = result["idle_lag_ms"]
    return {
        "attempted": len(recs),
        "failed": len(recs) - len(good),
        "malformed": sum(1 for r in recs if r[4] == 200 and not r[5]),
        "completed_in_window": len(inside),
        "latency_p50_ms": stats.percentile(lat, 50),
        "latency_p99_ms": stats.percentile(lat, 99),
        "latency_max_ms": max(lat) if lat else None,
        "beyond_p99": stats.samples_beyond(len(lat), 99),
        "served_qps": stats.mid_window_rate(
            [r[3] - t0 for r in good], span),
        "scheduled": result["scheduled"],
        "unsent": result["unsent"],
        "reconnects": result["reconnects"],
        "lateness": late,
        "generator_lag_ms": {
            "n": len(idle_lag),
            "mean": _mean(idle_lag),
            "p99": stats.percentile(idle_lag, 99)},
    }


def window_spans(layer, wall0: float, seconds: float) -> list[dict]:
    """The program's finished spans of requests that began in the
    window."""
    tracer = layer.tracer
    if tracer is None:
        return []
    lo, hi = wall0 * 1e3, (wall0 + seconds) * 1e3
    out = []
    for spans in tracer.traces_snapshot(limit=1 << 40).values():
        roots = [s for s in spans if s["parent_id"] is None]
        if roots and lo <= roots[0]["start_ms"] < hi:
            out.extend(spans)
    return out


class SliceProfiler:
    """Profiles ``TRACE_SLICE_S`` seconds from the middle of the window
    with ``jax.profiler`` in this process (the one that holds the chip):
    device operations and the runtime's own host events, no Python
    tracer."""

    def __init__(self, seconds: float, keep_dir: str | None = None):
        self.seconds = seconds
        self.slice_s = min(TRACE_SLICE_S, seconds / 2)
        self.keep = keep_dir is not None
        if self.keep:
            os.makedirs(keep_dir, exist_ok=True)
        self.dir = keep_dir or tempfile.mkdtemp(prefix="benchmark-trace-")

    def __call__(self, t0: float) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        begin = t0 + (self.seconds - self.slice_s) / 2
        time.sleep(max(0.0, begin - time.monotonic()))
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        time.sleep(self.slice_s)
        jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        from benchmark import trace_reduce

        try:
            path = trace_reduce.find_xplane(self.dir)
            if path is None:
                return None
            return trace_reduce.reduce_trace(
                trace_reduce.read_xplane(path))
        finally:
            if not self.keep:
                shutil.rmtree(self.dir, ignore_errors=True)


def device_report() -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in jax.local_devices():
        peak = max(peak, int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts jax's compile requests and persistent-cache hits from its
    own monitoring events; the difference is what really compiled."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def read(self) -> dict:
        return {"requests": self.requests, "hits": self.hits}


# -- the run --------------------------------------------------------------------

def run(args) -> int:
    try:
        cell = manifest.resolve(ROOT, args.manifest, args.workload)
    except manifest.ManifestError as e:
        raise Fatal(str(e), 2) from e
    if not os.path.isdir(os.path.join(ROOT, "oryx_tpu")):
        raise Fatal(f"no program beside the benchmark: {ROOT}/oryx_tpu "
                    "is not there", 2)
    try:
        rate = offered_rate(cell.traffic, cell.params)
    except TrafficError as e:
        raise Fatal(str(e), 2) from e
    if args.sweep and rate is None:
        raise Fatal("--sweep needs an open-loop cell", 2)
    if "app" not in cell.config:
        raise Fatal(f"{cell.config_file} names no app", 2)
    app = load_app(cell.config["app"])

    import jax

    devices = jax.devices()
    on_chip = jax.default_backend() != "cpu"
    if not on_chip and not args.rehearse:
        raise Fatal("JAX found no accelerator (backend "
                    f"{jax.default_backend()!r}); the benchmark does not "
                    "fall back to the CPU", 3)
    if len(devices) < cell.chips:
        raise Fatal(f"cell {cell.name} needs {cell.chips} chips, JAX "
                    f"found {len(devices)}", 3)
    peaks = costs.peaks_for(devices[0].device_kind) if on_chip else None
    compiles = CompileCounter()
    split: dict = {"import_s": round(time.monotonic() - T0, 3)}

    t = time.monotonic()
    layer, cache_dir = start_layer(cell, app, args.seed, bool(args.trace))
    window = None
    try:
        checker = app.Checker(layer, cell, args.seed)
        split.update(checker.split)
        split["layer_s"] = round(time.monotonic() - t, 3)

        t = time.monotonic()
        warmed = checker.warm()
        split["warm_s"] = round(time.monotonic() - t, 3)

        t = time.monotonic()
        problems = checker.precheck()
        split["precheck_s"] = round(time.monotonic() - t, 3)

        population = app.population(cell.config)
        if args.sweep:
            return sweep(args, cell, layer, checker, population, compiles,
                         split)

        t = time.monotonic()
        window = Window(layer, checker, cell.traffic, population,
                        args.seed, args.seconds, rate)
        split["generator_s"] = round(time.monotonic() - t, 3)
        profiler = SliceProfiler(args.seconds, args.trace_dir) \
            if args.trace else None
        before = compiles.read()
        result = window.run(profiler)
        after = compiles.read()
        setup_s = result["t0"] - T0
        summary = summarize(result)

        t = time.monotonic()
        problems += checker.check(result["samples"])
        post_s = round(time.monotonic() - t, 3)

        reduced = profiler.reduce() if profiler else None
        device = device_report()
        metrics: dict = {}
        if args.trace:
            obs = Observations(
                spans=window_spans(layer, result["wall0"], args.seconds),
                counters_start=result["counters_start"],
                counters_end=result["counters_end"],
                batch_sizes=result["batch_sizes"], trace=reduced,
                store=checker.store(), peaks=peaks)
            for m in cell.per_layer:
                if args.rehearse and m.source != "program_counter":
                    continue  # a CPU run gives counts, never times
                value = m.read(obs)
                if value is not None:
                    metrics[m.name] = {"value": float(value),
                                       "unit": m.unit}
            if reduced is not None:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
            elif on_chip:
                raise Fatal("the traced slice holds no device operation",
                            4)
        elif not args.rehearse:
            values = dict(summary, setup_s=setup_s)
            for m in cell.end_to_end:
                if values.get(m["name"]) is None:
                    raise Fatal(f"{m['name']} cannot be computed: "
                                f"{summary['completed_in_window']} requests "
                                "completed in the window", 4)
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}

        correct = not problems and summary["malformed"] == 0
        in_window = {k: after[k] - before[k] for k in after}
        detail = {
            "cell": cell.name, "seed": args.seed, "seconds": args.seconds,
            "trace": args.trace, "offered_qps": rate,
            "setup_s": setup_s, "setup_split": split,
            "warmed": warmed, "compile_cache_dir": cache_dir,
            "compile_cache": dict(compiles.read(), in_window=in_window,
                                  compiled_in_window=in_window["requests"]
                                  - in_window["hits"]),
            # this run's own end-to-end readings, whatever it prints: a
            # traced run's per-layer numbers explain an untraced run's
            # result only where these agree with it within the bound
            "end_to_end_in_this_run": {
                m["name"]: summary.get(m["name"])
                for m in cell.end_to_end if m["name"] != "setup_s"},
            "spans_recorded": len(obs.spans) if args.trace else None,
            "summary": summary,
            "counters": {"start": result["counters_start"],
                         "end": result["counters_end"]},
            "drains": len(result["batch_sizes"]),
            "mean_batch": _mean(result["batch_sizes"]),
            "in_window_full": len(result["samples"]),
            "post_window_s": post_s,
            "app": checker.detail(),
            "problems": problems[:20],
            "trace_reduced": None if reduced is None else {
                k: reduced[k] for k in ("window_s", "busy_s", "idle_share",
                                        "modules")},
        }
        print(json.dumps({"detail": detail}), flush=True)
        line = {"correct": bool(correct),
                "attempted": summary["attempted"],
                "failed": summary["failed"], "metrics": metrics,
                "device": device}
        if reduced is not None:
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
        if args.rehearse:
            line["rehearsal"] = True
        print(json.dumps(line), flush=True)
        return 0
    finally:
        if window is not None:
            window.close()
        layer.close()


def sweep(args, cell, layer, checker, population, compiles, split) -> int:
    """Offer the cell's mix at each rate of ``--sweep`` in turn, one
    window of ``--seconds`` each, and print what was served."""
    rows = []
    for n, rate in enumerate(float(r) for r in args.sweep.split(",")):
        window = Window(layer, checker, cell.traffic, population,
                        args.seed + n, args.seconds, rate)
        try:
            before = compiles.read()
            result = window.run()
            after = compiles.read()
        finally:
            window.close()
        s = summarize(result)
        sizes = result["batch_sizes"]
        rows.append({
            "offered_qps": rate,
            "connections": cell.traffic["connections"],
            "served_qps": s["served_qps"],
            "attempted": s["attempted"], "failed": s["failed"],
            "unsent": s["unsent"], "p50_ms": s["latency_p50_ms"],
            "p99_ms": s["latency_p99_ms"], "max_ms": s["latency_max_ms"],
            "lateness_mean_ms": s["lateness"]["mean_ms"],
            "lateness_drift_ms": s["lateness"]["drift_ms"],
            "generator_lag_mean_ms": s["generator_lag_ms"]["mean"],
            "drains": len(sizes), "mean_batch": _mean(sizes),
            "fallbacks": result["counters_end"]["twophase_fallbacks"]
            - result["counters_start"]["twophase_fallbacks"],
            "rows": sum(sizes),
            "problems": len(checker.check(result["samples"])),
            "compile_requests": after["requests"] - before["requests"]})
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"sweep": rows, "setup_split": split,
                      "device": device_report()}), flush=True)
    return 0


def main(argv=None) -> int:
    logging.basicConfig(
        stream=sys.stderr, level=logging.WARNING,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    args = _args(sys.argv[1:] if argv is None else argv)
    try:
        return run(args)
    except Fatal as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code


if __name__ == "__main__":
    sys.exit(main())
