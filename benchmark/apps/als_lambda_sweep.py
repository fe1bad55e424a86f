"""How an ``als_lambda`` cell's write rate is found, once, when the cell
is defined (not for the driver, like ``run.py --sweep``, which needs an
open-loop READ mix):

    python3 benchmark/apps/als_lambda_sweep.py --workload <cell> \\
        --seed <n> --seconds 40 --rates 50,100,200,400,800,1600

One process builds the cell's model once and then, for each rate in
turn, runs the configuration's write stream at that rate with the
cell's readers beside it, through the same ``Checker`` and ``Window``
as the benchmark's command.  A rate is SUSTAINED when nothing was left
unsent, every ``/pref`` was acknowledged, the input backlog did not grow
over the window, and the oldest event of every micro-batch was applied
within three generation intervals.  The cell's ``write_rate_per_s`` is
half the highest sustained rate, rounded down to a multiple of 50.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest, run  # noqa: E402
from benchmark.apps import als  # noqa: E402


class LagSampler(threading.Thread):
    """Input records not yet folded, read every ``period_s``."""

    def __init__(self, checker, period_s: float = 0.25):
        super().__init__(daemon=True)
        self.checker, self.period = checker, period_s
        self.lags: list[int] = []
        self._halt = threading.Event()

    def run(self) -> None:
        c = self.checker
        while not self._halt.wait(self.period):
            ends = c.broker.latest_offsets(c.input_topic)
            got = c.broker.get_offsets(c.speed._group, c.input_topic)
            self.lags.append(sum(e - (g or 0) for e, g in zip(ends, got)))

    def finish(self) -> list[int]:
        self._halt.set()
        self.join()
        return self.lags


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--rates", required=True)
    p.add_argument("--manifest", default="BENCHMARK.json")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    cell = manifest.resolve(ROOT, args.manifest, args.workload)
    app = run.load_app(cell.config["app"])

    import jax

    if jax.default_backend() == "cpu" and not args.rehearse:
        print("no accelerator", file=sys.stderr)
        return 3
    layer, _ = run.start_layer(cell, app, args.seed, False)
    rows = []
    try:
        checker = app.Checker(layer, cell, args.seed)
        checker.rate = max(rates)  # what warm() sizes the fold-in for
        checker.warm()
        problems = als.Checker.precheck(checker)
        problems += checker.fold_in_check()
        print(json.dumps({"before": problems[:10],
                          "split": checker.split,
                          "readings": checker.readings}), flush=True)
        pop = app.population(cell.config)
        lead = float(cell.config["writes"]["lead_s"])
        for n, rate in enumerate(rates):
            log0 = len(checker._update_log())
            checker.window_ms = []
            checker.start_writer(rate)
            time.sleep(lead)
            window = run.Window(layer, checker, cell.traffic, pop,
                                args.seed + n, args.seconds, None)
            lag = LagSampler(checker)
            try:
                lag.start()
                result = window.run()
            finally:
                lags = lag.finish()
                window.close()
            s = run.summarize(result)
            told, problems = checker.finish_writer()
            stale = checker._staleness_of(checker._update_log(), log0)
            quarter = max(1, len(lags) // 4)
            c0, c1 = result["counters_start"], result["counters_end"]
            row = {
                "write_rate_per_s": rate,
                "writes": {k: told[k] for k in (
                    "scheduled", "unsent", "acked", "failed",
                    "ack_p50_ms", "ack_p99_ms")},
                "input_lag": {"first_quarter_mean":
                              sum(lags[:quarter]) / quarter,
                              "last_quarter_mean":
                              sum(lags[-quarter:]) / quarter,
                              "max": max(lags) if lags else None},
                "ingest_to_applied": stale,
                "read_p50_ms": s["latency_p50_ms"],
                "read_p99_ms": s["latency_p99_ms"],
                "read_max_ms": s["latency_max_ms"],
                "reads": s["completed_in_window"],
                "read_failed": s["failed"],
                "syncs": c1["device_syncs"] - c0["device_syncs"],
                "rows_synced": c1["rows_synced"] - c0["rows_synced"],
                "updates_applied": c1["updates_applied"]
                - c0["updates_applied"],
                "micro_batches": c1["micro_batches"] - c0["micro_batches"],
                "problems": problems[:5],
            }
            grew = row["input_lag"]["last_quarter_mean"] \
                > row["input_lag"]["first_quarter_mean"] \
                + 2 * rate * checker.interval_s
            row["sustained"] = bool(
                not told["unsent"] and not told["failed"] and not grew
                and stale["p99_ms"] is not None
                and stale["p99_ms"] <= checker.stale_ms and not problems)
            rows.append(row)
            print(json.dumps(row), flush=True)
        print(json.dumps({"sweep": rows, "device": run.device_report(),
                          "detail": checker.detail()}), flush=True)
        return 0
    finally:
        layer.close()


if __name__ == "__main__":
    sys.exit(main())
