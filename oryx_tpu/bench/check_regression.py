"""CI guard: fail when the newest serving bench round regresses on
sustained throughput (ISSUE 3 satellite; gateway cells ISSUE 4;
observability overhead ISSUE 7).

Three artifact families share the machinery, selected by ``--kind``:

- ``grid`` (default): ``BENCH_GRID_*.json``, cells keyed by
  (features, items, lsh) — the single-node serving envelope.
- ``gateway``: ``BENCH_GATEWAY_*.json``, cells keyed by
  (features, items, replicas, replicas-per-shard) — the
  scatter-gather cluster's per-topology scaling rounds (R-way
  replica-group cells gate independently of their R=1 siblings;
  pre-r09 artifacts are all R=1).  Since r11 a row's hot-user Zipf
  rung gates as its own ``(..., "zipf")`` pseudo-cell — a
  result-cache regression cannot hide behind a healthy cold cell,
  and pre-cache artifacts simply lack the cell.  Since r12 a row's
  per-replica model-load telemetry (sharded model distribution,
  ISSUE 10) gates as the ``(..., "load")`` pseudo-cell on LOAD SPEED
  (1 / max replica ``model_load_s``), with the same
  lacking-cell-is-new back-compat.  Since r13 the ``--regions 2``
  mirror probe (ISSUE 11) gates as the ``(..., "mirror")``
  pseudo-cell on healed-partition catch-up speed (records/s), same
  back-compat.  Since r14 the connection-count rung (ISSUE 12, C10K
  front end) gates as the ``(..., "conns")`` pseudo-cell on qps
  sustained through the top rung's concurrent sockets, same
  back-compat.  Since r15 the write-heavy rung (ISSUE 17,
  ``--write-heavy``) gates as the ``(..., "writes")`` pseudo-cell on
  sustained ACKED writes/s through the durable-ack ingest path, same
  back-compat.  Also since r15 the IVF-ANN rung (ISSUE 18, ``--ann``)
  gates as the ``(..., "ann")`` pseudo-cell on the ANN door's
  sustained qps at the large-catalog cell (recall certificate and
  speedup-vs-exact ride along), same back-compat.
- ``obs``: ``BENCH_OBS_OVERHEAD_*.json`` — the observability
  hot-path microbench (bench/obs_overhead.py).  Gates on two rules:
  a HARD absolute budget (the unsampled per-request pipeline must
  stay under 10 µs — the standing single-digit-µs contract from
  docs/OBSERVABILITY.md) and a relative creep gate between
  same-backend rounds (default threshold 50% for this kind:
  nanosecond microbenches are box-noise-sensitive where qps cells
  are not, and the absolute budget is the real contract).  Since r16
  the budget gates ``unsampled_recorder_armed`` — the full pipeline
  with the flight recorder's rings fed (ISSUE 20), the worst
  unsampled cell — falling back to ``unsampled_full_pipeline`` for
  pre-r16 artifacts, which simply lack the cell in the relative
  gate.

Joins the two most recent rounds (by round number in the filename) on
the cell key and exits non-zero when any cell's HEADLINE metric —
``open_loop_sustained_qps``, the arrival-driven number the summaries
lead with — dropped by more than ``--threshold`` (default 10%).
Closed-loop qps and device_exec_ms are reported alongside for
diagnosis but do not gate (they are transport- and backend-sensitive).

Artifacts from different backends (a CPU smoke round vs a TPU round)
are never compared: the guard reports the skip and exits 0 — a silent
cross-backend "regression" would train people to ignore the gate.

Usage:
    python -m oryx_tpu.bench.check_regression [--kind grid|gateway|obs]
        [--dir .] [--threshold 0.10] [--current F] [--previous F]
Exit codes: 0 ok/skip, 1 regression, 2 usage/artifact error.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

__all__ = ["compare_grids", "compare_obs", "find_grid_artifacts",
           "find_gateway_artifacts", "find_obs_artifacts", "main"]

_GRID_RE = re.compile(r"BENCH_GRID(?:20M)?_r(\d+)([a-z]?)\.json$")
_GATEWAY_RE = re.compile(r"BENCH_GATEWAY_r(\d+)([a-z]?)\.json$")
_OBS_RE = re.compile(r"BENCH_OBS_OVERHEAD_r(\d+)([a-z]?)\.json$")

# the unsampled obs pipeline's hard budget (ns/request): single-digit
# microseconds, docs/OBSERVABILITY.md "Tracing overhead"
OBS_BUDGET_NS = 10_000


def _find_artifacts(directory: str, pattern: re.Pattern) -> list[str]:
    found = []
    for name in os.listdir(directory):
        m = pattern.match(name)
        if m:
            found.append((int(m.group(1)), m.group(2),
                          os.path.join(directory, name)))
    return [p for _, _, p in sorted(found)]


def find_grid_artifacts(directory: str) -> list[str]:
    """Grid artifact paths sorted oldest-to-newest by (round, suffix)."""
    return _find_artifacts(directory, _GRID_RE)


def find_gateway_artifacts(directory: str) -> list[str]:
    return _find_artifacts(directory, _GATEWAY_RE)


def find_obs_artifacts(directory: str) -> list[str]:
    return _find_artifacts(directory, _OBS_RE)


def compare_obs(prev: dict, cur: dict, threshold: float = 0.50,
                budget_ns: int = OBS_BUDGET_NS) -> dict:
    """Obs-overhead comparison: the absolute per-request budget gates
    unconditionally; the relative gate compares only keys both rounds
    measured (r08 predates ``unsampled_full_pipeline``)."""
    report: dict = {"regressions": [], "improved": [], "ok": [],
                    "skipped": None, "budget_ns": budget_ns}
    if not backends_comparable(prev.get("backend"), cur.get("backend")):
        report["skipped"] = (
            f"backend mismatch: previous={prev.get('backend')} "
            f"current={cur.get('backend')} — cross-backend ns is not "
            f"a regression signal")
        # the absolute budget still applies to the current round
        prev = {"microbench_ns_per_request": {}}
    p = prev.get("microbench_ns_per_request") or {}
    c = cur.get("microbench_ns_per_request") or {}
    # the budget gates the WORST unsampled cell the round measured:
    # recorder-armed (r16) > full pipeline (r10) > tracer-only (r08)
    hot = c.get("unsampled_recorder_armed",
                c.get("unsampled_full_pipeline",
                      c.get("unsampled_begin_branch_current")))
    if hot is None:
        report["regressions"].append(
            {"cell": "unsampled hot path",
             "error": "current round measured no unsampled ns"})
        return report
    if hot > budget_ns:
        report["regressions"].append(
            {"cell": "unsampled hot path", "ns_cur": hot,
             "over_budget_ns": budget_ns,
             "detail": "single-digit-µs contract broken"})
    for key in ("unsampled_begin_branch_current",
                "unsampled_full_pipeline",
                "unsampled_recorder_armed"):
        if key not in p or key not in c:
            continue
        old, new = float(p[key]), float(c[key])
        cell = {"cell": key, "ns_prev": old, "ns_cur": new}
        if old <= 0:
            report["ok"].append(cell)
            continue
        cell["ratio"] = round(new / old, 3)
        if new > old * (1.0 + threshold):
            report["regressions"].append(cell)
        elif new < old * (1.0 - threshold):
            report["improved"].append(cell)
        else:
            report["ok"].append(cell)
    return report


def _cells(doc: dict) -> dict:
    if doc.get("metric") == "gateway_recommend_scaling":
        # per-replica-count scaling cells (bench/gateway.py); the
        # replica-group size R joined the key in r09 — pre-elastic
        # rounds are all R=1, so they keep gating the R=1 cells.
        # r11 added the hot-user Zipf rung: it gates as its own
        # pseudo-cell (base key + "zipf") so a result-cache
        # regression cannot hide behind a healthy cold cell — and
        # pre-cache artifacts simply lack the cell (reported new,
        # never compared)
        out = {}
        for r in doc.get("rows", []):
            key = (r["features"], r["items"], r["replicas"],
                   r.get("replicas_per_shard", 1))
            out[key] = r
            z = r.get("zipf")
            if isinstance(z, dict) \
                    and z.get("open_loop_sustained_qps") is not None:
                out[key + ("zipf",)] = z
            # r12 added per-replica model-load telemetry (sharded model
            # distribution): it gates as its own (..., "load")
            # pseudo-cell whose headline is LOAD SPEED — 1 /
            # max-replica model_load_s, so a >10% drop in the gated
            # number means load time rose >11% (a slice-load
            # regression cannot hide behind a healthy qps cell).
            # Pre-r12 artifacts simply lack the cell.
            load = r.get("model_load")
            if isinstance(load, dict) \
                    and load.get("max_replica_load_s"):
                out[key + ("load",)] = {
                    "open_loop_sustained_qps": round(
                        1.0 / load["max_replica_load_s"], 4),
                    "model_load_s": load["max_replica_load_s"],
                    "mode": load.get("mode"),
                }
            # ISSUE 11 added the two-region mirror probe (`--regions
            # 2`): it gates as its own (..., "mirror") pseudo-cell
            # whose headline is healed-partition CATCH-UP SPEED
            # (records replayed per second after the link returns), so
            # a mirror-throughput regression cannot hide behind a
            # healthy qps cell; steady-state staleness rides along for
            # diagnosis.  Pre-region artifacts simply lack the cell.
            mir = r.get("mirror")
            if isinstance(mir, dict) \
                    and mir.get("catch_up_records_per_s"):
                out[key + ("mirror",)] = {
                    "open_loop_sustained_qps":
                        mir["catch_up_records_per_s"],
                    "catch_up_s": mir.get("catch_up_s"),
                    "steady_staleness_ms":
                        mir.get("steady_staleness_ms"),
                }
            # r14 added the connection-count rung (C10K front end,
            # ISSUE 12): it gates as its own (..., "conns")
            # pseudo-cell on the qps sustained THROUGH the top rung's
            # concurrent sockets, so a front-end regression (the
            # event loop losing throughput at high connection counts,
            # or errors appearing — errors zero the gated number)
            # cannot hide behind a healthy low-concurrency cell.
            # Socket and router-thread telemetry ride along for
            # diagnosis.  Pre-r14 artifacts simply lack the cell.
            conns = r.get("conns")
            if isinstance(conns, dict) \
                    and conns.get("open_loop_sustained_qps") \
                    is not None:
                out[key + ("conns",)] = {
                    "open_loop_sustained_qps":
                        conns["open_loop_sustained_qps"],
                    "connections": conns.get("connections"),
                    "router_threads_at_load":
                        conns.get("router_threads_at_load"),
                    "hit_p50_ms": conns.get("hit_p50_ms"),
                }
            # ISSUE 17 added the write-heavy rung (`--write-heavy`):
            # it gates as its own (..., "writes") pseudo-cell on the
            # highest sustained ACKED writes/s through the durable-ack
            # ingest path (serving door -> input topic -> speed
            # fold-in), so a write-path regression — gate, pipelined
            # produce, or broker append — cannot hide behind a healthy
            # read cell.  The acked==durable ledger and fold-in
            # freshness ride along for diagnosis.  Pre-r15 artifacts
            # simply lack the cell.
            w = r.get("writes")
            if isinstance(w, dict) \
                    and w.get("open_loop_sustained_qps") is not None:
                out[key + ("writes",)] = {
                    "open_loop_sustained_qps":
                        w["open_loop_sustained_qps"],
                    "acked_equals_durable":
                        w.get("acked_equals_durable"),
                    "ingest_to_servable_ms":
                        w.get("ingest_to_servable_ms"),
                    "p50_shed_ms":
                        (w.get("overload") or {}).get("p50_shed_ms"),
                }
            # ISSUE 18 added the IVF-ANN rung (`--ann`): it gates as
            # its own (..., "ann") pseudo-cell on the ANN door's
            # sustained qps at the probe's large-catalog cell, so an
            # index-build or routing regression (ANN silently failing
            # closed to the exact kernel serves correctly but at
            # exact-kernel speed — the gated number collapses) cannot
            # hide behind the healthy small-catalog cells.  The recall
            # certificate, the speedup over the exact door on the SAME
            # generation, and p99 ride along for diagnosis.  Pre-r15
            # artifacts simply lack the cell.
            a = r.get("ann")
            if isinstance(a, dict) \
                    and a.get("open_loop_sustained_qps") is not None:
                out[key + ("ann",)] = {
                    "open_loop_sustained_qps":
                        a["open_loop_sustained_qps"],
                    "speedup_vs_exact": a.get("speedup_vs_exact"),
                    "recall": (a.get("certificate") or {}).get("recall"),
                    "sustained_p99_ms": a.get("sustained_p99_ms"),
                }
        return out
    return {(r["features"], r["items"], r["lsh"]): r
            for r in doc.get("rows", [])}


def _cell_label(doc: dict, key: tuple) -> str:
    if doc.get("metric") == "gateway_recommend_scaling":
        label = f"{key[0]}f/{key[1] / 1e6:g}M/{key[2]}rep"
        if key[3] != 1:
            label += f"x{key[3]}"
        if len(key) > 4:
            label += f"/{key[4]}"
        return label
    return f"{key[0]}f/{key[1] / 1e6:g}M{'/lsh' if key[2] else ''}"


# the backend a chip round reports (jax.default_backend()); an artifact
# without the field is only comparable to this, never to e.g. a gpu
# round that merely isn't cpu
_TPU_BACKEND = "tpu"


def backends_comparable(prev_backend, cur_backend) -> bool:
    """Whether two rounds' qps numbers are a regression signal.  A
    missing backend field marks an artifact from before the field
    existed: those rounds all ran on the chip, so they stay comparable
    to a TPU-backend current round.  Every other pairing must match
    exactly."""
    if prev_backend == cur_backend:
        return True
    return prev_backend is None and cur_backend == _TPU_BACKEND


def compare_grids(prev: dict, cur: dict,
                  threshold: float = 0.10) -> dict:
    """Cell-by-cell comparison report; ``report["regressions"]`` is the
    gating list."""
    report: dict = {"regressions": [], "improved": [], "ok": [],
                    "missing_cells": [], "new_cells": [],
                    "skipped": None}
    prev_backend = prev.get("backend")
    cur_backend = cur.get("backend")
    if not backends_comparable(prev_backend, cur_backend):
        report["skipped"] = (
            f"backend mismatch: previous={prev_backend} "
            f"current={cur_backend} — cross-backend qps is not a "
            f"regression signal")
        return report
    pc, cc = _cells(prev), _cells(cur)
    report["missing_cells"] = sorted(str(k) for k in pc if k not in cc)
    report["new_cells"] = sorted(str(k) for k in cc if k not in pc)
    for key in sorted(k for k in pc if k in cc):
        p, c = pc[key], cc[key]
        old = p.get("open_loop_sustained_qps") or 0.0
        new = c.get("open_loop_sustained_qps") or 0.0
        cell = {
            "cell": _cell_label(cur, key),
            "sustained_qps_prev": old,
            "sustained_qps_cur": new,
            "closed_loop_prev": p.get("qps"),
            "closed_loop_cur": c.get("qps"),
            "device_exec_ms_prev": p.get("device_exec_ms"),
            "device_exec_ms_cur": c.get("device_exec_ms"),
        }
        if old <= 0.0:
            # nothing sustained last round: any measurement is progress
            report["ok"].append(cell)
            continue
        ratio = new / old
        cell["ratio"] = round(ratio, 3)
        if ratio < 1.0 - threshold:
            report["regressions"].append(cell)
        elif ratio > 1.0 + threshold:
            report["improved"].append(cell)
        else:
            report["ok"].append(cell)
    return report


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", choices=("grid", "gateway", "obs"),
                    default="grid",
                    help="artifact family: single-node serving grid, "
                         "the cluster gateway's per-replica scaling, "
                         "or the observability overhead microbench")
    ap.add_argument("--dir", default=".",
                    help="directory holding BENCH_*_r*.json rounds")
    ap.add_argument("--threshold", type=float, default=None,
                    help="relative regression gate (default 0.10; "
                         "0.50 for --kind obs, where the absolute "
                         "budget is the real contract)")
    ap.add_argument("--current", default=None,
                    help="explicit current artifact (else newest)")
    ap.add_argument("--previous", default=None,
                    help="explicit previous artifact (else second-newest)")
    args = ap.parse_args(argv)
    if args.threshold is None:
        args.threshold = 0.50 if args.kind == "obs" else 0.10

    def _load(path):
        with open(path) as f:
            return json.load(f)

    skipped_rounds: list[str] = []
    if args.current and args.previous:
        cur_path, prev_path = args.current, args.previous
        try:
            cur, prev = _load(cur_path), _load(prev_path)
        except (OSError, json.JSONDecodeError) as e:
            print(json.dumps({"error": f"unreadable artifact: {e}"}))
            return 2
    else:
        finders = {"gateway": find_gateway_artifacts,
                   "obs": find_obs_artifacts,
                   "grid": find_grid_artifacts}
        arts = finders[args.kind](args.dir)
        if args.current:
            cur_path = args.current
            arts = [a for a in arts
                    if os.path.abspath(a) != os.path.abspath(cur_path)]
        elif arts:
            cur_path = arts.pop()
        else:
            kind = {"gateway": "GATEWAY", "obs": "OBS_OVERHEAD",
                    "grid": "GRID"}[args.kind]
            print(json.dumps({"error": f"no BENCH_{kind}_*.json found"}))
            return 2
        try:
            cur = _load(cur_path)
        except (OSError, json.JSONDecodeError) as e:
            print(json.dumps({"error": f"unreadable artifact: {e}"}))
            return 2
        if args.previous:
            prev_path = args.previous
            try:
                prev = _load(prev_path)
            except (OSError, json.JSONDecodeError) as e:
                print(json.dumps({"error": f"unreadable artifact: {e}"}))
                return 2
        else:
            # walk back to the NEWEST artifact on the same backend: a
            # CPU smoke round committed between two TPU rounds must not
            # un-gate the TPU sequence (the TPU r07 compares against
            # TPU r05, skipping the cpu r06 in between)
            prev_path = prev = None
            for cand in reversed(arts):
                try:
                    doc = _load(cand)
                except (OSError, json.JSONDecodeError):
                    skipped_rounds.append(os.path.basename(cand))
                    continue
                if backends_comparable(doc.get("backend"),
                                       cur.get("backend")):
                    prev_path, prev = cand, doc
                    break
                skipped_rounds.append(os.path.basename(cand))
            if prev is None:
                if args.kind == "obs":
                    # no relative comparison possible, but the HARD
                    # absolute budget is unconditional — a first round
                    # (or first round on a new backend) is exactly
                    # where a budget break is most likely
                    report = compare_obs(
                        {"backend": cur.get("backend"),
                         "microbench_ns_per_request": {}},
                        cur, threshold=args.threshold)
                    report["skipped"] = ("no prior obs round on "
                                        f"backend {cur.get('backend')!r}"
                                        " — absolute budget only")
                    report["skipped_rounds"] = skipped_rounds
                    report["current"] = os.path.basename(cur_path)
                    print(json.dumps(report, indent=1))
                    return 1 if report["regressions"] else 0
                print(json.dumps({
                    "skipped": f"no prior {args.kind} round on backend "
                               f"{cur.get('backend')!r}",
                    "skipped_rounds": skipped_rounds,
                    "current": os.path.basename(cur_path)}))
                return 0
    compare = compare_obs if args.kind == "obs" else compare_grids
    report = compare(prev, cur, threshold=args.threshold)
    report["previous"] = os.path.basename(prev_path)
    report["current"] = os.path.basename(cur_path)
    report["threshold"] = args.threshold
    if skipped_rounds:
        # rounds between current and the chosen base that were not
        # comparable (other backend / unreadable) — visible so a gap in
        # the gated sequence is never silent
        report["skipped_rounds"] = skipped_rounds
    print(json.dumps(report, indent=1))
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
