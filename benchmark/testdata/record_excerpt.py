"""By hand: cut the recorded trace the reduction's test reads from a
traced run's raw profile.

    python3 benchmark/run.py --workload <cell> --seed 1 --seconds 40 \
        --trace 1 --trace-dir chiprun_out/profile
    python3 benchmark/testdata/record_excerpt.py chiprun_out/profile \
        benchmark/testdata/<name>.json.gz [seconds]

Keeps the events that begin in the first ``seconds`` (0.25) after the
first device operation, as gzipped JSON in the form
``trace_reduce.load_excerpt`` reads.
"""

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce  # noqa: E402


def excerpt(trace: dict, seconds: float) -> dict:
    first = min((e[1] for p in trace_reduce._device_planes(trace)
                 for ln in p["lines"] for e in ln["events"]), default=0)
    last = first + int(seconds * 1e9)
    planes = []
    for p in trace["planes"]:
        lines = [{"name": ln["name"],
                  "events": [e for e in ln["events"]
                             if first <= e[1] < last]}
                 for ln in p["lines"]]
        lines = [ln for ln in lines if ln["events"]]
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def main(argv) -> int:
    if len(argv) not in (2, 3):
        print(__doc__, file=sys.stderr)
        return 2
    path = trace_reduce.find_xplane(argv[0])
    if path is None:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    seconds = float(argv[2]) if len(argv) == 3 else 0.25
    with gzip.open(argv[1], "wt", encoding="utf-8") as f:
        json.dump(excerpt(trace_reduce.read_xplane(path), seconds), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
