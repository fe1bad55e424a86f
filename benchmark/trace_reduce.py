"""From a profiler trace to device metrics: busy and idle time, time per
kernel module, the operations that took most time and what the host was
doing in the longest idle gaps.

Two steps, so that the arithmetic can be checked on a small recorded
trace without a chip (``benchmark/tests/test_trace_reduce.py`` on
``benchmark/testdata/``):

1. ``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
   into plain data: ``{"planes": [{"name", "lines": [{"name", "events":
   [[name, start_ns, duration_ns], ...]}]}]}``;
2. ``reduce_trace`` turns that into numbers.

A device plane is one whose name starts with ``/device:``; the host's
threads are the lines of ``/host:CPU``.  On the device plane the line
``XLA Ops`` holds one event per executed operation (control flow as an
enclosing event, so self time is the event minus what it encloses) and
``XLA Modules`` one event per executed program, named after the jitted
function.  Busy time is the union of the operation intervals; idle share
is one minus busy over the traced span.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_GAPS_LABELLED = 200
_TOP = 10


def find_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str) -> dict:
    """The trace as plain data (needs nothing but JAX)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_excerpt(path: str) -> dict:
    import gzip
    import json

    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


def _device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")
            and any(ln["name"] in (OPS_LINE, MODULES_LINE)
                    for ln in p["lines"])]


def _line(plane: dict, name: str) -> list | None:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return None


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_op_name(text: str) -> str:
    """``custom-call.9 TopK (f32[8,128], s32[8,128])`` from the HLO line
    the trace names an operation by: its name, what it is (the custom
    call's target, else the opcode) and its result without layouts.
    Operations of different programs that share all three are one row."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text[:120]
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):
        shape, _, after = rest.partition(") ")
        shape += ")"
    else:
        shape, _, after = rest.partition(" ")
    target = _TARGET.search(text)
    kind = target.group(1) if target else after.partition("(")[0]
    return f"{head.lstrip('%')} {kind} {shape}"[:120]


def _self_times(events: list) -> dict[str, float]:
    """Seconds per operation name, an enclosing event (a loop, a call)
    counted without what it encloses."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + self_ns / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return out


def _host_events(trace: dict):
    names, starts, ends = [], [], []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for ln in plane["lines"]:
            thread = re.sub(r"/-?\d+$", "", ln["name"])
            for name, start, dur in ln["events"]:
                if dur > 0:
                    names.append(f"{thread}: {name}")
                    starts.append(start)
                    ends.append(start + dur)
    return names, np.asarray(starts, np.int64), np.asarray(ends, np.int64)


def _label_gaps(gaps: list[tuple[int, int]], trace: dict) -> list[list]:
    """Idle seconds by what the host was doing: each of the longest gaps
    takes the name of the host event that covers most of it, the
    innermost where several cover it all."""
    names, starts, ends = _host_events(trace)
    by_label: dict[str, float] = {}
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:_GAPS_LABELLED]
    for g0, g1 in longest:
        label = "(no host event)"
        if len(names):
            overlap = np.minimum(ends, g1) - np.maximum(starts, g0)
            best = int(overlap.max())
            if best > 0:
                ties = np.flatnonzero(overlap == best)
                label = names[int(ties[np.argmin(
                    (ends - starts)[ties])])]
        by_label[label] = by_label.get(label, 0.0) + (g1 - g0) / 1e9
    rest = sum(g1 - g0 for g0, g1 in gaps) / 1e9 - sum(by_label.values())
    ranked = sorted(by_label.items(), key=lambda kv: -kv[1])
    out = [[k, v] for k, v in ranked[:_TOP - 1] if v >= 1e-6]
    rest += sum(v for _, v in ranked[len(out):])
    if rest >= 1e-6:
        out.append(["(shorter gaps and other host work)", rest])
    return out


def reduce_trace(trace: dict) -> dict | None:
    """The device numbers of one traced span, or None where no operation
    ran on a device (a CPU rehearsal, a trace that caught nothing).

    ``busy_s`` is averaged over the device planes; ``window_s`` is the
    traced span, from the first to the last event of any plane."""
    planes = _device_planes(trace)
    if not planes:
        return None
    lo = min(e[1] for p in trace["planes"] for ln in p["lines"]
             for e in ln["events"])
    hi = max(e[1] + e[2] for p in trace["planes"] for ln in p["lines"]
             for e in ln["events"])
    busy, ops, modules = [], {}, {}
    gaps: list[tuple[int, int]] = []
    for n, plane in enumerate(planes):
        op_events = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        merged = _union([(s, s + d) for _, s, d in op_events if d > 0])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, secs in _self_times(op_events).items():
            name = short_op_name(name)
            ops[name] = ops.get(name, 0.0) + secs
        for name, _, dur in _line(plane, MODULES_LINE) or []:
            m = modules.setdefault(name, [0, 0.0])
            m[0] += 1
            m[1] += dur / 1e9
        if n == 0:  # gaps are labelled on the first device
            edges = [lo] + [x for se in merged for x in se] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy)
    if busy_s <= 0 or window_s <= 0:
        return None
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:_TOP]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": len(planes),
        "modules": {k: {"count": v[0], "seconds": v[1]}
                    for k, v in modules.items()},
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": _label_gaps(gaps, trace),
    }


def device_ms_per_window(reduced: dict, window_module: str) -> float | None:
    """Device time per scan window, in ms: all the time in which an
    operation ran on the device in the traced slice, over the number of
    windows dispatched in it — the executions of the programs whose name
    matches ``window_module`` (one two-phase program per window; the
    exact scan of a fallback is device time of the window that needed
    it)."""
    windows, _ = module_executions(reduced, window_module)
    if not windows:
        return None
    return 1e3 * reduced["busy_s"] * reduced["devices"] / windows


def module_executions(reduced: dict, pattern: str) -> tuple[int, float]:
    """(how often, for how many seconds) programs whose name matches
    ``pattern`` ran on the device."""
    rx = re.compile(pattern)
    hits = [m for name, m in reduced["modules"].items() if rx.search(name)]
    return (sum(m["count"] for m in hits),
            sum(m["seconds"] for m in hits))
