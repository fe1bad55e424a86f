"""Device time per execution, in ms, of the SLOWEST of the programs
whose name matches ``module``: over the compiled programs of the traced
slice's ``XLA Modules`` line (one a (window, k): the profiler names each
with its own id), the largest of seconds on the device over executions.
Where ``trace_module_ms.py`` is a mean over whatever mix of shapes the
slice happened to hold, this is the program of the widest fetch it
held (the two-phase scan at k = 256 in the two-caller cells), the one a
tail request rides.  Over several chips a program's executions and
seconds count on every chip, so the ratio is a chip's."""

import re


def read(obs, params):
    if obs.trace is None:
        return None
    rx = re.compile(params["module"])
    per_execution = [m["seconds"] / m["count"]
                     for name, m in obs.trace["modules"].items()
                     if rx.search(name) and m["count"]]
    if not per_execution:
        return None
    return 1e3 * max(per_execution)
