"""Device time per execution, in ms, of the programs whose name matches
``module``: their seconds on the device's ``XLA Modules`` line over how
often they ran in the traced slice (``trace_reduce.module_executions``).
Unlike ``trace_window_ms.py`` this is one program's own time: the
two-phase scan without the fallbacks other windows needed, or the exact
scan alone.  It is a mean over every execution that matched: where the
program ran at several shapes (the exact scan at k = 32 .. 256) it moves
with the mix of shapes the slice happened to hold."""

from benchmark.trace_reduce import module_executions


def read(obs, params):
    if obs.trace is None:
        return None
    count, seconds = module_executions(obs.trace, params["module"])
    if not count:
        return None
    return 1e3 * seconds / count
