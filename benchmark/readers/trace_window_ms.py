"""Device time per scan window, in ms (``trace_reduce.device_ms_per_window``)."""

from benchmark.trace_reduce import device_ms_per_window


def read(obs, params):
    if obs.trace is None:
        return None
    return device_ms_per_window(obs.trace, params["window_module"])
