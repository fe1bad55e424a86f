"""Share, in %, of its roofline that a scan window reaches: the least
time the chip could take for one window of the modal width (the larger
of bytes over peak bandwidth and operations over peak rate, from
``benchmark/costs.py``) over the device time per window."""

import collections

from benchmark import costs
from benchmark.trace_reduce import device_ms_per_window


def modal_window(batch_sizes) -> int | None:
    widths = collections.Counter(
        w for n in batch_sizes for w in costs.window_sizes(n))
    return widths.most_common(1)[0][0] if widths else None


def read(obs, params):
    batch = modal_window(obs.batch_sizes)
    if obs.trace is None or batch is None or obs.peaks is None:
        return None
    window_ms = device_ms_per_window(obs.trace, params["window_module"])
    if window_ms is None:
        return None
    n_bytes, flops = costs.scan_window(
        obs.store["rows"], obs.store["device_features"],
        obs.store["itemsize"], batch)
    least_s, _bound = costs.least_time_s(n_bytes, flops, obs.peaks)
    return 100.0 * least_s * 1e3 / window_ms
