"""Share, in %, of its roofline that the program matching ``module``
reaches by itself: the least time the chip could take for one scan
window of the modal width (``benchmark/costs.py``, as
``trace_roofline.py`` has it) over that program's own device time per
execution (``trace_module_ms.py``) — so a slower or rarer fallback
cannot move it."""

from benchmark import costs
from benchmark.readers.trace_module_ms import read as module_ms
from benchmark.readers.trace_roofline import modal_window


def read(obs, params):
    batch = modal_window(obs.batch_sizes)
    own_ms = module_ms(obs, params)
    if own_ms is None or batch is None or obs.peaks is None:
        return None
    n_bytes, flops = costs.scan_window(
        obs.store["rows"], obs.store["device_features"],
        obs.store["itemsize"], batch)
    least_s, _bound = costs.least_time_s(n_bytes, flops, obs.peaks)
    return 100.0 * least_s * 1e3 / own_ms
