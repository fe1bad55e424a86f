"""Median, in ms, over the window's micro-batches, of the time from the
arrival of a micro-batch's oldest input record to the end of the device
sync that made the last of its item rows servable.

Two spans are joined: ``batch_span`` (one per micro-batch: ``batch`` its
number, ``oldest_wait_ms`` how long its oldest record had waited when it
began) and ``sync_span`` (one per in-place sync of the item store:
``batches`` the numbers of the micro-batches whose rows it carried).  A
micro-batch whose last sync is not among the spans (it fell after the
window, or its drain was not sampled) is left out."""

from benchmark.stats import median


def read(obs, params):
    servable: dict = {}
    for s in obs.spans:
        if s["name"] != params["sync_span"]:
            continue
        end = s["start_ms"] + s["duration_ms"]
        for batch in s.get("attrs", {}).get("batches") or ():
            servable[str(batch)] = max(servable.get(str(batch), end), end)
    waits = []
    for s in obs.spans:
        attrs = s.get("attrs", {})
        if s["name"] != params["batch_span"] \
                or attrs.get("oldest_wait_ms") is None:
            continue
        end = servable.get(str(attrs.get("batch")))
        if end is not None:
            waits.append(end - (s["start_ms"] - attrs["oldest_wait_ms"]))
    return median(waits)
