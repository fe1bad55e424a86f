"""Of the slowest requests, the share, in %, whose child span ``child``
carries ``attr`` equal to ``equals``.  The slowest are the spans named
``span`` (of one ``route``, where given) at and beyond their nearest-rank
``percentile``, counted as the load generator counts it: of 7,040
requests the 71 from the p99 one on, of 100 the last two.  A tail in
which no request has such a child with the attribute reads as nothing."""

from benchmark.stats import samples_beyond


def read(obs, params):
    route = params.get("route")
    roots = sorted(
        (s for s in obs.spans if s["name"] == params["span"]
         and (route is None or (s.get("attrs") or {}).get("route") == route)),
        key=lambda s: s["duration_ms"])
    if not roots:
        return None
    tail = {s["span_id"] for s in roots[
        -(samples_beyond(len(roots), params["percentile"]) + 1):]}
    values = [s["attrs"][params["attr"]] for s in obs.spans
              if s["name"] == params["child"] and s.get("parent_id") in tail
              and params["attr"] in (s.get("attrs") or {})]
    if not values:
        return None
    return 100.0 * sum(v == params["equals"] for v in values) / len(values)
