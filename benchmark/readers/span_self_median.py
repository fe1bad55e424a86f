"""Median self time, in ms, of a span: its duration minus the part its
named child spans cover.  ``route`` keeps the request spans of one
route."""

from benchmark.stats import median


def read(obs, params):
    children = set(params["children"])
    covered: dict = {}
    for s in obs.spans:
        if s["name"] in children and s.get("parent_id"):
            covered[s["parent_id"]] = covered.get(s["parent_id"], 0.0) \
                + s["duration_ms"]
    route = params.get("route")
    return median([
        s["duration_ms"] - covered.get(s["span_id"], 0.0)
        for s in obs.spans if s["name"] == params["span"]
        and (route is None or s.get("attrs", {}).get("route") == route)])
