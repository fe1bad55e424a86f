"""Median duration, in ms, of the spans of one name."""

from benchmark.stats import median


def read(obs, params):
    return median([s["duration_ms"] for s in obs.spans
                   if s["name"] == params["span"]])
