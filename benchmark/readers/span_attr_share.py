"""Share, in %, of the spans named ``span`` that carry the attribute
``attr`` for which it ``equals`` a value, or is ``at_least`` one.  Spans
that lack the attribute, or carry it as null, are not counted; a program
whose spans never carry it reads as nothing, not as zero."""


def read(obs, params):
    values = [s["attrs"][params["attr"]] for s in obs.spans
              if s["name"] == params["span"]
              and (s.get("attrs") or {}).get(params["attr"]) is not None]
    if not values:
        return None
    if "equals" in params:
        hits = sum(v == params["equals"] for v in values)
    else:
        hits = sum(v >= params["at_least"] for v in values)
    return 100.0 * hits / len(values)
