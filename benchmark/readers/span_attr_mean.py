"""Mean of the attribute ``attr`` over the spans named ``span`` that
carry it.  A program whose spans lack the attribute reads as nothing,
not as zero."""


def read(obs, params):
    values = [s["attrs"][params["attr"]] for s in obs.spans
              if s["name"] == params["span"]
              and params["attr"] in (s.get("attrs") or {})]
    if not values:
        return None
    return sum(values) / len(values)
