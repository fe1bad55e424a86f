"""Share, in %, of the traced slice in which no operation ran on the
device."""


def read(obs, params):
    if obs.trace is None:
        return None
    return 100.0 * obs.trace["idle_share"]
