"""Share, in %, of the rows dispatched in the window that the two-phase
certificate failed and the exact scan recomputed: the change of
``twophase_fallbacks`` over the rows the batcher dispatched."""


def read(obs, params):
    rows = sum(obs.batch_sizes)
    fallbacks = obs.delta("twophase_fallbacks")
    if not rows or fallbacks is None:
        return None
    return 100.0 * fallbacks / rows
