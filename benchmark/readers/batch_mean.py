"""Mean size of the drains the batcher dispatched in the window."""


def read(obs, params):
    if not obs.batch_sizes:
        return None
    return sum(obs.batch_sizes) / len(obs.batch_sizes)
