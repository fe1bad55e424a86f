"""How far the program counter ``counter`` moved over the window, in % of
the rows the batcher dispatched in it (``shard.fallback_share``: the
(query row, shard) pairs whose certificate failed, each answered by that
shard's exact scan; a row that failed on every one of four shards reads
400%).  A program without the counter reads as nothing."""


def read(obs, params):
    rows = sum(obs.batch_sizes)
    moved = obs.delta(params["counter"])
    if not rows or moved is None:
        return None
    return 100.0 * moved / rows
