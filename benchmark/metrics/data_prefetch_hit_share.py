"""Data: of the blocks the consumer asked for over the run, the share
that was local and sealed when asked (counters `data.blocks_ready` over
`data.blocks`): the prefetch had done its work."""

from benchmark.harness import timeline


def value(tl):
    blocks = tl.counters.get("data.blocks")
    if not blocks:
        return None
    return 100.0 * tl.counters.get("data.blocks_ready", 0) / blocks


def read(obs):
    return timeline.read(obs, value)
