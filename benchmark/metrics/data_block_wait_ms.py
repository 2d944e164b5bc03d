"""Data: time the consumer was blocked in `data.block_wait` inside the
window over the blocks it consumed there: the object store's read when
the prefetch had the block ready, a read task's remainder when not."""

from benchmark.harness import timeline


def value(tl):
    waits = tl.in_window("data.block_wait")
    if not waits:
        return None
    return sum(r["duration_us"] for r in waits) / 1e3 / len(waits)


def read(obs):
    return timeline.read(obs, value)
