"""Model: of the (token, expert) rows a step's routers make, the share the
grouped matmuls' buffers hold rows for (timeline counters
`moe.rows_buffered` over `moe.rows_routed`, counted once a routed layer as
the step is traced): 100 is a buffer of every routed row, held / experts
(12.5 at 16 of 128) one sized to the expected load."""

from benchmark.harness import timeline


def value(tl):
    routed = tl.counters.get("moe.rows_routed")
    if not routed:
        return None
    return 100.0 * tl.counters.get("moe.rows_buffered", 0) / routed


def read(obs):
    return timeline.read(obs, value)
