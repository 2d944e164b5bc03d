"""Model: device time in the routed experts' operations that are not the
grouped matmuls (the router, the sorts, the gathers of rows to and from
expert order, the gate's activation, the weighted sum) over device busy
time, from the run's trace."""

from benchmark.harness import moe_trace


def read(obs):
    found = moe_trace.of(obs)
    return None if found is None else \
        100.0 * (found["moe_s"] - found["moe_matmul_s"]) / found["busy_s"]
