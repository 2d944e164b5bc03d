"""Model: device time in the routed experts' operations (route, dispatch,
the grouped matmuls, combine; told from the rest by the family's
`is_moe_op`) over device busy time, from the run's trace."""

from benchmark.harness import moe_trace


def read(obs):
    found = moe_trace.of(obs)
    return None if found is None else \
        100.0 * found["moe_s"] / found["busy_s"]
