"""Model: device time of one chip's share of a mixture layer (route,
dispatch, the grouped matmuls over the experts held, combine, and the
shared experts; told from the rest by the family's `is_moe_op`) over device
busy time, from the run's trace: `moe_share`'s reading, reported only by a
family that holds a share of its experts."""

from benchmark.harness import registry


def read(obs):
    if not hasattr(obs["family"], "expected_rows_per_token"):
        return None
    return registry.metric("moe_share").read(obs)
