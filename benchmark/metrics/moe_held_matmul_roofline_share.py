"""Kernels: the least time one chip could take for the grouped matmuls over
the rows its held experts are EXPECTED to be sent (the family's `moe_cost`
at top_k x held / experts rows a token; the larger of operations over the
bf16 peak and bytes over the HBM peak) over the time the grouped matmuls
took on it, from the run's trace: `moe_matmul_roofline_share`'s reading,
reported only by a family that holds a share of its experts.  Rows
buffered beyond the expected load are work nobody asked for and lower the
share."""

from benchmark.harness import registry


def read(obs):
    if not hasattr(obs["family"], "expected_rows_per_token"):
        return None
    return registry.metric("moe_matmul_roofline_share").read(obs)
