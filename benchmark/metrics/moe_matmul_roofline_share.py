"""Kernels: the least time one chip could take for a step's grouped
matmuls (the family's `moe_cost`: the larger of operations over the bf16
peak and bytes over the HBM peak) over the time they took on it, from the
run's trace."""

from benchmark.harness import moe_trace


def read(obs):
    found = moe_trace.of(obs)
    if found is None or not found["moe_matmul_s"]:
        return None
    return 100.0 * least_seconds(obs)[0] * found["steps"] \
        / found["moe_matmul_s"]


def least_seconds(obs):
    """(seconds per step per chip, which peak bounds it)."""
    cost = obs["family"].moe_cost(obs["traffic"]["batch"],
                                  obs["traffic"]["seq"])
    compute = cost["flops"] / obs["chips"] / obs["peaks"]["bf16_flops_per_s"]
    memory = cost["bytes"] / obs["chips"] / obs["peaks"]["hbm_bytes_per_s"]
    return max((compute, "compute"), (memory, "memory"))
