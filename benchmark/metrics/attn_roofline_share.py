"""Kernels: the least time one chip could take for its share of the
attention kernels' operations and bytes (the family's counts; the larger of
operations over the bf16 peak and bytes over the HBM peak) over the time
the kernels took on it, from the trace."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["kernel_s"] or not obs["peaks"]:
        return None
    return 100.0 * least_seconds(obs)[0] * trace["steps"] / trace["kernel_s"]


def least_seconds(obs):
    """(seconds per step per chip, which peak bounds it)."""
    cost = obs["family"].attention_cost(obs["traffic"]["batch"],
                                        obs["traffic"]["seq"])
    compute = cost["flops"] / obs["chips"] / obs["peaks"]["bf16_flops_per_s"]
    memory = cost["bytes"] / obs["chips"] / obs["peaks"]["hbm_bytes_per_s"]
    return max((compute, "compute"), (memory, "memory"))
