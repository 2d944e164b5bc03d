"""Kernels: the least time one chip could take for the conv operators'
operations that a trace names by shape (the family's `shortconv_cost`:
W_in's forward product and its weight gradient; the larger of operations
over the bf16 peak and bytes over the HBM peak; recomputation not counted)
over the device time `shortconv_share` reads for the same operations (with
the recomputed forward and AdamW's update of W_in, which XLA fuses behind
its gradient), from the run's trace."""

from benchmark.harness import shortconv_trace


def read(obs):
    found = shortconv_trace.of(obs)
    if found is None:
        return None
    return 100.0 * least_seconds(obs)[0] * found["steps"] \
        / found["shortconv_s"]


def least_seconds(obs):
    """(seconds per step per chip, which peak bounds it)."""
    cost = obs["family"].shortconv_cost(obs["traffic"]["batch"],
                                        obs["traffic"]["seq"])
    compute = cost["flops"] / obs["chips"] / obs["peaks"]["bf16_flops_per_s"]
    memory = cost["bytes"] / obs["chips"] / obs["peaks"]["hbm_bytes_per_s"]
    return max((compute, "compute"), (memory, "memory"))
