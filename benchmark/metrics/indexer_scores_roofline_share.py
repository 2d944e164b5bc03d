"""Kernels: the least time one chip could take for a step's index scores
(the family's `index_scores_cost`, whatever implements them: the larger of
operations over the bf16 peak and bytes over the HBM peak; recomputation
not counted) over the device time under the scope
`attention/indexer/scores`, from the run's trace
(`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    if not hasattr(obs["family"], "index_scores_cost") \
            or not obs.get("trace") or not obs["peaks"]:
        return None
    found = scope_trace.of(obs)
    scores_s = found and (found["scopes"] or {}).get(
        "attention/indexer/scores")
    if not scores_s:
        return None
    return 100.0 * least_seconds(obs)[0] * obs["trace"]["steps"] / scores_s


def least_seconds(obs):
    """(seconds per step per chip, which peak bounds it)."""
    cost = obs["family"].index_scores_cost(obs["traffic"]["batch"],
                                           obs["traffic"]["seq"])
    compute = cost["flops"] / obs["chips"] / obs["peaks"]["bf16_flops_per_s"]
    memory = cost["bytes"] / obs["chips"] / obs["peaks"]["hbm_bytes_per_s"]
    return max((compute, "compute"), (memory, "memory"))
