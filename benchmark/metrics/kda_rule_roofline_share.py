"""Kernels: the least time one chip could take for a step's delta rules (the
family's `kda_cost`, whatever implements them: the larger of operations over
the bf16 peak and bytes over the HBM peak; recomputation not counted) over the
device time under the scope `kda/rule`, from the run's trace
(`harness/scope_trace.py`).  The rule's decays, masks and sums are the vector
unit's work and `harness/peaks.json` states no peak for it: against the peaks
it does state, the share says how far the rule is from being a pass over its
bytes."""

from benchmark.harness import scope_trace

SCOPE = "kda/rule"


def read(obs):
    if not hasattr(obs["family"], "kda_cost") or not obs.get("trace") \
            or not obs["peaks"]:
        return None
    found = scope_trace.of(obs)
    rule_s = found and (found["scopes"] or {}).get(SCOPE)
    if not rule_s:
        return None
    return 100.0 * least_seconds(obs)[0] * obs["trace"]["steps"] / rule_s


def least_seconds(obs):
    """(seconds per step per chip, which peak bounds it)."""
    cost = obs["family"].kda_cost(obs["traffic"]["batch"],
                                  obs["traffic"]["seq"])
    compute = cost["flops"] / obs["chips"] / obs["peaks"]["bf16_flops_per_s"]
    memory = cost["bytes"] / obs["chips"] / obs["peaks"]["hbm_bytes_per_s"]
    return max((compute, "compute"), (memory, "memory"))
