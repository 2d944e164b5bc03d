"""Kernels: the least time one chip could take for a step's Mamba-1
recurrences (the family's `selective_scan_cost`, whatever implements them:
the larger of operations over the bf16 peak and bytes over the HBM peak;
recomputation not counted) over the device time under the scope
`mamba/scan`, from the run's trace (`harness/scope_trace.py`).  The
recurrence is the vector unit's work and `harness/peaks.json` states no peak
for it: against the peaks it does state, the share says how far the scan is
from being a pass over its bytes."""

from benchmark.harness import scope_trace

SCOPE = "mamba/scan"


def read(obs):
    if not hasattr(obs["family"], "selective_scan_cost") \
            or not obs.get("trace") or not obs["peaks"]:
        return None
    found = scope_trace.of(obs)
    scan_s = found and (found["scopes"] or {}).get(SCOPE)
    if not scan_s:
        return None
    return 100.0 * least_seconds(obs)[0] * obs["trace"]["steps"] / scan_s


def least_seconds(obs):
    """(seconds per step per chip, which peak bounds it)."""
    cost = obs["family"].selective_scan_cost(obs["traffic"]["batch"],
                                             obs["traffic"]["seq"])
    compute = cost["flops"] / obs["chips"] / obs["peaks"]["bf16_flops_per_s"]
    memory = cost["bytes"] / obs["chips"] / obs["peaks"]["hbm_bytes_per_s"]
    return max((compute, "compute"), (memory, "memory"))
