"""Kernels: the least time one chip could take for a step's pooling (the
family's `eva_summary_cost`, whatever implements it: k and v read once and
the summaries written, the backward's likewise; the larger of operations over
the bf16 peak and bytes over the HBM peak, which is the bytes'; recomputation
not counted) over the device time under the scope `eva/summary`, from the
run's trace (`harness/scope_trace.py`).  The softmax over a chunk's positions
and the weighted sums are the vector unit's work and `harness/peaks.json`
states no peak for it: the share says how far the pooling is from being a
pass over its bytes."""

from benchmark.harness import scope_trace

SCOPES = ("eva/summary",)
COST = "eva_summary_cost"


def read(obs, scopes=SCOPES, cost=COST):
    if not hasattr(obs["family"], cost) or not obs.get("trace") \
            or not obs["peaks"]:
        return None
    found = scope_trace.of(obs)
    took = found and sum((found["scopes"] or {}).get(scope, 0.0)
                         for scope in scopes)
    if not took:
        return None
    return 100.0 * least_seconds(obs, cost)[0] * obs["trace"]["steps"] / took


def least_seconds(obs, cost=COST):
    """(seconds per step per chip, which peak bounds it)."""
    cost = getattr(obs["family"], cost)(obs["traffic"]["batch"],
                                        obs["traffic"]["seq"])
    compute = cost["flops"] / obs["chips"] / obs["peaks"]["bf16_flops_per_s"]
    memory = cost["bytes"] / obs["chips"] / obs["peaks"]["hbm_bytes_per_s"]
    return max((compute, "compute"), (memory, "memory"))
