"""Model: device time under the scope `attention/indexer/select` alone (the
exact top-k of every query's row of index scores as a mask: on a TPU the
sort, or what stands in for it) over device busy time, from the run's trace
(`harness/scope_trace.py`).  None for a family whose attention selects
nothing, and for a program that states no such scope."""

from benchmark.harness import scope_trace


def read(obs):
    if not hasattr(obs["family"], "index_scores_cost"):
        return None
    return scope_trace.share(obs, "attention/indexer/select")
