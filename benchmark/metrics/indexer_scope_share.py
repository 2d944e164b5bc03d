"""Model: device time of everything under the scope `attention/indexer`
(attention that selects its keys: the indexer's projections, the index
scores of every causal pair, the selection of each query's keys and the
indexer's loss; forward, replay and backward) over device busy time, from
the run's trace (`harness/scope_trace.py`).  None for a family whose
attention selects nothing, and for a program that states no such scope."""

from benchmark.harness import scope_trace


def read(obs):
    if not hasattr(obs["family"], "index_scores_cost"):
        return None
    return scope_trace.share(obs, "attention/indexer")
