"""Model: device time under the scope `attention/indexer/loss` alone (the
indexer's KL loss: its target, every head's QK' of a block of queries
against every key, exponentiated and summed over the heads, and the
gradient to the index scores; forward and replayed) over device busy time,
from the run's trace (`harness/scope_trace.py`).  The largest single part
of the keye step, and what a fused kernel for the target takes away.  None
for a family whose attention selects nothing, and for a program that states
no such scope."""

from benchmark.harness import scope_trace


def read(obs):
    if not hasattr(obs["family"], "index_scores_cost"):
        return None
    return scope_trace.share(obs, "attention/indexer/loss")
