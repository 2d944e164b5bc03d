"""Model: device time of everything under the scope `ffn` (the dense feed-
forward and the mixture of experts, forward, recomputed and backward) over
device busy time, from the run's trace (`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    return scope_trace.share(obs, "ffn")
