"""Model: device time of everything under the scope `head_and_loss` (the
head's matmul and the loss, forward, recomputed and backward) over device
busy time, from the run's trace (`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    return scope_trace.share(obs, "head_and_loss")
