"""Model: device time of everything under the scope `short_conv` (the whole
operator: W_in, the gates and taps, W_out, and their backward) over device
busy time, from the run's trace (`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    return scope_trace.share(obs, "short_conv")
