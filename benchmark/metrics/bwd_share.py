"""Model: device time of the backward pass's operations (a `transpose(` in
the `tf_op`, not recomputed) over device busy time, from the run's trace
(`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    return scope_trace.phase_share(obs, "bwd")
