"""Model: device time of the forward pass's operations (a `jvp(` in the
`tf_op`, no `transpose(`, not recomputed) over device busy time, from the
run's trace (`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    return scope_trace.phase_share(obs, "fwd")
