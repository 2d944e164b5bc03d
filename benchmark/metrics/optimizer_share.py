"""Model: device time of the optimizer's update and the routing biases' rule
(`optimizer_update`, `routing_bias_update`; a floor: a fusion has one
`tf_op`, and an update fused behind a weight gradient counts as that
gradient) over device busy time, from the run's trace
(`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    return scope_trace.phase_share(obs, "optimizer")
