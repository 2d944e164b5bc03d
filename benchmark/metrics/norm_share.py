"""Model: device time of everything under the scope `norm` (the layers'
norms on the residual stream and the final norm; a norm inside an operator
counts there) over device busy time, from the run's trace
(`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    return scope_trace.share(obs, "norm")
