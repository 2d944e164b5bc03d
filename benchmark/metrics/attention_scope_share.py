"""Model: device time of everything under the scope `attention`
(projections, glue, the kernels, and the backward of all of them) over
device busy time, from the run's trace (`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    return scope_trace.share(obs, "attention")
