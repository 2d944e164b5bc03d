"""Model: device time of everything under the scope `ffn/moe` (route,
dispatch, the grouped matmuls, combine and the shared experts, whatever rows
the buffer has) over device busy time, from the run's trace
(`harness/scope_trace.py`)."""

from benchmark.harness import scope_trace


def read(obs):
    return scope_trace.share(obs, "ffn/moe")
