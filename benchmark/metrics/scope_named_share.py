"""Model: device time of the operations whose `tf_op` lies under a scope of
the program's vocabulary (`ray_tpu/models/layers.py:SCOPES`) over device busy
time, from the run's trace (`harness/scope_trace.py`): how much of the step
the program's own names account for."""

from benchmark.harness import scope_trace


def read(obs):
    found = scope_trace.of(obs)
    if found is None or found["named_s"] is None:
        return None
    return 100.0 * found["named_s"] / found["busy_s"]
