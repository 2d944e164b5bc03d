"""Model: device time under the scope `exit_gate` (a looped model's gate
after every walk, the exit distribution, its entropy and the weighting of
the rows' losses by it; forward and backward) over device busy time, from
the run's trace (`harness/scope_trace.py`).  None for a family that walks
its layers once, and for a program whose vocabulary has no such scope."""

from benchmark.harness import scope_trace


def read(obs):
    scopes, _ = scope_trace.vocabulary()
    if not hasattr(obs["family"], "n_walk") or "exit_gate" not in (
            scopes or ()):
        return None
    return scope_trace.share(obs, "exit_gate")
