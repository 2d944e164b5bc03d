"""Train: what a profiler session took from the loop: the sum of d - m over
the window's `train.step` records that are `profiled` (the session was on at
the step's start or end: the steps that held `start_trace` and `stop_trace`
and those between), m the median of all the window's steps.  The cost of
tracing when it is on; None in a run that traced nothing."""

from benchmark.harness import registry, timeline


def value(tl):
    found = registry.metric("step_stall_share").steps(tl)
    if found is None or not found[1]:
        return None
    _, profiled, m = found
    return sum(r["duration_us"] - m for r in profiled) / 1e3


def read(obs):
    return timeline.read(obs, value)
