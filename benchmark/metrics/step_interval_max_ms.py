"""Train: the longest step of the window as the program records it
(`train.step`), steps that held a profiler session left out: the one
outlier that `step_ms_p90` cannot see (ten of 104 intervals lie beyond
that percentile) and a median hides."""

from benchmark.harness import registry, timeline


def value(tl):
    found = registry.metric("step_stall_share").steps(tl)
    if found is None or not found[0]:
        return None
    return max(r["duration_us"] for r in found[0]) / 1e3


def read(obs):
    return timeline.read(obs, value)
