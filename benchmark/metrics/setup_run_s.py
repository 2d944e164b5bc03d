"""Train: of the loop's start to its first report, what was no trace,
lowering, compile or cache read (`train.setup`, `run_us`): the loop's
thread executing, waiting on the device or in Python: in this loop the
reference's steps, the initialiser's run and the first step."""

from benchmark.harness import registry, timeline


def value(tl):
    found = registry.metric("step_trace_s").setup(tl)
    if found is None or "run_us" not in found:
        return None
    return found["run_us"] / 1e6


def read(obs):
    return timeline.read(obs, value)
