"""Model: what the train step's executable took before the loop's first
report, compiled or read from the compile cache (`train.setup`,
`own_us["compile/step"]` + `own_us["cache_read/step"]`: a cache read lies
inside its compile and is the compile's own time no more);
`step_cache_served_share` says which it was."""

from benchmark.harness import registry, timeline


def value(tl):
    return registry.metric("step_trace_s").own_s(
        tl, "compile/step", "cache_read/step")


def read(obs):
    return timeline.read(obs, value)
