"""Train: what jax's tracer, lowering, compiler and cache took before the
loop's first report for every function that is not the train step
(`train.setup`, the four `own_us[".../other"]`): in this loop the float32
reference's step, the initialiser and some thirty small programs."""

from benchmark.harness import registry, timeline


def value(tl):
    return registry.metric("step_trace_s").own_s(
        tl, "trace/other", "lower/other", "compile/other", "cache_read/other")


def read(obs):
    return timeline.read(obs, value)
