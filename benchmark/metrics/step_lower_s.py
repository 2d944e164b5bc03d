"""Model: what the lowering of the train step to MLIR took before the
loop's first report, Mosaic's of every kernel in it included and the
kernels' bodies traced meanwhile left to `step_trace_s` (`train.setup`,
`own_us["lower/step"]`)."""

from benchmark.harness import registry, timeline


def value(tl):
    return registry.metric("step_trace_s").own_s(tl, "lower/step")


def read(obs):
    return timeline.read(obs, value)
