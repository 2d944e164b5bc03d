"""Model: of the calls of a layer a looped model's step makes (T walks of n
layers), the share the program holds a body of its own for (timeline
counters `loop.layer_traces` over `loop.layer_calls`, counted by
`models/layers.py:trunk` as the step is traced): 100 where the walks are
unrolled, 100 / T where one walk's bodies serve them all.  What is traced,
lowered and compiled grows with it.  A program that counts neither (one
that walks once): nothing to read."""

from benchmark.harness import timeline


def value(tl):
    calls = tl.counters.get("loop.layer_calls")
    if not calls:
        return None
    return 100.0 * tl.counters.get("loop.layer_traces", 0) / calls


def read(obs):
    return timeline.read(obs, value)
