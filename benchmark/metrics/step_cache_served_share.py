"""Model: whether the train step's executable was read from the compile
cache: 100 where the step's last compile before the loop's first report
was served (`train.setup`, `step_cache` `hit`), 0 where it was compiled
(`miss`, or `off` without a cache).  A checkout's first run reads 0 and
its later ones 100; a step with the run's seed among its constants reads 0
on every run."""

from benchmark.harness import registry, timeline


def value(tl):
    found = registry.metric("step_trace_s").setup(tl)
    if found is None or found.get("step_cache") is None:
        return None
    return 100.0 if found["step_cache"] == "hit" else 0.0


def read(obs):
    return timeline.read(obs, value)
