"""Train: CPU time of every thread of the loop's process (`process_cpu_us`
of the window's `train.step` records: the loop, the trace, metrics and
profile flushers, the raylet client, jax's own threads) over the steps'
wall time, as a share of one core; steps that held a profiler session left
out.  What the worker burns on the host while the device works."""

from benchmark.harness import registry, timeline


def value(tl):
    found = registry.metric("step_stall_share").steps(tl)
    if found is None or not found[0]:
        return None
    clean = found[0]
    return (100.0 * sum(r["attributes"]["process_cpu_us"] for r in clean)
            / sum(r["duration_us"] for r in clean))


def read(obs):
    return timeline.read(obs, value)
