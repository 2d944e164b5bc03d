"""Raylet: the raylet's `Popen` of the worker that ran the loop to that
worker's registration (`raylet.worker_spawn`, joined by pid): process
start, imports (jax among them, for a worker granted chips), socket."""

from benchmark.harness import timeline


def value(tl):
    pid = tl.loop_pid()
    spawns = [r for r in tl.named("raylet.worker_spawn")
              if r["attributes"].get("pid") == pid]
    return spawns[0]["duration_us"] / 1e6 if spawns else None


def read(obs):
    return timeline.read(obs, value)
