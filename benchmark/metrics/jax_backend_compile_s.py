"""Model: what the loop's process paid JAX's compiler and its compile
cache before the window opened: the seconds covered by
`jax.backend_compile` and `jax.cache_read` spans (a cache read lies inside
its compile span and is counted once)."""

from benchmark.harness import timeline


def value(tl):
    spans = tl.set_up("jax.backend_compile", "jax.cache_read")
    return timeline.covered_s(spans) if spans else None


def read(obs):
    return timeline.read(obs, value)
