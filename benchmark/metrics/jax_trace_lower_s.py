"""Model: what the loop's process paid JAX's tracer and its lowering to
MLIR (Mosaic kernels included) before the window opened: the seconds
covered by `jax.trace` and `jax.lower` spans (nested ones counted once).
The reference's and the initialiser's share is in it."""

from benchmark.harness import timeline


def value(tl):
    spans = tl.set_up("jax.trace", "jax.lower")
    return timeline.covered_s(spans) if spans else None


def read(obs):
    return timeline.read(obs, value)
