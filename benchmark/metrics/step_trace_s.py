"""Model: what jax's tracer took for the train step before the loop's first
report, as the program booked it (`train.setup`, `own_us["trace/step"]`:
`ray_tpu/train/backend.py`): the own time of every trace whose outermost
function is `layers.train_step`'s, the kernels' bodies traced while it is
lowered among them, and nothing of the reference's or the initialiser's."""

from benchmark.harness import timeline


def setup(tl):
    """The attributes of the `train.setup` record that the loop's process
    wrote before the window opened, or None (a program from before it)."""
    found = tl.set_up("train.setup")
    return found[-1]["attributes"] if found else None


def own_s(tl, *keys):
    """Seconds under these keys of `own_us`, or None."""
    found = setup(tl)
    if found is None or "own_us" not in found:
        return None
    return sum(found["own_us"][key] for key in keys) / 1e6


def value(tl):
    return own_s(tl, "trace/step")


def read(obs):
    return timeline.read(obs, value)
