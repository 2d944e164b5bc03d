"""Train: the worker's first look at its devices, where libtpu opens the
chips (`train.chip_claim`, the longest rank)."""

from benchmark.harness import timeline


def value(tl):
    claims = tl.named("train.chip_claim")
    return max(r["duration_us"] for r in claims) / 1e6 if claims else None


def read(obs):
    return timeline.read(obs, value)
