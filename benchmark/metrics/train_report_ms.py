"""Train: median of the program's own `train.report` span over the
window: put to consumed, the time the loop is blocked on the trainer."""

import numpy as np

from benchmark.harness import timeline


def value(tl):
    spans = tl.in_window("train.report")
    if not spans:
        return None
    return float(np.median([r["duration_us"] for r in spans])) / 1e3


def read(obs):
    return timeline.read(obs, value)
