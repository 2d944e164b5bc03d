"""Data: median host time per step of the window waiting in the shard
iterator's `next` plus the `device_put` (the loop's `data_wait` span)."""

import numpy as np


def read(obs):
    spans = obs["spans"].get("data_wait")
    return 1e3 * float(np.median(spans)) if spans else None
