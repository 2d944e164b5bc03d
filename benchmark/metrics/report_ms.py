"""Train: median host time inside `train.report` per step of the window
(the loop's `report` span: the round trip to the trainer in the parent)."""

import numpy as np


def read(obs):
    spans = obs["spans"].get("report")
    return 1e3 * float(np.median(spans)) if spans else None
