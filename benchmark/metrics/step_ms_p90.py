"""90th percentile of the time between successive step completions in the
window, all of them: the tail a job's user sees as a stalled step.  p90
because a window holds some 110 steps and a percentile needs ten samples
beyond it."""

import numpy as np


def read(obs):
    return 1e3 * float(np.percentile(obs["step_intervals_s"], 90))
