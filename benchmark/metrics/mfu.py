"""Model: tokens of a step over the window's median step interval, times
the operations a token's forward and backward passes need (the family's
count, recomputation not counted), over chips times the chip's bf16 peak
from `benchmark/harness/peaks.json`.  The median interval and not the
window's rate: this is read in the traced run, whose window also holds the
profiler's start and stop."""

import numpy as np


def read(obs):
    if not obs["peaks"] or not obs["step_intervals_s"]:
        return None
    rate = obs["tokens_per_step"] / float(np.median(obs["step_intervals_s"]))
    flops = obs["family"].flops_per_token(obs["traffic"]["seq"])
    return 100.0 * rate * flops / (
        obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
