"""Raylet: `fit()` called in the parent to `train_loop` entered in the
worker that holds the chips (both `time.time()` on one host)."""


def read(obs):
    return obs["t_enter"] - obs["t_fit"]
