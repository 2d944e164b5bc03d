"""Tokens of all steps completed in the window over the window's seconds.
The window opens on the completion of the last warm-up step and closes on
the last completion before `--seconds` was up, both seen on the host after
the step's loss had arrived from the device."""


def read(obs):
    return obs["window_steps"] * obs["tokens_per_step"] / obs["window_s"]
