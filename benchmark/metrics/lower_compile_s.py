"""Model: trace + lower + compile of the train step as this run paid it
(the compile is served from the checkout's cache after the first run; the
Python trace and Mosaic lowering are paid on every start)."""


def read(obs):
    return obs["lower_compile_s"]
