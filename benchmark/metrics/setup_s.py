"""Process start to window open: `ray_tpu.init`, worker spawn and chip
claim, the reference check, parameter init on the device, trace + lower +
compile of the step (cache-served after a checkout's first run), warm-up."""


def read(obs):
    return obs["t_open"] - obs["t_start"]
