"""Model: device time of the latent-attention path outside the kernels (W_q,
W_kv_a, the latent norm, W_kv_b, the RoPE parts, assembling k, the
transposes to the kernels' layout, W_o, and their backward; told from the
rest by the family's `is_mla_op`) over device busy time, from the run's
trace."""

from benchmark.harness import mla_trace


def read(obs):
    found = mla_trace.of(obs)
    return None if found is None else \
        100.0 * found["mla_s"] / found["busy_s"]
